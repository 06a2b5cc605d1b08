"""Process start-up: which backend the process runs on, and where its
compiled programs are cached.

The installed JAX honours ``JAX_PLATFORMS`` and
``JAX_COMPILATION_CACHE_DIR`` itself, so both helpers here only add
what the environment cannot say: :func:`force_backend` selects a
platform from code (the test conftest's virtual CPU mesh, the
``GLT_PLATFORM``/``GLT_BENCH_PLATFORM`` conventions of the examples and
benches) and turns a too-late selection into an error instead of a
silently different device; :func:`configure_compile_cache` gives every
entry point the same persistent cache directory. Both must run before
the first backend contact, so every entry point (``chip_smoke.py``,
``__graft_entry__``, the benchmarks, the test conftest,
``examples/common.py``) calls them first.
"""
from __future__ import annotations

import os
from typing import Optional

from .env import raw as raw_env

_ENV_VARS = ('GLT_BENCH_PLATFORM', 'GLT_PLATFORM')

#: the checkout this package was imported from; its ``.jax_cache`` is
#: the compile cache when the environment names no other
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def force_backend(platform: Optional[str] = None,
                  host_devices: Optional[int] = None) -> Optional[str]:
  """Select the jax platform safely; call before any other jax use.

  Args:
    platform: 'cpu' / 'tpu' / None. None consults GLT_BENCH_PLATFORM
      then GLT_PLATFORM (the bench/example conventions) and leaves the
      default backend alone when neither is set.
    host_devices: if given, ensure XLA_FLAGS carries
      ``--xla_force_host_platform_device_count=<n>`` (the virtual-mesh
      testing setup) — also only effective before backend init.

  Returns the platform applied (or None if untouched).

  Raises RuntimeError when a DIFFERENT backend was already initialized:
  the selection would be ignored, and the caller would carry on on a
  device it did not ask for.
  """
  if platform is None:
    for var in _ENV_VARS:
      if raw_env(var):
        platform = raw_env(var)
        break
  if host_devices is not None:
    flags = raw_env('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
      os.environ['XLA_FLAGS'] = (
          flags + f' --xla_force_host_platform_device_count'
          f'={host_devices}').strip()
  if platform is None:
    return None

  import jax
  # private: the only place jax records which backends are already up
  from jax._src import xla_bridge
  initialized = sorted(xla_bridge._backends)
  if initialized:
    if platform not in initialized:
      raise RuntimeError(
          f'force_backend({platform!r}) called after backend(s) '
          f'{initialized} initialized — platform selection must run '
          'before the first jax backend contact')
    return platform  # already on the requested platform: idempotent
  jax.config.update('jax_platforms', platform)
  return platform


def configure_compile_cache() -> str:
  """Point JAX's persistent compilation cache at one stable directory;
  call before the first compile. Returns the directory in effect.

  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
  and nothing is set here, so the directory can be placed from outside
  the program. Otherwise the cache lives at ``<checkout>/.jax_cache``.
  The path is part of the cache key, so it is never a temporary name.
  So is a program's metadata (see below).
  """
  import jax
  if not raw_env('JAX_COMPILATION_CACHE_DIR'):
    jax.config.update('jax_compilation_cache_dir',
                      os.path.join(_CHECKOUT, '.jax_cache'))
  # JAX strips locations and scope names before it hashes a program, so
  # a cached executable would keep the ``op_name``s it was compiled
  # with, and ``obs/device.py`` would read the scopes of an older tree
  # out of a trace. Keyed on the metadata too, an edit that moves a
  # scope or a line compiles once more.
  jax.config.update('jax_compilation_cache_include_metadata_in_key', True)
  return jax.config.jax_compilation_cache_dir
