"""Process start-up contracts: what the entry points do before they
touch a device. The device side itself is chip_smoke.py's job, on a
chip; these pin the parts a CPU can see."""
import importlib.util
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_dir_is_fixed_or_left_to_the_environment(
    monkeypatch):
  from glt_tpu.utils.backend import configure_compile_cache
  updates = []
  monkeypatch.setattr(jax.config, 'update',
                      lambda name, value: updates.append((name, value)))
  monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
  # scope names are part of the key wherever the cache lives: a cached
  # executable must not carry an older tree's (obs/device.py reads them)
  keyed = ('jax_compilation_cache_include_metadata_in_key', True)
  configure_compile_cache()
  assert updates == [('jax_compilation_cache_dir',
                      os.path.join(REPO, '.jax_cache')), keyed]
  # with the variable set, JAX has read it itself at start-up and the
  # helper sets no directory in code
  del updates[:]
  monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/somewhere/else')
  configure_compile_cache()
  assert updates == [keyed]


def test_chip_smoke_refuses_to_run_without_a_tpu():
  # the timeout is the "within seconds, before any graph is built" part
  proc = subprocess.run(
      [sys.executable, os.path.join(REPO, 'chip_smoke.py')],
      env={**os.environ, 'JAX_PLATFORMS': 'cpu'}, capture_output=True,
      text=True, timeout=60)
  assert proc.returncode != 0
  assert 'not tpu' in proc.stderr
  assert proc.stdout == ''  # no phase ran, no result line


def test_package_imports_without_the_compat_shims():
  import glt_tpu  # noqa: F401
  assert importlib.util.find_spec('glt_tpu.utils.compat') is None
  assert callable(jax.shard_map) and callable(jax.lax.axis_size)
  assert jax.memory.Space.Host is not None
