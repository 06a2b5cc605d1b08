"""Device time by layer for the per-layer readers that want it.

A reader is handed the window's trace already reduced to sums
(``run.py::run_cell``), so the scope readers take a short traced stretch
of their own through the entry point the program offers an operator:
``SPMDSageTrainStep.scope_profile``. The trainer is the one the window
drove, found through ``glt_tpu.obs.device.live_step_programs``. Every
input is made on the host with numpy and ``jax.device_put`` in the
types and placements of the window's own calls, so that nothing is
traced or compiled after the window opened (``drivers/fused.py::
compilations`` counts, with limit 0). It runs once a process, and the
readers share what it found.

Against a program that has no scopes (no ``glt_tpu.obs.device``) every
reader returns ``None`` and the line leaves its metric out.
"""
import json
import sys
import time

import numpy as np

STEPS = 8          # the first and the last are cut: 6 whole steps count
AGREE = 0.03       # scoped busy time against the window's own, a step

_PROFILE = []      # [profile or None], once a process


def inputs(trainer, cfg, traffic, chips, steps=STEPS, seed=0):
  """``(params, opt_state, batches)`` for ``scope_profile``: weights in
  the tree of ``graphgen.weights`` (normal at 1/sqrt(fan_in), biases at
  a tenth), an optimizer state of zeros, ``steps`` batches of fresh
  seeds with full ``n_valid`` and typed keys."""
  import jax
  from jax.sharding import NamedSharding, PartitionSpec as P
  rng = np.random.default_rng([int(seed), 25])
  dims = ([cfg['feature_dim']] + [cfg['hidden_dim']] * (
      cfg['num_layers'] - 1) + [cfg['num_classes']])
  normal = lambda shape, scale: (
      rng.standard_normal(shape, dtype=np.float32) * np.float32(scale))
  tree = {f'conv{i}': {
      'lin_root': {'kernel': normal((a, b), a ** -0.5),
                   'bias': normal((b,), 0.1)},
      'lin_nbr': {'kernel': normal((a, b), a ** -0.5)}}
          for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))}
  everywhere = NamedSharding(trainer.mesh, P())
  params = jax.device_put({'params': tree}, everywhere)
  opt_state = jax.device_put(
      jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                   jax.eval_shape(trainer.tx.init, params)), everywhere)
  per_step = chips * traffic['batch_per_chip']
  seeds = rng.choice(cfg['num_nodes'], size=(steps, per_step),
                     replace=False).astype(np.int32)
  n_valid = np.full((chips,), traffic['batch_per_chip'], np.int32)
  key_bits = rng.integers(0, 2 ** 32, size=(steps, chips, 2),
                          dtype=np.uint32)
  # wrapping key data runs no program; the array is uncommitted on the
  # default device, as a slice of the window's split keys is
  batches = [(seeds[t], n_valid,
              jax.random.wrap_key_data(jax.device_put(key_bits[t])))
             for t in range(steps)]
  return params, opt_state, batches


def _take(run):
  try:
    from glt_tpu.obs.device import live_step_programs
  except ImportError:
    print('chipbench: scope window: this program has no '
          'glt_tpu.obs.device; no scope metric', file=sys.stderr)
    return None
  programs = live_step_programs()
  if len(programs) != 1:
    print(f'chipbench: scope window: {len(programs)} live step programs, '
          'not one; no scope metric', file=sys.stderr)
    return None
  t0 = time.perf_counter()
  params, opt_state, batches = inputs(
      programs[0], run['cfg'], run['traffic'], run['chips'])
  profile = programs[0].scope_profile(params, opt_state, batches)
  took = time.perf_counter() - t0
  window_ms = run['trace']['top_busy_s'] * 1e3 / run['trace']['steps']
  off = profile['busy_ms'] / window_ms - 1.0
  print(f'chipbench: scope window: {took:.2f} s; busy '
        f"{profile['busy_ms']:.3f} ms a step over {profile['steps']} steps, "
        f'the window\'s own {window_ms:.3f} ({100 * off:+.2f} %)',
        file=sys.stderr)
  print('chipbench: scope profile ' + json.dumps(profile), file=sys.stderr)
  if abs(off) > AGREE:
    print(f'chipbench: scope window: the two busy times differ by more '
          f'than {100 * AGREE:.0f} %; no scope metric', file=sys.stderr)
    return None
  return profile


def profile(run):
  if not _PROFILE:
    _PROFILE.append(_take(run))
  return _PROFILE[0]


def layer_ms(run, layer):
  """Device ms a step of the ops under ``layer``, or ``None``."""
  found = profile(run)
  return None if found is None else found['layers'].get(layer)
