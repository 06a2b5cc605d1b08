"""Device time by layer of the SEAL step, for the ``seal_*`` readers:
``chipbench/scope_window.py``'s rules for an ``SPMDSageTrainStep`` that
was given an ``EncloseSpec``.

The trainer is the one the window drove, found through
``glt_tpu.obs.device.live_step_programs``; it is driven for 8 steps
through its own ``scope_profile`` on fresh positive edges of its own
graph, drawn as the window's are. Every input is made on the host with
numpy and ``jax.device_put`` in the types and placements of the window's
own calls, so that nothing is traced or compiled after the window opened
(``drivers/seal_fused.py::compilations`` counts, with limit 0). The
scoped busy time must agree with the window's own within 3 %, or the
readers say nothing. It runs once a process, and the readers share what
it found.

Against a program with no enclosing-subgraph step every reader returns
``None`` and the line leaves its metric out.
"""
import json
import sys
import time

import numpy as np

STEPS = 8          # the first and the last are cut: 6 whole steps count
AGREE = 0.03       # scoped busy time against the window's own, a step

_PROFILE = []      # [profile or None], once a process


def inputs(trainer, cfg, traffic, chips, steps=STEPS, seed=0):
  """``(params, opt_state, batches)`` for ``scope_profile``: weights of
  the shapes the trainer's model has (normal at 1/sqrt(fan_in), biases
  at a tenth, the label embedding standard normal), an optimizer state
  of zeros, full ``n_valid``, typed keys, fresh positive pairs drawn as
  the window's are: edges of the graph as generated (the driver leaves its
  directed CSR on the trainer, which itself holds the undirected graph),
  so a source comes by its out-degree and a destination by its in-degree
  and the data-dependent parts of the step (hub pairs, DRNL's rounds, the
  tiles' locality) see the window's own mix."""
  import jax
  from jax.sharding import NamedSharding, PartitionSpec as P
  rng = np.random.default_rng([int(seed), 40])
  shapes = jax.eval_shape(trainer.init_params, jax.random.key(0))

  def leaf(path, a):
    name = jax.tree_util.keystr(path)
    scale = (0.1 if 'bias' in name else 1.0 if 'embedding' in name
             else float(np.prod(a.shape[:-1])) ** -0.5)
    return rng.standard_normal(a.shape, dtype=np.float32) * np.float32(scale)

  everywhere = NamedSharding(trainer.mesh, P())
  params = jax.device_put(jax.tree_util.tree_map_with_path(leaf, shapes),
                          everywhere)
  opt_state = jax.device_put(
      jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                   jax.eval_shape(trainer.tx.init, params)), everywhere)
  from chipbench.drivers.seal_fused import positive_pairs
  per_step = chips * traffic['batch_per_chip']
  pairs = positive_pairs(trainer.chipbench_directed, rng,
                         steps * per_step).reshape(steps, per_step, 2)
  n_valid = np.full((chips,), traffic['batch_per_chip'], np.int32)
  key_bits = rng.integers(0, 2 ** 32, size=(steps, chips, 2),
                          dtype=np.uint32)
  return params, opt_state, [
      (pairs[t], n_valid,
       jax.random.wrap_key_data(jax.device_put(key_bits[t])))
      for t in range(steps)]


def _take(run):
  try:
    from glt_tpu.obs.device import live_step_programs
  except ImportError:
    print('chipbench: seal scope window: this program has no '
          'glt_tpu.obs.device; no scope metric', file=sys.stderr)
    return None
  programs = [p for p in live_step_programs()
              if getattr(p, '_enclose', None) is not None]
  if len(programs) != 1:
    print(f'chipbench: seal scope window: {len(programs)} live '
          'enclosing-subgraph step programs, not one; no scope metric',
          file=sys.stderr)
    return None
  t0 = time.perf_counter()
  params, opt_state, batches = inputs(
      programs[0], run['cfg'], run['traffic'], run['chips'])
  profile = programs[0].scope_profile(params, opt_state, batches)
  took = time.perf_counter() - t0
  window_ms = run['trace']['top_busy_s'] * 1e3 / run['trace']['steps']
  off = profile['busy_ms'] / window_ms - 1.0
  print(f'chipbench: seal scope window: {took:.2f} s; busy '
        f"{profile['busy_ms']:.3f} ms a step over {profile['steps']} steps, "
        f'the window\'s own {window_ms:.3f} ({100 * off:+.2f} %)',
        file=sys.stderr)
  print('chipbench: scope profile ' + json.dumps(profile), file=sys.stderr)
  if abs(off) > AGREE:
    print(f'chipbench: seal scope window: the two busy times differ by '
          f'more than {100 * AGREE:.0f} %; no scope metric',
          file=sys.stderr)
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


def stage_ms(run, *prefixes):
  """Device ms a step of the stages at or under one of the scope paths
  ``prefixes`` (``sampler/enclose/induce``), forward and backward;
  ``None`` where the profile has no such stage."""
  found = profile(run)
  if found is None:
    return None
  hit = [ms for stage, ms in found['stages'].items()
         if any(stage == p or stage.startswith(p + '/') for p in prefixes)]
  return sum(hit) if hit else None
