"""Device time by layer of the typed step, for the ``rgat_*`` readers:
``chipbench/scope_window.py``'s rules for ``DistHeteroTrainStep``.

The trainer is the one the window drove, found through
``glt_tpu.obs.device.live_step_programs``; it is driven for 8 steps
through its own ``scope_profile`` on fresh inputs. Every input is made on
the host with numpy and ``jax.device_put`` in the types and placements of
the window's own calls, so that nothing is traced or compiled after the
window opened (``drivers/hetero_fused.py::compilations`` counts, with
limit 0). The scoped busy time must agree with the window's own within
3 %, or the readers say nothing. It runs once a process, and the readers
share what it found.

Against a program whose typed step has no scopes (no ``scope_profile``)
every reader returns ``None`` and the line leaves its metric out.
"""
import json
import sys
import time

import numpy as np

STEPS = 8          # the first and the last are cut: 6 whole steps count
AGREE = 0.03       # scoped busy time against the window's own, a step

_PROFILE = []      # [profile or None], once a process


def inputs(trainer, cfg, traffic, steps=STEPS, seed=0):
  """``(params, opt_state, batches)`` for ``scope_profile``: weights in
  the tree of ``graphgen_hetero.weights`` (normal at 1/sqrt(fan_in)), an
  optimizer state of zeros, ``steps`` batches of fresh seeds of the seed
  type with full ``n_valid`` and a typed key each."""
  import jax
  from jax.sharding import NamedSharding, PartitionSpec as P
  from chipbench.drivers.hetero_fused import relations
  rng = np.random.default_rng([int(seed), 27])
  hidden, heads = cfg['hidden_dim'], cfg['heads']
  normal = lambda shape, scale: (
      rng.standard_normal(shape, dtype=np.float32) * np.float32(scale))
  tree = {}
  for i in range(cfg['num_layers']):
    a = cfg['feature_dim'] if i == 0 else hidden
    tree[f'layer{i}'] = {
        'conv_' + '__'.join(e): {
            'proj': {'kernel': normal((a, hidden), a ** -0.5)},
            'att_src': normal((heads, hidden // heads),
                              (hidden // heads) ** -0.5),
            'att_dst': normal((heads, hidden // heads),
                              (hidden // heads) ** -0.5)}
        for e in relations(cfg)[1]}
  tree['head'] = {'kernel': normal((hidden, cfg['num_classes']),
                                   hidden ** -0.5),
                  'bias': normal((cfg['num_classes'],), 0.1)}
  everywhere = NamedSharding(trainer.mesh, P())
  params = jax.device_put({'params': tree}, everywhere)
  opt_state = jax.device_put(
      jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                   jax.eval_shape(trainer.tx.init, params)), everywhere)
  batch = traffic['batch_per_chip']
  seeds = rng.choice(cfg['num_nodes'][traffic['seed_type']],
                     size=(steps, batch), replace=False).astype(np.int32)
  n_valid = np.full((1,), batch, np.int32)
  key_bits = rng.integers(0, 2 ** 32, size=(steps, 2), dtype=np.uint32)
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
    print('chipbench: hetero scope window: this program has no '
          'glt_tpu.obs.device; no scope metric', file=sys.stderr)
    return None
  programs = [p for p in live_step_programs()
              if hasattr(p, 'scope_profile') and hasattr(p, 'node_budget')]
  if len(programs) != 1:
    print(f'chipbench: hetero scope window: {len(programs)} live typed '
          'step programs, not one; no scope metric', file=sys.stderr)
    return None
  t0 = time.perf_counter()
  params, opt_state, batches = inputs(programs[0], run['cfg'],
                                      run['traffic'])
  profile = programs[0].scope_profile(params, opt_state, batches)
  took = time.perf_counter() - t0
  window_ms = run['trace']['top_busy_s'] * 1e3 / run['trace']['steps']
  off = profile['busy_ms'] / window_ms - 1.0
  print(f'chipbench: hetero scope window: {took:.2f} s; busy '
        f"{profile['busy_ms']:.3f} ms a step over {profile['steps']} steps, "
        f'the window\'s own {window_ms:.3f} ({100 * off:+.2f} %)',
        file=sys.stderr)
  print('chipbench: scope profile ' + json.dumps(profile), file=sys.stderr)
  if abs(off) > AGREE:
    print(f'chipbench: hetero scope window: the two busy times differ by '
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


def stage_ms(run, layer, *parts):
  """Device ms a step of the stages under ``layer`` whose path holds one
  of ``parts`` as a component, forward and backward; ``None`` where the
  profile has no such stage."""
  found = profile(run)
  if found is None:
    return None
  hit = [ms for stage, ms in found['stages'].items()
         if stage.split('/')[0] == layer
         and set(parts) & set(stage.split('/'))]
  return sum(hit) if hit else None
