"""Device time by layer of the link-prediction step, for the ``link_*``
readers: ``chipbench/scope_window.py``'s rules for an
``SPMDSageTrainStep`` that was given a ``NegativeSampling``.

The trainer is the one the window drove, found through
``glt_tpu.obs.device.live_step_programs``; it is driven for 8 steps
through its own ``scope_profile`` on fresh positive edges of its own
graph, drawn as the window's are. Every input is made on the host with
numpy and ``jax.device_put`` in the types and placements of the window's
own calls, so that nothing is traced or compiled after the window opened
(``drivers/link_fused.py::compilations`` counts, with limit 0). The
scoped busy time must agree with the window's own within 3 %, or the
readers say nothing. It runs once a process, and the readers share what
it found.

Against a program with no link step every reader returns ``None`` and the
line leaves its metric out.
"""
import json
import sys
import time

import numpy as np

STEPS = 8          # the first and the last are cut: 6 whole steps count
AGREE = 0.03       # scoped busy time against the window's own, a step

_PROFILE = []      # [profile or None], once a process


def inputs(trainer, cfg, traffic, chips, steps=STEPS, seed=0):
  """``(params, opt_state, batches)`` for ``scope_profile``: what
  ``scope_window.inputs`` makes for the node step (weights in the tree of
  ``graphgen.weights`` with ``out_dim`` where the classes stood, an
  optimizer state of zeros, full ``n_valid``, typed keys), with fresh
  positive edges of the trainer's own graph where its node seeds stood."""
  from chipbench import scope_window
  from chipbench.drivers.link_fused import positive_edges
  params, opt_state, batches = scope_window.inputs(
      trainer, dict(cfg, num_classes=cfg['out_dim']), traffic, chips,
      steps=steps, seed=seed)
  per_step = chips * traffic['batch_per_chip']
  topo = trainer.graph.topo
  pairs = positive_edges(
      np.asarray(topo.indptr), np.asarray(topo.indices),
      np.random.default_rng([int(seed), 31]),
      steps * per_step).reshape(steps, per_step, 2)
  return params, opt_state, [
      (pairs[t], n_valid, keys) for t, (_, n_valid, keys) in
      enumerate(batches)]


def _take(run):
  try:
    from glt_tpu.obs.device import live_step_programs
  except ImportError:
    print('chipbench: link scope window: this program has no '
          'glt_tpu.obs.device; no scope metric', file=sys.stderr)
    return None
  programs = [p for p in live_step_programs()
              if getattr(p, 'neg_sampling', None) is not None]
  if len(programs) != 1:
    print(f'chipbench: link scope window: {len(programs)} live link step '
          'programs, not one; no scope metric', file=sys.stderr)
    return None
  t0 = time.perf_counter()
  params, opt_state, batches = inputs(
      programs[0], run['cfg'], run['traffic'], run['chips'])
  profile = programs[0].scope_profile(params, opt_state, batches)
  took = time.perf_counter() - t0
  window_ms = run['trace']['top_busy_s'] * 1e3 / run['trace']['steps']
  off = profile['busy_ms'] / window_ms - 1.0
  print(f'chipbench: link scope window: {took:.2f} s; busy '
        f"{profile['busy_ms']:.3f} ms a step over {profile['steps']} steps, "
        f'the window\'s own {window_ms:.3f} ({100 * off:+.2f} %)',
        file=sys.stderr)
  print('chipbench: scope profile ' + json.dumps(profile), file=sys.stderr)
  print('chipbench: link step layer_rows '
        f'{programs[0].layer_rows} layer_groups {programs[0].layer_groups}',
        file=sys.stderr)
  if abs(off) > AGREE:
    print(f'chipbench: link scope window: the two busy times differ by '
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


def stage_ms(run, prefix):
  """Device ms a step of the stages at or under the scope path
  ``prefix`` (``sampler/negative``), forward and backward; ``None``
  where the profile has no such stage."""
  found = profile(run)
  if found is None:
    return None
  hit = [ms for stage, ms in found['stages'].items()
         if stage == prefix or stage.startswith(prefix + '/')]
  return sum(hit) if hit else None
