"""Device time by layer of the typed step seeded by edges, for the
``bisage_*`` readers: ``chipbench/hetero_scope_window.py``'s rules (8
steps through the window's own trainer's ``scope_profile`` on fresh
inputs made on the host, 6 whole steps counted, the scoped busy time
within 3 % of the window's own or the readers say nothing) with this
model's parameter tree and this step's feed: fresh positive ``(user,
item)`` edges of the trainer's own graph, drawn by edge as the window's.
That module's ``inputs`` is closed over R-GAT's tree, so the take is
carried here; its steps and its tolerance are imported. The step's
program owns the state it is given, and ``scope_profile`` steps on with
what each call returns.

Against a program without the typed link model or without the typed
scopes every reader returns ``None`` and the line leaves its metric out.
"""
import json
import sys
import time

import numpy as np

from chipbench.hetero_scope_window import AGREE, STEPS

_PROFILE = []      # [profile or None], once a process


def inputs(trainer, cfg, traffic, steps=STEPS, seed=0):
  """``(params, opt_state, batches)`` for ``scope_profile``: weights in
  the tree of ``graphgen_bipartite.weights``, an optimizer state of
  zeros, ``steps`` batches of fresh positive edges of the trainer's own
  seed relation (fetched from the device) with full ``n_valid`` and a
  typed key each; all made with numpy and ``jax.device_put``, so that
  nothing is traced or compiled."""
  import jax
  from jax.sharding import NamedSharding, PartitionSpec as P
  from chipbench import graphgen_bipartite
  rng = np.random.default_rng([int(seed), 38])
  tree = graphgen_bipartite.tree_of(
      cfg, lambda i, shape: rng.standard_normal(shape, dtype=np.float32))
  everywhere = NamedSharding(trainer.mesh, P())
  params = jax.device_put(tree, everywhere)
  opt_state = jax.device_put(
      jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                   jax.eval_shape(trainer.tx.init, params)), everywhere)
  batch = traffic['batch_per_chip']
  store = trainer.g.graphs[tuple(traffic['seed_relation'])]
  pairs = graphgen_bipartite.positive_edges(
      np.asarray(store.indptr)[0], np.asarray(store.indices)[0], rng,
      steps * batch).reshape(steps, batch, 2)
  n_valid = np.full((1,), batch, np.int32)
  key_bits = rng.integers(0, 2 ** 32, size=(steps, 2), dtype=np.uint32)
  batches = [(pairs[t], n_valid,
              jax.random.wrap_key_data(jax.device_put(key_bits[t])))
             for t in range(steps)]
  return params, opt_state, batches


def _take(run):
  try:
    from glt_tpu.obs.device import live_step_programs
  except ImportError:
    print('chipbench: bisage scope window: this program has no '
          'glt_tpu.obs.device; no scope metric', file=sys.stderr)
    return None
  programs = [p for p in live_step_programs()
              if hasattr(p, 'scope_profile') and hasattr(p, 'node_budget')
              and hasattr(p.model, 'embedding_tables')]
  if len(programs) != 1:
    print(f'chipbench: bisage scope window: {len(programs)} live typed '
          'step programs over embedding tables, not one; no scope metric',
          file=sys.stderr)
    return None
  t0 = time.perf_counter()
  params, opt_state, batches = inputs(programs[0], run['cfg'],
                                      run['traffic'])
  profile = programs[0].scope_profile(params, opt_state, batches)
  took = time.perf_counter() - t0
  window_ms = run['trace']['top_busy_s'] * 1e3 / run['trace']['steps']
  off = profile['busy_ms'] / window_ms - 1.0
  print(f'chipbench: bisage scope window: {took:.2f} s; busy '
        f"{profile['busy_ms']:.3f} ms a step over {profile['steps']} steps, "
        f'the window\'s own {window_ms:.3f} ({100 * off:+.2f} %)',
        file=sys.stderr)
  print('chipbench: scope profile ' + json.dumps(profile), file=sys.stderr)
  if abs(off) > AGREE:
    print('chipbench: bisage scope window: the two busy times differ by '
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
  of ``parts`` as a run of adjacent components (``'update/tables'``: an
  ``update`` with ``tables`` right behind it), forward and backward;
  ``None`` where the profile has no such stage."""
  found = profile(run)
  if found is None:
    return None
  hit = [ms for stage, ms in found['stages'].items()
         if stage.split('/')[0] == layer and any(
             f'/{p}/' in stage + '/' for p in parts)]
  return sum(hit) if hit else None
