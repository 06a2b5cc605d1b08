"""Sampling-pipeline breakdown: where does a multihop batch spend time?

Times the composed pipeline against its constituent
stages at bench.py shapes (2.45M nodes / 62M edges, batch 1024,
[15,10,5]) and, with ``--trace DIR``, also captures a ``jax.profiler``
trace of 10 steady-state iterations for op-level inspection.

Stages timed (each as its own jitted program, steady state):
  one_hop_h{i}    sample_neighbors at hop i's frontier width
  assign_h{i}     dense_assign (dedup/relabel) at hop i's output width
  composed        the full multihop_sample program
  composed_scan   multihop_sample_many with GLT_BENCH_SCAN batches fused

Prints one JSON line with per-stage ms and the top-3 costliest stages.
``GLT_BENCH_PLATFORM=cpu`` forces the CPU backend.
"""
import argparse
import functools
import json
import os
import time

import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # repo root -> glt_tpu

import numpy as np

NUM_NODES = 2_450_000
NUM_EDGES = 62_000_000
BATCH = 1024
FANOUT = (15, 10, 5)


def _time_fn(fn, args, iters=20, warmup=3, donate_state=False):
  """Steady-state seconds/call for a jitted fn; fn returns arrays."""
  import jax
  out = None
  state = args
  for _ in range(warmup):
    out = fn(*state)
    if donate_state:
      state = (state[0], state[1], out[1], out[2])
  jax.block_until_ready(out)
  t0 = time.time()
  for _ in range(iters):
    out = fn(*state)
    if donate_state:
      state = (state[0], state[1], out[1], out[2])
  jax.block_until_ready(out)
  return (time.time() - t0) / iters


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--trace', default=None,
                  help='also dump a jax.profiler trace to this dir')
  ap.add_argument('--iters', type=int, default=20)
  args = ap.parse_args()

  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  import jax.numpy as jnp
  from glt_tpu.data import Topology
  from glt_tpu.ops.pipeline import multihop_sample, multihop_sample_many
  from glt_tpu.ops.sample import sample_neighbors
  from glt_tpu.ops.unique import dense_assign, dense_init, \
      dense_make_tables, dense_reset

  def record(stages, name, secs):
    # incremental output: stage timings are too expensive to lose to
    # a failure late in a print-at-the-end design
    stages[name] = secs
    print(f'# {name}: {secs * 1e3:.3f} ms', file=_sys.stderr, flush=True)

  rng = np.random.default_rng(0)
  src = rng.integers(0, NUM_NODES, NUM_EDGES, dtype=np.int64)
  dst = (rng.random(NUM_EDGES) ** 2 * NUM_NODES).astype(np.int64) \
      % NUM_NODES
  topo = Topology(indptr=None, edge_index=np.stack([src, dst]),
                  num_nodes=NUM_NODES)
  del src, dst
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  indices = jnp.asarray(topo.indices)
  key = jax.random.key(0)

  stages = {}

  # per-hop one_hop and dense_assign at the real frontier widths
  width = BATCH
  for h, k in enumerate(FANOUT):
    frontier = jnp.asarray(
        rng.integers(0, NUM_NODES, width).astype(np.int32))
    mask = jnp.ones((width,), bool)

    @jax.jit
    def hop_only(fr, m, key, _k=k):
      out = sample_neighbors(indptr, indices, fr, _k, key, seed_mask=m)
      return out.nbrs, out.mask

    record(stages, f'one_hop_h{h}', _time_fn(
        lambda fr, m: hop_only(fr, m, key), (frontier, mask),
        iters=args.iters))

    nbrs = np.asarray(hop_only(frontier, mask, key)[0]).reshape(-1)
    nmask = np.asarray(hop_only(frontier, mask, key)[1]).reshape(-1)
    budget = width * k + 8

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def assign_only(ids, ok, table, scratch, _budget=budget):
      state = dense_init(table, scratch, _budget)
      state, labels = dense_assign(state, ids, ok)
      table, scratch = dense_reset(state)
      return labels, table, scratch

    table, scratch = dense_make_tables(NUM_NODES)
    record(stages, f'assign_h{h}', _time_fn(
        assign_only,
        (jnp.asarray(nbrs), jnp.asarray(nmask), table, scratch),
        iters=args.iters, donate_state=True))

    # the sort-merge inducer's equivalent stage at the same widths, with
    # a realistic seen-set size (everything deduped before this hop)
    from glt_tpu.ops.unique import sorted_hop_dedup
    seen_c = sum(BATCH * int(np.prod(FANOUT[:i])) for i in range(h + 1))
    u_ids = jnp.asarray(
        rng.choice(NUM_NODES, seen_c, replace=False).astype(np.int32))
    u_labs = jnp.arange(seen_c, dtype=jnp.int32)
    rows_flat = jnp.asarray(
        rng.integers(0, seen_c, width * k).astype(np.int32))

    @jax.jit
    def sorted_only(uid, ula, ids, ok, rows):
      d = sorted_hop_dedup(uid, ula, jnp.asarray(seen_c, jnp.int32),
                           ids, ok, rows)
      return (d['labels3'], d['rows3'], d['new_head3'], d['u_ids2'],
              d['count2'])

    record(stages, f'sorted_h{h}', _time_fn(
        sorted_only,
        (u_ids, u_labs, jnp.asarray(nbrs), jnp.asarray(nmask),
         rows_flat), iters=args.iters))
    width *= k

  # composed program (bench.py's work unit)
  one_hop = lambda ids, fanout, key, mask: sample_neighbors(
      indptr, indices, ids, fanout, key, seed_mask=mask)

  from glt_tpu.ops.pipeline import checksum_outputs as checksum
  from glt_tpu.ops.pipeline import make_dedup_tables

  @functools.partial(jax.jit, donate_argnums=(2, 3))
  def composed(seeds, key, table, scratch):
    out, table, scratch = multihop_sample(
        one_hop, seeds, jnp.asarray(BATCH), FANOUT, key, table, scratch)
    return (out['num_sampled_edges'].sum() + checksum(out), table,
            scratch)

  table, scratch = make_dedup_tables(NUM_NODES)
  seeds = jnp.asarray(rng.integers(0, NUM_NODES, BATCH).astype(np.int32))
  record(stages, 'composed', _time_fn(composed, (seeds, key, table, scratch),
                                      iters=args.iters, donate_state=True))

  scan = max(int(os.environ.get('GLT_BENCH_SCAN', '4')), 1)

  @functools.partial(jax.jit, donate_argnums=(2, 3))
  def composed_scan(seeds2, key, table, scratch):
    outs, table, scratch = multihop_sample_many(
        one_hop, seeds2, jnp.full(scan, BATCH, jnp.int32), FANOUT, key,
        table, scratch)
    return (outs['num_sampled_edges'].sum() + checksum(outs), table,
            scratch)

  seeds2 = jnp.asarray(
      rng.integers(0, NUM_NODES, (scan, BATCH)).astype(np.int32))
  table, scratch = make_dedup_tables(NUM_NODES)
  record(stages, 'composed_scan_per_batch', _time_fn(
      composed_scan, (seeds2, key, table, scratch),
      iters=args.iters, donate_state=True) / scan)

  if args.trace:
    table, scratch = make_dedup_tables(NUM_NODES)
    state = (seeds, key, table, scratch)
    out = composed(*state)  # ensure compiled before tracing
    jax.block_until_ready(out)
    with jax.profiler.trace(args.trace):
      for _ in range(10):
        out = composed(state[0], state[1], out[1], out[2])
      jax.block_until_ready(out)
    print(f'# trace written to {args.trace}')

  ms = {k: round(v * 1e3, 3) for k, v in stages.items()}
  # op_sum models the ACTIVE engine's composed program: both engines'
  # dedup stages are timed above, but only one runs inside `composed`
  from glt_tpu.ops.pipeline import dedup_engine
  skip = 'sorted_' if dedup_engine() == 'table' else 'assign_'
  in_sum = lambda k: not k.startswith('composed') and not k.startswith(skip)
  op_sum = sum(v for k, v in ms.items() if in_sum(k))
  top3 = sorted((k for k in ms if in_sum(k)), key=lambda k: -ms[k])[:3]
  dev = jax.devices()[0]
  out = {
      'metric': 'sampler_stage_ms',
      'stages': ms,
      'engine': dedup_engine(),
      'op_sum_ms': round(op_sum, 3),
      'composed_over_opsum': round(ms['composed'] / max(op_sum, 1e-9), 2),
      'top3': top3,
      'backend': dev.platform,
  }
  try:
    # XLA's own estimate of the composed program's work: bytes accessed
    # vs flops shows how bandwidth-bound the sampler is. lower() only
    # needs avals, so pass shape specs instead of fresh device buffers.
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    t_spec = jax.ShapeDtypeStruct(table.shape, jnp.int32)
    ca = composed.lower(spec(seeds), spec(key), t_spec, t_spec) \
        .compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
      ca = ca[0] if ca else {}
    out['cost_analysis'] = {
        k: float(ca[k]) for k in ('flops', 'bytes accessed')
        if k in ca}
  except Exception as e:  # cost model availability varies by backend
    out['cost_analysis_error'] = str(e)[:120]
  print(json.dumps(out))


if __name__ == '__main__':
  main()
