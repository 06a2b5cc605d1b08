"""Adjudicate the scan8 fused-engine anomaly (bench_sort_scan8.json).

The round-5 suite captured 3.5e9 edges/s for the fused engine at
GLT_BENCH_SCAN=8 — 117x the scan4 number, while the unfused sort engine
held ~28.6M at every scan width. Either lax.scan at T=8 unlocked a real
schedule win, or that capture is an artifact. This script decides from
first principles on hardware:

  1. identical seed stacks through BOTH engines at scan widths 4 and 8;
  2. cross-engine checksum + valid-edge-count equality (the engines are
     bit-compatible by contract, tests/test_fused_hop.py);
  3. honest timing: per-call block_until_ready (no async pipelining
     credit), plus the bench's async-loop timing for comparison.

Emits one JSON line per (engine, scan) cell plus a verdict line.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main():
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  import jax.numpy as jnp
  from glt_tpu.data import Topology
  from glt_tpu.ops.pipeline import (make_dedup_tables, multihop_sample_many,
                                    checksum_outputs)
  from glt_tpu.ops.sample import sample_neighbors
  from glt_tpu.utils.rng import make_key

  NUM_NODES = int(os.environ.get('GLT_BENCH_NODES', 2_450_000))
  NUM_EDGES = int(os.environ.get('GLT_BENCH_EDGES', 62_000_000))
  BATCH = 1024
  FANOUT = (15, 10, 5)
  ITERS = int(os.environ.get('GLT_ADJ_ITERS', 10))

  dev = jax.devices()[0]
  print(f'# backend: {dev.platform} ({dev.device_kind})', file=sys.stderr)

  rng = np.random.default_rng(0)
  src = rng.integers(0, NUM_NODES, NUM_EDGES, dtype=np.int64)
  dst = (rng.random(NUM_EDGES) ** 2 * NUM_NODES).astype(np.int64) % NUM_NODES
  topo = Topology(indptr=None, edge_index=np.stack([src, dst]),
                  num_nodes=NUM_NODES)
  del src, dst
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  indices = jnp.asarray(topo.indices)
  one_hop = lambda ids, fanout, key, mask: sample_neighbors(
      indptr, indices, ids, fanout, key, seed_mask=mask)

  results = {}
  for scan in (4, 8):
    seed_pool = np.random.default_rng(7).integers(
        0, NUM_NODES, (ITERS, scan, BATCH))
    for engine in ('sort', 'fused'):
      os.environ['GLT_FUSED_HOP'] = '1' if engine == 'fused' else '0'
      os.environ['GLT_DEDUP'] = 'sort'

      def sample_batch(seeds, key, table, scratch):
        outs, table, scratch = multihop_sample_many(
            one_hop, seeds, jnp.full(scan, BATCH, jnp.int32), FANOUT,
            key, table, scratch)
        return (outs['num_sampled_edges'].sum(),
                checksum_outputs(outs), table, scratch)

      fn = jax.jit(sample_batch, donate_argnums=(2, 3))
      table, scratch = make_dedup_tables(NUM_NODES)
      keys = jax.random.split(make_key(0), ITERS)
      # warmup (compile)
      e, s, table, scratch = fn(jnp.asarray(seed_pool[0], jnp.int32),
                                keys[0], table, scratch)
      jax.block_until_ready((e, s))
      # honest per-call timing: sync every call
      edge_sum, sig_sum, tsync = 0, 0, 0.0
      per_call = []
      for i in range(ITERS):
        t0 = time.time()
        e, s, table, scratch = fn(jnp.asarray(seed_pool[i], jnp.int32),
                                  keys[i], table, scratch)
        jax.block_until_ready((e, s))
        dt = time.time() - t0
        per_call.append(dt)
        tsync += dt
        edge_sum += int(e)
        sig_sum += int(np.asarray(s, np.uint64)) & 0xFFFFFFFFFFFFFFFF
      eps_sync = edge_sum / tsync
      cell = {
          'engine': engine, 'scan': scan, 'iters': ITERS,
          'edges_total': edge_sum,
          'checksum': f'{sig_sum & 0xFFFFFFFFFFFFFFFF:016x}',
          'eps_sync': round(eps_sync, 1),
          'ms_per_call_median': round(1e3 * float(np.median(per_call)), 2),
          'ms_per_call_min': round(1e3 * float(np.min(per_call)), 2),
      }
      results[(engine, scan)] = cell
      print(json.dumps(cell))
      sys.stdout.flush()

  verdict = {
      'checksum_match_scan4':
          results[('sort', 4)]['checksum'] == results[('fused', 4)]['checksum'],
      'checksum_match_scan8':
          results[('sort', 8)]['checksum'] == results[('fused', 8)]['checksum'],
      'edges_match_scan8':
          results[('sort', 8)]['edges_total']
          == results[('fused', 8)]['edges_total'],
      'fused8_vs_sort8_speedup':
          round(results[('fused', 8)]['eps_sync']
                / results[('sort', 8)]['eps_sync'], 2),
  }
  print(json.dumps({'verdict': verdict}))


if __name__ == '__main__':
  main()
