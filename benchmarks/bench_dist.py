"""Distributed sampling scaling benchmark — the reference's scale_up
figure protocol (benchmarks/: sampled edges/s as workers are added).

Runs DistNeighborSampler over a partitioned synthetic products-slice
graph at 1..P devices and reports throughput per mesh size. On the
virtual CPU mesh this measures SCALING SHAPE (collective overhead vs
parallel speedup), not absolute TPU throughput — the same program runs
unmodified on a real slice.

Prints one JSON line: edges/s per mesh size + parallel efficiency.
``GLT_BENCH_PLATFORM=cpu`` + XLA_FLAGS=--xla_force_host_platform_device_count=8
run it hardware-free.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root -> glt_tpu

import numpy as np


def run_mesh(n_dev, root_by_p, num_nodes, fanout, batch, iters, warmup):
  import jax
  import jax.numpy as jnp
  from glt_tpu.distributed import DistGraph, DistNeighborSampler
  from glt_tpu.parallel import make_mesh
  mesh = make_mesh(n_dev)
  dg = DistGraph.from_dataset_partitions(mesh, root_by_p[n_dev])
  s = DistNeighborSampler(dg, fanout, seed=0)
  warmup = max(warmup, 1)  # first call compiles; never time it
  iters = max(iters, 1)
  rng = np.random.default_rng(0)
  outs = None
  t0 = None
  for it in range(warmup + iters):
    if it == warmup:
      jax.block_until_ready(outs['num_sampled_edges'])
      t0 = time.time()
    seeds = rng.integers(0, num_nodes, (n_dev, batch))
    outs = s.sample_from_nodes(seeds, np.full(n_dev, batch))
  total = np.asarray(
      jax.block_until_ready(outs['num_sampled_edges'])).sum()
  dt = time.time() - t0
  # num_sampled_edges is per-batch; edges/s = edges-per-iter * iters / dt
  edges_per_iter = float(total)
  return edges_per_iter * iters / dt


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--num-nodes', type=int, default=200_000)
  ap.add_argument('--avg-degree', type=int, default=15)
  ap.add_argument('--batch-size', type=int, default=512)
  ap.add_argument('--fanout', default='15,10')
  ap.add_argument('--iters', type=int, default=15)
  ap.add_argument('--warmup', type=int, default=3)
  ap.add_argument('--mesh-sizes', default='1,2,4,8')
  args = ap.parse_args()

  sizes = [int(x) for x in args.mesh_sizes.split(',')]
  os.environ.setdefault(
      'XLA_FLAGS',
      f'--xla_force_host_platform_device_count={max(sizes)}')
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  from glt_tpu.partition import RandomPartitioner

  n = args.num_nodes
  e = n * args.avg_degree
  rng = np.random.default_rng(0)
  src = rng.integers(0, n, e, dtype=np.int64)
  dst = (rng.random(e) ** 2 * n).astype(np.int64) % n
  fanout = [int(x) for x in args.fanout.split(',')]

  root_by_p = {}
  for p in sizes:
    root = tempfile.mkdtemp(prefix=f'bdist{p}_')
    RandomPartitioner(root, num_parts=p, num_nodes=n,
                      edge_index=np.stack([src, dst])).partition()
    root_by_p[p] = root

  results = {}
  for p in sizes:
    eps = run_mesh(p, root_by_p, n, fanout, args.batch_size,
                   args.iters, args.warmup)
    results[p] = round(eps, 1)

  base = results[sizes[0]] / sizes[0]
  eff = {p: round(results[p] / (p * base), 3) for p in sizes}
  backend = jax.devices()[0].platform
  out = {
      'metric': 'dist_sampled_edges_per_sec',
      'value': results[sizes[-1]],
      'unit': 'edges/s',
      'vs_baseline': None,
      'per_mesh_size': results,
      'parallel_efficiency': eff,
      'backend': backend,
  }
  if backend == 'cpu':
    # all virtual devices share the same physical cores: efficiency
    # here measures collective/program overhead (a regression canary),
    # NOT speedup — real speedup needs real chips per device
    out['note'] = ('cpu virtual mesh shares cores; efficiency is an '
                   'overhead canary, not a speedup measurement')
  print(json.dumps(out))


if __name__ == '__main__':
  main()
