"""Beyond-HBM training through the loader-driven host-spill path.

VERDICT r3 next #4: the host-spill stores exist and pass parity tests,
but nothing TRAINS against a feature table larger than one chip's HBM.
This benchmark does, and quantifies the spill tax:

  * builds a [N, D] float32 feature table whose full size exceeds one
    chip's HBM at the TPU-scale defaults (--num-nodes 40M --feat-dim 128
    = 20.5 GB > 16 GB v5e HBM; the hot split is what fits), degree-
    sorted so hot rows are the frequently sampled ones (reference
    reorder + UnifiedTensor cache semantics, unified_tensor.cu:202-231);
  * trains GraphSAGE through NeighborLoader (the loader-driven spill
    path, which resolves cold rows on host between device calls; the
    fused-step alternative is measured by bench_fused_spill.py) at
    prefetch_depth {0, 2} and, as the control, the SAME graph with a
    fully device-resident table;
  * reports seeds/s for each, the spill/resident throughput ratio, and
    the measured cold rate (fraction of gathered rows served from
    host) — the number that decides whether the default prefetch_depth
    should overlap host gathers with device compute.

CPU-mesh runs (GLT_BENCH_PLATFORM=cpu) measure the RATIO scaled down
(--num-nodes 300k); the absolute beyond-HBM claim needs the real chip.

Prints one JSON line.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main():
  ap = argparse.ArgumentParser()
  cpu = os.environ.get('GLT_BENCH_PLATFORM') == 'cpu'
  ap.add_argument('--num-nodes', type=int,
                  default=300_000 if cpu else 40_000_000)
  ap.add_argument('--avg-degree', type=int, default=8)
  ap.add_argument('--feat-dim', type=int, default=128)
  ap.add_argument('--split-ratio', type=float,
                  default=0.2,
                  help='hot fraction; at TPU defaults hot = 8M rows '
                       '(4.1 GB HBM) of a 20.5 GB table')
  ap.add_argument('--batch-size', type=int, default=1024)
  ap.add_argument('--fanout', default='10,5')
  ap.add_argument('--hidden', type=int, default=128)
  ap.add_argument('--steps', type=int, default=30)
  ap.add_argument('--warmup', type=int, default=3)
  args = ap.parse_args()

  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  import jax.numpy as jnp
  import optax
  from glt_tpu.data import Dataset
  from glt_tpu.data.reorder import sort_by_in_degree
  from glt_tpu.loader import NeighborLoader
  from glt_tpu.models import GraphSAGE

  rng = np.random.default_rng(0)
  n, e = args.num_nodes, args.num_nodes * args.avg_degree
  src = rng.integers(0, n, e, dtype=np.int64)
  # skewed in-degrees so the degree-sorted hot split actually captures
  # the frequently-sampled rows, as on real graphs
  dst = (rng.random(e) ** 2 * n).astype(np.int64) % n
  feats = rng.normal(size=(n, args.feat_dim)).astype(np.float32)
  labels = rng.integers(0, 16, n).astype(np.int32)
  fanout = [int(x) for x in args.fanout.split(',')]
  train_idx = rng.choice(n, min(n, 200_000), replace=False)

  def build(split_ratio, host_offload=False):
    # host_offload=False by default: this bench quantifies the LEGACY
    # host-phase route and the prefetch overlap; the offloaded config
    # is measured separately below (and the fused-step variant by
    # bench_fused_spill.py)
    ds = Dataset(edge_dir='out')
    ds.init_graph(edge_index=np.stack([src, dst]), num_nodes=n)
    ds.init_node_features(feats, split_ratio=split_ratio,
                          sort_func=sort_by_in_degree,
                          host_offload=host_offload)
    ds.init_node_labels(labels)
    return ds

  def run(ds, prefetch_depth, count_cold=False):
    loader = NeighborLoader(ds, fanout, input_nodes=train_idx,
                            batch_size=args.batch_size, shuffle=True,
                            drop_last=True, seed=0,
                            prefetch_depth=prefetch_depth)
    model = GraphSAGE(hidden_features=args.hidden, out_features=16,
                      num_layers=len(fanout))
    tx = optax.adam(1e-3)
    feat = ds.get_node_feature()
    cold_rows = total_rows = 0
    if count_cold:
      orig = feat.gather_cold_host

      def counting(rows):
        nonlocal cold_rows
        cold_rows += int(rows.shape[0])
        return orig(rows)
      feat.gather_cold_host = counting

    it = iter(loader)
    b0 = next(it)
    params = model.init(jax.random.key(0), b0)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, batch):
      def loss_fn(p):
        logits = model.apply(p, batch)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch.y).mean()
      loss, g = jax.value_and_grad(loss_fn)(params)
      up, opt = tx.update(g, opt)
      return optax.apply_updates(params, up), opt, loss

    params, opt, loss = step(params, opt, b0)
    jax.block_until_ready(loss)
    steps = seeds = 0
    t0 = None
    for i, batch in enumerate(it):
      if i == args.warmup:
        jax.block_until_ready(loss)
        cold_rows = 0
        total_rows = 0
        t0 = time.time()
      params, opt, loss = step(params, opt, batch)
      if i >= args.warmup:
        steps += 1
        seeds += args.batch_size
        total_rows += int(np.asarray(batch.node_count))
      if steps >= args.steps:
        break
    jax.block_until_ready(loss)
    dt = time.time() - (t0 or time.time())
    return {'seeds_per_s': round(seeds / max(dt, 1e-9), 1),
            'steps': steps,
            'cold_rate': (round(cold_rows / max(total_rows, 1), 4)
                          if count_cold else None)}

  t_build = time.time()
  resident = run(build(1.0), 0)
  spill_ds = build(args.split_ratio)
  spill0 = run(spill_ds, 0, count_cold=True)
  spill2 = run(build(args.split_ratio), 2, count_cold=True)
  # offloaded route: pinned-host cold block served inside the jitted
  # collate (gather_mixed) — no host phase, prefetch irrelevant
  offload = run(build(args.split_ratio, host_offload=True), 0)

  ratio0 = spill0['seeds_per_s'] / max(resident['seeds_per_s'], 1e-9)
  ratio2 = spill2['seeds_per_s'] / max(resident['seeds_per_s'], 1e-9)
  ratio_off = offload['seeds_per_s'] / max(resident['seeds_per_s'],
                                           1e-9)
  table_gb = n * args.feat_dim * 4 / 2**30
  hot_gb = table_gb * args.split_ratio
  dev = jax.devices()[0]
  print(json.dumps({
      'metric': 'spill_train_seeds_per_sec',
      'value': max(spill0['seeds_per_s'], spill2['seeds_per_s'],
                   offload['seeds_per_s']),
      'unit': 'seeds/s',
      'vs_baseline': round(max(ratio0, ratio2, ratio_off), 4),
      'detail': {
          'table_gb': round(table_gb, 2), 'hot_gb': round(hot_gb, 2),
          'split_ratio': args.split_ratio,
          'resident': resident,
          'spill_prefetch0': spill0, 'spill_prefetch2': spill2,
          'spill_offload': offload,
          'ratio_prefetch0': round(ratio0, 4),
          'ratio_prefetch2': round(ratio2, 4),
          'ratio_offload': round(ratio_off, 4),
          'recommended_prefetch_depth': 2 if ratio2 > ratio0 else 0,
          'wall_s': round(time.time() - t_build, 1),
          'backend': dev.platform},
  }))


if __name__ == '__main__':
  main()
