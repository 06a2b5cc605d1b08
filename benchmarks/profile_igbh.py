"""IGBH bottleneck profile: where do the seconds per step go?

VERDICT r3 next #5 asks for the 65 seeds/s (r3 54M-edge run) to be
EXPLAINED by a profile. The fused DistHeteroTrainStep is one SPMD
program, so this times its separable sub-programs at identical shapes:

  * sample   — DistHeteroNeighborSampler.sample_from_nodes alone
               (hetero hop loops + dedup + collective exchanges);
  * eval     — eval_step: sample + feature all_to_all + batch assembly
               + model FORWARD (no backward/optimizer);
  * train    — the full fused step (adds backward + grad pmean + adam).

Decomposition: assembly+forward = eval - sample;
backward+optimizer = train - eval. (A dummy-batch model-only timing
overestimates badly — the fused path trims per-hop — so the model cost
is bounded between the two differences, not measured standalone.)
Every stage is synced to the host each iteration — eval_step blocks on
a scalar transfer internally, so the other stages must block too or
the differences absorb the dispatch-pipelining gap and bwd_opt can go
negative.

Prints one JSON line; the seeds/s of the fused step should reproduce
the r3 number at --papers 4000000 and the stage shares say what to fix.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'examples', 'igbh'))

import numpy as np


def timed(fn, iters, warmup, sync):
  import jax
  for _ in range(warmup):
    jax.block_until_ready(sync(fn()))
  t0 = time.time()
  for _ in range(iters):
    out = fn()
    jax.block_until_ready(sync(out))
  return (time.time() - t0) / iters * 1e3, out


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--num-devices', type=int, default=8)
  ap.add_argument('--papers', type=int, default=1_000_000)
  ap.add_argument('--batch-size', type=int, default=64)
  ap.add_argument('--fanout', default='10,5')
  ap.add_argument('--hidden', type=int, default=128)
  ap.add_argument('--conv', default='rsage')
  ap.add_argument('--iters', type=int, default=8)
  ap.add_argument('--warmup', type=int, default=2)
  ap.add_argument('--cpu-mesh', action=argparse.BooleanOptionalAction,
                  default=True)
  ap.add_argument('--trace', default=None)
  ap.add_argument('--data-root', default=None,
                  help='reuse an existing synthesized tree')
  ap.add_argument('--part-root', default=None,
                  help='reuse an existing partition dir')
  args = ap.parse_args()

  if args.cpu_mesh:
    os.environ['XLA_FLAGS'] = (
        os.environ.get('XLA_FLAGS', '') +
        f' --xla_force_host_platform_device_count={args.num_devices}')
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  if args.cpu_mesh:
    force_backend('cpu')
  configure_compile_cache()
  import jax.numpy as jnp
  import optax
  from glt_tpu.distributed import (
      DistDataset, DistFeature, DistHeteroGraph, DistHeteroTrainStep,
  )
  from glt_tpu.models import RGNN
  from glt_tpu.parallel import make_mesh
  from glt_tpu.partition import RandomPartitioner
  from glt_tpu.typing import reverse_edge_type
  from compress_graph import synthesize, compress
  from split_seeds import split_seeds
  from dist_train_rgnn import load_igbh_root

  root = args.data_root
  if root is not None and not os.path.exists(
      os.path.join(root, 'processed', 'meta.txt')):
    ap.error(f'--data-root {root} has no processed/meta.txt — refusing '
             'to silently re-synthesize into it')
  if root is None:
    root = tempfile.mkdtemp(prefix='igbh_prof_')
    print(f'synthesizing at {args.papers} papers...', file=sys.stderr)
    synthesize(root, args.papers)
    compress(root, layout='CSC', bf16=True, topology=False)
    split_seeds(root)
  counts, edges, feats, labels, train_idx, _ = load_igbh_root(root)
  num_classes = int(labels.max()) + 1
  fanout = [int(x) for x in args.fanout.split(',')]
  rev = {}
  for (s, r, d), ei in list(edges.items()):
    if s != d:
      rev[(d, f'rev_{r}', s)] = ei[::-1].copy()
  edges.update(rev)
  total_edges = sum(e.shape[1] for e in edges.values())

  part_root = args.part_root
  if part_root is not None:
    if not os.path.exists(os.path.join(part_root, 'META.json')):
      ap.error(f'--part-root {part_root} has no META.json — refusing '
               'to silently re-partition into it')
    from glt_tpu.partition.base import load_meta
    meta_parts = load_meta(part_root)['num_parts']
    if meta_parts != args.num_devices:
      ap.error(f'--part-root was partitioned with num_parts='
               f'{meta_parts} but --num-devices={args.num_devices}')
  else:
    part_root = tempfile.mkdtemp(prefix='igbh_prof_parts_')
    part_feats = {t: np.asarray(f, dtype=np.float32)
                  for t, f in feats.items()}
    RandomPartitioner(part_root, num_parts=args.num_devices,
                      num_nodes=dict(counts), edge_index=edges,
                      node_feat=part_feats).partition()
    del part_feats

  mesh = make_mesh(args.num_devices)
  dg = DistHeteroGraph.from_dataset_partitions(mesh, part_root)
  dss = [DistDataset().load(part_root, p)
         for p in range(args.num_devices)]
  dfeats = {t: DistFeature.from_dist_datasets(mesh, dss, ntype=t,
                                              dtype=jnp.bfloat16)
            for t in counts}
  model = RGNN(edge_types=[reverse_edge_type(e) for e in edges],
               hidden_features=args.hidden, out_features=num_classes,
               num_layers=len(fanout), conv=args.conv)
  tx = optax.adam(2e-3)
  step = DistHeteroTrainStep(
      dg, dfeats, model, tx, {'paper': labels},
      {e: fanout for e in edges},
      batch_size_per_device=args.batch_size, seed_type='paper', seed=0)
  params = step.init_params(jax.random.key(0))
  opt = tx.init(params)

  n_dev, bs = args.num_devices, args.batch_size
  rng = np.random.default_rng(0)
  seeds = train_idx[rng.integers(0, train_idx.shape[0],
                                 n_dev * bs)].reshape(n_dev, bs)
  nv = np.full(n_dev, bs)

  # --- stage: sampling only -------------------------------------------
  ms_sample, _ = timed(
      lambda: step.sampler.sample_from_nodes('paper', seeds, nv),
      args.iters, args.warmup,
      lambda o: jax.tree.leaves(o)[:1])

  # --- stage: eval step = sample + gather + assemble + model FORWARD --
  def eval_only():
    return step.eval_step(params, seeds, nv, jax.random.key(2))
  ms_eval, _ = timed(eval_only, args.iters, args.warmup, lambda o: o[0])

  # --- full fused train step ------------------------------------------
  state = {'p': params, 'o': opt}

  def full():
    p, o, loss = step(state['p'], state['o'], seeds, nv,
                      jax.random.key(1))
    state['p'], state['o'] = p, o
    return loss
  ms_train, _ = timed(full, args.iters, args.warmup, lambda o: o)

  if args.trace:
    with jax.profiler.trace(args.trace):
      for _ in range(3):
        loss = full()
      jax.block_until_ready(loss)
    print(f'# trace written to {args.trace}', file=sys.stderr)

  seeds_per_s = n_dev * bs / (ms_train / 1e3)
  assembly_fwd = ms_eval - ms_sample
  bwd_opt = ms_train - ms_eval
  print(json.dumps({
      'metric': 'igbh_step_breakdown',
      'value': round(seeds_per_s, 1),
      'unit': 'seeds/s',
      'vs_baseline': None,
      'detail': {
          'papers': int(counts['paper']), 'total_edges': total_edges,
          'batch_global': n_dev * bs,
          'ms_train_step': round(ms_train, 1),
          'ms_eval_step': round(ms_eval, 1),
          'ms_sample_only': round(ms_sample, 1),
          'ms_assembly_plus_forward': round(assembly_fwd, 1),
          'ms_backward_plus_optimizer': round(bwd_opt, 1),
          'share_sample': round(ms_sample / ms_train, 3),
          'share_assembly_fwd': round(assembly_fwd / ms_train, 3),
          'share_bwd_opt': round(bwd_opt / ms_train, 3),
          'backend': jax.devices()[0].platform},
  }))


if __name__ == '__main__':
  main()
