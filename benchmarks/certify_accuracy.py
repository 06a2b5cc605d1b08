"""Accuracy certification: reference-equivalent learning, skeptic-proof.

The reference certifies learning with ogbn-products test accuracy
(examples/train_sage_ogbn_products.py:16, ~0.787). Real datasets are not
downloadable in this environment, so this harness certifies the SAME
capability — multi-hop neighborhood aggregation through the sampled
pipeline — with a synthetic protocol designed to admit no shortcut:

  * labels are a fixed random linear readout of each node's MEAN 2-HOP
    NEIGHBOR FEATURES ONLY (label_i = argmax W . (A_mean^2 f)_i). Own
    features and 1-hop aggregates carry (asymptotically) no label
    signal, so
      - a feature-only linear probe must sit at ~chance,
      - a 1-layer GNN (sees f_i and (A f)_i) must sit at ~chance,
      - a 2-layer GNN can only climb by actually aggregating the
        sampled 2-hop frontier — the capability under test.
  * >= 3 seeds, mean +/- std reported per model family.
  * per-epoch accuracy curve committed for the 2-layer model.

Writes benchmarks/results/certify_accuracy.json (the committed
artifact) and prints one JSON summary line.

Run (CPU is fine; accuracy is backend-independent):
  GLT_BENCH_PLATFORM=cpu python benchmarks/certify_accuracy.py
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root -> glt_tpu

import numpy as np


def mean_aggregate(src, dst, feats, num_nodes, chunk=2_000_000):
  """(A_mean f)_i = mean of feats[dst] over out-edges of i, chunked."""
  acc = np.zeros((num_nodes, feats.shape[1]), np.float32)
  deg = np.zeros(num_nodes, np.float32)
  for lo in range(0, src.shape[0], chunk):
    s, d = src[lo:lo + chunk], dst[lo:lo + chunk]
    np.add.at(acc, s, feats[d])
    np.add.at(deg, s, 1.0)
  return acc / np.maximum(deg, 1.0)[:, None]


def run_family(ds, train_idx, test_idx, fanout, hidden, n_classes,
               batch_size, epochs, seed, eval_batches, curve=False):
  """Train one GraphSAGE through the sampled pipeline; returns
  (final_test_acc, per_epoch_accs or None)."""
  import jax
  import jax.numpy as jnp
  import optax
  from glt_tpu.loader import NeighborLoader
  from glt_tpu.models import GraphSAGE

  loader = NeighborLoader(ds, fanout, input_nodes=train_idx,
                          batch_size=batch_size, shuffle=True,
                          drop_last=True, seed=seed)
  model = GraphSAGE(hidden_features=hidden, out_features=n_classes,
                    num_layers=len(fanout))
  b0 = next(iter(loader))
  params = model.init(jax.random.key(seed), b0)
  tx = optax.adam(1e-3)
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

  @jax.jit
  def predict(params, batch):
    return jnp.argmax(model.apply(params, batch), -1)

  def evaluate():
    ev = NeighborLoader(ds, fanout, input_nodes=test_idx,
                        batch_size=batch_size, shuffle=False,
                        drop_last=False, seed=seed + 1)
    correct = total = 0
    for i, batch in enumerate(ev):
      if i >= eval_batches:
        break
      pred = np.asarray(predict(params, batch))
      yb = np.asarray(batch.y)
      nv = int((batch.metadata or {}).get('n_valid', yb.shape[0]))
      correct += int((pred[:nv] == yb[:nv]).sum())
      total += nv
    return correct / max(total, 1)

  accs = []
  for _ in range(epochs):
    for batch in loader:
      params, opt, _ = step(params, opt, batch)
    if curve:
      accs.append(round(evaluate(), 4))
  final = accs[-1] if curve else evaluate()
  return final, (accs if curve else None)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--num-nodes', type=int, default=200_000)
  ap.add_argument('--avg-degree', type=int, default=10)
  ap.add_argument('--feat-dim', type=int, default=64)
  ap.add_argument('--classes', type=int, default=16)
  ap.add_argument('--hidden', type=int, default=128)
  ap.add_argument('--batch-size', type=int, default=512)
  ap.add_argument('--epochs', type=int, default=8)
  ap.add_argument('--seeds', type=int, default=3)
  ap.add_argument('--train-frac', type=float, default=0.1)
  ap.add_argument('--eval-batches', type=int, default=20)
  ap.add_argument('--out', default=os.path.join(
      os.path.dirname(os.path.abspath(__file__)), 'results',
      'certify_accuracy.json'))
  args = ap.parse_args()

  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  from glt_tpu.data import Dataset

  rng = np.random.default_rng(0)
  n, e = args.num_nodes, args.num_nodes * args.avg_degree
  src = rng.integers(0, n, e, dtype=np.int64)
  dst = (rng.random(e) ** 2 * n).astype(np.int64) % n
  feats = rng.normal(size=(n, args.feat_dim)).astype(np.float32)
  # 2-hop-only label signal: A_mean(A_mean f)
  hop1 = mean_aggregate(src, dst, feats, n)
  hop2 = mean_aggregate(src, dst, hop1, n)
  w = rng.normal(size=(args.feat_dim, args.classes)).astype(np.float32)
  labels = np.argmax(hop2 @ w, 1).astype(np.int32)
  del hop1, hop2

  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([src, dst]), num_nodes=n)
  ds.init_node_features(feats)
  ds.init_node_labels(labels)
  perm = rng.permutation(n)
  train_idx = perm[: int(n * args.train_frac)]
  test_idx = perm[int(n * args.train_frac): int(n * args.train_frac)
                  + 20_000]

  # control 1: feature-only least-squares probe (fresh fit)
  sub = rng.choice(train_idx, min(20_000, train_idx.shape[0]),
                   replace=False)
  onehot = np.eye(args.classes, dtype=np.float32)[labels[sub]]
  w_fit, *_ = np.linalg.lstsq(feats[sub], onehot, rcond=None)
  probe_acc = float(
      (np.argmax(feats[test_idx] @ w_fit, 1) == labels[test_idx]).mean())

  chance = 1.0 / args.classes
  t0 = time.time()
  one_hop, two_hop, curves = [], [], []
  for s in range(args.seeds):
    # control 2: 1-layer GNN — sees f and (A f); must stay ~chance
    acc1, _ = run_family(ds, train_idx, test_idx, [args.avg_degree],
                         args.hidden, args.classes, args.batch_size,
                         args.epochs, 100 + s, args.eval_batches)
    # under test: 2-layer GNN through the sampled pipeline
    acc2, curve = run_family(
        ds, train_idx, test_idx, [args.avg_degree, args.avg_degree],
        args.hidden, args.classes, args.batch_size, args.epochs,
        200 + s, args.eval_batches, curve=True)
    one_hop.append(round(acc1, 4))
    two_hop.append(round(acc2, 4))
    curves.append(curve)
    print(f'# seed {s}: 1-hop {acc1:.4f}  2-hop {acc2:.4f}  '
          f'curve {curve}', file=sys.stderr)

  result = {
      'metric': 'certify_accuracy_2hop',
      'value': round(float(np.mean(two_hop)), 4),
      'unit': 'accuracy',
      'vs_baseline': None,
      'detail': {
          'protocol': '2-hop-only labels; controls must sit at chance',
          'chance': round(chance, 4),
          'linear_probe_acc': round(probe_acc, 4),
          'one_hop_acc_mean': round(float(np.mean(one_hop)), 4),
          'one_hop_acc_std': round(float(np.std(one_hop)), 4),
          'one_hop_accs': one_hop,
          'two_hop_acc_mean': round(float(np.mean(two_hop)), 4),
          'two_hop_acc_std': round(float(np.std(two_hop)), 4),
          'two_hop_accs': two_hop,
          'two_hop_curves_per_epoch': curves,
          'seeds': args.seeds, 'epochs': args.epochs,
          'num_nodes': n, 'num_edges': e,
          'seconds': round(time.time() - t0, 1),
          'backend': jax.devices()[0].platform,
      },
  }
  os.makedirs(os.path.dirname(args.out), exist_ok=True)
  with open(args.out, 'w') as f:
    json.dump(result, f, indent=1)
  print(json.dumps(result))


if __name__ == '__main__':
  main()
