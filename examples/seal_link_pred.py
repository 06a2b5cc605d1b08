"""SEAL link prediction — real SEAL semantics, TPU-first.

Reference: examples/seal_link_pred.py (238 LoC): full-neighborhood
enclosing subgraphs via ``NeighborSampler([-1]*hops).subgraph``, target
link removed, DRNL node labels one-hot encoded as the only features, a
DGCNN (GCN stack -> sort-pool -> Conv1d -> MLP) trained with BCE, model
selection by validation ROC-AUC. The reference runs on Cora; this
environment has no dataset downloads, so the graph is a synthetic
ring-plus-chords graph whose link structure is learnable from topology
alone.

TPU design: enclosing subgraphs are padded static [N_cap]-node graphs,
DRNL is a jitted edge-parallel BFS (``glt_tpu.ops.drnl``), and the DGCNN
forward is vmapped over the batch so XLA fuses the whole batch into
dense MXU matmuls.

This script is the loader path: ``NeighborSampler.subgraph`` a link at a
time (two full-neighbourhood hops, edge slots with edge ids, the one-set
``ops/subgraph.py::induced_subgraph``, exact while ``max_degree`` bounds
every member's row), the labels fetched to the host between extraction
and model. SEAL also runs through the fused step: ``SPMDSageTrainStep``
given a ``NegativeSampling`` and an ``ops.subgraph.EncloseSpec`` draws the
negatives, takes one hop, extracts every link's enclosing subgraph as a
dense block (exact on a graph with hubs inside counted budgets), labels
it and trains DGCNN over the whole batch of graphs in one device program
a step; the benchmark's cell ``seal-papers100m-c1.fused`` runs SEAL_OGB's
ogbl-citation2 recipe that way (``PERF.md`` sections 4 and 5,
``tests/test_seal_step.py``). The batched extraction returns blocks, not
edge slots with edge ids, and one hop of fringe, so this script's
two-hop, edge-slot extraction stays on the one-set op.
"""
import argparse
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), '..'))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: F401  (GLT_PLATFORM handling)

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from glt_tpu.data import Dataset
from glt_tpu.models.dgcnn import DGCNN
from glt_tpu.ops.drnl import drnl_node_labeling
from glt_tpu.sampler import NeighborSampler

MAX_Z = 12  # DRNL vocabulary clip (2-hop labels are small)


def ring_chord_graph(n=200, chords=60, seed=0):
  """Undirected ring + random chords; returns directed-both-ways COO."""
  rng = np.random.default_rng(seed)
  ring = {(min(i, (i + 1) % n), max(i, (i + 1) % n)) for i in range(n)}
  while len(ring) < n + chords:
    a, b = rng.integers(0, n, 2)
    if a != b:
      ring.add((min(int(a), int(b)), max(int(a), int(b))))
  und = sorted(ring)
  return und


def link_split(und_edges, rng, num_val=0.05, num_test=0.10, n=200):
  """RandomLinkSplit equivalent: held-out positives + sampled negatives."""
  und = list(und_edges)
  rng.shuffle(und)
  n_test = int(len(und) * num_test)
  n_val = int(len(und) * num_val)
  test_pos, val_pos = und[:n_test], und[n_test:n_test + n_val]
  train_pos = und[n_test + n_val:]
  edge_set = set(und_edges)
  negs = []
  while len(negs) < n_test + n_val + len(train_pos):
    a, b = int(rng.integers(0, n)), int(rng.integers(0, n))
    if a != b and (min(a, b), max(a, b)) not in edge_set:
      negs.append((a, b))
  test_neg = negs[:n_test]
  val_neg = negs[n_test:n_test + n_val]
  train_neg = negs[n_test + n_val:]
  return train_pos, train_neg, val_pos, val_neg, test_pos, test_neg


def build_train_dataset(train_pos, n):
  both = np.array(train_pos + [(b, a) for a, b in train_pos], np.int64)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=both.T.copy(), num_nodes=n)
  return ds


def extract_enclosing(sampler, links, y, drnl_fn, n_cap):
  """Enclosing subgraph + DRNL features per candidate link (reference
  SEALDataset.extract_enclosing_subgraphs)."""
  out = []
  for src, dst in links:
    sub = sampler.subgraph(np.array([src, dst], np.int64),
                           node_capacity=n_cap)
    # target-link removal + DRNL run jitted on device
    z, rows, cols, emask = drnl_fn(sub.rows, sub.cols, sub.edge_mask,
                                   sub.node_count)
    out.append((np.asarray(z), np.asarray(rows), np.asarray(cols),
                np.asarray(emask),
                np.arange(n_cap) < int(sub.node_count), y))
  return out


def collate(items):
  z = np.stack([i[0] for i in items])
  rows = np.stack([i[1] for i in items])
  cols = np.stack([i[2] for i in items])
  emask = np.stack([i[3] for i in items])
  nmask = np.stack([i[4] for i in items])
  y = np.array([i[5] for i in items], np.float32)
  x = np.eye(MAX_Z + 1, dtype=np.float32)[z]  # one-hot DRNL features
  return x, rows, cols, emask, nmask, y


def roc_auc(y_true, scores):
  """Rank-statistic ROC-AUC (no sklearn dependency)."""
  order = np.argsort(scores)
  ranks = np.empty_like(order, dtype=np.float64)
  ranks[order] = np.arange(1, len(scores) + 1)
  # average ranks over ties
  for s in np.unique(scores):
    m = scores == s
    ranks[m] = ranks[m].mean()
  pos = y_true > 0.5
  n_pos, n_neg = pos.sum(), (~pos).sum()
  if n_pos == 0 or n_neg == 0:
    return 0.5
  return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=10)
  ap.add_argument('--nodes', type=int, default=200)
  ap.add_argument('--hops', type=int, default=2)
  ap.add_argument('--batch-size', type=int, default=32)
  args = ap.parse_args()

  rng = np.random.default_rng(0)
  und = ring_chord_graph(n=args.nodes, seed=0)
  train_pos, train_neg, val_pos, val_neg, test_pos, test_neg = \
      link_split(und, rng, n=args.nodes)
  ds = build_train_dataset(train_pos, args.nodes)
  g = ds.get_graph()

  sampler = NeighborSampler(g, [-1] * args.hops, seed=0)
  from glt_tpu.ops.pipeline import sample_budget
  # 2 seeds expanded through the resolved full-neighborhood windows
  n_cap = sample_budget(2, sampler.num_neighbors)

  @jax.jit
  def drnl_fn(rows, cols, emask, node_count):
    # remove the target link (labels 0 and 1 by first-occurrence order)
    keep = emask & ~(((rows == 0) & (cols == 1)) |
                     ((rows == 1) & (cols == 0)))
    z = drnl_node_labeling(rows, cols, keep, n_cap,
                           jnp.int32(0), jnp.int32(1), MAX_Z)
    z = jnp.where(jnp.arange(n_cap) < node_count, z, 0)
    return z, rows, cols, keep

  print('extracting enclosing subgraphs...')
  splits = {}
  for name, pos, neg in [('train', train_pos, train_neg),
                         ('val', val_pos, val_neg),
                         ('test', test_pos, test_neg)]:
    items = (extract_enclosing(sampler, pos, 1.0, drnl_fn, n_cap)
             + extract_enclosing(sampler, neg, 0.0, drnl_fn, n_cap))
    splits[name] = collate(items)
    print(f'  {name}: {len(items)} subgraphs')

  # sort-pool k = 60th percentile of subgraph sizes (reference k=0.6)
  sizes = sorted(splits['train'][4].sum(axis=1).tolist())
  k = max(10, int(sizes[int(np.ceil(0.6 * len(sizes))) - 1]))
  model = DGCNN(hidden=32, num_layers=3, k=k)

  fwd = jax.vmap(model.apply, in_axes=(None, 0, 0, 0, 0, 0))
  x0 = jax.tree.map(jnp.asarray, splits['train'][:5])
  params = model.init(jax.random.key(0), *[a[0] for a in x0])
  tx = optax.adam(1e-3)
  opt = tx.init(params)

  @jax.jit
  def train_step(params, opt, batch):
    x, rows, cols, emask, nmask, y = batch
    def loss_fn(p):
      logits = fwd(p, x, rows, cols, emask, nmask)
      return optax.sigmoid_binary_cross_entropy(logits, y).mean()
    loss, grads = jax.value_and_grad(loss_fn)(params)
    ups, opt = tx.update(grads, opt)
    return optax.apply_updates(params, ups), opt, loss

  @jax.jit
  def predict(params, batch):
    x, rows, cols, emask, nmask, _ = batch
    return fwd(params, x, rows, cols, emask, nmask)

  def evaluate(split):
    x, rows, cols, emask, nmask, y = splits[split]
    scores = np.asarray(predict(params,
                                tuple(map(jnp.asarray, splits[split]))))
    return roc_auc(y, scores)

  x, rows, cols, emask, nmask, y = splits['train']
  n_train = y.shape[0]
  bs = args.batch_size
  best_val = test_auc = 0.0
  for epoch in range(1, args.epochs + 1):
    perm = rng.permutation(n_train)
    losses = []
    for lo in range(0, n_train - bs + 1, bs):
      sel = perm[lo:lo + bs]
      batch = tuple(jnp.asarray(a[sel]) for a in
                    (x, rows, cols, emask, nmask, y))
      params, opt, loss = train_step(params, opt, batch)
      losses.append(float(loss))
    val_auc = evaluate('val')
    if val_auc > best_val:
      best_val, test_auc = val_auc, evaluate('test')
    print(f'Epoch: {epoch:02d}, Loss: {np.mean(losses):.4f}, '
          f'Val: {val_auc:.4f}, Test: {test_auc:.4f}')
  return test_auc


if __name__ == '__main__':
  main()
