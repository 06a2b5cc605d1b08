"""Bipartite unsupervised SAGE — the reference's
examples/hetero/bipartite_sage_unsup.py (Taobao): user<->item link
prediction with a sparsified item<->item co-occurrence relation, hetero
LinkNeighborLoader over ('user','to','item') seed edges, learnable id
embeddings (the nodes have no features), two SAGE encoders and an MLP
decoder (``glt_tpu.models.BipartiteSAGE``), BCE, ROC-AUC eval. The
loader path on a toy graph; the same model through the fused typed step
at Taobao's scale is the benchmark's ``bisage-taobao-c1.fused``.

Synthetic stand-in (no downloads): users have latent group preferences,
items belong to groups, so observed links are predictable from graph
structure. item<->item edges connect items co-purchased by >= 3 users —
the same co-occurrence construction the reference computes from the
user-item matrix (``A^T A >= 3``).
"""
import argparse
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), '..'))
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), '..', '..'))

import common  # noqa: F401

import collections

import jax
import numpy as np
import optax

from glt_tpu.data import Dataset
from glt_tpu.loader import LinkNeighborLoader
from glt_tpu.models import BipartiteSAGE
from glt_tpu.sampler import NegativeSampling
from glt_tpu.typing import reverse_edge_type


def synthetic_taobao(num_users=600, num_items=300, num_groups=6,
                     links_per_user=8, seed=0):
  rng = np.random.default_rng(seed)
  item_group = rng.integers(0, num_groups, num_items)
  user_pref = rng.integers(0, num_groups, num_users)
  src, dst = [], []
  for u in range(num_users):
    own = np.nonzero(item_group == user_pref[u])[0]
    picks = rng.choice(own, min(links_per_user, own.shape[0]),
                       replace=False)
    src += [u] * picks.shape[0]
    dst += picks.tolist()
  ui = np.stack([np.array(src), np.array(dst)])
  # item<->item co-occurrence (>= 3 shared users), the reference's comat
  per_user = collections.defaultdict(list)
  for u, i in zip(ui[0], ui[1]):
    per_user[u].append(i)
  pair_count = collections.Counter()
  for items in per_user.values():
    for a in items:
      for b in items:
        if a != b:
          pair_count[(a, b)] += 1
  ii = np.array([[a, b] for (a, b), c in pair_count.items()
                 if c >= 3]).T
  if ii.size == 0:
    ii = np.zeros((2, 0), np.int64)
  return ui, ii, num_users, num_items


def roc_auc(y, s):
  order = np.argsort(s)
  ranks = np.empty(len(s))
  ranks[order] = np.arange(1, len(s) + 1)
  pos = y > 0.5
  np_, nn = pos.sum(), (~pos).sum()
  if np_ == 0 or nn == 0:
    return 0.5
  return (ranks[pos].sum() - np_ * (np_ + 1) / 2) / (np_ * nn)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=3)
  ap.add_argument('--batch-size', type=int, default=64)
  ap.add_argument('--users', type=int, default=600)
  args = ap.parse_args()

  ui, ii, nu, ni = synthetic_taobao(num_users=args.users,
                                    num_items=args.users // 2)
  u2i = ('user', 'to', 'item')
  i2u = ('item', 'rev_to', 'user')
  i2i = ('item', 'to', 'item')
  # 80/20 link split (RandomLinkSplit equivalent)
  rng = np.random.default_rng(1)
  perm = rng.permutation(ui.shape[1])
  n_test = ui.shape[1] // 5
  test_edges = ui[:, perm[:n_test]]
  train_edges = ui[:, perm[n_test:]]

  ds = Dataset(edge_dir='out')
  ds.init_graph(
      edge_index={u2i: train_edges, i2u: train_edges[::-1].copy(),
                  i2i: ii},
      num_nodes={'user': nu, 'item': ni})
  # no node features: a node is its id, read through the model's own
  # embedding tables (torch's Embedding(num_users, 64), (num_items, 64))

  loader = LinkNeighborLoader(
      ds, [8, 4], edge_label_index=(u2i, train_edges),
      batch_size=args.batch_size, shuffle=True, seed=0,
      neg_sampling=NegativeSampling('binary', amount=1))

  # message-flow keys: items into users, items into items
  model = BipartiteSAGE(num_nodes={'user': nu, 'item': ni},
                        item_user=reverse_edge_type(u2i),
                        item_item=reverse_edge_type(i2i),
                        hidden_features=64, out_features=64)
  b0 = next(iter(loader))
  params = model.init(jax.random.key(0), b0)
  tx = optax.adam(3e-3)   # dense: the tables are parameters like the rest
  opt = tx.init(params)

  @jax.jit
  def step(params, opt, batch):
    def loss_fn(p):
      logit = model.apply(p, batch)
      lab = batch.metadata['edge_label']
      return optax.sigmoid_binary_cross_entropy(logit, lab).mean()
    loss, g = jax.value_and_grad(loss_fn)(params)
    up, opt = tx.update(g, opt)
    return optax.apply_updates(params, up), opt, loss

  score = jax.jit(model.apply)

  def clean_meta(batch):
    meta = {k: v for k, v in (batch.metadata or {}).items()
            if k in ('edge_label_index', 'edge_label')}
    return batch.replace(metadata=meta)

  eval_loader = LinkNeighborLoader(
      ds, [8, 4], edge_label_index=(u2i, test_edges),
      batch_size=args.batch_size, seed=3,
      neg_sampling=NegativeSampling('binary', amount=1))

  for epoch in range(args.epochs):
    for batch in loader:
      params, opt, loss = step(params, opt, clean_meta(batch))
    ys, ss = [], []
    for batch in eval_loader:
      b = clean_meta(batch)
      ss.append(np.asarray(score(params, b)))
      ys.append(np.asarray(batch.metadata['edge_label']))
    auc = roc_auc(np.concatenate(ys), np.concatenate(ss))
    print(f'epoch {epoch}: loss={float(loss):.4f} test_auc={auc:.4f}')


if __name__ == '__main__':
  main()
