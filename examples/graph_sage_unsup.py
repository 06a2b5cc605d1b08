"""Unsupervised GraphSAGE link prediction with negative sampling — the
reference's examples/graph_sage_unsup_ppi.py workload:
LinkNeighborLoader + binary NegativeSampling + dot-product BCE. With
``--fused`` the same recipe runs as one device program a step
(``SPMDSageTrainStep`` given the ``NegativeSampling``: negatives, hop
loop, gather, model, loss and Adam in one dispatch), the path the
benchmark's cell ``link-papers100m-c1.fused`` measures."""
import argparse
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), '..'))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from glt_tpu.loader import LinkNeighborLoader
from glt_tpu.models import GraphSAGE
from glt_tpu.sampler import NegativeSampling

from common import synthetic_products


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--epochs', type=int, default=3)
  ap.add_argument('--batch-size', type=int, default=128)
  ap.add_argument('--fused', action='store_true',
                  help='one device program a step (SPMDSageTrainStep)')
  args = ap.parse_args()

  ds, _ = synthetic_products(num_nodes=3_000)
  if args.fused:
    return fused(ds, args)
  loader = LinkNeighborLoader(
      ds, [8, 4], batch_size=args.batch_size, shuffle=True, seed=0,
      neg_sampling=NegativeSampling('binary', amount=1))
  model = GraphSAGE(hidden_features=128, out_features=64, num_layers=2)
  b0 = next(iter(loader))
  params = model.init(jax.random.key(0), b0)
  tx = optax.adam(3e-3)
  opt = tx.init(params)

  @jax.jit
  def step(params, opt, batch):
    def loss_fn(p):
      emb = model.apply(p, batch, method=GraphSAGE.embed)
      eli = batch.metadata['edge_label_index']
      lab = batch.metadata['edge_label']
      logit = (emb[eli[0]] * emb[eli[1]]).sum(-1)
      return optax.sigmoid_binary_cross_entropy(logit, lab).mean()
    loss, g = jax.value_and_grad(loss_fn)(params)
    up, opt = tx.update(g, opt)
    return optax.apply_updates(params, up), opt, loss

  for epoch in range(args.epochs):
    for batch in loader:
      meta = dict(batch.metadata)
      meta['n_valid'] = jnp.asarray(meta['n_valid'])
      params, opt, loss = step(params, opt, batch.replace(metadata=meta))
    print(f'epoch {epoch}: loss={float(loss):.4f}')


def fused(ds, args):
  """The loader loop above as ``SPMDSageTrainStep``'s per-batch entry on
  one device: the step is handed ``[B, 2]`` positive edges and a key."""
  from glt_tpu.loader.link_loader import get_edge_label_index
  from glt_tpu.parallel import (ShardedFeature, SPMDSageTrainStep,
                                make_mesh)
  mesh = make_mesh(1)
  model = GraphSAGE(hidden_features=128, out_features=64, num_layers=2)
  tx = optax.adam(3e-3)
  table = np.asarray(ds.get_node_feature()[np.arange(
      ds.get_graph().num_nodes)])
  step = SPMDSageTrainStep(
      mesh, model, tx, ds.get_graph(), ShardedFeature(table, mesh), None,
      [8, 4], args.batch_size,
      neg_sampling=NegativeSampling('binary', amount=1, strict=True))
  params = step.init_params(jax.random.key(0))
  opt = tx.init(params)
  pairs = get_edge_label_index(ds)[1].T.astype(np.int32)
  rng, key = np.random.default_rng(0), jax.random.key(1)
  for epoch in range(args.epochs):
    order = rng.permutation(pairs.shape[0])
    for at in range(0, order.shape[0], args.batch_size):
      batch = pairs[order[at:at + args.batch_size]]
      n_valid = batch.shape[0]   # the last batch is padded with its first
      batch = np.resize(batch, (args.batch_size, 2))
      key, sub = jax.random.split(key)
      params, opt, loss = step(params, opt, batch, [n_valid],
                               jax.random.split(sub, 1))
    print(f'epoch {epoch}: loss={float(loss[0]):.4f} '
          f'fused=1 seed_unique={int(step.link_counters()["seed_unique"][0])}')


if __name__ == '__main__':
  main()
