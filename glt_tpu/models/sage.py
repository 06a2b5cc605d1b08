"""GraphSAGE / GAT / GCN stacks over Batch pytrees.

Reference workloads: examples/train_sage_ogbn_products.py (supervised
SAGE), examples/graph_sage_unsup_ppi.py (unsupervised link-pred SAGE).
Hop-trimming (`trim_to_layer`, examples/train_sage_prod_with_trim.py) is
built in: with ``trim=True`` layer l only processes the edge slots of the
hops it still needs — a *static* slice thanks to edge_hop_offsets, so
trimming costs zero recompilation and shrinks every matmul.
"""
from __future__ import annotations


import flax.linen as nn
import jax
import jax.numpy as jnp

from ..loader.transform import Batch
from .conv import GATConv, GCNConv, SAGEConv

_CONVS = {
    'sage': lambda d, i: SAGEConv(d, name=f'conv{i}'),
    'gcn': lambda d, i: GCNConv(d, name=f'conv{i}'),
    'gat': lambda d, i: GATConv(d, heads=1, name=f'conv{i}'),
}


class GraphSAGE(nn.Module):
  """num_layers of conv + relu + dropout, then a classifier head read off
  the seed rows. Matches the reference example topology (3 layers, hidden
  256 for ogbn-products, train_sage_ogbn_products.py:111-120)."""
  hidden_features: int
  out_features: int
  num_layers: int = 3
  conv: str = 'sage'
  dropout: float = 0.0
  trim: bool = True

  @nn.compact
  def __call__(self, batch: Batch, train: bool = False,
               return_all: bool = False) -> jax.Array:
    x = batch.x
    row, col, mask = batch.row, batch.col, batch.edge_mask
    offsets = batch.edge_hop_offsets
    num_hops = len(offsets) - 1 if offsets else self.num_layers
    for i in range(self.num_layers):
      dim = (self.hidden_features if i < self.num_layers - 1
             else self.out_features)
      if self.trim and offsets is not None:
        # layer i still feeds num_layers-1-i later propagations, so hop
        # h is useful iff h <= num_layers - i (clamped to sampled hops);
        # later-hop edges feed representations no later layer reads
        keep = max(min(num_hops, self.num_layers - i), 1)
        end = offsets[keep]
        r, c, m = row[:end], col[:end], mask[:end]
      else:
        r, c, m = row, col, mask
      # one scope a layer, its activation included, so that a device
      # trace tells the layers apart (obs/device.py reads the labels)
      with jax.named_scope(f'conv{i}'):
        x = _CONVS[self.conv](dim, i)(x, r, c, m)
        if i < self.num_layers - 1:
          x = nn.relu(x)
          if self.dropout > 0:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
    if return_all:
      return x
    return x[:batch.batch_size]

  def embed(self, batch: Batch, train: bool = False) -> jax.Array:
    """Embeddings for ALL sampled nodes (link/unsupervised tasks index
    these by edge_label_index / src_index / dst_*_index, which range over
    every seed endpoint, not just the first batch_size labels)."""
    return self.__call__(batch, train=train, return_all=True)
