"""GraphSAGE / GAT / GCN stacks over Batch pytrees.

Reference workloads: examples/train_sage_ogbn_products.py (supervised
SAGE), examples/graph_sage_unsup_ppi.py (unsupervised link-pred SAGE).
Hop-trimming (`trim_to_layer`, examples/train_sage_prod_with_trim.py) is
built in. With ``trim=True`` layer l only processes the edge slots of the
hops it still needs, a *static* slice thanks to ``edge_hop_offsets``. Where
the batch also carries ``node_hop_offsets`` (the producer's promise that
labels are hop-compact) and only the seed rows are asked for, layer l also
computes output rows only for the nodes a later layer reads: the static
prefix within ``num_layers - 1 - l`` hops of a seed, which cuts the
aggregation target and the layer's matmuls (GCN's and GAT's input
projection stays over every row, since children are read from it). Both
slices are static, so trimming costs no recompilation. Where the batch
carries ``hop_fanouts`` (the producer's promise that a hop's edge slots
are groups of ``K_h`` adjacent slots with one parent each,
ops/pipeline.py::hop_fanouts), each ``SAGEConv`` layer is handed the
groups of the hops it keeps and sums a parent's children by a reshape
and a masked reduce (models/conv.py::grouped_aggregate); a batch without
it, and the GCN and GAT stacks, aggregate over segments as before. On
the benchmark's cells (3 layers, fanout 15,10,5, 1024 seeds a chip) the
node trim took the model's device time a step from 111.2 ms to 51.7 ms
and a step from 217.1 ms to 156.2 ms on one chip (my chip runs, PR 26),
and the grouped reduce took the model from 51.7 ms to 21.9 ms and the
step from 102.9 ms to 73.5 ms (my chip runs, PR 30); the driver's
numbers are PERF_LEDGER.jsonl's lines of those PRs, the split is in
PERF.md, section 5.
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


def _hops_kept(num_layers: int, i: int, num_hops: int) -> int:
  # layer i still feeds num_layers-1-i later propagations, so hop h is
  # useful iff h <= num_layers - i (clamped to the sampled hops);
  # later-hop edges feed representations no later layer reads
  return max(min(num_hops, num_layers - i), 1)


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
    rows = self.layer_rows(batch, return_all)
    groups = self.layer_groups(batch)
    for i in range(self.num_layers):
      dim = (self.hidden_features if i < self.num_layers - 1
             else self.out_features)
      if self.trim and offsets is not None:
        end = offsets[_hops_kept(self.num_layers, i, num_hops)]
        r, c, m = row[:end], col[:end], mask[:end]
      else:
        r, c, m = row, col, mask
      # the groups only where the promise is read: the other
      # convolutions' calls, and so their programs, stay as they were
      kw = {'groups': groups[i]} if groups[i] else {}
      # one scope a layer, its activation included, so that a device
      # trace tells the layers apart (obs/device.py reads the labels)
      with jax.named_scope(f'conv{i}'):
        x = _CONVS[self.conv](dim, i)(x, r, c, m, num_out=rows[i], **kw)
        if i < self.num_layers - 1:
          x = nn.relu(x)
          if self.dropout > 0:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
    if return_all:
      return x
    return x[:batch.batch_size]

  @nn.nowrap
  def layer_rows(self, batch: Batch, return_all: bool = False) -> tuple:
    """Output rows each layer computes, static ints. Layer i's output is
    read by num_layers-1-i later propagations, so only for the nodes
    within that many hops of a seed: ``node_hop_offsets`` makes them a
    prefix. Every row where the batch does not carry both offset tuples,
    ``trim`` is off or all rows are asked for."""
    offsets, node_offsets = batch.edge_hop_offsets, batch.node_hop_offsets
    if not (self.trim and offsets and node_offsets) or return_all:
      return (batch.x.shape[0],) * self.num_layers
    num_hops = len(offsets) - 1
    return tuple(node_offsets[min(num_hops, self.num_layers - 1 - i)]
                 for i in range(self.num_layers))

  @nn.nowrap
  def layer_groups(self, batch: Batch) -> tuple:
    """For each layer the static ``(offset, S_h, K_h)`` triples of the
    hops whose edge slots it reads: ``S_h`` groups of ``K_h`` adjacent
    slots from ``offset`` on, one parent a group (``Batch.hop_fanouts``).
    ``()`` for a layer that aggregates over segments: a batch without
    the promise or without ``edge_hop_offsets``, a stack whose
    convolution does not read groups. The promise holds whatever rows
    are asked for, so ``return_all`` does not enter."""
    offsets, widths = batch.edge_hop_offsets, batch.hop_fanouts
    if not (offsets and widths) or self.conv != 'sage':
      return ((),) * self.num_layers
    num_hops = len(offsets) - 1
    if len(widths) != num_hops or any(
        k and (offsets[h + 1] - offsets[h]) % k
        for h, k in enumerate(widths)):
      raise ValueError(f'hop_fanouts {widths} does not divide the hop '
                       f'blocks of edge_hop_offsets {offsets}')
    hops = tuple((offsets[h], (offsets[h + 1] - offsets[h]) // k, k)
                 for h, k in enumerate(widths) if k)
    ends = [offsets[_hops_kept(self.num_layers, i, num_hops)]
            if self.trim else offsets[-1] for i in range(self.num_layers)]
    return tuple(tuple(g for g in hops if g[0] < end) for end in ends)

  def embed(self, batch: Batch, train: bool = False) -> jax.Array:
    """Embeddings for ALL sampled nodes (link/unsupervised tasks index
    these by edge_label_index / src_index / dst_*_index, which range over
    every seed endpoint, not just the first batch_size labels). Every
    row at every layer: no node trim. The endpoints' labels are the
    first ``seed count`` ones, so a producer that sets ``batch_size`` to
    the seed count (the fused link step, parallel/train.py: ``4B`` for
    ``B`` pairs) reads them off ``__call__`` and keeps the trim; the two
    give one loss (tests/test_link_step.py)."""
    return self.__call__(batch, train=train, return_all=True)
