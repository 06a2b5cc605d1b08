"""Relational (hetero) GNNs: HeteroConv composition + RGNN stacks.

Reference workloads: examples/igbh/rgnn.py:22 (RGAT / RSAGE for the
MLPerf IGBH benchmark), examples/hetero/* (hetero SAGE variants). The
composition rule matches PyG's HeteroConv: one conv per edge type, then
per-destination-type aggregation of the relation outputs.

Batch contract: HeteroBatch edge keys (s, r, d) carry row = s-type child
labels, col = d-type parent labels (message-flow orientation).

A relation's convolution reads its children from the source type's rows
and its parents from the destination type's (``x_dst`` of models/conv.py):
a node type's rows are projected once per relation that reads them as
children, never as parents, and no ``[src || dst]`` view is stacked.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import flax.linen as nn
import jax

from ..loader.transform import HeteroBatch
from ..typing import EdgeType, NodeType, as_str
from . import plan
from .conv import GATConv, SAGEConv


class HeteroConvLayer(nn.Module):
  """Applies a per-edge-type conv and sums relation outputs per dst type.

  ``concat`` (attention only): the heads are ``out_features // heads``
  wide and concatenated (PyG's ``GATConv(in, out // heads, heads)``, the
  reference's rgnn.py); else each head is ``out_features`` wide and the
  heads are averaged. ``remat``: a relation's convolution is computed
  again in the backward pass instead of keeping its projected rows.
  """
  edge_types: Sequence[EdgeType]
  out_features: int
  conv: str = 'sage'       # 'sage' | 'gat'
  heads: int = 1
  concat: bool = False
  remat: bool = False

  def _make(self, etype):
    cls, width, kw = SAGEConv, self.out_features, {}
    if self.conv == 'gat':
      cls, kw = GATConv, dict(heads=self.heads, concat=self.concat)
      if self.concat:
        assert width % self.heads == 0, (width, self.heads)
        width //= self.heads
    if self.remat:   # num_out and groups are static: arguments 5 and
      cls = nn.remat(cls, static_argnums=(5, 7))   # 7, the module being 0
    return cls(width, name=f'conv_{as_str(etype)}', **kw)

  @nn.compact
  def __call__(self, x_dict: Dict[NodeType, jax.Array],
               row_dict, col_dict, mask_dict,
               num_out: Optional[Dict[NodeType, int]] = None,
               groups: Optional[Dict[EdgeType, tuple]] = None):
    """``num_out[t]``: output rows to compute for type ``t`` (static;
    ``None`` or a type left out: every row of ``x_dict[t]``).
    ``groups[e]``: static ``(offset, S, K)`` triples over relation
    ``e``'s edge slots as given here, the producer's promise that they
    are parent-major (models/conv.py); ``None`` or a relation left out:
    no promise, the segment path."""
    rows_of = lambda t: (num_out or {}).get(t, x_dict[t].shape[0])
    out: Dict[NodeType, jax.Array] = {}
    for etype in self.edge_types:
      if etype not in row_dict:
        continue
      src_t, _, dst_t = etype
      if src_t not in x_dict or dst_t not in x_dict:
        continue
      # bipartite message passing: children from the src type's rows,
      # parents (and the output rows) from the dst type's
      h = self._make(etype)(
          x_dict[src_t], row_dict[etype], col_dict[etype],
          mask_dict[etype], rows_of(dst_t),
          None if src_t == dst_t else x_dict[dst_t],
          (groups or {}).get(etype))
      out[dst_t] = out.get(dst_t, 0) + h
    # types with no incoming relation keep a transformed self-embedding
    for t, x in x_dict.items():
      if t not in out:
        out[t] = nn.Dense(self.out_features,
                          name=f'self_{t}')(x[:rows_of(t)])
    return out


class RGNN(nn.Module):
  """Relational GNN stack (reference examples/igbh/rgnn.py): 'rsage' or
  'rgat' layers over a HeteroBatch, classifier head on the seed type.

  The layers follow the typed models' one plan (models/plan.py): with
  ``edge_hop_offsets_dict`` (hetero NeighborLoader batches carry it)
  layer i only reads the edge slots of hops [0, num_hops - i) per edge
  type; with ``node_hop_offsets_dict`` it computes output rows only for
  the nodes a later layer reads; with ``hop_fanouts_dict`` (the promise
  that a relation's edge slots are parent-major) a relation's convolution
  reduces a parent's children over the fanout axis (models/conv.py), and
  without it over segments: the same mathematics in another order of
  additions.

  ``head``: every layer is ``hidden_features`` wide (attention heads
  concatenated, ``hidden_features // heads`` each) and a linear layer maps
  the seeds' rows to ``out_features``: IGB's own R-GAT and the MLPerf
  recipe. Without it the last layer is ``out_features`` wide and the
  attention heads are averaged.
  """
  edge_types: Sequence[EdgeType]
  hidden_features: int
  out_features: int
  num_layers: int = 2
  conv: str = 'rsage'      # 'rsage' | 'rgat'
  heads: int = 4
  dropout: float = 0.0
  trim: bool = True
  head: bool = False
  remat: bool = False

  def layer_plan(self, batch: HeteroBatch, return_all: bool = False):
    """Per layer ``(edge_ends, rows, groups)``: models/plan.py, the one
    plan every typed model reads."""
    return plan.layer_plan(batch, self.num_layers, self.trim, return_all)

  def layer_rows(self, batch: HeteroBatch, return_all: bool = False):
    """``[{type: output rows}]`` a layer, as the step's counter reads."""
    return plan.layer_rows(self.layer_plan(batch, return_all), batch)

  def layer_groups(self, batch: HeteroBatch):
    """``[{relation: groups}]`` a layer: the groups of adjacent edge
    slots a relation's convolution reduces over the fanout axis, 0 where
    it aggregates over segments. The step's counter reads it; the
    promise holds whatever rows are asked for."""
    return plan.layer_groups(self.layer_plan(batch), self.edge_types,
                             batch)

  @nn.compact
  def __call__(self, batch: HeteroBatch, train: bool = False,
               return_all: bool = False):
    conv_kind = 'gat' if self.conv == 'rgat' else 'sage'
    x_dict = dict(batch.x_dict)
    for i, (ends, rows, groups) in enumerate(
        self.layer_plan(batch, return_all)):
      last = i == self.num_layers - 1
      dim = (self.out_features if last and not self.head
             else self.hidden_features)
      cut = lambda d: plan.cut_edges(d, ends)
      x_dict = HeteroConvLayer(
          edge_types=list(self.edge_types), out_features=dim,
          conv=conv_kind, heads=self.heads, concat=self.head,
          remat=self.remat, name=f'layer{i}')(
              x_dict, cut(batch.row_dict), cut(batch.col_dict),
              cut(batch.edge_mask_dict), rows, groups)
      if not last or self.head:
        x_dict = {t: nn.relu(v) for t, v in x_dict.items()}
      if not last and self.dropout > 0:
        drop = nn.Dropout(self.dropout, deterministic=not train)
        x_dict = {t: drop(v) for t, v in x_dict.items()}
    if return_all:
      return x_dict
    seeds = x_dict[batch.input_type][:batch.batch_size]
    if self.head:
      return nn.Dense(self.out_features, name='head')(seeds)
    return seeds
