"""Batch structures and SamplerOutput -> Batch conversion.

Reference: graphlearn_torch/python/loader/transform.py:26-136 (to_data /
to_hetero_data building PyG Data/HeteroData). Torch-geometric is not a
TPU-side dependency, so the yielded object is a jax pytree (flax struct)
carrying the same fields PyG models read — x, edge_index(row/col), y,
batch, batch_size, num_sampled_nodes/edges — plus the padding masks that
make every shape static. ``to_torch_data`` converts to a real PyG Data
when torch_geometric is importable (CPU interop only).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import flax.struct
import jax
import jax.numpy as jnp

from ..sampler.base import HeteroSamplerOutput, SamplerOutput
from ..typing import EdgeType, NodeType


@flax.struct.dataclass
class Batch:
  """Homogeneous mini-batch, padded static shapes throughout."""
  x: Optional[jax.Array]            # [node_cap, D]
  row: jax.Array                    # [edge_cap] child labels
  col: jax.Array                    # [edge_cap] parent labels
  edge_mask: jax.Array              # [edge_cap]
  node: jax.Array                   # [node_cap] global node ids
  node_count: jax.Array
  y: Optional[jax.Array] = None     # [batch_size] seed labels
  edge_attr: Optional[jax.Array] = None
  edge: Optional[jax.Array] = None  # [edge_cap] edge ids
  num_sampled_nodes: Optional[jax.Array] = None
  num_sampled_edges: Optional[jax.Array] = None
  metadata: Optional[Dict[str, Any]] = None
  batch_size: int = flax.struct.field(pytree_node=False, default=0)
  edge_hop_offsets: Optional[tuple] = flax.struct.field(
      pytree_node=False, default=None)
  #: static; ``node_hop_offsets[h]`` leading node slots hold every node
  #: within h hops of a seed (ops.pipeline.node_hop_offsets). A promise
  #: of the producer that labels are hop-compact: models trim the nodes
  #: by it as they trim the edges by edge_hop_offsets. None: no promise.
  node_hop_offsets: Optional[tuple] = flax.struct.field(
      pytree_node=False, default=None)
  #: static; ``(K_0, K_1, ...)``: hop h's block of edge slots
  #: (``edge_hop_offsets``) is groups of ``K_h`` adjacent slots, ``col``
  #: is one value over a group (a group's parent is its first slot's
  #: ``col``: no new array crosses the step), and a label that heads a
  #: group with a live slot heads no other such group. A promise of the
  #: producer (ops.pipeline.hop_fanouts: SPMDSageTrainStep, and
  #: NeighborSampler on a one-type graph); models/conv.py::SAGEConv
  #: reads it and sums a parent's children with a reshape and a masked
  #: reduce where it would scatter-add every slot. None: no promise,
  #: ``col`` is taken as arbitrary.
  hop_fanouts: Optional[tuple] = flax.struct.field(
      pytree_node=False, default=None)

  @property
  def edge_index(self) -> jax.Array:
    return jnp.stack([self.row, self.col])

  @property
  def num_nodes(self) -> int:
    return self.node.shape[0]

  @property
  def batch(self) -> jax.Array:
    """Global ids of the seed nodes (first batch_size labels)."""
    return self.node[:self.batch_size]


@flax.struct.dataclass
class HeteroBatch:
  x_dict: Dict[NodeType, jax.Array]
  row_dict: Dict[EdgeType, jax.Array]
  col_dict: Dict[EdgeType, jax.Array]
  edge_mask_dict: Dict[EdgeType, jax.Array]
  node_dict: Dict[NodeType, jax.Array]
  node_count_dict: Dict[NodeType, jax.Array]
  y_dict: Optional[Dict[NodeType, jax.Array]] = None
  edge_attr_dict: Optional[Dict[EdgeType, jax.Array]] = None
  edge_dict: Optional[Dict[EdgeType, jax.Array]] = None
  num_sampled_nodes: Optional[Dict[NodeType, jax.Array]] = None
  num_sampled_edges: Optional[Dict[EdgeType, jax.Array]] = None
  metadata: Optional[Dict[str, Any]] = None
  input_type: Optional[NodeType] = flax.struct.field(
      pytree_node=False, default=None)
  batch_size: int = flax.struct.field(pytree_node=False, default=0)
  #: static per-etype hop offsets into the edge buffers (hierarchical
  #: per-layer trimming, reference trim_to_layer); Dict[etype, tuple]
  edge_hop_offsets_dict: Optional[Dict] = flax.struct.field(
      pytree_node=False, default=None)
  #: static per-type ``Dict[ntype, tuple]``: ``[h]`` leading node slots of
  #: a type hold every node of it within h hops of a seed. A promise of
  #: the producer that labels are hop-compact per type; where it is
  #: ``None`` models/rgnn.py computes every row.
  node_hop_offsets_dict: Optional[Dict] = flax.struct.field(
      pytree_node=False, default=None)
  #: static per-etype ``Dict[etype, ((offset, S, K), ...)]``, keyed like
  #: ``edge_hop_offsets_dict``: from ``offset`` on, a relation's edge
  #: slots are ``S`` groups of ``K`` adjacent slots with one value of
  #: ``col`` each, and a label heads at most one group with a live slot
  #: (ops/pipeline.py::hetero_hop_fanouts). A promise of the producer
  #: that the slots are parent-major; where it is ``None`` models/rgnn.py
  #: aggregates over segments.
  hop_fanouts_dict: Optional[Dict] = flax.struct.field(
      pytree_node=False, default=None)

  def edge_index_dict(self) -> Dict[EdgeType, jax.Array]:
    return {k: jnp.stack([self.row_dict[k], self.col_dict[k]])
            for k in self.row_dict}

  @property
  def batch(self) -> jax.Array:
    return self.node_dict[self.input_type][:self.batch_size]


def to_batch(out: SamplerOutput,
             x: Optional[jax.Array] = None,
             y: Optional[jax.Array] = None,
             edge_attr: Optional[jax.Array] = None,
             batch_size: Optional[int] = None) -> Batch:
  """Assemble a Batch from a SamplerOutput (+ gathered payloads)."""
  return Batch(
      x=x, y=y, edge_attr=edge_attr,
      row=out.row, col=out.col, edge_mask=out.edge_mask,
      node=out.node, node_count=out.node_count, edge=out.edge,
      num_sampled_nodes=out.num_sampled_nodes,
      num_sampled_edges=out.num_sampled_edges,
      metadata=out.metadata,
      batch_size=batch_size if batch_size is not None
      else (out.batch.shape[0] if out.batch is not None else 0),
      edge_hop_offsets=tuple(out.edge_hop_offsets)
      if out.edge_hop_offsets else None,
      node_hop_offsets=tuple(out.node_hop_offsets)
      if out.node_hop_offsets else None,
      hop_fanouts=tuple(out.hop_fanouts) if out.hop_fanouts else None,
  )


def to_hetero_batch(out: HeteroSamplerOutput,
                    x_dict=None, y_dict=None, edge_attr_dict=None,
                    batch_size: Optional[int] = None) -> HeteroBatch:
  # hop offsets are STATIC config, not batch data: they live in the
  # non-pytree field below and must not leak into the traced metadata
  meta = {k: v for k, v in (out.metadata or {}).items()
          if k != 'edge_hop_offsets'}
  return HeteroBatch(
      x_dict=x_dict or {},
      row_dict=out.row, col_dict=out.col, edge_mask_dict=out.edge_mask,
      node_dict=out.node, node_count_dict=out.node_count,
      y_dict=y_dict, edge_attr_dict=edge_attr_dict, edge_dict=out.edge,
      num_sampled_nodes=out.num_sampled_nodes,
      num_sampled_edges=out.num_sampled_edges,
      metadata=meta, input_type=out.input_type,
      batch_size=batch_size if batch_size is not None
      else (out.batch[out.input_type].shape[0] if out.batch else 0),
      edge_hop_offsets_dict=_freeze_offsets(
          (out.metadata or {}).get('edge_hop_offsets')),
  )


def _freeze_offsets(offs):
  if not offs:
    return None
  return {k: tuple(v) for k, v in offs.items()}


class EdgeIndex(NamedTuple):
  """Vendored PyG-v1 ``EdgeIndex`` adj (the reference re-exports
  torch_geometric's, sampler/neighbor_sampler.py:32; vendoring the
  3-field NamedTuple keeps the v1 training-loop idiom
  ``for batch_size, n_id, adjs in loader: ... adj.edge_index ...``
  working without a torch_geometric install)."""
  edge_index: object   # [2, m] numpy, message-flow orientation
  e_id: object         # [m] numpy global edge ids, or None
  size: tuple          # (src_count, dst_count)

  def to(self, device):  # PyG-v1 loops call adj.to(device); no-op here
    return self


def to_pyg_v1(batch: Batch):
  """PyG-v1-style (batch_size, n_id, adjs) view (the reference's
  ``as_pyg_v1`` NeighborLoader mode, loader/neighbor_loader.py:110,
  sampler/neighbor_sampler.py:448-472).

  adjs are returned outermost-hop-first (the order layer loops consume):
  each is an :class:`EdgeIndex` (edge_index [2, m] numpy in message-flow
  orientation, e_id or None, size (src_count, dst_count)). Requires
  edge_hop_offsets.
  """
  import numpy as np
  assert batch.edge_hop_offsets is not None
  offs = batch.edge_hop_offsets
  em = np.asarray(batch.edge_mask)
  row = np.asarray(batch.row)
  col = np.asarray(batch.col)
  eid = np.asarray(batch.edge) if batch.edge is not None else None
  counts = np.asarray(batch.num_sampled_nodes)
  n_id = np.asarray(batch.node)[:int(batch.node_count)]
  adjs = []
  for h in range(len(offs) - 1):
    sl = slice(offs[h], offs[h + 1])
    keep = em[sl]
    edge_index = np.stack([row[sl][keep], col[sl][keep]])
    e_id = eid[sl][keep] if eid is not None else None
    src_count = int(counts[:h + 2].sum())
    dst_count = int(counts[:h + 1].sum())
    adjs.append(EdgeIndex(edge_index, e_id, (src_count, dst_count)))
  return batch.batch_size, n_id, list(reversed(adjs))


def to_torch_data(batch: Batch):
  """Optional PyG interop (CPU): mirrors reference to_data field-for-field.
  Requires torch_geometric; raises ImportError otherwise."""
  import numpy as np
  import torch
  from torch_geometric.data import Data
  em = np.asarray(batch.edge_mask)
  edge_index = torch.as_tensor(
      np.stack([np.asarray(batch.row)[em], np.asarray(batch.col)[em]]))
  nc = int(batch.node_count)
  data = Data(
      x=torch.as_tensor(np.asarray(batch.x)[:nc])
      if batch.x is not None else None,
      edge_index=edge_index.long(),
      y=torch.as_tensor(np.asarray(batch.y))
      if batch.y is not None else None)
  data.node = torch.as_tensor(np.asarray(batch.node)[:nc])
  data.batch_size = batch.batch_size
  if batch.num_sampled_nodes is not None:
    data.num_sampled_nodes = np.asarray(batch.num_sampled_nodes).tolist()
    data.num_sampled_edges = np.asarray(batch.num_sampled_edges).tolist()
  return data
