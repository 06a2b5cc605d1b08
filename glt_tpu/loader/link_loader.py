"""LinkLoader / LinkNeighborLoader — edge-seeded mini-batch loading.

Reference: graphlearn_torch/python/loader/link_loader.py:35-230 and
link_neighbor_loader.py:27-155. Iterates (row, col, label) edge seeds,
samples the combined endpoint neighborhood (with binary/triplet negative
sampling), and yields batches whose metadata carries edge_label_index /
edge_label or triplet indices. ``get_edge_label_index`` defaults to the
full COO of the graph (reference link_loader.py:203-230).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..data import Dataset
from ..data.feature import gather_features
from ..sampler import (
    EdgeSamplerInput, NegativeSampling, NeighborSampler,
)
from ..utils import as_numpy
from .node_loader import NodeLoader
from .transform import Batch, to_batch


def get_edge_label_index(data: Dataset, edge_label_index=None,
                         input_type=None):
  """Resolve edge seeds: explicit [2, E] array, or (etype, array), or all
  edges of the graph when None."""
  if isinstance(edge_label_index, tuple) \
      and not isinstance(edge_label_index[0], (np.ndarray, list)):
    input_type, edge_label_index = edge_label_index
  if edge_label_index is None:
    g = data.get_graph(input_type)
    ptr, other, _ = g.topo.to_coo()
    if g.layout == 'CSR':
      edge_label_index = np.stack([ptr, other])
    else:
      edge_label_index = np.stack([other, ptr])
  edge_label_index = as_numpy(edge_label_index)
  return input_type, edge_label_index


class LinkLoader(NodeLoader):
  """Edge-seeded loader over an arbitrary sampler."""

  def __init__(self,
               data: Dataset,
               sampler,
               edge_label_index=None,
               edge_label=None,
               neg_sampling: Optional[NegativeSampling] = None,
               batch_size: int = 512,
               shuffle: bool = False,
               drop_last: bool = False,
               collect_features: bool = True,
               rng: Optional[np.random.Generator] = None):
    self.input_type, eli = get_edge_label_index(data, edge_label_index)
    self.edge_rows = eli[0].astype(np.int64)
    self.edge_cols = eli[1].astype(np.int64)
    self.edge_label = as_numpy(edge_label)
    self.neg_sampling = NegativeSampling.cast(neg_sampling)
    input_type = self.input_type
    super().__init__(data, sampler, input_nodes=np.arange(
        self.edge_rows.shape[0]), batch_size=batch_size, shuffle=shuffle,
        drop_last=drop_last, collect_features=collect_features, rng=rng)
    # NodeLoader.__init__ resets input_type (its seeds are node ids, ours
    # are edge positions) — restore the edge type
    self.input_type = input_type

  def _make_batch(self, seed_idx: np.ndarray, n_valid: int):
    rows = self.edge_rows[seed_idx]
    cols = self.edge_cols[seed_idx]
    label = (self.edge_label[seed_idx]
             if self.edge_label is not None else None)
    inputs = EdgeSamplerInput(rows, cols, label,
                              input_type=self.input_type,
                              neg_sampling=self.neg_sampling)
    out = self.sampler.sample_from_edges(inputs)
    if self.input_type is not None:
      return self._collate_hetero_link(out, n_valid)
    return self._collate_homo_link(out, n_valid)

  def _collate_homo_link(self, out, n_valid) -> Batch:
    x = None
    if self.collect_features and self.data.node_features is not None:
      x = gather_features(self.data.get_node_feature(), out.node)
    batch = to_batch(out, x=x, batch_size=self.batch_size)
    meta = dict(batch.metadata or {})
    meta['n_valid'] = n_valid
    return batch.replace(metadata=meta)

  def _collate_hetero_link(self, out, n_valid):
    from .transform import to_hetero_batch
    x_dict = {}
    if self.collect_features and self.data.node_features is not None:
      for ntype, node in out.node.items():
        feat = (self.data.node_features.get(ntype)
                if isinstance(self.data.node_features, dict) else None)
        if feat is not None:
          x_dict[ntype] = gather_features(feat, node)
    batch = to_hetero_batch(out, x_dict=x_dict, batch_size=self.batch_size)
    meta = dict(batch.metadata or {})
    meta['n_valid'] = n_valid
    return batch.replace(metadata=meta)


class LinkNeighborLoader(LinkLoader):
  """LinkLoader with a NeighborSampler (reference
  link_neighbor_loader.py:27-155)."""

  def __init__(self,
               data: Dataset,
               num_neighbors,
               edge_label_index=None,
               edge_label=None,
               neg_sampling: Optional[NegativeSampling] = None,
               batch_size: int = 512,
               shuffle: bool = False,
               drop_last: bool = False,
               with_edge: bool = False,
               with_weight: bool = False,
               collect_features: bool = True,
               replace: bool = False,
               seed: Optional[int] = None,
               device=None,
               rng: Optional[np.random.Generator] = None):
    sampler = NeighborSampler(
        data.graph, num_neighbors, device=device, with_edge=with_edge,
        with_weight=with_weight, edge_dir=data.edge_dir, replace=replace,
        seed=seed)
    super().__init__(data, sampler, edge_label_index=edge_label_index,
                     edge_label=edge_label, neg_sampling=neg_sampling,
                     batch_size=batch_size, shuffle=shuffle,
                     drop_last=drop_last,
                     collect_features=collect_features, rng=rng)
