"""Sampler I/O dataclasses — the PyG-compatible sampling contract.

Reference: graphlearn_torch/python/sampler/base.py (NodeSamplerInput:44,
EdgeSamplerInput:149, SamplerOutput:207, HeteroSamplerOutput:245,
NegativeSampling:85-145, SamplingConfig:339-352, BaseSampler:355-407).
Semantics preserved; payloads are jax arrays in **padded static-shape
layout**: every variable-length field carries a companion mask or count,
which is what lets the whole sampling step live inside one jit.

Orientation convention (reference neighbor_sampler.py:186-230): ``row`` is
the message-source (child) label and ``col`` the message-destination
(parent) label, i.e. ``edge_index = stack([row, col])`` is already in PyG
message-passing order for both edge_dir settings.
"""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from ..typing import EdgeType, NodeType


class SamplingType(enum.Enum):
  NODE = 'node'
  LINK = 'link'
  SUBGRAPH = 'subgraph'
  RANDOM_WALK = 'random_walk'


@dataclasses.dataclass
class NodeSamplerInput:
  """Seed nodes for node-based sampling (reference base.py:44-82)."""
  node: np.ndarray
  input_type: Optional[NodeType] = None

  def __len__(self):
    return int(np.asarray(self.node).shape[0])

  def __getitem__(self, index) -> 'NodeSamplerInput':
    return NodeSamplerInput(np.asarray(self.node)[index], self.input_type)

  def share_memory(self):  # API-compat no-op (numpy is process-local)
    return self


from ..utils.common import CastMixin


@dataclasses.dataclass
class NegativeSampling(CastMixin):
  """Binary or triplet negative sampling config (reference base.py:85-145).
  CastMixin lets callers pass a dict/tuple anywhere a NegativeSampling is
  accepted (reference utils/mixin.py pattern)."""
  mode: str = 'binary'          # 'binary' | 'triplet'
  amount: Union[int, float] = 1
  strict: bool = False

  def __post_init__(self):
    assert self.mode in ('binary', 'triplet')
    if isinstance(self.amount, (int, float)) and self.amount <= 0:
      raise ValueError(
          f'negative sampling amount must be positive, got {self.amount}')
    if self.is_triplet() and isinstance(self.amount, float):
      # triplet mode needs an integral per-positive count
      # (reference base.py NegativeSampling.__init__ coerces via ceil)
      self.amount = int(math.ceil(self.amount))

  def is_binary(self) -> bool:
    return self.mode == 'binary'

  def is_triplet(self) -> bool:
    return self.mode == 'triplet'

  def sample_size(self, num_pos: int) -> int:
    # ceil matches the reference sampler's num_neg computation
    # (neighbor_sampler.py:344)
    return int(math.ceil(num_pos * float(self.amount)))


@dataclasses.dataclass
class EdgeSamplerInput:
  """Seed edges for link-based sampling (reference base.py:149-204)."""
  row: np.ndarray
  col: np.ndarray
  label: Optional[np.ndarray] = None
  input_type: Optional[EdgeType] = None
  neg_sampling: Optional[NegativeSampling] = None

  def __len__(self):
    return int(np.asarray(self.row).shape[0])

  def __getitem__(self, index) -> 'EdgeSamplerInput':
    return EdgeSamplerInput(
        np.asarray(self.row)[index],
        np.asarray(self.col)[index],
        np.asarray(self.label)[index] if self.label is not None else None,
        self.input_type, self.neg_sampling)

  def share_memory(self):
    return self


@dataclasses.dataclass
class SamplerOutput:
  """Homogeneous sampling result (reference base.py:207-242), padded.

  node: [node_capacity] global ids (-1 padded); node_count valid.
  row/col: [edge_capacity] compact labels into ``node``; edge_mask valid.
  edge: [edge_capacity] edge ids (optional).
  batch: [batch_size] labels of the seeds (always the first entries).
  num_sampled_nodes/num_sampled_edges: per-hop counts for trim_to_layer
  (reference loader/transform.py:79-100).
  """
  node: jax.Array
  node_count: jax.Array
  row: jax.Array
  col: jax.Array
  edge_mask: jax.Array
  edge: Optional[jax.Array] = None
  batch: Optional[jax.Array] = None
  num_sampled_nodes: Optional[jax.Array] = None
  num_sampled_edges: Optional[jax.Array] = None
  #: per-hop static slot boundaries (python ints; hop h edges occupy
  #: slots [edge_hop_offsets[h], edge_hop_offsets[h+1]) of row/col)
  edge_hop_offsets: Optional[List[int]] = None
  #: per-hop static node prefixes (python ints; nodes within h hops of a
  #: seed hold labels below node_hop_offsets[h]); None where the producer
  #: does not promise hop-compact labels
  node_hop_offsets: Optional[List[int]] = None
  #: per-hop widths of the groups of adjacent edge slots that share a
  #: parent (ops.pipeline.hop_fanouts); None where the producer does not
  #: promise parent-major slots
  hop_fanouts: Optional[Sequence[int]] = None
  metadata: Optional[Dict] = None

  @property
  def batch_size(self):
    return None if self.batch is None else int(self.batch.shape[0])


@dataclasses.dataclass
class HeteroSamplerOutput:
  """Heterogeneous sampling result (reference base.py:245-302), padded:
  every per-type field mirrors SamplerOutput."""
  node: Dict[NodeType, jax.Array]
  node_count: Dict[NodeType, jax.Array]
  row: Dict[EdgeType, jax.Array]
  col: Dict[EdgeType, jax.Array]
  edge_mask: Dict[EdgeType, jax.Array]
  edge: Optional[Dict[EdgeType, jax.Array]] = None
  batch: Optional[Dict[NodeType, jax.Array]] = None
  num_sampled_nodes: Optional[Dict[NodeType, jax.Array]] = None
  num_sampled_edges: Optional[Dict[EdgeType, jax.Array]] = None
  edge_hop_offsets: Optional[Dict[EdgeType, List[int]]] = None
  input_type: Optional[Union[NodeType, EdgeType]] = None
  metadata: Optional[Dict] = None

  def get_edge_index(self) -> Dict[EdgeType, jax.Array]:
    import jax.numpy as jnp
    return {k: jnp.stack([self.row[k], self.col[k]]) for k in self.row}


@dataclasses.dataclass
class SamplingConfig:
  """The single sampling descriptor shipped to workers
  (reference base.py:339-352)."""
  sampling_type: SamplingType = SamplingType.NODE
  num_neighbors: Optional[Union[List[int], Dict[EdgeType, List[int]]]] = None
  batch_size: int = 1
  shuffle: bool = False
  drop_last: bool = False
  with_edge: bool = False
  with_weight: bool = False
  collect_features: bool = False
  edge_dir: str = 'out'
  seed: Optional[int] = None
  neg_sampling: Optional[NegativeSampling] = None


class BaseSampler:
  """ABC (reference base.py:355-407)."""

  def sample_from_nodes(self, inputs: NodeSamplerInput, **kwargs):
    raise NotImplementedError

  def sample_from_edges(self, inputs: EdgeSamplerInput, **kwargs):
    raise NotImplementedError

  @property
  def edge_permutation(self):
    return None
