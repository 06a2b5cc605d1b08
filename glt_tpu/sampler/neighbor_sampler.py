"""NeighborSampler — the single-machine multi-hop sampling engine.

Reference: graphlearn_torch/python/sampler/neighbor_sampler.py:38-692.
The reference lazily builds per-edge-type native samplers + an inducer and
runs a Python hop loop issuing CUDA kernels. Here the *entire multi-hop
walk* — sampling, dedup/relabel, frontier advance — is one jitted XLA
program per (batch_size,) shape: static padded frontiers per hop (capacity
``B·Πfanouts``, the same bound the reference sizes its inducer with,
neighbor_sampler.py:660-677), deduped by the sort-merge inducer over
batch-sized arrays (ops/pipeline.py), so a program holds no per-graph
state.

Orientation contract (verified against the reference, see
neighbor_sampler.py:186-320): for every output edge key, ``row`` holds
message-source (child) labels and ``col`` message-destination (parent)
labels. For hetero graphs with edge_dir='out' the output key is the
*reversed* traversal type ('rev_' convention); with 'in' it is the
traversal type itself.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..data import Graph
from ..ops.pipeline import edge_hop_offsets, hetero_edge_hop_offsets, \
    hop_fanouts, multihop_sample, multihop_sample_hetero, node_hop_offsets
from ..ops.sample import (
    neighbor_probs, sample_full_neighbors, sample_neighbors,
    sample_neighbors_weighted,
)
from ..obs import get_tracer
from ..ops.subgraph import induced_subgraph
from ..typing import EdgeType, NodeType, reverse_edge_type
from ..utils import as_numpy
from ..utils.rng import RandomSeedManager
from .base import (
    BaseSampler, HeteroSamplerOutput, NodeSamplerInput, SamplerOutput,
)


class NeighborSampler(BaseSampler):
  """Uniform/weighted multi-hop neighbor sampling over device CSR/CSC.

  Args:
    graph: a :class:`Graph` or Dict[EdgeType, Graph] (hetero).
    num_neighbors: [K_1..K_h] or Dict[EdgeType, [K...]]; ``-1`` means
      full neighborhood (reference semantics, e.g. SEAL's ``[-1, -1]``):
      every neighbor is expanded inside a static window of
      ``full_neighbor_cap`` (default: the graph's max degree, which makes
      the expansion exact). Frontier capacity multiplies by the window
      size per ``-1`` hop, so use it on bounded-degree graphs or set
      ``full_neighbor_cap`` explicitly.
    with_edge: emit edge ids (for edge features).
    with_weight: edge-weight-biased sampling (reference CPUWeightedSampler
      equivalent, device-side).
    edge_dir: 'out' (CSR expansion) or 'in' (CSC expansion).
    max_weighted_degree: static neighbor-window bound for the weighted
      path; defaults to the graph's max degree.
    full_neighbor_cap: static neighbor-window bound for ``-1`` hops.
    seed: RNG seed; defaults to the process RandomSeedManager.
  """

  def __init__(
      self,
      graph: Union[Graph, Dict[EdgeType, Graph]],
      num_neighbors,
      device: Optional[jax.Device] = None,
      with_edge: bool = False,
      with_weight: bool = False,
      edge_dir: str = 'out',
      replace: bool = False,
      seed: Optional[int] = None,
      max_weighted_degree: Optional[int] = None,
      full_neighbor_cap: Optional[int] = None,
  ):
    assert edge_dir in ('out', 'in')
    self.graph = graph
    self.is_hetero = isinstance(graph, dict)
    self.with_edge = with_edge
    self.with_weight = with_weight
    self.edge_dir = edge_dir
    self.replace = replace
    self.device = device
    self.max_weighted_degree = max_weighted_degree
    self.full_neighbor_cap = full_neighbor_cap
    from ..utils.rng import make_key
    self._base_key = make_key(
        seed if seed is not None
        else RandomSeedManager.getInstance().getSeed())
    self._step = 0

    # device placement must happen eagerly — inside a jit trace the
    # lazily-created arrays would be tracers and leak out of the trace
    if isinstance(graph, dict):
      for g in graph.values():
        g.lazy_init()
    else:
      graph.lazy_init()

    if self.is_hetero:
      self.edge_types = list(graph.keys())
      if isinstance(num_neighbors, dict):
        self.num_neighbors = {k: list(v) for k, v in num_neighbors.items()}
      else:
        self.num_neighbors = {
            k: list(num_neighbors) for k in self.edge_types}
      self.num_neighbors = {
          k: [self._resolve_fanout(f, graph[k]) for f in v]
          for k, v in self.num_neighbors.items()}
      hops = {len(v) for v in self.num_neighbors.values()}
      assert len(hops) == 1, 'all edge types need the same hop count'
      self.num_hops = hops.pop()
      self._node_counts = self._infer_node_counts()
    else:
      self.edge_types = None
      self.num_neighbors = [self._resolve_fanout(f, graph)
                            for f in num_neighbors]
      self.num_hops = len(self.num_neighbors)
      self._node_counts = None

    self._fn_cache = {}

  # -- helpers -----------------------------------------------------------

  @property
  def num_compiled_fns(self) -> int:
    """Number of compiled multihop programs (one per seed-shape
    signature). The serving engine's zero-recompile steady-state
    guarantee is asserted against this: after bucket warmup it must
    never grow."""
    return sum(1 for k in self._fn_cache if k[0] in ('homo', 'hetero'))

  def _resolve_fanout(self, fanout: int, g: Graph) -> int:
    """Map the user-facing fanout to the internal encoding: positive =
    sample ``fanout``; ``-1`` resolves to ``-window`` where ``window`` is
    the static full-neighborhood cap (pipeline capacity math uses |k|)."""
    fanout = int(fanout)
    if fanout == -1:
      cap = self.full_neighbor_cap or g.topo.max_degree
      assert cap > 0, 'graph has no edges; fanout=-1 is meaningless'
      return -int(cap)
    assert fanout > 0, f'fanout must be positive or -1, got {fanout}'
    return fanout

  def _infer_node_counts(self) -> Dict[NodeType, int]:
    counts: Dict[NodeType, int] = {}
    for (src, _, dst), g in self.graph.items():
      row_t = src if g.layout == 'CSR' else dst
      col_t = dst if g.layout == 'CSR' else src
      counts[row_t] = max(counts.get(row_t, 0), g.topo.num_rows)
      counts[col_t] = max(counts.get(col_t, 0), g.topo.num_cols)
    return counts

  def _next_key(self) -> jax.Array:
    self._step += 1
    return jax.random.fold_in(self._base_key, self._step)

  def _one_hop(self, g: Graph, frontier, fanout, key, mask):
    """Dispatch full/uniform/weighted one-hop sampling on graph ``g``."""
    eids = g.edge_ids if self.with_edge else None
    if fanout < 0:  # full neighborhood inside a |fanout|-wide window
      return sample_full_neighbors(
          g.indptr, g.indices, frontier, -fanout, seed_mask=mask,
          edge_ids=eids)
    if self.with_weight and g.edge_weights is not None:
      max_deg = self.max_weighted_degree or g.topo.max_degree
      max_deg = max(max_deg, fanout)
      return sample_neighbors_weighted(
          g.indptr, g.indices, g.edge_weights, frontier, fanout, key,
          max_degree=max_deg, seed_mask=mask, edge_ids=eids)
    return sample_neighbors(
        g.indptr, g.indices, frontier, fanout, key, seed_mask=mask,
        edge_ids=eids, replace=self.replace)

  # -- homogeneous sampling ---------------------------------------------

  def _build_homo_fn(self, batch_size: int):
    g: Graph = self.graph
    one_hop = lambda ids, fanout, key, mask: self._one_hop(
        g, ids, fanout, key, mask)

    def fn(seeds, n_valid, key):
      # trace-time side effect: one compiles_total{fn=...} tick per
      # compiled seed-shape program (the registry counterpart of
      # num_compiled_fns — executions never bump it)
      from ..obs.perf import count_compile
      count_compile('sampler.homo')
      return multihop_sample(one_hop, seeds, n_valid, self.num_neighbors,
                             key, with_edge=self.with_edge)

    return jax.jit(fn)

  def _edge_hop_offsets(self, batch_size: int) -> List[int]:
    return edge_hop_offsets(batch_size, self.num_neighbors)

  def sample_from_nodes(self, inputs, **kwargs) -> SamplerOutput:
    """Multi-hop sampling from seed nodes (reference
    neighbor_sampler.py:169-230). ``inputs`` may be a NodeSamplerInput or a
    plain array of seed ids; padded seeds (beyond ``n_valid``) are ignored.
    """
    if self.is_hetero:
      with get_tracer().span('sample.multihop', kind='hetero'):
        return self._hetero_sample_from_nodes(inputs, **kwargs)
    if isinstance(inputs, NodeSamplerInput):
      seeds = as_numpy(inputs.node)
    else:
      seeds = as_numpy(inputs)
    n_valid = kwargs.get('n_valid', seeds.shape[0])
    batch_size = seeds.shape[0]
    cache_key = ('homo', batch_size)
    if cache_key not in self._fn_cache:
      self._fn_cache[cache_key] = self._build_homo_fn(batch_size)
    # dispatch is async: the sync closure hands the output back to the
    # span so sampled device-syncs (GLT_OBS_TRACE_SAMPLE) measure real
    # compute, not just dispatch
    _synced = {}
    with get_tracer().span('sample.multihop', batch=batch_size,
                           hops=len(self.num_neighbors),
                           sync=lambda: _synced.get('out')):
      out = self._fn_cache[cache_key](
          jnp.asarray(seeds.astype(np.int32)), jnp.asarray(n_valid),
          kwargs.get('key', self._next_key()))
      _synced['out'] = out['num_sampled_edges']
    return SamplerOutput(
        node=out['node'], node_count=out['node_count'],
        row=out['row'], col=out['col'], edge_mask=out['edge_mask'],
        edge=out.get('edge'), batch=out['batch'],
        num_sampled_nodes=out['num_sampled_nodes'],
        num_sampled_edges=out['num_sampled_edges'],
        edge_hop_offsets=self._edge_hop_offsets(batch_size),
        node_hop_offsets=node_hop_offsets(batch_size, self.num_neighbors),
        hop_fanouts=hop_fanouts(self.num_neighbors),
        metadata={'seed_labels': out['seed_labels'],
                  'seed_count': out['seed_count']},
    )

  # -- heterogeneous sampling -------------------------------------------

  def _traversal_types(self):
    """Per traversal etype: (expand-from ntype, neighbor ntype)."""
    out = {}
    for etype in self.edge_types:
      src, _, dst = etype
      g = self.graph[etype]
      row_t = src if g.layout == 'CSR' else dst
      col_t = dst if g.layout == 'CSR' else src
      out[etype] = (row_t, col_t)
    return out

  def _hetero_caps(self, batch_sizes: Dict[NodeType, int]):
    """Static per-type frontier capacities and node budgets per hop."""
    trav = self._traversal_types()
    caps = [{t: batch_sizes.get(t, 0) for t in self._node_counts}]
    for h in range(self.num_hops):
      nxt = {t: 0 for t in self._node_counts}
      for etype, (row_t, col_t) in trav.items():
        k = self.num_neighbors[etype][h]
        nxt[col_t] += caps[h][row_t] * abs(k)
      caps.append(nxt)
    budgets = {t: max(1, sum(c[t] for c in caps))
               for t in self._node_counts}
    return caps, budgets

  def _build_hetero_fn(self, batch_sizes: Dict[NodeType, int]):
    """Multi-type seeding: ``batch_sizes`` gives each seed type's static
    batch size (single-type node sampling passes one entry; two-type
    link sampling passes both endpoint types). The hop loop itself is
    the shared ops.pipeline.multihop_sample_hetero core."""
    trav = self._traversal_types()
    caps, budgets = self._hetero_caps(batch_sizes)
    one_hops = {
        e: (lambda ids, fanout, key, mask, _e=e: self._one_hop(
            self.graph[_e], ids, fanout, key, mask))
        for e in self.edge_types}

    def fn(seeds, n_valid, key):
      from ..obs.perf import count_compile
      count_compile('sampler.hetero')  # trace-time only, like homo
      return multihop_sample_hetero(
          one_hops, trav, self.num_neighbors, self.num_hops, caps,
          budgets, seeds, n_valid, key, with_edge=self.with_edge)

    return jax.jit(fn)

  def _hetero_sample_from_nodes(self, inputs, **kwargs) \
      -> HeteroSamplerOutput:
    if isinstance(inputs, NodeSamplerInput):
      seed_dict = {inputs.input_type: as_numpy(inputs.node)}
      seed_type = inputs.input_type
    elif isinstance(inputs, dict):
      seed_dict = {t: as_numpy(s) for t, s in inputs.items()}
      seed_type = kwargs.pop('seed_type', next(iter(seed_dict)))
    else:
      seed_type, seeds = inputs
      seed_dict = {seed_type: as_numpy(seeds)}
    assert seed_type is not None, 'hetero sampling needs a seed node type'
    n_valid = kwargs.get('n_valid')
    if not isinstance(n_valid, dict):
      n_valid = {t: (n_valid if n_valid is not None else s.shape[0])
                 for t, s in seed_dict.items()}
    batch_sizes = {t: s.shape[0] for t, s in seed_dict.items()}
    cache_key = ('hetero', tuple(sorted(batch_sizes.items())))
    if cache_key not in self._fn_cache:
      self._fn_cache[cache_key] = self._build_hetero_fn(batch_sizes)
    key = kwargs.pop('key', None)
    out = self._fn_cache[cache_key](
        {t: jnp.asarray(s.astype(np.int32))
         for t, s in seed_dict.items()},
        {t: jnp.asarray(v) for t, v in n_valid.items()},
        key if key is not None else self._next_key())

    # final keys: 'out' reverses the traversal type, 'in' keeps it; row
    # must carry child labels (= our cols), col parent labels (= our rows)
    def final_key(etype):
      return reverse_edge_type(etype) if self.edge_dir == 'out' else etype

    row = {final_key(e): v for e, v in out['col'].items()}
    col = {final_key(e): v for e, v in out['row'].items()}
    edge_mask = {final_key(e): v for e, v in out['edge_mask'].items()}
    edge = ({final_key(e): v for e, v in out['edge'].items()}
            if self.with_edge else None)
    num_sampled_edges = {final_key(e): v
                         for e, v in out['num_sampled_edges'].items()}
    # static per-etype hop offsets (final-key space) for hierarchical
    # per-layer trimming (reference trim_to_layer) — cached per
    # batch-size signature alongside the compiled fn
    offs_key = ('hetero_offs', cache_key[1])
    if offs_key not in self._fn_cache:
      caps, _ = self._hetero_caps(batch_sizes)
      raw = hetero_edge_hop_offsets(
          caps, self._traversal_types(), self.num_neighbors,
          self.num_hops)
      self._fn_cache[offs_key] = {
          final_key(e): tuple(v) for e, v in raw.items()}
    hop_offs = {k: v for k, v in self._fn_cache[offs_key].items()
                if k in row}
    return HeteroSamplerOutput(
        node=out['node'], node_count=out['node_count'],
        row=row, col=col, edge_mask=edge_mask, edge=edge,
        batch=out['batch'],
        num_sampled_nodes=out['num_sampled_nodes'],
        num_sampled_edges=num_sampled_edges,
        input_type=seed_type,
        metadata={'seed_labels': out['seed_labels'],
                  'edge_hop_offsets': hop_offs},
    )

  # -- link sampling (reference neighbor_sampler.py:319-446) --------------

  def _get_neg_sampler(self, etype=None):
    if not hasattr(self, '_neg_samplers'):
      self._neg_samplers = {}
    if etype not in self._neg_samplers:
      from .negative_sampler import RandomNegativeSampler
      g = self.graph[etype] if self.is_hetero else self.graph
      self._neg_samplers[etype] = RandomNegativeSampler(
          g, mode='non-strict', edge_dir=self.edge_dir)
    return self._neg_samplers[etype]

  def sample_from_edges(self, inputs: 'EdgeSamplerInput', **kwargs):
    """Link-prediction sampling: seeds are the endpoints of positive
    (and sampled negative) edges; metadata carries edge_label_index /
    edge_label (binary) or src/dst_pos/dst_neg indices (triplet) exactly
    as the reference emits them. The inducer's first-occurrence seed
    labels are the reference's `unique(return_inverse=True)` inverse.

    Static-shape note: strict negative sampling uses padding=True so the
    negative block is always full (the reference's padding semantics);
    hetero inputs are supported for same-src/dst edge types (two-type
    merge is handled by the link loaders at collate time).
    """
    from .base import EdgeSamplerInput
    assert isinstance(inputs, EdgeSamplerInput)
    src = as_numpy(inputs.row).astype(np.int64)
    dst = as_numpy(inputs.col).astype(np.int64)
    edge_label = (as_numpy(inputs.label)
                  if inputs.label is not None else None)
    input_type = inputs.input_type
    neg = inputs.neg_sampling
    num_pos = src.shape[0]
    num_neg = 0
    key = kwargs.pop('key', None)
    if key is None:
      key = self._next_key()

    if neg is not None:
      num_neg = neg.sample_size(num_pos)
      sampler = self._get_neg_sampler(input_type)
      sampler.strict = neg.strict
      kneg, key = jax.random.split(key)
      pair = sampler.sample(num_neg, padding=True, key=kneg)
      if neg.is_binary():
        src = np.concatenate([src, as_numpy(pair.rows)])
        dst = np.concatenate([dst, as_numpy(pair.cols)])
        if edge_label is None:
          edge_label = np.ones(num_pos, np.float32)
        edge_label = np.concatenate(
            [edge_label,
             np.zeros((num_neg,) + edge_label.shape[1:],
                      edge_label.dtype)])
      else:  # triplet
        assert num_neg % max(num_pos, 1) == 0, \
            'triplet amount must be an integer multiple'
        dst = np.concatenate([dst, as_numpy(pair.cols)])
        assert edge_label is None

    if input_type is not None and input_type[0] != input_type[-1]:
      # two distinct endpoint types: seed both type spaces at once (the
      # reference merges two sampler outputs, neighbor_sampler.py:376-398;
      # our multi-type hetero engine seeds them natively)
      src_t, _, dst_t = input_type
      out = self._hetero_sample_from_nodes(
          {src_t: src, dst_t: dst}, seed_type=src_t, key=key, **kwargs)
      inverse_src = out.metadata['seed_labels'][src_t]
      inverse_dst = out.metadata['seed_labels'][dst_t]
      meta = dict(out.metadata or {})
      if neg is None or neg.is_binary():
        meta['edge_label_index'] = jnp.stack([inverse_src, inverse_dst])
        meta['edge_label'] = (jnp.asarray(edge_label)
                              if edge_label is not None else None)
      else:
        meta['src_index'] = inverse_src[:num_pos]
        meta['dst_pos_index'] = inverse_dst[:num_pos]
        dst_neg = inverse_dst[num_pos:]
        if num_pos > 0 and num_neg // num_pos > 1:
          dst_neg = dst_neg.reshape(num_pos, -1)
        meta['dst_neg_index'] = dst_neg
      meta['num_pos'] = num_pos
      meta['num_neg'] = num_neg
      out.metadata = meta
      out.input_type = input_type
      return out

    seeds = np.concatenate([src, dst])
    if input_type is not None:
      out = self._hetero_sample_from_nodes(
          NodeSamplerInput(seeds, input_type[0]), key=key, **kwargs)
      inverse = out.metadata['seed_labels'][input_type[0]]
    else:
      out = self.sample_from_nodes(seeds, key=key, **kwargs)
      # a link batch reads the embedding of every endpoint and its batch
      # size is not the seed count: the nodes are not trimmed
      out.node_hop_offsets = None
      inverse = out.metadata['seed_labels']
    meta = dict(out.metadata or {})
    if neg is None or neg.is_binary():
      meta['edge_label_index'] = inverse.reshape(2, -1)
      meta['edge_label'] = (jnp.asarray(edge_label)
                            if edge_label is not None else None)
    else:
      meta['src_index'] = inverse[:num_pos]
      meta['dst_pos_index'] = inverse[num_pos:2 * num_pos]
      dst_neg = inverse[2 * num_pos:]
      if num_pos > 0 and num_neg // num_pos > 1:
        dst_neg = dst_neg.reshape(num_pos, -1)
      meta['dst_neg_index'] = dst_neg
    meta['num_pos'] = num_pos
    meta['num_neg'] = num_neg
    out.metadata = meta
    if input_type is not None:
      out.input_type = input_type
    return out

  # -- subgraph & hotness ------------------------------------------------

  def subgraph(self, seeds, max_degree: Optional[int] = None,
               node_capacity: Optional[int] = None):
    """Induced subgraph over the merged multi-hop neighborhood (reference
    neighbor_sampler.py:474-498 NodeSubGraph path)."""
    assert not self.is_hetero, 'subgraph is homogeneous-only (as upstream)'
    seeds = as_numpy(seeds)
    out = self.sample_from_nodes(seeds)
    g: Graph = self.graph
    cap = node_capacity or out.node.shape[0]
    return induced_subgraph(
        g.indptr, g.indices, out.node,
        jnp.arange(out.node.shape[0]) < out.node_count,
        node_capacity=cap,
        max_degree=max_degree or g.topo.max_degree,
        edge_ids=g.edge_ids, with_edge=self.with_edge)

  def sample_prob(self, train_idx, node_count=None):
    """Pre-sampling hotness estimation (reference
    neighbor_sampler.py:500-627 + CalNbrProbKernel): propagate access
    probability from the training seeds through the fanouts.

    Homo: ``train_idx`` array + ``node_count`` int -> [N] probs.
    Hetero: ``train_idx`` = (seed_type, ids); ``node_count`` optional
    Dict[ntype, int] (defaults to the inferred counts); returns
    Dict[ntype, probs], pushing probability across edge types each hop
    (the per-etype loop of the reference's hetero estimator).
    """
    if self.is_hetero:
      seed_type, ids = train_idx
      counts = dict(node_count or self._node_counts)
      probs = {t: jnp.zeros((counts[t],), jnp.float32) for t in counts}
      probs[seed_type] = probs[seed_type].at[
          jnp.asarray(as_numpy(ids))].set(1.0)
      acc = {t: p for t, p in probs.items()}
      trav = self._traversal_types()
      for h in range(self.num_hops):
        nxt = {t: jnp.zeros((counts[t],), jnp.float32) for t in counts}
        for etype, (row_t, col_t) in trav.items():
          g = self.graph[etype]
          k = self.num_neighbors[etype][h]
          if k == 0:
            continue
          contrib = neighbor_probs(g.indptr, g.indices, acc[row_t], k,
                                   counts[col_t])
          nxt[col_t] = jnp.minimum(nxt[col_t] + contrib, 1.0)
        acc = nxt
        probs = {t: jnp.minimum(probs[t] + acc[t], 1.0) for t in counts}
      return probs

    g: Graph = self.graph
    assert node_count is not None
    probs = jnp.zeros((node_count,), jnp.float32)
    probs = probs.at[jnp.asarray(as_numpy(train_idx))].set(1.0)
    acc = probs
    for fanout in self.num_neighbors:
      acc = neighbor_probs(g.indptr, g.indices, acc, fanout, node_count)
      probs = jnp.minimum(probs + acc, 1.0)
    return probs
