"""Hetero distributed stores + sampler — IGBH-class workloads.

Reference: the hetero paths of dist_neighbor_sampler.py (per-etype
concurrent rpc tasks, :315-347) and dist_dataset/dist_graph hetero
handling; the deployment target is examples/igbh/dist_train_rgnn.py
(billion-edge hetero training). TPU design: one DistGraph-style sharded
store per edge type (all on the same mesh) and a shard_map hop loop that
issues the collective one-hop of every edge type then merges each
destination type once — the same structure as the single-device hetero
engine with the one-hop swapped for the all_to_all version.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.device import StepCounters, register_step_program, scope
from ..ops.negative import random_negative_sample
from ..ops.pipeline import (hetero_edge_hop_offsets, hetero_hop_fanouts,
                            multihop_sample_hetero)
from ..parallel.mesh import replicate
from ..typing import EdgeType, NodeType, as_str, reverse_edge_type
from ..utils import as_numpy
from ..utils.rng import RandomSeedManager
from .dist_graph import DistGraph
from .dist_neighbor_sampler import make_dist_one_hop

#: rounds of proposals a strict negative is drawn over inside an
#: edge-seeded step (parallel/train.py's, the reference sampler's default)
NEG_TRIALS = 5


class DistHeteroGraph:
  """Dict of per-edge-type sharded stores over one mesh.

  Built from per-partition hetero GraphPartitionData dicts + per-ntype
  partition books.
  """

  def __init__(self, mesh: Mesh, node_counts: Dict[NodeType, int],
               parts_per_etype: Dict[EdgeType, Sequence],
               node_pbs: Dict[NodeType, object], edge_dir: str = 'out',
               axis: str = 'data'):
    self.mesh = mesh
    self.axis = axis
    self.edge_dir = edge_dir
    self.node_counts = dict(node_counts)
    self.graphs: Dict[EdgeType, DistGraph] = {}
    for etype, parts in parts_per_etype.items():
      src_t, _, dst_t = etype
      row_t = src_t if edge_dir == 'out' else dst_t
      col_t = dst_t if edge_dir == 'out' else src_t
      # the per-etype store routes by the *row* type's partition book and
      # emits col-type global ids
      store = DistGraph.__new__(DistGraph)
      self._build_etype_store(store, mesh, parts, node_pbs[row_t],
                              node_counts[row_t], node_counts[col_t],
                              axis)
      self.graphs[etype] = store
    self.num_partitions = mesh.shape[axis]

  @staticmethod
  def _build_etype_store(store, mesh, parts, node_pb, num_rows_global,
                         num_cols_global, axis):
    """Like DistGraph.__init__ but with independent row/col id spaces."""
    from ..data import Topology
    from .dist_graph import _pb_dense
    n_parts = len(parts)
    indptrs, indices_l, eids_l, locals_l, weights_l = [], [], [], [], []
    max_rows, max_edges, max_degree = 1, 1, 1
    has_weights = all(p.weights is not None for p in parts)
    built = []
    for g in parts:
      src, dst = as_numpy(g.edge_index)
      row, col = src, dst  # caller passes pre-oriented (row, col)
      owned = np.unique(row)
      local_of = np.full(num_rows_global, -1, np.int32)
      local_of[owned] = np.arange(owned.shape[0], dtype=np.int32)
      topo = Topology(edge_index=np.stack([local_of[row], col]),
                      edge_ids=as_numpy(g.eids),
                      edge_weights=(as_numpy(g.weights) if has_weights
                                    else None),
                      layout='CSR',
                      num_rows=owned.shape[0],
                      num_cols=num_cols_global)
      built.append((topo, local_of))
      max_rows = max(max_rows, owned.shape[0])
      max_edges = max(max_edges, topo.num_edges)
      max_degree = max(max_degree, topo.max_degree)
    for topo, local_of in built:
      ip = topo.indptr.astype(np.int32)
      ip = np.concatenate(
          [ip, np.full(max_rows + 1 - ip.shape[0], ip[-1], np.int32)])
      indptrs.append(ip)
      indices_l.append(np.concatenate(
          [topo.indices,
           np.zeros(max_edges - topo.num_edges, topo.indices.dtype)]))
      eids_l.append(np.concatenate(
          [topo.edge_ids.astype(np.int64),
           np.full(max_edges - topo.num_edges, -1, np.int64)]))
      locals_l.append(local_of)
      if has_weights:
        weights_l.append(np.concatenate(
            [topo.edge_weights.astype(np.float32),
             np.zeros(max_edges - topo.num_edges, np.float32)]))
    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    store._finish_init(mesh, axis, num_rows_global, 'out', n_parts,
                       max_rows, max_edges, max_degree)
    store.indptr = jax.device_put(np.stack(indptrs), shard)
    store.indices = jax.device_put(np.stack(indices_l), shard)
    store.edge_ids = jax.device_put(np.stack(eids_l), shard)
    store.edge_weights = (jax.device_put(np.stack(weights_l), shard)
                          if has_weights else None)
    store.local_row = jax.device_put(np.stack(locals_l), shard)
    store.node_pb = jax.device_put(_pb_dense(node_pb, num_rows_global),
                                   repl)

  @classmethod
  def from_csr(cls, mesh: Mesh, node_counts: Dict[NodeType, int],
               csr: Dict[EdgeType, tuple], edge_dir: str = 'out',
               axis: str = 'data'):
    """One partition on a mesh of one device, from per-edge-type
    ``(indptr [n_row + 1], indices [E])`` taken as given (ascending
    within rows, pre-oriented row -> col): every row is kept, with or
    without edges, so the stores' shapes follow from the counts alone
    and one compiled program serves every graph of these sizes. No sort,
    and edge ids are positions."""
    assert mesh.shape[axis] == 1, 'from_csr builds a single partition'
    out = cls.__new__(cls)
    out.mesh, out.axis, out.edge_dir = mesh, axis, edge_dir
    out.node_counts = dict(node_counts)
    out.num_partitions = 1
    out.graphs = {}
    shard = NamedSharding(mesh, P(axis))
    for etype, (indptr, indices) in csr.items():
      row_t = etype[0] if edge_dir == 'out' else etype[2]
      n_rows, n_edges = node_counts[row_t], int(indices.shape[0])
      assert indptr.shape[0] == n_rows + 1 and indptr[-1] == n_edges
      store = DistGraph.__new__(DistGraph)
      store._finish_init(mesh, axis, n_rows, 'out', 1, n_rows,
                         max(n_edges, 1),
                         max(int(np.diff(indptr).max(initial=0)), 1))
      put = lambda a: jax.device_put(np.asarray(a, np.int32)[None], shard)
      store.indptr = put(indptr)
      store.indices = put(indices if n_edges else np.zeros(1, np.int32))
      store.edge_ids = put(np.arange(max(n_edges, 1), dtype=np.int32))
      store.edge_weights = None
      store.local_row = put(np.arange(n_rows, dtype=np.int32))
      store.node_pb = jax.device_put(np.zeros(n_rows, np.int32),
                                     NamedSharding(mesh, P()))
      out.graphs[etype] = store
    return out

  @classmethod
  def from_dataset_partitions(cls, mesh: Mesh, root_dir: str,
                              edge_dir: str = 'out', axis: str = 'data'):
    from ..partition import load_meta, load_partition
    meta = load_meta(root_dir)
    assert meta['data_cls'] == 'hetero'
    # routing uses the expand-from node's PB: edges must have been
    # assigned by that same endpoint or cross-partition neighbors would
    # silently vanish (ok = local_row >= 0 masks them)
    need = 'by_src' if edge_dir == 'out' else 'by_dst'
    got = meta.get('edge_assign', 'by_src')
    if got != need:
      raise ValueError(
          f'partition was edge-assigned {got!r} but edge_dir='
          f'{edge_dir!r} sampling requires {need!r}; re-partition with '
          f'edge_assign_strategy={need!r}')
    etypes = [tuple(e) for e in meta['edge_types']]
    parts_per_etype = {e: [] for e in etypes}
    node_pbs = None
    for p in range(meta['num_parts']):
      _, graphs, _, _, npb, _ = load_partition(root_dir, p)
      node_pbs = npb
      for e in etypes:
        g = graphs[e]
        src, dst = g.edge_index
        if edge_dir == 'out':
          oriented = np.stack([src, dst])
        else:
          oriented = np.stack([dst, src])
        from ..typing import GraphPartitionData
        parts_per_etype[e].append(
            GraphPartitionData(edge_index=oriented, eids=g.eids,
                               weights=g.weights))
    node_counts = {nt: pb.table.shape[0] for nt, pb in node_pbs.items()}
    return cls(mesh, node_counts, parts_per_etype, node_pbs,
               edge_dir=edge_dir, axis=axis)


def dist_hetero_graph_from_partitions_multihost(
    mesh: Mesh, root_dir: str, edge_dir: str = 'out',
    axis: str = 'data') -> DistHeteroGraph:
  """Multi-host DistHeteroGraph: each process loads ONLY the partitions
  owned by its local devices and contributes per-etype blocks to the
  global sharded stacks (jax.make_array_from_process_local_data) — the
  hetero counterpart of dist_graph_from_partitions_multihost, and the
  reference's per-rank partition loading discipline for IGBH-class
  training (dist_train_rgnn.py loads rank-local partitions only).

  Padding widths (max rows/edges/degree per etype) are agreed with one
  allgather so every process lowers the identical SPMD program.
  """
  import jax
  from ..partition import load_meta, load_partition
  from .dist_graph import (
      _assemble_multihost_store, _build_partition_block,
  )
  meta = load_meta(root_dir)
  assert meta['data_cls'] == 'hetero'
  need = 'by_src' if edge_dir == 'out' else 'by_dst'
  got = meta.get('edge_assign', 'by_src')
  if got != need:
    raise ValueError(
        f'partition was edge-assigned {got!r} but edge_dir='
        f'{edge_dir!r} sampling requires {need!r}')
  etypes = [tuple(e) for e in meta['edge_types']]
  devices = mesh.devices.reshape(-1)
  n_parts = devices.shape[0]
  if meta['num_parts'] != n_parts:
    raise ValueError(
        f"mesh has {n_parts} devices but the partition dir holds "
        f"{meta['num_parts']} partitions — they must match")
  mine = [i for i, d in enumerate(devices)
          if d.process_index == jax.process_index()]

  node_pbs = None
  parts_raw = {}
  for p in mine:
    _, graphs, _, _, npb, _ = load_partition(root_dir, p)
    node_pbs = npb
    parts_raw[p] = graphs
  if node_pbs is None:  # a process with no shards still needs the PBs
    _, _, _, _, node_pbs, _ = load_partition(root_dir, 0)
  node_counts = {nt: pb.table.shape[0] for nt, pb in node_pbs.items()}

  # per-etype local blocks + maxima; weights-presence must also be
  # agreed globally (all-or-nothing per etype)
  blocks = {e: {} for e in etypes}
  local_stats = np.zeros((len(etypes), 4), np.int64)  # rows,edges,deg,w
  local_stats[:, 3] = 1
  for p, graphs in parts_raw.items():
    for i, e in enumerate(etypes):
      src_t, _, dst_t = e
      g = graphs[e]
      row_t = src_t if edge_dir == 'out' else dst_t
      col_t = dst_t if edge_dir == 'out' else src_t
      topo, local_of = _build_partition_block(
          g, node_counts[row_t], edge_dir,
          with_weights=g.weights is not None,
          num_cols=node_counts[col_t])
      blocks[e][p] = (topo, local_of)
      local_stats[i, :3] = np.maximum(
          local_stats[i, :3],
          [topo.num_rows, topo.num_edges, topo.max_degree])
      if g.weights is None:
        local_stats[i, 3] = 0
  if jax.process_count() > 1:
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    gathered = np.asarray(
        multihost_utils.process_allgather(jnp.asarray(local_stats)))
    stats = np.concatenate([gathered[..., :3].max(axis=0),
                            gathered[..., 3:].min(axis=0)], axis=-1)
  else:
    stats = local_stats

  out = DistHeteroGraph.__new__(DistHeteroGraph)
  out.mesh = mesh
  out.axis = axis
  out.edge_dir = edge_dir
  out.node_counts = node_counts
  out.num_partitions = n_parts
  out.graphs = {}
  for i, e in enumerate(etypes):
    src_t, _, dst_t = e
    row_t = src_t if edge_dir == 'out' else dst_t
    # per-etype stores are always pre-oriented, hence edge_dir='out'
    # (same convention as _build_etype_store)
    out.graphs[e] = _assemble_multihost_store(
        mesh, axis, mine, blocks[e], node_counts[row_t],
        max_rows=max(int(stats[i, 0]), 1),
        max_edges=max(int(stats[i, 1]), 1),
        max_degree=max(int(stats[i, 2]), 1),
        has_weights=bool(stats[i, 3]), node_pb=node_pbs[row_t],
        n_parts=n_parts, edge_dir='out')
  return out


def _seed_caps(batch_size: int, seed_type) -> Dict[NodeType, int]:
  """``{type: seed slots}``: ``batch_size`` seeds of one type, or of each
  of a tuple of types (a type named twice holds both blocks)."""
  caps: Dict[NodeType, int] = {}
  for t in ((seed_type,) if isinstance(seed_type, str) else seed_type):
    caps[t] = caps.get(t, 0) + batch_size
  return caps


class DistHeteroNeighborSampler:
  """SPMD hetero sampling: per-device seed batches of one seed type, or
  inside the train step of the two ends of a seed relation."""

  def __init__(self, graph: DistHeteroGraph, num_neighbors,
               with_edge: bool = False, with_weight: bool = False,
               max_weighted_degree: Optional[int] = None,
               seed: Optional[int] = None,
               full_neighbor_cap: Optional[int] = None):
    self.g = graph
    self.mesh = graph.mesh
    self.axis = graph.axis
    self.with_edge = with_edge
    self.with_weight = with_weight and all(
        s.edge_weights is not None for s in graph.graphs.values())
    self.max_weighted_degree = max_weighted_degree
    self.edge_types = list(graph.graphs.keys())
    if isinstance(num_neighbors, dict):
      self.num_neighbors = {k: list(v) for k, v in num_neighbors.items()}
    else:
      self.num_neighbors = {k: list(num_neighbors)
                            for k in self.edge_types}
    for e, v in self.num_neighbors.items():
      for i, f in enumerate(v):
        f = int(f)
        if f == -1:  # full neighborhood: resolve to a static -window
          cap = full_neighbor_cap or getattr(graph.graphs[e],
                                             'max_degree', 0)
          assert cap > 0, (f'fanout=-1 for {e} needs full_neighbor_cap '
                           'or a store with a known max_degree')
          f = -int(cap)
        else:
          assert f >= 0, f'fanout must be >= 0 or -1, got {f} for {e}'
        v[i] = f
    hops = {len(v) for v in self.num_neighbors.values()}
    assert len(hops) == 1
    self.num_hops = hops.pop()
    from ..utils.rng import make_key
    self._base_key = make_key(
        seed if seed is not None
        else RandomSeedManager.getInstance().getSeed())
    self._step = 0
    self._fn_cache = {}

  def _next_key(self):
    self._step += 1
    return jax.random.fold_in(self._base_key, self._step)

  def _trav(self):
    out = {}
    for etype in self.edge_types:
      src_t, _, dst_t = etype
      row_t = src_t if self.g.edge_dir == 'out' else dst_t
      col_t = dst_t if self.g.edge_dir == 'out' else src_t
      out[etype] = (row_t, col_t)
    return out

  def _caps(self, batch_size: int, seed_type):
    trav = self._trav()
    types = list(self.g.node_counts)
    seeded = _seed_caps(batch_size, seed_type)
    caps = [{t: seeded.get(t, 0) for t in types}]
    for h in range(self.num_hops):
      nxt = {t: 0 for t in types}
      for etype, (row_t, col_t) in trav.items():
        nxt[col_t] += caps[h][row_t] * abs(self.num_neighbors[etype][h])
      caps.append(nxt)
    budgets = {t: max(1, sum(c[t] for c in caps)) for t in types}
    return caps, budgets

  def _make_device_core(self, batch_size: int, seed_type):
    """Returns device_core(shards, seeds, n_valid_scalar, key) -> result
    dict with NO leading shard dims — reusable by
    the train step. ``seed_type`` a tuple of node types (the ends of a
    seed relation, ``batch_size`` slots each): ``seeds`` and the last
    argument ``seed_mask`` are dicts by type, and so are the result's
    ``batch`` and ``seed_labels``."""
    many = not isinstance(seed_type, str)
    g = self.g
    trav = self._trav()
    caps, budgets = self._caps(batch_size, seed_type)
    axis = self.axis
    n_parts = g.num_partitions
    types = list(g.node_counts)
    # an edge type participates only if its expand-from type ever has a
    # frontier; inactive types produce no edges and must be excluded from
    # outputs (and from shard_map out_specs)
    etypes = [e for e in self.edge_types
              if any(caps[h][trav[e][0]] * abs(self.num_neighbors[e][h])
                     > 0 for h in range(self.num_hops))]

    def device_core(shards, seeds, n_valid, key, seed_mask=None):
      one_hops = {}
      for e in etypes:
        sh = shards[e]
        gs = dict(indptr=sh['indptr'], indices=sh['indices'],
                  edge_ids=sh['edge_ids'],
                  local_row=sh['local_row'],
                  node_pb=sh['node_pb'])
        if 'edge_weights' in sh:
          gs['edge_weights'] = sh['edge_weights']
        one_hops[e] = make_dist_one_hop(
            gs, g.graphs[e].num_nodes, n_parts, g.graphs[e].max_rows,
            axis, with_weight=self.with_weight,
            max_weighted_degree=(self.max_weighted_degree
                                 or getattr(g.graphs[e], 'max_degree',
                                            1)))

      trav_active = {e: trav[e] for e in etypes}
      if many:
        return multihop_sample_hetero(
            one_hops, trav_active, self.num_neighbors, self.num_hops,
            caps, budgets, seeds, {t: n_valid for t in seeds}, key,
            with_edge=self.with_edge, seed_mask=seed_mask)
      result = multihop_sample_hetero(
          one_hops, trav_active, self.num_neighbors, self.num_hops,
          caps, budgets, {seed_type: seeds},
          {seed_type: n_valid}, key, with_edge=self.with_edge)
      # flatten the per-seed-type dicts to the flat fields dist callers
      # consume (single seed type in dist mode)
      result['batch'] = result['batch'][seed_type]
      result['seed_labels'] = result['seed_labels'][seed_type]
      return result

    return device_core, caps, budgets, etypes

  def _build(self, batch_size: int, seed_type: NodeType):
    g = self.g
    types = list(g.node_counts)
    device_core, caps, budgets, etypes = self._make_device_core(
        batch_size, seed_type)

    def device_fn(shards, seeds, n_valid, key):
      def unpack(sh):
        d = dict(indptr=sh['indptr'][0], indices=sh['indices'][0],
                 edge_ids=sh['edge_ids'][0],
                 local_row=sh['local_row'][0], node_pb=sh['node_pb'])
        if 'edge_weights' in sh:
          d['edge_weights'] = sh['edge_weights'][0]
        return d
      shards_in = {e: unpack(sh) for e, sh in shards.items()}
      key = jax.random.fold_in(key[0], jax.lax.axis_index(self.axis))
      result = device_core(shards_in, seeds, n_valid[0], key)
      return jax.tree_util.tree_map(lambda a: a[None], result)

    sp = P(self.axis)
    def etype_spec(e):
      d = dict(indptr=sp, indices=sp, edge_ids=sp, local_row=sp,
               node_pb=P())
      if g.graphs[e].edge_weights is not None:
        d['edge_weights'] = sp
      return d
    shard_specs = {e: etype_spec(e) for e in etypes}
    out_elem = {
        'node': {t: sp for t in types},
        'node_count': {t: sp for t in types},
        'row': {e: sp for e in etypes}, 'col': {e: sp for e in etypes},
        'edge_mask': {e: sp for e in etypes},
        'batch': sp, 'seed_labels': sp,
        'num_sampled_nodes': {t: sp for t in types},
        'num_sampled_edges': {e: sp for e in etypes},
        'hop_rows_read': {e: sp for e in etypes},
    }
    if self.with_edge:
      out_elem['edge'] = {e: sp for e in etypes}

    fn = jax.shard_map(
        device_fn, mesh=self.mesh,
        in_specs=(shard_specs, sp, sp, sp),
        out_specs=out_elem, check_vma=False)

    @jax.jit
    def step(seeds, n_valid, keys):
      def etype_payload(e):
        d = dict(indptr=g.graphs[e].indptr, indices=g.graphs[e].indices,
                 edge_ids=g.graphs[e].edge_ids,
                 local_row=g.graphs[e].local_row,
                 node_pb=g.graphs[e].node_pb)
        if g.graphs[e].edge_weights is not None:
          d['edge_weights'] = g.graphs[e].edge_weights
        return d
      shards = {e: etype_payload(e) for e in etypes}
      return fn(shards, seeds, n_valid, keys)

    return step

  def sample_from_nodes(self, seed_type: NodeType,
                        seeds_per_device, n_valid_per_device=None,
                        key=None) -> dict:
    seeds = as_numpy(seeds_per_device)
    n_dev = self.mesh.shape[self.axis]
    if seeds.ndim == 2:
      seeds = seeds.reshape(-1)
    batch_size = seeds.shape[0] // n_dev
    if n_valid_per_device is None:
      n_valid_per_device = np.full(n_dev, batch_size, np.int32)
    cache_key = (batch_size, seed_type)
    if cache_key not in self._fn_cache:
      self._fn_cache[cache_key] = self._build(batch_size, seed_type)
    if key is None:
      key = self._next_key()
    shard = NamedSharding(self.mesh, P(self.axis))
    out = self._fn_cache[cache_key](
        jax.device_put(jnp.asarray(seeds, jnp.int32), shard),
        jax.device_put(jnp.asarray(n_valid_per_device, jnp.int32), shard),
        jax.random.split(key, n_dev))

    def final_key(e):
      return reverse_edge_type(e) if self.g.edge_dir == 'out' else e

    # message-passing orientation + key reversal, as the single-device
    # hetero engine emits
    out['row'], out['col'] = (
        {final_key(e): v for e, v in out['col'].items()},
        {final_key(e): v for e, v in out['row'].items()})
    out['edge_mask'] = {final_key(e): v
                        for e, v in out['edge_mask'].items()}
    for counted in ('num_sampled_edges', 'hop_rows_read'):
      out[counted] = {final_key(e): v for e, v in out[counted].items()}
    if self.with_edge:
      out['edge'] = {final_key(e): v for e, v in out['edge'].items()}
    out['input_type'] = seed_type
    return out


def _update_by_group(tx, grads, opt_state, params, tables):
  """``tx.update`` and ``apply_updates`` in two calls, under the scopes
  ``tables`` and ``rest``: the leaves under a top-level collection named
  in ``tables`` (a model's ``table_params``: its embedding tables), then
  all others. Each call sees the other group's leaves as ``None``, in
  the gradient, the parameters and every part of the state that is
  shaped like the parameters, and the halves are joined again. For an
  optimizer that treats each leaf alone (Adam: the recipe's) the two
  calls compute, leaf for leaf, what one call does, every row of a table
  included; one that couples leaves (a clip by the global norm) would
  see each group alone."""
  import optax
  none = lambda x: x is None
  shape = jax.tree.structure(params)
  like = lambda t: jax.tree.structure(t, is_leaf=none) == shape
  in_tables = lambda path: any(getattr(k, 'key', None) in tables
                               for k in path)

  def part(tree, keep):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if in_tables(path) == keep else None, tree)

  def join(a, b):
    return jax.tree.map(lambda x, y: y if x is None else x, a, b,
                        is_leaf=none)

  halves = []
  for name, keep in (('tables', True), ('rest', False)):
    with jax.named_scope(name):
      state = jax.tree.map(lambda t: part(t, keep) if like(t) else t,
                           opt_state, is_leaf=like)
      p = part(params, keep)
      updates, state = tx.update(part(grads, keep), state, p)
      halves.append((optax.apply_updates(p, updates), state))
  (p_tab, s_tab), (p_rest, s_rest) = halves
  return join(p_tab, p_rest), jax.tree.map(
      lambda a, b: join(a, b) if like(a) else a, s_tab, s_rest,
      is_leaf=like)


def _hetero_update(model, tx, axis, bs, params, opt_state, batch, y,
                   n_valid):
  """Forward/backward + gradient pmean + optimizer update for one typed
  batch: the training tail shared by the per-batch step and the
  superstep scan (identical op sequence = loss parity), under the layer
  scopes of parallel/train.py::_sage_update. ``y`` the seeds' labels, or
  ``None`` for a batch of edge seeds: the model gives the logits of the
  ``2 * bs`` pairs of ``edge_label_index`` and the loss is the binary
  cross-entropy against ``edge_label`` (parallel/train.py::_link_loss's
  form; a pair past ``n_valid`` and its negative are left out). A model
  that names ``table_params`` has them updated under ``update/tables``
  and the rest under ``update/rest``."""
  import optax

  def loss_fn(p):
    with jax.named_scope('forward'):
      logits = model.apply(p, batch)
      mask = jnp.arange(bs) < n_valid
      if y is None:
        with jax.named_scope('link_loss'):
          mask = jnp.tile(mask, 2)
          l = optax.sigmoid_binary_cross_entropy(
              logits, batch.metadata['edge_label'])
      else:
        l = optax.softmax_cross_entropy_with_integer_labels(logits, y)
      return jnp.where(mask, l, 0).sum() / jnp.maximum(mask.sum(), 1)

  with scope('model_step'):
    loss, grads = jax.value_and_grad(loss_fn)(params)
  with scope('collectives', 'grad_sync'):
    grads = jax.lax.pmean(grads, axis)
    loss = jax.lax.pmean(loss, axis)
  tables = getattr(model, 'table_params', ())
  with scope('model_step', 'update'):
    if tables:
      params, opt_state = _update_by_group(tx, grads, opt_state, params,
                                           tables)
    else:
      updates, opt_state = tx.update(grads, opt_state, params)
      params = optax.apply_updates(params, updates)
  return params, opt_state, loss


class DistHeteroTrainStep(StepCounters):
  """One-program hetero distributed training (the IGBH deployment shape,
  examples/igbh/dist_train_rgnn.py): hetero collective sampling +
  per-type feature all_to_all + the typed model's forward/backward +
  gradient pmean, all inside a single shard_map step. ``model`` is any
  flax module over a ``HeteroBatch`` that returns the seeds' logits:
  ``models/rgnn.py::RGNN`` and ``models/hgt.py::HGT`` both read the
  batch's static promises through models/plan.py. Every per-batch step
  also says how full its padded budgets were: ``counters()`` reads what
  the newest steps counted by type and relation, ``counter_slots()`` the
  slots the counts are read against.

  Given a ``neg_sampling`` the step is seeded by edges (the form
  ``SPMDSageTrainStep(neg_sampling=...)`` has): ``seed_type`` names a
  stored relation, ``labels`` is ``None``, a call's seeds are ``[B, 2]``
  positive ``(src, dst)`` edges of it a device, and the program draws
  ``B`` binary negatives on the relation's CSR (strict: ``NEG_TRIALS``
  rounds, padded), seeds one typed expansion with the ``2B`` sources and
  the ``2B`` destinations (repeats are deduplicated by the front) and
  hands the model ``metadata['edge_label_index']`` (``[2, 2B]`` labels
  of the pairs' ends within their types' rows) and ``edge_label``;
  ``model`` returns the pairs' logits. A type without an entry in
  ``features`` has no table: its ``x`` is its ids. That program donates
  ``params`` and ``opt_state``: use the ones a call returns. It runs per
  batch, on one partition whose seed relation keeps every row; binary
  negatives of amount 1 only.
  """

  def __init__(self, graph: DistHeteroGraph,
               features: Dict[NodeType, object],   # DistFeature per type
               model, tx, labels: Dict[NodeType, np.ndarray],
               num_neighbors, batch_size_per_device: int,
               seed_type: NodeType, seed: Optional[int] = None,
               edge_features: Optional[Dict[EdgeType, object]] = None,
               with_weight: bool = False,
               max_weighted_degree: Optional[int] = None,
               keep_sample: bool = False, neg_sampling=None,
               keep_seeds: bool = False):
    """``edge_features`` maps *traversal* edge types to edge-id-space
    DistFeatures; when given, sampling emits eids and the batch carries
    ``edge_attr_dict`` (reference efeat collate,
    dist_neighbor_sampler.py:689-807). ``with_weight`` enables the
    weighted per-etype collective one-hop (reference
    neighbor_sampler.py:96-144 hetero weighted loops). ``keep_sample``
    has the per-batch step return the structure it sampled and trained
    on, kept as ``last_sample`` until the next call: what a check of the
    sample, or a reference's own step on it, reads, with no second
    program. ``keep_seeds``: an edge-seeded step also hands back its
    ``[4B]`` endpoint seeds ``[src; neg_src; dst; neg_dst]`` among its
    counters."""
    from ..parallel.dist_feature import require_device_resident
    from ..sampler import NegativeSampling
    for t, st in features.items():
      require_device_resident(st, f'DistHeteroTrainStep features[{t!r}]')
    for e, st in (edge_features or {}).items():
      require_device_resident(
          st, f'DistHeteroTrainStep edge_features[{e!r}]')
    self.g = graph
    self.features = features
    self.edge_features = edge_features or {}
    self.model = model
    self.tx = tx
    self.seed_type = seed_type
    self.bs = int(batch_size_per_device)
    self.mesh = graph.mesh
    self.axis = graph.axis
    self.neg_sampling = NegativeSampling.cast(neg_sampling)
    self._link = self.neg_sampling is not None
    self._keep_seeds = bool(keep_seeds)
    #: what seeds the typed expansion, and the seed slots of each: the
    #: seed type's ``bs``, or ``2 * bs`` of each end of the seed relation
    self._seeded, self._seed_slots = seed_type, self.bs
    if self._link:
      self._check_edge_seeds(labels)
      self._seeded = (seed_type[0], seed_type[2])
      self._seed_slots = 2 * self.bs
    self.sampler = DistHeteroNeighborSampler(
        graph, num_neighbors, with_edge=bool(self.edge_features),
        with_weight=with_weight, max_weighted_degree=max_weighted_degree,
        seed=seed)
    self.labels = {t: jax.device_put(as_numpy(v),
                                     NamedSharding(self.mesh, P()))
                   for t, v in (labels or {}).items()}
    #: times each program was TRACED (trace-time side effects;
    #: executions never bump these) — the zero-steady-state-recompile
    #: assertions on the hetero train path read them
    self.step_traces = 0
    self.superstep_traces = 0
    self.keep_sample = bool(keep_sample)
    #: with ``keep_sample``, the last ``__call__``'s batch as
    #: ``sampler.sample_from_nodes`` lays one out (``node``,
    #: ``node_count`` by type; ``row``, ``col``, ``edge_mask`` by
    #: message-flow relation; a leading axis of devices), on the device
    self.last_sample = None
    _, caps, budgets, active = self.sampler._make_device_core(
        self._seed_slots, self._seeded)
    trav = {e: tc for e, tc in self.sampler._trav().items()
            if e in active}
    #: static counters of the step, in slots: rows of each type's padded
    #: node budget and edge slots of each relation (message-flow keys),
    #: known when the step is built
    edge_offsets = {
        self._final_key(e): tuple(v)
        for e, v in hetero_edge_hop_offsets(
            caps, trav, self.sampler.num_neighbors,
            self.sampler.num_hops).items()}
    self.node_budget = dict(budgets)
    self.edge_budget = {e: v[-1] for e, v in edge_offsets.items()}
    #: slots of the frontier each relation expands in each hop (0 for a
    #: hop it is not read in): what ``hop_rows_read`` is read against
    self._frontier_slots = {
        self._final_key(e): tuple(
            caps[h][row_t] if self.sampler.num_neighbors[e][h] else 0
            for h in range(self.sampler.num_hops))
        for e, (row_t, _) in trav.items()}
    #: the rows of the step's ``nodes_by_hop`` and ``edges_by_hop``
    #: counters, in order: node types, and relations as ``edge_budget``
    #: keys them
    self.counter_node_types = tuple(budgets)
    self.counter_edge_types = tuple(edge_offsets)
    # every node store serves in place: the step counts ``store_chunks``
    self._stores_in_place = bool(features) and all(
        st.in_place for st in features.values())
    #: output rows each layer of the model computes for each type, filled
    #: when a program is traced (the node trim engages at trace time);
    #: None before, and for a model that does not say
    self.layer_rows = None
    #: groups of adjacent edge slots that each layer reduces over the
    #: fanout axis for each relation (``HeteroBatch.hop_fanouts_dict``),
    #: ``[{relation: groups}]``, 0 where a relation aggregates over
    #: segments; filled beside ``layer_rows``
    self.layer_groups = None
    #: relations that share each parent type's softmax in each layer, for
    #: a model whose softmax crosses relations (models/hgt.py says
    #: ``layer_joint_relations``; ``[{type: relations}]``); filled beside
    #: ``layer_rows``, None for a model that does not say
    self.layer_joint_relations = None
    #: what the producer promises of a batch's labels, per type and per
    #: relation: the static hop prefixes that models/plan.py trims by,
    #: and the parent-major groups of a relation's edge slots
    self._batch_static = dict(
        edge_hop_offsets_dict=edge_offsets,
        node_hop_offsets_dict={
            t: tuple(int(x) for x in np.cumsum([c[t] for c in caps]))
            for t in budgets},
        hop_fanouts_dict={
            self._final_key(e): v for e, v in hetero_hop_fanouts(
                caps, trav, self.sampler.num_neighbors,
                self.sampler.num_hops).items()})
    from ..obs.perf import gauge_budgets
    gauge_budgets('train.hetero_step', self.node_budget, self.edge_budget)
    self._init_counters()
    self._step_fn = self._build()
    self._superstep_fn = None  # built lazily on first superstep call
    self._eval_fn = None  # built lazily on first eval_step call
    register_step_program(self)

  def _final_key(self, e):
    return reverse_edge_type(e) if self.g.edge_dir == 'out' else e

  def _check_edge_seeds(self, labels):
    """What an edge-seeded step can run, refused by name where it
    cannot."""
    neg, rel = self.neg_sampling, self.seed_type
    if not neg.is_binary() or neg.amount != 1:
      raise NotImplementedError(
          'an edge-seeded DistHeteroTrainStep draws one binary negative '
          f'a positive in its program; got {neg}: triplet mode and '
          'other amounts run through LinkNeighborLoader')
    if rel not in self.g.graphs:
      raise ValueError(
          f'seed_type {rel!r} is no stored relation of the graph; an '
          f'edge-seeded step takes one of {sorted(self.g.graphs)}')
    if labels:
      raise ValueError('an edge-seeded step has no labels table: its '
                       "labels are the pairs' own (edge_label)")
    store = self.g.graphs[rel]
    if self.g.num_partitions != 1 or store.max_rows != store.num_nodes:
      raise NotImplementedError(
          "strict negatives are drawn against the seed relation's CSR "
          'in place: one partition that keeps every row of it '
          '(DistHeteroGraph.from_csr); got '
          f'{self.g.num_partitions} partitions, {store.max_rows} of '
          f'{store.num_nodes} rows')

  def _link_seeds(self, shard, pairs, n_valid, key):
    """The edge-seeded step's front, inside the ``sampler`` scope
    (parallel/train.py::_link_seeds's typed twin): ``B`` negatives from
    ``key`` on the seed relation's CSR (uniform pairs of its two id
    spaces, ``NEG_TRIALS`` rounds, the first round that is no edge, the
    last round's proposal where none is), then by type the seeds
    ``[src; neg_src]`` and ``[dst; neg_dst]`` (one block of ``4B`` where
    both ends are one type) with their masks: a pair past ``n_valid``
    and its negative seed nothing. Returns ``(seeds, seed_mask,
    edge_label [2B], counters)``."""
    bs, counts = self.bs, self.g.node_counts
    src_t, _, dst_t = self.seed_type
    row_t, col_t = self.sampler._trav()[self.seed_type]
    with jax.named_scope('negative'):
      neg = random_negative_sample(
          shard['indptr'], shard['indices'], bs, NEG_TRIALS, key,
          counts[row_t], counts[col_t], strict=self.neg_sampling.strict,
          padding=True)
    neg_src, neg_dst = ((neg.rows, neg.cols) if self.g.edge_dir == 'out'
                        else (neg.cols, neg.rows))
    src = jnp.concatenate([pairs[:, 0], neg_src]).astype(jnp.int32)
    dst = jnp.concatenate([pairs[:, 1], neg_dst]).astype(jnp.int32)
    live = jnp.tile(jnp.arange(bs) < n_valid, 2)
    if src_t == dst_t:
      seeds = {src_t: jnp.concatenate([src, dst])}
      mask = {src_t: jnp.tile(live, 2)}
    else:
      seeds, mask = {src_t: src, dst_t: dst}, {src_t: live, dst_t: live}
    edge_label = jnp.concatenate(
        [jnp.ones((bs,), jnp.float32), jnp.zeros((bs,), jnp.float32)])
    counted = dict(negatives_rejected=neg.rejected,
                   negatives_padded=neg.padded)
    if self._keep_seeds:
      counted['seeds'] = jnp.concatenate([src, dst])
    return seeds, mask, edge_label, counted

  def dummy_batch(self):
    from ..loader.transform import HeteroBatch
    budgets = self.node_budget
    # a type without a table is read by its ids
    x_dict = {t: (jnp.zeros((budgets[t], self.features[t].feature_dim))
                  if t in self.features
                  else jnp.zeros((budgets[t],), jnp.int32))
              for t in budgets}
    row_d, col_d, mask_d, eattr_d, eid_d = {}, {}, {}, {}, {}
    for e in self.sampler.edge_types:
      k = self._final_key(e)
      if k not in self.edge_budget:   # no frontier ever reaches it
        continue
      ecap = max(self.edge_budget[k], 1)
      row_d[k] = jnp.zeros((ecap,), jnp.int32)
      col_d[k] = jnp.zeros((ecap,), jnp.int32)
      mask_d[k] = jnp.zeros((ecap,), bool)
      if self.sampler.with_edge:
        eid_d[k] = jnp.zeros((ecap,), jnp.int32)
      if e in self.edge_features:
        eattr_d[k] = jnp.zeros((ecap,
                                self.edge_features[e].feature_dim))
    return HeteroBatch(
        x_dict=x_dict, row_dict=row_d, col_dict=col_d,
        edge_mask_dict=mask_d,
        edge_attr_dict=eattr_d or None,
        edge_dict=eid_d or None,
        node_dict={t: jnp.zeros((budgets[t],), jnp.int32)
                   for t in budgets},
        node_count_dict={t: jnp.zeros((), jnp.int32) for t in budgets},
        y_dict=(None if self._link else
                {self.seed_type: jnp.zeros((self.bs,), jnp.int32)}),
        metadata=(dict(
            edge_label_index=jnp.zeros((2, 2 * self.bs), jnp.int32),
            edge_label=jnp.zeros((2 * self.bs,), jnp.float32))
                  if self._link else None),
        input_type=self.seed_type, batch_size=self.bs,
        **self._batch_static)

  def _note_layer_rows(self, batch) -> None:
    """Trace-time side effect, as ``step_traces``: what the node trim
    leaves each layer to compute for each type and how many groups of
    edge slots it reduces over the fanout axis for each relation, on the
    attributes and as the gauges ``model_layer_rows{fn, layer, type}``
    and ``model_grouped_aggregation{fn, layer, relation}``; for a model
    whose softmax crosses relations also
    ``model_joint_softmax_relations{fn, layer, type}``."""
    from ..obs.perf import (gauge_grouped_aggregation, gauge_joint_softmax,
                            gauge_layer_rows)
    rows_of = getattr(self.model, 'layer_rows', None)
    if rows_of is not None:
      self.layer_rows = rows_of(batch)
      gauge_layer_rows('train.hetero_step', self.layer_rows)
    groups_of = getattr(self.model, 'layer_groups', None)
    if groups_of is not None:
      self.layer_groups = groups_of(batch)
      gauge_grouped_aggregation('train.hetero_step', self.layer_groups)
    joint_of = getattr(self.model, 'layer_joint_relations', None)
    if joint_of is not None:
      self.layer_joint_relations = joint_of(batch)
      gauge_joint_softmax('train.hetero_step', self.layer_joint_relations)
    tables = getattr(self.model, 'embedding_tables', None)
    if tables is not None:
      from ..obs.perf import gauge_embedding_rows
      gauge_embedding_rows('train.hetero_step', tables)

  def init_params(self, key):
    params = self.model.init(key, self.dummy_batch())
    return replicate(params, self.mesh)

  def _assembly(self):
    """Shared device-batch assembly for the train and eval programs:
    returns (device_batch, specs, payloads) where ``device_batch(...)``
    runs sampling + feature/efeat collate inside shard_map and yields
    (batch, y, counters): ``counters`` is what the sampler
    counted, ``nodes_by_hop`` ``[T, H + 1]``, ``edges_by_hop`` and
    ``hop_rows_read`` ``[R, H]`` in the order of ``counter_node_types``
    and ``counter_edge_types``, and where every node store serves in place
    ``store_chunks`` ``[T]``, the chunks of a type's request slots that
    its store gathered."""
    from ..loader.transform import HeteroBatch
    g, axis, bs = self.g, self.axis, self.bs
    seed_type, link = self.seed_type, self._link
    device_core, caps, budgets, etypes = self.sampler._make_device_core(
        self._seed_slots, self._seeded)
    types = list(g.node_counts)
    feats = self.features
    stored = [t for t in self.counter_node_types if t in feats]
    unknown = set(self.edge_features) - set(self.sampler.edge_types)
    assert not unknown, (
        f'edge_features keys {sorted(map(str, unknown))} are not '
        'traversal edge types; valid keys: '
        f'{sorted(map(str, self.sampler.edge_types))} '
        '(pass the traversal type, not the reversed output key)')
    # inactive etypes (no frontier ever reaches them) sample no edges
    efeats = {e: v for e, v in self.edge_features.items() if e in etypes}

    def device_batch(shards, feat_shards, efeat_shards, labels, seeds,
                     n_valid, key):
      def unpack(sh):
        d = dict(indptr=sh['indptr'][0], indices=sh['indices'][0],
                 edge_ids=sh['edge_ids'][0],
                 local_row=sh['local_row'][0], node_pb=sh['node_pb'])
        if 'edge_weights' in sh:
          d['edge_weights'] = sh['edge_weights'][0]
        return d
      shards_in = {e: unpack(sh) for e, sh in shards.items()}
      my_key = jax.random.fold_in(key[0], jax.lax.axis_index(axis))
      fk = self._final_key
      with scope('sampler'):
        seed_mask, counted, meta = None, {}, None
        if link:
          kneg, my_key = jax.random.split(my_key)
          seeds, seed_mask, edge_label, counted = self._link_seeds(
              shards_in[seed_type], seeds, n_valid[0], kneg)
        out = device_core(shards_in, seeds, n_valid[0], my_key,
                          seed_mask)
        by_relation = lambda counted: self._by_relation_and_hop(
            {fk(e): v for e, v in out[counted].items()})
        counters = dict(
            nodes_by_hop=jnp.stack([out['num_sampled_nodes'][t]
                                    for t in self.counter_node_types]),
            edges_by_hop=by_relation('num_sampled_edges'),
            hop_rows_read=by_relation('hop_rows_read'))
        if link:
          # a seed slot's label is its endpoint's row of its type: the
          # labels of a type's seeds are its first ones
          ends = [out['seed_labels'][t] for t in seeds]
          meta = dict(
              edge_label_index=(jnp.stack(ends) if len(ends) == 2
                                else ends[0].reshape(2, -1)),
              edge_label=edge_label)
          counters.update(counted, seed_unique=jnp.stack(
              [out['num_sampled_nodes'][t][0] for t in self._seeded]))
      x_dict, chunks = {}, {}
      for t in types:
        if t not in feats:   # no table: the type is read by its ids
          x_dict[t] = out['node'][t]
          continue
        fs = feat_shards[t]
        with scope('feature_store', 'gather', t):
          valid = (jnp.arange(out['node'][t].shape[0])
                   < out['node_count'][t])
          rows = feats[t].lookup_local(
              fs['array'][0], fs['id2index'][0], fs['feat_pb'][0],
              jnp.maximum(out['node'][t], 0), valid, axis_name=axis,
              cold_shard=fs['cold'][0] if 'cold' in fs else None,
              counters=self._stores_in_place)
          if self._stores_in_place:
            rows, chunks[t] = rows[0], rows[1]['store_chunks']
          x_dict[t] = rows
      if self._stores_in_place:
        counters['store_chunks'] = jnp.stack([chunks[t] for t in stored])
      y = None
      if not link:
        with scope('feature_store'):
          y = jnp.take(labels[seed_type],
                       jnp.maximum(out['batch'], 0)[:bs])
      edge_attr_dict = None
      if efeats:
        edge_attr_dict = {}
        for e in efeats:
          fs = efeat_shards[e]
          with scope('feature_store', 'gather', as_str(fk(e))):
            edge_attr_dict[fk(e)] = efeats[e].lookup_local(
                fs['array'][0], fs['id2index'][0], fs['feat_pb'][0],
                jnp.maximum(out['edge'][e], 0), out['edge_mask'][e],
                axis_name=axis,
                cold_shard=fs['cold'][0] if 'cold' in fs else None)
      batch = HeteroBatch(
          x_dict=x_dict,
          row_dict={fk(e): out['col'][e] for e in etypes},
          col_dict={fk(e): out['row'][e] for e in etypes},
          edge_mask_dict={fk(e): out['edge_mask'][e] for e in etypes},
          edge_attr_dict=edge_attr_dict,
          edge_dict=({fk(e): out['edge'][e] for e in etypes}
                     if 'edge' in out else None),
          node_dict=out['node'], node_count_dict=out['node_count'],
          y_dict=None if link else {seed_type: y}, metadata=meta,
          input_type=seed_type, batch_size=bs, **self._batch_static)
      self._note_layer_rows(batch)
      slots_of = getattr(self.model, 'embedding_slots', None)
      if slots_of is not None:
        with scope('sampler'):
          counters['embedding_rows'] = self._embedding_rows(
              slots_of(batch), out)
      return batch, y, counters

    sp = P(self.axis)
    def etype_spec(e):
      d = dict(indptr=sp, indices=sp, edge_ids=sp, local_row=sp,
               node_pb=P())
      if g.graphs[e].edge_weights is not None:
        d['edge_weights'] = sp
      return d
    def store_spec(st):
      d = dict(array=sp, id2index=sp, feat_pb=sp)
      if st.cold_array is not None:  # pinned-host offloaded cold block
        d['cold'] = sp
      return d
    specs = dict(
        shards={e: etype_spec(e) for e in etypes},
        feats={t: store_spec(st) for t, st in feats.items()},
        efeats={e: store_spec(efeats[e]) for e in efeats},
        labels={t: P() for t in self.labels},
        sp=sp)

    def payloads():
      def etype_payload(e):
        d = dict(indptr=g.graphs[e].indptr, indices=g.graphs[e].indices,
                 edge_ids=g.graphs[e].edge_ids,
                 local_row=g.graphs[e].local_row,
                 node_pb=g.graphs[e].node_pb)
        if g.graphs[e].edge_weights is not None:
          d['edge_weights'] = g.graphs[e].edge_weights
        return d
      def store_payload(st):
        d = dict(array=st.array, id2index=st.id2index,
                 feat_pb=st.feat_pb)
        if st.cold_array is not None:
          d['cold'] = st.cold_array
        return d
      return (
          {e: etype_payload(e) for e in etypes},
          {t: store_payload(st) for t, st in feats.items()},
          {e: store_payload(efeats[e]) for e in efeats})

    return device_batch, specs, payloads

  def _build(self):
    model, tx, axis, bs = self.model, self.tx, self.axis, self.bs
    device_batch, specs, payloads = self._assembly()

    def device_step(params, opt_state, shards, feat_shards, efeat_shards,
                    labels, seeds, n_valid, key):
      batch, y, counters = device_batch(
          shards, feat_shards, efeat_shards, labels, seeds, n_valid, key)
      params, opt_state, loss = _hetero_update(
          model, tx, axis, bs, params, opt_state, batch, y, n_valid[0])
      out = (params, opt_state,
             (loss[None], jax.tree.map(lambda a: a[None], counters)))
      if self.keep_sample:
        out += (jax.tree_util.tree_map(lambda a: a[None], dict(
            node=batch.node_dict, node_count=batch.node_count_dict,
            row=batch.row_dict, col=batch.col_dict,
            edge_mask=batch.edge_mask_dict)),)
      return out

    sp = specs['sp']
    out_specs = (P(), P(), sp)
    fn = jax.shard_map(
        device_step, mesh=self.mesh,
        in_specs=(P(), P(), specs['shards'], specs['feats'],
                  specs['efeats'], specs['labels'], sp, sp, sp),
        out_specs=out_specs + ((sp,) if self.keep_sample else ()),
        check_vma=False)

    # an edge-seeded step owns its state (tables of parameters with their
    # moments: a second copy live across the update is gigabytes): the
    # caller steps on with what a call returns. The node-seeded drivers
    # hold on to what they passed in, so that program donates nothing
    import functools
    @functools.partial(jax.jit,
                       donate_argnums=(0, 1) if self._link else ())
    def step(params, opt_state, shards, feat_shards, efeat_shards,
             labels, seeds, n_valid, keys):
      self.step_traces += 1  # trace-time side effect only
      from ..obs.perf import count_compile
      count_compile('train.hetero_step')
      return fn(params, opt_state, shards, feat_shards, efeat_shards,
                labels, seeds, n_valid, keys)

    def run(params, opt_state, seeds, n_valid, keys):
      shards, feat_shards, efeat_shards = payloads()
      return step(params, opt_state, shards, feat_shards, efeat_shards,
                  self.labels, seeds, n_valid, keys)

    run.jitted = step   # the compiled program, for its cache's size
    return run

  # -- superstep: K hetero batches per donated dispatch ------------------

  def _build_superstep(self):
    """The fused hetero superstep program (ISSUE 14 tentpole, first
    move): lax.scan of the per-batch hetero body — per-edge-type
    collective sampling + per-type feature all_to_all + RGNN
    forward/backward + pmean'd update — with params/opt-state threaded
    through the carry (ops/superstep.py::superstep). K batches then cost ONE
    donated dispatch: the per-batch train loop's host round-trip, seed
    transfer, and dispatch latency amortize 1/K — exactly the homo
    superstep's collapse (parallel/train.py), now on the per-edge-type
    dispatch train VERDICT round 5 measured at 174 seeds/s."""
    model, tx, axis, bs = self.model, self.tx, self.axis, self.bs
    device_batch, specs, payloads = self._assembly()
    from ..ops.superstep import superstep

    def device_superstep(params, opt_state, shards, feat_shards,
                         efeat_shards, labels, seeds_stack,
                         n_valid_stack, keys):
      def body(params, opt_state, seeds, n_valid, key):
        # a scanned batch keeps its loss and drops what it counted
        batch, y, _ = device_batch(
            shards, feat_shards, efeat_shards, labels, seeds, n_valid,
            key)
        params, opt_state, loss = _hetero_update(
            model, tx, axis, bs, params, opt_state, batch, y, n_valid[0])
        return params, opt_state, loss[None]

      run = superstep(body)
      return run(params, opt_state, seeds_stack, n_valid_stack, keys)

    stacked = P(None, self.axis)
    fn = jax.shard_map(
        device_superstep, mesh=self.mesh,
        in_specs=(P(), P(), specs['shards'], specs['feats'],
                  specs['efeats'], specs['labels'], stacked, stacked,
                  stacked),
        out_specs=(P(), P(), stacked),
        check_vma=False)

    import functools
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, shards, feat_shards, efeat_shards,
             labels, seeds_stack, n_valid_stack, keys):
      self.superstep_traces += 1  # trace-time side effect only
      from ..obs.perf import count_compile
      count_compile('train.hetero_superstep')
      return fn(params, opt_state, shards, feat_shards, efeat_shards,
                labels, seeds_stack, n_valid_stack, keys)

    def run(params, opt_state, seeds_stack, n_valid_stack, keys):
      shards, feat_shards, efeat_shards = payloads()
      return step(params, opt_state, shards, feat_shards, efeat_shards,
                  self.labels, seeds_stack, n_valid_stack, keys)

    return run

  def superstep(self, params, opt_state, seeds_stack, n_valid_stack,
                keys):
    """Run T hetero training steps in ONE donated dispatch.

    seeds_stack: [T, n_dev * bs] shard-major per batch; n_valid_stack:
    [T, n_dev]; keys: [T, n_dev] PRNG keys (batch t on device d
    consumes keys[t, d], exactly as T sequential ``__call__``\\ s
    would). Params/opt-state are DONATED — reuse the returned ones.
    Returns (params, opt_state, loss [T, n_dev]). Steady state is one
    dispatch per T batches — ``dispatches_per_step`` drops from 1 to
    1/T — with zero recompiles across calls of the same T
    (``superstep_traces`` stays flat; a ragged epoch tail traces once
    more by design, like the homo superstep)."""
    if self._link:
      raise NotImplementedError(
          'an edge-seeded step (neg_sampling given) runs per batch, '
          'through __call__')
    if self._superstep_fn is None:
      self._superstep_fn = self._build_superstep()
    sh = NamedSharding(self.mesh, P(None, self.axis))
    seeds = jax.device_put(
        jnp.asarray(np.asarray(seeds_stack).reshape(
            len(seeds_stack), -1), jnp.int32), sh)
    nv = jax.device_put(jnp.asarray(n_valid_stack, jnp.int32), sh)
    keys = jax.device_put(keys, sh)
    params, opt_state = replicate((params, opt_state), self.mesh)
    from ..obs import get_registry, get_tracer
    tracer = get_tracer()
    _synced = {}
    with tracer.span('train.hetero_superstep', k=int(seeds.shape[0]),
                     sync=lambda: _synced.get('loss')):
      params, opt_state, loss = self._superstep_fn(params, opt_state,
                                                   seeds, nv, keys)
      _synced['loss'] = loss
    if tracer.enabled:
      get_registry().set('train_hetero_superstep_traces',
                         float(self.superstep_traces))
    return params, opt_state, loss

  def __call__(self, params, opt_state, seeds, n_valid_per_device, key):
    n_dev = self.mesh.shape[self.axis]
    shard = NamedSharding(self.mesh, P(self.axis))
    from ..obs import get_tracer
    tracer = get_tracer()
    _synced = {}
    # the spans of SPMDSageTrainStep.__call__: an idle gap of the device
    # is named after the child that covers it
    with tracer.span('train.step', sync=lambda: _synced.get('loss')):
      with tracer.span('train.step/put'):
        seeds = np.asarray(seeds)
        seeds = jax.device_put(jnp.asarray(
            seeds.reshape(-1, 2) if self._link else seeds.reshape(-1),
            jnp.int32), shard)
        nv = jax.device_put(jnp.asarray(n_valid_per_device, jnp.int32),
                            shard)
        keys = jax.random.split(key, n_dev)
        params, opt_state = replicate((params, opt_state), self.mesh)
      with tracer.span('train.step/dispatch'):
        out = self._step_fn(params, opt_state, seeds, nv, keys)
        params, opt_state, (loss, counted) = out[:3]
        if self.keep_sample:
          self.last_sample = out[3]
      self._keep_counters(counted)
      _synced['loss'] = loss
    return params, opt_state, loss

  def _by_relation_and_hop(self, by_relation):
    """``[R, H]`` from the sampler's ``num_sampled_edges`` or
    ``hop_rows_read``, which hold for each relation the hops it is read
    in: rows in the order of ``counter_edge_types``, 0 for a hop a
    relation is not read in (no width in ``edge_hop_offsets_dict``)."""
    offsets = self._batch_static['edge_hop_offsets_dict']
    rows = []
    for e in self.counter_edge_types:
      read = iter(by_relation[e])
      rows.append(jnp.stack([next(read) if width else jnp.int32(0)
                             for width in np.diff(offsets[e])]))
    return jnp.stack(rows)

  def _embedding_rows(self, slots, out):
    """``[T]``: the distinct rows of each type's table that a step reads,
    from what the model says it reads of a type's node slots
    (``embedding_slots``: a hop prefix under the node trim, or every
    slot): the live nodes among them."""
    return jnp.stack([
        jnp.minimum(out['node_count'][t], slots.get(t, 0))
        for t in self.counter_node_types]).astype(jnp.int32)

  def link_counters(self) -> dict:
    """What the newest edge-seeded step counted, a device an entry, read
    back from the device (it waits for that step): a view of
    :meth:`counters`' newest entry, as
    ``SPMDSageTrainStep.link_counters``. ``negatives_rejected``,
    ``negatives_padded``, ``seed_unique`` (``[2]``: distinct valid
    sources and destinations, hop 0 of their types' ``nodes_by_hop``),
    ``embedding_rows`` (``[T]``, for a model with tables) and, built
    with ``keep_seeds``, ``seeds`` (``[4B]``: ``[src; neg_src; dst;
    neg_dst]``)."""
    if not self._link or not self._counted:
      raise RuntimeError('no edge-seeded step has run')
    return self._newest_counters(
        ('negatives_rejected', 'negatives_padded', 'seed_unique',
         'embedding_rows', 'seeds'))

  def counter_slots(self) -> dict:
    """The contract of :meth:`StepCounters.counter_slots`: by type the
    node slots of each hop (``node_hop_offsets_dict``), by relation the
    edge slots (``edge_hop_offsets_dict``) and the slots of the frontier
    it expands, rows in the order of ``counter_node_types`` and
    ``counter_edge_types``; where the node
    stores serve in place, by type the chunks its request slots are
    served in; of an edge-seeded step the ``NEG_TRIALS x B`` proposals,
    the ``B`` negatives and the ``2B`` seed slots of each end, and for a
    model with tables each table's rows."""
    from ..parallel.dist_feature import serve_chunks
    static = self._batch_static
    slots = dict(
        nodes_by_hop=np.stack([
            np.diff(static['node_hop_offsets_dict'][t], prepend=0)
            for t in self.counter_node_types]).astype(np.int64),
        edges_by_hop=np.stack([
            np.diff(static['edge_hop_offsets_dict'][e])
            for e in self.counter_edge_types]).astype(np.int64),
        hop_rows_read=np.asarray(
            [self._frontier_slots[e] for e in self.counter_edge_types],
            np.int64))
    if self._stores_in_place:
      slots['store_chunks'] = np.asarray(
          [serve_chunks(self.node_budget[t])
           for t in self.counter_node_types if t in self.features],
          np.int64)
    if self._link:
      slots.update(
          negatives_rejected=np.int64(NEG_TRIALS * self.bs),
          negatives_padded=np.int64(self.bs),
          seed_unique=np.full(2, 2 * self.bs, np.int64))
    tables = getattr(self.model, 'embedding_tables', None)
    if tables is not None:
      slots['embedding_rows'] = np.asarray(
          [tables.get(t, 0) for t in self.counter_node_types], np.int64)
    return slots

  def scope_profile(self, params, opt_state, batches) -> dict:
    """Device time by layer of the per-batch step, from a profiler
    session of its own: drives ``self(params, opt_state, *batch)`` over
    ``batches`` (an iterable of ``(seeds, n_valid, key)``) and returns
    what ``obs.device.reduce_scopes`` makes of the trace: the contract of
    ``SPMDSageTrainStep.scope_profile``. The state it is given is stepped
    and thrown away."""
    from ..obs.device import scope_profile
    return scope_profile(self, params, opt_state, batches,
                         step_program='jit_step')

  # -- evaluation (reference dist_train_rgnn.py evaluate loop) -----------

  def _build_eval(self):
    """Forward-only SPMD step returning (correct, total) mesh-summed."""
    model, axis, bs = self.model, self.axis, self.bs
    device_batch, specs, payloads = self._assembly()

    def device_eval(params, shards, feat_shards, efeat_shards, labels,
                    seeds, n_valid, key):
      batch, y, _ = device_batch(
          shards, feat_shards, efeat_shards, labels, seeds, n_valid, key)
      logits = model.apply(params, batch)
      mask = jnp.arange(bs) < n_valid[0]
      correct = jnp.where(mask, jnp.argmax(logits, -1) == y, False)
      correct = jax.lax.psum(correct.sum(), axis)
      total = jax.lax.psum(mask.sum(), axis)
      return correct[None], total[None]

    sp = specs['sp']
    fn = jax.shard_map(
        device_eval, mesh=self.mesh,
        in_specs=(P(), specs['shards'], specs['feats'], specs['efeats'],
                  specs['labels'], sp, sp, sp),
        out_specs=(sp, sp), check_vma=False)

    jfn = jax.jit(fn)

    def run(params, seeds, n_valid, keys):
      shards, feat_shards, efeat_shards = payloads()
      return jfn(params, shards, feat_shards, efeat_shards, self.labels,
                 seeds, n_valid, keys)

    return run

  def eval_step(self, params, seeds, n_valid_per_device, key):
    """Forward-only accuracy over one seed batch; returns
    (num_correct, num_total) summed over the mesh."""
    if self._eval_fn is None:
      self._eval_fn = self._build_eval()
    n_dev = self.mesh.shape[self.axis]
    shard = NamedSharding(self.mesh, P(self.axis))
    seeds = jax.device_put(
        jnp.asarray(np.asarray(seeds).reshape(-1), jnp.int32), shard)
    nv = jax.device_put(jnp.asarray(n_valid_per_device, jnp.int32),
                        shard)
    keys = jax.random.split(key, n_dev)
    correct, total = self._eval_fn(params, seeds, nv, keys)
    # every lane carries the same psum; read a process-LOCAL shard so
    # multihost runs (where the global array spans other processes)
    # can fetch it
    return (int(np.asarray(correct.addressable_shards[0].data)[0]),
            int(np.asarray(total.addressable_shards[0].data)[0]))
