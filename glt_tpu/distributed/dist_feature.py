"""DistFeature — partitioned feature store with collective lookup.

Reference: graphlearn_torch/python/distributed/dist_feature.py:69-452.
The design kept (per SURVEY.md §7) is the all2all path
(dist_feature.py:270-366); the rpc path has no TPU analogue. Unlike
parallel.ShardedFeature (uniform range sharding), this store follows an
arbitrary *feature partition book* — including hot-cache rewrites where
a remote row is also cached locally (cat_feature_cache,
partition/base.py:866-907): the PB maps each id to a serving partition
and the per-partition dense id2index maps it to the local row.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.collectives import all_to_all, bucket_by_owner, unbucket
from ..parallel.dist_feature import serve_live_chunks
from ..utils import as_numpy
from .dist_graph import _pb_dense


def _flag_lanes(flag) -> np.ndarray:
  """Global lane indices where a sharded bool array is True, collected
  from this process's addressable shards."""
  lanes = []
  for s in flag.addressable_shards:
    nz = np.nonzero(np.asarray(s.data))[0]
    if nz.size:
      lanes.append((s.index[0].start or 0) + nz)
  return (np.concatenate(lanes) if lanes else np.zeros(0, np.int64))


#: (rows [M, D], index [M]) — a partition's contribution to a lookup,
#: positions indexing into the requesting batch (reference
#: dist_feature.py:37-41 PartialFeature). The collective path stitches
#: positionally inside the program; this alias types the HOST-side
#: surfaces (cold_get / cold_fetcher payloads).
PartialFeature = Tuple[np.ndarray, np.ndarray]


class DistFeature:
  """Stacked per-partition feature blocks, sharded over the mesh.

  Args:
    mesh: device mesh; axis size == number of partitions.
    parts: per-partition (feats [R_p, D], id2index [N]) — id2index maps a
      global id to its row in this partition's block (-1 if absent).
    feat_pb: the feature partition book(s). Cache-rewritten PBs differ
      per partition (each marks its own cached remote rows as local,
      reference base.py:903-905), so this is a list of one PB per
      partition (a single PB is broadcast); routing uses the
      *requesting* device's book, exactly like the reference workers.
    num_ids: global id-space size.
  """

  def __init__(self, mesh: Mesh, parts: Sequence, feat_pb,
               num_ids: int, axis: str = 'data', dtype=None,
               split_ratio: float = 1.0,
               hot_counts: Optional[Sequence[int]] = None,
               cold_fetcher=None, bucket_cap: int = 0,
               host_offload: Optional[bool] = None):
    n_parts = len(parts)
    assert mesh.shape[axis] == n_parts
    rows_max = max(max(f.shape[0] for f, _ in parts), 1)
    if hot_counts is None:
      hot_counts = [int(round(f.shape[0] * float(split_ratio)))
                    for f, _ in parts]
    spill = any(h < f.shape[0] for h, (f, _) in zip(hot_counts, parts))
    self._finish_init(mesh, axis, num_ids, parts[0][0].shape[1],
                      rows_max, n_parts,
                      hot_counts=hot_counts, cold_fetcher=cold_fetcher,
                      spill=spill, bucket_cap=bucket_cap)
    if not isinstance(feat_pb, (list, tuple)):
      feat_pb = [feat_pb] * n_parts
    feats_l, maps_l, pbs_l = [], [], []
    for p, (feats, id2index) in enumerate(parts):
      feats = as_numpy(feats)
      if dtype is not None:
        feats = feats.astype(dtype)
      hot = self.hot_counts[p]
      pb_dense = _pb_dense(feat_pb[p], self.num_ids)
      pbs_l.append(pb_dense)
      if self._spill:
        # every local partition keeps its host routing book: a
        # fully-resident requester can still route a lane to a spilled
        # owner, and the host phase resolves by the requester's book
        self._host_pb[p] = pb_dense
      if hot < feats.shape[0]:   # spill: cold rows stay host-resident
        self._host_cold[p] = feats[hot:]
        self._host_id2index[p] = as_numpy(id2index).astype(np.int32)
      feats = feats[:hot]
      pad = self.hot_max - feats.shape[0]
      if pad:
        feats = np.concatenate(
            [feats, np.zeros((pad, feats.shape[1]), feats.dtype)])
      m = as_numpy(id2index).astype(np.int32)
      if m.shape[0] < self.num_ids:
        m = np.concatenate(
            [m, np.full(self.num_ids - m.shape[0], -1, np.int32)])
      feats_l.append(feats)
      maps_l.append(m[:self.num_ids])
    shard = NamedSharding(mesh, P(axis))
    self.array = jax.device_put(np.stack(feats_l), shard)  # [P, Rh, D]
    self.id2index = jax.device_put(np.stack(maps_l), shard)  # [P, N]
    self.feat_pb = jax.device_put(np.stack(pbs_l), shard)    # [P, N]
    # Host-offload (reference unified_tensor.cu:202-231 UVA analog, see
    # parallel.ShardedFeature): the cold blocks become one stacked
    # pinned-host array gathered INSIDE the compiled program, so fused
    # SPMD train steps can consume spilled stores and lookup() needs no
    # host phase. Default on when spilling (GLT_HOST_OFFLOAD=0 or
    # host_offload=False opt out).
    from ..utils.offload import maybe_pin_host, offload_requested
    if offload_requested(host_offload, self._spill) and self._host_cold:
      c_max = max(c.shape[0] for c in self._host_cold.values())
      np_dtype = np.dtype(self.array.dtype)
      stack = np.zeros((n_parts, c_max, self.feature_dim), np_dtype)
      for p, c in self._host_cold.items():
        stack[p, :c.shape[0]] = c
      self.cold_array = maybe_pin_host(
          lambda: jax.device_put(
              stack, NamedSharding(mesh, P(axis),
                                   memory_kind='pinned_host')),
          host_offload)
      if self.cold_array is not None:
        # host-phase state (and the cold_get rpc surface) is unused
        # when cold rows are served in-program; keeping the numpy
        # blocks would double the cold footprint in host RAM
        self._host_cold = {}
        self._host_id2index = {}
        self._host_pb = {}
      self._build_lookup_fn()

  def _finish_init(self, mesh: Mesh, axis: str, num_ids: int,
                   feat_dim: int, rows_max: int, n_parts: int,
                   hot_counts=None, cold_fetcher=None,
                   spill=None, bucket_cap: int = 0):
    """Non-array state shared by __init__ and every alternate builder.
    ANY new scalar/config field must be set here, so a builder that
    assembles the arrays differently (e.g. the multihost
    process-local path) can never miss it."""
    self.mesh = mesh
    self.axis = axis
    self.num_ids = int(num_ids)
    self.feature_dim = int(feat_dim)
    self.rows_max = int(rows_max)
    self.num_partitions = int(n_parts)
    # host-spill state (UnifiedTensor pinned-CPU shard analogue,
    # reference unified_tensor.cu:202-231): rows [hot_p, R_p) of each
    # partition's block stay in that process's host RAM. hot_counts ==
    # rows_max everywhere (the default) means fully device-resident.
    if hot_counts is None:
      hot_counts = [rows_max] * n_parts
    self.hot_counts = np.asarray(hot_counts, np.int32)
    self.hot_max = max(1, int(self.hot_counts.max()))
    if spill is None:
      spill = bool((self.hot_counts < rows_max).any())
    self._spill = spill
    self._host_cold = {}      # part -> np [R_p - hot_p, D]
    self._host_id2index = {}  # part -> np [N] (local partitions only)
    self._host_pb = {}        # part -> np [N] requester routing book
    self._cold_fetcher = cold_fetcher
    # bucket_cap < B caps each per-peer request bucket (see
    # parallel.ShardedFeature.bucket_cap); lookup_local drains the
    # overflow in-program (round loop + pmax round count)
    self.bucket_cap = int(bucket_cap)
    # the cap is baked into the shard_map trace on first lookup; a later
    # mutation would silently keep routing with the old cap — record
    # the cap actually traced and refuse mismatched lookups (lookup())
    self._traced_cap = None
    self._hot_counts_dev = jnp.asarray(self.hot_counts)
    # stacked pinned-host cold blocks [P, C_max, D]; builders that
    # host-offload set this after assembling the arrays and rebuild
    self.cold_array = None
    self._build_lookup_fn()

  def _call_lookup_fn(self, ids, valid):
    """Dispatch to the compiled lookup with the operand list matching
    the _build_lookup_fn variant in effect."""
    if self.cold_array is not None:
      return self._lookup_fn(self.array, self.id2index, self.feat_pb,
                             self.cold_array, ids, valid)
    return self._lookup_fn(self.array, self.id2index, self.feat_pb,
                           ids, valid)

  def _build_lookup_fn(self):
    """(Re)build the compiled whole-mesh lookup. Compiled once per
    build; rebuilding shard_map per call would re-trace."""
    sp = P(self.axis)
    if self.cold_array is not None:
      # offloaded: cold lanes are served in-program — single output
      self._lookup_fn = jax.jit(jax.shard_map(
          lambda f, m, pb, c, i, v: self.lookup_local(
              f[0], m[0], pb[0], i, v, cold_shard=c[0]),
          mesh=self.mesh, in_specs=(sp,) * 6, out_specs=sp,
          check_vma=False))
      return
    self._lookup_fn = jax.jit(jax.shard_map(
        lambda f, m, pb, i, v: self.lookup_local(f[0], m[0], pb[0], i, v),
        mesh=self.mesh,
        in_specs=(sp, sp, sp, sp, sp),
        out_specs=(sp if not self._spill else (sp, sp)),
        check_vma=False))

  # -- in-shard lookup (call inside shard_map) ---------------------------

  @property
  def in_place(self) -> bool:
    """One partition holds every row on the device: ``lookup_local``
    serves in place, the chunks of request slots that hold a request."""
    return self.num_partitions == 1 and not self._spill

  def lookup_local(self, feat_shard, map_shard, pb, ids, valid,
                   axis_name: Optional[str] = None, cold_shard=None,
                   counters: bool = False):
    """feat_shard: [Rh, D] hot block; map_shard: [N]; pb: [N] — THIS
    device's routing book; ids/valid: [B]. Returns [B, D] (zeros where
    invalid). With host spill active and no ``cold_shard``, returns
    ([B, D], cold_flag [B]): flagged lanes are valid ids whose row
    lives in the owner's host shard — served as zeros here and resolved
    by lookup()'s host phase. With ``cold_shard`` (this device's
    pinned-host [C_max, D] block), cold lanes are instead served
    in-program by a compute_on('device_host') gather and the return is
    the plain [B, D] — the form fused train steps consume.

    With ``bucket_cap`` set the overflow drain runs IN-PROGRAM (round k
    ships bucket ranks [k*cap, (k+1)*cap); the round count is the
    mesh-wide pmax of bucket occupancy over the cap) — no host replay
    of the routing, no retained books, and fused train steps can use
    capped stores (see parallel.collectives.drain_rounds).

    ``counters``: in place (one partition, nothing spilled) also return
    ``dict(store_chunks=...)``, the chunks of request slots that held a
    valid request and were gathered, of ``serve_chunks(B)``
    (``parallel.dist_feature.serve_live_chunks``); the exchange counts
    nothing and asking raises."""
    from ..parallel.collectives import bucket_payload, capped_drain
    ax = axis_name or self.axis
    n = self.num_partitions
    b = ids.shape[0]
    if self.in_place:
      # one partition holds every row: the rows are read in request
      # order, with nothing to bucket, exchange or stitch; the result is
      # the bucketed path's bit for bit
      def serve(ids, valid):
        rows = jnp.take(map_shard, jnp.clip(ids, 0, self.num_ids - 1),
                        mode='clip')
        ok = valid & (ids >= 0) & (rows >= 0)
        safe_rows = jnp.clip(rows, 0, self.hot_max - 1)
        rows_out = jnp.take(feat_shard, safe_rows, axis=0)
        return jnp.where(ok[:, None], rows_out, 0)

      with jax.named_scope('serve'):
        rows, chunks = serve_live_chunks(serve, ids, valid,
                                         self.feature_dim, feat_shard.dtype)
      return (rows, dict(store_chunks=chunks)) if counters else rows
    if counters:
      raise ValueError(
          'a store over more than one partition, or one that spills, '
          'exchanges: it gathers no chunks, so it has no counters')
    # stages as parallel/dist_feature.py names them, below the caller's
    # ``feature_store`` scope
    with jax.named_scope('bucket'):
      owner = jnp.take(pb, jnp.clip(ids, 0, self.num_ids - 1),
                       mode='clip')
      owner = jnp.where(valid, owner, n)
      cap = (self.bucket_cap if 0 < self.bucket_cap < b else 0)
      _, meta = bucket_by_owner(ids, owner, n, capacity=cap)
    eff_cap = cap if cap else b
    two_outputs = self._spill and cold_shard is None

    def round_serve(base):
      with jax.named_scope('bucket'):
        req = bucket_payload(ids, meta, n, fill_value=-1,
                             capacity=eff_cap, round_offset=base)
      with jax.named_scope('exchange'):
        req_in = all_to_all(req, ax)                    # [P, C]
      with jax.named_scope('serve'):
        flat = req_in.reshape(-1)
        rows = jnp.take(map_shard, jnp.clip(flat, 0, self.num_ids - 1),
                        mode='clip')
        ok = (flat >= 0) & (rows >= 0)
        if self._spill:
          my_hot = jnp.take(self._hot_counts_dev, jax.lax.axis_index(ax))
          cold = ok & (rows >= my_hot)
          ok = ok & (rows < my_hot)
        safe_rows = jnp.clip(rows, 0, self.hot_max - 1)
        rows_out = jnp.take(feat_shard, safe_rows, axis=0)
        served = jnp.where(ok[:, None], rows_out, 0)
      if not self._spill:
        with jax.named_scope('exchange'):
          resp = all_to_all(served.reshape(n, -1, self.feature_dim), ax)
        with jax.named_scope('unbucket'):
          return unbucket(resp, meta, n, round_offset=base)
      if cold_shard is not None:
        # serve the owner's spilled rows from pinned host memory
        # without leaving the program: index arithmetic stays on
        # device, the gather runs host-side (raw indexing — bounds ops
        # would materialize device-space constants inside the host
        # region)
        from jax.experimental import compute_on
        cold_idx = jnp.clip(rows - my_hot, 0, cold_shard.shape[0] - 1)
        idx_h = jax.device_put(cold_idx, jax.memory.Space.Host)
        with compute_on.compute_on('device_host'):
          cold_out = cold_shard[idx_h]
        cold_out = jax.device_put(cold_out, jax.memory.Space.Device)
        served = jnp.where(cold[:, None],
                           cold_out.astype(served.dtype), served)
        resp = all_to_all(served.reshape(n, -1, self.feature_dim), ax)
        return unbucket(resp, meta, n, round_offset=base)
      # ride the cold flag back as one extra response column so the
      # requester learns hot/cold without holding the owner's id2index
      payload = jnp.concatenate(
          [served, cold[:, None].astype(served.dtype)], axis=1)
      resp = all_to_all(payload.reshape(n, -1, self.feature_dim + 1),
                        ax)
      full = unbucket(resp, meta, n, round_offset=base)
      return full[:, :self.feature_dim], full[:, self.feature_dim] > 0

    if not cap:
      return round_serve(0)
    zeros_feat = jnp.zeros((b, self.feature_dim), feat_shard.dtype)
    zeros = ((zeros_feat, jnp.zeros((b,), bool)) if two_outputs
             else zeros_feat)
    return capped_drain(round_serve, meta, n, eff_cap, ax, zeros)

  def lookup(self, ids, valid=None) -> jax.Array:
    """Whole-mesh lookup: ids [P * B] shard-major.

    Capped stores drain their overflow inside the compiled program
    (lookup_local runs the round loop on device) — one call regardless
    of skew. With host spill, flagged cold lanes are resolved from the
    host shards at the end; both compose: a lane that overflowed in
    round k and turns out cold in round k+1 still resolves exactly
    once."""
    if self._traced_cap is None:
      self._traced_cap = self.bucket_cap
    elif self.bucket_cap != self._traced_cap:
      raise RuntimeError(
          f'bucket_cap changed from {self._traced_cap} to '
          f'{self.bucket_cap} after the first lookup compiled it in; '
          'the cached program would keep routing with the old cap. '
          'Set bucket_cap before the first lookup, or build a new '
          'store.')
    ids_np = as_numpy(ids).astype(np.int64)
    ids = jnp.asarray(ids_np, jnp.int32)
    if valid is None:
      valid_np = np.ones(ids_np.shape, bool)
    else:
      valid_np = as_numpy(valid).astype(bool)
    res = self._call_lookup_fn(ids, jnp.asarray(valid_np))
    if self._spill and self.cold_array is None:
      out, flag = res
      lanes = _flag_lanes(flag)
      if lanes.size:
        out = self._resolve_cold(out, lanes, ids_np)
      return out
    return res

  # -- host spill resolution ---------------------------------------------

  def _resolve_cold(self, out, lanes, ids_np) -> jax.Array:
    """Serve the flagged lanes from the host shards and merge on device.
    Cold lanes are zero in ``out`` (the device phase masks them), so the
    merge is one sharded add — no SPMD-hostile scatter. Remote-process
    partitions resolve through ``cold_fetcher(part, ids) -> [M, D]``
    (e.g. an rpc callee); local ones read the in-process block."""
    b = ids_np.shape[0] // self.num_partitions
    cold_ids = ids_np[lanes]
    dev_of = lanes // b
    owners = np.empty(lanes.shape[0], np.int64)
    for d in np.unique(dev_of):
      m = dev_of == d
      book = self._host_pb.get(int(d))
      if book is None:
        raise RuntimeError(
            f'cold lane routed by partition {d} but its host routing '
            'book is not in this process — build the store with '
            'host-spill in the owning process')
      owners[m] = book[np.clip(cold_ids[m], 0, self.num_ids - 1)]
    np_dtype = np.dtype(out.dtype)
    vals = np.zeros((lanes.shape[0], self.feature_dim), np_dtype)
    for p in np.unique(owners):
      m = owners == p
      p = int(p)
      if p in self._host_cold:
        rows = self._host_id2index[p][cold_ids[m]]
        vals[m] = self._host_cold[p][rows - int(self.hot_counts[p])]
      elif self._cold_fetcher is not None:
        vals[m] = self._cold_fetcher(p, cold_ids[m])
      else:
        raise RuntimeError(
            f'partition {p} holds cold rows in another process and no '
            'cold_fetcher is registered (see set_cold_fetcher)')
    delta = np.zeros((ids_np.shape[0], self.feature_dim), np_dtype)
    delta[lanes] = vals
    if jax.process_count() == 1:
      delta_arr = jax.device_put(delta, out.sharding)
    else:
      # flat [P*B, D] layout: supply this process's B-row blocks in
      # device order (global_from_local is for [P, ...] stacks)
      local = np.concatenate(
          [delta[d * b:(d + 1) * b]
           for d, dev in enumerate(self.mesh.devices.reshape(-1))
           if dev.process_index == jax.process_index()])
      delta_arr = jax.make_array_from_process_local_data(
          NamedSharding(self.mesh, P(self.axis)), local,
          global_shape=delta.shape)
    return out + delta_arr

  def set_cold_fetcher(self, fetcher) -> None:
    """Register the remote cold-row resolver:
    ``fetcher(partition: int, ids: np.int64 [M]) -> np [M, D]``.
    Wrap with :func:`resilient_cold_fetcher` for replica failover +
    bounded-staleness degradation on dead owners."""
    self._cold_fetcher = fetcher

  def cold_get(self, partition: int, ids: np.ndarray) -> np.ndarray:
    """Serve cold rows of a locally-held partition (the rpc-callee
    counterpart of ``cold_fetcher``; reference RpcFeatureLookupCallee,
    dist_feature.py:57-66). Only meaningful on the legacy host-phase
    path — host-offloaded stores serve cold rows in-program and free
    this surface's state."""
    if self.cold_array is not None:
      raise RuntimeError(
          'cold_get is the legacy host-phase rpc surface; this store '
          'host-offloads its cold rows (served in-program) and does '
          'not retain the numpy blocks — build with host_offload=False '
          'to use cold_get/cold_fetcher')
    rows = self._host_id2index[int(partition)][np.asarray(ids)]
    return self._host_cold[int(partition)][
        rows - int(self.hot_counts[int(partition)])]

  # -- builders ----------------------------------------------------------

  def collate_edge_attr(self, out: dict) -> None:
    """Attach ``out['edge_attr']`` gathered for the sampler output's
    padded [P, E] eids grid (one static-shape whole-mesh lookup —
    the shared collate used by every dist loader)."""
    eids = out['edge']
    ea = self.lookup(jnp.maximum(jnp.asarray(eids).reshape(-1), 0),
                     jnp.asarray(out['edge_mask']).reshape(-1))
    out['edge_attr'] = ea.reshape(tuple(eids.shape) + (-1,))

  @classmethod
  def from_dist_datasets(cls, mesh: Mesh, datasets, ntype=None,
                         axis: str = 'data', dtype=None,
                         kind: str = 'node',
                         cold_fetcher=None, split_ratio=None,
                         bucket_cap: int = 0,
                         host_offload: Optional[bool] = None):
    """Single-host simulation: build from every partition's DistDataset.
    Each partition Feature's own hot/cold split carries over: its cold
    rows become this store's host shard for that partition (beyond-HBM
    distributed features, reference unified_tensor.cu:202-231).
    ``split_ratio`` overrides the per-Feature split when given.

    ``kind='edge'`` builds the *edge*-feature store (id space = global
    edge ids, routed by the edge-feature partition book) — the TPU
    counterpart of the reference's edge DistFeature
    (dist_feature.py:69-452 with group='edge_feat'); ``ntype`` then
    selects the edge type for hetero datasets.
    """
    assert kind in ('node', 'edge')
    parts, pbs, hots = [], [], []
    num_ids = 0
    for ds in datasets:
      if kind == 'edge':
        feat = (ds.edge_features[ntype] if ntype is not None
                else ds.edge_features)
        pb = ds.get_edge_feat_pb(ntype)
      else:
        feat = (ds.node_features[ntype] if ntype is not None
                else ds.node_features)
        pb = ds.get_node_feat_pb(ntype)
      feat.lazy_init()
      pbs.append(pb)
      num_ids = max(num_ids, pb.table.shape[0])
      if feat.fully_device_resident:
        block = np.asarray(feat.device_part)
      else:  # reassemble [hot | cold] on host; __init__ re-splits.
        # _cold keeps the SOURCE dtype — cast it so a compression cast
        # (Feature(dtype=bf16)) survives instead of promoting the stack
        block = np.concatenate(
            [np.asarray(feat.device_part, dtype=feat.dtype),
             np.asarray(feat.cold_block_numpy(), dtype=feat.dtype)])
      hots.append(feat.hot_count if split_ratio is None
                  else int(round(block.shape[0] * float(split_ratio))))
      parts.append((block, feat._id2index))
    return cls(mesh, parts, pbs, num_ids, axis=axis, dtype=dtype,
               hot_counts=hots,
               cold_fetcher=cold_fetcher, bucket_cap=bucket_cap,
               host_offload=host_offload)


def resilient_cold_fetcher(fetchers, feature_dim: Optional[int] = None,
                           metrics=None, cache_capacity: int = 200_000):
  """Compose per-partition cold fetchers into one fault-tolerant
  ``fetcher(partition, ids) -> [M, D]`` for
  :meth:`DistFeature.set_cold_fetcher`.

  Args:
    fetchers: ``{partition: [fn, ...]}`` — each ``fn(ids) -> [M, D]``,
      primaries first, replicas after (build the list from
      ``rpc_sync_data_partitions``: every rank serving a partition is a
      replica of its rows).
    feature_dim: row width for zero-fill before any fetch succeeded.
    metrics: optional ServingMetrics — failovers and stale serves are
      counted there (the same counters the serving stack uses).

  Ladder per lookup: primary -> replicas in order (each connection
  failure recorded, first success wins and refreshes the staleness
  cache) -> cached rows + zero-fill for true misses. Raises only when
  degradation is impossible (no cache rows AND unknown row width).
  """
  from ..resilience import DegradedFeatureCache
  stale = DegradedFeatureCache(capacity=cache_capacity)
  if feature_dim is not None:
    stale.feature_dim = int(feature_dim)
  fetchers = {int(p): list(fs) for p, fs in fetchers.items()}

  def fetch(partition: int, ids: np.ndarray) -> np.ndarray:
    chain = fetchers.get(int(partition), [])
    last: Optional[BaseException] = None
    for k, fn in enumerate(chain):
      try:
        rows = np.asarray(fn(np.asarray(ids, np.int64)))
      except (ConnectionError, OSError) as e:
        last = e
        continue
      if k > 0 and metrics is not None:
        metrics.record_failover()
      stale.update(ids, rows)
      return rows
    return stale.serve_counted(
        ids, metrics, what=f'cold fetch(partition {partition})',
        cause=last)

  return fetch


def dist_feature_from_partitions_multihost(mesh, root_dir: str,
                                           ntype=None, axis: str = 'data',
                                           dtype=None,
                                           kind: str = 'node',
                                           split_ratio: float = 1.0,
                                           cold_fetcher=None,
                                           bucket_cap: int = 0,
                                           host_offload=None
                                           ) -> DistFeature:
  """Multi-host DistFeature: each process loads ONLY its partitions'
  feature blocks (cache-concat + PB rewrite included) and contributes
  them via process-local assembly; padding agreed with an allgather.
  Counterpart of dist_graph_from_partitions_multihost.

  ``split_ratio < 1`` spills each partition's cold tail to its OWN
  process's host RAM (beyond-HBM features). By default (host_offload
  auto) the cold tails become a pinned-host sharded array served
  in-program — each partition's cold rows live in its OWN process's
  host RAM and are gathered by its own device, so no cross-process
  fetch exists at all. With ``host_offload=False`` cross-process cold
  lookups instead need a ``cold_fetcher`` wired to the rpc fabric (see
  DistFeature.set_cold_fetcher / cold_get).

  ``kind='edge'`` builds the edge-feature store from the partitions'
  efeat blocks + edge partition books (``ntype`` then selects the edge
  type for hetero trees)."""
  assert kind in ('node', 'edge')
  import jax
  import jax.numpy as jnp
  from ..parallel.multihost import global_from_local
  from ..partition import cat_feature_cache, load_meta, load_partition
  meta = load_meta(root_dir)
  devices = mesh.devices.reshape(-1)
  n_parts = devices.shape[0]
  if meta['num_parts'] != n_parts:
    raise ValueError(
        f"mesh has {n_parts} devices but the partition dir holds "
        f"{meta['num_parts']} partitions")
  mine = [i for i, d in enumerate(devices)
          if d.process_index == jax.process_index()]

  blocks = {}
  num_ids = 0
  feat_dim = None
  local_max_rows = 0
  for p in mine:
    _, _, nfeat, efeat, node_pb, edge_pb = load_partition(root_dir, p)
    src, books = ((efeat, edge_pb) if kind == 'edge'
                  else (nfeat, node_pb))
    f = src[ntype] if isinstance(src, dict) and ntype is not None else src
    pb = (books[ntype] if isinstance(books, dict) and ntype is not None
          else books)
    if f is None:
      raise ValueError(
          f'partition {p} of {root_dir} holds no {kind} features '
          f'(ntype={ntype!r}); partition with '
          f'{"edge_feat" if kind == "edge" else "node_feat"} to use '
          f'kind={kind!r}')
    feats, ids, id2index, pb2 = cat_feature_cache(p, f, pb)
    blocks[p] = (feats, id2index, pb2)
    num_ids = max(num_ids, pb2.table.shape[0])
    feat_dim = feats.shape[1]
    local_max_rows = max(local_max_rows, feats.shape[0])

  spill = float(split_ratio) < 1.0
  # per-partition hot counts must be agreed globally (they are baked
  # into every process's trace); partitions are disjoint so a summed
  # allgather assembles the full [P] vector
  local_hot = np.zeros(n_parts, np.int64)
  for p in mine:
    r = blocks[p][0].shape[0]
    local_hot[p] = int(round(r * float(split_ratio))) if spill else r
  if jax.process_count() > 1:
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(
        jnp.asarray([local_max_rows, num_ids, feat_dim or 0]))
    arr = np.asarray(gathered)
    rows_max = int(arr[:, 0].max())
    num_ids = int(arr[:, 1].max())
    feat_dim = int(arr[:, 2].max())
    hot_counts = np.asarray(
        multihost_utils.process_allgather(jnp.asarray(local_hot))
    ).sum(axis=0)
  else:
    rows_max = max(local_max_rows, 1)
    hot_counts = local_hot
  pad_rows = int(hot_counts.max()) if spill else rows_max
  pad_rows = max(pad_rows, 1)

  store = DistFeature.__new__(DistFeature)
  store._finish_init(mesh, axis, num_ids, feat_dim, rows_max, n_parts,
                     hot_counts=hot_counts,
                     cold_fetcher=cold_fetcher, spill=spill,
                     bucket_cap=bucket_cap)

  feats_l, maps_l, pbs_l = [], [], []
  for p in mine:
    feats, id2index, pb2 = blocks[p]
    if dtype is not None:
      feats = feats.astype(dtype)
    pb_dense = _pb_dense(pb2, num_ids)
    if spill:
      store._host_pb[p] = pb_dense
      hot = int(hot_counts[p])
      if hot < feats.shape[0]:
        store._host_cold[p] = feats[hot:]
        store._host_id2index[p] = np.asarray(id2index).astype(np.int32)
      feats = feats[:hot]
    pad = pad_rows - feats.shape[0]
    if pad:
      feats = np.concatenate(
          [feats, np.zeros((pad, feats.shape[1]), feats.dtype)])
    m = np.asarray(id2index).astype(np.int32)
    if m.shape[0] < num_ids:
      m = np.concatenate([m, np.full(num_ids - m.shape[0], -1,
                                     np.int32)])
    feats_l.append(feats)
    maps_l.append(m[:num_ids])
    pbs_l.append(pb_dense)

  def stack_or_empty(parts, shape_tail, dtype_):
    if parts:
      return np.stack(parts)
    return np.zeros((0,) + shape_tail, dtype_)

  store.array = global_from_local(
      mesh, stack_or_empty(feats_l, (pad_rows, feat_dim), np.float32),
      axis)
  store.id2index = global_from_local(
      mesh, stack_or_empty(maps_l, (num_ids,), np.int32), axis)
  store.feat_pb = global_from_local(
      mesh, stack_or_empty(pbs_l, (num_ids,), np.int32), axis)
  from ..utils.offload import maybe_pin_host, offload_requested
  if offload_requested(host_offload, spill) and spill:
    # global cold capacity must be agreed (it is baked into every
    # process's trace); partitions are disjoint, so max-allgather
    local_cmax = max((c.shape[0] for c in store._host_cold.values()),
                     default=0)
    if jax.process_count() > 1:
      from jax.experimental import multihost_utils
      c_max = int(np.asarray(multihost_utils.process_allgather(
          jnp.asarray([local_cmax]))).max())
    else:
      c_max = local_cmax
    if c_max:
      np_dtype = np.dtype(store.array.dtype)
      local_stack = np.zeros((len(mine), c_max, feat_dim), np_dtype)
      for i, p in enumerate(mine):
        c = store._host_cold.get(p)
        if c is not None:
          local_stack[i, :c.shape[0]] = c
      store.cold_array = maybe_pin_host(
          lambda: global_from_local(mesh, local_stack, axis,
                                    memory_kind='pinned_host'),
          host_offload)
      if store.cold_array is not None:
        store._host_cold = {}
        store._host_id2index = {}
        if not store.bucket_cap:
          store._host_pb = {}
      store._build_lookup_fn()
  return store
