"""Sharded feature store with collective lookup — DistFeature, the SPMD way.

Reference: graphlearn_torch/python/distributed/dist_feature.py:69-452. The
reference looks up remote node features either by async RPC to the owner
(dist_feature.py:380-430) or — the design SURVEY.md §7 says to keep — by a
gloo all2all exchange (ids out, features back, dist_feature.py:270-366).
Here that exchange is the native formulation: the feature table is one
jax array row-sharded over the mesh ('range partition book': owner =
id // rows_per_shard), and lookup inside shard_map is

    bucket ids by owner -> all_to_all -> local gather -> all_to_all back
    -> positional un-bucket (the stitch, stitch_sample_results.cu analog)

with fixed-capacity buckets so shapes stay static: an even share of the
request vector a peer (exchange_cap), as many rounds as the fullest one
needs; a request's slot is its rank among its owner's requests (no sort:
collectives.rank_by_owner). The pack, the owner's local gather and the
stitch each visit only the chunks of SERVE_CHUNK slots that hold a
request, and the stitch writes the rows straight into the drain's carry.
On one shard there is one owner and the exchange would be the identity:
lookup_local then serves in place (the local gather alone), the same
rows bit for bit, and gathers only the chunks of request slots that
hold a valid request (serve_live_chunks).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.device import scope
from ..utils import as_numpy
from .collectives import live_chunks


def _more_rounds_global(more: bool) -> bool:
  """Agree a drain-loop continuation across processes. The serving path
  no longer needs this (lookup_local drains in-program with a pmax'd
  round count); kept for host-side analysis/benchmarks."""
  if jax.process_count() == 1:
    return more
  from jax.experimental import multihost_utils
  return bool(np.asarray(multihost_utils.process_allgather(
      jnp.asarray([1 if more else 0]))).max())


def overflow_lanes(owner_key: np.ndarray, n_shards: int, b: int,
                   cap: int) -> np.ndarray:
  """Host replay of the device bucketing: True where a valid request
  (owner_key < n_shards) ranks past its per-owner bucket capacity for
  its B-lane device block. The SERVING path no longer uses this (the
  drain runs in-program, see lookup_local); it remains for round-count
  analysis (benchmarks/bench_bucket_drain.py predicts the grid with
  it)."""
  over = np.zeros(owner_key.shape[0], bool)
  for lo in range(0, owner_key.shape[0], b):
    ok = owner_key[lo:lo + b]
    order = np.argsort(ok, kind='stable')
    osort = ok[order]
    counts = np.bincount(np.minimum(osort, n_shards),
                         minlength=n_shards + 1)[:n_shards]
    offsets = np.cumsum(counts) - counts
    pos = np.arange(ok.shape[0]) - offsets[
        np.minimum(osort, n_shards - 1)]
    blk = np.zeros(ok.shape[0], bool)
    blk[order] = (osort < n_shards) & (pos >= cap)
    over[lo:lo + b] = blk
  return over


#: request slots in one chunk of the in-place ``serve``, and of the
#: exchange's pack, owner's serve and stitch over more shards: the least
#: sum among 4,096 / 8,192 / 16,384 / 32,768 of a probe of the store alone on
#: a v5e over the cells' request vectors (PERF.md section 6, PR 36: 18.48
#: / 18.36 / 18.91 / 22.95 ms where the plain gathers read 67.05). A small
#: chunk wins where a node type fills one chunk (the pads that share it
#: are read), a large one where the live prefix is long (fewer trips).
SERVE_CHUNK = 8192


def serve_chunks(b: int) -> int:
  """Chunks that ``serve_live_chunks`` cuts ``b`` request slots into."""
  return max(1, -(-b // SERVE_CHUNK))


def serve_live_chunks(serve, ids, valid, feature_dim: int, dtype):
  """``(rows [B, D], chunks gathered)`` of an in-place store:
  ``serve(ids, valid)`` (the store's gather of any number of slots, zero
  where not valid) run on the chunks of ``SERVE_CHUNK`` consecutive
  request slots that hold a valid request, and on no other. A fused
  step's requests are a live prefix behind which two slots in three
  (GraphSAGE) to 24 in 25 (the typed steps) are pads, and a pad costs a
  row read like any request. The flags come from ``valid`` alone, so
  the rows are ``serve(ids, valid)``'s bit for bit for any mask: a
  scattered one flags more chunks. The rows fill a zero ``[B, D]``
  buffer in place, in one ``lax.while_loop`` over the flagged chunks;
  the last chunk of a ``B`` that ``SERVE_CHUNK`` does not divide starts
  at ``B - SERVE_CHUNK`` and writes its neighbour's rows again. Up to
  ``SERVE_CHUNK`` slots are one chunk and one plain gather."""
  b = ids.shape[0]
  c = SERVE_CHUNK
  if b <= c:
    return serve(ids, valid), valid.any().astype(jnp.int32)
  starts, count = live_chunks(valid, c)

  def gather(carry):
    k, rows = carry
    lo = starts[k]
    got = serve(jax.lax.dynamic_slice(ids, (lo,), (c,)),
                jax.lax.dynamic_slice(valid, (lo,), (c,)))
    return k + 1, jax.lax.dynamic_update_slice(rows, got, (lo, 0))

  _, rows = jax.lax.while_loop(
      lambda carry: carry[0] < count, gather,
      (jnp.int32(0), jnp.zeros((b, feature_dim), dtype)))
  return rows, count


def require_device_resident(store, ctx: str) -> None:
  """Fused SPMD train steps gather features with ``lookup_local`` inside
  one jitted program, where the host-spill phase can never run — a
  spilled store there would silently train on zero vectors for every
  cold row. Trainers call this up front to fail loudly instead."""
  if store is None:
    return
  if getattr(store, '_spill', False) and \
      getattr(store, 'cold_array', None) is None:
    raise NotImplementedError(
        f'{ctx}: this train step runs sampling+gather+update as one '
        'jitted SPMD program and cannot resolve host-spilled (cold) '
        'feature rows; use host_offload=True (pinned-host cold block '
        'served inside the program via compute_on), a device-resident '
        'store (split_ratio=1.0), or the loader-driven path '
        '(DistLoader / NodeLoader collate, which resolves cold rows '
        'on host between device calls)')
  # bucket_cap needs NO rejection here: lookup_local drains capped
  # buckets in-program (round loop + pmax round count), so fused steps
  # serve overflow lanes exactly — including combined with host-offload


class ShardedFeature:
  """[N, D] feature table row-sharded over one mesh axis.

  The partition book is the range rule: owner(id) = id // rows_per_shard
  (a RangePartitionBook with uniform bounds, reference
  partition/partition_book.py:6-47).
  """

  def __init__(self, feats, mesh: Mesh, axis: str = 'data', dtype=None,
               split_ratio: float = 1.0, bucket_cap: int = 0,
               host_offload: Optional[bool] = None):
    feats = as_numpy(feats)
    self.mesh = mesh
    self.axis = axis
    n_shards = mesh.shape[axis]
    n = feats.shape[0]
    self.num_rows = n
    self.rows_per_shard = math.ceil(n / n_shards)
    pad = self.rows_per_shard * n_shards - n
    if pad:
      feats = np.concatenate(
          [feats, np.zeros((pad,) + feats.shape[1:], feats.dtype)])
    if dtype is not None:
      feats = feats.astype(dtype)
    self.feature_dim = feats.shape[1]
    # bucket_cap < B caps each per-peer request bucket: the two
    # all_to_alls then move n_shards*C elements per device instead of
    # the [P, B] worst case (VERDICT r2: P-times the necessary ICI
    # bytes), and the overflow drains in-program (lookup_local). Left
    # at 0 the cap follows from the request count and the shard count
    # (exchange_cap).
    self.bucket_cap = int(bucket_cap)
    # cap is baked into the shard_map trace on first lookup; mutating it
    # later would desync the host drain from the compiled routing —
    # lookup() records the traced value and rejects mismatches
    self._traced_cap = None
    # host spill (reference unified_tensor.cu:202-231 pinned-CPU shard):
    # rows [hot_count, rows_per_shard) of EVERY shard stay host-side;
    # the uniform per-shard split keeps hot-ness arithmetic, so the
    # requester resolves cold lanes without any device flag. Cold
    # blocks are numpy views of ``feats`` — no extra host copy.
    self.split_ratio = float(split_ratio)
    self.hot_count = (self.rows_per_shard if self.split_ratio >= 1.0
                      else max(1, int(round(self.rows_per_shard
                                            * self.split_ratio))))
    self._spill = self.hot_count < self.rows_per_shard
    if self._spill:
      self._host_cold = [
          feats[p * self.rows_per_shard + self.hot_count:
                (p + 1) * self.rows_per_shard]
          for p in range(n_shards)]
      hot = np.concatenate([
          feats[p * self.rows_per_shard:
                p * self.rows_per_shard + self.hot_count]
          for p in range(n_shards)])
    else:
      self._host_cold = None
      hot = feats
    self.array = jax.device_put(
        hot, NamedSharding(mesh, P(axis)))
    # Host-offload: the cold block lives in PINNED HOST memory as a jax
    # array and is gathered INSIDE the compiled program via
    # compute_on('device_host') — the TPU-native analog of the
    # reference's UVA zero-copy CPU shard (unified_tensor.cu:202-231:
    # cudaHostRegisterMapped + device-side GatherTensorKernel reads
    # across PCIe). This is what lets fused SPMD train steps consume
    # spilled stores; without it cold rows resolve in lookup()'s host
    # phase between device calls. Default: on when spilling (opt out
    # with GLT_HOST_OFFLOAD=0 or host_offload=False).
    from ..utils.offload import maybe_pin_host, offload_requested
    self.cold_array = None
    if offload_requested(host_offload, self._spill) and self._spill:
      self.cold_array = maybe_pin_host(
          lambda: jax.device_put(
              np.concatenate(self._host_cold),
              NamedSharding(mesh, P(axis), memory_kind='pinned_host')),
          host_offload)
      if self.cold_array is not None:
        # the numpy blocks are the host-phase path's state; keeping
        # them would double the cold footprint in host RAM
        self._host_cold = None
    # compiled once; rebuilding shard_map per call would re-trace
    if self.cold_array is not None:
      self._lookup_fn = jax.jit(jax.shard_map(
          lambda shard, cold_shard, i, v: self.lookup_local(
              shard, i, v, cold_shard=cold_shard),
          mesh=self.mesh,
          in_specs=(P(self.axis),) * 4,
          out_specs=P(self.axis), check_vma=False))
    else:
      self._lookup_fn = jax.jit(jax.shard_map(
          lambda shard, i, v: self.lookup_local(shard, i, v),
          mesh=self.mesh,
          in_specs=(P(self.axis), P(self.axis), P(self.axis)),
          out_specs=P(self.axis), check_vma=False))

  # -- in-shard lookup ---------------------------------------------------

  @property
  def in_place(self) -> bool:
    """One shard owns every row: ``lookup_local`` serves in place."""
    return self.mesh.shape[self.axis] == 1

  def exchange_cap(self, b: int) -> int:
    """Slots of one per-owner request bucket for ``b`` requests a
    device. ``bucket_cap`` where one was given; else an even share,
    ``ceil(b / P)`` rounded up to a multiple of 128: requests that
    spread fill one round of ``P`` such buckets, a quarter of the
    ``[P, b]`` slots on four shards, and under any skew the drain's
    ``ceil(fullest / cap) <= P`` rounds serve and ship less than a round
    over ``[P, b]``. Never more than ``b``, at which one round holds
    everything and there is nothing to drain."""
    if self.bucket_cap > 0:
      return min(self.bucket_cap, b)
    share = -(-b // self.mesh.shape[self.axis])   # ceil(b / P)
    return min(-(-share // 128) * 128, b)

  def lookup_local(self, local_shard: jax.Array, ids: jax.Array,
                   valid: jax.Array, axis_name: Optional[str] = None,
                   cold_shard: Optional[jax.Array] = None,
                   counters: bool = False):
    """Gather rows for global ``ids`` from inside shard_map.

    Args:
      local_shard: this device's [rows_per_shard, D] block (the shard_map
        view of ``self.array``).
      ids: [B] global row ids requested by this device.
      valid: [B] mask.
      axis_name: mesh axis to exchange over (defaults to ``self.axis``).
      cold_shard: this device's pinned-host [cold_count, D] block when
        host-offloading; cold lanes are then served in-program by a
        compute_on('device_host') gather instead of lookup()'s host
        phase. Fused train steps pass ``self.cold_array``'s shard here.

      counters: also return what the store counted. Over more than one
        shard, what the exchange did (a fused step hands it out as
        ``SPMDSageTrainStep.store_counters()``): ``store_rounds`` (the
        drain's round count, the same on every device),
        ``store_bucket_max`` (requests in this device's fullest
        per-owner bucket), ``store_requests`` (its valid requests) and
        ``store_exchange_chunks`` (the chunks of ``SERVE_CHUNK`` slots
        the pack, the serve and the stitch gathered, summed over the
        rounds, of ``exchange_chunks(B)`` a round). In place
        ``store_chunks``: the chunks of request slots that held a valid
        request and were gathered, of ``serve_chunks(B)``.

    Returns [B, D]; invalid slots are zero. With ``counters``:
    ``(rows, counters)``.

    On a mesh of one shard the owner of every row is this device, so the
    requests are served IN PLACE, in request order: no bucketing by
    owner, no exchange, no stitch, and ``bucket_cap`` has no rounds to
    drain (it is ignored; ``lookup()`` still pins it). Where nothing
    spills, only the chunks of request slots that hold a valid request
    are gathered (``serve_live_chunks``). The rows are the
    exchange's bit for bit, in every form (resident, hot-only spill with
    cold lanes zero, ``cold_shard``) and at any cap: the drain writes
    each request's row once and adds nothing. The choice follows from
    the mesh at trace time; the gauge
    ``feature_store_in_place{fn="ShardedFeature.lookup_local"}`` says
    which form the last trace took.

    On more shards each per-owner bucket holds ``exchange_cap(B)`` slots
    (the gauge ``feature_store_bucket_cap{fn=...}`` says how many) and
    the overflow drain runs IN-PROGRAM: the round count is the mesh-wide
    max bucket occupancy over the cap (pmax — identical everywhere, so
    the collectives inside the lax.while_loop stay aligned) and round k
    ships the requests ranked [k*cap, (k+1)*cap) within each bucket. No
    host replay, no cross-process agreement round — fused SPMD train
    steps use capped stores directly. Each of a round's pack, serve and
    stitch visits only the chunks of ``SERVE_CHUNK`` slots that hold a
    request of the round; the loops hold no collective, so their trip
    counts may differ by device.
    """
    from ..obs.perf import gauge_in_place
    ax = axis_name or self.axis
    gauge_in_place('ShardedFeature.lookup_local', self.in_place)
    if not self.in_place:
      return self._lookup_exchange(local_shard, ids, valid, ax, cold_shard,
                                   counters)
    with scope('feature_store', 'serve'):
      if self._spill:
        rows = self._serve(local_shard, jnp.where(valid, ids, -1), ax,
                           cold_shard)
        chunks = valid.any().astype(jnp.int32)
      else:
        rows, chunks = serve_live_chunks(
            lambda i, v: self._serve(local_shard, jnp.where(v, i, -1), ax,
                                     None),
            ids, valid, self.feature_dim, local_shard.dtype)
    return (rows, dict(store_chunks=chunks)) if counters else rows

  def serve_chunks(self, b: int) -> int:
    """Chunks the in-place ``serve`` cuts ``b`` request slots into: what
    a step's ``store_chunks`` counter is read against. A store that
    spills gathers them as one."""
    return 1 if self._spill else serve_chunks(b)

  def exchange_chunks(self, b: int) -> int:
    """Chunks of ``SERVE_CHUNK`` that one drain round's ``b`` request
    slots (the pack and the stitch) and ``P x exchange_cap(b)`` bucket
    slots (the serve) are cut into: what a step's
    ``store_exchange_chunks`` is read against."""
    return 2 * serve_chunks(b) + self.serve_chunks(
        self.mesh.shape[self.axis] * self.exchange_cap(b))

  def _serve(self, local_shard, req_in, ax, cold_shard):
    """Rows of the local block for the requests ``req_in`` (any shape;
    -1 = no request): hot rows only when spilling without a
    ``cold_shard`` (cold lanes return zero and the host phase in
    lookup(), or the superstep's staged rows, fill them)."""
    my_index = jax.lax.axis_index(ax)
    local_rows = req_in - my_index * self.rows_per_shard
    ok = (local_rows >= 0) & (local_rows < self.hot_count) & \
        (req_in >= 0)
    safe_rows = jnp.clip(local_rows, 0, self.hot_count - 1)
    rows_out = jnp.take(local_shard, safe_rows, axis=0)
    served = jnp.where(ok[..., None], rows_out, 0)
    if cold_shard is not None and self._spill:
      # serve the owner's SPILLED rows from pinned host memory
      # without leaving the program: index arithmetic stays on
      # device, the gather itself runs host-side (raw indexing —
      # bounds logic would materialize device-space constants inside
      # the host region)
      from jax.experimental import compute_on
      cold_count = self.rows_per_shard - self.hot_count
      cold_ok = (local_rows >= self.hot_count) & \
          (local_rows < self.rows_per_shard) & (req_in >= 0)
      cold_rows_idx = jnp.clip(local_rows - self.hot_count, 0,
                               cold_count - 1)
      idx_h = jax.device_put(cold_rows_idx.reshape(-1),
                             jax.memory.Space.Host)
      with compute_on.compute_on('device_host'):
        cold_out = cold_shard[idx_h]
      cold_out = jax.device_put(
          cold_out, jax.memory.Space.Device).reshape(
              cold_rows_idx.shape + (self.feature_dim,))
      served = jnp.where(cold_ok[..., None],
                         cold_out.astype(served.dtype), served)
    return served

  def _lookup_exchange(self, local_shard, ids, valid, ax, cold_shard,
                       counters=False):
    """``lookup_local`` over more than one shard: bucket the requests by
    owner, all_to_all, serve, all_to_all back, stitch to request order,
    as many rounds as the fullest bucket needs. The pack, the serve and
    the stitch run over the chunks of ``SERVE_CHUNK`` slots that hold a
    request of the round (``collectives.pack_live_chunks``,
    ``serve_live_chunks`` where nothing spills,
    ``collectives.stitch_live_chunks``), and the stitch writes each
    round's rows into the drain's zero ``[b, D]`` carry. Correct on one
    shard too (the exchange is then the identity), which is how tests
    hold the in-place form to it."""
    from ..obs.perf import gauge_bucket_cap
    from .collectives import (all_to_all, drain_rounds, pack_live_chunks,
                              rank_by_owner, stitch_live_chunks)
    n_shards = self.mesh.shape[self.axis]
    b, d = ids.shape[0], self.feature_dim
    store = lambda stage: scope('feature_store', stage)
    with store('bucket'):
      owner = jnp.clip(ids // self.rows_per_shard, 0, n_shards - 1)
      owner = jnp.where(valid, owner, n_shards)  # pads are dropped
      meta = rank_by_owner(owner, n_shards)      # bucket, slot a request
    # fixed-capacity request buckets [n_shards, cap]
    cap = self.exchange_cap(b)
    gauge_bucket_cap('ShardedFeature.lookup_local', cap)
    capped = cap < b  # else one round holds every request
    if capped or counters:
      with store('bucket'):
        rounds = drain_rounds(meta, n_shards, cap, ax)

    def serve(req):
      if self._spill:
        return (self._serve(local_shard, req, ax, cold_shard),
                (req >= 0).any().astype(jnp.int32))
      return serve_live_chunks(
          lambda i, v: self._serve(local_shard, i, ax, None), req,
          req >= 0, d, local_shard.dtype)

    def round_into(base, rows):
      """One bucket-exchange-serve-stitch pass over the requests ranked
      [base, base+cap) per bucket, their rows written into ``rows``;
      and the chunks its three loops gathered."""
      with store('bucket'):
        req, packed = pack_live_chunks(ids, meta, n_shards, cap, base,
                                       SERVE_CHUNK, fill_value=-1)
      with store('exchange'):
        # exchange requests: row p of the result = what peer p asked us
        req_in = all_to_all(req, ax)
      with store('serve'):
        served, gathered = serve(req_in.reshape(-1))
      with store('exchange'):
        # responses back; row p now holds our requests served by peer p
        resp = all_to_all(served.reshape(n_shards, cap, d), ax)
      with store('unbucket'):
        rows, stitched = stitch_live_chunks(rows, resp, meta, n_shards,
                                            base, SERVE_CHUNK)
      return rows, packed + gathered + stitched

    rows = jnp.zeros((b, d), local_shard.dtype)
    if capped:
      def drain(state):
        k, rows, chunks = state
        rows, more = round_into(k * cap, rows)
        return k + 1, rows, chunks + more

      _, rows, chunks = jax.lax.while_loop(
          lambda state: state[0] < rounds, drain,
          (jnp.int32(0), rows, jnp.int32(0)))
    else:
      rows, chunks = round_into(0, rows)
    if not counters:
      return rows
    return rows, dict(store_rounds=rounds,
                      store_bucket_max=meta.counts.max().astype(jnp.int32),
                      store_requests=meta.counts.sum().astype(jnp.int32),
                      store_exchange_chunks=chunks)

  def _cold_values_host(self, nodes: np.ndarray, valid: np.ndarray):
    """The host cold-row gather core shared by the lookup() host phase
    and the streaming stager: range-rule arithmetic finds the cold
    lanes (owner = id // rows_per_shard, cold = local >= hot_count),
    values come from the per-partition ``_host_cold`` blocks. Returns
    ([..., D] values with zeros on non-cold lanes, any_cold)."""
    n_shards = self.mesh.shape[self.axis]
    owner = np.clip(nodes // self.rows_per_shard, 0, n_shards - 1)
    local = nodes - owner * self.rows_per_shard
    cold = valid & (local >= self.hot_count) & (nodes >= 0) \
        & (nodes < self.num_rows)
    np_dtype = np.dtype(self.array.dtype)
    out = np.zeros(nodes.shape + (self.feature_dim,), np_dtype)
    lanes = np.nonzero(cold)
    own = owner[lanes]
    for p in np.unique(own):
      m = tuple(ax[own == p] for ax in lanes)
      out[m] = self._host_cold[int(p)][
          local[m] - self.hot_count].astype(np_dtype)
    return out, bool(lanes[0].size)

  def stage_cold_rows(self, nodes: np.ndarray,
                      counts: np.ndarray) -> np.ndarray:
    """Host-gather the SPILLED rows for pre-sampled node stacks — the
    staging half of the superstep cold-row streaming pipeline
    (parallel/train.py). Cold-ness is arithmetic under the range rule,
    so no device round-trip is needed to find the lanes.

    Args:
      nodes: [..., n_shards * B] global node ids, shard-major blocks
        (device d's B sampled slots at [..., d*B:(d+1)*B]).
      counts: [..., n_shards] valid node counts per device block.

    Returns [..., n_shards * B, D] numpy: cold-row values on cold valid
    lanes, zeros elsewhere — exactly the lanes the in-program hot lookup
    (``lookup_local`` without a cold shard) returns as zero, so the
    consumer merges with one elementwise add.
    """
    if self._host_cold is None:
      raise ValueError(
          'stage_cold_rows serves host-spilled stores without a '
          'pinned-host cold block; this store resolves cold rows '
          'in-program (cold_array) or is fully device-resident')
    nodes = as_numpy(nodes).astype(np.int64)
    counts = as_numpy(counts)
    n_shards = self.mesh.shape[self.axis]
    nb = nodes.shape[-1]
    b = nb // n_shards
    lane = np.arange(nb) % b
    dev = np.arange(nb) // b
    valid = lane < counts[..., dev]
    return self._cold_values_host(nodes, valid)[0]

  def lookup(self, ids, valid=None) -> jax.Array:
    """Whole-mesh lookup from the host side: ids [n_shards * B] laid out
    shard-major; returns globally-sharded [n_shards * B, D]. Capped
    stores drain their overflow inside the compiled program (see
    lookup_local) — one call regardless of skew."""
    if self._traced_cap is None:
      self._traced_cap = self.bucket_cap
    elif self.bucket_cap != self._traced_cap:
      raise RuntimeError(
          f'bucket_cap changed from {self._traced_cap} to '
          f'{self.bucket_cap} after the first lookup compiled it in; '
          'the cached program would keep routing with the old cap. '
          'Set bucket_cap before the first lookup, or build a new '
          'ShardedFeature.')
    ids_np = as_numpy(ids).astype(np.int64)
    ids = jnp.asarray(ids_np)
    if valid is None:
      valid = jnp.ones(ids.shape, bool)
    n_shards = self.mesh.shape[self.axis]
    assert ids.shape[0] % n_shards == 0
    out = self._call_lookup_fn(ids, valid)
    if not self._spill or self.cold_array is not None:
      # host-offloaded stores serve cold lanes inside the program
      return out
    return self._resolve_cold_sharded(out, ids_np,
                                      as_numpy(valid).astype(bool),
                                      n_shards)

  def _call_lookup_fn(self, ids, valid):
    if self.cold_array is not None:
      return self._lookup_fn(self.array, self.cold_array, ids, valid)
    return self._lookup_fn(self.array, ids, valid)

  def _resolve_cold_sharded(self, out, ids_np, valid_np, n_shards):
    """Host phase: cold-ness is arithmetic under the range rule, so the
    requester finds its cold lanes without any device round-trip and
    merges them as one sharded add (cold lanes are zero in ``out``)."""
    delta, any_cold = self._cold_values_host(ids_np, valid_np)
    if not any_cold:
      return out
    delta_arr = jax.device_put(delta.astype(np.dtype(out.dtype)),
                               out.sharding)
    return out + delta_arr
