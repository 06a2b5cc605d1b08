"""Bucket-exchange-unbucket: the SPMD request/response pattern.

This is the TPU-native replacement for the reference's cross-partition
RPC fan-out (dist_neighbor_sampler.py:616-687: split ids by partition
book -> rpc to owners -> stitch): requests are packed into fixed-capacity
per-owner buckets, exchanged with one all_to_all over ICI, served
locally, and sent back with a second all_to_all; the un-bucketing gather
is the positional stitch (stitch_sample_results.cu analog). All shapes
static: a bucket holds the full request vector at worst, or a cap, with
the requests ranked past it served by further rounds (capped_drain).

The map between request order and bucket order is no permutation: a
request's bucket is its owner and its slot there is its rank, the number
of earlier requests with the same owner (``BucketMeta``, from one running
count an owner over the owners in request order: ``rank_by_owner``). That
is where a stable argsort by owner would put it, so the buckets are such
a sort's, with no sort: the pack scatters a payload straight to
``(owner, rank)`` and the stitch is one gather from there, already in
request order.

The four-chip feature store's exchange runs the pack and the stitch (and
its owner's serve, ``parallel/dist_feature.py``) over the chunks of
request or bucket slots that hold a request and over no other
(``live_chunks``, ``over_chunks``): ``pack_live_chunks`` and
``stitch_live_chunks``, the rows written straight into the drain's
carry. The plain ``bucket_payload`` and ``unbucket`` serve every other
caller.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.scan import cumsum_i32


class BucketMeta(NamedTuple):
  owner: jax.Array   # [B] in request order; n_shards = a dropped request
  rank: jax.Array    # [B] earlier requests with the same owner
  counts: jax.Array  # [n_shards] requests an owner


def rank_by_owner(owner: jax.Array, n_shards: int) -> BucketMeta:
  """Where each request goes: bucket ``owner[i]``, slot ``rank[i]`` = the
  number of earlier requests with the same owner, from ``n_shards``
  running counts of ``owner == p`` in request order (exact: 0/1
  indicators). ``owner`` is in [0, n_shards) for a request and
  == n_shards for a dropped one, which gets no rank."""
  owner = owner.astype(jnp.int32)
  rank = jnp.zeros(owner.shape, jnp.int32)
  counts = []
  for p in range(n_shards):
    hit = owner == p
    run = cumsum_i32(hit)
    rank = jnp.where(hit, run - 1, rank)
    counts.append(run[-1])
  return BucketMeta(owner, rank, jnp.stack(counts))


def _round_slots(meta: BucketMeta, n_shards: int, cap: int, round_offset):
  """(ok [B], slot [B]) of the drain round that holds the requests ranked
  [round_offset, round_offset + cap) per bucket: whether a request is in
  it, and its place ``owner * cap + rank - round_offset`` among the
  round's ``n_shards * cap`` bucket slots."""
  pos = meta.rank - round_offset
  ok = (meta.owner < n_shards) & (pos >= 0) & (pos < cap)
  return ok, meta.owner * cap + pos


def bucket_by_owner(ids: jax.Array, owner: jax.Array, n_shards: int,
                    fill_value=-1, capacity: int = 0):
  """Pack ids into per-owner buckets [n_shards, C].

  ``owner`` must be in [0, n_shards) for valid entries and == n_shards
  for invalid/padded ones (they are dropped). Bucket slots beyond each
  owner's request count hold ``fill_value``.

  ``capacity`` (default 0 = B, the worst case) caps each per-owner
  bucket: a device then ships n_shards*C elements instead of
  n_shards*B. Requests ranked past the cap are NOT packed — they come
  back as ``invalid_value`` from :func:`unbucket`, and the caller
  re-issues them with a ``round_offset`` (capped_drain).
  """
  meta = rank_by_owner(owner, n_shards)
  return bucket_payload(ids, meta, n_shards, fill_value,
                        capacity=capacity), meta


def unbucket(resp: jax.Array, meta: BucketMeta, n_shards: int,
             invalid_value=0, round_offset=0) -> jax.Array:
  """Invert bucket_by_owner over a response [n_shards, C, ...]: returns
  [B, ...] in the original request order; dropped and over-capacity
  slots get ``invalid_value``. ``round_offset`` (may be a traced
  scalar) selects the drain round: only requests whose in-bucket rank
  lies in [round_offset, round_offset + C) are decoded — the inverse of
  the same offset passed to :func:`bucket_payload`. One gather: a
  request reads the slot it was packed to, and a masked slot reads a row
  of its own (on a TPU a gather whose masked slots share a row waits on
  it: 15.3 ms against 10.6 at 937,984 slots of which 63 % are pads,
  benchmarks/bench_bucket_drain.py --forms): :func:`stitch_live_chunks`
  over rows of ``invalid_value``, in one chunk. The sharded feature
  store's drain runs that form chunk by chunk, into its own carry."""
  b = meta.owner.shape[0]
  rows = jnp.full((b,) + resp.shape[2:], invalid_value, resp.dtype)
  return stitch_live_chunks(rows, resp, meta, n_shards, round_offset, b)[0]


def drain_rounds(meta: BucketMeta, n_shards: int, cap: int,
                 axis_name: str) -> jax.Array:
  """How many capped-exchange rounds serve every request: the max
  per-owner bucket occupancy over the WHOLE mesh, ceil-divided by the
  capacity. pmax makes the value identical on every device, so a
  lax.while_loop conditioned on it keeps the collectives inside the
  loop aligned — the drain runs entirely in-program (no host replay of
  the bucketing, no cross-process agreement round)."""
  local = (meta.counts.max() + cap - 1) // cap
  return jax.lax.pmax(local.astype(jnp.int32), axis_name)


def capped_drain(round_out, meta: 'BucketMeta', n_shards: int, cap: int,
                 axis_name: str, zeros, rounds=None):
  """Accumulate ``round_out(base)`` over however many capped-exchange
  rounds serve every request (see :func:`drain_rounds`, whose count a
  caller that reports it hands in as ``rounds``).

  ``round_out`` returns a pytree of per-request accumulators for the
  requests ranked [base, base+cap) per bucket; rounds past the true
  occupancy pack only fill lanes and therefore contribute exact
  zeros/False. ``zeros`` is the matching all-zero pytree. Bool leaves
  merge with ``|``, everything else with ``+``.

  The round count is a pmax'd traced scalar driving one
  ``lax.while_loop`` (typical skew: one round). The capped lookups of
  ``distributed.DistFeature`` run it; ``parallel.ShardedFeature``'s
  exchange writes each round's rows into its carry instead
  (:func:`stitch_live_chunks`).
  """
  def merge(a, o):
    return a | o if a.dtype == jnp.bool_ else a + o

  if rounds is None:
    rounds = drain_rounds(meta, n_shards, cap, axis_name)

  def body(state):
    k, acc = state
    return k + 1, jax.tree.map(merge, acc, round_out(k * cap))

  _, acc = jax.lax.while_loop(lambda s: s[0] < rounds, body,
                              (jnp.zeros((), jnp.int32), zeros))
  return acc


def live_chunks(mask: jax.Array, chunk: int):
  """``(starts [n], count)`` of the chunks of ``chunk`` consecutive slots
  of ``mask`` [B] that hold a True: their first slots in slot order,
  then filler, and how many they are. The flags come from ``mask``
  alone, so any mask is served: a scattered one flags more chunks. The
  last chunk of a ``B`` that ``chunk`` does not divide starts at ``B -
  chunk`` and covers some of its neighbour's slots again. Needs ``B >
  chunk``."""
  b = mask.shape[0]
  n = -(-b // chunk)
  flags = jnp.pad(mask, (0, n * chunk - b)).reshape(n, chunk).any(axis=1)
  count = flags.sum().astype(jnp.int32)
  live = jnp.nonzero(flags, size=n, fill_value=0)[0].astype(jnp.int32)
  return jnp.minimum(live * chunk, b - chunk), count


def over_chunks(starts: jax.Array, count: jax.Array, body, carry):
  """``body(lo, carry)`` for the first ``count`` of ``starts`` in turn,
  in one ``lax.while_loop`` (``live_chunks`` gives both). ``body`` must
  be idempotent on a slot: two chunks may overlap."""
  def step(state):
    k, carry = state
    return k + 1, body(starts[k], carry)

  _, carry = jax.lax.while_loop(lambda state: state[0] < count, step,
                                (jnp.int32(0), carry))
  return carry


def pack_live_chunks(values: jax.Array, meta: BucketMeta, n_shards: int,
                     cap: int, round_offset, chunk: int, fill_value=0):
  """``(buckets [n_shards, cap, ...], chunks)``: :func:`bucket_payload`'s
  buckets element for element, scattered chunk by chunk over the chunks
  of ``chunk`` request slots that hold a request of the round
  (``live_chunks``), so that a chunk of pads costs nothing; and the
  count of those chunks. Up to ``chunk`` slots are one plain scatter.
  The targets of a round are distinct, so the order of the chunks does
  not matter."""
  b = values.shape[0]
  ok, slot = _round_slots(meta, n_shards, cap, round_offset)
  tail = values.shape[1:]

  def put(values, ok, slot, buckets):
    return buckets.at[jnp.where(ok, slot, n_shards * cap)].set(
        values, mode='drop')

  buckets = jnp.full((n_shards * cap,) + tail, fill_value, values.dtype)
  if b <= chunk:
    buckets, count = put(values, ok, slot, buckets), ok.any()
  else:
    cut = lambda a, lo: jax.lax.dynamic_slice_in_dim(a, lo, chunk)
    starts, count = live_chunks(ok, chunk)
    buckets = over_chunks(
        starts, count, lambda lo, buckets: put(
            cut(values, lo), cut(ok, lo), cut(slot, lo), buckets),
        buckets)
  return (buckets.reshape((n_shards, cap) + tail),
          count.astype(jnp.int32))


def stitch_live_chunks(rows: jax.Array, resp: jax.Array, meta: BucketMeta,
                       n_shards: int, round_offset, chunk: int):
  """``(rows, chunks)``: the response ``resp`` [n_shards, C, ...] of the
  drain round at ``round_offset`` stitched back to request order and
  written into ``rows`` [B, ...] in place: a request of the round takes
  the slot it was packed to, as from :func:`unbucket`, and every other
  slot keeps what ``rows`` held. A request is in one round alone, so a
  drain over zero ``rows`` gives every request its row bit for bit
  (``-0.0`` too) and the pads zero, with no add. The row gather runs
  chunk by chunk over the chunks of ``chunk`` request slots that hold a
  request of the round (``live_chunks``); up to ``chunk`` slots are
  one plain gather. A masked slot of a gathered chunk reads a row of its
  own, as in :func:`unbucket`."""
  cap = resp.shape[1]
  flat = resp.reshape((n_shards * cap,) + resp.shape[2:])
  ok, slot = _round_slots(meta, n_shards, cap, round_offset)
  b = ok.shape[0]
  wide = (1,) * (flat.ndim - 1)

  def put(lo, ok, slot, old):
    lane = (lo + jnp.arange(ok.shape[0], dtype=slot.dtype)) % (
        n_shards * cap)
    got = jnp.take(flat, jnp.where(ok, slot, lane), axis=0)
    return jnp.where(ok.reshape(ok.shape + wide), got, old)

  if b <= chunk:
    return put(0, ok, slot, rows), ok.any().astype(jnp.int32)
  cut = lambda a, lo: jax.lax.dynamic_slice_in_dim(a, lo, chunk)
  starts, count = live_chunks(ok, chunk)
  return over_chunks(
      starts, count, lambda lo, rows: jax.lax.dynamic_update_slice_in_dim(
          rows, put(lo, cut(ok, lo), cut(slot, lo), cut(rows, lo)), lo,
          axis=0), rows), count


def all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
  """Exchange row p of x with peer p along ``axis_name``; x: [P, ...]."""
  n = x.shape[0]
  y = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)
  return y.reshape((n,) + x.shape[1:])


def bucket_payload(values: jax.Array, meta: BucketMeta, n_shards: int,
                   fill_value=0, capacity: int = 0,
                   round_offset=0) -> jax.Array:
  """Pack a companion payload [B, ...] into the slots of an existing
  bucket_by_owner call (e.g. the col of a (row, col) pair routed by the
  row's owner): one scatter to ``(owner, rank)``. ``round_offset`` (may
  be a traced scalar, e.g. the drain-loop counter times the capacity)
  packs the requests ranked [round_offset, round_offset + cap) within
  each bucket — drain round k of a capped exchange packs offset k*cap.
  Everything else is sent past the last slot and dropped: it is
  :func:`pack_live_chunks` in one chunk."""
  b = values.shape[0]
  cap = capacity if capacity and capacity < b else b
  return pack_live_chunks(values, meta, n_shards, cap, round_offset, b,
                          fill_value)[0]


def sharded_segment_mean(msgs: jax.Array, targets: jax.Array,
                         mask: jax.Array, num_segments: int,
                         axis_name: str) -> jax.Array:
  """Context-parallel neighborhood aggregation (call inside shard_map).

  The graph-domain analogue of sequence/context parallelism (SURVEY.md
  §5.7: the 'sequence length' axis of this domain is neighborhood size):
  when a node's neighbor list is too large for one chip, its message
  rows are sharded across the mesh; every device reduces its local
  shard with a masked segment-sum and the partial sums/counts are
  psum'd over ICI — a ring-attention-style reduction where the softmax
  is replaced by the GNN's mean.

  Args:
    msgs: [M_local, D] this device's message shard.
    targets: [M_local] destination segment per message.
    mask: [M_local] validity.
    num_segments: global segment count (static).
    axis_name: mesh axis to reduce over.

  Returns [num_segments, D] — identical on every device.
  """
  total, cnt = _local_segment_sums(msgs, targets, mask, num_segments)
  total = jax.lax.psum(total, axis_name)
  cnt = jax.lax.psum(cnt, axis_name)
  return total / jnp.maximum(cnt[:, None], 1.0)


def _local_segment_sums(msgs, targets, mask, num_segments):
  """This device's masked (sum, count) per segment."""
  seg = jnp.where(mask, targets, num_segments)
  total = jax.ops.segment_sum(
      jnp.where(mask[:, None], msgs, 0.0), seg, num_segments + 1
  )[:num_segments]
  cnt = jax.ops.segment_sum(mask.astype(msgs.dtype), seg,
                            num_segments + 1)[:num_segments]
  return total, cnt


def sharded_segment_mean_scattered(msgs: jax.Array, targets: jax.Array,
                                   mask: jax.Array, num_segments: int,
                                   axis_name: str) -> jax.Array:
  """Ring (reduce-scatter) variant of :func:`sharded_segment_mean`:
  the aggregated output stays SHARDED — device i returns only its
  segment block [i*S/P, (i+1)*S/P) — so per-device memory and ICI
  bandwidth drop by the mesh size. ``psum_scatter`` lowers to the ring
  reduce-scatter on ICI (the reduce half of ring attention; the GNN
  mean replaces the softmax).

  ``num_segments`` must be divisible by the axis size. Returns
  [num_segments / P, D].
  """
  n_dev = jax.lax.axis_size(axis_name)
  assert num_segments % n_dev == 0, (
      f'num_segments ({num_segments}) must divide by the axis size '
      f'({n_dev}) for the scattered layout')
  total, cnt = _local_segment_sums(msgs, targets, mask, num_segments)
  total = jax.lax.psum_scatter(total, axis_name, scatter_dimension=0,
                               tiled=True)
  cnt = jax.lax.psum_scatter(cnt, axis_name, scatter_dimension=0,
                             tiled=True)
  return total / jnp.maximum(cnt[:, None], 1.0)
