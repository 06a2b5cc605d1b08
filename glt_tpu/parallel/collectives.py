"""Bucket-exchange-unbucket: the SPMD request/response pattern.

This is the TPU-native replacement for the reference's cross-partition
RPC fan-out (dist_neighbor_sampler.py:616-687: split ids by partition
book -> rpc to owners -> stitch): requests are packed into fixed-capacity
per-owner buckets, exchanged with one all_to_all over ICI, served
locally, and sent back with a second all_to_all; the un-bucketing scatter
is the positional stitch (stitch_sample_results.cu analog). All shapes
static: a bucket holds the full request vector at worst, or a cap, with
the requests ranked past it served by further rounds (capped_drain).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class BucketMeta(NamedTuple):
  order: jax.Array         # argsort of owner (stable)
  owner_sorted: jax.Array  # [B]
  pos_in_bucket: jax.Array  # [B]


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
  re-issues them (the bucketing is deterministic, so the host can
  replay it and drain overflow through the same compiled program; see
  ShardedFeature.lookup).
  """
  b = ids.shape[0]
  cap = capacity if capacity and capacity < b else b
  order = jnp.argsort(owner, stable=True)
  owner_sorted = jnp.take(owner, order)
  counts = jnp.bincount(jnp.minimum(owner_sorted, n_shards),
                        length=n_shards + 1)[:n_shards]
  offsets = jnp.cumsum(counts) - counts
  pos = jnp.arange(b) - jnp.take(
      offsets, jnp.minimum(owner_sorted, n_shards - 1))
  meta = BucketMeta(order, owner_sorted, pos)
  return bucket_payload(ids, meta, n_shards, fill_value,
                        capacity=cap), meta


def unbucket(resp: jax.Array, meta: BucketMeta, n_shards: int,
             invalid_value=0, round_offset=0) -> jax.Array:
  """Invert bucket_by_owner over a response [n_shards, C, ...]: returns
  [B, ...] in the original request order; dropped and over-capacity
  slots get ``invalid_value``. ``round_offset`` (may be a traced
  scalar) selects the drain round: only requests whose in-bucket rank
  lies in [round_offset, round_offset + C) are decoded — the inverse of
  the same offset passed to :func:`bucket_payload`."""
  cap = resp.shape[1]
  pos = meta.pos_in_bucket - round_offset
  ok = (meta.owner_sorted < n_shards) & (pos >= 0) & (pos < cap)
  gathered = resp[jnp.minimum(meta.owner_sorted, n_shards - 1),
                  jnp.clip(pos, 0, cap - 1)]
  shape = (ok.shape[0],) + (1,) * (gathered.ndim - 1)
  gathered = jnp.where(ok.reshape(shape), gathered, invalid_value)
  out = jnp.zeros_like(gathered)
  return out.at[meta.order].set(gathered)


def drain_rounds(meta: BucketMeta, n_shards: int, cap: int,
                 axis_name: str) -> jax.Array:
  """How many capped-exchange rounds serve every request: the max
  per-owner bucket occupancy over the WHOLE mesh, ceil-divided by the
  capacity. pmax makes the value identical on every device, so a
  lax.while_loop conditioned on it keeps the collectives inside the
  loop aligned — the drain runs entirely in-program (no host replay of
  the bucketing, no cross-process agreement round)."""
  counts = jnp.bincount(jnp.minimum(meta.owner_sorted, n_shards),
                        length=n_shards + 1)[:n_shards]
  local = (counts.max() + cap - 1) // cap
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
  ``lax.while_loop`` (typical skew: one round). One implementation for
  every capped lookup path (parallel + distributed feature stores).
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


def all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
  """Exchange row p of x with peer p along ``axis_name``; x: [P, ...]."""
  n = x.shape[0]
  y = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)
  return y.reshape((n,) + x.shape[1:])


def bucket_payload(values: jax.Array, meta: BucketMeta, n_shards: int,
                   fill_value=0, capacity: int = 0,
                   round_offset=0) -> jax.Array:
  """Pack a companion payload with the SAME ordering as an existing
  bucket_by_owner call (e.g. the col of a (row, col) pair routed by the
  row's owner). ``round_offset`` (may be a traced scalar, e.g. the
  drain-loop counter times the capacity) packs the requests ranked
  [round_offset, round_offset + cap) within each bucket — drain round k
  of a capped exchange packs offset k*cap."""
  b = values.shape[0]
  cap = capacity if capacity and capacity < b else b
  vals_sorted = jnp.take(values, meta.order)
  pos = meta.pos_in_bucket - round_offset
  ok = (meta.owner_sorted < n_shards) & (pos >= 0) & (pos < cap)
  buckets = jnp.full((n_shards + 1, cap), fill_value, values.dtype)
  buckets = buckets.at[
      jnp.where(ok, meta.owner_sorted, n_shards),
      jnp.where(ok, jnp.clip(pos, 0, cap - 1), 0)].set(
          jnp.where(ok, vals_sorted, fill_value))
  return buckets[:n_shards]


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
