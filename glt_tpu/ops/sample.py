"""Neighbor sampling primitives — static-shape, XLA-friendly.

TPU-native equivalent of the reference's fused CUDA sampling kernels
(csrc/cuda/random_sampler.cu:36-165, csrc/cpu/random_sampler.cc,
csrc/cpu/weighted_sampler.cc). Design differences, per SURVEY.md §7:

* The reference allocates exact-size outputs after a device prefix-scan
  (random_sampler.cu:284-301). XLA wants static shapes, so every seed gets
  exactly ``fanout`` output slots plus a validity mask; ``nbrs_num``
  becomes ``mask.sum(-1)``.
* The reference's warp-per-row reservoir sampling with atomicMax ordering
  (random_sampler.cu:59-109) is replaced by **Floyd's algorithm**: K
  rounds of (draw, collision->swap-in-boundary) per seed. Same
  uniform-without-replacement distribution, no atomics, fully vectorized
  over the seed batch on the VPU; K is static and small so the loop
  unrolls into straight-line vector code.
* Weighted sampling (CPU-only upstream, weighted_sampler.cc:26-79) is done
  device-side via Gumbel-top-k over a degree-capped neighbor window —
  weight-proportional sampling *without replacement* in one vectorized
  top_k.

All functions are jit-safe and shard_map-safe (pure gathers + elementwise).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .scan import cumsum_i32


class NeighborOutput(NamedTuple):
  """One-hop sampling result (reference sampler/base.py NeighborOutput),
  in padded layout: every field is [S, K]."""
  nbrs: jax.Array        # neighbor node ids, undefined where ~mask
  mask: jax.Array        # bool validity
  eids: jax.Array        # edge ids (compressed-slot or original), if requested
  # frontier rows whose ``indptr`` and ``indices`` were read (a scalar;
  # None from a hop that does not count them: every one of the S was)
  rows_read: Optional[jax.Array] = None

  @property
  def nbrs_num(self) -> jax.Array:
    return self.mask.sum(axis=-1)


def _empty_output(s: int, width: int, indices, edge_ids,
                  indptr) -> 'NeighborOutput':
  """All-masked output for a zero-edge graph; dtypes follow the same
  contract as the non-empty paths (nbrs: indices.dtype, eids:
  edge_ids.dtype, or int32 — slot planes are int32 throughout the hot
  path)."""
  eid_dtype = edge_ids.dtype if edge_ids is not None else jnp.int32
  return NeighborOutput(nbrs=jnp.zeros((s, width), indices.dtype),
                        mask=jnp.zeros((s, width), bool),
                        eids=jnp.full((s, width), -1, eid_dtype))


def _floyd_offsets(deg: jax.Array, u: jax.Array, fanout: int) -> jax.Array:
  """Floyd's uniform sampling of `fanout` distinct offsets from [0, deg).

  Valid only where deg >= fanout (caller selects). u: [fanout, S] uniforms.
  """
  s = deg.shape[0]
  chosen = jnp.zeros((s, fanout), jnp.int32)
  for j in range(fanout):
    bound = deg - fanout + j           # draw from [0, bound] inclusive
    bound = jnp.maximum(bound, 0)
    t = jnp.minimum((u[j] * (bound + 1).astype(u.dtype)).astype(jnp.int32),
                    bound)
    if j > 0:
      dup = jnp.any(chosen[:, :j] == t[:, None], axis=1)
    else:
      dup = jnp.zeros((s,), bool)
    pick = jnp.where(dup, bound, t)
    chosen = chosen.at[:, j].set(pick)
  return chosen


def _hop_degrees(indptr, seeds, seed_mask):
  """Row start and masked degree of each frontier row: the prefix the
  uniform, full-neighbourhood and weighted hops share."""
  start = jnp.take(indptr, seeds, mode='clip')
  end = jnp.take(indptr, seeds + 1, mode='clip')
  deg = (end - start).astype(jnp.int32)
  if seed_mask is not None:
    deg = jnp.where(seed_mask, deg, 0)
  return start, deg


def _slots_i32(start, offsets, num_edges):
  """Absolute edge slots, narrowed to int32 — half the index bytes on
  the hot path. The narrowing is only sound while the edge count fits
  int32; ``num_edges`` is static, so the guard is a free trace-time
  assert that fails LOUDLY instead of letting slots wrap to negative
  (which take-clip would silently clamp to edge 0 — corrupt samples)."""
  assert num_edges < 2 ** 31, (
      f'{num_edges} edges exceed the int32 slot range: the hot-path '
      'slot planes are int32 by design — shard the graph (the '
      'distributed partitioner splits well before 2^31 edges/shard)')
  return jnp.clip(start[:, None] + offsets.astype(start.dtype),
                  0, max(num_edges - 1, 0)).astype(jnp.int32)


#: frontier rows in one chunk of the live-rows read: the least time at all
#: three shapes of a probe of the hop alone on a v5e among 1,024 / 2,048 /
#: 4,096 / 8,192 (``benchmarks/bench_hop_read.py --forms``; PERF.md
#: section 6, PR 41): hop 2 of the GraphSAGE cells (153,600 slots, 37.9 %
#: live) 9.61 / 12.05 / 10.05 / 10.82 ms where the plain read takes 18.36;
#: their hop 1 (15,360 slots, 53.7 % live) 2.24 / 2.60 / 2.98 / 4.55
#: against 3.11; a typed cell's hop 2 over one relation (48,000 slots,
#: 13.5 % live, with edge ids) 2.23 / 2.91 / 2.89 / 2.74 against 6.78. A
#: small chunk rounds the live count up by less, and a trip of the loop
#: costs little beside its 13 element reads a row.
HOP_CHUNK = 1024

#: a frontier whose chunks of live rows would hold more than this share
#: of its slots is read whole: a row of the chunked read costs 121 ns (it
#: also reads its seed and its ``K`` draws) and 2.5 ms a hop are fixed
#: (the ranks, the prefix, the draws, the gather back), where the plain
#: read costs 86 ns a live row and 151 ns a dead one (whose lanes all
#: clip to one address). At 153,600 slots the two meet between 60 % live
#: (13.87 against 15.65 ms) and 70 % (15.73 against 14.62): the same probe
#: with ``--shares``.
HOP_LIVE_SHARE = 0.65


def _hop_uniforms(key, s: int, fanout: int, replace: bool):
  """A hop's draws, one column (``replace``: one row) a frontier slot."""
  return jax.random.uniform(key, (s, fanout) if replace else (fanout, s))


def _read_rows(indptr, indices, edge_ids, rows, live, draws, fanout: int,
               replace: bool):
  """The uniform hop's read of ``rows`` ([R] row ids, ``live`` their
  mask or None): ``(nbrs [R, K], mask, eids)``. ``draws()`` gives the
  rows' uniforms; it is called where the hop has always drawn, so the
  plain read traces to the program it always was."""
  num_edges = indices.shape[0]
  start, deg = _hop_degrees(indptr, rows, live)
  iota = jnp.arange(fanout, dtype=jnp.int32)[None, :]    # [1, K]
  u = draws()
  if replace:
    offsets = jnp.minimum((u * deg[:, None]).astype(jnp.int32),
                          jnp.maximum(deg[:, None] - 1, 0))
    mask = jnp.broadcast_to(deg[:, None] > 0, offsets.shape)
  else:
    sampled = _floyd_offsets(deg, u, fanout)
    exhaustive = jnp.broadcast_to(iota, sampled.shape)
    offsets = jnp.where((deg <= fanout)[:, None], exhaustive, sampled)
    mask = iota < jnp.minimum(deg, fanout)[:, None]
  # int32 everywhere edge slots flow: a shard's edge count fits int32
  # by construction in this stack (the partitioner splits well before
  # 2^31 edges/shard), so an int64 indptr must not widen the [S, K]
  # slot/eid planes it feeds — half the index bytes on the hot path
  slots = _slots_i32(start, offsets, num_edges)
  nbrs = jnp.take(indices, slots, mode='clip')
  eids = jnp.take(edge_ids, slots, mode='clip') if edge_ids is not None \
      else slots
  return nbrs, mask, eids


def _read_live_rows(indptr, indices, edge_ids, seeds, seed_mask, live, key,
                    fanout: int, replace: bool):
  """The uniform hop over the ``live`` live rows of a frontier alone. The
  live slots' indices are written to a prefix in slot order (a slot's
  rank among the live ones is its row there), ``ceil(live / HOP_CHUNK)``
  chunks of ``HOP_CHUNK`` rows are read in one ``lax.while_loop``, each
  with its own slots' columns of the draws (drawn whole, so a row draws
  what the plain read gives it), and every slot gathers its row back by
  its rank. A row past the live count in the last chunk is masked and
  reads at its own position; a dead slot gathers a row of its own.
  Returns ``(nbrs, mask, eids, rows read)``: ``mask`` is the plain
  read's, ``nbrs`` and ``eids`` are its where ``mask`` holds."""
  s, c = seeds.shape[0], HOP_CHUNK
  n = -(-s // c)
  chunks = (live + (c - 1)) // c
  rank = cumsum_i32(seed_mask) - 1
  pos = jnp.arange(n * c, dtype=jnp.int32)
  order = pos.at[jnp.where(seed_mask, rank, n * c)].set(
      pos[:s], mode='drop')
  u = _hop_uniforms(key, s, fanout, replace)
  eid_dtype = edge_ids.dtype if edge_ids is not None else jnp.int32

  def read(carry):
    k, nbrs, eids, lanes = carry
    slot = jax.lax.dynamic_slice(order, (k * c,), (c,))
    row_live = k * c + pos[:c] < live
    rows = jnp.where(row_live, jnp.take(seeds, slot, mode='clip'),
                     slot.astype(seeds.dtype))
    got, mask, got_eids = _read_rows(
        indptr, indices, edge_ids, rows, row_live,
        lambda: jnp.take(u, slot, axis=0 if replace else 1, mode='clip'),
        fanout, replace)
    return (k + 1, jax.lax.dynamic_update_slice(nbrs, got, (k * c, 0)),
            jax.lax.dynamic_update_slice(eids, got_eids, (k * c, 0)),
            jax.lax.dynamic_update_slice(
                lanes, mask.sum(axis=1, dtype=jnp.int32), (k * c,)))

  _, nbrs, eids, lanes = jax.lax.while_loop(
      lambda carry: carry[0] < chunks, read,
      (jnp.int32(0), jnp.zeros((n * c, fanout), indices.dtype),
       jnp.zeros((n * c, fanout), eid_dtype),
       jnp.zeros((n * c,), jnp.int32)))
  row = jnp.where(seed_mask, rank, pos[:s])
  # a row's live lanes are a prefix of its K (all or none of them under
  # ``replace``), so their count is its mask
  lanes = jnp.where(seed_mask, jnp.take(lanes, row), 0)
  mask = jnp.arange(fanout, dtype=jnp.int32)[None, :] < lanes[:, None]
  return (jnp.take(nbrs, row, axis=0), mask, jnp.take(eids, row, axis=0),
          chunks * c)


def sample_neighbors(
    indptr: jax.Array,
    indices: jax.Array,
    seeds: jax.Array,
    fanout: int,
    key: jax.Array,
    seed_mask: Optional[jax.Array] = None,
    edge_ids: Optional[jax.Array] = None,
    replace: bool = False,
) -> NeighborOutput:
  """Uniformly sample up to ``fanout`` neighbors per seed from a CSR/CSC.

  fanout == -1 is not supported here (full neighborhood is the subgraph
  op's job); fanout must be a static positive int.

  Returns padded [S, fanout] neighbors + mask; when a seed's degree is
  <= fanout the sample is exhaustive and in adjacency order (which makes
  tiny-graph tests exact, the reference test strategy SURVEY.md §4).

  Neighbor values are read by a per-element gather from ``indices``: the
  one hop read in the tree. Which rows it reads follows from the input
  alone. A frontier of at most ``HOP_CHUNK`` slots, or one without a
  mask, is read whole, as every line of PERF_LEDGER.jsonl before PR 41
  was: the program is that read's and no other. A larger frontier is
  read for its live rows only (:func:`_read_live_rows`: the sort
  engine's hop loops keep a frontier slot-aligned, so most of its slots
  are no new head and have nothing to read), unless its live rows fill
  more than ``HOP_LIVE_SHARE`` of its slots: then it is read whole
  after all, by one ``lax.cond`` on the live count. Either way every
  live row draws what it has always drawn (``uniform(key, (fanout,
  S))`` by slot), ``mask`` is the same everywhere, and ``nbrs`` and
  ``eids`` are the same where ``mask`` holds. A masked lane's ``nbrs``
  and ``eids`` are unspecified (some node id, some edge id: no caller
  reads them unmasked). ``rows_read`` counts the frontier rows read:
  ``S``, or the live rows in whole chunks.
  """
  assert fanout > 0, 'fanout must be a static positive int'
  seeds = seeds.astype(indptr.dtype)
  s, num_edges = seeds.shape[0], indices.shape[0]
  if num_edges == 0:  # legitimately empty (e.g. a rare-etype partition)
    return _empty_output(s, fanout, indices, edge_ids, indptr)

  def plain(key):
    return _read_rows(indptr, indices, edge_ids, seeds, seed_mask,
                      lambda: _hop_uniforms(key, s, fanout, replace),
                      fanout, replace)

  if seed_mask is None or s <= HOP_CHUNK:
    return NeighborOutput(*plain(key), rows_read=s)
  live = seed_mask.sum(dtype=jnp.int32)
  read = (live + (HOP_CHUNK - 1)) // HOP_CHUNK * HOP_CHUNK
  return NeighborOutput(*jax.lax.cond(
      read > int(HOP_LIVE_SHARE * s),
      lambda key: (*plain(key), jnp.int32(s)),
      lambda key: _read_live_rows(indptr, indices, edge_ids, seeds,
                                  seed_mask, live, key, fanout, replace),
      key))


def sample_full_neighbors(
    indptr: jax.Array,
    indices: jax.Array,
    seeds: jax.Array,
    max_degree: int,
    seed_mask: Optional[jax.Array] = None,
    edge_ids: Optional[jax.Array] = None,
) -> NeighborOutput:
  """Full-neighborhood expansion — the reference's ``fanout = -1``
  (csrc/cpu/random_sampler.cc FullSample path; examples/seal_link_pred.py
  uses ``[-1, -1]``). Every neighbor is returned in adjacency order
  inside a static ``[S, max_degree]`` window; callers pass
  ``max_degree >= graph max degree`` for exact semantics (NeighborSampler
  resolves this automatically). Degrees above the window are truncated.
  """
  assert max_degree > 0
  seeds = seeds.astype(indptr.dtype)
  num_edges = indices.shape[0]
  if num_edges == 0:
    return _empty_output(seeds.shape[0], max_degree, indices, edge_ids,
                         indptr)
  start, deg = _hop_degrees(indptr, seeds, seed_mask)
  deg = jnp.minimum(deg, max_degree)
  win = jnp.arange(max_degree, dtype=jnp.int32)[None, :]   # [1, D]
  mask = win < deg[:, None]
  slots = _slots_i32(start, win, num_edges)
  nbrs = jnp.take(indices, slots, mode='clip')
  eids = jnp.take(edge_ids, slots, mode='clip') if edge_ids is not None \
      else slots
  return NeighborOutput(nbrs=nbrs, mask=mask, eids=eids)


def sample_neighbors_weighted(
    indptr: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    seeds: jax.Array,
    fanout: int,
    key: jax.Array,
    max_degree: int,
    seed_mask: Optional[jax.Array] = None,
    edge_ids: Optional[jax.Array] = None,
) -> NeighborOutput:
  """Weight-proportional sampling without replacement via Gumbel-top-k.

  The neighbor window per seed is capped at ``max_degree`` (static): for
  hub nodes with more neighbors only the first ``max_degree`` (in
  adjacency order) participate. Pass ``max_degree >= topo.max_degree``
  for exact semantics.
  """
  assert fanout > 0
  assert fanout <= max_degree, (
      f'fanout ({fanout}) must be <= max_degree ({max_degree}); raise '
      'max_degree to at least the fanout')
  seeds = seeds.astype(indptr.dtype)
  num_edges = indices.shape[0]
  if num_edges == 0:
    return _empty_output(seeds.shape[0], fanout, indices, edge_ids,
                         indptr)
  start, deg = _hop_degrees(indptr, seeds, seed_mask)
  deg = jnp.minimum(deg, max_degree)

  win = jnp.arange(max_degree, dtype=jnp.int32)[None, :]  # [1, D]
  valid = win < deg[:, None]                               # [S, D]
  slots = jnp.clip(start[:, None] + win.astype(start.dtype),
                   0, max(num_edges - 1, 0))
  w = jnp.take(weights, slots, mode='clip').astype(jnp.float32)
  w = jnp.where(valid & (w > 0), w, 0.0)
  g = -jnp.log(-jnp.log(
      jax.random.uniform(key, w.shape, minval=1e-20, maxval=1.0)))
  keys = jnp.where(w > 0, jnp.log(w) + g, -jnp.inf)
  _, top = jax.lax.top_k(keys, fanout)                    # [S, K] window idx
  top_valid = jnp.take_along_axis(keys, top, axis=1) > -jnp.inf
  off = top.astype(start.dtype)
  # int32 edge slots (see _slots_i32): the weighted path's picks were
  # the residual wide operands in the slots/labels flow
  pick = _slots_i32(start, off, num_edges)
  nbrs = jnp.take(indices, pick, mode='clip')
  eids = jnp.take(edge_ids, pick, mode='clip') if edge_ids is not None \
      else pick
  return NeighborOutput(nbrs=nbrs, mask=top_valid, eids=eids)


def neighbor_probs(
    indptr: jax.Array,
    indices: jax.Array,
    seed_probs: jax.Array,
    fanout: int,
    num_nodes: int,
) -> jax.Array:
  """Hotness propagation for FrequencyPartitioner — the CalNbrProbKernel
  equivalent (random_sampler.cu:167-209): given per-node access
  probabilities, push one hop of expected sampling probability to
  neighbors: p_nbr += p(src) * min(fanout, deg)/deg spread per neighbor.

  Edge-parallel formulation: for each edge (u -> v),
  contribution(v) = p(u) * min(fanout/deg(u), 1). A negative fanout
  (full-neighborhood hop) touches every neighbor: rate = 1.
  """
  deg = (indptr[1:] - indptr[:-1]).astype(jnp.float32)
  if fanout < 0:
    rate = jnp.where(deg > 0, 1.0, 0.0)
  else:
    rate = jnp.where(deg > 0,
                     jnp.minimum(fanout / jnp.maximum(deg, 1.0), 1.0),
                     0.0)
  contrib_per_src = seed_probs * rate                     # [N]
  # expand to edges: edge e has src = row(e). ``indices`` may carry a
  # padded tail (a stream snapshot's capacity padding); positions
  # at/after indptr[-1] are not edges — zero their contribution and
  # clamp the sentinel (-1) ids.
  pos = jnp.arange(indices.shape[0], dtype=indptr.dtype)
  rows = jnp.searchsorted(indptr, pos, side='right') - 1
  contrib = jnp.take(contrib_per_src, rows, mode='clip')
  contrib = jnp.where(pos < indptr[-1], contrib, 0.0)
  out = jnp.zeros((num_nodes,), jnp.float32)
  out = out.at[jnp.maximum(indices, 0)].add(contrib)
  return jnp.minimum(out, 1.0)
