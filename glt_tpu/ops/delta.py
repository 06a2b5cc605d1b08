"""Delta-aware one-hop sampling: base CSR + insert window - tombstones.

The live-update subsystem (:mod:`glt_tpu.stream`) keeps the hot sampling
path on an immutable, locality-sorted CSR and layers mutations on top as
two small static-shape CSR overlays:

  * an **insert overlay** of edges appended since the last compaction;
  * a **tombstone overlay** of edges deleted since the last compaction.

:func:`delta_one_hop` merges both into one hop inside the jitted
multi-hop walk: the base hop samples as usual, base lanes whose neighbor
appears in the frontier row's tombstone window are masked out, and up to
``ins_window`` delta neighbors per frontier node are appended. The
output width is ``abs(fanout) + ins_window`` — a **static** shape, so a
compiled program keeps serving unchanged across delta refreshes and
snapshot swaps (the overlay arrays are jit *arguments*, never closure
constants).

Exactness contract (what the stream tests pin):

  * full-neighborhood hops (``fanout < 0``) are exact over the effective
    adjacency ``(base \\ tombstones) ∪ inserts`` as long as each row's
    delta fits its window — identical node/edge sets to sampling the
    compacted CSR;
  * uniform hops (``fanout > 0``) draw from the base adjacency and then
    drop tombstoned picks, so rows with pending deletes see a reduced
    effective fanout until compaction (bounded-staleness approximation,
    documented in docs/streaming.md); inserted edges join the candidate
    pool via the full insert window.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .sample import (
    NeighborOutput, sample_full_neighbors, sample_neighbors,
)


def tombstone_mask(nbrs: jax.Array, mask: jax.Array,
                   del_nbrs: jax.Array,
                   del_mask: jax.Array) -> jax.Array:
  """Mask out sampled lanes whose neighbor id is tombstoned.

  nbrs/mask: [S, K] one-hop sample; del_nbrs/del_mask: [S, W] the
  per-row tombstone windows (same frontier rows). Returns the [S, K]
  validity with tombstone hits cleared. A delete of (u, v) kills every
  sampled copy of v under u — multigraph deletes are all-instances.
  """
  hit = (nbrs[:, :, None] == del_nbrs[:, None, :]) \
      & del_mask[:, None, :]                       # [S, K, W]
  return mask & ~hit.any(axis=-1)


def delta_one_hop(
    indptr: jax.Array,
    indices: jax.Array,
    ins_indptr: jax.Array,
    ins_indices: jax.Array,
    del_indptr: jax.Array,
    del_indices: jax.Array,
    frontier: jax.Array,
    fanout: int,
    key: jax.Array,
    seed_mask: Optional[jax.Array],
    ins_window: int,
    del_window: int,
    replace: bool = False,
) -> NeighborOutput:
  """One delta-merged hop; output width ``abs(fanout) + ins_window``.

  Args:
    indptr/indices: base CSR/CSC (indices may be capacity-padded past
      the live edge count — valid lanes never read the pad).
    ins_indptr/ins_indices: insert-overlay CSR over the same row space
      (indices padded to the static delta capacity).
    del_indptr/del_indices: tombstone-overlay CSR, same contract.
    frontier: [S] row ids to expand.
    fanout: static hop fanout; positive = uniform sample, negative =
      full neighborhood inside a ``-fanout`` window (NeighborSampler's
      internal encoding).
    seed_mask: [S] validity of frontier lanes.
    ins_window/del_window: static per-node delta window capacities. A
      row with more pending inserts (deletes) than the window truncates
      (under-masks) until compaction folds the delta into the base —
      the stream ingestor's occupancy policy bounds how long that lasts.

  Edge ids are slot-encoded (with_edge consumers are unsupported on the
  stream path — delta edges have no stable compressed slot until
  compaction).
  """
  if fanout < 0:
    base = sample_full_neighbors(indptr, indices, frontier, -fanout,
                                 seed_mask=seed_mask)
  else:
    base = sample_neighbors(indptr, indices, frontier, fanout, key,
                            seed_mask=seed_mask, replace=replace)
  keep = base.mask
  if del_window > 0:
    dels = sample_full_neighbors(del_indptr, del_indices, frontier,
                                 del_window, seed_mask=seed_mask)
    keep = tombstone_mask(base.nbrs, base.mask, dels.nbrs, dels.mask)
  if ins_window <= 0:
    return NeighborOutput(nbrs=base.nbrs, mask=keep, eids=base.eids)
  ins = sample_full_neighbors(ins_indptr, ins_indices, frontier,
                              ins_window, seed_mask=seed_mask)
  return NeighborOutput(
      nbrs=jnp.concatenate([base.nbrs, ins.nbrs], axis=1),
      mask=jnp.concatenate([keep, ins.mask], axis=1),
      eids=jnp.concatenate([base.eids.astype(jnp.int32),
                            ins.eids.astype(jnp.int32)], axis=1),
  )
