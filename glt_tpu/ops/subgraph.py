"""Induced-subgraph extraction over a node set.

Reference: csrc/cuda/subgraph_op.cu (hash-insert nodes, count edges whose
dst is in the set with a warp reduce, prefix-scan, emit relabeled COO).
TPU formulation: the node set is deduped with :func:`ordered_unique`; each
node's neighbor window (capped at ``max_degree``) is gathered, membership
of the endpoint in the set is a fixed-depth binary search over the *sorted*
unique node list, and the relabeled COO comes out padded [U, max_degree]
with a mask — compaction happens only if the caller asks for it.

Two extractions live here, and each says what it does past its budget:

* :func:`induced_subgraph`, one node set, edge slots with edge ids: a
  member's row is read through a ``[U, max_degree]`` window, so it is
  exact only where ``max_degree`` bounds every member's degree; a wider
  row is truncated to its first ``max_degree`` entries, silently
  (``NeighborSampler.subgraph`` passes the graph's widest row, so the
  loader path is exact; on a graph with hubs that window is the memory).
* :func:`enclosing_subgraphs`, ``L`` small node sets at once (a batch of
  SEAL links) as dense ``[L, S, S]`` blocks, exact on a graph with hubs
  inside static budgets: what a budget cannot hold is counted
  (``edges_dropped``), never dropped silently. A budget is a bound, not
  the work: the members' rows are read tile by tile in one loop over
  the tiles the batch holds (:func:`live_tiles_seen`).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .negative import edge_in_csr
from .unique import ordered_unique, unique_rows


class SubGraph(NamedTuple):
  """Reference py_export_glt.cc:77-82 SubGraph{nodes,rows,cols,eids}, in
  padded layout."""
  nodes: jax.Array       # [U_cap] unique input nodes, -1 padded
  node_count: jax.Array  # scalar
  rows: jax.Array        # [U_cap * D] relabeled src
  cols: jax.Array        # [U_cap * D] relabeled dst
  eids: jax.Array        # [U_cap * D]
  edge_mask: jax.Array   # [U_cap * D]


def _searchsorted_in_set(sorted_set: jax.Array, set_count: jax.Array,
                         queries: jax.Array):
  """Position of each query in the ascending ``sorted_set`` (padded with
  int-max); returns (pos, found)."""
  pos = jnp.searchsorted(sorted_set, queries)
  cap = sorted_set.shape[0]
  at = jnp.take(sorted_set, jnp.clip(pos, 0, cap - 1), mode='clip')
  found = (pos < set_count) & (at == queries)
  return pos, found


def induced_subgraph(
    indptr: jax.Array,
    indices: jax.Array,
    srcs: jax.Array,
    src_mask: jax.Array,
    node_capacity: int,
    max_degree: int,
    edge_ids: Optional[jax.Array] = None,
    with_edge: bool = True,
) -> SubGraph:
  """NodeSubGraph(srcs, with_edge) equivalent (subgraph_op.cu:34-117).

  Labels follow first-occurrence order of ``srcs`` (matching the
  reference's inducer-based relabeling). ``max_degree`` must bound the
  degree of every node in the set for exact extraction: a wider member's
  row is cut to its first ``max_degree`` entries and the edges past them
  are missing from the result without a count (the module's text;
  :func:`enclosing_subgraphs` is the extraction that counts).
  """
  uniq, count, _ = ordered_unique(srcs, src_mask, node_capacity)
  node_valid = jnp.arange(node_capacity) < count

  # membership structure: sort unique ids ascending (-1 pads -> int max)
  big = jnp.iinfo(uniq.dtype).max
  masked = jnp.where(node_valid, uniq, big)
  sort_order = jnp.argsort(masked)
  sorted_ids = jnp.take(masked, sort_order)
  # label of sorted_ids[k] is sort_order[k] (position in appearance order)

  num_edges = indices.shape[0]
  start = jnp.take(indptr, jnp.clip(uniq, 0, None), mode='clip')
  win = jnp.arange(max_degree, dtype=jnp.int32)[None, :]
  deg = (jnp.take(indptr, jnp.clip(uniq, 0, None) + 1, mode='clip')
         - start).astype(jnp.int32)
  deg = jnp.where(node_valid, deg, 0)
  slot_valid = win < deg[:, None]                       # [U, D]
  slots = jnp.clip(start[:, None] + win.astype(start.dtype),
                   0, max(num_edges - 1, 0))
  nbr = jnp.take(indices, slots, mode='clip')           # [U, D] global ids
  pos, found = _searchsorted_in_set(sorted_ids, count, nbr.reshape(-1))
  nbr_label = jnp.take(sort_order, jnp.clip(pos, 0, node_capacity - 1),
                       mode='clip').astype(jnp.int32)
  edge_mask = slot_valid.reshape(-1) & found
  rows = jnp.repeat(jnp.arange(node_capacity, dtype=jnp.int32), max_degree)
  cols = jnp.where(edge_mask, nbr_label.reshape(-1), -1)
  rows = jnp.where(edge_mask, rows, -1)
  if with_edge:
    eids = (jnp.take(edge_ids, slots, mode='clip') if edge_ids is not None
            else slots).reshape(-1)
    eids = jnp.where(edge_mask, eids, -1)
  else:
    eids = jnp.full((node_capacity * max_degree,), -1, jnp.int32)
  return SubGraph(nodes=uniq, node_count=count, rows=rows, cols=cols,
                  eids=eids, edge_mask=edge_mask)


# -- a batch of enclosing subgraphs, dense ---------------------------------

#: entries of ``indices`` one read of the batched extraction takes: a row
#: of ``indices`` seen as ``[E / TILE, TILE]`` (one row gather a tile; an
#: element gather costs as much for one entry)
TILE = 128


class EncloseSpec(NamedTuple):
  """The static budgets of :func:`enclosing_subgraphs` (one hop).

  ``fanout``: neighbours taken of an endpoint: its whole row where that
    is no wider, else a uniform sample without replacement; a link has
    ``node_slots = 2 + 2 * fanout`` node slots.
  ``tile_budget``: the most tiles of ``TILE`` entries of ``indices`` read
    a link. A member at most ``hub_width`` wide is *read*: its whole row,
    in ``ceil`` of its span over ``TILE`` tiles, members in slot order
    while the link's budget lasts. The budget bounds a link and sizes no
    read: the extraction gathers and matches the tiles the batch's links
    hold (``tiles_read``, in whole chunks: ``tiles_matched``), all ``L x
    tile_budget`` of them only when every link fills its own.
  ``hub_width``: a member wider than this is never read (a row of the
    benchmark's graph is up to 40 k wide).
  ``hub_pairs``: pairs of members of one link that were both not read
    (wider than ``hub_width``, or past the tile budget), probed one by
    one with ``edge_in_csr``, over the whole batch. An edge with a read
    end is found from that end (the graph is symmetric); an edge between
    two unread members only by its probe, so the pairs past this budget
    are what the extraction can lose: ``edges_dropped`` counts them.
  ``max_z``: DRNL labels are clipped to ``max_z - 1`` (the embedding's
    rows).
  """
  fanout: int = 127
  tile_budget: int = 512
  hub_width: int = 2048
  hub_pairs: int = 8192
  max_z: int = 1000

  @property
  def node_slots(self) -> int:
    return 2 + 2 * self.fanout


def pad_to_tiles(indices: jax.Array) -> jax.Array:
  """``indices`` padded with -1 to whole tiles (a copy; a trainer pads
  its graph once, when it is built)."""
  short = -indices.shape[0] % TILE
  return jnp.pad(indices, (0, short), constant_values=-1) if short \
      else indices


#: tiles of the batch-wide list one trip of the induction's loop gathers
#: and matches. ``benchmarks/bench_enclose_match.py`` on a v5e, the
#: library's form at the SEAL cell's shapes (PERF.md section 6, PR 42):
#: 5.84 / 5.84 / 5.84 / 5.82 / 6.06 / 6.20 ms at 512 / 1,024 / 2,048 /
#: 4,096 / 8,192 / 16,384 (the dense form 22.7): flat until the last
#: chunk's idle slots show, so the small end, which rounds up least
MATCH_CHUNK = 1024
#: tiles a link's live tiles are rounded up to in that list, so that a
#: block has one link, reads one row of its link's members and is one
#: write into the plane. The same probe: 6.16 / 5.85 / 5.87 / 6.29 ms at 8
#: / 16 / 32 / 64 (a block costs a write of 0.2 us, a link 16 idle tiles
#: of 60 ns at 32); a flat list of tiles (a block of 1) 8.5 to 22 by the
#: way its matches reach their owners
MATCH_BLOCK = 32


def live_tiles_seen(indices: jax.Array, start: jax.Array, deg: jax.Array,
                    lo: jax.Array, hi: jax.Array, member: jax.Array,
                    tile_budget: int):
  """``(seen [L, S, S] float32, tiles matched)``: ``seen[l, i, j]`` counts
  the tiles of member ``i``'s row in which member ``j`` of the same link
  stands, over the tiles the batch holds and no other.

  ``start``, ``deg`` ``[L, S]``: the members' rows; ``lo``, ``hi`` ``[L,
  S]``: the tiles ``lo .. hi - 1`` of its link's budget that a read member
  owns (0, 0 for a member not read), so a link's live tiles are the prefix
  ``0 .. hi.max() - 1`` of its budget; ``member`` ``[L, S]``: the node
  ids, a dead slot below -1.

  Which tile of ``indices`` a budgeted slot reads and which span of it is
  its owner's row is arithmetic over every slot (masked sums over ``[L,
  TL, S]``: cheap). The gather and the match are not, and run over the
  batch's live tiles alone: one list in (link, tile) order, a link's tiles
  rounded up to whole blocks of ``MATCH_BLOCK`` so that a block has one
  link (a running sum of the links' blocks gives a link's base, a block
  finds its link by counting bases). One ``lax.while_loop`` over the
  list's chunks of ``MATCH_CHUNK`` tiles gathers a chunk's tiles, masks
  them to their owners' rows, matches them against their links' members
  (the members in the lanes, a tile's entries OR-ed over a major axis)
  and puts each block's matches at its link's place in a zero ``[L, TL,
  S]`` plane, a block a write. The owners' sums are then one product on
  the matrix unit: 0/1 products, sums of at most the budget, exact in
  float32. A slot with no owner (a link's last block, the list's last
  chunk) reads a tile of its own, the slots' tiles spread evenly over
  ``indices``, and matches nothing."""
  num_links, s = start.shape
  b = MATCH_BLOCK
  per_link = -(-tile_budget // b)
  tl = per_link * b
  c = min(MATCH_CHUNK // b, num_links * per_link) * b
  g = c // b
  num_tiles = indices.shape[0] // TILE
  t = jnp.arange(tl, dtype=jnp.int32)[None, :, None]
  owner = (lo[:, None, :] <= t) & (t < hi[:, None, :])       # [L, TL, S]
  of_owner = lambda a: jnp.sum(
      jnp.where(owner, a[:, None, :], 0), axis=-1)          # [L, TL]
  # a budgeted slot's tile and its owner's row, a block a row; one more
  # row, of slots with no owner, for the blocks past the list's end
  slots = jnp.concatenate(
      [a.reshape(-1, b) for a in (of_owner(start // TILE - lo) + t[:, :, 0],
                                  of_owner(start), of_owner(start + deg))],
      axis=1)                                               # [L TL / B, 3 B]
  slots = jnp.concatenate([slots, jnp.zeros((1, 3 * b), jnp.int32)])
  blocks = (hi.max(axis=1) + (b - 1)) // b           # a link's live blocks
  upto = jnp.cumsum(blocks)
  chunks = (upto[-1] + (g - 1)) // g
  cap = -(-num_links * per_link // g) * g    # the most blocks the list holds
  # block q of the list: its link by counting the links that end before
  # it (a block past the list's end falls behind the last link's), its
  # place among its link's blocks
  q = jnp.arange(cap, dtype=jnp.int32)
  before = upto[None, :-1] <= q[:, None]                     # [cap, L - 1]
  link = before.sum(-1).astype(jnp.int32)
  first = q - jnp.sum(jnp.where(before, blocks[None, :-1], 0), axis=-1)
  rows = indices.reshape(num_tiles, TILE)
  stride = max(num_tiles // (cap * b), 1)
  lane = jnp.arange(TILE, dtype=jnp.int32)

  def match_chunk(carry):
    k, plane = carry
    of = jax.lax.dynamic_slice(link, (k * g,), (g,))
    at = jax.lax.dynamic_slice(first, (k * g,), (g,))
    got = jnp.take(slots, of * per_link + at, axis=0, mode='clip')
    tile, row_lo, row_hi = got[:, :b], got[:, b:2 * b], got[:, 2 * b:]
    spare = ((k * c + jnp.arange(c, dtype=jnp.int32)) * stride
             % num_tiles).reshape(g, b)
    tile = jnp.where(row_hi > row_lo, tile, spare)
    vals = jnp.take(rows, tile.reshape(-1), axis=0,
                    mode='clip').reshape(g, b, TILE)
    pos = tile[..., None] * TILE + lane
    vals = jnp.where((pos >= row_lo[..., None]) & (pos < row_hi[..., None]),
                     vals, -1)
    mine = jnp.take(member, of, axis=0, mode='clip')        # [G, S]
    match = (vals[..., None] == mine[:, None, None, :]).any(2)
    # a block past the list's end has no place: dropped
    return k + 1, plane.at[of, at].set(
        match.astype(jnp.bfloat16), mode='drop', unique_indices=True)

  _, plane = jax.lax.while_loop(
      lambda carry: carry[0] < chunks, match_chunk,
      (jnp.int32(0), jnp.zeros((num_links, per_link, b, s), jnp.bfloat16)))
  seen = jnp.einsum('lti,ltj->lij', owner.astype(jnp.bfloat16),
                    plane.reshape(num_links, tl, s),
                    preferred_element_type=jnp.float32)
  return seen, chunks * c


def enclosing_subgraphs(indptr: jax.Array, indices: jax.Array,
                        ends: jax.Array, nbrs: jax.Array,
                        nbr_mask: jax.Array, link_mask: jax.Array,
                        spec: EncloseSpec) -> dict:
  """The one-hop enclosing subgraphs of ``L`` links, each deduped and
  induced on its own, as dense blocks.

  The CSR must be symmetric (every edge in both directions), ascending
  within rows, ``indices`` a whole number of tiles long
  (:func:`pad_to_tiles`).

  Args:
    ends: ``[2, L]`` the links' sources and destinations.
    nbrs, nbr_mask: ``[2, L, K]`` the neighbours taken of each endpoint
      (``sample_neighbors``' output for the sources and destinations).
    link_mask: ``[L]``; a masked link has no node.

  Returns a dict: ``nodes [L, S]`` (global ids, -1 padded: source in slot
  0, destination in slot 1, then the fringe in the order it was taken, no
  fringe node twice nor equal to an endpoint), ``node_mask [L, S]``,
  ``adj [L, S, S]`` bool (symmetric, no loops; every edge of the graph
  between two nodes of a link, less the link itself in both directions),
  and the scalar int32 counts ``subgraph_nodes``, ``subgraph_edges``
  (directed), ``tiles_read`` (the tiles the read members' rows span: a
  link's are a prefix of its budget), ``tiles_matched`` (the tiles
  gathered and matched for them: the batch's live tiles in whole chunks
  of :func:`live_tiles_seen`'s loop, never the budget's idle rest),
  ``hub_members`` (live members not read), ``hub_pairs_probed`` and
  ``edges_dropped`` (pairs of unread members past ``hub_pairs``: each may
  be an edge that ``adj`` lacks; 0 means ``adj`` is exact).
  """
  num_links, s = ends.shape[1], spec.node_slots
  tl, cap = spec.tile_budget, spec.hub_pairs
  assert indices.shape[0] % TILE == 0, 'pad_to_tiles(indices) first'
  with jax.named_scope('dedup'):
    ids = jnp.concatenate([ends[0][:, None], ends[1][:, None],
                           nbrs[0], nbrs[1]], axis=1).astype(jnp.int32)
    on = link_mask[:, None]
    valid = jnp.concatenate([on, on, nbr_mask[0] & on, nbr_mask[1] & on],
                            axis=1)
    nodes, count = unique_rows(ids, valid, fixed=2)
    slot = jnp.arange(s, dtype=jnp.int32)
    live = slot[None, :] < count[:, None]
  with jax.named_scope('induce'):
    at = jnp.maximum(nodes, 0)
    start = jnp.take(indptr, at, mode='clip').astype(jnp.int32)
    deg = jnp.where(
        live, jnp.take(indptr, at + 1, mode='clip').astype(jnp.int32)
        - start, 0)
    # the tiles a member's row spans, and which members the budget reads
    span = jnp.where((deg > 0) & (deg <= spec.hub_width),
                     (start % TILE + deg + TILE - 1) // TILE, 0)
    hi = jnp.cumsum(span, axis=1)
    read = (span > 0) & (hi <= tl)
    hi = jnp.where(read, hi, 0)
    lo = jnp.where(read, hi - span, 0)
    member = jnp.where(live, nodes, -2)
    seen, tiles_matched = live_tiles_seen(indices, start, deg, lo, hi,
                                          member, tl)
    unread = live & (deg > 0) & ~read
    with jax.named_scope('hub_pairs'):
      ranks = jnp.cumsum(unread, axis=1, dtype=jnp.int32)    # [L, S]
      h = ranks[:, -1]
      pairs = h * (h - 1) // 2
      upto = jnp.cumsum(pairs)
      total = upto[-1]
      q = jnp.arange(cap, dtype=jnp.int32)
      link = jnp.minimum((upto[None, :] <= q[:, None]).sum(-1),
                         num_links - 1).astype(jnp.int32)
      r = q - jnp.take(upto - pairs, link)
      hq = jnp.take(h, link)
      # pair r of a link's h unread members, ranks a < b: row a of the
      # triangle starts at a * (2h - a - 1) / 2
      first = lambda a: a * (2 * hq[:, None] - a - 1) // 2
      a = ((slot[None, 1:] <= hq[:, None] - 1)
           & (first(slot[None, 1:]) <= r[:, None])).sum(-1).astype(jnp.int32)
      b = a + 1 + r - first(a[:, None])[:, 0]
      rank_rows = jnp.take(ranks, link, axis=0)              # [P, S]
      slot_of = lambda k: (rank_rows <= k[:, None]).sum(-1).astype(jnp.int32)
      sa, sb = slot_of(a), slot_of(b)
      flat = nodes.reshape(-1)
      ua = jnp.take(flat, link * s + jnp.minimum(sa, s - 1))
      ub = jnp.take(flat, link * s + jnp.minimum(sb, s - 1))
      # a slot past the list's end probes a row of its own, the slots'
      # rows spread evenly over the graph: left on one node, two slots in
      # three read ONE address a round, which takes the chip twice the
      # time of reads apart and a time that moves with the address (a
      # step in one of three speeds, 42 to 46 ms, on the benchmark's cell)
      num_nodes = indptr.shape[0] - 1
      spare = q * max(num_nodes // cap, 1) % num_nodes
      listed = q < total
      found = listed & edge_in_csr(
          indptr, indices, jnp.where(listed, jnp.maximum(ua, 0), spare),
          jnp.where(listed, jnp.maximum(ub, 0), spare))
      where = jnp.where(found, (link * s + sa) * s + sb, seen.size)
      seen = seen.reshape(-1).at[where].add(1.0, mode='drop').reshape(
          seen.shape)
      probed = jnp.minimum(total, cap)
    adj = (seen + jnp.swapaxes(seen, 1, 2)) > 0
    adj &= live[:, :, None] & live[:, None, :]
    adj &= slot[:, None] != slot[None, :]
    target = (slot[:, None] < 2) & (slot[None, :] < 2)
    adj &= ~target[None]
  return dict(
      nodes=nodes, node_mask=live, adj=adj,
      subgraph_nodes=count.sum(dtype=jnp.int32),
      subgraph_edges=adj.sum(dtype=jnp.int32),
      tiles_read=hi.max(axis=1).sum(dtype=jnp.int32),
      tiles_matched=tiles_matched,
      hub_members=unread.sum(dtype=jnp.int32),
      hub_pairs_probed=probed.astype(jnp.int32),
      edges_dropped=(total - probed).astype(jnp.int32))
