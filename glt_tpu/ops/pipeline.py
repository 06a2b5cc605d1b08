"""Functional multi-hop sampling pipeline.

The hop loop shared by the single-device NeighborSampler and the SPMD
(shard_map) training step: sample -> induce -> advance frontier, all
static shapes. Mirrors the reference homo loop
(neighbor_sampler.py:186-230) with the padded-frontier design described
in the NeighborSampler docstring.

One inducer backs both loops (:func:`multihop_sample` and
:func:`multihop_sample_hetero`): the sort-merge dedup of ops/unique.py,
whose seen-set lives in batch-sized arrays, so a loop carries no state
from one batch to the next. The seed hop runs
:func:`~glt_tpu.ops.unique.sorted_hop_dedup`, which hands labels out in
first-occurrence order, so ``batch`` and ``seed_labels`` are exact; every
later hop runs :func:`~glt_tpu.ops.unique.sorted_hop_dedup_fused`, one
narrow sort and one packed scatter, whose per-slot outputs come back in
slot order (a hop's new ids are labelled in value order). Every line of
PERF_LEDGER.jsonl was produced by this inducer.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..typing import as_str
from .sample import NeighborOutput
from .unique import (sorted_hop_dedup, sorted_hop_dedup_fused,
                     sorted_nodes_by_label)

OneHopFn = Callable[[jax.Array, int, jax.Array, jax.Array], NeighborOutput]


def sample_budget(batch_size: int, fanouts: Sequence[int]) -> int:
  # a negative fanout encodes a full-neighborhood hop with static window
  # |k| (NeighborSampler resolves -1 to -max_degree); capacity math uses
  # the window size either way
  budget, width = batch_size, batch_size
  for k in fanouts:
    width *= abs(k)
    budget += width
  return budget


def edge_hop_offsets(batch_size: int, fanouts: Sequence[int]) -> List[int]:
  offs, cap = [0], batch_size
  for k in fanouts:
    cap *= abs(k)
    offs.append(offs[-1] + cap)
  return offs


def node_hop_offsets(batch_size: int, fanouts: Sequence[int]) -> List[int]:
  """``node_hop_offsets[h]`` leading node slots hold every node within
  ``h`` hops of a seed. The inducer hands labels out hop by hop, so a
  node first seen at hop ``h`` has a label under the budget of the first
  ``h`` hops: a static prefix of the node buffer, as
  :func:`edge_hop_offsets` is of the edge slots (tests/test_node_trim.py
  pins it; tests/sampler_oracle.py checks it of every batch)."""
  return [sample_budget(batch_size, fanouts[:h])
          for h in range(len(fanouts) + 1)]


def hop_fanouts(fanouts: Sequence[int]) -> Tuple[int, ...]:
  """The promise behind ``Batch.hop_fanouts``: ``(K_0, K_1, ...)``,
  ``K_h = |fanout_h|``. The hop loop writes a hop's parents as
  ``jnp.repeat(frontier_labels, K_h)`` and hands its children back in
  slot order, so hop ``h``'s block of edge slots
  (:func:`edge_hop_offsets`) is groups of ``K_h`` adjacent slots with one
  value of ``col`` each, the label of the frontier slot the group was
  drawn for. A frontier slot that is no new head (a duplicate, a pad) has
  every slot of its group masked, and a node is a new head in one hop,
  so a label heads at most one group with a live slot in the whole batch
  (tests/sampler_oracle.py checks it of every batch)."""
  return tuple(abs(k) for k in fanouts)


def hop_rows_read(out: NeighborOutput, frontier_ids: jax.Array):
  """Frontier rows a hop read ``indptr`` and ``indices`` for: what the
  hop counted (``NeighborOutput.rows_read``), or every slot of the
  frontier for a hop that does not say."""
  rows = frontier_ids.shape[0] if out.rows_read is None else out.rows_read
  return jnp.asarray(rows, jnp.int32)


def multihop_sample(one_hop: OneHopFn,
                    seeds: jax.Array,
                    n_valid: jax.Array,
                    fanouts: Sequence[int],
                    key: jax.Array,
                    with_edge: bool = False,
                    seed_mask: Optional[jax.Array] = None,
                    ) -> Dict[str, jax.Array]:
  """Runs the full hop loop; returns the out dict.

  ``one_hop(frontier_ids, fanout, key, mask)`` performs one sampling hop.
  The valid seeds are the first ``n_valid``, or where ``seed_mask``
  ([batch] bool) is given, the slots it marks (edge seeds: the endpoints
  of a pair past the valid pairs are no suffix). Seeds may repeat: a
  repeated seed has one label, and every slot that holds it reads that
  label in ``seed_labels``.

  Result contract (homo and hetero): lanes where ``edge_mask`` is False
  carry -1 in the child-label buffer (``row`` here; ``col`` holds parent
  labels which are always valid), and invalid seed slots carry -1 in
  ``seed_labels`` (tests/test_sorted_inducer.py pins this). A hop's edge
  slots stay in the order ``one_hop`` drew them (:func:`hop_fanouts`).
  """
  # trace-time tick on the shared hop loop: every enclosing program
  # that (re)traces it shows up under one process-wide label — the
  # pipeline-level row of compiles_total{fn=...} (jit-boundary callers
  # carry their own finer labels)
  from ..obs.perf import count_compile
  count_compile('ops.multihop_sample')
  batch_size = seeds.shape[0]
  budget = sample_budget(batch_size, fanouts)
  if seed_mask is None:
    seed_mask = jnp.arange(batch_size) < n_valid

  u_ids = jnp.zeros((0,), jnp.int32)
  u_labs = jnp.zeros((0,), jnp.int32)
  count = jnp.zeros((), jnp.int32)
  # the seed hop keeps first-occurrence order: ``batch`` is exact
  d = sorted_hop_dedup(u_ids, u_labs, count, seeds, seed_mask)
  # contract: seed_labels in seed-slot order (tiny unsort over [batch])
  seed_labels = jax.lax.sort([d['pos3'], d['labels3']], num_keys=1)[1]
  seed_labels = jnp.where(seed_mask, seed_labels, -1)
  seed_count = d['count2']
  u_ids, u_labs, count = d['u_ids2'], d['u_labs2'], d['count2']
  frontier_ids = d['ids3']
  frontier_labels = d['labels3']
  frontier_mask = d['new_head3']

  rows_parent, cols_child, emasks, eid_list = [], [], [], []
  hop_node_counts = [seed_count]
  hop_edge_counts, hop_rows = [], []
  for hop_idx, fanout in enumerate(fanouts):
    width = abs(fanout)  # negative = full-neighborhood hop, window |k|
    key, sub = jax.random.split(key)
    # named_scope: trace-time-only labels, kept as the op_name of every
    # HLO instruction. A step program nests them under its ``sampler``
    # scope, and obs/device.py::reduce_scopes sums a device trace by
    # them (``sampler/sample_hop0``, ``sampler/dedup0``, ...)
    with jax.named_scope(f'sample_hop{hop_idx}'):
      out = one_hop(frontier_ids, fanout, sub, frontier_mask)
    hop_rows.append(hop_rows_read(out, frontier_ids))
    rows_flat = jnp.repeat(frontier_labels, width)
    ids_flat = out.nbrs.reshape(-1)
    mask_flat = out.mask.reshape(-1)
    # single-sort assign; per-element outputs come back in SLOT order,
    # so edge payloads (rows/mask/eids) never ride a sort
    with jax.named_scope(f'dedup{hop_idx}'):
      d = sorted_hop_dedup_fused(u_ids, u_labs, count, ids_flat, mask_flat)
    rows_parent.append(rows_flat)
    cols_child.append(d['labels3'])
    emasks.append(mask_flat)
    if with_edge:
      eid_list.append(out.eids.reshape(-1))
    frontier_ids = jnp.where(d['new_head3'], ids_flat.astype(jnp.int32),
                             jnp.iinfo(jnp.int32).max)
    u_ids, u_labs, count = d['u_ids2'], d['u_labs2'], d['count2']
    hop_node_counts.append(d['new_count'])
    hop_edge_counts.append(out.mask.sum().astype(jnp.int32))
    frontier_labels = d['labels3']
    frontier_mask = d['new_head3']

  nodes = sorted_nodes_by_label(u_ids, u_labs, count, budget)
  out_dict = dict(
      node=nodes,
      node_count=count,
      row=jnp.concatenate(cols_child),
      col=jnp.concatenate(rows_parent),
      edge_mask=jnp.concatenate(emasks),
      batch=jax.lax.slice(nodes, (0,), (batch_size,)),
      seed_labels=seed_labels,
      seed_count=seed_count,
      num_sampled_nodes=jnp.stack(hop_node_counts),
      num_sampled_edges=jnp.stack(hop_edge_counts),
      hop_rows_read=jnp.stack(hop_rows),
  )
  if with_edge:
    out_dict['edge'] = jnp.concatenate(eid_list)
  return out_dict


def hetero_edge_capacities(caps, trav, num_neighbors, num_hops):
  """Per-etype total edge-slot capacity across hops."""
  out = {}
  for e, (row_t, _) in trav.items():
    out[e] = sum(caps[h][row_t] * abs(num_neighbors[e][h])
                 for h in range(num_hops))
  return out


def hetero_edge_hop_offsets(caps, trav, num_neighbors, num_hops):
  """Per-etype cumulative hop offsets into the concatenated edge
  buffers — the hetero counterpart of :func:`edge_hop_offsets`, used for
  hierarchical per-layer trimming (reference trim_to_layer over
  num_sampled_edges_dict, examples/hetero/hierarchical_sage.py)."""
  offs = {e: [0] for e in trav}
  for h in range(num_hops):
    for e, (row_t, _) in trav.items():
      k = num_neighbors[e][h]
      w = caps[h][row_t] * abs(k) if (caps[h][row_t] and k) else 0
      offs[e].append(offs[e][-1] + w)
  return offs


def hetero_hop_fanouts(caps, trav, num_neighbors, num_hops):
  """The promise behind ``HeteroBatch.hop_fanouts_dict``, the typed
  counterpart of :func:`hop_fanouts`: per relation the static
  ``(offset, S, K)`` triples of its edge buffer, one a hop the loop
  below runs for it (its own test skips the others): from ``offset``
  (:func:`hetero_edge_hop_offsets`) on, ``S = caps[h][row_t]`` groups of
  ``K = |num_neighbors[e][h]|`` adjacent slots. The typed loop appends
  ``jnp.repeat(f_labels, K)`` as a hop's parents and builds its edge
  buffers in slot order, so the parent label is one value over a group,
  and a label heads at most one group with a live slot inside a
  relation: a node is a new head in one hop, and a frontier slot that is
  no new head has its whole group masked (tests/sampler_oracle.py checks
  it of every typed batch)."""
  offs = hetero_edge_hop_offsets(caps, trav, num_neighbors, num_hops)
  return {e: tuple((offs[e][h], caps[h][row_t], abs(num_neighbors[e][h]))
                   for h in range(num_hops)
                   if caps[h][row_t] and num_neighbors[e][h])
          for e, (row_t, _) in trav.items()}


def multihop_sample_hetero(one_hops, trav, num_neighbors, num_hops,
                           caps, budgets, seeds, n_valid, key,
                           with_edge: bool = False, seed_mask=None):
  """Hetero hop loop shared by the single-device engine and the SPMD
  distributed engine (only the per-edge-type ``one_hops`` differ:
  in-HBM sampling vs the all_to_all collective version).

  Per node type an append-form seen-set threaded through the inducer of
  :func:`multihop_sample`: the seed hop on :func:`sorted_hop_dedup`
  (first-occurrence labels, so ``batch`` is exact), every later (type,
  hop) on :func:`sorted_hop_dedup_fused`, whose labels come back in slot
  order, so a hop's edge buffers are cut out of one type's labels by a
  per-etype cursor.

  Args:
    one_hops: Dict[EdgeType, OneHopFn].
    trav: Dict[EdgeType, (expand_from_type, neighbor_type)].
    num_neighbors: Dict[EdgeType, List[int]].
    caps/budgets: static per-hop frontier capacities / node budgets per
      node type (callers compute them identically from trav).
    seeds/n_valid: Dict[NodeType, array] — multi-type seeding.
    seed_mask: Dict[NodeType, bool array] in place of ``n_valid``'s
      prefixes, for seed slots that are live in no prefix order (the
      endpoints of edge seeds: a masked pair masks a slot of each half).

  Returns the result dict with per-type node lists, per-etype
  row(parent)/col(child) label buffers in traversal orientation, batch
  and seed_labels dicts, per-hop counts.
  """
  from ..obs.perf import count_compile
  count_compile('ops.multihop_sample_hetero')  # trace-time only
  types = list(budgets)
  seen = {t: (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
              jnp.zeros((), jnp.int32)) for t in types}
  seed_labels = {}
  frontier = {}
  for t in types:
    c0 = max(1, caps[0][t])
    if t in seeds:
      s = seeds[t]
      mask = (seed_mask[t] if seed_mask
              else jnp.arange(s.shape[0]) < n_valid[t])
      d = sorted_hop_dedup(*seen[t], s, mask)
      sl = jax.lax.sort([d['pos3'], d['labels3']], num_keys=1)[1]
      seed_labels[t] = jnp.where(mask, sl, -1)
      seen[t] = (d['u_ids2'], d['u_labs2'], d['count2'])
      frontier[t] = (d['ids3'], d['labels3'], d['new_head3'])
    else:
      frontier[t] = (jnp.zeros((c0,), jnp.int32),
                     jnp.full((c0,), -1, jnp.int32),
                     jnp.zeros((c0,), bool))

  rows_d, cols_d, mask_d, eid_d = {}, {}, {}, {}
  hop_nodes = {t: [seen[t][2]] for t in types}
  hop_edges, hop_rows = {}, {}
  for h in range(num_hops):
    per_type = {t: [] for t in types}
    per_meta = []
    for e, (row_t, col_t) in trav.items():
      k = num_neighbors[e][h]
      if caps[h][row_t] == 0 or k == 0:
        continue
      width = abs(k)
      f_ids, f_labels, f_mask = frontier[row_t]
      with jax.named_scope(f'sample_hop{h}'), jax.named_scope(as_str(e)):
        key, sub = jax.random.split(key)
        out = one_hops[e](f_ids, k, sub, f_mask)
      hop_rows.setdefault(e, []).append(hop_rows_read(out, f_ids))
      mflat = out.mask.reshape(-1)
      per_type[col_t].append((out.nbrs.reshape(-1), mflat))
      per_meta.append((e, col_t, jnp.repeat(f_labels, width), mflat,
                       out.eids.reshape(-1) if with_edge else None,
                       caps[h][row_t] * width))
    labels_by_type = {}
    for t, chunks in per_type.items():
      if not chunks:
        cap_next = max(1, caps[h + 1][t])
        frontier[t] = (jnp.zeros((cap_next,), jnp.int32),
                       jnp.full((cap_next,), -1, jnp.int32),
                       jnp.zeros((cap_next,), bool))
        hop_nodes[t].append(jnp.zeros((), jnp.int32))
        continue
      with jax.named_scope(f'dedup{h}'), jax.named_scope(t):
        ids = jnp.concatenate([c[0] for c in chunks])
        ok = jnp.concatenate([c[1] for c in chunks])
        # a typed program holds one such dedup a type and hop: the
        # forms that compile quickly, same outputs
        d = sorted_hop_dedup_fused(*seen[t], ids, ok, fast_compile=True)
        labels_by_type[t] = d['labels3']
        frontier[t] = (jnp.where(d['new_head3'], ids.astype(jnp.int32),
                                 jnp.iinfo(jnp.int32).max),
                       d['labels3'], d['new_head3'])
      seen[t] = (d['u_ids2'], d['u_labs2'], d['count2'])
      hop_nodes[t].append(d['new_count'])
    cursor = {t: 0 for t in types}
    for e, col_t, rows_parent, mask, eids, width in per_meta:
      s = cursor[col_t]
      cursor[col_t] += width
      lab = jax.lax.slice(labels_by_type[col_t], (s,), (s + width,))
      rows_d.setdefault(e, []).append(rows_parent)
      cols_d.setdefault(e, []).append(jnp.where(mask, lab, -1))
      mask_d.setdefault(e, []).append(mask)
      if with_edge:
        eid_d.setdefault(e, []).append(eids)
      hop_edges.setdefault(e, []).append(mask.sum().astype(jnp.int32))

  nodes = {t: sorted_nodes_by_label(*seen[t], budgets[t],
                                    fast_compile=True) for t in types}
  result = dict(
      node=nodes,
      node_count={t: seen[t][2] for t in types},
      row={e: jnp.concatenate(v) for e, v in rows_d.items()},
      col={e: jnp.concatenate(v) for e, v in cols_d.items()},
      edge_mask={e: jnp.concatenate(v) for e, v in mask_d.items()},
      batch={t: jax.lax.slice(nodes[t], (0,), (seeds[t].shape[0],))
             for t in seeds},
      seed_labels=seed_labels,
      num_sampled_nodes={t: jnp.stack(v) for t, v in hop_nodes.items()},
      num_sampled_edges={e: jnp.stack(v) for e, v in hop_edges.items()},
      hop_rows_read={e: jnp.stack(v) for e, v in hop_rows.items()},
  )
  if with_edge:
    result['edge'] = {e: jnp.concatenate(v) for e, v in eid_d.items()}
  return result


def multihop_sample_many(one_hop: OneHopFn,
                         seeds_stack: jax.Array,
                         n_valid_stack: jax.Array,
                         fanouts: Sequence[int],
                         key: jax.Array,
                         with_edge: bool = False):
  """T sampling batches in ONE dispatch via lax.scan.

  seeds_stack: [T, B]; n_valid_stack: [T]. Returns the stacked out dicts
  [T, ...]. Amortizes per-dispatch latency when host round-trips
  dominate (e.g. small batches over an interconnect-attached
  accelerator); iterations are independent, so results are identical to
  T separate multihop_sample calls.
  """
  def step(k, inp):
    seeds, n_valid = inp
    k, sub = jax.random.split(k)
    out = multihop_sample(one_hop, seeds, n_valid, fanouts, sub,
                          with_edge=with_edge)
    return k, out

  _, outs = jax.lax.scan(step, key, (seeds_stack, n_valid_stack))
  return outs
