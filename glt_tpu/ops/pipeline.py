"""Functional multi-hop sampling pipeline.

The hop loop shared by the single-device NeighborSampler and the SPMD
(shard_map) training step: sample -> dense-induce -> advance frontier,
all static shapes. Mirrors the reference homo loop
(neighbor_sampler.py:186-230) with the padded-frontier design described
in the NeighborSampler docstring.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..typing import as_str
from ..utils.env import knob
from .sample import NeighborOutput
from .unique import (dense_assign, dense_init, dense_reset,
                     sorted_hop_dedup, sorted_hop_dedup_fused,
                     sorted_nodes_by_label)

OneHopFn = Callable[[jax.Array, int, jax.Array, jax.Array], NeighborOutput]


def dedup_engine() -> str:
  """Which inducer backs the hop loops (:func:`multihop_sample` and
  :func:`multihop_sample_hetero`), the one choice a hop loop has left:
  'table' (dense scatter tables over [N]) or 'sort' (sort-merge over
  batch-sized arrays; see ops/unique.py). GLT_DEDUP=table|sort|auto
  overrides; auto is 'sort' on a TPU and 'table' elsewhere. Every line
  of PERF_LEDGER.jsonl was produced by 'sort'; 'table' is not measured
  on the chip. The hetero sorted path restores slot order with one
  extra per-type sort so per-etype slicing stays exact."""
  mode = knob('GLT_DEDUP', 'auto')
  if mode not in ('auto', 'sort', 'table'):
    raise ValueError(f'GLT_DEDUP={mode!r}: expected auto|sort|table')
  if mode == 'auto':
    return 'sort' if jax.default_backend() == 'tpu' else 'table'
  return mode


def fused_hops() -> bool:
  """GLT_FUSED_HOP switches the sort engine's per-hop assign stage to
  :func:`glt_tpu.ops.unique.sorted_hop_dedup_fused` (one narrow sort +
  one packed scatter per hop instead of two wide sorts; within-hop new
  labels come out in value order rather than slot order — see its
  docstring for why that is the only observable change). The seed hop
  always stays on the exact path so ``batch``/``seed_labels`` remain
  bit-identical to the table engine. Read at trace time, like
  :func:`dedup_engine`.

  Default is ``auto``: ON when the sort engine is active on a TPU, OFF
  elsewhere. Every line of PERF_LEDGER.jsonl was produced with it on
  (``dedup0/1/2`` in PERF.md section 5 are this assign); the plain
  two-sort assign is not measured on the chip. GLT_FUSED_HOP=1|0
  forces."""
  mode = knob('GLT_FUSED_HOP', 'auto').lower()
  if mode == 'auto':
    return dedup_engine() == 'sort' and jax.default_backend() == 'tpu'
  return mode in ('1', 'true')


def checksum_outputs(out: Dict[str, jax.Array]) -> jax.Array:
  """Fold every multihop output into one scalar so no pipeline stage is
  dead code under jit. Benchmarks that return only an edge-count
  reduction get their neighbor gathers and dedup deleted by XLA (their
  values feed nothing) and then measure a program no real consumer
  runs; summing each output is the static-shape equivalent of the
  reference bench materializing full sample results."""
  acc = jnp.zeros((), jnp.int32)
  for k in ('node', 'row', 'col', 'batch', 'seed_labels'):
    acc += out[k].sum(dtype=jnp.int32)
  acc += out['edge_mask'].sum(dtype=jnp.int32)
  acc += out['node_count'].sum(dtype=jnp.int32)
  return acc


def make_dedup_tables(num_nodes: int):
  """Allocate inducer state for the active dedup engine: the dense
  [N+1] tables for 'table', or 1-element placeholders for 'sort' —
  whose seen-set lives in batch-sized arrays, so allocating real tables
  would pin O(N) dead HBM per node type (~900 MB on papers100M). The
  engine choice is read once here and again at trace time in
  :func:`multihop_sample`; GLT_DEDUP must not change between allocating
  a sampler's tables and tracing its step."""
  from .unique import dense_make_tables
  if dedup_engine() == 'sort':
    # two distinct buffers: callers donate both, and donating one buffer
    # twice is an XLA execute error. Shape (1,) doubles as the engine
    # tag _check_engine_tables verifies at trace time (dense tables are
    # always [num_nodes + 1] >= 2).
    return jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)
  return dense_make_tables(num_nodes)


def _check_engine_tables(table) -> None:
  """Trace-time guard for the alloc-time/trace-time engine contract:
  running the dense path against the sort engine's 1-element placeholder
  tables would produce silently wrong samples (every dense_assign
  collides on slot 0). Raising here turns an env flip between
  make_dedup_tables and the jitted trace into a loud error."""
  if dedup_engine() == 'table' and table.shape[0] < 2:
    raise ValueError(
        "dedup tables were allocated for the 'sort' engine (placeholder "
        "shape (1,)) but GLT_DEDUP/backend now selects 'table'; "
        "re-allocate with make_dedup_tables under the active engine")


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
  ``h`` hops of a seed. Every dedup engine below hands labels out hop
  by hop, so a node first seen at hop ``h`` has a label under the budget
  of the first ``h`` hops: a static prefix of the node buffer, as
  :func:`edge_hop_offsets` is of the edge slots (tests/test_node_trim.py
  pins it for each; tests/sampler_oracle.py checks it of every batch)."""
  return [sample_budget(batch_size, fanouts[:h])
          for h in range(len(fanouts) + 1)]


def hop_fanouts(fanouts: Sequence[int]) -> Optional[Tuple[int, ...]]:
  """The promise behind ``Batch.hop_fanouts``: ``(K_0, K_1, ...)``,
  ``K_h = |fanout_h|``, where the hop loop that will run keeps slot
  order, ``None`` where it does not. Every loop below writes a hop's
  parents as ``jnp.repeat(frontier_labels, K_h)``, so hop ``h``'s block
  of edge slots (:func:`edge_hop_offsets`) is groups of ``K_h`` adjacent
  slots with one value of ``col`` each, the label of the frontier slot
  the group was drawn for. A frontier slot that is no new head (a
  duplicate, a pad) has every slot of its group masked, and a node is a
  new head in one hop, so a label heads at most one group with a live
  slot in the whole batch. The table engine and the sort engine's fused
  assign hand a block back in slot order; the unfused ``sort`` loop's
  :func:`sorted_hop_dedup` permutes a block's edges, so it gives no
  promise. Read when a producer is built, like
  :func:`make_dedup_tables` (tests/sampler_oracle.py checks it of every
  batch)."""
  if dedup_engine() == 'sort' and not fused_hops():
    return None
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
                    table: jax.Array,
                    scratch: jax.Array,
                    with_edge: bool = False,
                    seed_mask: Optional[jax.Array] = None,
                    ) -> Dict[str, jax.Array]:
  """Runs the full hop loop; returns (out_dict, table, scratch).

  ``one_hop(frontier_ids, fanout, key, mask)`` performs one sampling hop.
  Tables are returned reset, ready for the next batch. The valid seeds
  are the first ``n_valid``, or where ``seed_mask`` ([batch] bool) is
  given, the slots it marks (edge seeds: the endpoints of a pair past
  the valid pairs are no suffix). Seeds may repeat: a repeated seed has
  one label, and every slot that holds it reads that label in
  ``seed_labels``.

  Result contract (both engines, homo and hetero): lanes where
  ``edge_mask`` is False carry -1 in the child-label buffer (``row``
  here; ``col`` holds parent labels which are always valid), and invalid
  seed slots carry -1 in ``seed_labels`` — consumers that ignore
  edge_mask still see one well-defined value per engine
  (tests/test_sorted_inducer.py pins this).
  """
  # trace-time tick on the shared hop loop: every enclosing program
  # that (re)traces it shows up under one process-wide label — the
  # pipeline-level row of compiles_total{fn=...} (jit-boundary callers
  # carry their own finer labels)
  from ..obs.perf import count_compile
  count_compile('ops.multihop_sample')
  if dedup_engine() == 'sort':
    out = _multihop_sample_sorted(one_hop, seeds, n_valid, fanouts, key,
                                  with_edge=with_edge, seed_mask=seed_mask)
    return out, table, scratch
  _check_engine_tables(table)
  batch_size = seeds.shape[0]
  budget = sample_budget(batch_size, fanouts)
  state = dense_init(table, scratch, budget)
  if seed_mask is None:
    seed_mask = jnp.arange(batch_size) < n_valid
  state, seed_labels = dense_assign(state, seeds, seed_mask)
  frontier_ids = jax.lax.slice(state.nodes, (0,), (batch_size,))
  frontier_labels = jnp.arange(batch_size, dtype=jnp.int32)
  frontier_mask = frontier_labels < state.count
  seed_count = state.count

  rows_parent, cols_child, emasks, eid_list = [], [], [], []
  hop_node_counts = [seed_count]
  hop_edge_counts, hop_rows = [], []
  cap = batch_size
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
    prev_count = state.count
    with jax.named_scope(f'dedup{hop_idx}'):
      state, labels_flat = dense_assign(
          state, out.nbrs.reshape(-1), out.mask.reshape(-1))
    rows_parent.append(jnp.repeat(frontier_labels, width))
    cols_child.append(labels_flat)
    emasks.append(out.mask.reshape(-1))
    if with_edge:
      eid_list.append(out.eids.reshape(-1))
    hop_node_counts.append(state.count - prev_count)
    hop_edge_counts.append(out.mask.sum().astype(jnp.int32))
    cap = cap * width
    frontier_labels = prev_count + jnp.arange(cap, dtype=jnp.int32)
    frontier_mask = frontier_labels < state.count
    frontier_ids = jnp.take(state.nodes,
                            jnp.minimum(frontier_labels, budget))

  table, scratch = dense_reset(state)
  out_dict = dict(
      node=jax.lax.slice(state.nodes, (0,), (budget,)),
      node_count=state.count,
      row=jnp.concatenate(cols_child),
      col=jnp.concatenate(rows_parent),
      edge_mask=jnp.concatenate(emasks),
      batch=jax.lax.slice(state.nodes, (0,), (batch_size,)),
      seed_labels=seed_labels,
      seed_count=seed_count,
      num_sampled_nodes=jnp.stack(hop_node_counts),
      num_sampled_edges=jnp.stack(hop_edge_counts),
      hop_rows_read=jnp.stack(hop_rows),
  )
  if with_edge:
    out_dict['edge'] = jnp.concatenate(eid_list)
  return out_dict, table, scratch


def _multihop_sample_sorted(one_hop: OneHopFn,
                            seeds: jax.Array,
                            n_valid: jax.Array,
                            fanouts: Sequence[int],
                            key: jax.Array,
                            with_edge: bool = False,
                            seed_mask: Optional[jax.Array] = None,
                            ) -> Dict[str, jax.Array]:
  """The hop loop on the sort-merge inducer (ops/unique.py
  sorted_hop_dedup): no [N]-sized tables, no scatters, no gathers — two
  multi-operand sorts + prefix scans per hop. Labels, node list, batch,
  seed_labels and per-hop counts match the table path EXACTLY; edge
  tuples (row/col/mask/eid) are the same multiset per hop block but in a
  permuted order within the block (consumers are order-insensitive; the
  parity test canonicalizes)."""
  batch_size = seeds.shape[0]
  budget = sample_budget(batch_size, fanouts)
  if seed_mask is None:
    seed_mask = jnp.arange(batch_size) < n_valid

  u_ids = jnp.zeros((0,), jnp.int32)
  u_labs = jnp.zeros((0,), jnp.int32)
  count = jnp.zeros((), jnp.int32)
  d = sorted_hop_dedup(u_ids, u_labs, count, seeds, seed_mask)
  # contract: seed_labels in seed-slot order (tiny unsort over [batch])
  seed_labels = jax.lax.sort([d['pos3'], d['labels3']], num_keys=1)[1]
  seed_labels = jnp.where(seed_mask, seed_labels, -1)
  seed_count = d['count2']
  u_ids, u_labs, count = d['u_ids2'], d['u_labs2'], d['count2']
  frontier_ids = d['ids3']
  frontier_labels = d['labels3']
  frontier_mask = d['new_head3']

  fused = fused_hops()
  rows_parent, cols_child, emasks, eid_list = [], [], [], []
  hop_node_counts = [seed_count]
  hop_edge_counts, hop_rows = [], []
  for hop_idx, fanout in enumerate(fanouts):
    width = abs(fanout)
    key, sub = jax.random.split(key)
    # trace-time stage labels for device profiler traces (the in-jit
    # counterpart of the host obs spans; see multihop_sample above)
    with jax.named_scope(f'sample_hop{hop_idx}'):
      out = one_hop(frontier_ids, fanout, sub, frontier_mask)
    hop_rows.append(hop_rows_read(out, frontier_ids))
    rows_flat = jnp.repeat(frontier_labels, width)
    ids_flat = out.nbrs.reshape(-1)
    mask_flat = out.mask.reshape(-1)
    if fused:
      # single-sort assign; per-element outputs come back in SLOT
      # order, so edge payloads (rows/mask/eids) never ride a sort
      with jax.named_scope(f'dedup{hop_idx}'):
        d = sorted_hop_dedup_fused(u_ids, u_labs, count, ids_flat,
                                   mask_flat)
      rows_parent.append(rows_flat)
      cols_child.append(d['labels3'])
      emasks.append(mask_flat)
      if with_edge:
        eid_list.append(out.eids.reshape(-1))
      frontier_ids = jnp.where(d['new_head3'],
                               ids_flat.astype(jnp.int32),
                               jnp.iinfo(jnp.int32).max)
    else:
      eflat = out.eids.reshape(-1) if with_edge else None
      with jax.named_scope(f'dedup{hop_idx}'):
        d = sorted_hop_dedup(u_ids, u_labs, count, ids_flat, mask_flat,
                             rows_flat, eflat, with_mask=True)
      rows_parent.append(d['rows3'])
      cols_child.append(d['labels3'])
      emasks.append(d['mask3'])
      if with_edge:
        eid_list.append(d['eids3'])
      frontier_ids = d['ids3']
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
  ``(offset, S, K)`` triples of its edge buffer, one a hop the loops
  below run for it (their own test skips the others): from ``offset``
  (:func:`hetero_edge_hop_offsets`) on, ``S = caps[h][row_t]`` groups of
  ``K = |num_neighbors[e][h]|`` adjacent slots. Both typed loops append
  ``jnp.repeat(f_labels, K)`` as a hop's parents and rebuild its edge
  buffers in slot order, whatever the dedup engine (the unfused ``sort``
  loop un-permutes its labels first), so the parent label is one value
  over a group, and a label heads at most one group with a live slot
  inside a relation: a node is a new head in one hop, and a frontier
  slot that is no new head has its whole group masked
  (tests/sampler_oracle.py checks it of every typed batch)."""
  offs = hetero_edge_hop_offsets(caps, trav, num_neighbors, num_hops)
  return {e: tuple((offs[e][h], caps[h][row_t], abs(num_neighbors[e][h]))
                   for h in range(num_hops)
                   if caps[h][row_t] and num_neighbors[e][h])
          for e, (row_t, _) in trav.items()}


def multihop_sample_hetero(one_hops, trav, num_neighbors, num_hops,
                           caps, budgets, seeds, n_valid, key, tables,
                           with_edge: bool = False, seed_mask=None):
  """Hetero hop loop shared by the single-device engine and the SPMD
  distributed engine (only the per-edge-type ``one_hops`` differ:
  in-HBM sampling vs the all_to_all collective version).

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
    tables: Dict[NodeType, (table, scratch)].

  Returns (result dict, out_tables) with per-type node lists, per-etype
  row(parent)/col(child) label buffers in traversal orientation, batch
  and seed_labels dicts, per-hop counts. Tables come back reset.
  """
  from ..obs.perf import count_compile
  count_compile('ops.multihop_sample_hetero')  # trace-time only
  from .unique import dense_assign, dense_init, dense_reset
  if dedup_engine() == 'sort':
    result = _multihop_sample_hetero_sorted(
        one_hops, trav, num_neighbors, num_hops, caps, budgets, seeds,
        n_valid, key, with_edge=with_edge, seed_mask=seed_mask)
    return result, tables
  for t in tables:
    _check_engine_tables(tables[t][0])
  types = list(budgets)
  states = {t: dense_init(tables[t][0], tables[t][1], budgets[t])
            for t in types}
  seed_labels = {}
  for t, s in seeds.items():
    mask = (seed_mask[t] if seed_mask
            else jnp.arange(s.shape[0]) < n_valid[t])
    states[t], seed_labels[t] = dense_assign(states[t], s, mask)

  frontier = {}
  for t in types:
    c0 = max(1, caps[0][t])
    labels = jnp.arange(c0, dtype=jnp.int32)
    frontier[t] = (jax.lax.slice(states[t].nodes, (0,), (c0,)),
                   labels, labels < states[t].count)

  rows_d, cols_d, mask_d, eid_d = {}, {}, {}, {}
  hop_nodes = {t: [states[t].count] for t in types}
  hop_edges, hop_rows = {}, {}
  for h in range(num_hops):
    per_type_nbrs = {t: [] for t in types}
    per_meta = []
    for e, (row_t, col_t) in trav.items():
      k = num_neighbors[e][h]
      if caps[h][row_t] == 0 or k == 0:
        continue
      width = abs(k)  # negative = full-neighborhood hop, window |k|
      f_ids, f_labels, f_mask = frontier[row_t]
      with jax.named_scope(f'sample_hop{h}'), jax.named_scope(as_str(e)):
        key, sub = jax.random.split(key)
        out = one_hops[e](f_ids, k, sub, f_mask)
      hop_rows.setdefault(e, []).append(hop_rows_read(out, f_ids))
      per_type_nbrs[col_t].append(
          (out.nbrs.reshape(-1), out.mask.reshape(-1)))
      per_meta.append((e, col_t, jnp.repeat(f_labels, width),
                       out.mask.reshape(-1),
                       out.eids.reshape(-1) if with_edge else None,
                       caps[h][row_t] * width))
    prev = {t: states[t].count for t in types}
    labels_by_type = {}
    for t, chunks in per_type_nbrs.items():
      if not chunks:
        continue
      with jax.named_scope(f'dedup{h}'), jax.named_scope(t):
        ids = jnp.concatenate([c[0] for c in chunks])
        ok = jnp.concatenate([c[1] for c in chunks])
        states[t], labels = dense_assign(states[t], ids, ok)
      labels_by_type[t] = labels
    cursor = {t: 0 for t in types}
    for e, col_t, rows_parent, mask, eids, width in per_meta:
      s = cursor[col_t]
      cursor[col_t] += width
      lab = jax.lax.slice(labels_by_type[col_t], (s,), (s + width,))
      rows_d.setdefault(e, []).append(rows_parent)
      cols_d.setdefault(e, []).append(lab)
      mask_d.setdefault(e, []).append(mask)
      if with_edge:
        eid_d.setdefault(e, []).append(eids)
      hop_edges.setdefault(e, []).append(mask.sum().astype(jnp.int32))
    for t in types:
      cap_next = max(1, caps[h + 1][t])
      labels = prev[t] + jnp.arange(cap_next, dtype=jnp.int32)
      frontier[t] = (
          jnp.take(states[t].nodes, jnp.minimum(labels, budgets[t])),
          labels, labels < states[t].count)
      hop_nodes[t].append(states[t].count - prev[t])

  out_tables = {}
  for t in types:
    out_tables[t] = dense_reset(states[t])
  result = dict(
      node={t: jax.lax.slice(states[t].nodes, (0,), (budgets[t],))
            for t in types},
      node_count={t: states[t].count for t in types},
      row={e: jnp.concatenate(v) for e, v in rows_d.items()},
      col={e: jnp.concatenate(v) for e, v in cols_d.items()},
      edge_mask={e: jnp.concatenate(v) for e, v in mask_d.items()},
      batch={t: jax.lax.slice(states[t].nodes, (0,),
                              (seeds[t].shape[0],)) for t in seeds},
      seed_labels=seed_labels,
      num_sampled_nodes={t: jnp.stack(v) for t, v in hop_nodes.items()},
      num_sampled_edges={e: jnp.stack(v) for e, v in hop_edges.items()},
      hop_rows_read={e: jnp.stack(v) for e, v in hop_rows.items()},
  )
  if with_edge:
    result['edge'] = {e: jnp.concatenate(v) for e, v in eid_d.items()}
  return result, out_tables


def _multihop_sample_hetero_sorted(one_hops, trav, num_neighbors,
                                   num_hops, caps, budgets, seeds,
                                   n_valid, key, with_edge: bool = False,
                                   seed_mask=None):
  """The hetero hop loop on the sort-merge inducer: per node type an
  append-form seen-set threaded through :func:`sorted_hop_dedup`, with
  one extra sort per (type, hop) un-permuting labels back to slot order
  so the per-etype cursor slicing below is identical to the table path.
  Label/node/batch/count semantics match the table engine exactly (same
  first-occurrence order over valid slots); per-etype edge tuples are
  the same sets in the same slot order."""
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
        if fused_hops():
          # single-sort assign already returns slot order — the
          # per-(type, hop) un-permuting sort below disappears too
          # a typed program holds one such dedup a type and hop: the
          # forms that compile quickly, same outputs
          d = sorted_hop_dedup_fused(*seen[t], ids, ok,
                                     fast_compile=True)
          labels_by_type[t] = d['labels3']
          frontier[t] = (jnp.where(d['new_head3'], ids.astype(jnp.int32),
                                   jnp.iinfo(jnp.int32).max),
                         d['labels3'], d['new_head3'])
        else:
          # rows/mask/eids are NOT threaded through the sorts here: the
          # hop's edge buffers are rebuilt in slot order below
          # (per_meta), so the dedup sorts stay as narrow as possible
          d = sorted_hop_dedup(*seen[t], ids, ok)
          # slot-order labels: cols for this hop's edge buffers
          labels_by_type[t] = jax.lax.sort([d['pos3'], d['labels3']],
                                           num_keys=1)[1]
          frontier[t] = (d['ids3'], d['labels3'], d['new_head3'])
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


def multihop_sample_hetero_many(one_hops, trav, num_neighbors,
                                num_hops, caps, budgets, seeds_stack,
                                n_valid_stack, key, tables,
                                with_edge: bool = False):
  """T hetero sampling batches in ONE dispatch via lax.scan — the
  hetero counterpart of :func:`multihop_sample_many` (the sampling
  half of the hetero superstep; ops/superstep.py scans the full train
  body the same way). ``seeds_stack``: Dict[NodeType, [T, B_t]];
  ``n_valid_stack``: Dict[NodeType, [T]]. Iterations are independent
  (the table path's per-batch reset contract carries over), so results
  are identical to T separate :func:`multihop_sample_hetero` calls on
  the same key stream."""
  def step(carry, inp):
    tabs, k = carry
    seeds, n_valid = inp
    k, sub = jax.random.split(k)
    out, tabs = multihop_sample_hetero(
        one_hops, trav, num_neighbors, num_hops, caps, budgets, seeds,
        n_valid, sub, tabs, with_edge=with_edge)
    return (tabs, k), out

  (tables, _), outs = jax.lax.scan(step, (tables, key),
                                   (seeds_stack, n_valid_stack))
  return outs, tables


def multihop_sample_many(one_hop: OneHopFn,
                         seeds_stack: jax.Array,
                         n_valid_stack: jax.Array,
                         fanouts: Sequence[int],
                         key: jax.Array,
                         table: jax.Array,
                         scratch: jax.Array,
                         with_edge: bool = False):
  """T sampling batches in ONE dispatch via lax.scan.

  seeds_stack: [T, B]; n_valid_stack: [T]. Returns (stacked out dicts
  [T, ...], table, scratch). Amortizes per-dispatch latency when host
  round-trips dominate (e.g. small batches over an interconnect-attached
  accelerator); the per-batch table reset keeps iterations independent,
  so results are identical to T separate multihop_sample calls.
  """
  def step(carry, inp):
    tab, scr, k = carry
    seeds, n_valid = inp
    k, sub = jax.random.split(k)
    out, tab, scr = multihop_sample(one_hop, seeds, n_valid, fanouts,
                                    sub, tab, scr, with_edge=with_edge)
    return (tab, scr, k), out

  (table, scratch, _), outs = jax.lax.scan(
      step, (table, scratch, key), (seeds_stack, n_valid_stack))
  return outs, table, scratch
