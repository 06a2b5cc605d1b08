"""Functional multi-hop sampling pipeline.

The hop loop shared by the single-device NeighborSampler and the SPMD
(shard_map) training step: sample -> dense-induce -> advance frontier,
all static shapes. Mirrors the reference homo loop
(neighbor_sampler.py:186-230) with the padded-frontier design described
in the NeighborSampler docstring.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

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
  :func:`multihop_sample_hetero`): 'table' (dense scatter tables, fast
  where random access is cheap — CPU) or 'sort' (sort-merge, fast where
  sorts are the vectorized primitive — TPU; see ops/unique.py).
  GLT_DEDUP=table|sort|auto overrides; auto picks by backend. The
  hetero sorted path restores slot order with one extra per-type sort
  so per-etype slicing stays exact."""
  mode = knob('GLT_DEDUP', 'auto')
  if mode not in ('auto', 'sort', 'table'):
    raise ValueError(f'GLT_DEDUP={mode!r}: expected auto|sort|table')
  if mode == 'auto':
    if knob('GLT_HOP_ENGINE', '') == 'pallas_fused':
      # the fused engine implements the sort/fused inducer CONTRACT in
      # its kernel (and its fallbacks land on the sort path), so the
      # auto dedup choice follows it on every backend — flipping to
      # dense tables mid-stack would allocate O(N) HBM nothing reads
      return 'sort'
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

  Default is ``auto``: ON when the sort engine is active on TPU —
  decided by the round-5 hardware A/B (benchmarks/tpu_runs/
  bench_sort_scan4.json: fused 29.87M vs plain 28.51M edges/s/chip,
  and fused >= plain in every scan/PRNG variant measured that round);
  OFF elsewhere (CPU measured it neutral-to-slower under contention).
  GLT_FUSED_HOP=1|0 forces."""
  mode = knob('GLT_FUSED_HOP', 'auto').lower()
  if mode == 'auto':
    return dedup_engine() == 'sort' and jax.default_backend() == 'tpu'
  return mode in ('1', 'true')


#: registered one-hop neighbor-read engines (sampler-side dispatch —
#: distinct from the dedup engines above, which pick the inducer)
HOP_ENGINES = ('element', 'window', 'pallas', 'pallas_fused')


def fused_walk_mode() -> str:
  """How the ``pallas_fused`` engine runs a multi-hop walk:

  * ``cross`` (the ``auto`` default) — the cross-hop fused walk: the
    WHOLE walk is one ``sample_walk_dedup`` kernel invocation whose
    grid spans every hop's frontier blocks, with the VMEM dedup table
    carried across hop boundaries (it never exists in HBM) and one
    window-DMA pipeline serving every hop.
  * ``per_hop`` — the unrolled per-hop kernel family
    (``sample_hop_dedup`` once per hop, table planes round-tripping
    HBM at each boundary) — the ISSUE-10 form, kept for A/B racing and
    as the fallback for shapes the walk does not serve (full-
    neighborhood/weighted hops never reach either form; an empty graph
    routes per-hop, whose empty-input early-outs are exact).

  ``GLT_FUSED_WALK=auto|cross|per_hop``; read at trace time like
  :func:`dedup_engine`. ``auto`` resolves to ``cross`` on a compiled
  TPU backend and ``per_hop`` under interpret mode: the walk's win is
  on-chip table residency and launch collapse, which the interpreter
  cannot deliver — it would only pay the (much larger) whole-walk
  interpret compile on every CPU parity/CI run. Forced values apply
  everywhere (the parity tests and the bench cost duel force
  ``cross`` in interpret mode deliberately)."""
  mode = knob('GLT_FUSED_WALK', 'auto')
  if mode not in ('auto', 'cross', 'per_hop'):
    raise ValueError(
        f'GLT_FUSED_WALK={mode!r}: expected auto|cross|per_hop')
  if mode == 'auto':
    from .pallas_kernels import interpret_default
    return 'per_hop' if interpret_default() else 'cross'
  return mode


def count_engine_fallback(requested: str, resolved: str,
                          reason: str) -> None:
  """Record an engine-fallback event on the metrics registry
  (``hop_engine_fallbacks_total{requested,resolved,reason}``): a
  requested ``pallas``/``pallas_fused`` engine silently resolving to a
  weaker one is an operational fact worth a counter, not just a log
  line — dashboards can alert on a fleet that quietly lost its fused
  kernels. Counted once per resolution event — a sampler gating a
  shape it can't fuse (callers dedupe per instance) — never per sample
  call or per trace-time env read."""
  import logging
  logging.getLogger(__name__).warning(
      'GLT_HOP_ENGINE=%s resolved to %r (%s)', requested, resolved,
      reason)
  try:
    from ..obs import get_recorder, get_registry
    get_registry().counter('hop_engine_fallbacks_total',
                           requested=requested, resolved=resolved,
                           reason=reason).inc()
    # breadcrumb for postmortems: a fleet that quietly lost its fused
    # kernels shows up in the flight-recorder ring next to whatever
    # tripped later
    get_recorder().record('hop_engine_fallback', requested=requested,
                          resolved=resolved, reason=reason)
  except Exception:  # metrics must never break sampling
    pass


def hop_engine() -> str:
  """How the samplers read neighbor values inside a uniform hop:

  * ``element`` — [S, fanout] per-element random gather (the XLA
    baseline; every backend).
  * ``window``  — [S, W] contiguous per-row window read via
    ``lax.gather`` + exact hub fix-up (ops/sample.py window path).
  * ``pallas``  — the one-hop megakernel: window DMA + offset pick +
    hub tail pass fused in one Pallas kernel
    (ops/pallas_kernels.py::sample_hop). Off-TPU backends run it in
    interpret mode (parity/CI); only a TPU backend runs it compiled.
  * ``pallas_fused`` — the full per-hop pipeline fused: sample + dedup
    against a VMEM-resident table in one kernel, plus the optional
    in-walk feature row gather (ops/pallas_kernels.py::
    sample_hop_dedup, routed via ops/sample.py::FusedHopPlan). Label
    semantics are exactly the ``sort+fused`` inducer's; hops the
    fusion cannot serve (hetero, weighted, full-neighborhood, stream
    overlays, table-overflow budgets) fall back to ``pallas`` with a
    counted ``hop_engine_fallbacks_total`` event.

  ``GLT_HOP_ENGINE`` selects. ``auto`` (the default) is ``element`` on
  every backend: a fixed answer, reached without compiling or probing
  anything. On a TPU that makes the sampler the XLA ``sort+fused`` path
  (:func:`dedup_engine` and :func:`fused_hops` resolve to it there), the
  one engine with a driver-recorded chip number (BENCH_r05.json). A
  Pallas family becomes a TPU default only after it has compiled on the
  chip at the widths its samplers run and matched ``sort+fused`` bit
  for bit there (``benchmarks/probe_pallas_compile.py`` rungs 8-10). On
  the v5e with jax 0.9.0 none does: Mosaic tiles a 1-D int32 HBM
  operand in 1024-element tiles and refuses the ``W``-wide window slice
  at an arbitrary edge offset that ``pallas``, ``pallas_fused`` (per-hop
  and cross-hop) and the hetero type plane are all built on. They stay
  reachable by an explicit ``GLT_HOP_ENGINE=``, where a compile failure
  raises. ``window`` is never a default either: the XLA window gather
  measured 437 ms for 153k x 96 rows on a v5e
  (benchmarks/tpu_runs/microbench_prims_tpu2.json).

  All engines draw offsets from the same ``jax.random`` stream, so
  results are bit-identical (ops/sample.py; ``pallas_fused`` is
  bit-identical to the ``sort+fused`` dedup engine, which it
  subsumes). Read at trace time, like :func:`dedup_engine`."""
  mode = knob('GLT_HOP_ENGINE', 'auto')
  if mode not in ('auto',) + HOP_ENGINES:
    raise ValueError(
        f'GLT_HOP_ENGINE={mode!r}: expected '
        'auto|element|window|pallas|pallas_fused')
  return 'element' if mode == 'auto' else mode


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
  ``h`` hops of a seed. Every engine below hands labels out in order of
  first appearance, hop by hop, so a node first seen at hop ``h`` has a
  label under the budget of the first ``h`` hops: a static prefix of the
  node buffer, as :func:`edge_hop_offsets` is of the edge slots
  (tests/test_node_trim.py pins it for each engine)."""
  return [sample_budget(batch_size, fanouts[:h])
          for h in range(len(fanouts) + 1)]


def multihop_sample(one_hop: OneHopFn,
                    seeds: jax.Array,
                    n_valid: jax.Array,
                    fanouts: Sequence[int],
                    key: jax.Array,
                    table: jax.Array,
                    scratch: jax.Array,
                    with_edge: bool = False,
                    fused_plan=None) -> Dict[str, jax.Array]:
  """Runs the full hop loop; returns (out_dict, table, scratch).

  ``one_hop(frontier_ids, fanout, key, mask)`` performs one sampling hop.
  Tables are returned reset, ready for the next batch.

  ``fused_plan`` (an :class:`glt_tpu.ops.sample.FusedHopPlan`) routes
  every hop through the ``pallas_fused`` kernel family instead of
  ``one_hop`` + the sort dedup — label semantics identical to the
  ``sort+fused`` engine (the seed hop stays on the exact path), with
  the dedup table resident in VMEM and, when the plan carries a
  ``gather_fn``, each hop's fresh feature rows gathered in-walk
  (``node_feats`` lands in the output dict). The dedup-engine knob is
  ignored on this path; ``table``/``scratch`` pass through untouched
  (allocate them with :func:`make_dedup_tables`, which hands out
  placeholders under this engine).

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
  if fused_plan is not None:
    out = _multihop_sample_fused(fused_plan, seeds, n_valid, fanouts,
                                 key, with_edge=with_edge)
    return out, table, scratch
  if dedup_engine() == 'sort':
    out = _multihop_sample_sorted(one_hop, seeds, n_valid, fanouts, key,
                                  with_edge=with_edge)
    return out, table, scratch
  _check_engine_tables(table)
  batch_size = seeds.shape[0]
  budget = sample_budget(batch_size, fanouts)
  state = dense_init(table, scratch, budget)
  seed_mask = jnp.arange(batch_size) < n_valid
  state, seed_labels = dense_assign(state, seeds, seed_mask)
  frontier_ids = jax.lax.slice(state.nodes, (0,), (batch_size,))
  frontier_labels = jnp.arange(batch_size, dtype=jnp.int32)
  frontier_mask = frontier_labels < state.count
  seed_count = state.count

  rows_parent, cols_child, emasks, eid_list = [], [], [], []
  hop_node_counts = [seed_count]
  hop_edge_counts = []
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
  )
  if with_edge:
    out_dict['edge'] = jnp.concatenate(eid_list)
  return out_dict, table, scratch


def _multihop_sample_sorted(one_hop: OneHopFn,
                            seeds: jax.Array,
                            n_valid: jax.Array,
                            fanouts: Sequence[int],
                            key: jax.Array,
                            with_edge: bool = False) -> Dict[str, jax.Array]:
  """The hop loop on the sort-merge inducer (ops/unique.py
  sorted_hop_dedup): no [N]-sized tables, no scatters, no gathers — two
  multi-operand sorts + prefix scans per hop. Labels, node list, batch,
  seed_labels and per-hop counts match the table path EXACTLY; edge
  tuples (row/col/mask/eid) are the same multiset per hop block but in a
  permuted order within the block (consumers are order-insensitive; the
  parity test canonicalizes)."""
  batch_size = seeds.shape[0]
  budget = sample_budget(batch_size, fanouts)
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
  hop_edge_counts = []
  for hop_idx, fanout in enumerate(fanouts):
    width = abs(fanout)
    key, sub = jax.random.split(key)
    # trace-time stage labels for device profiler traces (the in-jit
    # counterpart of the host obs spans; see multihop_sample above)
    with jax.named_scope(f'sample_hop{hop_idx}'):
      out = one_hop(frontier_ids, fanout, sub, frontier_mask)
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
  )
  if with_edge:
    out_dict['edge'] = jnp.concatenate(eid_list)
  return out_dict


def _fused_seed_hop(plan, seeds, n_valid, budget):
  """The exact seed hop shared by both fused walk forms: sorted-path
  seed dedup (``batch``/``seed_labels`` bit-identical to every engine)
  plus, when the plan gathers, the seed rows' feature block. Returns
  ``(d, seed_labels, feats|None)`` with ``d`` the raw
  ``sorted_hop_dedup`` dict."""
  big = jnp.iinfo(jnp.int32).max
  batch_size = seeds.shape[0]
  seed_mask = jnp.arange(batch_size) < n_valid
  zero = jnp.zeros((0,), jnp.int32)
  d = sorted_hop_dedup(zero, zero, jnp.zeros((), jnp.int32), seeds,
                       seed_mask)
  seed_labels = jax.lax.sort([d['pos3'], d['labels3']], num_keys=1)[1]
  seed_labels = jnp.where(seed_mask, seed_labels, -1)
  feats = None
  if plan.gather_fn is not None:
    feats = jnp.zeros((budget + 1, plan.feat_dim), plan.feat_dtype)
    # seed rows in label order: one tiny [B] sort
    lab_key = jnp.where(d['new_head3'], d['labels3'], big)
    seed_sorted = jax.lax.sort(
        [lab_key, jnp.where(d['new_head3'], d['ids3'], big)],
        num_keys=1)[1]
    feats = _gather_fresh_rows(feats, plan.gather_fn, seed_sorted,
                               jnp.zeros((), jnp.int32), d['count2'],
                               budget)
  return d, seed_labels, feats


def _fused_output_dict(plan, nodes, count, cols_child, rows_parent,
                       emasks, eid_list, batch_size, seed_labels,
                       seed_count, hop_node_counts, hop_edge_counts,
                       feats, with_edge, budget):
  """Assemble the multihop output surface shared by the per-hop fused
  loop and the cross-hop walk (identical contract, one constructor)."""
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
  )
  if with_edge:
    out_dict['edge'] = jnp.concatenate(eid_list)
  if feats is not None:
    # padded lanes (label >= count) must match the post-hoc gather at
    # node == -1 bit-for-bit, so parity with gather_features holds on
    # EVERY lane, not just the live prefix
    pad_row = plan.gather_fn(jnp.full((1,), -1, jnp.int32))
    lanes = jnp.arange(budget) < count
    out_dict['node_feats'] = jnp.where(
        lanes[:, None], feats[:budget], pad_row.astype(feats.dtype))
  return out_dict


def _multihop_sample_walk(plan, seeds, n_valid, fanouts, key,
                          with_edge: bool = False):
  """The CROSS-HOP fused walk (GLT_FUSED_WALK=cross, the default): one
  ``sample_walk_dedup`` kernel invocation runs every uniform hop —
  window DMA, offset pick, hub fix-up and dedup-table assign — with
  the table resident in VMEM across hop boundaries. The XLA epilogue
  restores the exact ``sorted_hop_dedup_fused`` label contract with an
  incremental remap table: per hop, one narrow [M_h] sort ranks the
  fresh ids by value, the (provisional -> final) mapping accumulates
  into ``R``, and every hop's emitted labels are one gather through
  ``R`` — no per-hop table rewrite exists because the table's
  provisional labels never leave the kernel. Outputs bit-identical to
  ``sort+fused`` and to the per-hop form on every surface (asserted in
  interpret mode by tests/test_pallas_fused.py)."""
  from .pallas_kernels import sample_walk_dedup, walk_geometry
  from .sample import hop_valid_mask, walk_hop_uniforms, \
      _value_order_ranks
  big = jnp.iinfo(jnp.int32).max
  batch_size = seeds.shape[0]
  budget = sample_budget(batch_size, fanouts)
  d, seed_labels, feats = _fused_seed_hop(plan, seeds, n_valid, budget)
  seed_count = d['count2']
  u_ids, u_labs = d['u_ids2'], d['u_labs2']
  count = seed_count
  num_edges = int(plan.indices.shape[0])

  hops, _ = walk_geometry(batch_size, fanouts)
  u_hops = walk_hop_uniforms(key, batch_size, fanouts, plan.replace)
  s1_pad = hops[0]['s_pad']
  pad1 = s1_pad - batch_size
  seed_ids = jnp.pad(d['ids3'].astype(jnp.int32), (0, pad1),
                     constant_values=big)
  seed_ok = jnp.pad(d['new_head3'].astype(jnp.int32), (0, pad1))
  stab_ids = jnp.pad(
      jnp.where(d['new_head3'], d['ids3'].astype(jnp.int32), -1),
      (0, pad1), constant_values=-1)
  stab_labs = jnp.pad(d['labels3'].astype(jnp.int32), (0, pad1))

  picks_t, eidp_t, prov_t, newh_t = sample_walk_dedup(
      plan.indices_win,
      plan.edge_ids_win if plan.edge_ids is not None else None,
      plan.indptr_pad, seed_ids, seed_ok, stab_ids, stab_labs,
      seed_count, u_hops,
      fanouts=tuple(int(f) for f in fanouts), width=plan.width,
      num_nodes=int(plan.indptr.shape[0]) - 1, num_edges=num_edges,
      table_slots=plan.table_slots, batch_size=batch_size,
      replace=plan.replace, interpret=plan.interpret)

  # XLA epilogue: per hop, recompute the draw mask from the shared
  # degree formula, rank the fresh ids by value, extend the
  # provisional->final remap, and emit the final-label surfaces
  remap = jnp.arange(budget + 1, dtype=jnp.int32)  # seeds: identity
  frontier_ids = d['ids3']
  frontier_mask = d['new_head3']
  frontier_labels = d['labels3']
  rows_parent, cols_child, emasks, eid_list = [], [], [], []
  hop_node_counts = [seed_count]
  hop_edge_counts = []
  for h_idx, fanout in enumerate(fanouts):
    h = hops[h_idx]
    s_h, k_h = h['s'], h['k']
    m_h = s_h * k_h
    picks = picks_t[h_idx][:s_h]
    prov_flat = prov_t[h_idx][:s_h].reshape(-1)
    nh = newh_t[h_idx][:s_h].reshape(-1) != 0
    ids_flat = picks.reshape(-1).astype(jnp.int32)
    mask = hop_valid_mask(plan.indptr, frontier_ids, k_h,
                          frontier_mask, plan.replace)
    mask_flat = mask.reshape(-1)
    sorted_new_ids, val_rank = _value_order_ranks(
        ids_flat, nh, prov_flat - count, m_h)
    final = count + jnp.take(
        val_rank, jnp.clip(prov_flat - count, 0, m_h - 1))
    remap = remap.at[jnp.where(nh, prov_flat, budget)].set(
        jnp.where(nh, final, remap[budget]))
    labels3 = jnp.where(
        mask_flat, jnp.take(remap, jnp.clip(prov_flat, 0, budget)), -1)
    new_count = nh.sum(dtype=jnp.int32)

    rows_parent.append(jnp.repeat(frontier_labels, k_h))
    cols_child.append(labels3)
    emasks.append(mask_flat)
    if with_edge:
      eid_list.append(eidp_t[h_idx][:s_h].reshape(-1))
    u_ids = jnp.concatenate([u_ids, jnp.where(nh, ids_flat, big)])
    u_labs = jnp.concatenate([u_labs, jnp.where(nh, labels3, big)])
    if feats is not None:
      with jax.named_scope(f'gather_walk{h_idx}'):
        feats = _gather_fresh_rows(feats, plan.gather_fn,
                                   sorted_new_ids, count, new_count,
                                   budget)
    hop_node_counts.append(new_count)
    hop_edge_counts.append(mask_flat.sum().astype(jnp.int32))
    frontier_ids = jnp.where(nh, ids_flat, big)
    frontier_mask = nh
    frontier_labels = labels3
    count = count + new_count

  nodes = sorted_nodes_by_label(u_ids, u_labs, count, budget)
  return _fused_output_dict(
      plan, nodes, count, cols_child, rows_parent, emasks, eid_list,
      batch_size, seed_labels, seed_count, hop_node_counts,
      hop_edge_counts, feats, with_edge, budget)


def _multihop_sample_fused(plan, seeds, n_valid, fanouts, key,
                           with_edge: bool = False):
  """The hop loop on the ``pallas_fused`` kernel family: the seed hop
  dedups on the EXACT sorted path (same as the fused sort engine, so
  ``batch``/``seed_labels`` stay bit-identical to every engine), its
  uniques seed the VMEM dedup table, and each subsequent hop is ONE
  fused kernel call (sample + table assign) plus the narrow value-order
  relabel — outputs bit-identical to ``sort+fused``
  (GLT_DEDUP=sort GLT_FUSED_HOP=1), asserted in interpret mode by
  tests/test_pallas_fused.py. With ``plan.gather_fn``, each hop's fresh
  unique rows are feature-gathered while the walk runs and assembled
  into ``node_feats`` (label order = row order, exactly
  ``gather_features(feat, node)`` including the padded-lane values).

  Under ``GLT_FUSED_WALK=cross`` (the default) a walk whose shapes the
  cross-hop kernel serves — uniform positive fanouts over a non-empty
  graph — routes to :func:`_multihop_sample_walk` instead: ONE kernel
  invocation for the whole walk, the dedup table never leaving VMEM."""
  if (fused_walk_mode() == 'cross' and plan.indices.shape[0] > 0
      and len(fanouts) > 0 and all(int(f) > 0 for f in fanouts)
      and (not with_edge or plan.edge_ids is not None)):
    # with_edge over a graph WITHOUT an edge-id plane stays per-hop:
    # its eids contract is the raw CSR slots, which only exist where
    # the offsets do — in the per-hop wrapper's XLA prologue (the walk
    # draws offsets on-chip and never materializes slots)
    return _multihop_sample_walk(plan, seeds, n_valid, fanouts, key,
                                 with_edge=with_edge)
  big = jnp.iinfo(jnp.int32).max
  batch_size = seeds.shape[0]
  budget = sample_budget(batch_size, fanouts)

  d, seed_labels, feats = _fused_seed_hop(plan, seeds, n_valid, budget)
  seed_count = d['count2']
  u_ids, u_labs, count = d['u_ids2'], d['u_labs2'], d['count2']
  frontier_ids = d['ids3']
  frontier_labels = d['labels3']
  frontier_mask = d['new_head3']
  table = plan.init_table(jnp.where(d['new_head3'], d['ids3'], -1),
                          d['labels3'],
                          d['new_head3'].astype(jnp.int32))

  rows_parent, cols_child, emasks, eid_list = [], [], [], []
  hop_node_counts = [seed_count]
  hop_edge_counts = []
  for hop_idx, fanout in enumerate(fanouts):
    width = abs(fanout)
    key, sub = jax.random.split(key)
    # one fused kernel = the whole sample+dedup stage; a single device
    # profiler scope covers what sample_hop<i>+dedup<i> label elsewhere
    with jax.named_scope(f'sample_dedup_fused{hop_idx}'):
      out, dd, table = plan(frontier_ids, fanout, sub, frontier_mask,
                            table, count)
    ids_flat = out.nbrs.reshape(-1).astype(jnp.int32)
    mask_flat = out.mask.reshape(-1)
    rows_parent.append(jnp.repeat(frontier_labels, width))
    cols_child.append(dd['labels3'])
    emasks.append(mask_flat)
    if with_edge:
      eid_list.append(out.eids.reshape(-1))
    u_ids = jnp.concatenate(
        [u_ids, jnp.where(dd['new_head3'], ids_flat, big)])
    u_labs = jnp.concatenate(
        [u_labs, jnp.where(dd['new_head3'], dd['labels3'], big)])
    if feats is not None:
      with jax.named_scope(f'gather_fused{hop_idx}'):
        feats = _gather_fresh_rows(feats, plan.gather_fn,
                                   dd['sorted_new_ids'], count,
                                   dd['new_count'], budget)
    frontier_ids = jnp.where(dd['new_head3'], ids_flat, big)
    frontier_labels = dd['labels3']
    frontier_mask = dd['new_head3']
    hop_node_counts.append(dd['new_count'])
    hop_edge_counts.append(out.mask.sum().astype(jnp.int32))
    count = dd['count2']

  nodes = sorted_nodes_by_label(u_ids, u_labs, count, budget)
  return _fused_output_dict(
      plan, nodes, count, cols_child, rows_parent, emasks, eid_list,
      batch_size, seed_labels, seed_count, hop_node_counts,
      hop_edge_counts, feats, with_edge, budget)


def _gather_fresh_rows(feats, gather_fn, ids_sorted, base, n_new,
                       budget):
  """Gather one stage's fresh unique rows (ascending id = label order)
  and scatter them at labels ``base..base+n_new-1``; lanes past
  ``n_new`` land on the sink row. The gather itself rides the plan's
  ``gather_fn`` — the resolve_row_gather seam, so injected/Pallas row
  kernels serve the fused path exactly like the post-hoc one."""
  cap = ids_sorted.shape[0]
  vals = gather_fn(ids_sorted)
  iota = jnp.arange(cap, dtype=jnp.int32)
  idx = jnp.where(iota < n_new, base + iota, budget)
  idx = jnp.clip(idx, 0, budget)
  return feats.at[idx].set(vals.astype(feats.dtype))


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


def multihop_sample_hetero(one_hops, trav, num_neighbors, num_hops,
                           caps, budgets, seeds, n_valid, key, tables,
                           with_edge: bool = False, fused_plan=None):
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
    tables: Dict[NodeType, (table, scratch)].
    fused_plan: a :class:`glt_tpu.ops.sample.HeteroFusedPlan` — routes
      every hop through ONE padded multi-edge-type ``sample_hop_dedup``
      invocation (per-edge-type sampling batched over the flat
      edge-type plane, per-type dedup namespaces via type-tagged keys)
      instead of the per-etype ``one_hops`` + per-type sort dedup.
      Label semantics identical to the per-edge-type sorted reference
      with GLT_FUSED_HOP=1; ``tables`` pass through untouched.

  Returns (result dict, out_tables) with per-type node lists, per-etype
  row(parent)/col(child) label buffers in traversal orientation, batch
  and seed_labels dicts, per-hop counts. Tables come back reset.
  """
  from ..obs.perf import count_compile
  count_compile('ops.multihop_sample_hetero')  # trace-time only
  from .unique import dense_assign, dense_init, dense_reset
  if fused_plan is not None:
    result = _multihop_sample_hetero_fused(
        fused_plan, trav, num_neighbors, num_hops, caps, budgets,
        seeds, n_valid, key, with_edge=with_edge)
    return result, tables
  if dedup_engine() == 'sort':
    result = _multihop_sample_hetero_sorted(
        one_hops, trav, num_neighbors, num_hops, caps, budgets, seeds,
        n_valid, key, with_edge=with_edge)
    return result, tables
  for t in tables:
    _check_engine_tables(tables[t][0])
  types = list(budgets)
  states = {t: dense_init(tables[t][0], tables[t][1], budgets[t])
            for t in types}
  seed_labels = {}
  for t, s in seeds.items():
    mask = jnp.arange(s.shape[0]) < n_valid[t]
    states[t], seed_labels[t] = dense_assign(states[t], s, mask)

  frontier = {}
  for t in types:
    c0 = max(1, caps[0][t])
    labels = jnp.arange(c0, dtype=jnp.int32)
    frontier[t] = (jax.lax.slice(states[t].nodes, (0,), (c0,)),
                   labels, labels < states[t].count)

  rows_d, cols_d, mask_d, eid_d = {}, {}, {}, {}
  hop_nodes = {t: [states[t].count] for t in types}
  hop_edges = {}
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
  )
  if with_edge:
    result['edge'] = {e: jnp.concatenate(v) for e, v in eid_d.items()}
  return result, out_tables


def _multihop_sample_hetero_sorted(one_hops, trav, num_neighbors,
                                   num_hops, caps, budgets, seeds,
                                   n_valid, key, with_edge: bool = False):
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
      mask = jnp.arange(s.shape[0]) < n_valid[t]
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
  hop_edges = {}
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
  )
  if with_edge:
    result['edge'] = {e: jnp.concatenate(v) for e, v in eid_d.items()}
  return result


def _pad_cols(a, k_max):
  """Pad a [S, k] plane to [S, k_max] lanes (zeros — padded lanes ride
  an all-False validity plane, so the kernel never probes them)."""
  k = a.shape[1]
  return a if k == k_max else jnp.pad(a, ((0, 0), (0, k_max - k)))


def _empty_frontier(c0):
  """Placeholder frontier for a type with no live rows — identical to
  the sorted reference's (zero ids, -1 labels, all-False mask)."""
  return (jnp.zeros((c0,), jnp.int32), jnp.full((c0,), -1, jnp.int32),
          jnp.zeros((c0,), bool))


def _multihop_sample_hetero_fused(plan, trav, num_neighbors, num_hops,
                                  caps, budgets, seeds, n_valid, key,
                                  with_edge: bool = False):
  """The hetero hop loop on the ``pallas_fused`` kernel family: each
  hop's per-edge-type sampling runs as ONE padded multi-edge-type
  ``sample_hop_dedup`` invocation over the flat edge-type plane.

  Per hop: the XLA prologue draws offsets per edge type from the SAME
  key sequence as the reference loop (bit-identical offsets by
  construction), rebases each segment's window starts into the flat
  plane, pads fanouts to the hop's K_max behind the validity lanes,
  and concatenates the per-etype hub fix-ups. The kernel samples every
  segment's windows through one double-buffered DMA pipeline and
  probes/inserts the type-tagged picks into ONE VMEM-resident table —
  global ids never collide across types, so the per-type dedup
  namespaces come free. The XLA epilogue restores the exact per-type
  ``sorted_hop_dedup_fused`` label contract (new ids labeled
  ``count_t..count_t+n_t-1`` in within-hop VALUE order per type) with
  one narrow [m_t] sort per (type, hop) and an incremental provisional
  -> final remap ``R`` (the cross-hop walk's epilogue pattern), so the
  kernel's global first-occurrence labels never leave this function.

  Bit-identical to the per-edge-type sorted reference
  (GLT_DEDUP=sort GLT_FUSED_HOP=1) on every output surface; masked-out
  edge lanes are undefined per engine, as for every fused form
  (asserted in interpret mode by tests/test_pallas_fused.py)."""
  from .pallas_kernels import sample_hop_dedup
  from .sample import _draw_hop, _hub_fixup_inputs, _slots_i32
  big = jnp.iinfo(jnp.int32).max
  types = list(budgets)
  budget_total = int(plan.budget_total)

  # -- exact multi-type seed hop (identical to the sorted reference) --
  seen, seed_labels, frontier = {}, {}, {}
  zero = jnp.zeros((0,), jnp.int32)
  for t in types:
    c0 = max(1, caps[0][t])
    if t in seeds:
      s = seeds[t]
      mask = jnp.arange(s.shape[0]) < n_valid[t]
      d = sorted_hop_dedup(zero, zero, jnp.zeros((), jnp.int32), s,
                           mask)
      sl = jax.lax.sort([d['pos3'], d['labels3']], num_keys=1)[1]
      seed_labels[t] = jnp.where(mask, sl, -1)
      seen[t] = (d['u_ids2'], d['u_labs2'], d['count2'])
      frontier[t] = (d['ids3'], d['labels3'], d['new_head3'])
    else:
      seen[t] = (zero, zero, jnp.zeros((), jnp.int32))
      frontier[t] = _empty_frontier(c0)

  # provisional-global label space: type t's seed uniques take the
  # range [gbase_t, gbase_t + count_t) (gbase = running total in type
  # order); R maps provisional-global -> final per-type labels.
  count = {t: seen[t][2] for t in types}
  gcount = jnp.zeros((), jnp.int32)
  remap = jnp.zeros((budget_total + 1,), jnp.int32)
  ins_ids, ins_labs, ins_ok = [], [], []
  for t in types:
    if t not in seeds:
      continue
    ids3, labels3, nh3 = frontier[t]
    gid = jnp.where(nh3, ids3.astype(jnp.int32) + plan.type_base[t],
                    -1)
    gprov = jnp.where(nh3, gcount + labels3, 0)
    ins_ids.append(gid)
    ins_labs.append(gprov)
    ins_ok.append(nh3.astype(jnp.int32))
    remap = remap.at[jnp.where(nh3, gcount + labels3,
                               budget_total)].set(
        jnp.where(nh3, labels3, remap[budget_total]))
    gcount = gcount + count[t]
  table = plan.init_table(
      jnp.concatenate(ins_ids) if ins_ids else zero,
      jnp.concatenate(ins_labs) if ins_labs else zero,
      jnp.concatenate(ins_ok) if ins_ok else zero)

  rows_d, cols_d, mask_d, eid_d = {}, {}, {}, {}
  hop_nodes = {t: [count[t]] for t in types}
  hop_edges = {}
  for h in range(num_hops):
    # -- XLA prologue: per-etype draws (reference key sequence) -------
    segs = []
    for e, (row_t, col_t) in trav.items():
      k = num_neighbors[e][h]
      if caps[h][row_t] == 0 or k == 0:
        continue
      f_ids, f_labels, f_mask = frontier[row_t]
      key, sub = jax.random.split(key)
      sg = dict(e=e, row_t=row_t, col_t=col_t, k=k, s=f_ids.shape[0],
                f_labels=f_labels, empty=plan.num_edges[e] == 0)
      if not sg['empty']:
        indptr = plan.indptr[e]
        start, deg, offsets, mask = _draw_hop(
            indptr, f_ids.astype(indptr.dtype), k, sub, f_mask,
            plan.replace)
        sg.update(start=start, deg=deg, offsets=offsets, mask=mask,
                  slots=_slots_i32(start, offsets, plan.num_edges[e]))
      segs.append(sg)

    if segs:
      k_max = max(sg['k'] for sg in segs)
      starts_c, offs_c, valid_c, hub_idx_c, hub_slots_c = \
          [], [], [], [], []
      row_off = 0
      for sg in segs:
        sg['row_off'] = row_off
        s_e, k = sg['s'], sg['k']
        if sg['empty']:
          starts_c.append(jnp.zeros((s_e,), jnp.int32))
          offs_c.append(jnp.zeros((s_e, k_max), jnp.int32))
          valid_c.append(jnp.zeros((s_e, k_max), jnp.int32))
        else:
          eb = plan.edge_base[sg['e']]
          starts_c.append((sg['start'].astype(jnp.int32) + eb))
          offs_c.append(_pad_cols(sg['offsets'], k_max))
          valid_c.append(_pad_cols(sg['mask'].astype(jnp.int32),
                                   k_max))
          h_e = min(plan.hub_count[sg['e']], s_e)
          hub_idx, hub_slots = _hub_fixup_inputs(
              sg['deg'], sg['slots'] + eb, plan.width, h_e, k, s_e)
          hub_idx_c.append(jnp.where(hub_idx >= 0,
                                     hub_idx + row_off, -1))
          hub_slots_c.append(_pad_cols(hub_slots, k_max))
        row_off += s_e
      if not hub_idx_c:  # static dummy row: -1 never matches a block
        hub_idx_c = [jnp.full((1,), -1, jnp.int32)]
        hub_slots_c = [jnp.zeros((1, k_max), jnp.int32)]
      tab_ids, tab_labs = table
      with jax.named_scope(f'sample_dedup_hetero_fused{h}'):
        picks, eidp, prov, newh, tab_ids, tab_labs = sample_hop_dedup(
            plan.indices_flat,
            plan.eids_flat if (with_edge and plan.eids_flat is not None)
            else None,
            jnp.concatenate(starts_c), jnp.concatenate(offs_c),
            jnp.concatenate(valid_c), jnp.concatenate(hub_idx_c),
            jnp.concatenate(hub_slots_c), tab_ids, tab_labs, gcount,
            width=plan.width, interpret=plan.interpret)
      table = (tab_ids, tab_labs)
      for sg in segs:
        r0, s_e, k = sg['row_off'], sg['s'], sg['k']
        sg['picks'] = jax.lax.slice(
            picks, (r0, 0), (r0 + s_e, k)).reshape(-1)
        sg['prov'] = jax.lax.slice(
            prov, (r0, 0), (r0 + s_e, k)).reshape(-1)
        sg['nh'] = jax.lax.slice(
            newh, (r0, 0), (r0 + s_e, k)).reshape(-1) != 0
        if with_edge and eidp is not None:
          sg['eidp'] = jax.lax.slice(
              eidp, (r0, 0), (r0 + s_e, k)).reshape(-1)
        sg['mask_flat'] = (jnp.zeros((s_e * k,), bool) if sg['empty']
                          else sg['mask'].reshape(-1))

    # -- XLA epilogue: per-type value-order relabel through R ---------
    labels_by_type = {}
    new_this_hop = jnp.zeros((), jnp.int32)
    for t in types:
      tsegs = [sg for sg in segs if sg['col_t'] == t]
      if not tsegs:
        frontier[t] = _empty_frontier(max(1, caps[h + 1][t]))
        hop_nodes[t].append(jnp.zeros((), jnp.int32))
        continue
      ids_t = jnp.concatenate([sg['picks'].astype(jnp.int32)
                               for sg in tsegs])
      prov_t = jnp.concatenate([sg['prov'] for sg in tsegs])
      nh_t = jnp.concatenate([sg['nh'] for sg in tsegs])
      mask_t = jnp.concatenate([sg['mask_flat'] for sg in tsegs])
      m_t = ids_t.shape[0]
      # one narrow 2-operand sort ranks this hop's fresh type-t ids by
      # VALUE (global order == local order: the type base is a shared
      # additive constant) — the sorted_hop_dedup_fused contract
      keyv = jnp.where(nh_t, ids_t, big)
      iota = jnp.arange(m_t, dtype=jnp.int32)
      sorted_ids, sorted_pos = jax.lax.sort([keyv, iota], num_keys=1)
      rank_slot = jnp.zeros((m_t + 1,), jnp.int32).at[
          jnp.where(sorted_ids < big, sorted_pos, m_t)].set(iota)[:m_t]
      final_t = count[t] + rank_slot
      remap = remap.at[jnp.where(nh_t, prov_t, budget_total)].set(
          jnp.where(nh_t, final_t, remap[budget_total]))
      labels3_t = jnp.where(
          mask_t, jnp.take(remap, jnp.clip(prov_t, 0, budget_total)),
          -1)
      labels_by_type[t] = labels3_t
      new_t = nh_t.sum(dtype=jnp.int32)
      local_ids = ids_t - plan.type_base[t]
      u_ids_t, u_labs_t, _ = seen[t]
      seen[t] = (
          jnp.concatenate([u_ids_t, jnp.where(nh_t, local_ids, big)]),
          jnp.concatenate([u_labs_t, jnp.where(nh_t, labels3_t, big)]),
          count[t] + new_t)
      frontier[t] = (jnp.where(nh_t, local_ids, big), labels3_t, nh_t)
      hop_nodes[t].append(new_t)
      count[t] = count[t] + new_t
      new_this_hop = new_this_hop + new_t
    gcount = gcount + new_this_hop

    # -- per-etype edge buffers, cursor-sliced in traversal order -----
    cursor = {t: 0 for t in types}
    for sg in segs:
      e, col_t, k = sg['e'], sg['col_t'], sg['k']
      w_e = sg['s'] * k
      c0 = cursor[col_t]
      cursor[col_t] += w_e
      lab = jax.lax.slice(labels_by_type[col_t], (c0,), (c0 + w_e,))
      rows_d.setdefault(e, []).append(jnp.repeat(sg['f_labels'], k))
      cols_d.setdefault(e, []).append(
          jnp.where(sg['mask_flat'], lab, -1))
      mask_d.setdefault(e, []).append(sg['mask_flat'])
      if with_edge:
        if sg['empty']:
          eid = jnp.full((w_e,), -1, jnp.int32)
        elif plan.has_eids[e]:
          eid = sg['eidp']
        else:  # no edge-id plane for this type: slot contract (local)
          eid = sg['slots'].reshape(-1)
        eid_d.setdefault(e, []).append(eid)
      hop_edges.setdefault(e, []).append(
          sg['mask_flat'].sum().astype(jnp.int32))

  nodes = {t: sorted_nodes_by_label(*seen[t], budgets[t])
           for t in types}
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
  )
  if with_edge:
    result['edge'] = {e: jnp.concatenate(v) for e, v in eid_d.items()}
  return result


def multihop_sample_hetero_many(one_hops, trav, num_neighbors,
                                num_hops, caps, budgets, seeds_stack,
                                n_valid_stack, key, tables,
                                with_edge: bool = False,
                                fused_plan=None):
  """T hetero sampling batches in ONE dispatch via lax.scan — the
  hetero counterpart of :func:`multihop_sample_many` (the sampling
  half of the hetero superstep; ops/superstep.py scans the full train
  body the same way). ``seeds_stack``: Dict[NodeType, [T, B_t]];
  ``n_valid_stack``: Dict[NodeType, [T]]. Iterations are independent
  (the fused path builds a fresh VMEM table per step; the table path's
  per-batch reset contract carries over), so results are identical to
  T separate :func:`multihop_sample_hetero` calls on the same key
  stream."""
  def step(carry, inp):
    tabs, k = carry
    seeds, n_valid = inp
    k, sub = jax.random.split(k)
    out, tabs = multihop_sample_hetero(
        one_hops, trav, num_neighbors, num_hops, caps, budgets, seeds,
        n_valid, sub, tabs, with_edge=with_edge, fused_plan=fused_plan)
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
                         with_edge: bool = False,
                         fused_plan=None):
  """T sampling batches in ONE dispatch via lax.scan.

  seeds_stack: [T, B]; n_valid_stack: [T]. Returns (stacked out dicts
  [T, ...], table, scratch). Amortizes per-dispatch latency when host
  round-trips dominate (e.g. small batches over an interconnect-attached
  accelerator); the per-batch table reset keeps iterations independent,
  so results are identical to T separate multihop_sample calls.
  ``fused_plan`` routes each batch through the ``pallas_fused`` engine
  (fresh VMEM table per scan step — iterations stay independent).
  """
  def step(carry, inp):
    tab, scr, k = carry
    seeds, n_valid = inp
    k, sub = jax.random.split(k)
    out, tab, scr = multihop_sample(one_hop, seeds, n_valid, fanouts,
                                    sub, tab, scr, with_edge=with_edge,
                                    fused_plan=fused_plan)
    return (tab, scr, k), out

  (table, scratch, _), outs = jax.lax.scan(
      step, (table, scratch, key), (seeds_stack, n_valid_stack))
  return outs, table, scratch
