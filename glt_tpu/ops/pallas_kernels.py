"""Pallas TPU kernels for the hot paths.

The XLA-native formulations in ops/ are the correctness baseline and
the default on every backend; these kernels are opt-in
(``GLT_USE_PALLAS=1``, ``GLT_HOP_ENGINE=pallas|pallas_fused``).
Interpret-mode parity tests gate their correctness on the CPU;
``benchmarks/probe_pallas_compile.py`` says which of them the installed
Mosaic compiles on a chip.

``gather_rows``: the feature-store row gather (UnifiedTensor's
GatherTensorKernel analogue, unified_tensor.cu:35-81). Uses the canonical
TPU embedding-gather pattern: row indices are scalar-prefetched so the
BlockSpec index_map can steer one row-block DMA per grid step, and the
Pallas pipeline double-buffers those HBM->VMEM copies behind the writes.

``sample_hop``: the one-hop sampling megakernel (the ``pallas`` hop
engine, ops/pipeline.py::hop_engine). Fuses the per-row CSR window read
and the fanout pick — the two stages GLT's CUDA samplers keep in one
kernel (random_sampler.cu:36-165) — so the [S, W] neighbor window never
round-trips through HBM: each frontier row's window is DMA'd HBM->VMEM
double-buffered across grid steps, the precomputed Floyd/replace
offsets pick inside VMEM, and hub rows (degree > W) are fixed up by a
per-element DMA tail pass folded into the same kernel.

``sample_hop_dedup`` + ``dedup_table_insert``: the ``pallas_fused``
kernel family. Extends ``sample_hop`` with the per-hop dedup stage run
against a VMEM-resident open-addressing table (bucketized, 128 ids per
bucket row so probes are vector compares), so the picked indices never
leave VMEM between the sample and the assign: each grid step DMAs its
CSR windows, picks in VMEM, and immediately probes/inserts the picks
into the table, emitting provisional first-occurrence labels. The
host-side wrapper (ops/sample.py::sample_neighbors_fused) converts
those to the exact ``sorted_hop_dedup_fused`` label contract (new ids
labeled in within-hop VALUE order) with ONE narrow single-operand sort
over the fresh unique ids — strictly narrower than the 3-operand
[C+M]-wide sort the ``sort+fused`` engine pays per hop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..utils.env import knob

#: trace-time kernel-launch accounting: every pallas_call built by this
#: module bumps the counter ONCE PER TRACE (executions never touch it).
#: ``kernel_launch_count()`` deltas around an AOT lower therefore equal
#: the number of kernel entries in the lowered program — the
#: interpret-mode fallback for bench.py's ``kernel_launches_per_dispatch``
#: (on TPU the lowered HLO's custom-call count is the ground truth; in
#: interpret mode kernels inline into plain HLO and leave no custom
#: call to count).
_LAUNCHES = {'n': 0}


def kernel_launch_count() -> int:
  """Cumulative pallas_call constructions traced by this process.
  CAVEAT: the bump lives in the jitted wrappers' Python bodies, so an
  inner jit-cache hit (same kernel, same avals, traced earlier) does
  NOT re-count — take deltas against a cold cache (jax.clear_caches())
  or around the FIRST lower of a given shape signature (what bench.py
  and instrument_compiled do)."""
  return _LAUNCHES['n']


def _count_launch() -> None:
  _LAUNCHES['n'] += 1


def use_pallas_default() -> bool:
  return (knob('GLT_USE_PALLAS', False)
          and jax.default_backend() == 'tpu')


def interpret_default() -> bool:
  """Whether Pallas kernels must run in interpret mode on this backend:
  the kernels are Mosaic/TPU programs, so every non-TPU backend (the
  tier-1 CPU suite, the CI interpret job) executes them through the
  interpreter. On TPU, GLT_PALLAS_INTERPRET=1 forces interpretation for
  debugging."""
  if knob('GLT_PALLAS_INTERPRET', False):
    return True
  return jax.default_backend() != 'tpu'


def resolve_row_gather(override=None):
  """Gather-selection policy shared by every feature-serving path:
  an explicit override (tests inject the interpret-mode kernel) wins;
  otherwise the Pallas row-DMA gather when GLT_USE_PALLAS is on and the
  backend supports it; otherwise None (callers fall back to jnp.take)."""
  if override is not None:
    return override
  if use_pallas_default():
    return gather_rows
  return None


@functools.partial(jax.jit, static_argnames=('width', 'block',
                                             'interpret'))
def gather_windows(arr: jax.Array, starts: jax.Array, width: int,
                   block: int = 8, interpret: bool = False) -> jax.Array:
  """Contiguous-window gather: out[i] = arr[starts[i] : starts[i]+width].

  The windowed gathers of the sampling pipeline (weighted sampling and
  full-neighborhood expansion read a [S, max_degree] neighbor window per
  seed; the feature store reads [S, D] rows) lower on XLA:TPU to a
  serialized per-OUTPUT-element loop (~8-16 ns/element,
  benchmarks/microbench_prims.py) — ~0.8 us/row at width 96. Here each
  row is ONE async HBM->VMEM DMA descriptor instead; ``block`` rows'
  descriptors are in flight at once, so per-row cost is DMA-issue
  overhead + bytes/bandwidth, independent of width.

  CONTRACT (stricter than the XLA slice-gather): a window must lie
  fully inside the array — ``starts`` are clamped to
  [0, len(arr) - width], so a tail window with ``start > len - width``
  is SHIFTED left and returns wrong values in otherwise-valid lanes
  (XLA's per-element mode='clip' only corrupts lanes past the row's
  degree, which callers mask). Wire this into samplers only over a
  source array padded by ``width`` trailing elements; the microbench
  satisfies the precondition by drawing starts from [0, E - W].
  Callers mask invalid lanes themselves.
  """
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  e = arr.shape[0]
  s = starts.shape[0]
  assert e >= width, f'array ({e}) shorter than the window ({width})'
  starts = jnp.clip(starts.astype(jnp.int32), 0, e - width)
  pad = (-s) % block
  if pad:
    starts = jnp.pad(starts, (0, pad))
  n_blocks = (s + pad) // block

  def kernel(starts_ref, arr_ref, out_ref, sems):
    i = pl.program_id(0)

    def start_dma(j, _):
      st = starts_ref[i * block + j]
      pltpu.make_async_copy(arr_ref.at[pl.ds(st, width)],
                            out_ref.at[j], sems.at[j]).start()
      return 0

    def wait_dma(j, _):
      st = starts_ref[i * block + j]
      pltpu.make_async_copy(arr_ref.at[pl.ds(st, width)],
                            out_ref.at[j], sems.at[j]).wait()
      return 0

    jax.lax.fori_loop(0, block, start_dma, 0)   # block DMAs in flight
    jax.lax.fori_loop(0, block, wait_dma, 0)

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=1,
      grid=(n_blocks,),
      in_specs=[pl.BlockSpec(memory_space=pl.ANY)],   # stays in HBM
      out_specs=pl.BlockSpec((block, width), lambda i, idx: (i, 0)),
      scratch_shapes=[pltpu.SemaphoreType.DMA((block,))],
  )
  _count_launch()
  out = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((s + pad, width), arr.dtype),
      interpret=interpret,
  )(starts, arr)
  return out[:s]


@functools.partial(jax.jit, static_argnames=('interpret',))
def gather_rows(table: jax.Array, rows: jax.Array,
                interpret: bool = False) -> jax.Array:
  """table: [N, D]; rows: [B] int32 -> [B, D].

  Out-of-range rows are clamped (mode='clip' semantics of the XLA path).

  Lowering note (r5 hardware session): the original (1, D) block spec
  violated Mosaic's tiling rule (second-to-last block dim must be
  divisible by 8 or equal the array dim) and never compiled; the singleton middle
  dimension below satisfies it ("or equal": block (1, 1, D) vs array
  (N, 1, D)), and probe_pallas_compile.py rung 5 confirms this form
  compiles and runs on hardware. Measured there at 267 ns/row for
  (1, 128) blocks — grid-step overhead bound, SLOWER than XLA's row
  gather — so GLT_USE_PALLAS stays default-off; the kernel remains the
  scaffold for a multi-input steered variant if per-step overhead ever
  drops.
  """
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  n, d = table.shape
  b = rows.shape[0]
  rows = jnp.clip(rows.astype(jnp.int32), 0, n - 1)
  table3 = table.reshape(n, 1, d)

  def kernel(idx_ref, row_ref, out_ref):
    out_ref[:] = row_ref[:]

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=1,
      grid=(b,),
      in_specs=[
          pl.BlockSpec((1, 1, d), lambda i, idx: (idx[i], 0, 0)),
      ],
      out_specs=pl.BlockSpec((1, 1, d), lambda i, idx: (i, 0, 0)),
  )
  _count_launch()
  out = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((b, 1, d), table.dtype),
      interpret=interpret,
  )(rows, table3)
  return out.reshape(b, d)


def _sampled_window_picks(n_blocks, block, width, fanout, starts_ref,
                          hub_rows_ref, hub_slots_ref, offsets_ref,
                          flag_ref, src_refs, win_bufs, hub_bufs, sems,
                          hub_sems):
  """The sampling stages shared — by construction, not by copy — by
  ``sample_hop`` and ``sample_hop_dedup``: per-row window DMA
  double-buffered across grid steps (slot (i+1)%2 issued while slot
  i%2 computes), the in-VMEM one-hot offset pick, and the per-element
  hub tail pass folded into the owning block's grid step. Returns the
  merged picks ``[block, fanout]`` per source array; a divergence here
  would break BOTH engines' bit-identity contracts at once instead of
  silently forking them."""
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  n_a = len(src_refs)
  n_hub = hub_rows_ref.shape[0]
  i = pl.program_id(0)

  def window_dma(a, slot, row, j):
    st = starts_ref[row]
    return pltpu.make_async_copy(src_refs[a].at[pl.ds(st, width)],
                                 win_bufs[a].at[slot, j],
                                 sems[a].at[slot, j])

  def issue(slot, blk):
    for j in range(block):
      for a in range(n_a):
        window_dma(a, slot, blk * block + j, j).start()

  cur = jax.lax.rem(i, 2)
  nxt = jax.lax.rem(i + 1, 2)

  @pl.when(i == 0)
  def _():
    issue(cur, 0)                 # cold start: first block's windows

  @pl.when(i + 1 < n_blocks)
  def _():
    issue(nxt, i + 1)             # double-buffer: next block in flight

  for j in range(block):
    for a in range(n_a):
      window_dma(a, cur, i * block + j, j).wait()

  # hub tail pass: exact per-element reads for rows whose degree
  # exceeds the window, folded into the owning block's grid step
  def hub_issue(h, _):
    row = hub_rows_ref[h]
    in_block = (row >= i * block) & (row < (i + 1) * block)

    @pl.when(in_block)
    def _():
      j = row - i * block
      for k in range(fanout):
        sl = hub_slots_ref[h, k]
        for a in range(n_a):
          pltpu.make_async_copy(src_refs[a].at[pl.ds(sl, 1)],
                                hub_bufs[a].at[j, pl.ds(k, 1)],
                                hub_sems[a].at[j, k]).start()
      for k in range(fanout):
        sl = hub_slots_ref[h, k]
        for a in range(n_a):
          pltpu.make_async_copy(src_refs[a].at[pl.ds(sl, 1)],
                                hub_bufs[a].at[j, pl.ds(k, 1)],
                                hub_sems[a].at[j, k]).wait()
    return 0

  jax.lax.fori_loop(0, n_hub, hub_issue, 0)

  woff = jnp.minimum(offsets_ref[...], width - 1)      # [block, K]
  iota = jax.lax.broadcasted_iota(jnp.int32, (block, fanout, width), 2)
  onehot = iota == woff[:, :, None]
  is_hub = flag_ref[...] != 0                          # [block, 1]
  merged = []
  for a in range(n_a):
    win = win_bufs[a][cur]                             # [block, W]
    zero = jnp.zeros((), win.dtype)
    picks = jnp.sum(jnp.where(onehot, win[:, None, :], zero), axis=-1)
    merged.append(jnp.where(is_hub, hub_bufs[a][...], picks))
  return merged


@functools.partial(jax.jit, static_argnames=('width', 'block',
                                             'interpret'))
def sample_hop(arr_win: jax.Array,
               eids_win: 'Optional[jax.Array]',
               starts: jax.Array,
               offsets: jax.Array,
               hub_rows: jax.Array,
               hub_slots: jax.Array,
               width: int,
               block: int = 8,
               interpret: bool = False):
  """One-hop sampling megakernel: window DMA + offset pick + hub tail.

  For each frontier row ``i``, DMAs the ``width``-wide CSR window
  ``arr_win[starts[i] : starts[i]+width]`` HBM->VMEM (double-buffered
  across grid steps, ``block`` rows' descriptors in flight per slot),
  applies the precomputed sampling ``offsets`` inside VMEM, and emits
  the packed ``[S, K]`` neighbor picks — the ``[S, width]`` window never
  materializes in HBM. Rows listed in ``hub_rows`` (degree > width, so
  their offsets can exceed the window) are fixed up by a per-element DMA
  tail pass in the SAME kernel: ``hub_slots`` holds their exact edge
  slots, and the combine overwrites only those rows.

  Args:
    arr_win: [E + width] edge array padded per the ``gather_windows``
      contract — every real row window lies fully inside it, so
      ``starts`` need no clamping.
    eids_win: optional second edge array (edge ids) read through the
      same windows/offsets; pass None to skip the second output.
    starts: [S] int32 per-row window starts (CSR row offsets).
    offsets: [S, K] int32 within-row sampling offsets, as drawn by the
      element path (unclamped; the kernel clips to the window for the
      main pass — hub rows get exact values from the tail pass).
    hub_rows: [H] int32 frontier row indices needing exact fix-up; -1
      marks unused capacity. H is a static cap. Every grid step scans
      the whole list for rows in its block (O(grid * H) scalar
      compares), so H must stay small relative to S — pick W so hubs
      are rare (callers clamp H to the frontier size, and the degree
      distribution bounds it); a sorted-hub-list + per-block-offset
      variant is the follow-up if a hardware A/B ever shows the scan.
    hub_slots: [H, K] int32 exact edge slots for the hub rows (already
      clipped to the real edge range by the caller).

  Returns ``picks`` [S, K] (and ``eid_picks`` [S, K] when ``eids_win``
  is given, else None) with the same dtype(s) as the source arrays.
  Rows beyond the hub cap fall back to window-clipped picks — identical
  confinement to the XLA window path (ops/sample.py docstring).
  """
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  s = starts.shape[0]
  fanout = offsets.shape[1]
  n_hub = hub_rows.shape[0]
  with_eids = eids_win is not None
  if s == 0:
    empty = jnp.zeros((0, fanout), arr_win.dtype)
    return empty, (jnp.zeros((0, fanout), eids_win.dtype)
                   if with_eids else None)
  starts = starts.astype(jnp.int32)
  offsets = offsets.astype(jnp.int32)
  pad = (-s) % block
  if pad:
    starts = jnp.pad(starts, (0, pad))
    offsets = jnp.pad(offsets, ((0, pad), (0, 0)))
  n_blocks = (s + pad) // block
  # per-row fix-up flag, derived from the SAME hub list the tail pass
  # walks — a row is only flagged if a tail DMA will actually fill it
  # (hub rows past the H cap keep their window picks, the documented
  # confinement of an undersized cap)
  valid_hub = (hub_rows >= 0).astype(jnp.int32)
  hub_flag = jnp.zeros((s + pad, 1), jnp.int32).at[
      jnp.clip(hub_rows, 0, s + pad - 1), 0].max(valid_hub)
  hub_rows = jnp.where(valid_hub > 0, hub_rows, -1).astype(jnp.int32)
  hub_slots = hub_slots.astype(jnp.int32)

  arrs = (arr_win, eids_win) if with_eids else (arr_win,)

  def kernel(starts_ref, hub_rows_ref, hub_slots_ref, offsets_ref,
             flag_ref, *rest):
    src_refs = rest[:len(arrs)]
    out_refs = rest[len(arrs):2 * len(arrs)]
    win_bufs = rest[2 * len(arrs):3 * len(arrs)]
    hub_bufs = rest[3 * len(arrs):4 * len(arrs)]
    sems = rest[4 * len(arrs):5 * len(arrs)]
    hub_sems = rest[5 * len(arrs):6 * len(arrs)]
    merged = _sampled_window_picks(
        n_blocks, block, width, fanout, starts_ref, hub_rows_ref,
        hub_slots_ref, offsets_ref, flag_ref, src_refs, win_bufs,
        hub_bufs, sems, hub_sems)
    for a in range(len(arrs)):
      out_refs[a][...] = merged[a]

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=3,
      grid=(n_blocks,),
      in_specs=(
          [pl.BlockSpec((block, fanout), lambda i, *_: (i, 0)),
           pl.BlockSpec((block, 1), lambda i, *_: (i, 0))]
          + [pl.BlockSpec(memory_space=pl.ANY)] * len(arrs)),
      out_specs=[pl.BlockSpec((block, fanout), lambda i, *_: (i, 0))
                 for _ in arrs],
      scratch_shapes=(
          [pltpu.VMEM((2, block, width), a.dtype) for a in arrs]
          + [pltpu.VMEM((block, fanout), a.dtype) for a in arrs]
          + [pltpu.SemaphoreType.DMA((2, block)) for _ in arrs]
          + [pltpu.SemaphoreType.DMA((block, fanout)) for _ in arrs]),
  )
  _count_launch()
  outs = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=[jax.ShapeDtypeStruct((s + pad, fanout), a.dtype)
                 for a in arrs],
      interpret=interpret,
  )(starts, hub_rows, hub_slots, offsets, hub_flag, *arrs)
  picks = outs[0][:s]
  return picks, (outs[1][:s] if with_eids else None)


# ---------------------------------------------------------------------------
# pallas_fused: sample -> dedup fused in one kernel (ISSUE 10 tentpole).
#
# The dedup table is a bucketized open-addressing hash table living in
# VMEM for the whole kernel: [n_buckets, 128] int32 ids + labels, so a
# probe is ONE vector load + compare over a bucket's 128 lanes instead
# of 128 scalar reads. Grid steps run sequentially on TPU, which makes
# the insert order deterministic (slot order) — the same first-
# occurrence semantics the sort engines recover with stable sorts.
# ---------------------------------------------------------------------------

#: lanes per hash bucket — one VMEM vector row per probe
TABLE_LANES = 128


def fused_table_max_slots() -> int:
  """VMEM dedup-table sizing knob: the largest table (in id slots) the
  ``pallas_fused`` engine may allocate. Both planes (ids + labels) of a
  full-size table cost ``2 * slots * 4`` bytes of VMEM for the whole
  kernel — the default (2^20 slots = 8 MB) leaves room for the window
  double-buffers inside a 16 MB VMEM budget. A multihop whose node
  budget needs more slots falls back to the ``pallas`` engine (counted
  in ``hop_engine_fallbacks_total``)."""
  return knob('GLT_FUSED_TABLE_SLOTS', 1 << 20)


def fused_table_slots(budget: int) -> int:
  """Slots for a walk with ``budget`` worst-case distinct nodes: the
  next power-of-two bucket count whose slot count covers the budget
  (capacity > occupancy guarantees probe termination; typical fill is
  the ACTUAL distinct count, far below the static budget, so the load
  factor in practice stays low)."""
  n_buckets = 8  # (8, 128) min int32 tile
  while n_buckets * TABLE_LANES <= budget:
    n_buckets *= 2
  return n_buckets * TABLE_LANES


def make_dedup_table(slots: int):
  """Fresh (ids, labels) table planes; -1 marks an empty lane."""
  assert slots % TABLE_LANES == 0
  shape = (slots // TABLE_LANES, TABLE_LANES)
  return (jnp.full(shape, -1, jnp.int32), jnp.full(shape, -1, jnp.int32))


def _hash_bucket(x, n_buckets):
  """Multiplicative (Fibonacci) hash of an int32 id -> bucket index."""
  h = x * jnp.int32(-1640531527)
  h = jnp.bitwise_xor(h, jax.lax.shift_right_logical(h, 16))
  return jnp.bitwise_and(h, n_buckets - 1)


def _probe(tab_ids_ref, x, n_buckets):
  """Walk buckets from hash(x) until one holds ``x`` or has an empty
  lane. Terminates because callers size the table past the worst-case
  occupancy (fused_table_slots) and lanes are never deleted; the cond
  is pure (loads live in the body) so the loop discharges in interpret
  mode."""
  from jax.experimental import pallas as pl

  def cond(c):
    return jnp.logical_not(c[1])

  def step(c):
    b, _ = c
    row = tab_ids_ref[pl.ds(b, 1), :]
    stop = jnp.any(row == x) | jnp.any(row == -1)
    return (jnp.where(stop, b, jnp.bitwise_and(b + 1, n_buckets - 1)),
            stop)

  b, _ = jax.lax.while_loop(cond, step, (_hash_bucket(x, n_buckets),
                                         False))
  return b


def _probe_insert(tab_ids_ref, tab_labs_ref, x, valid, new_label,
                  n_buckets, lane_iota):
  """One dedup element: find ``x``'s bucket, return (label, inserted).
  Invalid elements probe with -1 (stops at the first empty lane, never
  matches a real id as "found new") and are neutralized by masked
  writes, so the whole element is straight-line code — no pl.when."""
  from jax.experimental import pallas as pl
  xs = jnp.where(valid, x, jnp.int32(-1))
  b = _probe(tab_ids_ref, xs, n_buckets)
  row = tab_ids_ref[pl.ds(b, 1), :]
  eq = row == xs
  # xs == -1 "finds" the empty lanes; valid gating below discards it
  found = jnp.any(eq)
  do_insert = jnp.logical_and(valid, jnp.logical_not(found))
  labrow = tab_labs_ref[pl.ds(b, 1), :]
  found_lab = jnp.max(jnp.where(eq, labrow, -1))
  empty = row == -1
  first_empty = jnp.min(jnp.where(empty, lane_iota, TABLE_LANES))
  put = jnp.logical_and(do_insert, lane_iota == first_empty)
  tab_ids_ref[pl.ds(b, 1), :] = jnp.where(put, xs, row)
  tab_labs_ref[pl.ds(b, 1), :] = jnp.where(put, new_label, labrow)
  lab = jnp.where(valid,
                  jnp.where(found, found_lab, new_label),
                  jnp.int32(-1))
  return lab, do_insert.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=('interpret',))
def dedup_table_insert(tab_ids: jax.Array, tab_labs: jax.Array,
                       ids: jax.Array, labs: jax.Array,
                       valid: jax.Array,
                       interpret: bool = False):
  """Insert pre-labeled ids into the dedup table (the seed hop: labels
  come from the EXACT seed dedup, the table just has to agree with them
  before the first fused hop probes it). Already-present ids keep their
  stored label; invalid slots are no-ops. Returns the updated planes.
  """
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  n_buckets = tab_ids.shape[0]
  m = ids.shape[0]
  if m == 0:
    return tab_ids, tab_labs
  ids = ids.astype(jnp.int32)
  labs = labs.astype(jnp.int32)
  valid = valid.astype(jnp.int32)

  def kernel(ids_ref, labs_ref, valid_ref, ids_in, labs_in,
             ids_out, labs_out, tids, tlabs, sems):
    # table planes live in HBM (ANY) in/out; ONE VMEM copy is staged
    # by explicit DMA — blocked in+out specs would keep TWO resident
    # copies per plane and double the VMEM footprint
    pltpu.make_async_copy(ids_in, tids, sems.at[0]).start()
    pltpu.make_async_copy(labs_in, tlabs, sems.at[1]).start()
    pltpu.make_async_copy(ids_in, tids, sems.at[0]).wait()
    pltpu.make_async_copy(labs_in, tlabs, sems.at[1]).wait()
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, TABLE_LANES), 1)

    def body(t, _):
      _probe_insert(tids, tlabs, ids_ref[t], valid_ref[t] != 0,
                    labs_ref[t], n_buckets, lane)
      return 0

    jax.lax.fori_loop(0, m, body, 0)
    pltpu.make_async_copy(tids, ids_out, sems.at[0]).start()
    pltpu.make_async_copy(tlabs, labs_out, sems.at[1]).start()
    pltpu.make_async_copy(tids, ids_out, sems.at[0]).wait()
    pltpu.make_async_copy(tlabs, labs_out, sems.at[1]).wait()

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=3,
      grid=(1,),
      in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
      out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)],
      scratch_shapes=[pltpu.VMEM(tab_ids.shape, jnp.int32),
                      pltpu.VMEM(tab_ids.shape, jnp.int32),
                      pltpu.SemaphoreType.DMA((2,))],
  )
  _count_launch()
  return pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=[jax.ShapeDtypeStruct(tab_ids.shape, jnp.int32),
                 jax.ShapeDtypeStruct(tab_ids.shape, jnp.int32)],
      interpret=interpret,
  )(ids, labs, valid, tab_ids, tab_labs)


@functools.partial(jax.jit, static_argnames=('width', 'block',
                                             'interpret'))
def sample_hop_dedup(arr_win: jax.Array,
                     eids_win: 'Optional[jax.Array]',
                     starts: jax.Array,
                     offsets: jax.Array,
                     valid: jax.Array,
                     hub_rows: jax.Array,
                     hub_slots: jax.Array,
                     tab_ids: jax.Array,
                     tab_labs: jax.Array,
                     count: jax.Array,
                     width: int,
                     block: int = 8,
                     interpret: bool = False):
  """The fused hop megakernel: window DMA + offset pick + hub tail +
  dedup-table assign, all in one kernel.

  The sampling stages are ``sample_hop``'s, unchanged (same
  double-buffered window DMA slots, same one-hot pick, same per-element
  hub fix-up). The new stage runs right after the pick, on the merged
  picks still in VMEM: each element probes the resident dedup table
  (``_probe_insert``) in slot order — grid steps are sequential, so
  insertion order is deterministic — and emits a PROVISIONAL label:
  previously seen ids return their stored label, fresh ids get
  ``count + r`` in first-occurrence order (r = running insert counter,
  carried across grid steps in SMEM). The ``sorted_hop_dedup_fused``
  value-order label contract is restored by the caller with one narrow
  sort over the fresh ids (ops/sample.py::sample_neighbors_fused),
  which also rewrites the table's labels for the next hop.

  Args (beyond sample_hop's):
    valid: [S, K] int32/bool element validity (the sample mask) — the
      dedup stage skips invalid lanes.
    tab_ids / tab_labs: [n_buckets, 128] table planes (make_dedup_table
      or a previous hop's outputs); n_buckets must be a power of two.
    count: scalar int32, labels assigned before this hop.

  Returns (picks, eid_picks|None, prov_labels [S, K], new_head [S, K]
  int32, tab_ids', tab_labs').
  """
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  s = starts.shape[0]
  fanout = offsets.shape[1]
  n_hub = hub_rows.shape[0]
  n_buckets = tab_ids.shape[0]
  assert n_buckets & (n_buckets - 1) == 0, 'bucket count must be pow2'
  with_eids = eids_win is not None
  if s == 0:
    empty = jnp.zeros((0, fanout), arr_win.dtype)
    return (empty,
            jnp.zeros((0, fanout), eids_win.dtype) if with_eids else None,
            jnp.zeros((0, fanout), jnp.int32),
            jnp.zeros((0, fanout), jnp.int32), tab_ids, tab_labs)
  starts = starts.astype(jnp.int32)
  offsets = offsets.astype(jnp.int32)
  valid = valid.astype(jnp.int32)
  pad = (-s) % block
  if pad:
    starts = jnp.pad(starts, (0, pad))
    offsets = jnp.pad(offsets, ((0, pad), (0, 0)))
    valid = jnp.pad(valid, ((0, pad), (0, 0)))  # padded rows never insert
  n_blocks = (s + pad) // block
  valid_hub = (hub_rows >= 0).astype(jnp.int32)
  hub_flag = jnp.zeros((s + pad, 1), jnp.int32).at[
      jnp.clip(hub_rows, 0, s + pad - 1), 0].max(valid_hub)
  hub_rows = jnp.where(valid_hub > 0, hub_rows, -1).astype(jnp.int32)
  hub_slots = hub_slots.astype(jnp.int32)
  count = count.astype(jnp.int32).reshape((1,))

  arrs = (arr_win, eids_win) if with_eids else (arr_win,)
  n_a = len(arrs)

  def kernel(starts_ref, hub_rows_ref, hub_slots_ref, count_ref,
             offsets_ref, flag_ref, valid_ref, tids_in, tlabs_in,
             *rest):
    src_refs = rest[:n_a]
    out_refs = rest[n_a:2 * n_a]
    lab_ref, newh_ref, tids_out, tlabs_out = rest[2 * n_a:2 * n_a + 4]
    scr = rest[2 * n_a + 4:]
    win_bufs = scr[:n_a]
    hub_bufs = scr[n_a:2 * n_a]
    sems = scr[2 * n_a:3 * n_a]
    hub_sems = scr[3 * n_a:4 * n_a]
    r_ref, tids, tlabs, tsems = scr[4 * n_a:4 * n_a + 4]
    i = pl.program_id(0)

    # table planes ride HBM (ANY) in/out; the working copy is ONE VMEM
    # scratch per plane, DMA'd in at the first step and written back at
    # the last — blocked in+out table specs would pin two resident
    # copies per plane (2x the table's VMEM share for nothing)
    @pl.when(i == 0)
    def _():
      pltpu.make_async_copy(tids_in, tids, tsems.at[0]).start()
      pltpu.make_async_copy(tlabs_in, tlabs, tsems.at[1]).start()
      pltpu.make_async_copy(tids_in, tids, tsems.at[0]).wait()
      pltpu.make_async_copy(tlabs_in, tlabs, tsems.at[1]).wait()
      r_ref[0] = 0

    # sampling stages: the SAME helper sample_hop runs — the fused
    # kernel only appends the dedup stage below
    merged = _sampled_window_picks(
        n_blocks, block, width, fanout, starts_ref, hub_rows_ref,
        hub_slots_ref, offsets_ref, flag_ref, src_refs, win_bufs,
        hub_bufs, sems, hub_sems)
    for a in range(n_a):
      out_refs[a][...] = merged[a]
    picks0 = merged[0]

    # dedup stage: probe/insert the merged picks, slot order (row-major
    # over [block, fanout], sequential grid => global slot order)
    base = count_ref[0]
    r = r_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, TABLE_LANES), 1)
    lab_rows, newh_rows = [], []
    for j in range(block):
      labs_k, newh_k = [], []
      for k in range(fanout):
        x = picks0[j, k].astype(jnp.int32)
        v = valid_ref[j, k] != 0
        lab, is_new = _probe_insert(tids, tlabs, x, v,
                                    base + r, n_buckets, lane)
        labs_k.append(lab)
        newh_k.append(is_new)
        r = r + is_new
      lab_rows.append(jnp.stack(labs_k))
      newh_rows.append(jnp.stack(newh_k))
    lab_ref[...] = jnp.stack(lab_rows)
    newh_ref[...] = jnp.stack(newh_rows)
    r_ref[0] = r

    @pl.when(i == n_blocks - 1)
    def _():
      pltpu.make_async_copy(tids, tids_out, tsems.at[0]).start()
      pltpu.make_async_copy(tlabs, tlabs_out, tsems.at[1]).start()
      pltpu.make_async_copy(tids, tids_out, tsems.at[0]).wait()
      pltpu.make_async_copy(tlabs, tlabs_out, tsems.at[1]).wait()

  tshape = tab_ids.shape
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=4,
      grid=(n_blocks,),
      in_specs=(
          [pl.BlockSpec((block, fanout), lambda i, *_: (i, 0)),
           pl.BlockSpec((block, 1), lambda i, *_: (i, 0)),
           pl.BlockSpec((block, fanout), lambda i, *_: (i, 0)),
           pl.BlockSpec(memory_space=pl.ANY),
           pl.BlockSpec(memory_space=pl.ANY)]
          + [pl.BlockSpec(memory_space=pl.ANY)] * n_a),
      out_specs=([pl.BlockSpec((block, fanout), lambda i, *_: (i, 0))
                  for _ in arrs]
                 + [pl.BlockSpec((block, fanout), lambda i, *_: (i, 0)),
                    pl.BlockSpec((block, fanout), lambda i, *_: (i, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY)]),
      scratch_shapes=(
          [pltpu.VMEM((2, block, width), a.dtype) for a in arrs]
          + [pltpu.VMEM((block, fanout), a.dtype) for a in arrs]
          + [pltpu.SemaphoreType.DMA((2, block)) for _ in arrs]
          + [pltpu.SemaphoreType.DMA((block, fanout)) for _ in arrs]
          + [pltpu.SMEM((1,), jnp.int32),
             pltpu.VMEM(tshape, jnp.int32),
             pltpu.VMEM(tshape, jnp.int32),
             pltpu.SemaphoreType.DMA((2,))]),
  )
  _count_launch()
  outs = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=([jax.ShapeDtypeStruct((s + pad, fanout), a.dtype)
                  for a in arrs]
                 + [jax.ShapeDtypeStruct((s + pad, fanout), jnp.int32),
                    jax.ShapeDtypeStruct((s + pad, fanout), jnp.int32),
                    jax.ShapeDtypeStruct(tshape, jnp.int32),
                    jax.ShapeDtypeStruct(tshape, jnp.int32)]),
      interpret=interpret,
  )(starts, hub_rows, hub_slots, count, offsets, hub_flag, valid,
    tab_ids, tab_labs, *arrs)
  picks = outs[0][:s]
  eid_picks = outs[1][:s] if with_eids else None
  prov_labels = outs[n_a][:s]
  new_head = outs[n_a + 1][:s]
  return (picks, eid_picks, prov_labels, new_head,
          outs[n_a + 2], outs[n_a + 3])


# ---------------------------------------------------------------------------
# Hetero edge-type plane (ISSUE 14): the geometry that lets ONE
# sample_hop_dedup invocation serve EVERY edge type of a hetero hop.
#
# The kernel itself is type-agnostic — it reads windows at `starts`,
# picks at `offsets`, and dedups whatever int32 ids the windows hold.
# The edge-type plane exploits that: each edge type's W-padded indices
# block is concatenated into ONE flat array with its neighbor values
# rebased into a GLOBAL node-id space (local id + type_base[ntype]), so
#   * per-type window geometry is a per-row affine shift baked into
#     `starts` (indptr_e[row] + edge_base[e]) — the same double-
#     buffered HBM->VMEM window DMA serves every type;
#   * per-type fanouts ride the [S, K_max] offset/validity planes
#     (lanes past an edge type's fanout are invalid, never probed);
#   * per-type dedup namespaces come FREE from the type-tagged keys:
#     global ids never collide across types, so one VMEM table holds
#     every type's seen-set and a probe is type-correct by construction.
# The XLA epilogue (ops/pipeline.py::_multihop_sample_hetero_fused)
# converts the kernel's global provisional labels back to the per-type
# value-order label contract of the per-edge-type sorted reference.
# ---------------------------------------------------------------------------


def build_type_plane(etypes, trav, node_counts, parts, width):
  """Build the flat multi-edge-type window geometry (eager, once per
  compiled hetero program — plans are constructed outside jit).

  Args:
    etypes: traversal-order edge-type list (the reference hop loop's
      iteration order; first-occurrence semantics depend on it).
    trav: Dict[EdgeType, (expand_from_type, neighbor_type)].
    node_counts: Dict[NodeType, int] — the per-type id spaces being
      tagged into one global space.
    parts: Dict[EdgeType, dict] with per-etype ``indices_win`` (the
      W-padded indices, Graph.window_arrays contract), ``num_edges``,
      and optional ``edge_ids_win``.
    width: window width W (every block carries its own W-slot pad, so
      any row's window read stays inside its block).

  Returns dict(type_base, edge_base, indices_flat, eids_flat,
  has_eids, total_nodes). Raises ValueError when the type-tagged key
  space or the flat edge plane exceeds int32 — the genuinely
  unservable hetero shapes (callers demote with reason ``hetero``).
  """
  types = list(node_counts)
  type_base, base = {}, 0
  for t in types:
    type_base[t] = base
    base += int(node_counts[t])
  if base >= 2 ** 31:
    raise ValueError(
        f'{base} nodes across types exceed the int32 type-tagged key '
        'space of the fused dedup table')
  has_eids = {e: parts[e].get('edge_ids_win') is not None
              for e in etypes}
  any_eids = any(has_eids.values())
  edge_base, off = {}, 0
  blocks, eid_blocks = [], []
  for e in etypes:
    p = parts[e]
    iw = jnp.asarray(p['indices_win'])
    assert int(iw.shape[0]) == int(p['num_edges']) + int(width), (
        'indices_win must carry exactly width trailing pad slots '
        '(Graph.window_arrays contract)', e)
    b = type_base[trav[e][1]]
    # sentinel pad lanes stay -1 in the global space; valid lanes never
    # read them (offsets < deg <= W stay inside the row's real window,
    # hub rows are fixed by exact in-range slots)
    blocks.append(jnp.where(iw >= 0, iw.astype(jnp.int32) + b,
                            jnp.int32(-1)))
    edge_base[e] = off
    off += int(iw.shape[0])
    if not any_eids:  # no zero-plane churn when no type carries eids
      continue
    ew = p.get('edge_ids_win')
    if ew is None:
      eid_blocks.append(jnp.zeros((int(iw.shape[0]),), jnp.int32))
      continue
    ew = jnp.asarray(ew)
    if jnp.dtype(ew.dtype).itemsize > 4 and int(ew.shape[0]) \
        and int(ew.max()) >= 2 ** 31:
      # the flat eid plane is int32 (one common dtype across types);
      # silently truncating 64-bit edge-id VALUES would diverge from
      # the per-etype reference — fail the plan loudly instead (the
      # sampler demotes with the counted `hetero` reason)
      raise ValueError(
          f'edge ids of {e} exceed the int32 range of the flat hetero '
          'eid plane; remap edge ids below 2^31 per type or sample '
          'this graph without the fused hetero engine')
    eid_blocks.append(ew.astype(jnp.int32))
  if off >= 2 ** 31:
    raise ValueError(
        f'{off} flat edge slots exceed the int32 window-start space')
  return dict(
      type_base=type_base,
      edge_base=edge_base,
      indices_flat=jnp.concatenate(blocks) if blocks
      else jnp.zeros((0,), jnp.int32),
      eids_flat=jnp.concatenate(eid_blocks) if any_eids else None,
      has_eids=has_eids,
      total_nodes=base,
  )


# ---------------------------------------------------------------------------
# Cross-hop fused walk (ISSUE 13 tentpole): the WHOLE multi-hop walk as
# one kernel invocation.
#
# The per-hop family above still pays, at every hop boundary: a kernel
# teardown/launch, a full HBM write-back + reload of both [n_buckets,
# 128] table planes, and a fresh read of the padded edge array operand.
# Here the grid covers every hop's frontier blocks back to back (hop
# boundaries are grid phases, statically unrolled), and the dedup table
# lives in VMEM *scratch* for the whole walk — it never exists in HBM
# at all: step 0 memsets it and inserts the exact-dedup'd seed hop, and
# each phase probes/inserts its picks against the same resident planes.
#
# What had to move in-kernel for the walk to stay on-chip: hop h+1's
# frontier is hop h's picks, so the kernel (a) writes each hop's masked
# picks to a small HBM staging buffer (the only cross-hop HBM traffic
# left — [S_h, K_h] int32 per hop vs two table planes + the edge-array
# operand per hop before), (b) DMAs the next block's frontier ids +
# their indptr pairs while the current block computes, and (c) derives
# the Floyd/replace offsets from precomputed per-hop uniform draws (the
# draws are data-independent, so XLA generates them up front from the
# same jax.random stream — bit-identical offsets by construction). Hub
# rows are fixed up per-row (degree > W => exact per-element reads), so
# the walk needs no hub list and no hub cap at all.
#
# One DMA pipeline serves every hop: the double-buffered window slots
# prefetch block i+1's CSR windows (frontier -> indptr -> window chain
# resolved ahead of the probe section) across hop-interior steps; the
# pipeline only hiccups for one block at each hop boundary, where the
# next frontier literally does not exist until the current step's picks
# are written.
# ---------------------------------------------------------------------------


def walk_geometry(batch_size: int, fanouts, block: int = 8):
  """Static hop-phase geometry of the cross-hop walk: per hop a dict of
  frontier rows (``s``), block-padded rows (``s_pad``), first grid step
  (``step0``), step count (``nb``) and fanout (``k``). Returns
  ``(hops, total_steps)``."""
  hops = []
  s = max(int(batch_size), 1)
  step = 0
  for k in fanouts:
    k = int(k)
    assert k > 0, 'the cross-hop walk serves uniform positive fanouts'
    nb = -(-s // block)
    hops.append(dict(s=s, s_pad=nb * block, step0=step, nb=nb, k=k))
    step += nb
    s = s * k
  return hops, step


@functools.partial(jax.jit, static_argnames=(
    'fanouts', 'width', 'num_nodes', 'num_edges', 'table_slots',
    'batch_size', 'replace', 'block', 'interpret'))
def sample_walk_dedup(arr_win: jax.Array,
                      eids_win: 'Optional[jax.Array]',
                      indptr_pad: jax.Array,
                      seed_ids: jax.Array,
                      seed_ok: jax.Array,
                      seed_tab_ids: jax.Array,
                      seed_tab_labs: jax.Array,
                      base_count: jax.Array,
                      u_hops,
                      *,
                      fanouts,
                      width: int,
                      num_nodes: int,
                      num_edges: int,
                      table_slots: int,
                      batch_size: int,
                      replace: bool = False,
                      block: int = 8,
                      interpret: bool = False):
  """The cross-hop walk megakernel: every uniform hop's window DMA +
  offset pick + hub fix-up + dedup-table assign in ONE kernel, the
  table resident in VMEM scratch for the whole walk.

  Args:
    arr_win / eids_win: W-padded edge array(s), as in ``sample_hop``.
    indptr_pad: [N + 2] int32 — the CSR indptr with ONE trailing
      ``num_edges`` sentinel, so the kernel's 2-wide row reads at a
      clamped address reproduce the element path's per-element
      ``take(..., mode='clip')`` start/degree semantics exactly
      (an invalid frontier id — INT32_MAX — clamps to row N and reads
      ``[E, E]``: degree 0, window over the sentinel padding, the same
      values the XLA engines read for masked rows).
    seed_ids: [S1_pad] int32 — hop 1's frontier in the sorted-seed
      order (``sorted_hop_dedup``'s ``ids3``), RAW ids: duplicate seeds
      keep their real id (they read real windows, exactly like the
      ``sort+fused`` reference) and validity rides ``seed_ok``.
    seed_ok: [S1_pad] int32 — hop 1 frontier validity (``new_head3``).
    seed_tab_ids / seed_tab_labs: [B_pad] int32 — the exact-dedup'd
      seed uniques (+ labels) inserted into the fresh table at step 0;
      -1 ids are skipped. Scalar-prefetched (the insert loop indexes
      them dynamically).
    base_count: [1] int32 — labels assigned before hop 1 (seed count);
      fresh ids get provisional labels ``base + r`` in global
      first-occurrence order, ``r`` carried in SMEM across all hops.
    u_hops: tuple of per-hop uniform draws, hop h shaped
      [S_h_pad, K_h] float32 with ``u[row, j] = uniform_h[j, row]``
      (the element path's ``_floyd_offsets`` orientation transposed;
      for ``replace`` the natural [S, K] draw). Data-independent, so
      the caller draws them up front from the unchanged key sequence.
    fanouts: static positive per-hop fanouts.
    table_slots: dedup-table capacity (``fused_table_slots``); the two
      VMEM-resident planes cost ``2 * table_slots * 4`` bytes of
      scratch for the whole kernel.

  Returns ``(picks, eid_picks|None, prov, new_head)`` — tuples with one
  [S_h_pad, K_h] entry per hop; ``prov`` labels are provisional (global
  first-occurrence order), converted to the ``sorted_hop_dedup_fused``
  value-order contract by the caller
  (ops/pipeline.py::_multihop_sample_walk) with one narrow sort per
  hop. The masked-lane values of ``picks``/``eid_picks`` match the
  window-read reference bit-for-bit (same physical slots).
  """
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  big = jnp.iinfo(jnp.int32).max
  n_hops = len(fanouts)
  hops, total_steps = walk_geometry(batch_size, fanouts, block)
  with_eids = eids_win is not None
  arrs = (arr_win, eids_win) if with_eids else (arr_win,)
  n_a = len(arrs)
  assert table_slots % TABLE_LANES == 0
  n_buckets = table_slots // TABLE_LANES
  assert n_buckets & (n_buckets - 1) == 0, 'bucket count must be pow2'
  tshape = (n_buckets, TABLE_LANES)
  assert seed_ids.shape[0] == hops[0]['s_pad']
  assert len(u_hops) == n_hops
  for h, u in zip(hops, u_hops):
    assert u.shape == (h['s_pad'], h['k']), (u.shape, h)
  b_pad = seed_tab_ids.shape[0]
  k_max = max(f for f in fanouts)

  seed_tab_ids = seed_tab_ids.astype(jnp.int32)
  seed_tab_labs = seed_tab_labs.astype(jnp.int32)
  base_count = base_count.astype(jnp.int32).reshape((1,))
  seed_ids = seed_ids.astype(jnp.int32)
  seed_ok = seed_ok.astype(jnp.int32)
  indptr_pad = indptr_pad.astype(jnp.int32)

  def kernel(stab_ids_ref, stab_labs_ref, base_ref, *rest):
    u_refs = rest[:n_hops]
    ip_ref, sid_ref, sok_ref = rest[n_hops:n_hops + 3]
    src_refs = rest[n_hops + 3:n_hops + 3 + n_a]
    pos = n_hops + 3 + n_a
    picks_refs = rest[pos:pos + n_hops]; pos += n_hops
    if with_eids:
      eidp_refs = rest[pos:pos + n_hops]; pos += n_hops
    prov_refs = rest[pos:pos + n_hops]; pos += n_hops
    newh_refs = rest[pos:pos + n_hops]; pos += n_hops
    fp_refs = rest[pos:pos + max(n_hops - 1, 1)]
    pos += max(n_hops - 1, 1)
    scr = rest[pos:]
    vf, vok, vip = scr[0], scr[1], scr[2]
    win_bufs = scr[3:3 + n_a]
    hub_bufs = scr[3 + n_a:3 + 2 * n_a]
    fscrs = scr[3 + 2 * n_a:3 + 2 * n_a + max(n_hops - 1, 1)]
    spos = 3 + 2 * n_a + max(n_hops - 1, 1)
    tids, tlabs, r_ref = scr[spos], scr[spos + 1], scr[spos + 2]
    fsem, oksem, ipsem = scr[spos + 3], scr[spos + 4], scr[spos + 5]
    wsems = scr[spos + 6:spos + 6 + n_a]
    hubsems = scr[spos + 6 + n_a:spos + 6 + 2 * n_a]
    fpsem = scr[spos + 6 + 2 * n_a]

    i = pl.program_id(0)
    cur = jax.lax.rem(i, 2)
    nxt = jax.lax.rem(i + 1, 2)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, TABLE_LANES), 1)

    # step 0: fresh table planes (memset, never read from HBM) + the
    # exact-dedup'd seed insert — the walk's phase 0, folded into the
    # first sampling step so no separate launch exists even for seeding
    @pl.when(i == 0)
    def _():
      tids[...] = jnp.full(tshape, -1, jnp.int32)
      tlabs[...] = jnp.full(tshape, -1, jnp.int32)

      def body(t, _):
        x = stab_ids_ref[t]
        _probe_insert(tids, tlabs, x, x >= 0, stab_labs_ref[t],
                      n_buckets, lane)
        return 0

      jax.lax.fori_loop(0, b_pad, body, 0)
      r_ref[0] = 0

    # -- DMA chain helpers (slot-parity double buffered) ----------------
    def start_frontier(hop, b, slot):
      for j in range(block):
        if hop == 0:
          pltpu.make_async_copy(sid_ref.at[pl.ds(b * block + j, 1)],
                                vf.at[slot, j], fsem.at[slot, j]).start()
          pltpu.make_async_copy(sok_ref.at[pl.ds(b * block + j, 1)],
                                vok.at[slot, j],
                                oksem.at[slot, j]).start()
        else:
          prev = hops[hop - 1]
          r = b * block + j
          q = jnp.minimum(r // prev['k'], prev['s_pad'] - 1)
          l = jax.lax.rem(r, prev['k'])
          pltpu.make_async_copy(fp_refs[hop - 1].at[q, pl.ds(l, 1)],
                                vf.at[slot, j], fsem.at[slot, j]).start()

    def wait_frontier(hop, slot):
      for j in range(block):
        pltpu.make_async_copy(vf.at[slot, j], vf.at[slot, j],
                              fsem.at[slot, j]).wait()
        if hop == 0:
          pltpu.make_async_copy(vok.at[slot, j], vok.at[slot, j],
                                oksem.at[slot, j]).wait()

    def start_ip(slot):
      for j in range(block):
        fid = vf[slot, j, 0]
        addr = jnp.clip(fid, 0, num_nodes)
        pltpu.make_async_copy(ip_ref.at[pl.ds(addr, 2)],
                              vip.at[slot, j], ipsem.at[slot, j]).start()

    def wait_ip(slot):
      for j in range(block):
        pltpu.make_async_copy(vip.at[slot, j], vip.at[slot, j],
                              ipsem.at[slot, j]).wait()

    def start_windows(slot):
      for j in range(block):
        st = jnp.clip(vip[slot, j, 0], 0, num_edges)
        for a in range(n_a):
          pltpu.make_async_copy(src_refs[a].at[pl.ds(st, width)],
                                win_bufs[a].at[slot, j],
                                wsems[a].at[slot, j]).start()

    def wait_windows(slot):
      for j in range(block):
        for a in range(n_a):
          pltpu.make_async_copy(win_bufs[a].at[slot, j],
                                win_bufs[a].at[slot, j],
                                wsems[a].at[slot, j]).wait()

    def fetch_block(hop, b, slot):
      """Cold-start chain for a block with nothing prefetched (first
      block of each hop — at a hop boundary the frontier is written by
      the immediately preceding step, so there is nothing to overlap
      with: the documented per-boundary pipeline bubble)."""
      start_frontier(hop, b, slot)
      wait_frontier(hop, slot)
      start_ip(slot)
      wait_ip(slot)
      start_windows(slot)

    # -- hop phases, statically unrolled --------------------------------
    for hop in range(n_hops):
      h = hops[hop]
      k_h = h['k']

      @pl.when((i >= h['step0']) & (i < h['step0'] + h['nb']))
      def _(hop=hop, h=h, k_h=k_h):
        b = i - h['step0']

        @pl.when(b == 0)
        def _():
          fetch_block(hop, b, cur)

        has_next = b + 1 < h['nb']

        # next block's frontier starts resolving while this block's
        # windows land and compute runs
        @pl.when(has_next)
        def _():
          start_frontier(hop, b + 1, nxt)

        wait_windows(cur)
        ids_v = vf[cur][:, 0]                            # [block]
        if hop == 0:
          ok_v = vok[cur][:, 0] != 0
        else:
          ok_v = ids_v != big
        rowpos = b * block + jax.lax.broadcasted_iota(
            jnp.int32, (block,), 0)
        ok_v = jnp.logical_and(ok_v, rowpos < h['s'])
        ipv = vip[cur]                                   # [block, 2]
        deg = jnp.where(ok_v, ipv[:, 1] - ipv[:, 0], 0)
        u = u_refs[hop][...]                             # [block, K_h]
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (block, k_h), 1)
        if replace:
          off = jnp.minimum(
              (u * deg[:, None].astype(u.dtype)).astype(jnp.int32),
              jnp.maximum(deg[:, None] - 1, 0))
          mask = jnp.broadcast_to(deg[:, None] > 0, (block, k_h))
        else:
          # Floyd's algorithm, vectorized over the block — literally
          # ops/sample.py::_floyd_offsets on the [block] slice, so the
          # offsets are bit-identical to every other engine's draw
          cols = []
          for j in range(k_h):
            bound = jnp.maximum(deg - k_h + j, 0)
            t = jnp.minimum(
                (u[:, j] * (bound + 1).astype(u.dtype)).astype(
                    jnp.int32), bound)
            if cols:
              prev_cols = jnp.stack(cols, axis=1)
              dup = jnp.any(prev_cols == t[:, None], axis=1)
            else:
              dup = jnp.zeros((block,), bool)
            cols.append(jnp.where(dup, bound, t))
          sampled = jnp.stack(cols, axis=1)
          off = jnp.where((deg <= k_h)[:, None], iota_k, sampled)
          mask = iota_k < jnp.minimum(deg, k_h)[:, None]

        # hub fix-up, per row: degree > W rows read their exact edge
        # slots element-wise (no hub list, no cap — every hub row in
        # the frontier is fixed, the per-hop engines' clamped-cap
        # guarantee strengthened to unconditional)
        for j in range(block):
          deg_j = deg[j]
          st_j = ipv[j, 0]

          @pl.when(deg_j > width)
          def _(j=j, st_j=st_j):
            for kk in range(k_h):
              sl = jnp.clip(st_j + off[j, kk], 0,
                            max(num_edges - 1, 0))
              for a in range(n_a):
                pltpu.make_async_copy(src_refs[a].at[pl.ds(sl, 1)],
                                      hub_bufs[a].at[j, pl.ds(kk, 1)],
                                      hubsems[a].at[j, kk]).start()
            for kk in range(k_h):
              for a in range(n_a):
                pltpu.make_async_copy(
                    src_refs[a].at[pl.ds(0, 1)],
                    hub_bufs[a].at[j, pl.ds(kk, 1)],
                    hubsems[a].at[j, kk]).wait()

        woff = jnp.minimum(off, width - 1)
        iota3 = jax.lax.broadcasted_iota(jnp.int32, (block, k_h, width),
                                         2)
        onehot = iota3 == woff[:, :, None]
        is_hub = deg > width
        merged = []
        for a in range(n_a):
          win = win_bufs[a][cur]                         # [block, W]
          zero = jnp.zeros((), win.dtype)
          p = jnp.sum(jnp.where(onehot, win[:, None, :], zero),
                      axis=-1)
          hubfix = hub_bufs[a][...][:, :k_h].astype(win.dtype)
          merged.append(jnp.where(is_hub[:, None], hubfix, p))

        # next block's dependent chain resolves NOW, so its window DMAs
        # overlap the probe section below — the one DMA pipeline that
        # serves every hop
        @pl.when(has_next)
        def _(hop=hop):
          wait_frontier(hop, nxt)
          start_ip(nxt)
          wait_ip(nxt)
          start_windows(nxt)

        # dedup stage against the walk-resident table, slot order
        base = base_ref[0]
        r = r_ref[0]
        picks0 = merged[0]
        lab_rows, new_rows = [], []
        for j in range(block):
          labs_k, newh_k = [], []
          for kk in range(k_h):
            x = picks0[j, kk].astype(jnp.int32)
            v = mask[j, kk]
            lab, is_new = _probe_insert(tids, tlabs, x, v, base + r,
                                        n_buckets, lane)
            labs_k.append(lab)
            newh_k.append(is_new)
            r = r + is_new
          lab_rows.append(jnp.stack(labs_k))
          new_rows.append(jnp.stack(newh_k))
        r_ref[0] = r
        lab_mat = jnp.stack(lab_rows)
        new_mat = jnp.stack(new_rows)

        picks_refs[hop][...] = picks0
        if with_eids:
          eidp_refs[hop][...] = merged[1]
        prov_refs[hop][...] = lab_mat
        newh_refs[hop][...] = new_mat

        if hop < n_hops - 1:
          # stage the next hop's frontier: first occurrences keep their
          # id, everything else reads the sentinel row — exactly the
          # where(new_head, ids, INT32_MAX) frontier of the sort engine
          fscrs[hop][...] = jnp.where(new_mat != 0,
                                      picks0.astype(jnp.int32), big)
          dst = fp_refs[hop].at[pl.ds(b * block, block), :]
          pltpu.make_async_copy(fscrs[hop], dst, fpsem.at[0]).start()
          pltpu.make_async_copy(fscrs[hop], dst, fpsem.at[0]).wait()

    # the walk's full output surface is the per-hop blocked outputs;
    # nothing else leaves the kernel — in particular the table planes
    # never touch HBM

  def out_map(h):
    step0, nb = h['step0'], h['nb']
    return lambda i, *_: (jnp.clip(i - step0, 0, nb - 1), 0)

  in_specs = (
      [pl.BlockSpec((block, h['k']), out_map(h)) for h in hops]   # u
      + [pl.BlockSpec(memory_space=pl.ANY)] * (3 + n_a))
  out_specs = []
  out_shapes = []
  for fam_dtype in ([a.dtype for a in arrs]
                    + [jnp.int32, jnp.int32]):
    for h in hops:
      out_specs.append(pl.BlockSpec((block, h['k']), out_map(h)))
      out_shapes.append(
          jax.ShapeDtypeStruct((h['s_pad'], h['k']), fam_dtype))
  # frontier staging buffers (ANY, explicit DMA): one per hop boundary
  n_fp = max(n_hops - 1, 1)
  for t in range(n_fp):
    h = hops[min(t, n_hops - 1)]
    out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out_shapes.append(
        jax.ShapeDtypeStruct((h['s_pad'], h['k']), jnp.int32))

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=3,
      grid=(total_steps,),
      in_specs=in_specs,
      out_specs=out_specs,
      scratch_shapes=(
          [pltpu.VMEM((2, block, 1), jnp.int32),       # vf
           pltpu.VMEM((2, block, 1), jnp.int32),       # vok
           pltpu.VMEM((2, block, 2), jnp.int32)]       # vip
          + [pltpu.VMEM((2, block, width), a.dtype) for a in arrs]
          + [pltpu.VMEM((block, k_max), a.dtype) for a in arrs]
          + [pltpu.VMEM((block, hops[t]['k']), jnp.int32)
             for t in range(n_fp)]                     # fscr per hop
          + [pltpu.VMEM(tshape, jnp.int32),            # tids
             pltpu.VMEM(tshape, jnp.int32),            # tlabs
             pltpu.SMEM((1,), jnp.int32),              # r
             pltpu.SemaphoreType.DMA((2, block)),      # fsem
             pltpu.SemaphoreType.DMA((2, block)),      # oksem
             pltpu.SemaphoreType.DMA((2, block))]      # ipsem
          + [pltpu.SemaphoreType.DMA((2, block)) for _ in arrs]
          + [pltpu.SemaphoreType.DMA((block, k_max)) for _ in arrs]
          + [pltpu.SemaphoreType.DMA((1,))]),          # fpsem
  )
  _count_launch()
  outs = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=out_shapes,
      interpret=interpret,
  )(seed_tab_ids, seed_tab_labs, base_count, *u_hops,
    indptr_pad, seed_ids, seed_ok, *arrs)
  picks = tuple(outs[:n_hops])
  pos = n_hops
  if with_eids:
    eidp = tuple(outs[pos:pos + n_hops])
    pos += n_hops
  else:
    eidp = None
  prov = tuple(outs[pos:pos + n_hops]); pos += n_hops
  newh = tuple(outs[pos:pos + n_hops])
  return picks, eidp, prov, newh
