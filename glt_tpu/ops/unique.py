"""Ordered dedup/relabel — the inducer's hash table, the TPU way.

The reference dedups frontier nodes with an open-addressing GPU hash table
(include/hash_table.cuh:27-84, atomicCAS insert + atomicMin first-occurrence
ordering) inside CUDAInducer (csrc/cuda/inducer.cu:33-133). TPUs have no
device atomics in that style, so we get identical semantics from sorts
(SURVEY.md §7 "Hard parts"): stable-sort by value, mark run heads, then
order runs by their first-occurrence position. All shapes static.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def ordered_unique(
    ids: jax.Array,
    valid: jax.Array,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
  """First-occurrence-ordered unique with inverse labels, static shapes.

  Args:
    ids: [M] integer ids.
    valid: [M] bool; invalid slots are ignored.
    capacity: static output size; must be >= the number of distinct valid
      ids (callers size it with the same Σ batch·Πfanouts bound the
      reference uses, neighbor_sampler.py:660-677).

  Returns:
    uniq: [capacity] distinct ids in order of first appearance, -1 padded.
    count: scalar int32 number of distinct ids.
    inverse: [M] int32, inverse[i] = position of ids[i] in uniq
      (first-occurrence order); -1 where ~valid.
  """
  m = ids.shape[0]
  big = jnp.iinfo(ids.dtype).max
  x = jnp.where(valid, ids, big)
  order = jnp.argsort(x, stable=True)                 # [M] value-sorted
  xs = jnp.take(x, order)
  head = jnp.concatenate(
      [jnp.ones((1,), bool), xs[1:] != xs[:-1]]) & (xs != big)
  # run index (value order) per sorted element; invalid tail inherits the
  # last run id but is masked out of `inverse` below.
  seg = jnp.cumsum(head) - 1                          # [M]
  # run heads carry the min original position (stable sort guarantees it)
  run_starts = jnp.nonzero(head, size=capacity, fill_value=m)[0]
  run_ok = run_starts < m
  safe = jnp.minimum(run_starts, m - 1)
  run_first_pos = jnp.where(run_ok, jnp.take(order, safe), m)
  run_vals = jnp.where(run_ok, jnp.take(xs, safe), big)
  # appearance order = ascending first position
  aorder = jnp.argsort(run_first_pos)
  uniq = jnp.take(run_vals, aorder)
  count = head.sum().astype(jnp.int32)
  # rank of each value-ordered run in appearance order
  rank = jnp.zeros((capacity,), jnp.int32).at[aorder].set(
      jnp.arange(capacity, dtype=jnp.int32))
  seg_at_orig = jnp.zeros((m,), jnp.int32).at[order].set(
      seg.astype(jnp.int32))
  inverse = jnp.take(rank, jnp.clip(seg_at_orig, 0, capacity - 1))
  inverse = jnp.where(valid, inverse, -1)
  uniq = jnp.where(jnp.arange(capacity) < count, uniq, -1)
  return uniq, count, inverse


def unique_rows(ids: jax.Array, valid: jax.Array, fixed: int = 0
                ) -> Tuple[jax.Array, jax.Array]:
  """``ordered_unique`` for a batch of small sets at once: row ``l`` of
  ``ids`` ``[L, M]`` is one set, deduped on its own (not over the batch).

  A valid slot is kept unless an earlier valid slot of its row holds its
  id; the kept ids move to the front of the row in their order, the rest
  of the row is -1. The first ``fixed`` slots are kept as they stand where
  valid, equal or not (a link's two endpoints keep slots 0 and 1). Dense:
  an ``[M, M]`` comparison a row and one sort along the row, no gather;
  meant for ``M`` of a few hundred.

  Returns ``(uniq [L, M], count [L] int32)``.
  """
  m = ids.shape[1]
  pos = jnp.arange(m, dtype=jnp.int32)
  earlier = pos[None, :] < pos[:, None]                # [i, j]: j < i
  dup = ((ids[:, :, None] == ids[:, None, :]) & valid[:, None, :]
         & earlier[None]).any(-1)
  keep = valid & ~(dup & (pos >= fixed)[None, :])
  key = jnp.where(keep, pos[None, :], m + pos[None, :])
  uniq = jax.lax.sort((key, ids), dimension=1, num_keys=1)[1]
  count = keep.sum(axis=1, dtype=jnp.int32)
  return jnp.where(pos[None, :] < count[:, None], uniq, -1), count


# ---------------------------------------------------------------------------
# Sort-merge inducer: the hop loops' one dedup (ops/pipeline.py).
#
# Hardware measurement (v5e): every random access XLA:TPU emits — gather or
# scatter, any operand size — costs ~7-16ns per OUTPUT ELEMENT, serialized;
# `lax.sort` by contrast runs vectorized at ~3-4ns/element and
# multi-operand sorts carry payloads for free. So dedup/relabel/frontier
# compaction are expressed as multi-operand sorts over the batch plus
# prefix scans, with no [N]-sized table: the same trick as the reference's
# sort-free GPU hash table but inverted for a machine whose fast primitive
# is the sort, not the atomic.
# ---------------------------------------------------------------------------

_BIG = jnp.iinfo(jnp.int32).max


def _fill_forward(hd: jax.Array, *vals: jax.Array):
  """Segmented fill-forward: out_k[i] = vals_k at the most recent j<=i
  with hd[j]. Log-depth associative scan — no gathers, no scatters."""
  def comb(a, b):
    ah = a[0]
    bh = b[0]
    return (ah | bh,) + tuple(
        jnp.where(bh, bv, av) for av, bv in zip(a[1:], b[1:]))
  return jax.lax.associative_scan(comb, (hd,) + vals)[1:]


def _fill_forward_take(hd: jax.Array, *vals: jax.Array):
  """What :func:`_fill_forward` returns where ``hd[0]`` holds, from a
  running maximum of the heads' positions and one take a value: a gather
  at run time where the scan has none, and a tenth of the scan's time in
  the TPU's compiler."""
  from .scan import cummax_i32
  pos = jnp.arange(hd.shape[0], dtype=jnp.int32)
  head = cummax_i32(jnp.where(hd, pos, 0))
  return tuple(jnp.take(v, head) for v in vals)


def sorted_hop_dedup(
    u_ids: jax.Array,    # [C] seen-set ids (any order, _BIG padding ok)
    u_labs: jax.Array,   # [C] their labels
    count: jax.Array,    # scalar int32: labels assigned so far
    ids: jax.Array,      # [M] sampled ids for this hop (dups allowed)
    valid: jax.Array,    # [M]
):
  """One hop of dedup/relabel with ZERO random-memory ops — two
  multi-operand sorts plus prefix scans. The hop loops run it on the
  seed hop, whose labels must be exact.

  Labels are exact reference-inducer semantics: previously seen ids keep
  their labels; new ids get ``count..count+n-1`` in first-occurrence
  (slot) order. The returned per-element arrays are in a PERMUTED order
  (appearance-grouped), not slot order; ``pos3`` maps them back.

  Returns a dict with:
    ids3 / labels3 : [M] aligned per-element
    new_head3 : [M] True at the first occurrence of each new id
    pos3      : [M] original slot index of each element
    u_ids2 / u_labs2 : [C+M] updated seen-set (append-form, not sorted)
    count2 : scalar, new_count : scalar
  """
  c = u_ids.shape[0]
  m = ids.shape[0]
  big = _BIG
  x = jnp.where(valid, ids.astype(jnp.int32), big)
  cat_id = jnp.concatenate([u_ids, x])
  cat_pos = jnp.concatenate([jnp.full((c,), -1, jnp.int32),
                             jnp.arange(m, dtype=jnp.int32)])
  cat_lab = jnp.concatenate([u_labs, jnp.full((m,), -1, jnp.int32)])
  # sort 1: (id, pos) — a seen-set entry (pos -1) heads its id-run
  sid, spos, slab = jax.lax.sort([cat_id, cat_pos, cat_lab], num_keys=2)

  hd = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
  hd = hd & (sid != big)
  head_slab, head_spos = _fill_forward(hd, slab, spos)
  is_new_run = (head_slab < 0) & (sid != big)    # run headed by a slot
  u_lab = jnp.where(is_new_run | (sid == big), -1, head_slab)

  # sort 2: (group key, pos). New runs group under their head's slot
  # position (= appearance order); seen/invalid slots key by their own
  # position; original seen-set entries are pushed to the back. All M
  # slot elements therefore land in [:M].
  is_slot = spos >= 0
  gkey = jnp.where(is_slot, jnp.where(is_new_run, head_spos, spos), big)
  s2 = jax.lax.sort([gkey, spos, sid, u_lab, is_new_run.astype(jnp.int32)],
                    num_keys=2)
  gkey2, pos3, ids3, ulab3, new3 = (a[:m] for a in s2)
  new3 = new3.astype(bool)

  # the first element of each new group is its head (pos == group key);
  # inclusive prefix count over appearance-ordered groups = label rank
  new_head3 = new3 & (pos3 == gkey2)
  from .scan import cumsum_i32
  rank = cumsum_i32(new_head3.astype(jnp.int32))
  labels3 = jnp.where(new3, count + rank - 1, ulab3)

  new_count = rank[-1] if m > 0 else jnp.zeros((), jnp.int32)
  # seen-set append: each new id exactly once (at its head element)
  u_ids2 = jnp.concatenate([u_ids, jnp.where(new_head3, ids3, big)])
  u_labs2 = jnp.concatenate([u_labs, jnp.where(new_head3, labels3,
                                               big)])
  return dict(ids3=ids3, labels3=labels3, new_head3=new_head3,
              pos3=pos3, u_ids2=u_ids2, u_labs2=u_labs2,
              count2=count + new_count, new_count=new_count)


def sorted_hop_dedup_fused(
    u_ids: jax.Array,    # [C] seen-set ids (append-form, _BIG padding)
    u_labs: jax.Array,   # [C] their labels (_BIG at padding)
    count: jax.Array,    # scalar int32: labels assigned so far
    ids: jax.Array,      # [M] sampled ids for this hop (dups allowed)
    valid: jax.Array,    # [M]
    *,
    fast_compile: bool = False,
):
  """One hop of dedup/relabel with ONE 3-operand sort — the fused
  sample+assign stage the hop loops run on every hop after the seed hop.

  The committed TPU trace (benchmarks/tpu_runs/profile_sampler_tpu.json)
  puts the hop-2 assign at 41.1 ms against 15.3 ms of sampling: the
  dedup stage is the profiled bottleneck the reference solves with one
  fused CUDA kernel (csrc/cuda/random_sampler.cu:59-109 samples and
  emits in a single launch). :func:`sorted_hop_dedup` pays TWO wide
  multi-operand sorts per hop (3 and 5 operands over [C+M]); this variant
  pays one narrow one, by relaxing one property nothing downstream
  relies on: NEW ids get labels ``count..count+n-1`` in within-hop
  VALUE order instead of first-occurrence slot order. Seen ids keep
  their labels exactly; counts, masks, seed handling (callers keep the
  exact path for the seed hop) and the label<->node bijection are
  unchanged, so edges map to the same global-id multiset.

  How: sort (id, labkey, pos) with 2 keys — a seen entry's label is
  < _BIG so it heads its run and wins via a segmented fill-forward;
  new runs are ranked by one prefix scan; results return to SLOT order
  with a single packed scatter (labels + new-head bit in one int32),
  so every per-element output below is aligned to the caller's flat
  sample buffers and edge payloads never ride a sort at all.

  ``fast_compile`` gives the same outputs bit for bit from forms that
  the TPU's compiler gets through in a fifth of the time (an unstable
  sort, where a stable one carries an iota as one more key, and
  :func:`_fill_forward_take`): for programs that hold one such hop a
  node type, whose build the sorts and scans otherwise are.

  Returns dict with (all [M], slot order):
    labels3   : compact labels, -1 at ~valid
    new_head3 : True at exactly one slot per newly-seen id
    u_ids2 / u_labs2 : [C+M] updated append-form seen-set
    count2 / new_count : scalars
  """
  c = u_ids.shape[0]
  m = ids.shape[0]
  big = _BIG
  x = jnp.where(valid, ids.astype(jnp.int32), big)
  cat_id = jnp.concatenate([u_ids, x])
  cat_labkey = jnp.concatenate([u_labs, jnp.full((m,), big, jnp.int32)])
  cat_pos = jnp.concatenate([jnp.full((c,), -1, jnp.int32),
                             jnp.arange(m, dtype=jnp.int32)])
  if fast_compile:
    # the same order from keys that leave no tie to break: within an id
    # the seen entry (pos -1) leads and the slots follow by position, and
    # the padding's equal triples are one another's copies
    sid, spos, slabkey = jax.lax.sort([cat_id, cat_pos, cat_labkey],
                                      num_keys=2, is_stable=False)
  else:
    sid, slabkey, spos = jax.lax.sort([cat_id, cat_labkey, cat_pos],
                                      num_keys=2)
  hd = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
  (run_lab,) = (_fill_forward_take if fast_compile
                else _fill_forward)(hd, slabkey)
  ok = sid != big
  is_new = (run_lab == big) & ok
  new_head = hd & is_new
  from .scan import cumsum_i32
  rank = cumsum_i32(new_head.astype(jnp.int32))
  labels_all = jnp.where(is_new, count + rank - 1,
                         jnp.where(ok, run_lab, -1))
  # pack (label, new_head) into one int32: labels fit in 31 bits and
  # label == -1 implies new_head is False, so -1 packs to -2 (>> 1
  # recovers it; & 1 reads 0). One scatter instead of two.
  packed = labels_all * 2 + new_head.astype(jnp.int32)
  # slot elements carry pos >= 0 (a new run is headed by a slot
  # element); seen-set entries route to the sink row m
  buf = jnp.full((m + 1,), -2, jnp.int32).at[
      jnp.where(spos >= 0, spos, m)].set(
      jnp.where(spos >= 0, packed, -2))
  packed_slot = buf[:m]
  labels3 = packed_slot >> 1
  new_head3 = (packed_slot & 1) == 1
  new_count = rank[-1] if m + c > 0 else jnp.zeros((), jnp.int32)
  u_ids2 = jnp.concatenate([u_ids, jnp.where(new_head3, x, big)])
  u_labs2 = jnp.concatenate([u_labs, jnp.where(new_head3, labels3,
                                               big)])
  return dict(labels3=labels3, new_head3=new_head3,
              u_ids2=u_ids2, u_labs2=u_labs2,
              count2=count + new_count, new_count=new_count)


def sorted_nodes_by_label(u_ids: jax.Array, u_labs: jax.Array,
                          count: jax.Array, budget: int,
                          fast_compile: bool = False) -> jax.Array:
  """Materialize the dense node list (position = label) from the
  append-form seen-set with ONE sort by label; -1 padding past count.
  ``fast_compile`` writes the same list with one scatter instead (labels
  are distinct; padding goes to a sink row), which the TPU's compiler
  gets through at once."""
  lab_key = jnp.where(u_labs < 0, _BIG, u_labs)
  if fast_compile:
    nodes = jnp.full((budget + 1,), -1, jnp.int32).at[
        jnp.minimum(lab_key, budget)].set(u_ids.astype(jnp.int32))
    return jnp.where(jnp.arange(budget) < count, nodes[:budget], -1)
  nodes = jax.lax.sort([lab_key, u_ids], num_keys=1)[1]
  nodes = nodes[:budget] if nodes.shape[0] >= budget else jnp.pad(
      nodes, (0, budget - nodes.shape[0]), constant_values=-1)
  return jnp.where(jnp.arange(budget) < count, nodes, -1)
