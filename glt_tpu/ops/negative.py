"""Negative edge sampling with vectorized strict-mode rejection.

Reference: csrc/cuda/random_negative_sampler.cu (uniform (row,col)
proposals; strict mode rejects existing edges via per-thread binary search
EdgeInCSR, retries ``trials_num`` times, compacts hits with thrust
copy_if, pads with non-strict samples). TPU translation (SURVEY.md §7):
all ``trials_num`` rounds are drawn at once, membership is a fixed-depth
vectorized binary search over the sorted-adjacency CSR, and compaction is
a stable argsort on validity — no dynamic shapes anywhere.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

#: probe rounds of :func:`edge_in_csr`: a lower-bound binary search
#: halves ``[lo, hi)`` once a round, so 34 rounds settle any row of up
#: to 2^34 entries, more than an int32-indexed CSR can hold. The count
#: is fixed by that worst case and not by the graph: a row of the
#: benchmark's graph is at most 2,000 wide (11 rounds), and the rounds
#: past a row's own depth re-read one settled element each.
PROBE_ROUNDS = 34


def edge_in_csr(indptr: jax.Array, indices: jax.Array,
                rows: jax.Array, cols: jax.Array) -> jax.Array:
  """Vectorized membership test: does edge (rows[i] -> cols[i]) exist?

  Requires columns sorted within each row (Topology guarantees this).
  Fixed-depth lower-bound binary search, the TPU analogue of EdgeInCSR
  (random_negative_sampler.cu:37-54): ``PROBE_ROUNDS`` dependent rounds
  of one element read a pair, whatever the graph (the bound is the
  widest row an index can address, 2^34 entries, not the widest row
  there is). A round past a row's own depth changes nothing. On the
  benchmark's link cell the whole negative stage (5 x 256 proposals)
  is ``link_negative_device_ms`` (PERF.md, section 5).
  """
  num_edges = indices.shape[0]
  lo = jnp.take(indptr, rows, mode='clip')
  hi = jnp.take(indptr, rows + 1, mode='clip')
  cols = cols.astype(indices.dtype)
  for _ in range(PROBE_ROUNDS):
    probing = lo < hi
    # overflow-safe midpoint: indptr may be int32 with values near 2^31
    mid = lo + ((hi - lo) >> 1)
    val = jnp.take(indices, jnp.clip(mid, 0, max(num_edges - 1, 0)),
                   mode='clip')
    go_right = probing & (val < cols)
    lo = jnp.where(go_right, mid + 1, lo)
    hi = jnp.where(probing & ~go_right, mid, hi)
  in_range = lo < jnp.take(indptr, rows + 1, mode='clip')
  at = jnp.take(indices, jnp.clip(lo, 0, max(num_edges - 1, 0)), mode='clip')
  return in_range & (at == cols)


class NegativeOutput(NamedTuple):
  rows: jax.Array   # [req]
  cols: jax.Array   # [req]
  mask: jax.Array   # [req] valid negatives (False only if padding=False
                    # and trials exhausted)
  # scalars, from random_negative_sample alone: proposals of all rounds
  # that were edges, and slots with no round that was not
  rejected: Optional[jax.Array] = None
  padded: Optional[jax.Array] = None


def random_negative_sample(
    indptr: jax.Array,
    indices: jax.Array,
    req_num: int,
    trials_num: int,
    key: jax.Array,
    num_rows: int,
    num_cols: int,
    strict: bool = True,
    padding: bool = False,
) -> NegativeOutput:
  """Sample ``req_num`` node pairs that are (in strict mode) not edges.

  Mirrors CUDARandomNegativeSampler::Sample(req_num, trials_num, padding)
  (py_export_glt.cc:198-201): propose uniform pairs, keep non-edges; with
  ``padding=True`` remaining slots are filled with (possibly-positive)
  uniform pairs so the output is always full. ``rejected`` counts the
  proposals of every round that were edges, ``padded`` the slots none of
  whose rounds was a non-edge (nought both in non-strict mode).
  """
  t = max(trials_num, 1)
  kr, kc = jax.random.split(key)
  prop_rows = jax.random.randint(kr, (t, req_num), 0, num_rows,
                                 dtype=jnp.int32)
  prop_cols = jax.random.randint(kc, (t, req_num), 0, num_cols,
                                 dtype=jnp.int32)
  if strict:
    ok = ~edge_in_csr(indptr, indices, prop_rows.reshape(-1),
                      prop_cols.reshape(-1)).reshape(t, req_num)
  else:
    ok = jnp.ones((t, req_num), bool)
  # column i: first trial row where ok — argmax over bool picks first True
  first = jnp.argmax(ok, axis=0)                       # [req]
  any_ok = jnp.any(ok, axis=0)
  sel_rows = jnp.take_along_axis(prop_rows, first[None, :], axis=0)[0]
  sel_cols = jnp.take_along_axis(prop_cols, first[None, :], axis=0)[0]
  if padding:
    # non-strict fill from the last trial round (reference
    # sampler/negative_sampler.py:39-57 semantics)
    rows = jnp.where(any_ok, sel_rows, prop_rows[-1])
    cols = jnp.where(any_ok, sel_cols, prop_cols[-1])
    mask = jnp.ones((req_num,), bool)
  else:
    rows, cols, mask = sel_rows, sel_cols, any_ok
  return NegativeOutput(
      rows=rows, cols=cols, mask=mask,
      rejected=(~ok).sum(dtype=jnp.int32),
      padded=(~any_ok).sum(dtype=jnp.int32))
