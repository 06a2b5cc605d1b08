"""Prefix-scan primitives tuned for TPU.

XLA lowers ``jnp.cumsum`` on TPU to a reduce-window pass that runs at
~2.4ns/element (benchmarks/microbench_prims.py). At the 1M-element scale
of the sampling pipeline a blocked formulation — per-block cumsum via a
triangular matmul on the MXU plus a tiny carry level — is ~6x faster
(benchmarks/proto_window_hop.py H3). int32 inputs stay exact: float32
accumulates exactly up to 2^24, and per-block sums of sampling
indicators are far below that; the carry level accumulates in int32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_BLOCK = 512


def cumsum_i32(x: jax.Array) -> jax.Array:
  """Inclusive int32 cumsum of a 1-D array. Exact iff every value and
  every within-block (512) partial sum is exactly representable in
  float32, i.e. magnitudes < 2^24 — true for the 0/1 indicators the
  sampling pipeline feeds it. The matmul is pinned to HIGHEST precision
  so f32 inputs are not rounded to bf16 on the MXU. Falls back to native
  cumsum below one block."""
  m = x.shape[0]
  if m <= _BLOCK:
    return jnp.cumsum(x.astype(jnp.int32))
  b = _BLOCK
  pad = (-m) % b
  x2 = jnp.pad(x.astype(jnp.int32), (0, pad)).reshape(-1, b)
  tri = jnp.tril(jnp.ones((b, b), jnp.float32))
  within = jnp.matmul(x2.astype(jnp.float32), tri.T,
                      precision=jax.lax.Precision.HIGHEST
                      ).astype(jnp.int32)                      # [nb, b]
  block_tot = within[:, -1]                                    # [nb]
  carry = jnp.cumsum(block_tot) - block_tot                    # exclusive
  out = within + carry[:, None]
  return out.reshape(-1)[:m]


def cummax_i32(x: jax.Array) -> jax.Array:
  """Inclusive running maximum of a 1-D int32 array, blocked like
  :func:`cumsum_i32`: a cumulative maximum along rows of ``_BLOCK`` and a
  carry level over the rows. Exact for any values. XLA's one-dimensional
  ``lax.cummax`` gives the same numbers; the TPU's compiler takes five
  times as long over it at half a million elements."""
  m = x.shape[0]
  if m <= _BLOCK:
    return jax.lax.cummax(x.astype(jnp.int32))
  low = jnp.iinfo(jnp.int32).min
  pad = (-m) % _BLOCK
  x2 = jnp.pad(x.astype(jnp.int32), (0, pad),
               constant_values=low).reshape(-1, _BLOCK)
  within = jax.lax.cummax(x2, axis=1)                          # [nb, b]
  carry = jax.lax.cummax(within[:, -1])                        # [nb]
  carry = jnp.concatenate([jnp.full((1,), low, jnp.int32), carry[:-1]])
  return jnp.maximum(within, carry[:, None]).reshape(-1)[:m]
