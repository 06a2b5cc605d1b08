"""Prototype A/B: window-gather one-hop + fast hash RNG + matmul cumsum
vs the current element-gather formulation, at hop-2 shapes.

Hypotheses (from microbench_prims):
  H1  `lax.gather` with a contiguous slice (one [W]-window per row)
      costs ~per-ROW not per-element -> replaces the 12.7ms [S,K]
      element gather with a ~3ms [S,W] window gather + vector select.
  H2  a counter-hash RNG (vectorized mul/xor) replaces threefry
      uniforms (7.5ms/1M) at VPU speed.
  H3  cumsum via blocked triangular matmul beats reduce-window cumsum.

Emits one JSON line with per-variant ms.
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

N = 2_450_000
E = 62_000_000
F = 153_600
K = 5
W = 96        # window: covers Poisson(25) degrees to ~1e-12 tail


def timed(fn, *args, iters=20, warmup=3):
  import jax
  out = None
  for _ in range(warmup):
    out = fn(*args)
  jax.block_until_ready(out)
  t0 = time.time()
  for _ in range(iters):
    out = fn(*args)
  jax.block_until_ready(out)
  return (time.time() - t0) / iters * 1e3


def main():
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  import jax.numpy as jnp
  from jax import lax

  res = {}
  def rec(name, ms):
    res[name] = round(ms, 3)
    print(f'# {name}: {ms:.3f} ms', file=sys.stderr, flush=True)

  rng = np.random.default_rng(0)
  indices = jnp.asarray(rng.integers(0, N, E, dtype=np.int64)
                        .astype(np.int32))
  # synthetic indptr with Poisson(25)-ish rows
  deg_np = rng.poisson(25.0, N).astype(np.int64)
  indptr_np = np.zeros(N + 1, np.int64)
  np.cumsum(deg_np, out=indptr_np[1:])
  scale = E / indptr_np[-1]
  indptr_np = (indptr_np * scale).astype(np.int64)
  indptr = jnp.asarray(indptr_np.astype(np.int32))
  frontier = jnp.asarray(rng.integers(0, N, F).astype(np.int32))
  key = jax.random.key(0)

  # ---- baseline: current sample_neighbors (element gather + threefry)
  from glt_tpu.ops.sample import sample_neighbors

  @jax.jit
  def base(fr, key):
    out = sample_neighbors(indptr, indices, fr, K, key,
                           seed_mask=jnp.ones((F,), bool))
    return out.nbrs, out.mask

  rec('baseline_one_hop', timed(base, frontier, key))

  # ---- H2: counter-hash uniforms --------------------------------------
  def hash_u01(key32, shape, salt):
    # 2-round multiply-xorshift mix of (counter, key) — murmur3-style
    # finalizer; statistical (not cryptographic) quality, VPU-speed.
    n = int(np.prod(shape))
    x = lax.iota(jnp.uint32, n) + jnp.uint32((salt * 0x9E3779B9)
                                             & 0xFFFFFFFF)
    x = x ^ key32
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x.astype(jnp.float32) * (1.0 / 4294967296.0)).reshape(shape)

  @jax.jit
  def h2(k32):
    return hash_u01(k32, (K, F), 1)

  rec('hash_uniform_5x153k', timed(h2, jnp.uint32(1234)))
  rec('threefry_uniform_5x153k',
      timed(jax.jit(lambda k: jax.random.uniform(k, (K, F))), key))

  # ---- H1: window gather + select (one-hot vs take_along_axis) ------
  def _window_and_offsets(fr, k32):
    """Shared: [F,W] contiguous window per row + Floyd offsets in it."""
    start = jnp.take(indptr, fr, mode='clip')
    end = jnp.take(indptr, fr + 1, mode='clip')
    deg = (end - start).astype(jnp.int32)
    win = lax.gather(
        indices, start[:, None],
        lax.GatherDimensionNumbers(
            offset_dims=(1,), collapsed_slice_dims=(),
            start_index_map=(0,), operand_batching_dims=(),
            start_indices_batching_dims=()),
        slice_sizes=(W,), mode=lax.GatherScatterMode.CLIP)   # [F, W]
    u = hash_u01(k32, (K, F), 2)
    degc = jnp.minimum(deg, W)
    chosen = jnp.zeros((F, K), jnp.int32)
    for j in range(K):
      bound = jnp.maximum(degc - K + j, 0)
      t = jnp.minimum((u[j] * (bound + 1).astype(u.dtype)).astype(
          jnp.int32), bound)
      if j > 0:
        dup = jnp.any(chosen[:, :j] == t[:, None], axis=1)
      else:
        dup = jnp.zeros((F,), bool)
      chosen = chosen.at[:, j].set(jnp.where(dup, bound, t))
    iota_k = jnp.arange(K, dtype=jnp.int32)[None, :]
    offs = jnp.where((degc <= K)[:, None],
                     jnp.broadcast_to(iota_k, chosen.shape), chosen)
    mask = iota_k < jnp.minimum(degc, K)[:, None]
    return win, offs, mask

  @jax.jit
  def window_hop(fr, k32):
    win, offs, mask = _window_and_offsets(fr, k32)
    wio = lax.iota(jnp.int32, W)[None, None, :]
    sel = (offs[:, :, None] == wio)
    nbrs = jnp.sum(jnp.where(sel, win[:, None, :], 0), axis=-1)
    return nbrs, mask

  rec('window_hop_W96', timed(window_hop, frontier, jnp.uint32(7)))

  @jax.jit
  def window_hop_taa(fr, k32):
    win, offs, mask = _window_and_offsets(fr, k32)
    nbrs = jnp.take_along_axis(win, offs, axis=1)
    return nbrs, mask

  rec('window_hop_taa_W96', timed(window_hop_taa, frontier,
                                  jnp.uint32(7)))

  # ---- H3: cumsum via blocked triangular matmul -----------------------
  M = 768_000
  v = jnp.asarray(rng.integers(0, 3, M).astype(np.int32))

  def matmul_cumsum(x):
    b = 512
    m = x.shape[0]
    pad = (-m) % b
    x2 = jnp.pad(x, (0, pad)).reshape(-1, b).astype(jnp.float32)
    tri = jnp.tril(jnp.ones((b, b), jnp.float32))
    within = x2 @ tri.T                       # inclusive row cumsum
    block_tot = within[:, -1]
    # recurse one level on block totals (<=1501 blocks)
    nb = block_tot.shape[0]
    pad2 = (-nb) % b
    bt = jnp.pad(block_tot, (0, pad2)).reshape(-1, b)
    bt_within = bt @ tri.T
    bt_tot = bt_within[:, -1]
    lvl2 = jnp.cumsum(bt_tot)                 # tiny
    offs2 = jnp.concatenate([jnp.zeros((1,), jnp.float32), lvl2[:-1]])
    block_prefix = (bt_within + offs2[:, None] - bt).reshape(-1)[:nb]
    out = within + block_prefix[:, None] - 0.0
    return out.reshape(-1)[:m].astype(jnp.int32)

  rec('cumsum_matmul_768k', timed(jax.jit(matmul_cumsum), v))
  rec('cumsum_native_768k', timed(jax.jit(jnp.cumsum), v))

  # parity check (host)
  got = np.asarray(jax.jit(matmul_cumsum)(v))
  want = np.cumsum(np.asarray(v))
  assert (got == want).all(), 'matmul cumsum mismatch'

  dev = jax.devices()[0]
  print(json.dumps({'metric': 'proto_window_ms', 'backend': dev.platform,
                    'W': W, 'ops': res}))


if __name__ == '__main__':
  main()
