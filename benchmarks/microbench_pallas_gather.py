"""Decide the next sampling/feature-gather design from hardware data.

The op trace (profile_ops_tpu.py) shows the composed sampling step is
bound by per-element random gathers from the [62M] edge array
(fusion.434: 11.0 ms/batch = 14.3 ns/elt). Candidate escapes, each
measured here in isolation:

  xla_elem  : baseline — jnp.take of M elements from [E] (the wall).
  xla_rows  : XLA row gather [B, 128] from [N, 128] — is the feature
              path per-row or per-element serialized?
  dma_rows  : per-row async-copy windows (gather_windows, compiled) —
              DMA-issue-bound cost.
  vmem_take : Mosaic dynamic gather from a VMEM-resident table (2-D
              row/col form — Mosaic supports only 2-D gathers) — does
              the hardware have a vectorized VMEM gather, or does
              Mosaic also emit a scalar loop?

MEASUREMENT RULE (r5 session): identical repeated executions were
served from a cache there, so every timed iteration MUST use distinct
inputs — rates from identical-args loops (earlier microbench cells like
window_gather_xla "0.016 ms") are cache reads, not measurements.

Prints one JSON line of ns/element rates; run on TPU (CPU = interpret
mode, parity only — rates there are meaningless).
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

ITERS = 6


def timed_varying(fn, variants):
  """Time fn over DISTINCT argument tuples, fenced by a host READBACK
  of one element of the last output — in the r5 session neither
  identical-args loops nor block_until_ready were trustworthy (see
  microbench_gather_chained.py's calibration cell)."""
  import numpy as np

  def fence(o):
    leaf = o[0] if isinstance(o, (tuple, list)) else o
    return np.asarray(leaf).reshape(-1)[:1]

  out = fn(*variants[0])
  fence(out)
  t0 = time.time()
  outs = [fn(*v) for v in variants[1:]]
  fence(outs[-1])
  return (time.time() - t0) / (len(variants) - 1), outs[-1]


def main():
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  import jax.numpy as jnp
  from jax.experimental import pallas as pl

  interpret = jax.default_backend() != 'tpu'
  E = 62_000_000
  M = 768_000
  rng = np.random.default_rng(0)
  arr = jnp.asarray(rng.integers(0, 2_450_000, E, dtype=np.int32))
  idxs = [jnp.asarray(rng.integers(0, E, M, dtype=np.int32))
          for _ in range(ITERS)]
  res = {'backend': jax.default_backend(), 'interpret': interpret}

  # --- xla_elem: the wall -------------------------------------------------
  f = jax.jit(lambda a, i: jnp.take(a, i, mode='clip'))
  dt, _ = timed_varying(f, [(arr, i) for i in idxs])
  res['xla_elem_ns_per_elt'] = round(1e9 * dt / M, 2)

  # --- xla_rows: feature-path row gather ----------------------------------
  NR, D = 1_000_000, 128
  BR = 153_600
  tab_rows = jnp.asarray(rng.normal(size=(NR, D)).astype(np.float32))
  rowss = [jnp.asarray(rng.integers(0, NR, BR, dtype=np.int32))
           for _ in range(ITERS)]
  fr = jax.jit(lambda t, r: jnp.take(t, r, axis=0, mode='clip'))
  dtr, _ = timed_varying(fr, [(tab_rows, r) for r in rowss])
  res['xla_rows_ns_per_row'] = round(1e9 * dtr / BR, 1)
  res['xla_rows_ns_per_elt'] = round(1e9 * dtr / (BR * D), 3)
  res['xla_rows_ms'] = round(1e3 * dtr, 3)

  # --- dma_rows: compiled gather_windows (row-block DMA) ------------------
  from glt_tpu.ops.pallas_kernels import gather_windows
  R, W = 153_600, 128
  startss = [jnp.asarray(
      np.sort(rng.integers(0, E - W, R).astype(np.int32)))
      for _ in range(ITERS)]
  for blk in (8, 32):
    try:
      g = functools.partial(gather_windows, block=blk,
                            interpret=interpret)
      dtw, _ = timed_varying(g, [(arr, s, W) for s in startss])
      res[f'dma_rows_b{blk}_ns_per_row'] = round(1e9 * dtw / R, 1)
      res[f'dma_rows_b{blk}_ms'] = round(1e3 * dtw, 3)
    except Exception as e:
      res[f'dma_rows_b{blk}_error'] = str(e)[:300]

  # --- vmem_take: Mosaic dynamic gather from a VMEM table (2-D form) ------
  # table [64, 128] VMEM-resident; idx [200, 3840] per variant, block
  # (8, 3840) per grid step; in-kernel gather tab[idx>>7, idx&127].
  TN, TD = 64, 128
  table2d = jnp.asarray(
      rng.integers(0, 1 << 20, (TN, TD), dtype=np.int32))
  idx_smalls = [jnp.asarray(
      rng.integers(0, TN * TD, M, dtype=np.int32)).reshape(200, 3840)
      for _ in range(ITERS)]

  def vmem_take_kernel(tab_ref, idx_ref, out_ref):
    idx = idx_ref[:]
    tab = tab_ref[:]
    out_ref[:] = tab[idx >> 7, idx & 127]

  @jax.jit
  def vmem_take(tab, ib):
    return pl.pallas_call(
        vmem_take_kernel,
        grid=(ib.shape[0] // 8,),
        in_specs=[
            pl.BlockSpec((TN, TD), lambda i: (0, 0)),
            pl.BlockSpec((8, 3840), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((8, 3840), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(ib.shape, jnp.int32),
        interpret=interpret,
    )(tab, ib)

  try:
    dtv, outv = timed_varying(vmem_take,
                              [(table2d, ib) for ib in idx_smalls])
    ref = jnp.take(table2d.reshape(-1), idx_smalls[-1], mode='clip')
    assert bool(jnp.array_equal(outv, ref)), 'vmem_take mismatch'
    res['vmem_take_ns_per_elt'] = round(1e9 * dtv / M, 2)
    res['vmem_take_ms'] = round(1e3 * dtv, 3)
  except Exception as e:
    res['vmem_take_error'] = str(e)[:300]

  print(json.dumps(res))


if __name__ == '__main__':
  main()
