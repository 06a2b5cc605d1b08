"""Bulletproof gather timing + the fusion hypothesis.

Two prior harnesses produced impossible rates in the r5 session
(identical-args loops AND varying-args loops both showed cells exceeding
HBM bandwidth), so every cell here serializes iterations with a DEVICE-SIDE
dependency chain: each call's index input is tied (via
lax.optimization_barrier) to a scalar carried out of the previous call,
making overlap and result reuse impossible, and wall time covers the
whole chain with one final fence (amortizing the dispatch latency).

Cells:
  copy_bw       : y = x + 1 over 256 MB — calibration. If this reports
                  > ~900 GB/s the harness is lying; trust nothing.
  elem_alone    : gather M from [E], result returned whole (un-fused —
                  the gather's consumer is the output buffer itself).
  elem_fused    : same gather + a cheap fused consumer+reducer, forcing
                  XLA to fuse the gather into a loop (the composed
                  pipeline's situation per the op trace).
  elem_barrier  : gather wrapped in optimization_barrier on BOTH sides,
                  then the same consumer/reducer — does the barrier
                  recover the un-fused rate inside a larger program?
  rows_alone    : [B,128] row gather from [1M,128].

THE QUESTION: the op trace charges fusion.434 (the hop-2 gather, fused
with reshapes) 11.0 ms/batch = 14.3 ns/elt, while a standalone gather
benchmarked at ~bandwidth. If elem_fused >> elem_alone ~= elem_barrier,
the sampler fix is one optimization_barrier around each hop gather.

Prints one JSON line. TPU only (rates on CPU are meaningless).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

ITERS = 8


def main():
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  import jax.numpy as jnp
  from jax import lax

  E = 62_000_000
  M = 768_000
  rng = np.random.default_rng(0)
  arr = jnp.asarray(rng.integers(0, 2_450_000, E, dtype=np.int32))
  idxs = [jnp.asarray(rng.integers(0, E, M, dtype=np.int32))
          for _ in range(ITERS)]
  res = {'backend': jax.default_backend(), 'iters': ITERS}

  def chain_run(fn, inputs, *extra):
    """fn(dep, x, *extra) -> (out_scalar_dep, payload). Runs the chain;
    times the second pass (the first eats compile/RTT warmup).

    The pass is fenced by an actual HOST READBACK of the carried
    scalar, not block_until_ready: in the r5 session the calibration
    cell showed block_until_ready returning before the device work was
    done (copy_bw read 23 TB/s — 29x physical HBM), while a value
    readback cannot lie. The chain ties every call to the previous
    call's output, so the final readback transitively fences them all.
    """
    dep = jnp.zeros((), jnp.int32)
    for x in inputs:          # warm pass: compile + page in
      dep, _ = fn(dep, x, *extra)
    _ = int(dep)              # hard fence: host readback
    t0 = time.time()
    for x in inputs:
      dep, _ = fn(dep, x, *extra)
    _ = int(dep)              # hard fence: host readback
    return (time.time() - t0) / len(inputs)

  # --- calibration: big elementwise copy ---------------------------------
  big = jnp.asarray(rng.normal(size=(64_000_000,)).astype(np.float32))
  bigs = [big, big + 1, big + 2, big + 3]

  @jax.jit
  def copy_step(dep, x):
    x2, _ = lax.optimization_barrier((x, dep))
    y = x2 + 1.0
    return y[0].astype(jnp.int32) + dep, y

  dt = chain_run(copy_step, bigs)
  res['copy_bw_GBps'] = round(2 * big.nbytes / dt / 1e9, 1)
  res['copy_ms'] = round(1e3 * dt, 3)
  print(json.dumps(res), file=sys.stderr, flush=True)

  # --- elem_alone --------------------------------------------------------
  # NB: the source array rides as an ARGUMENT everywhere below — a
  # closed-over array becomes a jit constant baked into the compiled
  # program (248 MB here).
  @jax.jit
  def elem_alone(dep, idx, a):
    idx2, _ = lax.optimization_barrier((idx, dep))
    out = jnp.take(a, idx2, mode='clip')
    return out[0] + dep, out

  dt = chain_run(elem_alone, idxs, arr)
  res['elem_alone_ns_per_elt'] = round(1e9 * dt / M, 3)
  res['elem_alone_ms'] = round(1e3 * dt, 3)
  print(json.dumps(res), file=sys.stderr, flush=True)

  # --- elem_fused: gather + fused consumer -------------------------------
  @jax.jit
  def elem_fused(dep, idx, a):
    idx2, _ = lax.optimization_barrier((idx, dep))
    out = jnp.take(a, idx2, mode='clip')
    s = (out ^ (out >> 7)).sum(dtype=jnp.int32)   # cheap fused consumer
    return s + dep, s

  dt = chain_run(elem_fused, idxs, arr)
  res['elem_fused_ns_per_elt'] = round(1e9 * dt / M, 3)
  res['elem_fused_ms'] = round(1e3 * dt, 3)
  print(json.dumps(res), file=sys.stderr, flush=True)

  # --- elem_barrier: barriered gather inside the same program ------------
  @jax.jit
  def elem_barrier(dep, idx, a):
    idx2, _ = lax.optimization_barrier((idx, dep))
    out = jnp.take(a, idx2, mode='clip')
    (out,) = lax.optimization_barrier((out,))
    s = (out ^ (out >> 7)).sum(dtype=jnp.int32)
    return s + dep, s

  dt = chain_run(elem_barrier, idxs, arr)
  res['elem_barrier_ns_per_elt'] = round(1e9 * dt / M, 3)
  res['elem_barrier_ms'] = round(1e3 * dt, 3)
  print(json.dumps(res), file=sys.stderr, flush=True)

  # --- rows_alone --------------------------------------------------------
  NR, D, BR = 1_000_000, 128, 153_600
  tab = jnp.asarray(rng.normal(size=(NR, D)).astype(np.float32))
  rowss = [jnp.asarray(rng.integers(0, NR, BR, dtype=np.int32))
           for _ in range(ITERS)]

  @jax.jit
  def rows_alone(dep, r, t):
    r2, _ = lax.optimization_barrier((r, dep))
    out = jnp.take(t, r2, axis=0, mode='clip')
    return out[0, 0].astype(jnp.int32) + dep, out

  dt = chain_run(rows_alone, rowss, tab)
  res['rows_alone_ns_per_row'] = round(1e9 * dt / BR, 2)
  res['rows_alone_ms'] = round(1e3 * dt, 3)
  res['rows_alone_GBps'] = round(BR * D * 4 / dt / 1e9, 1)

  print(json.dumps(res))


if __name__ == '__main__':
  main()
