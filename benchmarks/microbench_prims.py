"""Primitive-op microbenchmarks at sampler shapes — decides the dedup
formulation (scatter-table vs sort-based) and quantifies the gather
floor on the actual backend.

Each row: steady-state ms for one op at the bench.py hot-loop shapes
(frontier 153.6k, slots 768k, table 2.45M, edges 62M). Emits one JSON
line; ``GLT_BENCH_PLATFORM=cpu`` forces the CPU backend.
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
M = 768_000          # hop-2 slot count
F = 153_600          # hop-2 frontier width


def _fence(out):
  """Hard completion fence: HOST READBACK of one element. In the r5
  session block_until_ready returned before device work completed
  (microbench_gather_chained.py's calibration cell measured a 256 MB
  copy at 23 TB/s under block_until_ready — 29x physical HBM — vs
  31-800 GB/s under a value readback), so every timing boundary here
  transfers a real value instead."""
  import numpy as np
  leaf = out[0] if isinstance(out, (tuple, list)) else out
  return np.asarray(leaf).reshape(-1)[:1]


def timed(fn, *args, iters=20, warmup=3, donate_idx=None):
  """NB: without donate_idx every iteration reuses identical inputs;
  results are only trustworthy when corroborated (the committed r5
  cells for gathers/sorts match the in-program device trace). Cells
  measured with identical args AND contradicting the trace
  (window_gather_xla, uniform_rbg) are invalid."""
  import time as _t
  out = None
  state = list(args)
  for _ in range(warmup):
    out = fn(*state)
    if donate_idx is not None:
      state[donate_idx] = out[donate_idx] if isinstance(out, tuple) else out
  _fence(out)
  t0 = _t.time()
  for _ in range(iters):
    out = fn(*state)
    if donate_idx is not None:
      state[donate_idx] = out[donate_idx] if isinstance(out, tuple) else out
  _fence(out)
  return (_t.time() - t0) / iters * 1e3


def main():
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  import jax.numpy as jnp

  rng = np.random.default_rng(0)
  res = {}

  def rec(name, ms):
    res[name] = round(ms, 3)
    print(f'# {name}: {ms:.3f} ms', file=sys.stderr, flush=True)

  big = jnp.asarray(rng.integers(0, N, E, dtype=np.int64).astype(np.int32))
  table = jnp.full((N + 1,), -1, jnp.int32)
  idx_m = jnp.asarray(rng.integers(0, N, M).astype(np.int32))
  idx_f = jnp.asarray(rng.integers(0, E, F).astype(np.int32))
  idx_me = jnp.asarray(rng.integers(0, E, M).astype(np.int32))
  vals_m = jnp.asarray(rng.integers(0, 1 << 30, M).astype(np.int32))

  # -- gathers ---------------------------------------------------------
  rec('gather_768k_from_62M',
      timed(jax.jit(lambda i: jnp.take(big, i, mode='clip')), idx_me))
  rec('gather_768k_from_2.45M',
      timed(jax.jit(lambda i: jnp.take(table, i, mode='clip')), idx_m))
  rec('gather_153k_from_62M',
      timed(jax.jit(lambda i: jnp.take(big, i, mode='clip')), idx_f))

  # -- scatters into the [N+1] table -----------------------------------
  @functools.partial(jax.jit, donate_argnums=(0,))
  def scat_set(t, i, v):
    return t.at[i].set(v)

  @functools.partial(jax.jit, donate_argnums=(0,))
  def scat_min(t, i, v):
    return t.at[i].min(v)

  rec('scatter_set_768k_into_2.45M',
      timed(scat_set, table, idx_m, vals_m, donate_idx=0))
  rec('scatter_min_768k_into_2.45M',
      timed(scat_min, jnp.full((N + 1,), 2**31 - 1, jnp.int32), idx_m,
            vals_m, donate_idx=0))

  # -- sorts at dedup shapes -------------------------------------------
  rec('sort_768k_i32', timed(jax.jit(jnp.sort), vals_m))
  rec('argsort_768k_i32', timed(jax.jit(jnp.argsort), vals_m))
  two = jax.jit(lambda k, v: jax.lax.sort([k, v], num_keys=1))
  rec('sortpair_768k_i32', timed(two, idx_m, vals_m))

  # -- misc hot-loop ops -----------------------------------------------
  rec('cumsum_768k', timed(jax.jit(lambda v: jnp.cumsum(v)), vals_m))
  rec('top_k_768k_k5',
      timed(jax.jit(lambda v: jax.lax.top_k(v, 5)[0]),
            vals_m.reshape(F, 5).astype(jnp.float32)))
  rec('uniform_15x153k',
      timed(jax.jit(lambda k: jax.random.uniform(k, (15, F))),
            jax.random.key(1)))

  # -- sort-engine internals at hop-2 widths ---------------------------
  from glt_tpu.ops.scan import cumsum_i32
  from glt_tpu.ops.unique import _fill_forward, sorted_hop_dedup
  ind_m = (vals_m & 1)
  rec('cumsum_i32_768k', timed(jax.jit(cumsum_i32), ind_m))
  cm = 186_000 + M     # seen-set + slots, the real dedup sort width
  hd = jnp.asarray((rng.random(cm) < 0.2))
  pay1 = jnp.asarray(rng.integers(0, 1 << 20, cm).astype(np.int32))
  pay2 = jnp.asarray(rng.integers(0, 1 << 20, cm).astype(np.int32))
  rec('fill_forward_954k_2pay',
      timed(jax.jit(lambda h, a, b: _fill_forward(h, a, b)), hd, pay1,
            pay2))
  u_ids = jnp.asarray(
      rng.choice(N, 186_000, replace=False).astype(np.int32))
  u_labs = jnp.arange(186_000, dtype=jnp.int32)
  ok_m = jnp.asarray(rng.random(M) < 0.9)
  rows_m = jnp.asarray(rng.integers(0, F, M).astype(np.int32))

  @jax.jit
  def dedup_full(uid, ula, ids, ok, rows):
    d = sorted_hop_dedup(uid, ula, jnp.asarray(186_000, jnp.int32), ids,
                         ok, rows)
    return (d['labels3'], d['rows3'], d['new_head3'], d['u_ids2'],
            d['count2'])

  rec('sorted_hop_dedup_h2',
      timed(dedup_full, u_ids, u_labs, idx_m, ok_m, rows_m))

  # -- windowed gather -------------------------------------------------
  # the weighted / full-neighborhood samplers read a [S, W] neighbor
  # window per seed; feature lookup reads [S, D] rows. XLA charges per
  # output element.
  W = 96
  starts_f = jnp.asarray(rng.integers(0, E - W, F).astype(np.int32))

  @jax.jit
  def xla_windows(a, st):
    win = jnp.arange(W, dtype=jnp.int32)[None, :]
    return jnp.take(a, st[:, None] + win, mode='clip')

  rec(f'window_gather_xla_{F//1000}kx{W}', timed(xla_windows, big,
                                                 starts_f))

  # -- PRNG implementation A/B (threefry default vs rbg) ---------------
  try:
    rbg_key = jax.random.key(1, impl='rbg')
    rec('uniform_15x153k_rbg',
        timed(jax.jit(lambda k: jax.random.uniform(k, (15, F))),
              rbg_key))
  except Exception as e:
    print(f'# rbg unavailable: {e}', file=sys.stderr)

  dev = jax.devices()[0]
  print(json.dumps({'metric': 'prim_ms', 'backend': dev.platform,
                    'shapes': {'N': N, 'E': E, 'M': M, 'F': F},
                    'ops': res}))


if __name__ == '__main__':
  main()
