"""Capped-bucket drain economics: rounds + wall time vs skew.

VERDICT r3 weak #3 / next #7: the bucket_cap overflow drain is
host-sequential — each extra round replays the full compiled collective
pass. This measures, on a P-device mesh (virtual CPU by default, the
same program on a real slice):

  * drain ROUNDS for bucket_cap = slack * ceil(B/P), slack in {1, 2, 4},
    under uniform and zipfian(a) request-id distributions — rounds are
    decided by the deterministic host replay, so they are exact, not
    sampled;
  * wall-clock per lookup for each (cap, distribution) vs the uncapped
    baseline, so the ICI-bytes saving can be weighed against the round
    cost on real hardware.

Output: one JSON line with the rounds/time grid + a recommended default.
Reference pattern being improved: graphlearn_torch dist_feature.py
270-366 (gloo all2all moves [P, B] unconditionally).

``--forms`` times, on ONE device and with no exchange, the map between
request order and bucket order alone: ``bucket`` (owners -> meta -> the
packed ``[P, cap]`` requests) and ``unbucket`` (a ``[P, cap, D]``
response -> ``[b, D]`` rows in request order), as the library has them
(``collectives.rank_by_owner`` / ``bucket_payload`` / ``unbucket``: a
request's slot is its owner and its rank in request order) beside the
form they replaced (a stable argsort by owner and the permutation's
gathers and scatter, kept here as ``_sorted_*``), and the candidate
running counts, packs and stitches one by one. Defaults are the
four-chip cell's shape: b 937,984, P 4, cap 234,496, a 37 % live prefix
whose ids follow the generator's floor(N u^2) law (shard 0 owns half),
D 128 float32. One JSON line; every candidate is held to the sorted
form's buckets and rows bit for bit before it is timed. Beside them, the
forms the four-chip exchange runs (``collectives.pack_live_chunks`` and
``stitch_live_chunks``: the pack's scatter and the stitch's gather over
the chunks of ``SERVE_CHUNK`` request slots that hold a request, the rows
written into a zero ``[b, D]``), held to the plain library forms bit for
bit (``live_*``).
"""
import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def drain_rounds(ids, n_shards, b, rows_per_shard, cap):
  """Exact round count via the deterministic host replay."""
  from glt_tpu.parallel.dist_feature import overflow_lanes
  owner = np.clip(ids // rows_per_shard, 0, n_shards - 1)
  pending = np.ones(ids.shape[0], bool)
  rounds = 0
  while True:
    rounds += 1
    over = overflow_lanes(np.where(pending, owner, n_shards),
                          n_shards, b, cap)
    if not over.any():
      return rounds
    pending = over


# -- the forms of the request-order <-> bucket-order map -------------------

def _sorted_meta(owner, n_shards):
  """The replaced form: a stable argsort by owner, the owners in sorted
  order and each one's place in its bucket."""
  import jax.numpy as jnp
  order = jnp.argsort(owner, stable=True)
  owner_sorted = jnp.take(owner, order)
  counts = jnp.bincount(jnp.minimum(owner_sorted, n_shards),
                        length=n_shards + 1)[:n_shards]
  offsets = jnp.cumsum(counts) - counts
  pos = jnp.arange(owner.shape[0]) - jnp.take(
      offsets, jnp.minimum(owner_sorted, n_shards - 1))
  return order, owner_sorted, pos


def _sorted_pack(ids, meta, n_shards, cap):
  import jax.numpy as jnp
  order, owner_sorted, pos = meta
  vals = jnp.take(ids, order)
  ok = (owner_sorted < n_shards) & (pos >= 0) & (pos < cap)
  buckets = jnp.full((n_shards + 1, cap), -1, ids.dtype)
  return buckets.at[jnp.where(ok, owner_sorted, n_shards),
                    jnp.where(ok, jnp.clip(pos, 0, cap - 1), 0)].set(
                        jnp.where(ok, vals, -1))[:n_shards]


def _sorted_stitch(resp, meta, n_shards):
  import jax.numpy as jnp
  order, owner_sorted, pos = meta
  cap = resp.shape[1]
  ok = (owner_sorted < n_shards) & (pos >= 0) & (pos < cap)
  got = resp[jnp.minimum(owner_sorted, n_shards - 1),
             jnp.clip(pos, 0, cap - 1)]
  got = jnp.where(ok[:, None], got, 0)
  return jnp.zeros_like(got).at[order].set(got)


def _rank_forms(n_shards):
  """name -> f(owner) -> (rank [b], counts [P]): the candidate running
  counts of ``owner == p`` in request order."""
  import jax
  import jax.numpy as jnp
  from glt_tpu.ops.scan import cumsum_i32
  from glt_tpu.parallel.collectives import rank_by_owner
  owners = jnp.arange(n_shards, dtype=jnp.int32)

  def xla_cumsum_loop(owner):
    rank, counts = jnp.zeros(owner.shape, jnp.int32), []
    for p in range(n_shards):
      hit = owner == p
      run = jnp.cumsum(hit.astype(jnp.int32))
      rank = jnp.where(hit, run - 1, rank)
      counts.append(run[-1])
    return rank, jnp.stack(counts)

  def blocked_vmap(owner):
    hit = owner[None, :] == owners[:, None]
    run = jax.vmap(cumsum_i32)(hit)
    return jnp.where(hit, run - 1, 0).sum(axis=0), run[:, -1]

  def xla_cumsum_columns(owner):
    hit = owner[:, None] == owners[None, :]
    run = jnp.cumsum(hit.astype(jnp.int32), axis=0)
    return jnp.where(hit, run - 1, 0).sum(axis=1), run[-1]

  return {
      # the library's: cumsum_i32 an owner, one after the other
      'blocked_loop': lambda owner: rank_by_owner(owner, n_shards)[1:],
      'xla_cumsum_loop': xla_cumsum_loop,
      'blocked_vmap': blocked_vmap,
      'xla_cumsum_columns': xla_cumsum_columns,
  }


def _in_first_round(owner, rank, n_shards, cap):
  return (owner < n_shards) & (rank < cap)


def _pack_forms(n_shards, cap):
  """name -> f(ids, owner, rank) -> [P, cap]: where the pack's scatter
  sends the slots that are not packed (pads, other rounds)."""
  import jax.numpy as jnp
  ok_of = lambda owner, rank: _in_first_round(owner, rank, n_shards, cap)

  def dump_one(ids, owner, rank):
    ok = ok_of(owner, rank)
    out = jnp.full((n_shards + 1, cap), -1, ids.dtype)
    return out.at[jnp.where(ok, owner, n_shards),
                  jnp.where(ok, rank, 0)].set(
                      jnp.where(ok, ids, -1))[:n_shards]

  def dump_spread(ids, owner, rank):
    ok = ok_of(owner, rank)
    out = jnp.full((n_shards + 1, cap), -1, ids.dtype)
    lane = jnp.arange(ids.shape[0], dtype=jnp.int32) % cap
    return out.at[jnp.where(ok, owner, n_shards),
                  jnp.where(ok, rank, lane)].set(
                      jnp.where(ok, ids, -1))[:n_shards]

  def flat_dump_one(ids, owner, rank):
    ok = ok_of(owner, rank)
    out = jnp.full(((n_shards + 1) * cap,), -1, ids.dtype)
    slot = jnp.where(ok, owner * cap + rank, n_shards * cap)
    return out.at[slot].set(jnp.where(ok, ids, -1))[
        :n_shards * cap].reshape(n_shards, cap)

  def flat_drop(ids, owner, rank):
    ok = ok_of(owner, rank)
    out = jnp.full((n_shards * cap,), -1, ids.dtype)
    slot = jnp.where(ok, owner * cap + rank, n_shards * cap)
    return out.at[slot].set(ids, mode='drop').reshape(n_shards, cap)

  def flat_drop_unique(ids, owner, rank):
    ok = ok_of(owner, rank)
    out = jnp.full((n_shards * cap,), -1, ids.dtype)
    slot = jnp.where(ok, owner * cap + rank,
                     n_shards * cap + jnp.arange(ids.shape[0]))
    return out.at[slot].set(ids, mode='drop',
                            unique_indices=True).reshape(n_shards, cap)

  return {'dump_one': dump_one, 'dump_spread': dump_spread,
          'flat_dump_one': flat_dump_one, 'flat_drop': flat_drop,
          'flat_drop_unique': flat_drop_unique}


def _stitch_forms(n_shards, cap):
  """name -> f(resp [P, cap, D], owner, rank) -> [b, D]: the one gather,
  by two indices or one, and which row a masked slot reads."""
  import jax.numpy as jnp
  ok_of = lambda owner, rank: _in_first_round(owner, rank, n_shards, cap)

  def two_index_zero(resp, owner, rank):
    ok = ok_of(owner, rank)
    got = resp[jnp.where(ok, owner, 0), jnp.where(ok, rank, 0)]
    return jnp.where(ok[:, None], got, 0)

  def two_index_spread(resp, owner, rank):
    ok = ok_of(owner, rank)
    lane = jnp.arange(owner.shape[0], dtype=jnp.int32)
    got = resp[jnp.where(ok, owner, lane // cap % n_shards),
               jnp.where(ok, rank, lane % cap)]
    return jnp.where(ok[:, None], got, 0)

  def flat(masked_slot):
    def f(resp, owner, rank):
      ok = ok_of(owner, rank)
      lane = jnp.arange(owner.shape[0], dtype=jnp.int32)
      slot = jnp.where(ok, owner * cap + rank, masked_slot(lane))
      got = jnp.take(resp.reshape(n_shards * cap, -1), slot, axis=0)
      return jnp.where(ok[:, None], got, 0)
    return f

  def flat_fill(resp, owner, rank):
    ok = ok_of(owner, rank)
    slot = jnp.where(ok, owner * cap + rank, n_shards * cap)
    return jnp.take(resp.reshape(n_shards * cap, -1), slot, axis=0,
                    mode='fill', fill_value=0)

  return {'two_index_zero': two_index_zero,
          'two_index_spread': two_index_spread,
          'flat_zero': flat(lambda lane: jnp.zeros_like(lane)),
          'flat_spread': flat(lambda lane: lane % (n_shards * cap)),
          'flat_fill': flat_fill}


def bench_forms(args):
  """Time the forms above on one device; one JSON line."""
  import jax
  import jax.numpy as jnp
  from glt_tpu.parallel import collectives, dist_feature

  p, b, d = args.shards, args.requests, args.dim
  cap = args.cap or min(-(-(-(-b // p)) // 128) * 128, b)
  live = int(round(b * args.live))
  n = 24_000_000
  rps = math.ceil(n / p)
  rng = np.random.default_rng(args.seed)
  ids = np.floor(n * rng.random(b) ** 2).astype(np.int32)
  valid = np.arange(b) < live
  owner = jnp.asarray(np.where(valid, np.clip(ids // rps, 0, p - 1), p)
                      .astype(np.int32))
  ids = jnp.asarray(ids)
  resp = jax.random.normal(jax.random.key(args.seed % (2 ** 31)),
                           (p, cap, d), jnp.float32)

  def ms(fn, *a):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*a))
    for _ in range(args.warmup):
      jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(args.iters):
      out = fn(*a)
    jax.block_until_ready(out)
    return out, round((time.perf_counter() - t0) / args.iters * 1e3, 3)

  def same(a, b_):
    return bool(jax.tree.all(jax.tree.map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b_)))

  out = {}
  # the two whole forms, stage by stage and together
  s_meta, out['sorted_meta_ms'] = ms(lambda o: _sorted_meta(o, p), owner)
  s_req, out['sorted_pack_ms'] = ms(
      lambda i, m: _sorted_pack(i, m, p, cap), ids, s_meta)
  s_rows, out['sorted_stitch_ms'] = ms(
      lambda r, m: _sorted_stitch(r, m, p), resp, s_meta)

  def sorted_both(i, o, r):
    m = _sorted_meta(o, p)
    return _sorted_pack(i, m, p, cap), _sorted_stitch(r, m, p)
  _, out['sorted_bucket_unbucket_ms'] = ms(sorted_both, ids, owner, resp)

  meta, out['ranked_meta_ms'] = ms(
      lambda o: collectives.rank_by_owner(o, p), owner)
  req, out['ranked_pack_ms'] = ms(
      lambda i, m: collectives.bucket_payload(i, m, p, fill_value=-1,
                                              capacity=cap), ids, meta)
  rows, out['ranked_stitch_ms'] = ms(
      lambda r, m: collectives.unbucket(r, m, p), resp, meta)

  def ranked_both(i, o, r):
    m = collectives.rank_by_owner(o, p)
    return (collectives.bucket_payload(i, m, p, fill_value=-1, capacity=cap),
            collectives.unbucket(r, m, p))
  _, out['ranked_bucket_unbucket_ms'] = ms(ranked_both, ids, owner, resp)
  out['ranked_equals_sorted'] = same((req, rows), (s_req, s_rows))

  # the exchange's forms: the live chunks alone, rows into a zero carry
  chunk = dist_feature.SERVE_CHUNK
  (live_req, packed), out['live_pack_ms'] = ms(
      lambda i, m: collectives.pack_live_chunks(
          i, m, p, cap, 0, chunk, fill_value=-1), ids, meta)
  (live_rows, stitched), out['live_stitch_ms'] = ms(
      lambda r, m: collectives.stitch_live_chunks(
          jnp.zeros((b, d), r.dtype), r, m, p, 0, chunk), resp, meta)

  def live_both(i, o, r):
    m = collectives.rank_by_owner(o, p)
    return (collectives.pack_live_chunks(i, m, p, cap, 0, chunk,
                                         fill_value=-1)[0],
            collectives.stitch_live_chunks(jnp.zeros((b, d), r.dtype), r,
                                           m, p, 0, chunk)[0])
  _, out['live_bucket_unbucket_ms'] = ms(live_both, ids, owner, resp)
  out['live_equals_ranked'] = same(
      (live_req, np.asarray(live_rows).view(np.uint32)),
      (req, np.asarray(rows).view(np.uint32)))
  out['live_chunks'] = {'pack': int(packed), 'stitch': int(stitched),
                        'of': dist_feature.serve_chunks(b)}

  # the candidates, one stage at a time, each held to the sorted form
  want_rank = np.asarray(meta.rank)
  live_np = np.asarray(owner) < p
  for name, f in _rank_forms(p).items():
    (rank, counts), t = ms(f, owner)
    good = (np.asarray(rank)[live_np] == want_rank[live_np]).all() and \
        same(counts, meta.counts)
    out[f'rank.{name}_ms'] = t if good else 'wrong'
  for name, f in _pack_forms(p, cap).items():
    got, t = ms(f, ids, owner, meta.rank)
    out[f'pack.{name}_ms'] = t if same(got, s_req) else 'wrong'
  for name, f in _stitch_forms(p, cap).items():
    got, t = ms(f, resp, owner, meta.rank)
    out[f'stitch.{name}_ms'] = t if same(got, s_rows) else 'wrong'

  dev = jax.devices()[0]
  print(json.dumps({
      'metric': 'bucket_unbucket_forms',
      'value': out['ranked_bucket_unbucket_ms'],
      'unit': 'ms',
      'vs_baseline': out['sorted_bucket_unbucket_ms'],
      'detail': dict(out, shards=p, requests=b, cap=cap, live=live, dim=d,
                     iters=args.iters, backend=dev.platform,
                     device_kind=dev.device_kind),
  }))


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--forms', action='store_true',
                  help='time bucket + unbucket alone, sorted against '
                       'ranked, and the candidate forms (one device)')
  ap.add_argument('--shards', type=int, default=4)
  ap.add_argument('--requests', type=int, default=937_984)
  ap.add_argument('--cap', type=int, default=0,
                  help='bucket slots; 0 = ceil(requests / shards) '
                       'rounded up to 128')
  ap.add_argument('--live', type=float, default=0.372,
                  help='share of the request slots that are live (a '
                       'prefix)')
  ap.add_argument('--seed', type=int, default=0)
  ap.add_argument('--num-devices', type=int, default=8)
  ap.add_argument('--rows', type=int, default=1_000_000)
  ap.add_argument('--dim', type=int, default=128)
  ap.add_argument('--batch', type=int, default=4096,
                  help='request ids per device')
  ap.add_argument('--iters', type=int, default=20)
  ap.add_argument('--warmup', type=int, default=3)
  ap.add_argument('--cpu-mesh', action='store_true',
                  default=os.environ.get('GLT_BENCH_PLATFORM') == 'cpu')
  args = ap.parse_args()

  if args.forms:
    from glt_tpu.utils.backend import (configure_compile_cache,
                                       force_backend)
    if args.cpu_mesh:
      force_backend('cpu')
    configure_compile_cache()
    return bench_forms(args)

  if args.cpu_mesh:
    os.environ['XLA_FLAGS'] = (
        os.environ.get('XLA_FLAGS', '') +
        f' --xla_force_host_platform_device_count={args.num_devices}')
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  if args.cpu_mesh:
    force_backend('cpu')
  configure_compile_cache()
  import jax.numpy as jnp
  from glt_tpu.parallel import make_mesh
  from glt_tpu.parallel.dist_feature import ShardedFeature

  p = min(args.num_devices, len(jax.devices()))
  mesh = make_mesh(p)
  b = args.batch
  n = args.rows
  rps = math.ceil(n / p)
  feats = np.random.default_rng(0).normal(
      size=(n, args.dim)).astype(np.float32)

  rng = np.random.default_rng(1)
  dists = {
      'uniform': rng.integers(0, n, p * b),
      # zipf over rows: heavy head -> every device asks the head's
      # owner shard for most of its batch (the skew the cap fears)
      'zipf_1.2': (rng.zipf(1.2, p * b) - 1) % n,
      'zipf_2.0': (rng.zipf(2.0, p * b) - 1) % n,
      'hot_spot': np.zeros(p * b, np.int64),  # all-ask-one worst case
  }

  base_cap = math.ceil(b / p)
  grid = {}
  stores = {}

  def timed_lookup(store, ids):
    for _ in range(args.warmup):
      jax.block_until_ready(store.lookup(ids))
    t0 = time.time()
    for _ in range(args.iters):
      jax.block_until_ready(store.lookup(ids))
    return (time.time() - t0) / args.iters * 1e3  # ms

  uncapped = ShardedFeature(feats, mesh)
  for name, ids in dists.items():
    ids = ids.astype(np.int64)
    row = {'uncapped_ms': round(timed_lookup(uncapped, ids), 2)}
    for slack in (1, 2, 4):
      cap = slack * base_cap
      if cap not in stores:
        stores[cap] = ShardedFeature(feats, mesh, bucket_cap=cap)
      rounds = drain_rounds(ids, p, b, rps, cap)
      row[f'slack{slack}'] = {
          'cap': cap,
          'rounds': rounds,
          'ms': round(timed_lookup(stores[cap], ids), 2),
          # bytes each device puts on the wire per round vs uncapped:
          # request ids [P, C] + responses [P, C, D] vs [P, B](+[P,B,D])
          'ici_fraction': round(cap / b, 4),
      }
    grid[name] = row

  # recommendation: smallest slack whose rounds stay 1 on uniform AND
  # <= 3 under zipf_1.2 (real graph id streams are zipf-ish after
  # degree sort); hot_spot is the adversarial bound, not the default
  rec = None
  for slack in (1, 2, 4):
    if (grid['uniform'][f'slack{slack}']['rounds'] == 1
        and grid['zipf_1.2'][f'slack{slack}']['rounds'] <= 3):
      rec = slack
      break
  dev = jax.devices()[0]
  print(json.dumps({
      'metric': 'bucket_cap_drain_grid',
      'value': rec if rec is not None else 0,
      'unit': 'recommended_slack',
      'vs_baseline': None,
      'detail': {'devices': p, 'batch_per_device': b,
                 'base_cap': base_cap, 'grid': grid,
                 'backend': dev.platform},
  }))


if __name__ == '__main__':
  main()
