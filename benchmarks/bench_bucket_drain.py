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


def main():
  ap = argparse.ArgumentParser()
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
