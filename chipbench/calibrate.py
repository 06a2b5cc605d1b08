"""Reads the numbers that ``correct`` compares, at a cell's own size, on
many seeds in one process: the program against the reference (the lower
reading of each limit), and the reference put in the program's place,
computed in bfloat16 (the control) or with a fault planted (the upper
readings). The graph is built once; every seed brings its own weights,
batches and keys. ``PERF.md`` holds what it printed and the limits set
from it. Not part of a benchmark run.

  python3 chipbench/calibrate.py --workload <cell> --seeds 12 --controls 3
"""
import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAULTS = ('half_batch', 'no_exchange')


def read(state, driver, seed, controls):
  """{'program': gaps, 'bf16': gaps, <fault>: gaps} for one seed."""
  import jax.numpy as jnp
  from chipbench import reference
  driver.start(state, seed)
  s = state
  follow = lambda **kw: reference.follow(
      s.indptr, s.indices, s.feats, s.params0, lambda t: driver.feed(s, t),
      s.traffic['warmup_steps'], s.chips, s.fanout,
      s.cfg['learning_rate'], rows_per_shard=s.rows_per_shard, **kw)
  ref = follow()
  out = {'program': reference.compare(s.program, ref),
         'replica_gap': s.replicas}
  if controls:
    out['bf16'] = reference.compare(follow(dtype=jnp.bfloat16), ref)
    for fault in FAULTS:
      if fault == 'no_exchange' and s.chips == 1:
        continue
      out[fault] = reference.compare(follow(fault=fault), ref)
  return out


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seeds', type=int, default=12)
  ap.add_argument('--controls', type=int, default=3)
  ap.add_argument('--first-seed', type=int, default=2_500_000_001)
  args = ap.parse_args(argv)
  from chipbench import run
  _, cell, cfg, traffic = run.load_cell(args.workload)
  run.require_chips(cell['chips'])
  run.place_compile_cache()
  driver = importlib.import_module('chipbench.drivers.' + traffic['driver'])
  state = driver.build(cfg, traffic, cell['chips'], args.first_seed)
  for i in range(args.seeds):
    seed = args.first_seed + 7919 * i
    out = read(state, driver, seed, i < args.controls)
    print(json.dumps({'seed': seed, **out}), flush=True)


if __name__ == '__main__':
  main()
