"""Reads the numbers that the SEAL cell's ``correct`` compares, at the
cell's own size, on many seeds in one process: the program against the
reference (the lower reading of each limit), and the reference put in the
program's place, computed in bfloat16 (the control) or with a fault
planted (the upper readings: ``half_batch``, and ``no_labels`` = every
``z`` 1, which only a comparison that sees DRNL can fail). The graph is
built once; every seed brings its own weights, pairs and keys. ``PERF.md``
holds what it printed and the limits set from it. Not part of a benchmark
run.

  python3 chipbench/calibrate_seal.py --workload <cell> --seeds 6 --controls 3
"""
import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAULTS = ('half_batch', 'no_labels')


def read(state, driver, seed, controls):
  """{'program': gaps, 'bf16': gaps, <fault>: gaps} for one seed, each
  against the reference at the cell's stated precision; under ``highest``
  the program and the control once more against the reference whose
  matmuls round nothing; under ``counted`` what the first warm-up step
  counted."""
  import jax.numpy as jnp
  from chipbench import reference_seal
  driver.start(state, seed)
  s = state
  follow = lambda **kw: reference_seal.follow(
      s.indptr, s.indices, s.feats.rows, s.params0, driver.batches(s),
      s.cfg['learning_rate'], s.cfg['sortpool_k'], s.cfg['max_z'], **kw)
  operands = driver.stated_operands(s.cfg)
  ref = follow(operands=operands)
  out = {'program': reference_seal.compare(s.program, ref),
         'loss': s.program['loss'],
         'counted': {k: v.tolist() for k, v in s.counted[0].items()
                     if v.size <= 2}}
  if controls:
    bf16 = follow(dtype=jnp.bfloat16)
    out['bf16'] = reference_seal.compare(bf16, ref)
    for fault in FAULTS:
      out[fault] = reference_seal.compare(
          follow(operands=operands, fault=fault), ref)
    if operands is not None:
      plain = follow()
      out['highest'] = {'program': reference_seal.compare(s.program, plain),
                        'bf16': reference_seal.compare(bf16, plain)}
  return out


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seeds', type=int, default=6)
  ap.add_argument('--controls', type=int, default=3)
  ap.add_argument('--first-seed', type=int, default=4_000_000_001)
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
