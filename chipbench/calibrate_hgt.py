"""Reads the numbers that ``correct`` compares in the HGT cell, on several
seeds in one process: the program against the reference at the precision
the configuration states (the lower reading of each limit), and the
reference put in the program's place on the same batches, computed in
bfloat16 (the control) or with a fault planted (the upper readings:
``half_batch``, and ``per_relation_softmax`` = every relation normalised
alone, R-GAT's way). ``chipbench/calibrate_rgat.py``'s order: the
reference needs the memory that the table holds, so every seed is started
first (its own weights, batches and keys on the one graph), then the
trainer is freed and the references follow. Under ``highest`` the program
and the control once more against the reference whose matmuls round
nothing. ``PERF.md`` holds what it printed and the limits set from it.
Not part of a benchmark run.

  python3 chipbench/calibrate_hgt.py --workload hgt-igbh-c1.fused --seeds 4
"""
import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seeds', type=int, default=4)
  ap.add_argument('--controls', type=int, default=2)
  ap.add_argument('--first-seed', type=int, default=3_300_000_001)
  args = ap.parse_args(argv)
  import jax.numpy as jnp
  from chipbench import reference_hgt, run
  _, cell, cfg, traffic = run.load_cell(args.workload)
  run.require_chips(cell['chips'])
  run.place_compile_cache()
  driver = importlib.import_module('chipbench.drivers.' + traffic['driver'])
  s = driver.build(cfg, traffic, cell['chips'], args.first_seed)
  started = []
  for i in range(args.seeds):
    seed = args.first_seed + 7919 * i
    if i:
      driver.start(s, seed)
    started.append((seed, s.program, s.params0, s.sampled))
  s.trainer = s.params = s.opt = None
  gc.collect()
  for i, (seed, program, s.params0, s.sampled) in enumerate(started):
    ref = driver.follow(s)
    out = {'seed': seed, 'program': reference_hgt.compare(program, ref)}
    if i < args.controls:
      bf16 = driver.follow(s, dtype=jnp.bfloat16, operands=None)
      out['bf16'] = reference_hgt.compare(bf16, ref)
      for fault in reference_hgt.FAULTS:
        out[fault] = reference_hgt.compare(driver.follow(s, fault=fault),
                                           ref)
      if driver.stated_operands(cfg) is not None:
        plain = driver.follow(s, operands=None)
        out['highest'] = {'program': reference_hgt.compare(program, plain),
                          'bf16': reference_hgt.compare(bf16, plain)}
    print(json.dumps(out), flush=True)


if __name__ == '__main__':
  main()
