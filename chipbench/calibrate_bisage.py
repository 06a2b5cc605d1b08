"""Reads the numbers that ``correct`` compares in the user-item cell, on
several seeds in one process: the program against the reference at the
precision the configuration states (the lower reading of each limit), and
the reference put in the program's place on the same batches, computed in
bfloat16 (the control) or with a fault planted (the upper readings:
``half_batch``, and ``lazy_update`` = Adam on a table's touched rows
only). ``chipbench/calibrate_hgt.py``'s order: the reference needs the
memory that the trainer's state holds, so every seed is started first (its
own weights, pairs and keys on the one graph), then the trainer is freed
and the references follow. Under ``highest`` the program and the control
once more against the reference whose matmuls round nothing. ``PERF.md``
holds what it printed and the limits set from it. Not part of a benchmark
run.

  python3 chipbench/calibrate_bisage.py --workload bisage-taobao-c1.fused --seeds 4
"""
import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KEPT = ('seed', 'program', 'sampled', 'counted', 'watch')


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seeds', type=int, default=4)
  ap.add_argument('--controls', type=int, default=2)
  ap.add_argument('--first-seed', type=int, default=3_800_000_001)
  args = ap.parse_args(argv)
  import jax.numpy as jnp
  from chipbench import reference_bisage, run
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
    started.append({k: getattr(s, k) for k in KEPT})
  s.trainer = s.params = s.opt = None
  gc.collect()
  import jax
  import numpy as np
  compare = reference_bisage.compare
  # a side's readings hold two trees the tables' size: they wait on the
  # host, and ``compare`` brings them to the device a leaf at a time
  host = lambda readings: jax.tree.map(np.asarray, readings)
  for i, kept in enumerate(started):
    vars(s).update(kept)
    program = host(driver.program_readings(s))
    ref = host(driver.follow(s))
    out = {'seed': s.seed, 'program': compare(program, ref),
           'worst_leaf': driver.worst_leaf(program, ref)}
    if i < args.controls:
      bf16 = host(driver.follow(s, dtype=jnp.bfloat16, operands=None))
      out['bf16'] = compare(bf16, ref)
      for fault in reference_bisage.FAULTS:
        out[fault] = compare(driver.follow(s, fault=fault), ref)
      if driver.stated_operands(cfg) is not None:
        plain = driver.follow(s, operands=None)
        out['highest'] = {'program': compare(program, plain),
                          'bf16': compare(bf16, plain)}
        del plain
    print(json.dumps(out), flush=True)
    del program, ref


if __name__ == '__main__':
  main()
