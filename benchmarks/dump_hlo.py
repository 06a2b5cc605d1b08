"""Dump the optimized HLO of the composed sampling step and print the
bodies of the named fusions (default: the top ops from the device
trace, profile_ops_tpu.py) so the hot fusion can be attributed to
source ops. Host-side only — uses the persistent compile cache, cheap
once the profile run has compiled the program.

Usage: python benchmarks/dump_hlo.py fusion.434 fusion.440 [...]
Writes the full text to benchmarks/tpu_runs/sample_batch_opt.hlo.
"""
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main():
  names = [a for a in sys.argv[1:] if not a.startswith('-')] or \
      ['fusion.434', 'fusion.440', 'fusion.417']
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  force_backend()
  configure_compile_cache()
  import jax.numpy as jnp
  from glt_tpu.data import Topology
  from glt_tpu.ops.pipeline import (make_dedup_tables,
                                    multihop_sample_many,
                                    checksum_outputs)
  from glt_tpu.ops.sample import sample_neighbors
  from glt_tpu.utils.rng import make_key

  NUM_NODES = 2_450_000
  NUM_EDGES = 62_000_000
  BATCH, FANOUT, SCAN = 1024, (15, 10, 5), 4

  # tiny graph is fine for lowering; shapes of indptr/indices must match
  # the profiled program, so build the same-size arrays cheaply
  indptr = jnp.zeros((NUM_NODES + 1,), jnp.int32)
  indices = jnp.zeros((NUM_EDGES,), jnp.int32)
  one_hop = lambda ids, fanout, key, mask: sample_neighbors(
      indptr, indices, ids, fanout, key, seed_mask=mask)

  def sample_batch(seeds, key, table, scratch):
    outs, table, scratch = multihop_sample_many(
        one_hop, seeds, jnp.full(SCAN, BATCH, jnp.int32), FANOUT,
        key, table, scratch)
    return (outs['num_sampled_edges'].sum(), checksum_outputs(outs),
            table, scratch)

  table, scratch = make_dedup_tables(NUM_NODES)
  seeds = jnp.zeros((SCAN, BATCH), jnp.int32)
  lowered = jax.jit(sample_batch, donate_argnums=(2, 3)).lower(
      seeds, make_key(0), table, scratch)
  compiled = lowered.compile()
  txt = compiled.as_text()
  out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          'tpu_runs', 'sample_batch_opt.hlo')
  with open(out_path, 'w') as f:
    f.write(txt)
  print(f'# wrote {out_path} ({len(txt)} bytes)', file=sys.stderr)

  # print each requested fusion's computation body
  for name in names:
    # the fusion instruction line names its called computation
    m = re.search(rf'%?{re.escape(name)} = .*', txt)
    if not m:
      print(f'== {name}: NOT FOUND')
      continue
    line = m.group(0)
    print(f'== {name} instruction:\n{line[:2000]}\n')
    cm = re.search(r'calls=([%\w.\-]+)', line)
    if cm:
      comp = cm.group(1).lstrip('%')
      bm = re.search(
          rf'^(%?{re.escape(comp)}\b.*?^}})', txt,
          re.M | re.S)
      if bm:
        body = bm.group(1)
        print(f'-- body of {comp} ({len(body)} bytes):')
        print(body[:8000])
        print()


if __name__ == '__main__':
  main()
