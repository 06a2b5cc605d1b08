"""Sizes the SEAL cell's static numbers from its own traffic on its own
graph, on the host with numpy (no device): ``sortpool_k`` by SEAL_OGB's
rule (the ``ceil(0.6 n)``-th smallest node count of the links, at least
10) and, for the extraction's budgets, what a link needs of each
(``glt_tpu.ops.subgraph.EncloseSpec``: tiles of the members at most
``hub_width`` wide, members past it, pairs of unread members a batch).
The configuration's file holds what it printed and the command.

  python3 chipbench/seal_sizes.py --workload seal-papers100m-c1.fused --seed 40 --links 1000
"""
import argparse
import json
import math
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def link_nodes(indptr, indices, s, d, fanout, rng):
  """The node set of link ``(s, d)``: both ends, then up to ``fanout``
  neighbours of each (the whole row where it fits, else a uniform sample
  without replacement), first occurrences."""
  out = [s, d]
  for e in (s, d):
    row = indices[indptr[e]:indptr[e + 1]]
    out += list(row if row.shape[0] <= fanout
                else rng.choice(row, fanout, replace=False))
  return np.array(list(dict.fromkeys(out)), np.int64)


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seed', type=int, default=40)
  ap.add_argument('--links', type=int, default=1000)
  args = ap.parse_args(argv)
  from chipbench import graphgen, graphgen_seal, run
  from chipbench.drivers.link_fused import positive_edges
  from chipbench.drivers.seal_fused import tile_rule
  _, _, cfg, traffic = run.load_cell(args.workload)
  n, fanout = cfg['num_nodes'], traffic['fanout'][0]
  spec, batch = traffic['enclose'], traffic['batch_per_chip']
  d_ptr, d_idx = graphgen.csr(n, cfg['num_edges'], args.seed)
  indptr, indices, num_edges = graphgen_seal.symmetric_csr(d_ptr, d_idx, n)
  rng = np.random.default_rng([args.seed, 40])
  half = args.links // 2
  pairs = np.concatenate([positive_edges(d_ptr, d_idx, rng, half),
                          rng.integers(0, n, (half, 2))])
  deg = np.diff(indptr)
  sizes, tiles, unread = [], [], []
  for s, d in pairs:
    nodes = link_nodes(indptr, indices, s, d, fanout, rng)
    sizes.append(nodes.shape[0])
    # what the members at most hub_width wide would span, any budget
    tiles.append(int(tile_rule(indptr, nodes[None], spec['hub_width'],
                               1 << 30)[0][0]))
    unread.append(int(tile_rule(indptr, nodes[None], spec['hub_width'],
                                spec['tile_budget'])[1].sum()))
  sizes, tiles, unread = map(np.asarray, (sizes, tiles, unread))
  k = max(10, int(np.sort(sizes)[math.ceil(0.6 * sizes.size) - 1]))
  pairs_a_link = unread * (unread - 1) // 2
  pct = lambda a: [float(x) for x in np.percentile(a, [50, 90, 99, 100])]
  print(json.dumps({
      'seed': args.seed, 'links': int(sizes.size), 'sortpool_k': k,
      'symmetric_edges': int(num_edges),
      'max_degree': int(deg.max()),
      'nodes_a_link_mean_positive_negative': [
          float(sizes[:half].mean()), float(sizes[half:].mean())],
      'nodes_a_link_p50_p90_p99_max': pct(sizes),
      'tiles_a_link_p50_p90_p99_max': pct(tiles),
      'unread_members_a_link_p50_p90_p99_max': pct(unread),
      'hub_pairs_a_batch_mean': float(pairs_a_link.mean() * 2 * batch),
      'hub_pairs_a_link_max': int(pairs_a_link.max())}))


if __name__ == '__main__':
  main()
