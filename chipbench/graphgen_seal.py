"""The SEAL cell's graph: ``chipbench/graphgen.py``'s directed CSR read
undirected, as SEAL_OGB reads ogbl-citation2 (``to_undirected``): every
edge in both directions, coalesced (a pair of nodes is one edge however
many times and in whichever directions the generator drew it), self-loops
dropped (an enclosing subgraph has none, and the model adds its own).
Rows stay ascending. All of it is set-up on the host, on threads over
ranges of rows; nothing here runs in a step.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import graphgen

TILE = 128   # ``indices`` is padded to whole tiles of the extraction's read


def symmetric_csr(indptr, indices, num_nodes, chunks=8 * graphgen.THREADS):
  """(indptr int64 [N+1], indices int32 padded with -1, E2): the
  undirected, coalesced, loop-free CSR of the directed ``(indptr,
  indices)``. ``indices`` is as long as E2 can be, two slots a directed
  edge, in whole tiles of ``TILE``: E2 moves with the seed by the pairs
  drawn twice (a few thousand of 232.8 M), and an array as long as E2
  gave the step another shape, so another program to compile, a seed."""
  n = int(num_nodes)
  num_edges = int(indptr[-1])
  rev = np.empty(num_edges, np.int64)   # dst * n + src, then sorted
  bounds = np.linspace(0, n, chunks + 1).astype(np.int64)

  def fill(c):
    lo, hi = bounds[c], bounds[c + 1]
    e0, e1 = indptr[lo], indptr[hi]
    src = np.repeat(np.arange(lo, hi, dtype=np.int64),
                    np.diff(indptr[lo:hi + 1]))
    np.multiply(indices[e0:e1], n, out=rev[e0:e1], dtype=np.int64)
    rev[e0:e1] += src

  with ThreadPoolExecutor(graphgen.THREADS) as pool:
    list(pool.map(fill, range(chunks)))
    rev.sort()
    # rows of equal shares of the reverse keys, so that the hubs' rows
    # (low ids) do not all fall to one thread
    cuts = np.unique(rev[np.linspace(0, num_edges - 1, chunks + 1)
                         .astype(np.int64)[1:-1]] // n)
    cuts = np.concatenate([[0], cuts, [n]]).astype(np.int64)
    rev_at = np.searchsorted(rev, cuts * n)

    def merge(c):
      lo, hi = cuts[c], cuts[c + 1]
      e0, e1 = indptr[lo], indptr[hi]
      fwd = np.repeat(np.arange(lo, hi, dtype=np.int64),
                      np.diff(indptr[lo:hi + 1]))
      fwd *= n
      fwd += indices[e0:e1]
      keys = np.concatenate([fwd, rev[rev_at[c]:rev_at[c + 1]]])
      keys.sort()
      row = keys // n
      col = keys - row * n
      keep = row != col
      keep[1:] &= keys[1:] != keys[:-1]
      return (np.bincount(row[keep] - lo, minlength=hi - lo),
              col[keep].astype(np.int32))

    parts = list(pool.map(merge, range(len(cuts) - 1)))
  out_ptr = np.zeros(n + 1, np.int64)
  np.cumsum(np.concatenate([p[0] for p in parts]), out=out_ptr[1:])
  e2 = int(out_ptr[-1])
  out = np.full(-(-2 * num_edges // TILE) * TILE, -1, np.int32)
  at = 0
  for _, cols in parts:
    out[at:at + cols.shape[0]] = cols
    at += cols.shape[0]
  return out_ptr, out, e2
