"""The benchmark's own data: graph, features, labels and weights, all
from ``--seed``.

Every array has the same shape for every seed (the edge count is fixed
by the configuration, not drawn), so one compiled program serves all
seeds. The CSR is built directly, ascending within each row, with no
sort: row ``i`` of degree ``d`` holds ``floor(N * u_k**2)`` for the
stratified uniforms ``u_k = (k + r_k) / d``, which rise with ``k``. The
square is the mild power law of ``examples/common.py::synthetic_products``
(low ids are popular); two neighbouring strata can land on one id, so a
row may hold a repeated neighbour (a multi-edge), rarely.

Features are a function of the node id, so that the reference can make
the rows it needs again without the table: row ``i`` is row
``perm[i mod M]`` of a seeded base table of ``M`` rows, plus a scalar
``(i // M + 1) * STEP`` on every column, which makes all rows distinct.
Labels are a linear rule on the first eight columns, binned to equal
shares; the rule's weights sum to nought, so the scalar does not move it.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from glt_tpu.data.topology import Topology

BASE_ROWS = 1 << 20
STEP = 0.03125
THREADS = 8


class SortedCSR(Topology):
  """A ``Topology`` taken as given: the CSR is already ascending within
  rows, so ``Topology.__init__``'s lexsort over every edge is skipped,
  and no identity ``edge_ids`` are made (``Graph.lazy_init`` would upload
  them). The one place the benchmark goes round a constructor."""

  def __init__(self, indptr, indices, num_nodes):
    self.layout = 'CSR'
    self._index_dtype = np.int32
    self.indptr = indptr
    self.indices = indices
    self.num_rows = self.num_cols = int(num_nodes)
    self.edge_ids = None
    self.edge_weights = None


def _rng(seed, *stream):
  return np.random.default_rng([int(seed), *stream])


def _in_chunks(n, fill):
  """``fill(c, lo, hi)`` over 8 * THREADS ranges of [0, n), on threads:
  numpy releases the lock inside its loops."""
  bounds = np.linspace(0, n, 8 * THREADS + 1).astype(np.int64)
  with ThreadPoolExecutor(THREADS) as pool:
    list(pool.map(lambda c: fill(c, bounds[c], bounds[c + 1]),
                  range(len(bounds) - 1)))


def degrees(num_nodes, num_edges, seed):
  """[N] int64 out-degrees with a heavy tail (Pareto, shape 4/3), scaled
  and topped up so that they sum to ``num_edges`` exactly."""
  raw = (1.0 - _rng(seed, 1).random(num_nodes)) ** -0.75
  np.minimum(raw, 2000.0, out=raw)
  deg = np.floor(raw * (num_edges / raw.sum())).astype(np.int64)
  deg[:num_edges - int(deg.sum())] += 1
  return deg


def csr(num_nodes, num_edges, seed):
  """(indptr int64 [N+1], indices int32 [E])."""
  deg = degrees(num_nodes, num_edges, seed)
  indptr = np.zeros(num_nodes + 1, np.int64)
  np.cumsum(deg, out=indptr[1:])
  assert indptr[-1] == num_edges, (indptr[-1], num_edges)
  indices = np.empty(num_edges, np.int32)

  def fill(c, lo, hi):
    d = deg[lo:hi]
    e0, e1 = indptr[lo], indptr[hi]
    k = np.arange(e1 - e0, dtype=np.float64)
    k -= np.repeat((indptr[lo:hi] - e0).astype(np.float64), d)
    k += _rng(seed, 2, c).random(e1 - e0)
    k /= np.repeat(d.astype(np.float64), d)
    np.multiply(k, k, out=k)
    k *= num_nodes
    indices[e0:e1] = np.minimum(k, num_nodes - 1).astype(np.int32)

  _in_chunks(num_nodes, fill)
  return indptr, indices


class Features:
  """Rows by id; ``table()`` is the whole [N, D] float32 array."""

  def __init__(self, num_nodes, dim, num_classes, seed):
    self.n, self.dim = int(num_nodes), int(dim)
    rng = _rng(seed, 3)
    self.base = rng.standard_normal((BASE_ROWS, dim), dtype=np.float32)
    self.perm = rng.permutation(BASE_ROWS).astype(np.int32)
    w = rng.standard_normal(8).astype(np.float32)
    z = self.base[:, :8] @ (w - w.mean())
    rank = np.empty(BASE_ROWS, np.int64)
    rank[np.argsort(z, kind='stable')] = np.arange(BASE_ROWS)
    self.base_label = (rank * num_classes // BASE_ROWS).astype(np.int32)

  def rows(self, ids):
    ids = np.asarray(ids, np.int64)
    out = self.base[self.perm[ids % BASE_ROWS]]
    out += ((ids // BASE_ROWS + 1) * STEP).astype(np.float32)[:, None]
    return out

  def labels(self, ids=None):
    ids = np.arange(self.n) if ids is None else np.asarray(ids, np.int64)
    return self.base_label[self.perm[ids % BASE_ROWS]]

  def table(self):
    out = np.empty((self.n, self.dim), np.float32)

    def fill(c, lo, hi):
      out[lo:hi] = self.rows(np.arange(lo, hi))

    _in_chunks(self.n, fill)
    return out


def weights(seed, in_dim, hidden, num_classes, num_layers):
  """GraphSAGE weights in flax's tree (``conv<i>/lin_root|lin_nbr``),
  made on the device in one jitted call: kernels normal with variance
  1/fan_in, biases normal at a tenth."""
  import jax
  dims = [in_dim] + [hidden] * (num_layers - 1) + [num_classes]

  @jax.jit
  def make(key):
    tree = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
      kr, kn, kb = jax.random.split(jax.random.fold_in(key, i), 3)
      scale = 1.0 / np.sqrt(a)
      tree[f'conv{i}'] = {
          'lin_root': {'kernel': jax.random.normal(kr, (a, b)) * scale,
                       'bias': jax.random.normal(kb, (b,)) * 0.1},
          'lin_nbr': {'kernel': jax.random.normal(kn, (a, b)) * scale}}
    return {'params': tree}

  return make(jax_key(seed, 0))


def jax_key(seed, stream):
  """A typed PRNG key from a seed of any size and a stream number."""
  import jax
  seed = int(seed)
  key = jax.random.fold_in(jax.random.key(seed & 0x7fffffff), seed >> 31)
  return jax.random.fold_in(key, stream)
