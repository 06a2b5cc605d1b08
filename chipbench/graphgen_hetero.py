"""The typed benchmark's own data: a graph of several node and edge
types, per-type feature tables in bfloat16, labels and R-GAT weights, all
from ``--seed``. The typed counterpart of ``chipbench/graphgen.py``, whose
degree law it keeps per relation.

Every array has the same shape for every seed. A relation ``(s, r, d)``
of ``E`` edges is a CSR over the ``n_s`` source rows built directly,
ascending within each row: Pareto out-degrees (shape 4/3, capped, scaled
and topped up to ``E`` exactly), row ``i`` of degree ``k`` holding
``floor(n_d * u_j**2)`` for the stratified uniforms ``u_j = (j + r_j) /
k``. A relation whose configuration names ``reverse_of`` is the transpose
of that relation, edge for edge.

Features are a function of type and id, so that the reference can make
the rows it needs again without the tables: row ``i`` of type ``t`` is row
``perm_t[i mod M]`` of one seeded bfloat16 base table of ``M`` rows, with
column 0 overwritten by ``(i // M + 1) * STEP``, which tells the blocks
apart. Labels are a linear rule on columns 1 to 8 of the base row, binned
to equal shares.
"""
import ml_dtypes
import numpy as np

from chipbench.graphgen import THREADS, _in_chunks, _rng, jax_key

BASE_ROWS = 1 << 18
STEP = 0.03125
BF16 = ml_dtypes.bfloat16


def degrees(num_rows, num_edges, seed, stream):
  """[n] int64 out-degrees with a heavy tail, summing to ``num_edges``."""
  raw = (1.0 - _rng(seed, 10, stream).random(num_rows)) ** -0.75
  np.minimum(raw, 2000.0, out=raw)
  deg = np.floor(raw * (num_edges / raw.sum())).astype(np.int64)
  short = num_edges - int(deg.sum())
  deg[:short % num_rows] += 1
  deg += short // num_rows
  return deg


def relation_csr(num_src, num_dst, num_edges, seed, stream):
  """(indptr int64 [n_s + 1], indices int32 [E]) of one relation."""
  deg = degrees(num_src, num_edges, seed, stream)
  indptr = np.zeros(num_src + 1, np.int64)
  np.cumsum(deg, out=indptr[1:])
  assert indptr[-1] == num_edges, (indptr[-1], num_edges)
  indices = np.empty(num_edges, np.int32)

  def fill(c, lo, hi):
    d = deg[lo:hi]
    e0, e1 = indptr[lo], indptr[hi]
    k = np.arange(e1 - e0, dtype=np.float64)
    k -= np.repeat((indptr[lo:hi] - e0).astype(np.float64), d)
    k += _rng(seed, 11, stream, c).random(e1 - e0)
    k /= np.repeat(d.astype(np.float64), d)
    np.multiply(k, k, out=k)
    k *= num_dst
    indices[e0:e1] = np.minimum(k, num_dst - 1).astype(np.int32)

  _in_chunks(num_src, fill)
  return indptr, indices


def transpose(indptr, indices, num_dst):
  """The CSR of the reversed relation, rows ascending by the forward
  relation's source."""
  src = np.repeat(np.arange(indptr.shape[0] - 1, dtype=np.int32),
                  np.diff(indptr))
  order = np.argsort(indices, kind='stable')
  out = np.zeros(num_dst + 1, np.int64)
  np.cumsum(np.bincount(indices, minlength=num_dst), out=out[1:])
  return out, src[order]


def graph(cfg, seed):
  """{(s, r, d): (indptr, indices)} for every relation of ``cfg``."""
  nodes, out = cfg['num_nodes'], {}
  for k, rel in enumerate(cfg['relations']):
    if 'reverse_of' not in rel:
      out[rel['name']] = relation_csr(
          nodes[rel['src']], nodes[rel['dst']], rel['num_edges'], seed, k)
  by_name = {rel['name']: rel for rel in cfg['relations']}
  csr = {}
  for rel in cfg['relations']:
    key = (rel['src'], rel['name'], rel['dst'])
    if 'reverse_of' in rel:
      fwd = by_name[rel['reverse_of']]
      assert (fwd['src'], fwd['dst']) == (rel['dst'], rel['src']), rel
      csr[key] = transpose(*out[fwd['name']], nodes[rel['src']])
    else:
      csr[key] = out[rel['name']]
    assert csr[key][1].shape[0] == rel['num_edges'], rel
  return csr


class Features:
  """bfloat16 rows by type and id; ``table(t)`` is the whole [n_t, D]."""

  def __init__(self, num_nodes, dim, num_classes, seed):
    self.n, self.dim = dict(num_nodes), int(dim)
    base = np.empty((BASE_ROWS, dim), np.float32)

    def fill(c, lo, hi):
      _rng(seed, 12, c).standard_normal(out=base[lo:hi], dtype=np.float32)

    _in_chunks(BASE_ROWS, fill)
    self.base = base.astype(BF16)
    rng = _rng(seed, 13)
    self.perm = {t: rng.permutation(BASE_ROWS).astype(np.int32)
                 for t in sorted(self.n)}
    w = rng.standard_normal(8).astype(np.float32)
    z = base[:, 1:9] @ (w - w.mean())
    rank = np.empty(BASE_ROWS, np.int64)
    rank[np.argsort(z, kind='stable')] = np.arange(BASE_ROWS)
    self.base_label = (rank * num_classes // BASE_ROWS).astype(np.int32)

  def rows(self, t, ids):
    ids = np.asarray(ids, np.int64)
    out = self.base[self.perm[t][ids % BASE_ROWS]]
    out[:, 0] = ((ids // BASE_ROWS + 1) * STEP).astype(BF16)
    return out

  def labels(self, t, ids=None):
    ids = np.arange(self.n[t]) if ids is None else np.asarray(ids, np.int64)
    return self.base_label[self.perm[t][ids % BASE_ROWS]]

  def table(self, t):
    out = np.empty((self.n[t], self.dim), BF16)

    def fill(c, lo, hi):
      out[lo:hi] = self.rows(t, np.arange(lo, hi))

    _in_chunks(self.n[t], fill)
    return out


def weights(seed, relations, in_dim, hidden, heads, num_classes,
            num_layers):
  """R-GAT weights in the tree of ``RGNN(head=True)``:
  ``layer<i>/conv_<s>__<r>__<d>/{proj/kernel, att_src, att_dst}`` for the
  message-flow ``relations`` and ``head/{kernel, bias}``, made on the
  device in one jitted call, cut from ONE normal draw (a draw a leaf is a
  hundred unrolled Threefry programs, half a minute in the TPU's
  compiler): kernels with variance 1/fan_in, attention vectors with
  variance 1/F, the bias at a tenth."""
  import jax
  f = hidden // heads
  leaves = []          # (path, shape, scale)
  for i in range(num_layers):
    a = in_dim if i == 0 else hidden
    for etype in relations:
      at = (f'layer{i}', 'conv_' + '__'.join(etype))
      leaves += [(at + ('proj', 'kernel'), (a, hidden), a ** -0.5),
                 (at + ('att_src',), (heads, f), f ** -0.5),
                 (at + ('att_dst',), (heads, f), f ** -0.5)]
  leaves += [(('head', 'kernel'), (hidden, num_classes), hidden ** -0.5),
             (('head', 'bias'), (num_classes,), 0.1)]
  sizes = [int(np.prod(shape)) for _, shape, _ in leaves]

  @jax.jit
  def make(key):
    flat = jax.random.normal(key, (sum(sizes),))
    tree, lo = {}, 0
    for (path, shape, scale), n in zip(leaves, sizes):
      node = tree
      for k in path[:-1]:
        node = node.setdefault(k, {})
      node[path[-1]] = flat[lo:lo + n].reshape(shape) * scale
      lo += n
    return {'params': tree}

  return make(jax_key(seed, 0))
