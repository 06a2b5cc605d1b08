"""The plain reference: neighbour sampling with dedup in numpy, GraphSAGE
forward, loss and gradient in ``jax.numpy`` float32 at ``highest``
matmul precision, Adam by hand. Written from the equations of
``ops/sample.py``, ``ops/unique.py``, ``models/conv.py`` and optax's
``adam``; it imports nothing of ``glt_tpu`` and takes nothing the program
made (``chipbench.flops`` gives it the padded sizes). ``compare`` decides
``correct`` from the readings of both sides.

Sampling follows the program's random stream, so that both sides train
on the same sample: the step's key for a chip is folded with the chip's
index and split once per hop; a hop draws ``uniform(sub, (fanout, S))``
and picks ``fanout`` distinct offsets by Floyd's method where the degree
is larger, else the whole row. A node is expanded once, in the hop after
the one that first reached it, from the first slot that held it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops

B1, B2, EPS = 0.9, 0.999, 1e-8


def sample(indptr, indices, seeds, key, fanout):
  """(nodes [n] unique global ids, seeds first; child, parent [e] indices
  into ``nodes``), for one chip's batch of distinct seeds."""
  ids = np.asarray(seeds, np.int64)
  mask = np.ones(ids.shape[0], bool)
  nodes, children, parents = [ids], [], []
  seen = np.sort(ids)
  for k in fanout:
    key, sub = jax.random.split(key)
    u = np.asarray(jax.random.uniform(sub, (k, ids.shape[0])))
    start = indptr[ids]
    deg = np.where(mask, indptr[ids + 1] - start, 0).astype(np.int32)
    chosen = np.zeros((ids.shape[0], k), np.int32)
    for j in range(k):
      bound = np.maximum(deg - k + j, 0)
      t = np.minimum((u[j] * (bound + 1).astype(np.float32))
                     .astype(np.int32), bound)
      dup = (chosen[:, :j] == t[:, None]).any(axis=1)
      chosen[:, j] = np.where(dup, bound, t)
    iota = np.arange(k, dtype=np.int32)[None, :]
    offs = np.where((deg <= k)[:, None], iota, chosen)
    ok = (iota < np.minimum(deg, k)[:, None]).reshape(-1)
    nbrs = indices[np.minimum((start[:, None] + offs).reshape(-1),
                              indices.shape[0] - 1)].astype(np.int64)
    children.append(nbrs[ok])
    parents.append(np.repeat(ids, k)[ok])
    slot = np.flatnonzero(ok & ~np.isin(nbrs, seen))
    _, first = np.unique(nbrs[slot], return_index=True)
    head = np.zeros(nbrs.shape[0], bool)
    head[slot[first]] = True
    nodes.append(nbrs[head])
    seen = np.union1d(seen, nbrs[head])
    ids, mask = np.where(head, nbrs, 0), head
  nodes = np.concatenate(nodes)
  order = np.argsort(nodes, kind='stable')
  local = lambda g: order[np.searchsorted(nodes[order], g)].astype(np.int32)
  return (nodes, local(np.concatenate(children)),
          local(np.concatenate(parents)))


def _loss(params, x, child, parent, emask, y, dtype):
  """Mean cross-entropy of the seeds' logits. Every layer aggregates over
  every sampled edge; the rows that the program trims feed no seed."""
  n = x.shape[0]
  h = x.astype(dtype)
  w = emask.astype(dtype)
  cnt = jnp.maximum(jnp.zeros((n,), dtype).at[parent].add(w), 1)
  convs = params['params']
  for i in range(len(convs)):
    p = jax.tree.map(lambda a: a.astype(dtype), convs[f'conv{i}'])
    agg = jnp.zeros_like(h).at[parent].add(h[child] * w[:, None])
    agg = agg / cnt[:, None]
    h = (h @ p['lin_root']['kernel'] + p['lin_root']['bias']
         + agg @ p['lin_nbr']['kernel'])
    if i < len(convs) - 1:
      h = jnp.maximum(h, 0)
  logits = h[:y.shape[0]]
  picked = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
  return jnp.mean(jax.nn.logsumexp(logits, axis=1) - picked)


@functools.partial(jax.jit, static_argnames='dtype')
def loss_and_grad(params, x, child, parent, emask, y, dtype=jnp.float32):
  prec = 'highest' if dtype == jnp.float32 else 'default'
  with jax.default_matmul_precision(prec):
    loss, g = jax.value_and_grad(_loss)(params, x, child, parent, emask,
                                        y, dtype)
  f32 = lambda a: a.astype(jnp.float32)
  return f32(loss), jax.tree.map(f32, g)


def _pad(a, n):
  return np.concatenate([a, np.zeros(n - a.shape[0], a.dtype)])


def follow(indptr, indices, feats, params, feed, steps, n_chips, fanout,
           lr, dtype=jnp.float32, fault=None, rows_per_shard=None):
  """Train ``steps`` steps from ``params`` on the batches ``feed(t)``
  gives (seeds [chips * B], keys [chips]); returns the readings that
  ``compare`` takes. ``fault`` plants one of the faults that a cell can
  have, for the control runs and their tests: ``half_batch`` (the second
  half of every chip's seeds left out of the loss), ``no_exchange``
  (feature rows that another chip owns come back as nought)."""
  b = len(feed(0)[0]) // n_chips
  hop = flops.hop_slots(b, fanout)
  budget, ecap = sum(hop), sum(hop[1:])
  p0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
  p = p0
  m = jax.tree.map(np.zeros_like, p0)
  v = jax.tree.map(np.zeros_like, p0)
  losses, g1 = [], None
  for t in range(steps):
    seeds, keys = feed(t)
    loss, grad = 0.0, None
    for d in range(n_chips):
      s = np.asarray(seeds[d * b:(d + 1) * b])
      nodes, child, parent = sample(
          indptr, indices, s, jax.random.fold_in(keys[d], d), fanout)
      x = feats.rows(nodes)
      if fault == 'no_exchange':
        x[nodes // rows_per_shard != d] = 0
      y = feats.labels(s)
      if fault == 'half_batch':
        y = y[:b // 2]
      l, g = loss_and_grad(
          p, _pad(x.reshape(-1), budget * x.shape[1]).reshape(budget, -1),
          _pad(child, ecap), _pad(parent, ecap),
          _pad(np.ones(child.shape[0], bool), ecap), y, dtype=dtype)
      loss += float(l) / n_chips
      g = jax.tree.map(lambda a: np.asarray(a) / n_chips, g)
      grad = g if grad is None else jax.tree.map(np.add, grad, g)
    losses.append(loss)
    g1 = grad if g1 is None else g1
    m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, m, grad)
    v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, v, grad)
    c1, c2 = 1 - B1 ** (t + 1), 1 - B2 ** (t + 1)
    p = jax.tree.map(
        lambda a, m_, v_: a - lr * (m_ / c1) / (np.sqrt(v_ / c2) + EPS),
        p, m, v)
  return readings(losses, g1, p0, p)


def _leaf_norms(tree):
  return {jax.tree_util.keystr(k): float(np.linalg.norm(
      np.asarray(a, np.float64))) for k, a in
      jax.tree_util.tree_leaves_with_path(tree)}


def readings(losses, first_grad, params_before, params_after):
  """What one side hands to ``compare``: each step's loss, the norm of
  every leaf of the first gradient, and of the parameters' change."""
  change = jax.tree.map(lambda a, b: np.asarray(b, np.float64)
                        - np.asarray(a, np.float64),
                        params_before, params_after)
  return {'loss': [float(l) for l in losses],
          'grad': _leaf_norms(first_grad), 'change': _leaf_norms(change)}


def compare(prog, ref):
  """The numbers compared, each a gap of the program's reading from the
  reference's. Norms go by the worst leaf: the gap of the two norms over
  the reference's norm of that leaf or of the median leaf, whichever is
  larger. Leaves whose reference gradient is under a thousandth of the
  median leaf's move under Adam by round-off alone and are left out of
  the change."""
  gmed = float(np.median(list(ref['grad'].values())))
  cmed = float(np.median(list(ref['change'].values())))
  gap = lambda a, b, floor: abs(a - b) / max(b, floor)
  return {
      'loss_gap': max(gap(a, b, 1e-30)
                      for a, b in zip(prog['loss'], ref['loss'])),
      'grad_gap': max(gap(prog['grad'][k], r, gmed)
                      for k, r in ref['grad'].items()),
      'change_gap': max(gap(prog['change'][k], r, cmed)
                        for k, r in ref['change'].items()
                        if ref['grad'][k] >= 1e-3 * gmed),
  }
