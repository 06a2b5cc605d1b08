"""The plain reference for link prediction with GraphSAGE over a sampled
subgraph (the reference's unsupervised-GraphSAGE recipe: binary strict
negatives, dot-product logits, binary cross-entropy): negative sampling
and neighbour sampling with dedup in numpy, forward, loss and gradient in
``jax.numpy`` float32 at ``highest`` matmul precision, Adam by hand. No
trim, no grouped reduce, no dedup tables: a ``segment_sum`` mean over
explicit edges. Asked to (``operands``), its matmuls round both operands
first, in the backward pass too, and sum in float32 as before: float32
at the default precision as a TPU's matrix unit computes it, where a
cell states that precision and is held to it. It imports nothing of ``glt_tpu`` (``chipbench/
reference_link.py`` is its copy, and ``tests/test_link_step.py`` holds the
two to one text).

One step on a chip, ``B`` positive pairs ``(s_i, d_i)``, each an edge:

  negatives  ``T`` rounds of ``B`` uniform proposals ``(r_t,i, c_t,i)``;
             pair ``i`` takes its first round whose proposal is no edge,
             and the last round's proposal if none (padded);
  seeds      ``z = [s; r~; d; c~]``, labels ``y = [1_B; 0_B]``;
  sample     every distinct valid endpoint once, then ``fanout_h``
             neighbours of every node a hop first reached;
  model      ``h'_v = W_root h_v + W_nbr mean_{u in S(v)} h_u + b``, ReLU
             between layers and none after the last;
  loss       ``logit_j = <e[src_j], e[dst_j]>`` for ``j < 2B``; the mean
             over the valid ``j`` of ``softplus(logit_j) - y_j logit_j``.

Both samplers follow the program's random stream, so that both sides
train on the same sample and the comparison is of the arithmetic. A
chip's key is the step's key folded with the chip's index, then split:
the first half draws the negatives (split once more for rows and columns,
``randint(k, (T, B), 0, N)`` each), the second is split once a hop, and a
hop draws ``uniform(sub, (fanout, S))`` over its ``S`` frontier slots and
picks ``fanout`` distinct offsets by Floyd's method where the degree is
larger, else the whole row. The frontier of the first hop holds the ``4B``
endpoint slots grouped by node in the order of first appearance, a node's
repeats right behind its first slot and a masked slot at its own
position (what a sort by node and slot leaves); only a node's first slot
is expanded. A later hop's frontier is the hop's slots in place, and a
node is expanded from the first slot that held it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
TRIALS = 5


def is_edge(indptr, indices, rows, cols):
  """[n] bool: is ``rows[i] -> cols[i]`` in the CSR (ascending rows)?"""
  out = np.zeros(len(rows), bool)
  for i, (r, c) in enumerate(zip(rows, cols)):
    row = indices[indptr[r]:indptr[r + 1]]
    at = np.searchsorted(row, c)
    out[i] = at < row.shape[0] and row[at] == c
  return out


def negatives(indptr, indices, key, batch, num_nodes, trials=TRIALS):
  """``(rows [B], cols [B], padded [B] bool, rejected)``: strict binary
  negatives, one a positive, from ``key``."""
  kr, kc = jax.random.split(key)
  rows = np.asarray(jax.random.randint(kr, (trials, batch), 0, num_nodes,
                                       dtype=jnp.int32))
  cols = np.asarray(jax.random.randint(kc, (trials, batch), 0, num_nodes,
                                       dtype=jnp.int32))
  ok = ~is_edge(indptr, indices, rows.reshape(-1),
                cols.reshape(-1)).reshape(trials, batch)
  first = np.where(ok.any(axis=0), ok.argmax(axis=0), trials - 1)
  pick = lambda a: a[first, np.arange(batch)]
  return pick(rows), pick(cols), ~ok.any(axis=0), int((~ok).sum())


def first_frontier(seeds, valid):
  """``(ids [S], mask [S])``: the first hop's frontier, as the module's
  text describes it."""
  pos = np.arange(seeds.shape[0])
  _, first_of, inverse = np.unique(np.where(valid, seeds, -1 - pos),
                                   return_index=True, return_inverse=True)
  first = first_of[inverse]              # a masked slot is its own first
  order = np.lexsort((pos, first))
  return (np.where(valid, seeds, 0)[order].astype(np.int64),
          (valid & (first == pos))[order])


def sample(indptr, indices, ids, mask, key, fanout):
  """(nodes [n] unique global ids, the distinct seeds first; child,
  parent [e] indices into ``nodes``), from the first hop's frontier."""
  nodes, children, parents = [ids[mask]], [], []
  seen = np.sort(ids[mask])
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
  return nodes, local(np.concatenate(children)), local(
      np.concatenate(parents)), local


def default_operands():
  """What JAX's default precision rounds a float32 matmul's operands to
  on the backend at hand: bfloat16 on a TPU, nothing elsewhere."""
  return jnp.bfloat16 if jax.default_backend() == 'tpu' else None


@functools.cache
def _matmul(operands):
  """``a @ b`` with both operands rounded to ``operands`` first, in the
  two products of the backward pass as well; the sums stay as wide as
  ``a`` and ``b`` are."""
  if operands is None:
    return jnp.matmul
  to = jnp.finfo(operands)
  r = lambda t: jax.lax.reduce_precision(t, to.nexp, to.nmant)

  @jax.custom_vjp
  def mm(a, b):
    return r(a) @ r(b)

  mm.defvjp(lambda a, b: (mm(a, b), (a, b)),
            lambda ab, g: (r(g) @ r(ab[1]).T, r(ab[0]).T @ r(g)))
  return mm


def _loss(params, x, child, parent, emask, src, dst, y, weight, dtype,
          operands):
  """Mean binary cross-entropy of the pairs' logits. Every layer
  aggregates over every sampled edge and computes every row."""
  mm = _matmul(operands)
  n = x.shape[0]
  h = x.astype(dtype)
  w = emask.astype(dtype)
  cnt = jnp.maximum(jax.ops.segment_sum(w, parent, n), 1)
  convs = params['params']
  for i in range(len(convs)):
    p = jax.tree.map(lambda a: a.astype(dtype), convs[f'conv{i}'])
    agg = jax.ops.segment_sum(h[child] * w[:, None], parent, n)
    agg = agg / cnt[:, None]
    h = (mm(h, p['lin_root']['kernel']) + p['lin_root']['bias']
         + mm(agg, p['lin_nbr']['kernel']))
    if i < len(convs) - 1:
      h = jnp.maximum(h, 0)
  logit = (h[src] * h[dst]).sum(-1)
  losses = jnp.logaddexp(0, logit) - y.astype(dtype) * logit
  weight = weight.astype(dtype)
  return (losses * weight).sum() / jnp.maximum(weight.sum(), 1)


@functools.partial(jax.jit, static_argnames=('dtype', 'operands'))
def loss_and_grad(params, x, child, parent, emask, src, dst, y, weight,
                  dtype=jnp.float32, operands=None):
  prec = 'highest' if dtype == jnp.float32 else 'default'
  with jax.default_matmul_precision(prec):
    loss, g = jax.value_and_grad(_loss)(params, x, child, parent, emask,
                                        src, dst, y, weight, dtype,
                                        operands)
  f32 = lambda a: a.astype(jnp.float32)
  return f32(loss), jax.tree.map(f32, g)


def _pad(a, n):
  return np.concatenate([a, np.zeros(n - a.shape[0], a.dtype)])


def chip_batch(indptr, indices, pairs, key, chip, fanout, num_nodes,
               n_valid=None):
  """One chip's step as the equations give it: ``{'seeds' [4B], 'padded'
  [B] bool, 'rejected', 'nodes', 'child', 'parent', 'src' [2B], 'dst'
  [2B], 'y' [2B], 'weight' [2B]}``; ``src`` and ``dst`` index ``nodes``."""
  b = pairs.shape[0]
  kneg, key = jax.random.split(jax.random.fold_in(key, chip))
  rows, cols, padded, rejected = negatives(indptr, indices, kneg, b,
                                           num_nodes)
  seeds = np.concatenate([pairs[:, 0], rows, pairs[:, 1], cols]).astype(
      np.int64)
  live = np.arange(b) < (b if n_valid is None else n_valid)
  ids, mask = first_frontier(seeds, np.tile(live, 4))
  nodes, child, parent, local = sample(indptr, indices, ids, mask, key,
                                       fanout)
  at = local(np.where(np.tile(live, 4), seeds, nodes[0]))
  return dict(seeds=seeds, padded=padded, rejected=rejected, nodes=nodes,
              child=child, parent=parent, src=at[:2 * b], dst=at[2 * b:],
              y=np.concatenate([np.ones(b, np.float32),
                                np.zeros(b, np.float32)]),
              weight=np.tile(live, 2).astype(np.float32))


def follow(indptr, indices, rows_of, params, feed, steps, n_chips, fanout,
           lr, num_nodes, dtype=jnp.float32, operands=None, fault=None,
           n_valid=None):
  """Train ``steps`` steps from ``params`` on the batches ``feed(t)``
  gives (pairs [chips * B, 2], keys [chips]); ``rows_of(ids)`` gives
  feature rows. Returns the readings that ``compare`` takes, and under
  ``'batches'`` what each chip's step sampled. ``operands``: what the
  matmuls round their operands to (None: nothing). ``fault`` plants one of
  the faults a cell can have, for the control runs and their tests:
  ``half_batch`` (the second half of every chip's pairs, positives and
  negatives, left out of the loss), ``no_negatives`` (every label 1)."""
  b = len(feed(0)[0]) // n_chips
  hop = [4 * b]
  for k in fanout:
    hop.append(hop[-1] * k)
  budget, ecap = sum(hop), sum(hop[1:])
  p0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
  p = p0
  m = jax.tree.map(np.zeros_like, p0)
  v = jax.tree.map(np.zeros_like, p0)
  losses, g1, batches = [], None, []
  for t in range(steps):
    pairs, keys = feed(t)
    loss, grad = 0.0, None
    for d in range(n_chips):
      got = chip_batch(indptr, indices,
                       np.asarray(pairs[d * b:(d + 1) * b]), keys[d], d,
                       fanout, num_nodes,
                       None if n_valid is None else n_valid[d])
      batches.append(got)
      x = rows_of(got['nodes'])
      y, weight = got['y'], got['weight']
      if fault == 'half_batch':
        weight = weight * np.tile(np.arange(b) < b // 2, 2)
      if fault == 'no_negatives':
        y = np.ones_like(y)
      l, g = loss_and_grad(
          p, _pad(x.reshape(-1), budget * x.shape[1]).reshape(budget, -1),
          _pad(got['child'], ecap), _pad(got['parent'], ecap),
          _pad(np.ones(got['child'].shape[0], bool), ecap),
          got['src'], got['dst'], y, weight, dtype=dtype,
          operands=operands)
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
  return dict(readings(losses, g1, p0, p), batches=batches, params=p)


def _leaves(tree):
  return {jax.tree_util.keystr(k): np.asarray(a, np.float64) for k, a in
          jax.tree_util.tree_leaves_with_path(tree)}


def readings(losses, first_grad, params_before, params_after):
  """What one side hands to ``compare``: each step's loss, every leaf of
  the first gradient, and of the parameters' change."""
  change = jax.tree.map(lambda a, b: np.asarray(b, np.float64)
                        - np.asarray(a, np.float64),
                        params_before, params_after)
  return {'loss': [float(l) for l in losses],
          'grad': _leaves(first_grad), 'change': _leaves(change)}


def compare(prog, ref):
  """The numbers compared, each a gap of the program's reading from the
  reference's: the worst step's loss; the first gradient's worst leaf,
  the norm of the difference element by element over the reference's
  norm of that leaf or of the median leaf, whichever is larger; the
  parameters' change by the worst leaf's norm, the gap of the two norms
  over the same. Leaves whose reference gradient is under a thousandth
  of the median leaf's move under Adam by round-off alone and are left
  out of the change."""
  norm = lambda tree: {k: float(np.linalg.norm(a)) for k, a in tree.items()}
  rg, pc, rc = norm(ref['grad']), norm(prog['change']), norm(ref['change'])
  gmed = float(np.median(list(rg.values())))
  cmed = float(np.median(list(rc.values())))
  gap = lambda a, b, floor: abs(a - b) / max(b, floor)
  return {
      'loss_gap': max(gap(a, b, 1e-30)
                      for a, b in zip(prog['loss'], ref['loss'])),
      'grad_gap': max(float(np.linalg.norm(prog['grad'][k] - a))
                      / max(rg[k], gmed) for k, a in ref['grad'].items()),
      'change_gap': max(gap(pc[k], r, cmed) for k, r in rc.items()
                        if rg[k] >= 1e-3 * gmed),
  }


def pair_violations(indptr, indices, seeds, padded_count):
  """How far one chip's ``[4B]`` endpoint seeds ``[s; r~; d; c~]`` are
  from the contract: positives that are no edge of the CSR, plus the
  distance of the negatives that are edges from ``padded_count`` (an
  unpadded negative is a non-edge, a padded one carries a proposal that
  was an edge, so the two are equal)."""
  s, r, d, c = np.asarray(seeds, np.int64).reshape(4, -1)
  return (int((~is_edge(indptr, indices, s, d)).sum())
          + abs(int(is_edge(indptr, indices, r, c).sum())
                - int(padded_count)))
