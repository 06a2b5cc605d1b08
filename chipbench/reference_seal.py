"""The plain reference for SEAL link prediction (Zhang and Chen, NeurIPS
2018) with DGCNN, as SEAL_OGB's ``seal_link_pred.py`` runs it: the
enclosing subgraph of a link extracted from a CSR in numpy, DRNL by a
queue-based breadth-first search in numpy, DGCNN forward, loss and
gradient in ``jax.numpy`` float32 at ``highest`` matmul precision, Adam by
hand. Asked to (``operands``), the linear maps' matmuls round both
operands first, in the backward pass too, and sum in float32 as before:
float32 at the default precision as a TPU's matrix unit computes it, where
a cell states that precision and is held to it. It imports nothing of
``glt_tpu`` (``chipbench/reference_seal.py`` is its copy, and
``tests/test_seal_step.py`` holds the two to one text).

One link ``(s, d)`` with its node set ``V`` (``s`` first, ``d`` second,
then the fringe: the reference is GIVEN the node set, it does not sample):

  edges    every edge ``{u, v}`` of the graph with ``u, v`` in ``V``,
           less ``{s, d}``; the graph is read undirected, without loops;
  labels   ``z(v) = 1 + min(a, b) + (q // 2) (q // 2 + q % 2 - 1)``,
           ``q = a + b``, ``a`` the distance from ``s`` in the subgraph
           without ``d``, ``b`` from ``d`` without ``s``; ``z(s) = z(d) =
           1``; 0 where either cannot reach ``v``; clipped to ``max_z -
           1``;
  input    ``h0_v = [E[z(v)]; x_v]``, ``E`` an embedding of ``max_z``
           rows;
  GCN      ``h' = tanh(D^-1/2 (A + I) D^-1/2 (h W) + b)``, three times at
           the hidden width and once more to one channel; the four
           outputs concatenated (PyG's ``GCNConv``: self-loops,
           symmetric normalisation);
  readout  ``global_sort_pool``: the nodes sorted by the last channel,
           descending, ties to the lower slot, the first ``k`` kept,
           zero rows where the graph has fewer. A sort is a choice: two
           keys a rounding apart swap two rows of the head's input, and
           the logit jumps. So a comparison of the arithmetic may hand
           the order in as the program made it (``order``), and hold
           that order to this file's own keys (``sort_violations``);
  head     ``Conv1d(1, C1, F, F)`` (one window a node), ReLU (slope 0 at
           0, as PyTorch's: a zero row under a zero bias sits there),
           ``MaxPool1d(2, 2)``, ``Conv1d(C1, C2, 5, 1)``, ReLU, flatten,
           ``Linear(., 128)``, ReLU, ``Linear(128, 1)``;
  loss     the mean over the valid links of ``softplus(logit) - y
           logit``.

Departures from the recipe, each the cell's own and stated in its
configuration: no dropout (every training cell of the benchmark steps
without it); the flattened head input runs position-major (``[k', C2]``)
where PyTorch's runs channel-major, a fixed permutation of one weight's
rows; the neighbours' sum of a GCN layer is a float32 sum (PyG
scatter-adds), so it rounds nothing under ``operands`` either.
"""
import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
GCN = ('gcn0', 'gcn1', 'gcn2', 'gcn_key')


# -- extraction and labels, in numpy ---------------------------------------

def induced_edges(indptr, indices, nodes):
  """``[n, n]`` bool: the edges of the (symmetric, ascending) CSR among
  ``nodes`` (global ids, the link's ends first), without loops and
  without the link itself."""
  nodes = np.asarray(nodes, np.int64)
  n = nodes.shape[0]
  order = np.argsort(nodes, kind='stable')
  ranked = nodes[order]
  adj = np.zeros((n, n), bool)
  for i, u in enumerate(nodes):
    row = indices[indptr[u]:indptr[u + 1]]
    if row.shape[0] == 0:
      continue
    at = np.minimum(np.searchsorted(row, ranked), row.shape[0] - 1)
    adj[i, order[row[at] == ranked]] = True
  adj[np.arange(n), np.arange(n)] = False
  adj[:2, :2] = False
  return adj


def drnl(adj, max_z):
  """``(z [n], depth)``: the DRNL labels of one subgraph whose link joins
  nodes 0 and 1, and the largest distance either search found."""
  n = adj.shape[0]
  nbrs = [np.flatnonzero(adj[i]) for i in range(n)]

  def distances(source, barred):
    dist = np.full(n, -1, np.int64)
    dist[source] = 0
    queue = collections.deque([source])
    while queue:
      u = queue.popleft()
      for v in nbrs[u]:
        if v != barred and dist[v] < 0:
          dist[v] = dist[u] + 1
          queue.append(v)
    return dist

  a, b = distances(0, 1), distances(1, 0)
  q = a + b
  z = 1 + np.minimum(a, b) + (q // 2) * (q // 2 + q % 2 - 1)
  z = np.where((a >= 0) & (b >= 0), z, 0)
  z[:2] = 1
  return np.clip(z, 0, max_z - 1), max(a.max(), b.max())


def blocks(indptr, indices, nodes, max_z):
  """``(adj [L, S, S] bool, z [L, S], mask [L, S], depth [L])`` of a
  step's links from their node sets ``nodes [L, S]`` (-1 padded)."""
  nodes = np.asarray(nodes)
  num_links, s = nodes.shape
  adj = np.zeros((num_links, s, s), bool)
  z = np.zeros((num_links, s), np.int32)
  depth = np.zeros(num_links, np.int64)
  for l in range(num_links):
    n = int((nodes[l] >= 0).sum())
    if n:
      adj[l, :n, :n] = induced_edges(indptr, indices, nodes[l, :n])
      z[l, :n], depth[l] = drnl(adj[l, :n, :n], max_z)
  return adj, z, nodes >= 0, depth


# -- the model -------------------------------------------------------------

def default_operands():
  """What JAX's default precision rounds a float32 matmul's operands to
  on the backend at hand: bfloat16 on a TPU, nothing elsewhere."""
  return jnp.bfloat16 if jax.default_backend() == 'tpu' else None


@functools.cache
def _matmul(operands):
  """``a [.., m, k] @ b [k, n]`` with both operands rounded to
  ``operands`` first, in the two products of the backward pass as well;
  the sums stay as wide as ``a`` and ``b`` are. ``n = 1`` rounds
  nothing (below)."""
  if operands is None:
    return jnp.matmul
  to = jnp.finfo(operands)
  r = lambda t: jax.lax.reduce_precision(t, to.nexp, to.nmant)

  @jax.custom_vjp
  def mm2(a, b):
    return r(a) @ r(b)

  mm2.defvjp(lambda a, b: (mm2(a, b), (a, b)),
             lambda ab, g: (r(g) @ r(ab[1]).T, r(ab[0]).T @ r(g)))
  # a product one column wide is no matrix-unit product: the TPU's
  # compiler makes it a multiply and a float32 sum on the vector unit,
  # forward and backward (read in the step's compiled HLO: the sort key's
  # 32 -> 1 map and the last 128 -> 1 map), so nothing is rounded there
  return lambda a, b: a @ b if b.shape[-1] == 1 else mm2(
      a.reshape(-1, a.shape[-1]), b).reshape(a.shape[:-1] + b.shape[-1:])


def _logits(params, x, z, adj, mask, k, dtype, operands, order=None,
            keys=False):
  """One logit a link. ``order [L, k]``: the node slots to pool, in
  order, where the sort is taken as made (``sort_violations`` checks such
  an order against ``keys=True``'s sort keys ``[L, S]``, -inf on a masked
  slot)."""
  mm = _matmul(operands)
  p = jax.tree.map(lambda a: a.astype(dtype), params['params'])
  mask = mask.astype(dtype)
  h = jnp.concatenate([p['z_embed']['embedding'][z], x.astype(dtype)], -1)
  looped = adj.astype(dtype) + jnp.eye(adj.shape[-1], dtype=dtype)
  deg = looped.sum(-1)
  norm = looped / jnp.sqrt(deg[:, :, None] * deg[:, None, :])
  outs = []
  for name in GCN:
    h = jnp.tanh(norm @ mm(h, p[name]['lin']['kernel']) + p[name]['bias'])
    outs.append(h)
  h = jnp.concatenate(outs, -1) * mask[..., None]
  key = jnp.where(mask > 0, h[..., -1], -jnp.inf)
  if keys:
    return key
  top = (jnp.argsort(-key, axis=-1, stable=True)[:, :k] if order is None
         else order)
  pooled = (jnp.take_along_axis(h, top[..., None], axis=1)
            * jnp.take_along_axis(mask, top, axis=1)[..., None])
  c1, c2 = p['conv1'], p['conv2']
  y = jax.nn.relu(mm(pooled, c1['kernel'][:, 0, :]) + c1['bias'])
  half = y.shape[1] // 2
  y = y[:, :2 * half].reshape(y.shape[0], half, 2, -1).max(2)
  taps = c2['kernel'].shape[0]
  wins = jnp.concatenate([y[:, i:half - taps + 1 + i] for i in range(taps)],
                         axis=-1)                 # [L, k', taps * C1]
  y = jax.nn.relu(mm(wins, c2['kernel'].reshape(-1, c2['kernel'].shape[-1]))
                  + c2['bias'])
  y = y.reshape(y.shape[0], -1)
  y = jax.nn.relu(mm(y, p['mlp0']['kernel']) + p['mlp0']['bias'])
  return (mm(y, p['mlp1']['kernel']) + p['mlp1']['bias'])[:, 0]


def _loss(params, x, z, adj, mask, y, weight, order, k, dtype, operands):
  logit = _logits(params, x, z, adj, mask, k, dtype, operands, order)
  losses = jnp.logaddexp(0, logit) - y.astype(dtype) * logit
  weight = weight.astype(dtype)
  return (losses * weight).sum() / jnp.maximum(weight.sum(), 1)


@functools.partial(jax.jit, static_argnames=('k', 'dtype', 'operands'))
def loss_and_grad(params, x, z, adj, mask, y, weight, order, k,
                  dtype=jnp.float32, operands=None):
  """``(loss, gradient, sort keys [L, S])``; ``order``: None, or the
  readout's order taken as made."""
  prec = 'highest' if dtype == jnp.float32 else 'default'
  with jax.default_matmul_precision(prec):
    loss, g = jax.value_and_grad(_loss)(params, x, z, adj, mask, y, weight,
                                        order, k, dtype, operands)
    key = _logits(params, x, z, adj, mask, k, dtype, operands, keys=True)
  f32 = lambda a: a.astype(jnp.float32)
  return f32(loss), jax.tree.map(f32, g), f32(key)


def sort_violations(order, key, tol):
  """How far ``order [L, k]`` (node slots) is from a descending sort of
  the first ``k`` of ``key [L, S]`` (-inf on a masked slot), two keys
  within ``tol`` of each other in either order: slots kept twice, masked
  slots kept while a live one is left, neighbours in the order that rise
  by more than ``tol``, slots left out that beat the last kept by more."""
  order, key = np.asarray(order), np.asarray(key, np.float64)
  bad = 0
  for o, kv in zip(order, key):
    n = min(int(np.isfinite(kv).sum()), o.shape[0])
    kept = np.maximum(kv[o[:n]], -1e30)      # a masked slot kept: -inf
    bad += int(np.unique(o[:n]).size != n) + int((kept <= -1e30).sum())
    bad += int((np.diff(kept) > tol).sum())
    if n:
      left = np.delete(kv, o[:n])
      bad += int((left > kept[-1] + tol).sum())
  return bad


def follow(indptr, indices, rows_of, params, batches, lr, k, max_z,
           dtype=jnp.float32, operands=None, fault=None):
  """Train one step a batch from ``params``. A batch is one chip's step:
  ``{'nodes' [L, S] (-1 padded), 'y' [L], 'weight' [L]}`` and, where the
  readout's sort is taken as made, ``'order' [L, k]``; ``rows_of(ids)``
  gives feature rows. Returns the readings that ``compare`` takes and,
  under ``'blocks'``, each step's ``(adj, z, mask, depth)`` as extracted
  here, under ``'keys'`` each step's sort keys as computed here.
  ``operands``: what the linear maps' matmuls round their operands to
  (None: nothing). ``fault`` plants one of the faults a cell can have, for
  the control runs and their tests: ``half_batch`` (the second half of the
  positives and of the negatives left out of the loss), ``no_labels``
  (every ``z`` 1: a model that does not see DRNL)."""
  p0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
  p = p0
  m = jax.tree.map(np.zeros_like, p0)
  v = jax.tree.map(np.zeros_like, p0)
  losses, g1, kept, keys = [], None, [], []
  for t, batch in enumerate(batches):
    nodes = np.asarray(batch['nodes'])
    adj, z, mask, depth = blocks(indptr, indices, nodes, max_z)
    kept.append((adj, z, mask, depth))
    x = rows_of(np.maximum(nodes, 0).reshape(-1)).reshape(
        nodes.shape + (-1,)) * mask[..., None]
    weight = np.asarray(batch['weight'], np.float32)
    if fault == 'half_batch':
      b = weight.shape[0] // 2
      weight = weight * np.tile(np.arange(b) < b // 2, 2)
    if fault == 'no_labels':
      z = np.ones_like(z)
    loss, grad, key = loss_and_grad(
        p, x, z, adj, mask, batch['y'], weight, batch.get('order'), k,
        dtype=dtype, operands=operands)
    keys.append(np.asarray(key))
    grad = jax.tree.map(np.asarray, grad)
    losses.append(float(loss))
    g1 = grad if g1 is None else g1
    m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, m, grad)
    v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, v, grad)
    c1, c2 = 1 - B1 ** (t + 1), 1 - B2 ** (t + 1)
    p = jax.tree.map(
        lambda a, m_, v_: a - lr * (m_ / c1) / (np.sqrt(v_ / c2) + EPS),
        p, m, v)
  return dict(readings(losses, g1, p0, p), blocks=kept, keys=keys,
              params=p)


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
