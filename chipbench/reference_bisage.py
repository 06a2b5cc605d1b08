"""The plain reference for user-item link prediction over learnable id
embeddings (the reference's examples/hetero/bipartite_sage_unsup.py, PyG's
script of that name): the equations in ``jax.numpy`` float32, one explicit
edge list a relation, a ``segment_sum`` mean, every row computed; no plan,
no trim, no grouped reduce; loss, gradient (dense for the tables) and Adam
by hand, every row of every table updated every step. Asked to
(``operands``), its matmuls round both operands first, in the backward
pass too, and sum in float32 as before: float32 at the default precision
as a TPU's matrix unit computes it, where a cell states that precision and
is held to it; with ``operands=None`` the matmuls are float32 at
``highest``. It imports nothing of ``glt_tpu`` (``chipbench/
reference_bisage.py`` is its copy, and ``tests/test_bipartite_step.py``
holds the two to one text).

Users ``u`` and items ``i`` are their ids, read from the tables ``E_user``
and ``E_item``; ``S(W; x, y, R)_v = W_root y_v + b + W_nbr mean_{(c, v) in
R} x_c`` is a GraphSAGE convolution from the children's rows ``x`` to the
parents' ``y`` over the sampled edges ``R`` (the mean of no edge is 0):

  items    ``h = relu(S(I1; e, e, II))``, ``h = relu(S(I2; h, h, II))``,
           ``z_i = W_I h_i + b`` (``e = E_item`` rows, ``II`` items into
           items);
  users    ``a = relu(S(U1; e, e, II))``, ``g = relu(S(U2; e, f, IU))``,
           ``g = relu(S(U3; a, g, IU))``, ``z_u = W_U g_u + b`` (``f =
           E_user`` rows, ``IU`` items into users);
  pairs    ``logit_j = w2 . relu(W1 [z_u(j) ; z_i(j)] + b1) + b2``; the
           mean over the valid ``j`` of ``softplus(logit_j) - y_j
           logit_j``;
  Adam     ``m = b1 m + (1 - b1) g``, ``v = b2 v + (1 - b2) g g``, ``p -=
           lr (m / c1) / (sqrt(v / c2) + eps)``, on every element of every
           leaf: a table row that a batch did not touch has ``g = 0``, its
           moments decay and it moves by them.

A batch is ``{'nodes': {'user': ids [n_u], 'item': ids [n_i]}, 'edges':
{'item_user': (child [e], parent [e]), 'item_item': (child, parent)},
'pairs': (users [p], items [p]), 'y': [p], 'weight': [p]}``: labels are
positions in ``nodes``. Parameters are the tree of
``models/bipartite_sage.py::BipartiteSAGE``: ``embed_user/embedding``,
``embed_item/embedding``, ``item_encoder/{conv1, conv2}/{lin_root/{kernel,
bias}, lin_nbr/kernel}``, ``item_encoder/lin``, ``user_encoder/{conv1,
conv2, conv3, lin}`` and ``decoder/{lin1, lin2}``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
FAULTS = ('half_batch', 'lazy_update')
TABLES = ('embed_user', 'embed_item')


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


def forward(params, batch, dtype=jnp.float32, operands=None):
  """Logits ``[p]`` of the batch's pairs."""
  mm = _matmul(operands)
  tree = jax.tree.map(lambda a: a.astype(dtype), params['params'])
  dense = lambda p, x: mm(x, p['kernel']) + p['bias']

  def sage(p, x, y, edges):
    child, parent = edges
    n = y.shape[0]
    count = jax.ops.segment_sum(jnp.ones(child.shape, dtype), parent, n)
    mean = (jax.ops.segment_sum(x[child], parent, n)
            / jnp.maximum(count, 1)[:, None])
    return dense(p['lin_root'], y) + mm(mean, p['lin_nbr']['kernel'])

  relu = lambda a: jnp.maximum(a, 0)
  e = tree['embed_item']['embedding'][batch['nodes']['item']]
  f = tree['embed_user']['embedding'][batch['nodes']['user']]
  ii, iu = batch['edges']['item_item'], batch['edges']['item_user']
  enc = tree['item_encoder']
  h = relu(sage(enc['conv1'], e, e, ii))
  h = relu(sage(enc['conv2'], h, h, ii))
  z_item = dense(enc['lin'], h)
  enc = tree['user_encoder']
  a = relu(sage(enc['conv1'], e, e, ii))
  g = relu(sage(enc['conv2'], e, f, iu))
  g = relu(sage(enc['conv3'], a, g, iu))
  z_user = dense(enc['lin'], g)
  users, items = batch['pairs']
  z = jnp.concatenate([z_user[users], z_item[items]], axis=-1)
  dec = tree['decoder']
  hidden = relu(dense(dec['lin1'], z))
  # the last layer has one output: a dot product with one weight vector,
  # which no matrix unit computes and nothing rounds
  return (hidden * dec['lin2']['kernel'][:, 0]).sum(-1) + dec['lin2']['bias']


@functools.partial(jax.jit, static_argnames=('dtype', 'operands'))
def loss_and_grad(params, batch, dtype=jnp.float32, operands=None):
  """float32 loss and gradient, dense for the tables; ``dtype`` bfloat16
  is the control: the same equations with every array in the nearest
  precision below."""
  def loss(p):
    logit = forward(p, batch, dtype, operands)
    losses = jnp.logaddexp(0, logit) - batch['y'].astype(dtype) * logit
    weight = batch['weight'].astype(dtype)
    return (losses * weight).sum() / jnp.maximum(weight.sum(), 1)

  with jax.default_matmul_precision(
      'highest' if dtype == jnp.float32 else 'default'):
    value, g = jax.value_and_grad(loss)(params)
  f32 = lambda a: a.astype(jnp.float32)
  return f32(value), jax.tree.map(f32, g)


@functools.partial(jax.jit, static_argnames=('lazy',), donate_argnums=(0, 1, 2))
def _adam(p, m, v, g, c1, c2, lr, lazy=False):
  """One Adam step on one leaf. ``lazy`` is the planted fault: a row whose
  gradient is nought keeps its moments and its value, as a sparse or lazy
  optimizer leaves it."""
  m2 = B1 * m + (1 - B1) * g
  v2 = B2 * v + (1 - B2) * g * g
  p2 = p - lr * (m2 / c1) / (jnp.sqrt(v2 / c2) + EPS)
  if lazy:
    hit = jnp.any(g != 0, axis=-1, keepdims=True)
    return (jnp.where(hit, p2, p), jnp.where(hit, m2, m),
            jnp.where(hit, v2, v))
  return p2, m2, v2


def follow(params, batches, lr, dtype=jnp.float32, operands=None,
           fault=None, watch=None):
  """Train one Adam step a batch of ``batches`` (any iterable) from
  ``params``, on the backend's device: returns the readings that
  ``compare`` takes. ``operands``: what the matmuls round their operands
  to (None: nothing). ``fault`` plants one for the control runs and their
  tests: ``half_batch`` (the second half of the positives and of the
  negatives left out of the loss), ``lazy_update`` (Adam on the touched
  rows of a table only). ``watch``: ``{table: ids}``, rows whose movement
  in the second step is read (``moved``): by momentum alone where they
  are rows that the first batch touched and the second did not."""
  assert fault is None or fault in FAULTS, fault
  p0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
  p = jax.tree.map(jnp.copy, p0)
  m = jax.tree.map(jnp.zeros_like, p0)
  v = jax.tree.map(jnp.zeros_like, p0)
  rows = lambda tree: {k: np.asarray(tree['params'][k]['embedding'][ids])
                       for k, ids in (watch or {}).items()}
  losses, g1, moved = [], None, None
  for t, batch in enumerate(batches):
    if fault == 'half_batch':
      half = batch['weight'].shape[0] // 4
      batch = dict(batch, weight=batch['weight'] * np.tile(
          np.arange(2 * half) < half, 2))
    loss, grad = loss_and_grad(p, batch, dtype=dtype, operands=operands)
    losses.append(float(loss))
    g1 = grad if g1 is None else g1
    before = rows(p) if t == 1 else None
    c1, c2 = 1 - B1 ** (t + 1), 1 - B2 ** (t + 1)
    flat, tree = jax.tree_util.tree_flatten_with_path(p)
    out = [_adam(a, m_, v_, g_, c1, c2, lr,
                 lazy=(fault == 'lazy_update' and any(
                     getattr(k, 'key', None) in TABLES for k in path)))
           for (path, a), m_, v_, g_ in zip(
               flat, jax.tree.leaves(m), jax.tree.leaves(v),
               jax.tree.leaves(grad))]
    p, m, v = (jax.tree.unflatten(tree, [o[i] for o in out])
               for i in range(3))
    if t == 1:
      after = rows(p)
      moved = {k: after[k] - before[k] for k in after}
  return readings(losses, g1, p0, p, moved)


def _leaves(tree):
  return {jax.tree_util.keystr(k): a for k, a in
          jax.tree_util.tree_leaves_with_path(tree)}


def readings(losses, first_grad, params_before, params_after, moved=None):
  """What one side hands to ``compare``: each step's loss, every leaf of
  the first gradient and of the parameters' change (arrays as they are,
  on the host or on the device: a table is not copied for it), and the
  watched rows' movement in the second step."""
  change = jax.tree.map(lambda a, b: jnp.asarray(b) - jnp.asarray(a),
                        params_before, params_after)
  return {'loss': [float(l) for l in losses],
          'grad': _leaves(first_grad), 'change': _leaves(change),
          'moved': moved}


def _norm(a):
  """The 2-norm of an array wherever it lives, summed in float32 on the
  backend's device (a table's is a third of a billion squares)."""
  return float(jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(a, jnp.float32)))))


def compare(prog, ref):
  """The numbers compared, each a gap of the program's reading from the
  reference's: the worst step's loss; the first gradient's worst leaf, the
  tables among them, the norm of the difference element by element over
  the reference's norm of that leaf or of the median leaf, whichever is
  larger; the parameters' change by the worst leaf's norm, the gap of the
  two norms over the same (leaves whose reference gradient is under a
  thousandth of the median leaf's move under Adam by round-off alone and
  are left out); and ``table_momentum_gap``, over the watched rows of
  both tables the norm of the difference of the two movements in the
  second step over the norm of the reference's: a table whose untouched
  rows stand still reads 1."""
  norms = lambda tree: {k: _norm(a) for k, a in tree.items()}
  rg, pc, rc = norms(ref['grad']), norms(prog['change']), norms(ref['change'])
  gmed = float(np.median(list(rg.values())))
  cmed = float(np.median(list(rc.values())))
  gap = lambda a, b, floor: abs(a - b) / max(b, floor)
  out = {
      'loss_gap': max(gap(a, b, 1e-30)
                      for a, b in zip(prog['loss'], ref['loss'])),
      'grad_gap': max(_norm(jnp.asarray(prog['grad'][k]) - jnp.asarray(a))
                      / max(rg[k], gmed) for k, a in ref['grad'].items()),
      'change_gap': max(gap(pc[k], r, cmed) for k, r in rc.items()
                        if rg[k] >= 1e-3 * gmed),
  }
  if ref.get('moved'):
    cat = lambda side: np.concatenate(
        [np.asarray(side['moved'][k], np.float64).reshape(-1)
         for k in sorted(ref['moved'])])
    there = cat(ref)
    out['table_momentum_gap'] = float(
        np.linalg.norm(cat(prog) - there)
        / max(np.linalg.norm(there), 1e-30))
  return out
