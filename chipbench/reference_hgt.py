"""The plain reference for the Heterogeneous Graph Transformer (Hu, Dong,
Wang, Sun, WWW 2020; PyG's ``HGTConv``) over a typed sampled subgraph:
the equations in ``jax.numpy`` float32, one explicit edge list a
relation, one ``segment_max`` / ``segment_sum`` over the concatenation of
a parent type's relations; no trim, no groups, no padding tricks; loss,
gradient, and Adam by hand. Asked to (``operands``), its matmuls round
both operands first, in the backward pass too, and sum in float32 as
before: float32 at the default precision as a TPU's matrix unit computes
it, where a cell states that precision and is held to it; with
``operands=None`` the matmuls are float32 at ``highest``. It imports
nothing of ``glt_tpu`` (``chipbench/reference_hgt.py`` is its copy, and
``tests/test_hgt_step.py`` holds the two to one text).

``H`` heads of ``d``, ``F = H d``, node ``v`` of type ``tau(v)``:

  input    ``h_v = relu(W_in[tau(v)] x_v + b)``;
  a layer  ``K_v = W_K[tau(v)] h_v + b``, ``Q_v``, ``V_v`` likewise,
           ``[H, d]`` each; for a sampled edge from child ``u`` to parent
           ``v`` in relation ``r``: ``k = K_u^h A_r^h``, ``m = V_u^h
           M_r^h``, ``a = (Q_v^h . k) mu_r^h / sqrt(d)``;
           ``alpha = exp(a - max) / sum`` over every sampled edge into
           ``v``, **all relations together**; ``g_v = concat_h sum alpha
           m`` (0 where ``v`` has no edge); ``o_v = W_O[tau(v)] gelu(g_v)
           + b`` (the exact gelu); ``h'_v = sigmoid(s) o_v + (1 -
           sigmoid(s)) h_v`` with ``s = s[tau(v)]``;
  head     ``W_head h_seed + b``, mean softmax cross-entropy.

A batch is ``{'x': {type: [n_t, D]}, 'edges': {(s, r, d): (src [e], dst
[e])}, 'y': [b], 'seed_type': type}``: every row and every edge real,
labels are positions in ``x[type]``, the seeds the first ``b`` rows of
their type. Parameters are the tree of ``models/hgt.py::HGT``:
``in_<t>/{kernel, bias}``, ``layer<i>/{k,q,v,a}_<t>/{kernel, bias}``,
``layer<i>/skip_<t>``, ``layer<i>/{watt,wmsg,prior}_<s>__<r>__<d>`` and
``head/{kernel, bias}``.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
FAULTS = ('half_batch', 'per_relation_softmax')


def relation_name(etype):
  return '__'.join(etype)


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


@functools.cache
def _per_head(operands):
  """``rows[e, h] @ w[h]``: ``[e, H, d] x [H, d, d] -> [e, H, d]``, its
  operands rounded as :func:`_matmul` rounds them."""
  product = lambda a, w: jnp.einsum('ehd,hdf->ehf', a, w)
  if operands is None:
    return product
  to = jnp.finfo(operands)
  r = lambda t: jax.lax.reduce_precision(t, to.nexp, to.nmant)

  @jax.custom_vjp
  def mm(a, w):
    return product(r(a), r(w))

  mm.defvjp(lambda a, w: (mm(a, w), (a, w)),
            lambda aw, g: (jnp.einsum('ehf,hdf->ehd', r(g), r(aw[1])),
                           jnp.einsum('ehd,ehf->hdf', r(aw[0]), r(g))))
  return mm


@functools.partial(jax.checkpoint, static_argnums=(4, 5, 6, 7))
def _parent_type(layer, h, q, pairs, etypes, heads, operands, alone):
  """``g`` [n_d, F] of one parent type: the relations ``etypes`` into
  it, ``pairs`` their ``(src, dst)`` edge lists, in one softmax
  (``alone``: each relation in a softmax of its own, the planted fault).
  Its intermediates are made again in the backward pass."""
  mm, heads_mm = _matmul(operands), _per_head(operands)
  n_d, f = q.shape
  d = f // heads
  split = lambda a: a.reshape(a.shape[0], heads, d)
  made = {}

  def lin(n, t):   # keys or values of every node of a type, made once
    if (n, t) not in made:
      made[n, t] = split(mm(h[t], layer[f'{n}_{t}']['kernel'])
                         + layer[f'{n}_{t}']['bias'])
    return made[n, t]

  logits, msgs, dsts, owner = [], [], [], []
  for i, (etype, (src, dst)) in enumerate(zip(etypes, pairs)):
    s = etype[0]
    name = relation_name(etype)
    key = heads_mm(lin('k', s)[src], layer['watt_' + name])
    msgs.append(heads_mm(lin('v', s)[src], layer['wmsg_' + name]))
    logits.append((split(q)[dst] * key).sum(-1)
                  * layer['prior_' + name] / math.sqrt(d))
    dsts.append(dst)
    owner.append(jnp.full(dst.shape, i, jnp.int32))
  if not etypes:
    return jnp.zeros((n_d, f), q.dtype)
  a, m, dst = (jnp.concatenate(v) for v in (logits, msgs, dsts))
  # one softmax a parent over all its edges; alone: a parent and relation
  seg, n_seg = dst, n_d
  if alone:
    seg = dst * len(etypes) + jnp.concatenate(owner)
    n_seg = n_d * len(etypes)
  z = jnp.exp(a - jax.ops.segment_max(a, seg, n_seg)[seg])
  alpha = z / jax.ops.segment_sum(z, seg, n_seg)[seg]
  return jax.ops.segment_sum(alpha[:, :, None] * m, dst, n_d).reshape(n_d, f)


def forward(params, batch, num_layers, heads, dtype=jnp.float32,
            operands=None, alone=False):
  """Logits [b, classes] of the seeds."""
  mm = _matmul(operands)
  cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
  tree = cast(params['params'])
  h = {t: jnp.maximum(mm(v.astype(dtype), tree[f'in_{t}']['kernel'])
                      + tree[f'in_{t}']['bias'], 0)
       for t, v in batch['x'].items()}
  for i in range(num_layers):
    layer, out = tree[f'layer{i}'], {}
    for t, x in h.items():
      q = mm(x, layer[f'q_{t}']['kernel']) + layer[f'q_{t}']['bias']
      into = tuple(e for e in batch['edges'] if e[2] == t and e[0] in h)
      g = _parent_type(layer, h, q, tuple(batch['edges'][e] for e in into),
                       into, heads, operands, alone)
      o = (mm(jax.nn.gelu(g, approximate=False), layer[f'a_{t}']['kernel'])
           + layer[f'a_{t}']['bias'])
      gate = jax.nn.sigmoid(layer[f'skip_{t}'])
      out[t] = gate * o + (1 - gate) * x
    h = out
  seeds = h[batch['seed_type']][:batch['y'].shape[0]]
  return mm(seeds, tree['head']['kernel']) + tree['head']['bias']


@functools.partial(jax.jit, static_argnames=(
    'seed_type', 'num_layers', 'heads', 'dtype', 'operands', 'alone'))
def _value_and_grad(params, x, edges, y, *, seed_type, num_layers, heads,
                    dtype, operands, alone):
  def loss(p):
    logits = forward(p, {'x': x, 'edges': edges, 'y': y,
                         'seed_type': seed_type}, num_layers, heads, dtype,
                     operands, alone)
    picked = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=1) - picked)

  with jax.default_matmul_precision(
      'highest' if dtype == jnp.float32 else 'default'):
    return jax.value_and_grad(loss)(params)


def compiled(params, x, edges, y, *, seed_type, num_layers, heads,
             operands=None):
  """The float32 loss-and-gradient program compiled for arguments of
  these shapes (``jax.ShapeDtypeStruct`` will do) before anything is
  computed: what ``follow`` takes as ``program``, so that a caller can
  have it compiled while something else is."""
  return _value_and_grad.lower(
      params, x, edges, y, seed_type=seed_type, num_layers=num_layers,
      heads=heads, dtype=jnp.float32, operands=operands,
      alone=False).compile()


def loss_and_grad(params, batch, num_layers, heads, dtype=jnp.float32,
                  operands=None, alone=False, program=None):
  """float32 loss and gradient; ``dtype`` bfloat16 is the control: the
  same equations with every array in the nearest precision below."""
  if program is not None:
    loss, g = program(params, batch['x'], batch['edges'], batch['y'])
  else:
    loss, g = _value_and_grad(
        params, batch['x'], batch['edges'], batch['y'],
        seed_type=batch['seed_type'], num_layers=num_layers, heads=heads,
        dtype=dtype, operands=operands, alone=alone)
  f32 = lambda a: np.asarray(a.astype(jnp.float32))
  return float(loss), jax.tree.map(f32, g)


def follow(params, batches, num_layers, heads, lr, dtype=jnp.float32,
           operands=None, fault=None, program=None):
  """Train one Adam step a batch of ``batches`` (any iterable) from
  ``params``; returns the readings that ``compare`` takes, the
  parameters after the last step and the first gradient. ``operands``:
  what the matmuls round their operands to (None: nothing). ``fault``
  plants one for the control runs and their tests: ``half_batch`` (the
  second half of the seeds left out of the loss),
  ``per_relation_softmax`` (every relation normalised alone and the
  relations summed: R-GAT's way). ``program`` is what ``compiled`` gave
  for batches of this one shape, at these ``operands``, with no fault."""
  assert fault is None or fault in FAULTS, fault
  p0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
  p = p0
  m = jax.tree.map(np.zeros_like, p0)
  v = jax.tree.map(np.zeros_like, p0)
  losses, g1 = [], None
  for t, batch in enumerate(batches):
    if fault == 'half_batch':
      batch = dict(batch, y=batch['y'][:batch['y'].shape[0] // 2])
    loss, grad = loss_and_grad(p, batch, num_layers, heads, dtype,
                               operands, fault == 'per_relation_softmax',
                               program)
    losses.append(loss)
    g1 = grad if g1 is None else g1
    m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, m, grad)
    v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, v, grad)
    c1, c2 = 1 - B1 ** (t + 1), 1 - B2 ** (t + 1)
    p = jax.tree.map(
        lambda a, m_, v_: a - lr * (m_ / c1) / (np.sqrt(v_ / c2) + EPS),
        p, m, v)
  return readings(losses, g1, p0, p), p, g1


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
