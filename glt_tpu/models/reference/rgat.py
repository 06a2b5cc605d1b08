"""The plain reference for R-GAT over a typed sampled subgraph: the
equations in ``jax.numpy`` float32 at ``highest`` matmul precision, a
dense loop over relations, ``segment_max`` / ``segment_sum`` softmax, no
trimming, no padding tricks; loss, gradient, and Adam by hand. It imports
nothing of ``glt_tpu`` (``chipbench/reference_rgat.py`` is its copy, and
``tests/test_rgat_reference.py`` holds the two to one text).

One layer, per destination type ``d``, with relations ``r = (s, r, d)``:
``P_r = X_s W_r`` reshaped ``[n_s, H, F]``; ``e_ij = LeakyReLU_0.2(a_src_r
. P_r[j] + a_dst_r . (X_d W_r)[i])`` per head; ``alpha`` the softmax of
``e`` over the sampled in-edges of ``i`` within ``r``; ``out_r[i] = concat_h
sum_j alpha_ij P_r[j]``; ``h_d = sum_r out_r``; ReLU after every layer, a
linear head on the seeds' rows, mean softmax cross-entropy.

A batch is ``{'x': {type: [n_t, D]}, 'edges': {(s, r, d): (src [e], dst
[e])}, 'y': [b], 'seed_type': type}``: every row and every edge real,
labels are positions in ``x[type]``, the seeds the first ``b`` rows of
their type. Parameters are the tree of ``models/rgnn.py::RGNN(head=True)``:
``layer<i>/conv_<s>__<r>__<d>/{proj/kernel, att_src, att_dst}`` and
``head/{kernel, bias}``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
SLOPE = 0.2


def relation_name(etype):
  return 'conv_' + '__'.join(etype)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _relation(p, x_src, x_dst, src, dst, heads):
  """``out_r`` [n_d, H * F] of one relation. Its intermediates are made
  again in the backward pass (a block at a time, at the cell's size)."""
  w = p['proj']['kernel']
  n_s, n_d = x_src.shape[0], x_dst.shape[0]
  f = w.shape[1] // heads
  proj = (x_src @ w).reshape(n_s, heads, f)
  proj_dst = (x_dst @ w).reshape(n_d, heads, f)
  e = ((proj * p['att_src']).sum(-1)[src]
       + (proj_dst * p['att_dst']).sum(-1)[dst])
  e = jnp.where(e > 0, e, SLOPE * e)
  top = jax.ops.segment_max(e, dst, n_d)
  z = jnp.exp(e - top[dst])
  alpha = z / jax.ops.segment_sum(z, dst, n_d)[dst]
  out = jax.ops.segment_sum(proj[src] * alpha[:, :, None], dst, n_d)
  return out.reshape(n_d, heads * f)


def forward(params, batch, num_layers, heads, dtype=jnp.float32):
  """Logits [b, classes] of the seeds."""
  cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
  x = {t: v.astype(dtype) for t, v in batch['x'].items()}
  tree = params['params']
  for i in range(num_layers):
    layer, out = cast(tree[f'layer{i}']), {}
    for etype, (src, dst) in batch['edges'].items():
      s, _, d = etype
      if s not in x or d not in x:   # a type that nothing flowed into
        continue
      h = _relation(layer[relation_name(etype)], x[s], x[d], src, dst,
                    heads)
      out[d] = out[d] + h if d in out else h
    x = {t: jnp.maximum(v, 0) for t, v in out.items()}
  head = cast(tree['head'])
  seeds = x[batch['seed_type']][:batch['y'].shape[0]]
  return seeds @ head['kernel'] + head['bias']


@functools.partial(jax.jit, static_argnames=(
    'seed_type', 'num_layers', 'heads', 'dtype'))
def _value_and_grad(params, x, edges, y, *, seed_type, num_layers, heads,
                    dtype):
  def loss(p):
    logits = forward(p, {'x': x, 'edges': edges, 'y': y,
                         'seed_type': seed_type}, num_layers, heads, dtype)
    picked = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=1) - picked)

  with jax.default_matmul_precision(
      'highest' if dtype == jnp.float32 else 'default'):
    return jax.value_and_grad(loss)(params)


def compiled(params, x, edges, y, *, seed_type, num_layers, heads):
  """The float32 loss-and-gradient program compiled for arguments of
  these shapes (``jax.ShapeDtypeStruct`` will do) before anything is
  computed: what ``follow`` takes as ``program``, so that a caller can
  have it compiled while something else is."""
  return _value_and_grad.lower(
      params, x, edges, y, seed_type=seed_type, num_layers=num_layers,
      heads=heads, dtype=jnp.float32).compile()


def loss_and_grad(params, batch, num_layers, heads, dtype=jnp.float32,
                  program=None):
  """float32 loss and gradient; ``dtype`` bfloat16 is the control: the
  same equations in the nearest precision below."""
  if program is not None:
    loss, g = program(params, batch['x'], batch['edges'], batch['y'])
  else:
    loss, g = _value_and_grad(
        params, batch['x'], batch['edges'], batch['y'],
        seed_type=batch['seed_type'], num_layers=num_layers, heads=heads,
        dtype=dtype)
  f32 = lambda a: np.asarray(a.astype(jnp.float32))
  return float(loss), jax.tree.map(f32, g)


def follow(params, batches, num_layers, heads, lr, dtype=jnp.float32,
           fault=None, program=None):
  """Train one Adam step a batch of ``batches`` (any iterable) from
  ``params``; returns the readings that ``compare`` takes, the
  parameters after the last step and the first gradient. ``fault`` plants one for the control runs and
  their tests: ``half_batch`` (the second half of the seeds left out of
  the loss), ``no_attention`` (every ``att_src`` and ``att_dst`` nought:
  uniform attention). ``program`` is what ``compiled`` gave for batches
  of this one shape."""
  p0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
  p = p0
  m = jax.tree.map(np.zeros_like, p0)
  v = jax.tree.map(np.zeros_like, p0)
  losses, g1 = [], None
  for t, batch in enumerate(batches):
    if fault == 'half_batch':
      batch = dict(batch, y=batch['y'][:batch['y'].shape[0] // 2])
    used = p
    if fault == 'no_attention':
      used = jax.tree_util.tree_map_with_path(
          lambda k, a: np.zeros_like(a)
          if 'att_' in jax.tree_util.keystr(k) else a, p)
    loss, grad = loss_and_grad(used, batch, num_layers, heads, dtype,
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
  reference's, by ``chipbench/reference.py::compare``'s rule: norms go by
  the worst leaf, the gap of the two norms over the reference's norm of
  that leaf or of the median leaf, whichever is larger; leaves whose
  reference gradient is under a thousandth of the median leaf's move
  under Adam by round-off alone and are left out of the change."""
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
