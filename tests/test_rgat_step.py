"""R-GAT on a typed graph through ``DistHeteroTrainStep`` against the plain
reference (``glt_tpu/models/reference/rgat.py``), the node trim per type
against the untrimmed model, and the restructured ``HeteroConvLayer``
against the form it replaced, on the same parameters. Small sizes: three
node types, four relations, hidden 16, two heads."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.distributed import (DistFeature, DistHeteroGraph,
                                 DistHeteroTrainStep)
from glt_tpu.models import RGNN
from glt_tpu.models.conv import GATConv, SAGEConv, segment_mean
from glt_tpu.models.reference import rgat as reference
from glt_tpu.models.rgnn import HeteroConvLayer
from glt_tpu.parallel import make_mesh
from glt_tpu.typing import GraphPartitionData, as_str, reverse_edge_type

COUNTS = {'a': 40, 'b': 30, 'c': 5}
# traversal relations (edge_dir 'out'); messages flow along the reverses,
# and every type receives some
RELATIONS = [('a', 'aa', 'a'), ('a', 'ab', 'b'), ('b', 'bc', 'c'),
             ('c', 'ca', 'a')]
DIM, HIDDEN, HEADS, CLASSES, BATCH = 12, 16, 2, 7, 4
FANOUT = [3, 2, 2]


def typed_graph(seed=0):
  rng = np.random.default_rng(seed)
  edges = {}
  for s, r, d in RELATIONS:
    deg = rng.integers(0, 6, COUNTS[s])   # some rows have no edge
    src = np.repeat(np.arange(COUNTS[s]), deg)
    edges[(s, r, d)] = np.stack([src, rng.integers(0, COUNTS[d],
                                                   src.shape[0])])
  feats = {t: rng.standard_normal((n, DIM)).astype(np.float32)
           for t, n in COUNTS.items()}
  labels = {'a': rng.integers(0, CLASSES, COUNTS['a']).astype(np.int32)}
  return edges, feats, labels


def build_step(edges, feats, labels, layers, hops, **model_kw):
  mesh = make_mesh(1)
  book = {t: np.zeros(n, np.int32) for t, n in COUNTS.items()}
  graph = DistHeteroGraph(
      mesh, COUNTS,
      {e: [GraphPartitionData(ei, np.arange(ei.shape[1]))]
       for e, ei in edges.items()}, book)
  stores = {t: DistFeature(mesh, [(f, np.arange(f.shape[0]))], book[t],
                           f.shape[0]) for t, f in feats.items()}
  model = RGNN(edge_types=[reverse_edge_type(e) for e in RELATIONS],
               hidden_features=HIDDEN, out_features=CLASSES,
               num_layers=layers, conv='rgat', heads=HEADS, **model_kw)
  tx = optax.adam(1e-3)
  step = DistHeteroTrainStep(
      graph, stores, model, tx, labels, {e: FANOUT[:hops] for e in edges},
      batch_size_per_device=BATCH, seed_type='a', seed=0)
  return step, tx


def feed(t):
  rng = np.random.default_rng([7, t])
  return (rng.permutation(COUNTS['a'])[:BATCH].astype(np.int32),
          jax.random.key(100 + t))


def train(step, tx, params, steps=3):
  """Losses, first gradient (from Adam's mu) and the parameters after
  ``steps`` steps through ``DistHeteroTrainStep.__call__``."""
  opt, losses, first = tx.init(params), [], None
  for t in range(steps):
    seeds, key = feed(t)
    params, opt, loss = step(params, opt, seeds, np.full(1, BATCH), key)
    losses.append(float(np.asarray(loss)[0]))
    if first is None:
      first = jax.tree.map(lambda m: np.asarray(m) / (1 - reference.B1),
                           opt[0].mu)
  return losses, first, jax.tree.map(np.asarray, params)


def sampled_batch(step, feats, labels, t):
  """The reference's batch for step ``t``: what the step's own sampler
  draws on the step's key, every row and edge real."""
  seeds, key = feed(t)
  out = step.sampler.sample_from_nodes('a', seeds, key=key)
  count = {k: int(np.asarray(v)[0]) for k, v in out['node_count'].items()}
  nodes = {k: np.asarray(v)[0][:count[k]] for k, v in out['node'].items()}
  np.testing.assert_array_equal(nodes['a'][:BATCH], seeds)
  edges = {}
  for e in out['row']:
    ok = np.asarray(out['edge_mask'][e])[0]
    edges[e] = (np.asarray(out['row'][e])[0][ok],
                np.asarray(out['col'][e])[0][ok])
  return {'x': {k: feats[k][v] for k, v in nodes.items()}, 'edges': edges,
          'y': labels['a'][seeds], 'seed_type': 'a'}


@pytest.mark.parametrize('layers', [2, 3])
def test_step_matches_the_reference(layers):
  edges, feats, labels = typed_graph()
  step, tx = build_step(edges, feats, labels, layers, layers, head=True)
  params0 = step.init_params(jax.random.key(3))
  losses, first, params = train(step, tx, params0)
  # the promise is on: every layer reduced groups over the fanout axis
  assert all(sum(g.values()) > 0 for g in step.layer_groups)
  ref, ref_params, ref_first = reference.follow(
      params0, (sampled_batch(step, feats, labels, t) for t in range(3)),
      layers, HEADS, 1e-3)
  np.testing.assert_allclose(losses, ref['loss'], rtol=2e-5)
  flat = lambda tree: {jax.tree_util.keystr(k): np.asarray(v) for k, v in
                       jax.tree_util.tree_leaves_with_path(tree)}
  got, want = flat(first), flat(ref_first)
  assert set(got) == set(want) and len(got) >= 3 * 3 * layers + 2
  scale = max(np.abs(v).max() for v in want.values())
  for k in want:
    np.testing.assert_allclose(got[k], want[k], rtol=1e-3,
                               atol=2e-6 * scale, err_msg=k)
  got, want = flat(params), flat(ref_params)
  for k in want:   # three Adam steps of 1e-3 move an element by 3e-3
    np.testing.assert_allclose(got[k], want[k], atol=1e-4, err_msg=k)
  program = reference.readings(losses, first, jax.tree.map(
      np.asarray, params0), params)
  gaps = reference.compare(program, ref)
  assert max(gaps.values()) < 1e-3, gaps
  # the control: the same equations in bfloat16 are told apart
  low, _, _ = reference.follow(
      params0, (sampled_batch(step, feats, labels, t) for t in range(3)),
      layers, HEADS, 1e-3, dtype=jnp.bfloat16)
  assert max(reference.compare(low, ref).values()) > 10 * max(
      gaps.values())


@pytest.mark.parametrize('fault', ['half_batch', 'no_attention'])
def test_a_planted_fault_is_told_apart(fault):
  edges, feats, labels = typed_graph()
  step, tx = build_step(edges, feats, labels, 2, 2, head=True)
  params0 = step.init_params(jax.random.key(3))
  batches = [sampled_batch(step, feats, labels, t) for t in range(3)]
  ref, _, _ = reference.follow(params0, batches, 2, HEADS, 1e-3)
  bad, _, _ = reference.follow(params0, batches, 2, HEADS, 1e-3,
                               fault=fault)
  assert reference.compare(bad, ref)['grad_gap'] > 0.02


@pytest.mark.parametrize('layers,hops', [(2, 2), (3, 3), (3, 2), (2, 3)])
def test_node_trim_matches_untrimmed(layers, hops):
  """Labels are hop-compact per type, so computing only the rows a later
  layer reads changes neither the loss nor any gradient."""
  edges, feats, labels = typed_graph(1)
  out = {}
  for trim in (True, False):
    step, tx = build_step(edges, feats, labels, layers, hops, head=True,
                          trim=trim)
    out[trim] = train(step, tx, step.init_params(jax.random.key(5)),
                      steps=2) + (step,)
  (l1, g1, p1, trimmed), (l0, g0, p0, full) = out[True], out[False]
  np.testing.assert_allclose(l1, l0, rtol=1e-5)
  for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
  assert (jax.tree.structure(p1) == jax.tree.structure(p0))
  # the counter says what was computed: the seeds' type shrinks to the
  # batch in the last layer, the untrimmed model computes every slot
  assert trimmed.layer_rows[-1]['a'] == BATCH
  assert full.layer_rows[-1] == full.node_budget
  assert trimmed.layer_rows[0]['a'] <= trimmed.node_budget['a']
  assert sum(trimmed.layer_rows[-1].values()) < sum(
      full.layer_rows[-1].values())


def test_budgets_are_the_sum_of_slots():
  edges, feats, labels = typed_graph()
  step, _ = build_step(edges, feats, labels, 3, 3, head=True)
  # frontiers by hand, fanout 3, 2, 2: a = 4, 12, 24, 48 + 48 (from c);
  # b = 0, 12, 24, 48; c = 0, 0, 24, 48
  assert step.node_budget == {'a': 4 + 12 + 24 + 96, 'b': 12 + 24 + 48,
                              'c': 24 + 48}
  fk = reverse_edge_type
  assert step.edge_budget[fk(('a', 'aa', 'a'))] == 12 + 24 + 48
  assert step.edge_budget[fk(('c', 'ca', 'a'))] == 0 + 0 + 48
  assert step.layer_rows is None   # nothing traced yet


# -- the form that HeteroConvLayer replaced, kept here for the comparison --

class _OldGATConv(nn.Module):
  out_features: int
  heads: int = 1
  concat: bool = True

  @nn.compact
  def __call__(self, x, row, col, edge_mask):
    n, h, f = x.shape[0], self.heads, self.out_features
    ok = edge_mask & (row >= 0) & (col >= 0)
    proj = nn.Dense(h * f, use_bias=False, name='proj')(x).reshape(n, h, f)
    att_src = self.param('att_src', nn.initializers.glorot_uniform(),
                         (h, f), jnp.float32)
    att_dst = self.param('att_dst', nn.initializers.glorot_uniform(),
                         (h, f), jnp.float32)
    src = jnp.take(proj, jnp.clip(row, 0, n - 1), axis=0)
    dst = jnp.take(proj, jnp.clip(col, 0, n - 1), axis=0)
    logit = nn.leaky_relu((src * att_src).sum(-1) + (dst * att_dst).sum(-1),
                          negative_slope=0.2)
    seg = jnp.where(ok, col, n)
    seg_max = jax.ops.segment_max(
        jnp.where(ok[:, None], logit, -jnp.inf), seg, n + 1)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    z = jnp.where(ok[:, None], jnp.exp(logit - seg_max[seg]), 0.0)
    denom = jax.ops.segment_sum(z, seg, n + 1)
    alpha = z / jnp.maximum(denom[seg], 1e-16)
    out = jax.ops.segment_sum(src * alpha[:, :, None], seg, n + 1)[:n]
    return out.reshape(n, h * f) if self.concat else out.mean(axis=1)


class _OldSAGEConv(nn.Module):
  out_features: int

  @nn.compact
  def __call__(self, x, row, col, edge_mask):
    n = x.shape[0]
    agg = segment_mean(jnp.take(x, jnp.clip(row, 0, n - 1), axis=0),
                       jnp.clip(col, 0, n - 1),
                       edge_mask & (row >= 0) & (col >= 0), n)
    return (nn.Dense(self.out_features, name='lin_root')(x)
            + nn.Dense(self.out_features, use_bias=False,
                       name='lin_nbr')(agg))


class _OldHeteroConvLayer(nn.Module):
  """``[src || dst]`` stacked, both projected, the parents sliced off."""
  edge_types: tuple
  out_features: int
  conv: str = 'sage'
  heads: int = 1

  @nn.compact
  def __call__(self, x_dict, row_dict, col_dict, mask_dict):
    out = {}
    for etype in self.edge_types:
      src_t, _, dst_t = etype
      n_src = x_dict[src_t].shape[0]
      name = f'conv_{as_str(etype)}'
      conv = (_OldGATConv(self.out_features, heads=self.heads, concat=False,
                          name=name) if self.conv == 'gat'
              else _OldSAGEConv(self.out_features, name=name))
      same = src_t == dst_t
      x_cat = x_dict[src_t] if same else jnp.concatenate(
          [x_dict[src_t], x_dict[dst_t]], axis=0)
      h = conv(x_cat, row_dict[etype],
               col_dict[etype] + (0 if same else n_src), mask_dict[etype])
      out[dst_t] = out.get(dst_t, 0) + (h if same else h[n_src:])
    return out


@pytest.mark.parametrize('conv', ['sage', 'gat'])
def test_layer_matches_the_stacked_form_it_replaced(conv):
  rng = np.random.default_rng(2)
  etypes = (('a', 'aa', 'a'), ('b', 'ba', 'a'), ('a', 'ab', 'b'),
            ('c', 'cb', 'b'), ('b', 'bc', 'c'))
  x = {t: jnp.asarray(rng.standard_normal((n, DIM)), jnp.float32)
       for t, n in COUNTS.items()}
  row, col, mask = {}, {}, {}
  for s, r, d in etypes:
    row[(s, r, d)] = jnp.asarray(rng.integers(0, COUNTS[s], 50), jnp.int32)
    col[(s, r, d)] = jnp.asarray(rng.integers(0, COUNTS[d], 50), jnp.int32)
    mask[(s, r, d)] = jnp.asarray(rng.random(50) < 0.8)
  old = _OldHeteroConvLayer(etypes, HIDDEN, conv=conv, heads=HEADS)
  new = HeteroConvLayer(etypes, HIDDEN, conv=conv, heads=HEADS)
  params = old.init(jax.random.key(0), x, row, col, mask)
  assert (jax.tree.structure(params) == jax.tree.structure(
      new.init(jax.random.key(0), x, row, col, mask)))
  want = old.apply(params, x, row, col, mask)
  got = new.apply(params, x, row, col, mask)
  assert set(got) == set(want) == {'a', 'b', 'c'}
  for t in want:
    np.testing.assert_allclose(got[t], want[t], rtol=1e-5, atol=1e-6)
  grad = lambda m: jax.grad(lambda p: sum(
      (v ** 2).sum() for t, v in m.apply(p, x, row, col, mask).items()
      if t in want))(params)
  for a, b in zip(jax.tree.leaves(grad(new)), jax.tree.leaves(grad(old))):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_remat_changes_nothing():
  edges, feats, labels = typed_graph()
  runs = []
  for remat in (False, True):
    step, tx = build_step(edges, feats, labels, 2, 2, head=True,
                          remat=remat)
    runs.append(train(step, tx, step.init_params(jax.random.key(3)),
                      steps=2))
  np.testing.assert_allclose(runs[0][0], runs[1][0], rtol=1e-6)
  for a, b in zip(jax.tree.leaves(runs[0][1]), jax.tree.leaves(runs[1][1])):
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)


# -- the promise of parent-major edge slots (HeteroBatch.hop_fanouts_dict) --

def padded_batch(step, feats, labels, t=0):
  """Step ``t``'s batch as the step's own program assembles it: the
  padded buffers of the step's sampler on the step's key, the rows of
  every slot, and the step's static promises."""
  from glt_tpu.loader.transform import HeteroBatch
  seeds, key = feed(t)
  out = step.sampler.sample_from_nodes('a', seeds, key=key)
  first = lambda d: {k: jnp.asarray(np.asarray(v)[0]) for k, v in d.items()}
  node = first(out['node'])
  return HeteroBatch(
      x_dict={k: jnp.asarray(feats[k])[jnp.maximum(v, 0)]
              for k, v in node.items()},
      row_dict=first(out['row']), col_dict=first(out['col']),
      edge_mask_dict=first(out['edge_mask']), node_dict=node,
      node_count_dict=first(out['node_count']),
      y_dict={'a': jnp.asarray(labels['a'][seeds])}, input_type='a',
      batch_size=BATCH, **step._batch_static)


def _loss_and_grads(model, params, batch):
  def loss(p, x_dict):
    logits = model.apply(p, batch.replace(x_dict=x_dict))
    return -jax.nn.log_softmax(logits)[
        jnp.arange(BATCH), batch.y_dict['a']].mean(), logits
  (_, logits), grads = jax.jit(jax.value_and_grad(
      loss, argnums=(0, 1), has_aux=True))(params, batch.x_dict)
  return logits, grads


@pytest.mark.parametrize('conv,remat', [
    ('rgat', False), ('rgat', True), ('rsage', False), ('rsage', True)])
def test_rgnn_with_the_promise_and_with_it_withheld(conv, remat):
  """One batch, the promise on and withheld (``hop_fanouts_dict=None``):
  the same logits and the same gradients for the parameters and the
  features, and the counter says which path ran. The promise holds of
  the batch (the typed hop loop keeps it)."""
  edges, feats, labels = typed_graph()
  step, _ = build_step(edges, feats, labels, 2, 2, head=True)
  batch = padded_batch(step, feats, labels)
  for e, groups in batch.hop_fanouts_dict.items():
    col, mask = (np.asarray(d[e]) for d in (batch.col_dict,
                                            batch.edge_mask_dict))
    heads = []
    for off, s, k in groups:
      c = col[off:off + s * k].reshape(s, k)
      assert (c == c[:, :1]).all(), e
      heads += c[mask[off:off + s * k].reshape(s, k).any(axis=1), 0].tolist()
    assert len(heads) == len(set(heads)), e
    assert sum(s * k for _, s, k in groups) == col.shape[0] or not groups
  model = RGNN(edge_types=[reverse_edge_type(e) for e in RELATIONS],
               hidden_features=HIDDEN, out_features=CLASSES, num_layers=2,
               conv=conv, heads=HEADS, head=True, remat=remat)
  params = jax.jit(model.init)(jax.random.key(1), batch)
  withheld = batch.replace(hop_fanouts_dict=None)
  assert all(sum(g.values()) > 0 for g in model.layer_groups(batch))
  assert all(sum(g.values()) == 0 for g in model.layer_groups(withheld))
  got, g_got = _loss_and_grads(model, params, batch)
  want, g_want = _loss_and_grads(model, params, withheld)
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
  flat = lambda tree: {jax.tree_util.keystr(k): np.asarray(v) for k, v in
                       jax.tree_util.tree_leaves_with_path(tree)}
  g_got, g_want = flat(g_got), flat(g_want)
  assert set(g_got) == set(g_want)
  for k in sorted(g_want, key=lambda k: 'att_dst' not in k):
    np.testing.assert_allclose(g_got[k], g_want[k], rtol=1e-4, atol=1e-6,
                               err_msg=k)


def test_rgnn_without_the_promise_is_the_layers_without_groups():
  """No promise, no change: on a batch without ``hop_fanouts_dict`` RGNN
  is its layers applied with no ``groups`` at all, bit for bit, and a
  loader's batch carries no promise."""
  from glt_tpu.loader.transform import HeteroBatch
  assert HeteroBatch.__dataclass_fields__['hop_fanouts_dict'].default is None
  edges, feats, labels = typed_graph()
  step, _ = build_step(edges, feats, labels, 2, 2, head=True)
  batch = padded_batch(step, feats, labels).replace(hop_fanouts_dict=None)
  model = step.model
  params = model.init(jax.random.key(1), batch)
  x = dict(batch.x_dict)
  for i, (ends, rows, groups) in enumerate(model.layer_plan(batch)):
    assert groups is None
    cut = lambda d: {e: v[:ends[e]] for e, v in d.items()}
    layer = HeteroConvLayer(list(model.edge_types), HIDDEN, conv='gat',
                            heads=HEADS, concat=True)
    x = layer.apply({'params': params['params'][f'layer{i}']}, x,
                    cut(batch.row_dict), cut(batch.col_dict),
                    cut(batch.edge_mask_dict), rows)
    x = {t: nn.relu(v) for t, v in x.items()}
  head = params['params']['head']
  want = x['a'][:BATCH] @ head['kernel'] + head['bias']
  np.testing.assert_array_equal(np.asarray(model.apply(params, batch)),
                                np.asarray(want))


def test_a_block_across_the_trim_is_refused():
  edges, feats, labels = typed_graph()
  step, _ = build_step(edges, feats, labels, 2, 2, head=True)
  batch = step.dummy_batch()
  e = reverse_edge_type(('a', 'aa', 'a'))
  (o0, s0, k0), (o1, s1, k1) = batch.hop_fanouts_dict[e]
  bad = dict(batch.hop_fanouts_dict)
  bad[e] = ((o0, s0 + 1, k0), (o1 + k0, s1 - 1, k1))
  with pytest.raises(ValueError, match='across the edge trim'):
    step.model.layer_plan(batch.replace(hop_fanouts_dict=bad))


def test_layer_groups_counter_and_gauge():
  """``DistHeteroTrainStep.layer_groups``: per layer and relation the
  groups reduced over the fanout axis, the frontier slots of the hops
  the layer keeps; 0 for every relation when the promise is withheld;
  the gauge carries the relation."""
  from glt_tpu.obs import get_registry
  edges, feats, labels = typed_graph()
  step, tx = build_step(edges, feats, labels, 3, 3, head=True)
  assert step.layer_groups is None   # nothing traced yet
  params = step.init_params(jax.random.key(3))
  train(step, tx, params, steps=1)
  fk = reverse_edge_type
  # a hop's groups are its frontier's slots, by hand
  # (test_budgets_are_the_sum_of_slots): a = 4, 12, 24; b = 0, 12, 24;
  # c = 0, 0, 24. Layer i keeps 3 - i hops.
  assert step.layer_groups[0] == {
      fk(('a', 'aa', 'a')): 4 + 12 + 24, fk(('a', 'ab', 'b')): 4 + 12 + 24,
      fk(('b', 'bc', 'c')): 12 + 24, fk(('c', 'ca', 'a')): 24}
  assert step.layer_groups[1] == {
      fk(('a', 'aa', 'a')): 4 + 12, fk(('a', 'ab', 'b')): 4 + 12,
      fk(('b', 'bc', 'c')): 12, fk(('c', 'ca', 'a')): 0}
  assert step.layer_groups[2] == {
      fk(('a', 'aa', 'a')): 4, fk(('a', 'ab', 'b')): 4,
      fk(('b', 'bc', 'c')): 0, fk(('c', 'ca', 'a')): 0}
  # non-zero wherever a layer reads a real block of the relation
  offs = step._batch_static['edge_hop_offsets_dict']
  for i, groups in enumerate(step.layer_groups):
    for e, n in groups.items():
      assert (n > 0) == (offs[e][3 - i] > 0), (i, e)
  reg = get_registry()
  for i, groups in enumerate(step.layer_groups):
    for e, n in groups.items():
      assert reg.get('model_grouped_aggregation', -1.0,
                     fn='train.hetero_step', layer=str(i),
                     relation=as_str(e)) == n
  # the SAGE step's series carry no relation label, as before
  from glt_tpu.obs.perf import gauge_grouped_aggregation
  gauge_grouped_aggregation('test.sage', (7, 0))
  assert reg.get('model_grouped_aggregation', -1.0, fn='test.sage',
                 layer='0') == 7
  # withheld: the segment path everywhere, and the counter says so
  step2, tx2 = build_step(edges, feats, labels, 3, 3, head=True)
  step2._batch_static['hop_fanouts_dict'] = None
  step2._step_fn = step2._build()
  l2, g2, _ = train(step2, tx2, params, steps=1)
  assert all(n == 0 for g in step2.layer_groups for n in g.values())
  l1, g1, _ = train(step, tx, params, steps=1)
  np.testing.assert_allclose(l1, l2, rtol=1e-6)
  for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_step_on_one_partition_counts_its_chunks_and_trains_alike(
    monkeypatch):
  """The typed twin of ``test_parallel``'s one-shard step test: every
  type's store gathers the chunks of its request slots that hold a node,
  and the training is the plain gather's bit for bit."""
  from glt_tpu.parallel import dist_feature
  code_chunk = dist_feature.SERVE_CHUNK
  edges, feats, labels = typed_graph()

  def two_steps(chunk):
    monkeypatch.setattr(dist_feature, 'SERVE_CHUNK', chunk)
    step, tx = build_step(edges, feats, labels, 2, 2)
    losses, _, params = train(step, tx, step.init_params(jax.random.key(0)),
                              steps=2)
    return step, losses, params

  step, losses, params = two_steps(16)
  counted, slots = step.counters(), step.counter_slots()
  types = step.counter_node_types
  assert sorted(set(counted) - {'step'}) == [
      'edges_by_hop', 'hop_rows_read', 'nodes_by_hop', 'store_chunks']
  assert counted['store_chunks'].shape == (2, 1, len(types))
  assert slots['store_chunks'].tolist() == [
      max(1, -(-step.node_budget[t] // 16)) for t in types]
  assert max(slots['store_chunks']) > 1   # the loop is in the program
  # a type's requests are a live prefix of node_count slots: its chunks
  node_count = counted['nodes_by_hop'].sum(-1)
  np.testing.assert_array_equal(counted['store_chunks'],
                                -(-node_count // 16))
  assert (counted['store_chunks'] <= slots['store_chunks']).all()
  assert counted['store_chunks'].sum() < 2 * slots['store_chunks'].sum()
  plain, plain_losses, plain_params = two_steps(code_chunk)
  assert plain.counter_slots()['store_chunks'].tolist() == [1] * len(types)
  assert losses == plain_losses
  jax.tree.map(lambda got, want: np.testing.assert_array_equal(
      got.view(np.uint32), want.view(np.uint32)), params, plain_params)
