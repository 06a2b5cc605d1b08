"""The Heterogeneous Graph Transformer on a typed graph through
``DistHeteroTrainStep`` against the plain reference
(``glt_tpu/models/reference/hgt.py``): the step, the grouped form against
the segment form, the softmax that crosses relations, the alignment of
the relations' groups that it leans on, the node trim, the counters.
Small sizes: ``test_rgat_step``'s three node types and four relations
(type ``a`` is the parent of two), hidden 16, two heads."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.distributed import (DistFeature, DistHeteroGraph,
                                 DistHeteroTrainStep)
from glt_tpu.models import HGT, plan
from glt_tpu.models.hgt import (Relation, grouped_joint_attention,
                                segment_joint_attention)
from glt_tpu.models.reference import hgt as reference
from glt_tpu.parallel import make_mesh
from glt_tpu.typing import GraphPartitionData, as_str, reverse_edge_type

from test_rgat_step import (BATCH, CLASSES, COUNTS, FANOUT, HEADS, HIDDEN,
                            RELATIONS, padded_batch, sampled_batch,
                            train, typed_graph)

FLOW = [reverse_edge_type(e) for e in RELATIONS]


def make_model(layers, **kw):
  return HGT(node_types=list(COUNTS), edge_types=FLOW,
             hidden_features=HIDDEN, out_features=CLASSES,
             num_layers=layers, heads=HEADS, **kw)


def build_step(edges, feats, labels, layers, hops, **model_kw):
  mesh = make_mesh(1)
  book = {t: np.zeros(n, np.int32) for t, n in COUNTS.items()}
  graph = DistHeteroGraph(
      mesh, COUNTS,
      {e: [GraphPartitionData(ei, np.arange(ei.shape[1]))]
       for e, ei in edges.items()}, book)
  stores = {t: DistFeature(mesh, [(f, np.arange(f.shape[0]))], book[t],
                           f.shape[0]) for t, f in feats.items()}
  tx = optax.adam(1e-3)
  step = DistHeteroTrainStep(
      graph, stores, make_model(layers, **model_kw), tx, labels,
      {e: FANOUT[:hops] for e in edges}, batch_size_per_device=BATCH,
      seed_type='a', seed=0)
  return step, tx


def flat(tree):
  return {jax.tree_util.keystr(k): np.asarray(v) for k, v in
          jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize('layers,remat', [(2, False), (3, True)])
def test_step_matches_the_reference(layers, remat):
  edges, feats, labels = typed_graph()
  step, tx = build_step(edges, feats, labels, layers, layers, remat=remat)
  params0 = step.init_params(jax.random.key(3))
  losses, first, params = train(step, tx, params0)
  # the promise is on: every layer reduced groups over the fanout axis
  assert all(sum(g.values()) > 0 for g in step.layer_groups)
  batches = [sampled_batch(step, feats, labels, t) for t in range(3)]
  ref, ref_params, ref_first = reference.follow(params0, batches, layers,
                                                HEADS, 1e-3)
  np.testing.assert_allclose(losses, ref['loss'], rtol=2e-5)
  got, want = flat(first), flat(ref_first)
  assert set(got) == set(want)
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
  low, _, _ = reference.follow(params0, batches, layers, HEADS, 1e-3,
                               dtype=jnp.bfloat16)
  assert max(reference.compare(low, ref).values()) > 10 * max(
      gaps.values())


@pytest.mark.parametrize('fault', reference.FAULTS)
def test_a_planted_fault_is_told_apart(fault):
  edges, feats, labels = typed_graph()
  step, tx = build_step(edges, feats, labels, 2, 2)
  params0 = step.init_params(jax.random.key(3))
  batches = [sampled_batch(step, feats, labels, t) for t in range(3)]
  ref, _, _ = reference.follow(params0, batches, 2, HEADS, 1e-3)
  bad, _, _ = reference.follow(params0, batches, 2, HEADS, 1e-3,
                               fault=fault)
  assert reference.compare(bad, ref)['grad_gap'] > 0.02


def test_rounded_operands_move_the_reference_a_little():
  """``operands=bfloat16``: what a TPU's default precision does to a
  float32 matmul, on any backend; near the exact reference, not on it."""
  edges, feats, labels = typed_graph()
  step, tx = build_step(edges, feats, labels, 2, 2)
  params0 = step.init_params(jax.random.key(3))
  batches = [sampled_batch(step, feats, labels, t) for t in range(2)]
  exact, _, _ = reference.follow(params0, batches, 2, HEADS, 1e-3)
  rounded, _, _ = reference.follow(params0, batches, 2, HEADS, 1e-3,
                                   operands=jnp.bfloat16)
  gaps = reference.compare(rounded, exact)
  assert 1e-5 < gaps['grad_gap'] < 0.1, gaps
  assert reference.default_operands() is None   # the CPU rounds nothing


def _loss_and_grads(model, params, batch):
  def loss(p, x_dict):
    logits = model.apply(p, batch.replace(x_dict=x_dict))
    return -jax.nn.log_softmax(logits)[
        jnp.arange(BATCH), batch.y_dict['a']].mean(), logits
  (_, logits), grads = jax.jit(jax.value_and_grad(
      loss, argnums=(0, 1), has_aux=True))(params, batch.x_dict)
  return logits, grads


@pytest.mark.parametrize('remat', [False, True])
def test_hgt_with_the_promise_and_with_it_withheld(remat):
  """One batch, the promise on and withheld (``hop_fanouts_dict=None``):
  the grouped form and the segment form give the same logits and the
  same gradients for the parameters and the features, to float32
  rounding, and the counter says which form ran."""
  edges, feats, labels = typed_graph()
  step, _ = build_step(edges, feats, labels, 2, 2)
  batch = padded_batch(step, feats, labels)
  model = make_model(2, remat=remat)
  params = jax.jit(model.init)(jax.random.key(1), batch)
  withheld = batch.replace(hop_fanouts_dict=None)
  assert all(sum(g.values()) > 0 for g in model.layer_groups(batch))
  assert all(sum(g.values()) == 0 for g in model.layer_groups(withheld))
  got, g_got = _loss_and_grads(model, params, batch)
  want, g_want = _loss_and_grads(model, params, withheld)
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
  g_got, g_want = flat(g_got), flat(g_want)
  assert set(g_got) == set(g_want)
  for k in g_want:
    np.testing.assert_allclose(g_got[k], g_want[k], rtol=1e-4, atol=1e-6,
                               err_msg=k)


def test_the_relations_into_a_type_share_their_parents():
  """What the joint softmax leans on: for every parent type and hop the
  relations into it carry equal ``S`` in ``hop_fanouts_dict`` and the
  same parent, group by group; a group is live in none of them or heads
  one parent."""
  edges, feats, labels = typed_graph()
  step, _ = build_step(edges, feats, labels, 3, 3)
  seen = 0
  for t in range(3):
    batch = padded_batch(step, feats, labels, t)
    blocks = plan.group_hops(batch.hop_fanouts_dict,
                             batch.edge_hop_offsets_dict)
    by_parent = {}
    for e, mine in blocks.items():
      for off, s, k, hop in mine:
        col = np.asarray(batch.col_dict[e])[off:off + s * k].reshape(s, k)
        live = np.asarray(batch.edge_mask_dict[e])[
            off:off + s * k].reshape(s, k)
        assert (col == col[:, :1]).all()
        by_parent.setdefault((e[2], hop), []).append(
            (s, col[:, 0], live.any(axis=1)))
    for (dst, hop), found in by_parent.items():
      s0, parents0, _ = found[0]
      for s, parents, live in found[1:]:
        seen += 1
        assert s == s0, (dst, hop)
        np.testing.assert_array_equal(parents, parents0)
      # a parent heads one live group over all the relations
      heads = np.concatenate([parents0[np.any([f[2] for f in found],
                                              axis=0)]])
      assert len(heads) == len(set(heads.tolist())), (dst, hop)
  assert seen > 0   # type ``a`` is the parent of two relations


def _one_parent_type(rng, logit_scale=1.0):
  """One parent type of 3 nodes under two relations of 2 groups x 3
  slots each, messages one-hot by child so that ``g`` spells out the
  attention weights: heads 2, d 8, ``V_u^h = onehot(u)``."""
  h, d = 2, 8
  f = h * d
  ident = jnp.stack([jnp.eye(d)] * h)
  q = jnp.asarray(rng.standard_normal((3, f)) * logit_scale, jnp.float32)
  relations, raw = [], []
  for i, n_src in enumerate((7, 6)):
    keys = jnp.asarray(rng.standard_normal((n_src, f)), jnp.float32)
    onehot = jnp.tile(jnp.eye(d, dtype=jnp.float32)[:n_src], (1, h))
    # the children's rows carry their keys; values are made one-hot by a
    # linear of their own below
    row = jnp.asarray([0, 1, 2, 3, 4, 5][:6], jnp.int32) % n_src
    col = jnp.asarray([2, 2, 2, 0, 0, 0], jnp.int32)
    mask = jnp.asarray([True, True, i == 0, True, False, True])
    raw.append((keys, onehot, row, col, mask))
    relations.append(Relation(
        f'r{i}', (keys, onehot), row, col, mask, ident, ident,
        jnp.ones((h,), jnp.float32)))
  return q, relations, raw, h, d


def _expected_weights(q, raw, h, d, joint=True):
  """Per parent and head the softmax over its valid edges, by numpy:
  ``{(relation, slot): [H]}``."""
  logits = {}
  for i, (keys, _, row, col, mask) in enumerate(raw):
    for slot in range(row.shape[0]):
      if mask[slot]:
        a = (np.asarray(q)[col[slot]].reshape(h, d)
             * np.asarray(keys)[row[slot]].reshape(h, d)).sum(-1)
        logits[i, slot] = (int(col[slot]), a / np.sqrt(d))
  out = {}
  for key, (parent, a) in logits.items():
    peers = [v for k, (p, v) in logits.items()
             if p == parent and (joint or k[0] == key[0])]
    top = np.max(peers, axis=0)
    out[key] = np.exp(a - top) / np.sum(np.exp(np.asarray(peers) - top),
                                        axis=0)
  return out


@pytest.mark.parametrize('logit_scale', [1.0, 40.0])
def test_the_softmax_crosses_relations(logit_scale):
  """A parent's weights over the children of all its relations sum to
  one a head, are the exact softmax (logits of +-100 at scale 40: a
  clip at 30 would not be), and differ from the per-relation ones."""
  rng = np.random.default_rng(5)
  q, relations, raw, h, d = _one_parent_type(rng, logit_scale)
  g = np.asarray(segment_joint_attention(q, relations, h)).reshape(3, h, d)
  want = _expected_weights(q, raw, h, d)
  if logit_scale > 1:
    top = max(np.abs(np.log(np.maximum(w, 1e-300))).max()
              for w in want.values())
    assert top > 60   # some weight is under exp(-60): the logits are far
  total = np.zeros((3, h))
  for (i, slot), w in want.items():
    _, _, row, col, _ = raw[i]
    total[int(col[slot])] += w
  # parents 0 and 2 have children in both relations, parent 1 has none
  np.testing.assert_allclose(total[[0, 2]], 1.0, rtol=1e-6)
  np.testing.assert_allclose(g.sum(-1)[[0, 2]], 1.0, rtol=1e-5)
  assert np.all(g[1] == 0)
  # g is the sum over relations of the weights at the children's one-hots
  built = np.zeros((3, h, d))
  for (i, slot), w in want.items():
    _, _, row, col, _ = raw[i]
    built[int(col[slot]), :, int(row[slot])] += w
  np.testing.assert_allclose(g, built, rtol=1e-5, atol=1e-30)
  alone = _expected_weights(q, raw, h, d, joint=False)
  assert max(np.abs(alone[k] - want[k]).max() for k in want) > 0.05


def _grouped_inputs(rng, logit_scale=1.0):
  """``_one_parent_type`` for the grouped form: the same edges as two
  parent-major blocks (hop 0: 2 groups x 3 slots) a relation, children's
  rows with linears that give the same keys and one-hot values."""
  q, relations, raw, h, d = _one_parent_type(rng, logit_scale)
  f = h * d
  grouped = []
  for r, (keys, onehot, _, _, _) in zip(relations, raw):
    # rows [keys || onehot]: K = rows @ [I; 0], V = rows @ [0; I]
    rows = jnp.concatenate([keys, onehot], axis=1)
    pick_k = jnp.concatenate([jnp.eye(f), jnp.zeros((f, f))])
    pick_v = jnp.concatenate([jnp.zeros((f, f)), jnp.eye(f)])
    zero = jnp.zeros((f,), jnp.float32)
    grouped.append(r._replace(src=rows, key_lin=(pick_k, zero),
                              val_lin=(pick_v, zero)))
  return q, relations, grouped, raw, h, d


@pytest.mark.parametrize('remat', [False, True])
@pytest.mark.parametrize('logit_scale', [1.0, 40.0])
def test_grouped_joint_attention_is_the_segment_form(logit_scale, remat):
  rng = np.random.default_rng(6)
  q, relations, grouped, raw, h, d = _grouped_inputs(rng, logit_scale)
  blocks = [((0, 2, 3, 0),), ((0, 2, 3, 0),)]
  want = segment_joint_attention(q, relations, h)
  with jax.default_matmul_precision('highest'):
    got = grouped_joint_attention(q, grouped, blocks, h, remat)
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-30)
  # the gradient for the queries, through both forms
  loss = lambda fn, *a: lambda q_: (fn(q_, *a) ** 2).sum()
  with jax.default_matmul_precision('highest'):
    g_got = jax.grad(loss(grouped_joint_attention, grouped, blocks, h,
                          remat))(q)
  g_want = jax.grad(loss(segment_joint_attention, relations, h))(q)
  np.testing.assert_allclose(g_got, g_want, rtol=1e-4, atol=1e-7)


def test_a_parent_with_every_slot_masked_gets_nought():
  """``g_v = 0`` and a finite gradient, in both forms, whatever the
  masked slots' own logits are (they overflow ``exp`` here)."""
  rng = np.random.default_rng(7)
  q, relations, grouped, raw, h, d = _grouped_inputs(rng, 200.0)
  dead = lambda r: r._replace(mask=r.mask & (r.col != 0))
  relations, grouped = [dead(r) for r in relations], [
      dead(r) for r in grouped]
  blocks = [((0, 2, 3, 0),), ((0, 2, 3, 0),)]
  forms = {
      'segment': lambda q_: segment_joint_attention(q_, relations, h),
      'grouped': lambda q_: grouped_joint_attention(q_, grouped, blocks, h)}
  for name, form in forms.items():
    with jax.default_matmul_precision('highest'):
      g, grad = jax.value_and_grad(lambda q_: form(q_).sum())(q)
      rows = form(q)
    assert np.all(np.asarray(rows)[[0, 1]] == 0), name
    assert np.isfinite(np.asarray(rows)).all(), name
    assert np.isfinite(np.asarray(grad)).all(), name


def test_blocks_of_one_hop_with_other_parents_are_refused():
  rng = np.random.default_rng(8)
  q, _, grouped, _, h, _ = _grouped_inputs(rng)
  with pytest.raises(ValueError, match='do not share hop 0'):
    grouped_joint_attention(q, grouped, [((0, 2, 3, 0),), ((0, 3, 2, 0),)],
                            h)


@pytest.mark.parametrize('layers,hops', [(2, 2), (3, 3), (3, 2)])
def test_node_trim_matches_untrimmed(layers, hops):
  """Trimmed and ``return_all`` runs agree on the seeds' rows, and
  ``layer_rows`` reads what the plan says."""
  edges, feats, labels = typed_graph(1)
  step, _ = build_step(edges, feats, labels, layers, hops)
  batch = padded_batch(step, feats, labels)
  model = step.model
  params = jax.jit(model.init)(jax.random.key(5), batch)
  head = params['params']['head']
  logits = model.apply(params, batch)
  everything = model.apply(params, batch, return_all=True)
  want = everything['a'][:BATCH] @ head['kernel'] + head['bias']
  np.testing.assert_allclose(logits, want, rtol=1e-5, atol=1e-6)
  rows = model.layer_rows(batch)
  assert rows == [r for _, r, _ in model.layer_plan(batch)]
  assert rows[-1]['a'] == BATCH and rows[0]['a'] <= step.node_budget['a']
  assert all(v.shape[0] == step.node_budget[t]
             for t, v in everything.items())
  full = model.layer_rows(batch, return_all=True)
  assert full[-1] == step.node_budget
  untrimmed = make_model(layers, trim=False)
  np.testing.assert_allclose(untrimmed.apply(params, batch), logits,
                             rtol=1e-5, atol=1e-6)


def test_counters_and_gauges_of_the_step():
  """``layer_rows``, ``layer_groups`` and ``layer_joint_relations`` of
  the step, with their gauges: type ``a`` is the parent of two relations
  wherever a layer reads both, a type that no relation reaches reads 0."""
  from glt_tpu.obs import get_registry
  edges, feats, labels = typed_graph()
  step, tx = build_step(edges, feats, labels, 3, 3)
  assert step.layer_joint_relations is None   # nothing traced yet
  train(step, tx, step.init_params(jax.random.key(3)), steps=1)
  fk = reverse_edge_type
  # frontiers by hand (test_rgat_step): a = 4, 12, 24; b = 0, 12, 24;
  # c = 0, 0, 24. Layer i keeps 3 - i hops.
  assert step.layer_groups[0] == {
      fk(('a', 'aa', 'a')): 40, fk(('a', 'ab', 'b')): 40,
      fk(('b', 'bc', 'c')): 36, fk(('c', 'ca', 'a')): 24}
  assert step.layer_joint_relations == [
      {'a': 2, 'b': 1, 'c': 1}, {'a': 2, 'b': 1, 'c': 0},
      {'a': 2, 'b': 0, 'c': 0}]
  assert step.layer_rows[-1]['a'] == BATCH
  reg = get_registry()
  for i, joint in enumerate(step.layer_joint_relations):
    for t, n in joint.items():
      assert reg.get('model_joint_softmax_relations', -1.0,
                     fn='train.hetero_step', layer=str(i), type=t) == n
  for i, groups in enumerate(step.layer_groups):
    for e, n in groups.items():
      assert reg.get('model_grouped_aggregation', -1.0,
                     fn='train.hetero_step', layer=str(i),
                     relation=as_str(e)) == n
  # an R-GAT step says nothing of a joint softmax
  import test_rgat_step
  rgat, _ = test_rgat_step.build_step(edges, feats, labels, 2, 2,
                                      head=True)
  rgat._note_layer_rows(rgat.dummy_batch())
  assert rgat.layer_joint_relations is None


def test_the_scopes_of_the_step_name_the_stages():
  """The stages that only this model has reach the compiled program's
  metadata under ``model_step``."""
  import re
  from glt_tpu.obs.device import layer_of
  edges, feats, labels = typed_graph()
  step, tx = build_step(edges, feats, labels, 2, 2, remat=True)
  batch = padded_batch(step, feats, labels)
  params = jax.jit(step.model.init)(jax.random.key(1), batch)

  def loss(p):
    with jax.named_scope('model_step'), jax.named_scope('forward'):
      return step.model.apply(p, batch).sum()

  text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
  stages = {layer_of(n)[1] for n in re.findall(r'op_name="([^"]*)"', text)}
  stages = {s for s in stages if s}
  for want in ('forward/HGT/in_a', 'forward/HGT/layer0/kqv/a',
               'forward/HGT/layer0/rel_a__aa__a/transform',
               'forward/HGT/layer0/rel_b__rev_ab__a/attention',
               'forward/HGT/layer0/softmax/a',
               'forward/HGT/layer0/aggregate/a',
               'forward/HGT/layer1/out/a', 'forward/HGT/head'):
    assert any(('model_step/' + want) in s for s in stages), (
        want, sorted(stages)[:40])
