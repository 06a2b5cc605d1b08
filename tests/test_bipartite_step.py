"""User-item link prediction over learnable id embeddings through the
edge-seeded ``DistHeteroTrainStep`` against the plain reference
(``glt_tpu/models/reference/bipartite_sage.py``): loss, every leaf's
gradient, parameters and both tables after three steps, the rows that the
first step touched and the second did not; negatives and sampled edges
against the CSR; counters against numpy's counts; the loader path's loss;
the node-seeded form's refusals. Small sizes: 70 users, 50 items, 16-wide
tables, 8 positive edges a step, fanout 3, 2."""
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.distributed import DistHeteroGraph, DistHeteroTrainStep
from glt_tpu.distributed import dist_hetero
from glt_tpu.models import BipartiteSAGE
from glt_tpu.models.reference import bipartite_sage as reference
from glt_tpu.parallel import make_mesh
from glt_tpu.sampler import NegativeSampling
from glt_tpu.typing import reverse_edge_type

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTS = {'user': 70, 'item': 50}
U2I, I2U, I2I = (('user', 'to', 'item'), ('item', 'rev_to', 'user'),
                 ('item', 'to', 'item'))
ITEM_USER, ITEM_ITEM = reverse_edge_type(U2I), reverse_edge_type(I2I)
BATCH, FANOUT, WIDTH, LR = 8, [3, 2], 16, 1e-3


def csr_of(rows, cols, n_rows):
  order = np.lexsort((cols, rows))
  indptr = np.zeros(n_rows + 1, np.int64)
  np.cumsum(np.bincount(rows, minlength=n_rows), out=indptr[1:])
  return indptr, cols[order].astype(np.int32)


def bipartite_graph(seed=0):
  """{stored relation: (indptr, indices)}: every user has 2 to 6 items,
  items into users is the transpose, items into items is random."""
  rng = np.random.default_rng(seed)
  pairs = np.unique(np.stack([
      np.repeat(np.arange(COUNTS['user']), 6),
      rng.integers(0, COUNTS['item'], 6 * COUNTS['user'])], 1), axis=0)
  pairs = pairs[rng.random(pairs.shape[0]) < 0.8]
  ii = np.unique(rng.integers(0, COUNTS['item'], (160, 2)), axis=0)
  return {U2I: csr_of(pairs[:, 0], pairs[:, 1], COUNTS['user']),
          I2U: csr_of(pairs[:, 1], pairs[:, 0], COUNTS['item']),
          I2I: csr_of(ii[:, 0], ii[:, 1], COUNTS['item'])}


def edges_of(csr):
  indptr, indices = csr
  return np.stack([np.repeat(np.arange(indptr.shape[0] - 1),
                             np.diff(indptr)), indices], 1)


def make_model(**kw):
  return BipartiteSAGE(num_nodes=COUNTS, item_user=ITEM_USER,
                       item_item=ITEM_ITEM, hidden_features=WIDTH,
                       out_features=WIDTH, **kw)


def build_step(csr, seed_type=U2I, model=None, **kw):
  mesh = make_mesh(1)
  graph = DistHeteroGraph.from_csr(mesh, COUNTS, csr)
  tx = optax.adam(LR)
  kw.setdefault('neg_sampling', NegativeSampling('binary', 1, True))
  step = DistHeteroTrainStep(
      graph, {}, model or make_model(), tx, None,
      {e: FANOUT for e in csr},
      batch_size_per_device=BATCH, seed_type=seed_type, seed=0,
      keep_sample=True, keep_seeds=True, **kw)
  return step, tx


def positives(csr, steps, seed=5):
  pairs = edges_of(csr[U2I])
  pick = np.random.default_rng(seed).choice(
      pairs.shape[0], steps * BATCH, replace=False)
  return pairs[pick].reshape(steps, BATCH, 2).astype(np.int32)


def handed_back(step, n_valid=BATCH):
  """What the step just taken sampled and drew, as the reference takes a
  batch: real nodes and real edges only, the pairs by their labels."""
  out = step.last_sample
  counted = step.link_counters()
  count = {t: int(np.asarray(v)[0]) for t, v in out['node_count'].items()}
  nodes = {t: np.asarray(v)[0][:count[t]] for t, v in out['node'].items()}
  edges = {}
  for name, e in (('item_user', ITEM_USER), ('item_item', ITEM_ITEM)):
    ok = np.asarray(out['edge_mask'][e])[0]
    edges[name] = (np.asarray(out['row'][e])[0][ok],
                   np.asarray(out['col'][e])[0][ok])
  seeds = counted['seeds'][0].reshape(2, 2 * BATCH)
  live = np.tile(np.arange(BATCH) < n_valid, 2)
  label = lambda t, ids: np.where(
      live, np.argmax(nodes[t][None, :] == ids[:, None], axis=1), 0)
  batch = {'nodes': nodes, 'edges': edges,
           'pairs': (label('user', seeds[0]), label('item', seeds[1])),
           'y': np.repeat([1.0, 0.0], BATCH).astype(np.float32),
           'weight': live.astype(np.float32)}
  return batch, out, counted


def train(step, tx, params0, pairs, n_valid=BATCH):
  """Three steps; the state is the step's own once handed over, so what
  is read later is copied first."""
  params = jax.tree.map(jnp.copy, params0)
  opt = tx.init(params)
  losses, first, batches, raw, tables = [], None, [], [], []
  for t in range(pairs.shape[0]):
    params, opt, loss = step(params, opt, pairs[t],
                             np.full(1, n_valid, np.int32),
                             jax.random.key(100 + t))
    losses.append(float(np.asarray(loss)[0]))
    if first is None:
      first = jax.tree.map(lambda m: np.asarray(m) / (1 - reference.B1),
                           opt[0].mu)
    batch, out, counted = handed_back(step, n_valid)
    batches.append(batch)
    raw.append((out, counted))
    tables.append({k: np.asarray(params['params'][k]['embedding'])
                   for k in reference.TABLES})
  return losses, first, params, batches, raw, tables


def watch_of(batches):
  """Rows of each table in the first batch and not in the second."""
  return {'embed_' + t: np.setdiff1d(batches[0]['nodes'][t],
                                     batches[1]['nodes'][t])
          for t in COUNTS}


def flat(tree):
  return {jax.tree_util.keystr(k): np.asarray(v) for k, v in
          jax.tree_util.tree_leaves_with_path(tree)}


def test_step_matches_the_reference():
  csr = bipartite_graph()
  step, tx = build_step(csr)
  params0 = step.init_params(jax.random.key(3))
  kept0 = jax.tree.map(np.asarray, params0)
  losses, first, params, batches, _, tables = train(
      step, tx, params0, positives(csr, 3))
  # the promise is on: both layers reduced groups over the fanout axis
  assert all(sum(g.values()) > 0 for g in step.layer_groups)
  watch = watch_of(batches)
  assert all(ids.size for ids in watch.values())
  ref = reference.follow(kept0, batches, LR, watch=watch)
  np.testing.assert_allclose(losses, ref['loss'], rtol=2e-5)
  got, want = flat(first), {k: np.asarray(v) for k, v in ref['grad'].items()}
  assert set(got) == set(want)
  scale = max(np.abs(v).max() for v in want.values())
  for k in want:
    np.testing.assert_allclose(got[k], want[k], rtol=1e-3,
                               atol=2e-6 * scale, err_msg=k)
  change = flat(jax.tree.map(lambda a, b: np.asarray(b) - a, kept0, params))
  for k, v in ref['change'].items():   # three Adam steps of 1e-3
    np.testing.assert_allclose(change[k], np.asarray(v), atol=1e-5,
                               err_msg=k)
  # rows the first step touched and the second did not move in the second
  # by momentum: dense Adam's, to the reference's digits
  moved = {k: tables[1][k][ids] - tables[0][k][ids]
           for k, ids in watch.items()}
  assert max(np.abs(v).max() for v in moved.values()) > 1e-4
  for k in moved:
    np.testing.assert_allclose(moved[k], ref['moved'][k], atol=2e-6)
  program = reference.readings(losses, first, kept0, params, moved)
  gaps = reference.compare(program, ref)
  assert max(gaps.values()) < 1e-3, gaps
  # the control: the same equations in bfloat16 are told apart
  low = reference.follow(kept0, batches, LR, dtype=jnp.bfloat16,
                         watch=watch)
  assert max(reference.compare(low, ref).values()) > 10 * max(
      gaps.values())


@pytest.mark.parametrize('fault,number', [
    ('half_batch', 'grad_gap'), ('lazy_update', 'table_momentum_gap')])
def test_a_planted_fault_is_told_apart(fault, number):
  csr = bipartite_graph()
  step, tx = build_step(csr)
  params0 = jax.tree.map(np.asarray, step.init_params(jax.random.key(3)))
  batches = train(step, tx, params0, positives(csr, 3))[3]
  watch = watch_of(batches)
  ref = reference.follow(params0, batches, LR, watch=watch)
  bad = reference.follow(params0, batches, LR, fault=fault, watch=watch)
  gaps = reference.compare(bad, ref)
  assert gaps[number] > 0.05, gaps
  if fault == 'lazy_update':   # untouched rows stood still
    assert gaps['table_momentum_gap'] == pytest.approx(1.0)
    assert gaps['loss_gap'] < 1e-2 and gaps['grad_gap'] < 1e-6, gaps


def test_rounded_operands_move_the_reference_a_little():
  csr = bipartite_graph()
  step, tx = build_step(csr)
  params0 = jax.tree.map(np.asarray, step.init_params(jax.random.key(3)))
  batches = train(step, tx, params0, positives(csr, 2))[3]
  exact = reference.follow(params0, batches, LR)
  rounded = reference.follow(params0, batches, LR, operands=jnp.bfloat16)
  gaps = reference.compare(rounded, exact)
  assert 1e-5 < gaps['grad_gap'] < 0.1, gaps
  assert reference.default_operands() is None   # the CPU rounds nothing


def is_edge(csr, rows, cols):
  indptr, indices = csr
  return np.asarray([c in indices[indptr[r]:indptr[r + 1]]
                     for r, c in zip(rows, cols)])


@pytest.mark.parametrize('n_valid', [BATCH, 5])
def test_negatives_sample_and_counters_against_numpy(n_valid):
  csr = bipartite_graph()
  step, tx = build_step(csr)
  params0 = step.init_params(jax.random.key(3))
  pairs = positives(csr, 2)
  _, _, _, batches, raw, _ = train(step, tx, params0, pairs, n_valid)
  slots = step.counter_slots()
  counted = step.counters()
  assert counted['step'].tolist() == [0, 1]
  stored = {reverse_edge_type(e): e for e in csr}
  for t, (out, got) in enumerate(raw):
    src, neg_src, dst, neg_dst = got['seeds'][0].reshape(4, BATCH)
    np.testing.assert_array_equal(np.stack([src, dst], 1), pairs[t])
    # every positive is an edge; a negative is none unless it was padded
    assert is_edge(csr[U2I], src, dst).all()
    assert int(is_edge(csr[U2I], neg_src, neg_dst).sum()) == int(
        got['negatives_padded'][0])
    assert 0 <= int(got['negatives_rejected'][0]) <= slots[
        'negatives_rejected']
    live = np.tile(np.arange(BATCH) < n_valid, 2)
    users = np.concatenate([src, neg_src])[live]
    items = np.concatenate([dst, neg_dst])[live]
    assert got['seed_unique'][0].tolist() == [np.unique(users).size,
                                              np.unique(items).size]
    nodes = batches[t]['nodes']
    # the seeds lead their types, nothing repeats, the labels find them
    assert set(nodes['user'][:np.unique(users).size]) == set(users)
    assert set(nodes['item'][:np.unique(items).size]) == set(items)
    for k, ids in nodes.items():
      assert np.unique(ids).size == ids.size <= step.node_budget[k]
    user_at, item_at = batches[t]['pairs']
    np.testing.assert_array_equal(nodes['user'][user_at][live], users)
    np.testing.assert_array_equal(nodes['item'][item_at][live], items)
    # every sampled edge is an edge of its stored relation
    node_of = {k: np.asarray(v)[0] for k, v in out['node'].items()}
    for flow in out['row']:
      ok = np.asarray(out['edge_mask'][flow])[0]
      child = node_of[flow[0]][np.asarray(out['row'][flow])[0][ok]]
      parent = node_of[flow[2]][np.asarray(out['col'][flow])[0][ok]]
      assert is_edge(csr[stored[flow]], parent, child).all(), flow
    # counters: nodes by hop sum to the count, edges to the live slots
    by_hop = counted['nodes_by_hop'][t, 0]
    for i, k in enumerate(step.counter_node_types):
      assert by_hop[i].sum() == nodes[k].size
      assert (by_hop[i] <= slots['nodes_by_hop'][i]).all()
    for i, flow in enumerate(step.counter_edge_types):
      assert counted['edges_by_hop'][t, 0, i].sum() == int(
          np.asarray(out['edge_mask'][flow]).sum())
    # the tables' rows read: every item, and the users of the seed prefix
    rows = dict(zip(step.counter_node_types,
                    counted['embedding_rows'][t, 0]))
    assert rows['item'] == nodes['item'].size
    assert rows['user'] == min(nodes['user'].size, 2 * BATCH)
  assert slots['embedding_rows'].tolist() == [
      COUNTS[k] for k in step.counter_node_types]
  assert 'store_chunks' not in counted and step.step_traces == 1


def test_trimmed_equals_untrimmed_and_segments():
  """The plan's three promises change no value at the pairs: the model on
  the step's batch with them, and with all of them withheld."""
  csr = bipartite_graph()
  step, tx = build_step(csr)
  params = step.init_params(jax.random.key(3))
  kept = jax.tree.map(jnp.copy, params)   # the step consumes its own
  train(step, tx, params, positives(csr, 1))
  out = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)[0]),
                     step.last_sample)
  from glt_tpu.loader.transform import HeteroBatch
  seeds = step.link_counters()['seeds'][0].reshape(2, -1)
  index = jnp.stack([
      jnp.argmax(out['node'][t][None, :] == seeds[i][:, None], axis=1)
      for i, t in enumerate(('user', 'item'))])
  plain = HeteroBatch(
      x_dict={}, row_dict=out['row'], col_dict=out['col'],
      edge_mask_dict=out['edge_mask'], node_dict=out['node'],
      node_count_dict=out['node_count'],
      metadata={'edge_label_index': index})
  promised = plain.replace(**step._batch_static)
  model = make_model()
  a = model.apply(kept, promised)
  b = model.apply(kept, plain)
  assert sum(sum(g.values()) for g in model.layer_groups(promised)) > 0
  assert sum(sum(g.values()) for g in model.layer_groups(plain)) == 0
  np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_loader_path_gives_the_same_loss():
  """``LinkNeighborLoader`` over the typed edges with the same model and
  weights: its batch through the plain reference, one loss."""
  from glt_tpu.data import Dataset
  from glt_tpu.loader import LinkNeighborLoader
  csr = bipartite_graph()
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index={e: edges_of(c).T for e, c in csr.items()},
                num_nodes=COUNTS)
  loader = LinkNeighborLoader(
      ds, FANOUT, edge_label_index=(U2I, edges_of(csr[U2I]).T),
      batch_size=BATCH, shuffle=True, seed=0,
      neg_sampling=NegativeSampling('binary', amount=1))
  batch = next(iter(loader))
  model = make_model()
  params = model.init(jax.random.key(1), batch)
  logits = model.apply(params, batch)
  label = batch.metadata['edge_label']
  loss = float(optax.sigmoid_binary_cross_entropy(logits, label).mean())
  count = {t: int(v) for t, v in batch.node_count_dict.items()}
  edges = {}
  for name, e in (('item_user', ITEM_USER), ('item_item', ITEM_ITEM)):
    ok = np.asarray(batch.edge_mask_dict[e])
    edges[name] = (np.asarray(batch.row_dict[e])[ok],
                   np.asarray(batch.col_dict[e])[ok])
  index = np.asarray(batch.metadata['edge_label_index'])
  ref_loss, _ = reference.loss_and_grad(jax.tree.map(np.asarray, params), {
      'nodes': {t: np.asarray(batch.node_dict[t])[:count[t]]
                for t in COUNTS},
      'edges': edges, 'pairs': (index[0], index[1]),
      'y': np.asarray(label, np.float32),
      'weight': np.ones(label.shape[0], np.float32)})
  assert loss == pytest.approx(float(ref_loss), rel=2e-5)


class ItemDot(nn.Module):
  """One table of items, read at the pairs: a dot product's logits."""

  @nn.compact
  def __call__(self, batch):
    x = self.param('embedding', jax.nn.initializers.normal(1.0),
                   (COUNTS['item'], 4))
    x = x[jnp.maximum(batch.node_dict['item'], 0)]
    row, col = jnp.maximum(batch.metadata['edge_label_index'], 0)
    return (x[row] * x[col]).sum(-1)


def test_one_type_at_both_ends():
  """A seed relation from a type to itself: one block of ``4B`` seeds."""
  csr = bipartite_graph()
  step, tx = build_step(csr, seed_type=I2I, model=ItemDot())
  assert step.node_budget['item'] > 4 * BATCH
  pairs = edges_of(csr[I2I])[:BATCH].astype(np.int32)
  params = step.init_params(jax.random.key(0))
  params, opt, loss = step(params, tx.init(params), pairs,
                           np.full(1, BATCH, np.int32), jax.random.key(1))
  got = step.link_counters()
  src, neg_src, dst, neg_dst = got['seeds'][0].reshape(4, BATCH)
  np.testing.assert_array_equal(np.stack([src, dst], 1), pairs)
  assert is_edge(csr[I2I], src, dst).all()
  assert got['seed_unique'][0].tolist() == [np.unique(got['seeds']).size] * 2
  assert np.isfinite(np.asarray(loss)).all()


def test_what_an_edge_seeded_step_refuses():
  csr = bipartite_graph()
  with pytest.raises(NotImplementedError, match='triplet'):
    build_step(csr, neg_sampling=NegativeSampling('triplet', 1))
  with pytest.raises(NotImplementedError, match='binary negative'):
    build_step(csr, neg_sampling=NegativeSampling('binary', 2))
  with pytest.raises(ValueError, match='no stored relation'):
    build_step(csr, seed_type=('user', 'buys', 'item'))
  step, tx = build_step(csr)
  with pytest.raises(NotImplementedError, match='per batch'):
    step.superstep(None, None, np.zeros((2, BATCH, 2)), None, None)
  with pytest.raises(RuntimeError, match='no edge-seeded step'):
    step.link_counters()


def test_the_edge_seeded_program_donates_its_state():
  csr = bipartite_graph()
  step, tx = build_step(csr)
  params = step.init_params(jax.random.key(3))
  opt = tx.init(params)
  table = params['params']['embed_item']['embedding']
  new, _, _ = step(params, opt, positives(csr, 1)[0],
                   np.full(1, BATCH, np.int32), jax.random.key(1))
  assert table.is_deleted()
  assert not new['params']['embed_item']['embedding'].is_deleted()


def test_update_by_group_is_one_update():
  """Adam in two calls, tables and rest, is Adam in one, leaf for leaf,
  state included."""
  key = jax.random.key(0)
  params = {'params': {
      'embed_user': {'embedding': jax.random.normal(key, (9, 4))},
      'decoder': {'lin1': {'kernel': jax.random.normal(key, (4, 3)),
                           'bias': jnp.ones(3)}}}}
  grads = jax.tree.map(lambda a: jnp.sin(a), params)
  tx = optax.adam(1e-2)
  state = tx.init(params)
  for _ in range(2):
    want_u, want_s = tx.update(grads, state, params)
    want_p = optax.apply_updates(params, want_u)
    got_p, got_s = dist_hetero._update_by_group(
        tx, grads, state, params, ('embed_user',))
    assert jax.tree.structure(got_s) == jax.tree.structure(want_s)
    for a, b in zip(jax.tree.leaves((got_p, got_s)),
                    jax.tree.leaves((want_p, want_s))):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    params, state = want_p, want_s


def test_reference_copy_is_the_reference():
  """``chipbench/reference_bisage.py`` is this reference's copy."""
  with open(os.path.join(REPO, 'chipbench', 'reference_bisage.py')) as f:
    copy = f.read()
  with open(reference.__file__) as f:
    assert copy == f.read()
