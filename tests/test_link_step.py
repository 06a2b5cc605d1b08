"""The fused link-prediction step: ``SPMDSageTrainStep`` given a
``NegativeSampling``. Held to the plain reference
(``models/reference/sage_link.py``) and to the loader path
(``LinkNeighborLoader`` + ``sample_from_edges`` + ``GraphSAGE.embed``) on
seeded weights, at sizes the CPU holds."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from glt_tpu.data import Dataset
from glt_tpu.loader import LinkNeighborLoader
from glt_tpu.models import GraphSAGE
from glt_tpu.models.reference import sage_link
from glt_tpu.parallel import ShardedFeature, SPMDSageTrainStep, make_mesh
from glt_tpu.sampler import EdgeSamplerInput, NegativeSampling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FANOUT = [3, 2, 2]
BINARY = NegativeSampling('binary', 1, strict=True)
LR = 1e-3


def dataset(num_nodes, num_edges, seed, dim=16):
  """A random directed graph (multi-edges folded) with features."""
  rng = np.random.default_rng(seed)
  pairs = np.unique(rng.integers(0, num_nodes, (num_edges, 2)), axis=0)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=pairs.T, num_nodes=num_nodes)
  ds.init_node_features(
      rng.standard_normal((num_nodes, dim), dtype=np.float32))
  return ds


def csr(ds):
  topo = ds.get_graph().topo
  return np.asarray(topo.indptr), np.asarray(topo.indices)


def edges(ds, count, seed):
  """``[count, 2]`` distinct edges of the graph, seeded."""
  indptr, indices = csr(ds)
  eid = np.random.default_rng(seed).choice(indices.shape[0], count,
                                           replace=False)
  src = np.searchsorted(indptr, eid, side='right') - 1
  return np.stack([src, indices[eid]], 1).astype(np.int32)


def trainer(ds, chips, batch, neg=BINARY, hidden=32, fanout=FANOUT):
  mesh = make_mesh(chips)
  table = np.asarray(ds.get_node_feature()[np.arange(
      ds.get_graph().num_nodes)])
  model = GraphSAGE(hidden_features=hidden, out_features=hidden,
                    num_layers=len(fanout))
  tx = optax.adam(LR)
  step = SPMDSageTrainStep(mesh, model, tx, ds.get_graph(),
                           ShardedFeature(table, mesh), None, fanout,
                           batch, neg_sampling=neg, keep_seeds=True)
  return step, model, tx, table


def program_readings(step, tx, params, feed, steps, n_valid):
  """The program's side of ``sage_link.compare``, and its counters."""
  opt = tx.init(params)
  host = lambda tree: jax.tree.map(np.asarray, tree)
  p0, losses, first_grad, counted = host(params), [], None, []
  for t in range(steps):
    pairs, keys = feed(t)
    params, opt, loss = step(params, opt, pairs, n_valid, keys)
    losses.append(float(np.asarray(loss)[0]))
    counted.append(step.link_counters())
    if first_grad is None:
      first_grad = jax.tree.map(
          lambda m: np.asarray(m) / (1 - sage_link.B1), opt[0].mu)
  return sage_link.readings(losses, first_grad, p0,
                            host(params)), counted


# float32 on the CPU against float32 at ``highest``: the two differ by
# the order of sums (the trim, the grouped reduce, the scatter of the
# backward pass). Read on 1, 2 and 4 devices: loss 2.3e-6 to 3.1e-6 (a
# logit is a dot product of 32 terms of size 10 or so, and the loss
# adds 16), the first gradient's worst leaf, element by element, 1.9e-7
# to 2.4e-7, the parameters' change 0.7e-5 to 2.1e-5 (Adam divides by
# sqrt(v), so a small leaf's rounding is magnified). Each limit has three
# to fifty times of room; bfloat16 and both planted faults fail them (the
# test below)
LIMITS = {'loss_gap': 1e-5, 'grad_gap': 2e-6, 'change_gap': 1e-3}


@pytest.mark.parametrize('chips', [1, 2, 4])
def test_the_fused_link_step_against_the_reference(chips):
  ds, batch, steps = dataset(400, 3000, 11), 8, 3
  step, model, tx, table = trainer(ds, chips, batch)
  params = step.init_params(jax.random.key(3))
  pairs = edges(ds, steps * chips * batch, 5).reshape(
      steps, chips * batch, 2)
  keys = jax.random.split(jax.random.key(7), (steps, chips))
  feed = lambda t: (pairs[t], keys[t])
  n_valid = np.full((chips,), batch, np.int32)
  prog, counted = program_readings(step, tx, params, feed, steps, n_valid)
  indptr, indices = csr(ds)
  ref = sage_link.follow(indptr, indices, lambda ids: table[ids], params,
                         feed, steps, chips, FANOUT, LR, 400)
  gaps = sage_link.compare(prog, ref)
  assert all(gaps[k] <= LIMITS[k] for k in LIMITS), gaps
  # both sides drew the same negatives and counted the same
  for t in range(steps):
    for d in range(chips):
      got = ref['batches'][t * chips + d]
      assert np.array_equal(counted[t]['seeds'][d], got['seeds'])
      assert counted[t]['negatives_padded'][d] == got['padded'].sum()
      assert counted[t]['negatives_rejected'][d] == got['rejected']
      assert counted[t]['seed_unique'][d] == np.unique(got['seeds']).size
      assert sage_link.pair_violations(
          indptr, indices, counted[t]['seeds'][d],
          counted[t]['negatives_padded'][d]) == 0
  # the trim and the grouped reduce engage, with 4B where bs stood
  assert step.layer_rows == (4 * batch * (1 + 3 + 6), 4 * batch * 4,
                             4 * batch)
  assert step.layer_groups == step.layer_rows
  assert step.step_traces == 1


def test_the_reference_fails_a_planted_fault():
  ds, batch = dataset(400, 3000, 11), 8
  step, _, _, table = trainer(ds, 1, batch)
  params = step.init_params(jax.random.key(3))
  pairs = edges(ds, 3 * batch, 5).reshape(3, batch, 2)
  keys = jax.random.split(jax.random.key(7), (3, 1))
  follow = lambda **kw: sage_link.follow(
      *csr(ds), lambda ids: table[ids], params,
      lambda t: (pairs[t], keys[t]), 3, 1, FANOUT, LR, 400, **kw)
  ref = follow()
  fails = lambda got: any(v > LIMITS[k] for k, v in
                          sage_link.compare(got, ref).items())
  assert not fails(follow())
  assert fails(follow(fault='half_batch'))
  assert fails(follow(fault='no_negatives'))
  assert fails(follow(dtype=jnp.bfloat16))
  # at a stated precision: matmuls that round their operands to float32
  # round nothing; to bfloat16 they move the gradient by a few hundredths
  # (3.8e-2 read), and what is bfloat16 throughout stays as far from that
  # as from the plain reference (1.8e-2 and 2.6e-2 read): its sums and
  # its stored rows are rounded too
  gaps = lambda got, to: sage_link.compare(got, to)
  assert gaps(follow(operands=jnp.float32), ref)['grad_gap'] == 0
  stated = follow(operands=jnp.bfloat16)
  assert 1e-3 < gaps(stated, ref)['grad_gap'] < 0.2
  assert gaps(follow(dtype=jnp.bfloat16), stated)['grad_gap'] > 5e-3
  assert sage_link.default_operands() is None        # the CPU rounds none


def loader_loss(ds, model, params, pairs, key, fanout=FANOUT):
  """The loader path's loss on ``pairs``: ``sample_from_edges`` on
  ``key`` (its negatives and its hop loop), the loader's collate,
  ``GraphSAGE.embed`` over every row, the example's BCE."""
  loader = LinkNeighborLoader(ds, fanout, edge_label_index=pairs.T,
                              batch_size=pairs.shape[0],
                              neg_sampling=BINARY)
  out = loader.sampler.sample_from_edges(
      EdgeSamplerInput(pairs[:, 0], pairs[:, 1], neg_sampling=BINARY),
      key=key)
  batch = loader._collate_homo_link(out, pairs.shape[0])
  assert batch.node_hop_offsets is None       # untrimmed, as it says
  emb = model.apply(params, batch, method=GraphSAGE.embed)
  assert emb.shape[0] == batch.x.shape[0]
  eli = batch.metadata['edge_label_index']
  logit = (emb[eli[0]] * emb[eli[1]]).sum(-1)
  return float(optax.sigmoid_binary_cross_entropy(
      logit, batch.metadata['edge_label']).mean()), out


def test_the_fused_link_step_against_the_loader_path():
  """Equal loss to float32 rounding: ties the seed-prefix read of the
  trimmed model to ``embed`` over every row."""
  ds, batch = dataset(400, 3000, 13), 16
  step, model, tx, _ = trainer(ds, 1, batch)
  params = step.init_params(jax.random.key(2))
  pairs = edges(ds, batch, 9)
  keys = jax.random.split(jax.random.key(21), 1)
  want, out = loader_loss(ds, model, params, pairs,
                          jax.random.fold_in(keys[0], 0))
  _, _, loss = step(params, tx.init(params), pairs,
                    np.full((1,), batch, np.int32), keys)
  got = step.link_counters()
  # the same negatives, the same endpoints, the same labels for them
  assert np.array_equal(np.asarray(out.batch)[:int(got['seed_unique'][0])],
                        np.asarray(out.node)[:int(got['seed_unique'][0])])
  seeds = got['seeds'][0]
  eli = np.asarray(out.metadata['edge_label_index']).reshape(-1)
  assert np.array_equal(np.asarray(out.node)[eli], seeds)
  # one mean of O(1) terms, summed in another order: 1e-6 relative
  assert float(np.asarray(loss)[0]) == pytest.approx(want, rel=2e-6)


def test_repeated_endpoints_point_at_one_label():
  """A batch whose pairs share a hub: every slot that holds the hub
  reads one label, and the hub is expanded once."""
  rng = np.random.default_rng(4)
  hub, n = 7, 200
  spokes = rng.choice(np.setdiff1d(np.arange(n), [hub]), 60,
                      replace=False)
  fill = rng.integers(0, n, (600, 2))
  ei = np.unique(np.concatenate(
      [np.stack([spokes, np.full(60, hub)], 1), fill]), axis=0)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=ei.T, num_nodes=n)
  ds.init_node_features(rng.standard_normal((n, 16), dtype=np.float32))
  batch = 16
  pairs = np.stack([spokes[:batch], np.full(batch, hub)], 1).astype(
      np.int32)
  step, model, tx, table = trainer(ds, 1, batch)
  params = step.init_params(jax.random.key(0))
  keys = jax.random.split(jax.random.key(1), 1)
  want, out = loader_loss(ds, model, params, pairs,
                          jax.random.fold_in(keys[0], 0))
  lam = np.asarray(out.metadata['seed_labels'])
  assert len(set(lam[2 * batch:3 * batch])) == 1     # d is the hub
  assert int(out.metadata['seed_count']) <= 3 * batch + 1
  _, _, loss = step(params, tx.init(params), pairs,
                    np.full((1,), batch, np.int32), keys)
  got = step.link_counters()
  assert got['seed_unique'][0] == np.unique(got['seeds'][0]).size
  assert got['seed_unique'][0] == int(out.metadata['seed_count'])
  assert float(np.asarray(loss)[0]) == pytest.approx(want, rel=2e-6)
  ref = sage_link.follow(*csr(ds), lambda ids: table[ids], params,
                         lambda t: (pairs, keys), 1, 1, FANOUT, LR, n)
  assert float(np.asarray(loss)[0]) == pytest.approx(ref['loss'][0],
                                                     rel=1e-5)


def test_strict_rejection_fires_on_a_dense_graph():
  """Two thirds of all pairs are edges: proposals are rejected, some
  pairs run out of rounds, and the counters say how many."""
  n, batch = 30, 32
  rng = np.random.default_rng(8)
  dense = np.argwhere(rng.random((n, n)) < 0.67)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=dense.T, num_nodes=n)
  ds.init_node_features(rng.standard_normal((n, 16), dtype=np.float32))
  step, model, tx, table = trainer(ds, 1, batch)
  params = step.init_params(jax.random.key(0))
  pairs = edges(ds, batch, 2)
  keys = jax.random.split(jax.random.key(5), 1)
  _, _, loss = step(params, tx.init(params), pairs,
                    np.full((1,), batch, np.int32), keys)
  got = step.link_counters()
  indptr, indices = csr(ds)
  s, r, d, c = got['seeds'][0].reshape(4, batch)
  assert np.array_equal(np.stack([s, d], 1), pairs)
  hit = sage_link.is_edge(indptr, indices, r, c)
  rows, cols, padded, rejected = sage_link.negatives(
      indptr, indices, jax.random.split(jax.random.fold_in(keys[0], 0))[0],
      batch, n)
  assert np.array_equal(r, rows) and np.array_equal(c, cols)
  assert np.array_equal(hit, padded)   # an edge only where rounds ran out
  assert got['negatives_padded'][0] == padded.sum() > 0
  assert got['negatives_rejected'][0] == rejected > padded.sum()
  assert sage_link.pair_violations(indptr, indices, got['seeds'][0],
                                   got['negatives_padded'][0]) == 0
  assert np.isfinite(np.asarray(loss)).all()
  # not strict: the first round is taken as it comes, nothing counted
  loose, _, tx2, _ = trainer(ds, 1, batch,
                             neg=NegativeSampling('binary', 1, strict=False))
  loose(params, tx2.init(params), pairs, np.full((1,), batch, np.int32),
        keys)
  got = loose.link_counters()
  assert got['negatives_padded'][0] == got['negatives_rejected'][0] == 0


def test_n_valid_masks_the_tail_pairs_and_their_negatives():
  ds, batch, n_valid = dataset(400, 3000, 17), 8, 5
  step, model, tx, table = trainer(ds, 1, batch)
  params = step.init_params(jax.random.key(3))
  pairs = edges(ds, batch, 6)
  keys = jax.random.split(jax.random.key(12), 1)
  run = lambda p: float(np.asarray(step(
      params, tx.init(params), p, np.full((1,), n_valid, np.int32),
      keys)[2])[0])
  loss = run(pairs)
  got = step.link_counters()
  live = np.tile(np.arange(batch) < n_valid, 4)
  assert got['seed_unique'][0] == np.unique(got['seeds'][0][live]).size
  # the tail's endpoints are no seeds: other pairs there, the same loss
  other = pairs.copy()
  other[n_valid:] = edges(ds, batch, 99)[n_valid:]
  assert run(other) == loss
  ref = sage_link.follow(*csr(ds), lambda ids: table[ids], params,
                         lambda t: (pairs, keys), 1, 1, FANOUT, LR, 400,
                         n_valid=[n_valid])
  assert loss == pytest.approx(ref['loss'][0], rel=1e-5)
  full = sage_link.follow(*csr(ds), lambda ids: table[ids], params,
                          lambda t: (pairs, keys), 1, 1, FANOUT, LR, 400)
  assert abs(full['loss'][0] - loss) > 1e-3 * loss


@pytest.mark.parametrize('neg', [NegativeSampling('triplet', 1),
                                 ('binary', 2), {'mode': 'triplet'}])
def test_what_the_program_does_not_draw_raises(neg):
  ds = dataset(100, 500, 1)
  with pytest.raises(NotImplementedError, match='LinkNeighborLoader'):
    trainer(ds, 1, 4, neg=neg)


@pytest.mark.parametrize('entry', ['superstep', 'run_epoch',
                                   'make_epoch_loader'])
def test_the_supersteps_refuse_edge_seeds(entry):
  ds = dataset(100, 500, 1)
  step, _, tx, _ = trainer(ds, 1, 4)
  args = {'superstep': (None, None, None, None, None),
          'run_epoch': (None, None, None, None),
          'make_epoch_loader': (None,)}[entry]
  with pytest.raises(NotImplementedError, match='node seeds only'):
    getattr(step, entry)(*args)
  assert step._superstep_fn is None
  mesh = make_mesh(1)
  with pytest.raises(NotImplementedError, match='cold_streaming'):
    SPMDSageTrainStep(mesh, None, tx, ds.get_graph(), None, None, FANOUT,
                      4, cold_streaming=True, neg_sampling=BINARY)


def test_the_scopes_of_the_link_step():
  """``sampler/negative`` and ``model_step/forward/link_loss`` are in the
  compiled program's metadata."""
  import re
  from glt_tpu.obs.device import layer_of
  ds, batch = dataset(400, 3000, 11), 8
  step, model, tx, _ = trainer(ds, 1, batch)
  params = step.init_params(jax.random.key(3))
  pairs, keys = edges(ds, batch, 5), jax.random.split(jax.random.key(7), 1)
  params, opt, _ = step(params, tx.init(params), pairs,
                        np.full((1,), batch, np.int32), keys)
  rows = NamedSharding(step.mesh, P(step.axis))
  text = step._step_fn.lower(
      params, opt, jax.device_put(pairs, rows),
      jax.device_put(np.full((1,), batch, np.int32), rows), keys,
      step.feature.array, step.labels, step._indptr,
      step._indices).compile().as_text()
  stages = {layer_of(n)[1] for n in re.findall(r'op_name="([^"]*)"', text)}
  assert any(s and s.startswith('sampler/negative') for s in stages)
  assert any(s and s.startswith('model_step/forward/link_loss')
             for s in stages)


@pytest.mark.parametrize('chips', [1, 4])
def test_a_node_seeded_step_carries_nothing_of_the_link_front(chips):
  """The tiny node-seeded cells of tests/chipbench/test_chipbench.py:
  the step seeds its hop loop with the ``bs`` node ids, hands back a
  plain loss and has no link counters. (That their StableHLO is the
  parent's, byte for byte, was checked once, when the front came in:
  CHANGES.md, PR 31.)"""
  import sys
  sys.path.insert(0, REPO)
  sys.path.insert(0, os.path.join(REPO, 'tests', 'chipbench'))
  from chipbench.drivers import fused
  from test_chipbench import tiny_cell
  _, _, cfg, traffic = tiny_cell(chips)
  s = fused.build(cfg, traffic, chips, 5)
  t = s.trainer
  assert t.neg_sampling is None and t.seed_slots == t.bs
  rows = NamedSharding(t.mesh, P(t.axis))
  seeds, keys = fused.feed(s, 0)
  out = t._step_fn.lower(
      s.params, s.opt, jax.device_put(np.asarray(seeds, np.int32), rows),
      jax.device_put(s.n_valid, rows), keys, t.feature.array, t.labels,
      t._indptr, t._indices).out_info
  # (params, opt_state, (loss, counters)): every step
  # counts its nodes and edges by hop; over more than one shard the
  # exchanging store's three counters ride beside them; nothing of the
  # link front
  loss, counted = out[-1]
  store = ['store_bucket_max', 'store_exchange_chunks', 'store_requests',
           'store_rounds']
  # ... and on one the chunks it gathered
  assert sorted(counted) == ['edges_by_hop', 'hop_rows_read',
                             'nodes_by_hop'] + (
      store if chips > 1 else ['store_chunks'])
  assert jax.tree.structure(loss).num_leaves == 1
  assert tuple(loss.shape) == (chips,)
  assert np.asarray(fused.step(s, 0)).shape == (chips,)
  with pytest.raises(RuntimeError, match='no link step'):
    t.link_counters()


def test_the_references_two_copies_are_one_text():
  with open(os.path.join(REPO, 'chipbench', 'reference_link.py')) as f:
    ours = f.read()
  with open(os.path.join(REPO, 'glt_tpu', 'models', 'reference',
                         'sage_link.py')) as f:
    theirs = f.read()
  assert ours == theirs
  assert 'glt_tpu' not in [line.split()[1].split('.')[0]
                           for line in ours.splitlines()
                           if line.startswith(('import ', 'from '))]
