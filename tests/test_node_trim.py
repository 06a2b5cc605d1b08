"""Node trimming by hop (Batch.node_hop_offsets).

Layer i of L computes output rows only for the nodes within
min(num_hops, L-1-i) hops of a seed: a static prefix of the node buffer,
because the inducer hands labels out hop by hop. Under test: (a) the
trimmed model equals the untrimmed one on seed logits and on every
parameter's gradient, (b) the label property the slice rests on, on
several seed sets and on the loader path, (c) a batch without the field and
``return_all=True`` compute what they computed before, bit for bit, (d)
the SPMD trainer records the rows it computes and trains to the same
loss.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.data import Dataset, Topology
from glt_tpu.loader import NeighborLoader
from glt_tpu.loader.transform import Batch
from glt_tpu.models import GraphSAGE
from glt_tpu.obs import get_registry
from glt_tpu.ops.pipeline import (edge_hop_offsets, hop_fanouts,
                                  multihop_sample, node_hop_offsets,
                                  sample_budget)
from glt_tpu.ops.sample import sample_neighbors
from glt_tpu.parallel import ShardedFeature, SPMDSageTrainStep, make_mesh

N = 96
FEAT = 8
BS = 8


def _hub_edges(num_nodes=N, seed=3):
  """Hubs (nodes 0-2 point at a third of the graph and are pointed at by
  half of it), duplicate neighbours (every fourth edge twice), and nodes
  of out-degree 0 (the last eighth)."""
  rng = np.random.default_rng(seed)
  src, dst = [], []
  for v in range(num_nodes - num_nodes // 8):
    deg = num_nodes // 3 if v < 3 else int(rng.integers(1, 5))
    nbrs = rng.integers(0, num_nodes, deg)
    nbrs[::2] = rng.integers(0, 3, nbrs[::2].shape[0])   # into the hubs
    src += [v] * deg
    dst += nbrs.tolist()
  src, dst = np.asarray(src), np.asarray(dst)
  dup = np.arange(0, src.shape[0], 4)
  return (np.concatenate([src, src[dup]]),
          np.concatenate([dst, dst[dup]]))


@pytest.fixture(scope='module')
def hub_graph():
  src, dst = _hub_edges()
  t = Topology(edge_index=np.stack([src, dst]), num_nodes=N)
  return (jnp.asarray(t.indptr.astype(np.int32)), jnp.asarray(t.indices),
          np.stack([src, dst]))


#: seed sets of BS slots: 'ragged' is a ragged last batch (a duplicate
#: seed, a hub, a node of degree 0, padded slots beyond n_valid); in
#: 'hubs' every slot holds one of the three hubs; in 'one_seed' every
#: slot holds hub 1
SEEDS = {'ragged': [0, 17, 17, N - 1, 40, 2, 63, 5],
         'hubs': [0, 1, 2, 0, 1, 2, 0, 1],
         'one_seed': [1] * BS}


@functools.partial(jax.jit, static_argnums=(2,))
def _hop_loop(indptr, indices, fanouts, seeds, n_valid, key):
  one_hop = lambda ids, f, k, m: sample_neighbors(
      indptr, indices, ids, f, k, seed_mask=m)
  return multihop_sample(one_hop, seeds, n_valid, fanouts, key)


def _sample(hub_graph, fanouts, n_valid=BS, key=0, seeds='ragged'):
  indptr, indices, _ = hub_graph
  return _hop_loop(indptr, indices, tuple(fanouts),
                   jnp.asarray(SEEDS[seeds], jnp.int32),
                   jnp.asarray(n_valid), jax.random.key(key))


def _batch(hub_graph, fanouts, n_valid=BS):
  out = _sample(hub_graph, fanouts, n_valid)
  budget = sample_budget(BS, fanouts)
  x = jnp.asarray(np.random.default_rng(1).normal(size=(budget, FEAT))
                  .astype(np.float32))
  return Batch(
      x=x, row=out['row'], col=out['col'], edge_mask=out['edge_mask'],
      node=out['node'], node_count=out['node_count'], batch_size=BS,
      edge_hop_offsets=tuple(edge_hop_offsets(BS, fanouts)),
      node_hop_offsets=tuple(node_hop_offsets(BS, fanouts)))


def _assert_hop_compact(row, col, mask, node_count, bs, fanouts):
  """Every valid edge of hop h joins a child under node_hop_offsets[h]
  to a parent under node_hop_offsets[h-1]."""
  eoffs = edge_hop_offsets(bs, fanouts)
  noffs = node_hop_offsets(bs, fanouts)
  assert noffs[0] == bs and noffs[-1] == sample_budget(bs, fanouts)
  assert int(node_count) <= noffs[-1]
  row, col, mask = (np.asarray(a) for a in (row, col, mask))
  assert mask.any()
  for h in range(1, len(fanouts) + 1):
    sl = slice(eoffs[h - 1], eoffs[h])
    m = mask[sl].astype(bool)
    assert (row[sl][m] >= 0).all() and (col[sl][m] >= 0).all()
    assert (col[sl][m] < noffs[h - 1]).all(), f'hop {h} parent'
    assert (row[sl][m] < noffs[h]).all(), f'hop {h} child'
    assert (row[sl][m] < int(node_count)).all()


# -- (a) trimmed == untrimmed: seed logits and every gradient -------------

@pytest.mark.parametrize('conv', ['sage', 'gcn', 'gat'])
@pytest.mark.parametrize('num_layers,fanouts', [
    (2, (3, 2)), (3, (3, 2, 2)),
    (3, (3, 2)),       # more layers than hops
    (2, (3, 2, 2)),    # fewer layers than hops
])
def test_node_trim_matches_untrimmed(hub_graph, conv, num_layers, fanouts):
  batch = _batch(hub_graph, fanouts, n_valid=6)
  plain = batch.replace(node_hop_offsets=None)
  model = GraphSAGE(hidden_features=16, out_features=5,
                    num_layers=num_layers, conv=conv, trim=True)
  params = jax.jit(model.init)(jax.random.key(0), batch)
  y = jnp.arange(BS) % 5

  def loss_and_logits(p, b):
    logits = model.apply(p, b)
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    return loss.mean(), logits

  grad = jax.jit(jax.value_and_grad(loss_and_logits, has_aux=True))
  (_, lo_t), g_t = grad(params, batch)
  (_, lo_p), g_p = grad(params, plain)
  assert lo_t.shape == (BS, 5)
  rows = model.layer_rows(batch)
  noffs = node_hop_offsets(BS, fanouts)
  assert rows == tuple(noffs[min(len(fanouts), num_layers - 1 - i)]
                       for i in range(num_layers))
  assert rows[-1] == BS
  np.testing.assert_allclose(np.asarray(lo_t), np.asarray(lo_p),
                             rtol=1e-5, atol=1e-6)
  flat_t = jax.tree_util.tree_leaves_with_path(g_t)
  flat_p = jax.tree.leaves(g_p)
  assert len(flat_t) == len(flat_p) > 0
  for (path, a), b in zip(flat_t, flat_p):
    # (att_dst shifts every logit of a parent alike: no gradient)
    assert np.abs(np.asarray(b)).max() > 0 or 'att_dst' in str(path), path
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6, err_msg=str(path))


# -- (a') grouped aggregation == segment aggregation, end to end ----------

@pytest.mark.parametrize('n_valid', [6, 1], ids=['ragged', 'one_live_seed'])
@pytest.mark.parametrize('num_layers,fanouts', [
    (3, (5, 3, 2)), (2, (10, 2)), (3, (3, 2))])
def test_grouped_aggregation_matches_segment(hub_graph, n_valid,
                                             num_layers, fanouts):
  """A sampled batch with ``hop_fanouts`` against the same batch with
  the field cleared: seed logits, every row (``return_all``) and every
  gradient to float32 rounding, on a ragged batch and on a batch whose
  one live seed leaves every other group of each hop masked."""
  plain = _batch(hub_graph, fanouts, n_valid=n_valid)
  assert plain.hop_fanouts is None and hop_fanouts(fanouts) == fanouts
  batch = plain.replace(hop_fanouts=hop_fanouts(fanouts))
  model = GraphSAGE(hidden_features=16, out_features=5,
                    num_layers=num_layers)
  params = jax.jit(model.init)(jax.random.key(0), batch)
  y = jnp.arange(BS) % 5
  eoffs = edge_hop_offsets(BS, fanouts)
  hops = tuple((eoffs[h], (eoffs[h + 1] - eoffs[h]) // k, k)
               for h, k in enumerate(fanouts))
  assert model.layer_groups(batch) == tuple(
      hops[:max(min(len(fanouts), num_layers - i), 1)]
      for i in range(num_layers))
  assert model.layer_groups(plain) == ((),) * num_layers
  # only SAGEConv reads groups
  assert GraphSAGE(16, 5, num_layers=num_layers, conv='gat').layer_groups(
      batch) == ((),) * num_layers

  def loss_and_logits(p, b):
    logits = model.apply(p, b)
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    return loss.mean(), logits

  grad = jax.jit(jax.value_and_grad(loss_and_logits, has_aux=True))
  (_, lo_g), g_g = grad(params, batch)
  (_, lo_p), g_p = grad(params, plain)
  np.testing.assert_allclose(np.asarray(lo_g), np.asarray(lo_p),
                             rtol=1e-5, atol=1e-6)
  for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_g),
                          jax.tree.leaves(g_p)):
    assert np.abs(np.asarray(b)).max() > 0, path
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6, err_msg=str(path))
  every_row = jax.jit(functools.partial(model.apply, return_all=True))
  np.testing.assert_allclose(np.asarray(every_row(params, batch)),
                             np.asarray(every_row(params, plain)),
                             rtol=1e-5, atol=1e-6)


def test_hop_fanouts_must_divide_the_hop_blocks(hub_graph):
  batch = _batch(hub_graph, (3, 2)).replace(hop_fanouts=(3, 5))
  with pytest.raises(ValueError, match='hop_fanouts'):
    GraphSAGE(16, 5, num_layers=2).layer_groups(batch)


# -- (b) the property the slice rests on ---------------------------------

@pytest.mark.parametrize('seeds', sorted(SEEDS))
@pytest.mark.parametrize('fanouts', [(3, 2, 2), (4, 3)])
def test_labels_are_hop_compact(hub_graph, seeds, fanouts):
  for n_valid, key in ((BS, 0), (5, 1), (1, 2)):
    out = _sample(hub_graph, fanouts, n_valid=n_valid, key=key,
                  seeds=seeds)
    _assert_hop_compact(out['row'], out['col'], out['edge_mask'],
                        out['node_count'], BS, fanouts)
    # the hubs and the duplicates make the hops overlap, or the test
    # would pass on labels that are never shared
    assert int(out['node_count']) < int(np.asarray(out['edge_mask']).sum())


@pytest.mark.parametrize('fanouts', [(3, 2, 2), (4, 3)])
def test_neighbor_loader_batches_are_hop_compact(hub_graph, fanouts):
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=hub_graph[2], num_nodes=N)
  ds.init_node_features(np.random.default_rng(2).normal(size=(N, FEAT))
                        .astype(np.float32))
  ds.init_node_labels(np.arange(N, dtype=np.int32) % 5)
  # 96 - 5 input nodes over batches of 8: the last batch is ragged
  loader = NeighborLoader(ds, list(fanouts), input_nodes=np.arange(5, N),
                          batch_size=BS, shuffle=True, seed=0)
  batches = list(loader)
  assert batches[-1].metadata['n_valid'] < BS
  for b in batches:
    assert b.node_hop_offsets == tuple(node_hop_offsets(BS, fanouts))
    assert b.edge_hop_offsets == tuple(edge_hop_offsets(BS, fanouts))
    assert b.hop_fanouts == fanouts
    assert b.x.shape[0] == b.node_hop_offsets[-1]
    _assert_hop_compact(b.row, b.col, b.edge_mask, b.node_count, BS,
                        fanouts)
  # the loader path and the fused path are one model: trimmed on both
  model = GraphSAGE(hidden_features=16, out_features=5, num_layers=2)
  b = batches[-1]
  params = model.init(jax.random.key(0), b)
  assert model.layer_rows(b) == (b.node_hop_offsets[1], BS)
  np.testing.assert_allclose(
      np.asarray(model.apply(params, b)),
      np.asarray(model.apply(params, b.replace(node_hop_offsets=None))),
      rtol=1e-5, atol=1e-6)


def test_link_loader_batches_are_not_trimmed():
  from glt_tpu.loader import LinkNeighborLoader
  from fixtures import ring_dataset
  ds = ring_dataset(num_nodes=40, feat_dim=8)
  loader = LinkNeighborLoader(ds, [2, 2], batch_size=4, seed=0)
  b = next(iter(loader))
  assert b.edge_hop_offsets is not None and b.node_hop_offsets is None


# -- (c) without the field, and with return_all: as before ----------------

def _sage_before(params, batch, num_layers, trim):
  """GraphSAGE (conv='sage') as it was before the nodes were trimmed:
  edges sliced by hop, every node row aggregated and multiplied."""
  x = batch.x
  n = x.shape[0]
  offs = batch.edge_hop_offsets
  for i in range(num_layers):
    end = offs[max(min(len(offs) - 1, num_layers - i), 1)] if trim \
        else offs[-1]
    row, col, mask = batch.row[:end], batch.col[:end], batch.edge_mask[:end]
    ok = mask & (row >= 0) & (col >= 0)
    msgs = jnp.take(x, jnp.clip(row, 0, n - 1), axis=0)
    seg = jnp.where(ok, jnp.clip(col, 0, n - 1), n)
    total = jax.ops.segment_sum(jnp.where(ok[:, None], msgs, 0.0), seg,
                                n + 1)
    cnt = jax.ops.segment_sum(ok.astype(msgs.dtype), seg, n + 1)
    agg = total[:n] / jnp.maximum(cnt[:n, None], 1.0)
    p = params['params'][f'conv{i}']
    x = (x @ p['lin_root']['kernel'] + p['lin_root']['bias']
         + agg @ p['lin_nbr']['kernel'])
    if i < num_layers - 1:
      x = jax.nn.relu(x)
  return x


@pytest.mark.parametrize('trim', [True, False])
def test_batch_without_the_field_computes_what_it_did(hub_graph, trim):
  fanouts = (3, 2, 2)
  batch = _batch(hub_graph, fanouts, n_valid=6)
  plain = batch.replace(node_hop_offsets=None)
  model = GraphSAGE(hidden_features=16, out_features=5, num_layers=3,
                    trim=trim)
  params = model.init(jax.random.key(0), batch)
  before = _sage_before(params, plain, 3, trim)
  budget = sample_budget(BS, fanouts)
  assert model.layer_rows(plain) == (budget,) * 3
  np.testing.assert_array_equal(np.asarray(model.apply(params, plain)),
                                np.asarray(before[:BS]))
  np.testing.assert_array_equal(
      np.asarray(model.apply(params, plain, return_all=True)),
      np.asarray(before))
  if not trim:   # trim=False switches the node trim off with the edges'
    assert model.layer_rows(batch) == (budget,) * 3
    np.testing.assert_array_equal(np.asarray(model.apply(params, batch)),
                                  np.asarray(before[:BS]))


@pytest.mark.parametrize('conv', ['sage', 'gcn', 'gat'])
def test_return_all_is_not_trimmed(hub_graph, conv):
  fanouts = (3, 2, 2)
  batch = _batch(hub_graph, fanouts, n_valid=6)
  plain = batch.replace(node_hop_offsets=None)
  model = GraphSAGE(hidden_features=16, out_features=5, num_layers=3,
                    conv=conv)
  params = model.init(jax.random.key(0), batch)
  budget = sample_budget(BS, fanouts)
  assert model.layer_rows(batch, return_all=True) == (budget,) * 3
  emb = model.apply(params, batch, method=model.embed)
  assert emb.shape == (budget, 5)
  np.testing.assert_array_equal(
      np.asarray(emb),
      np.asarray(model.apply(params, plain, return_all=True)))
  # and the seed rows of the untrimmed pass are the trimmed logits
  np.testing.assert_allclose(np.asarray(model.apply(params, batch)),
                             np.asarray(emb[:BS]), rtol=1e-5, atol=1e-6)


# -- (d) the trainer ------------------------------------------------------

@pytest.fixture(scope='module')
def mesh():
  return make_mesh(8)


def _trainer(mesh, hub_graph, trim, fanouts=(3, 2, 2), bs=4):
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=hub_graph[2], num_nodes=N)
  rng = np.random.default_rng(5)
  feats = rng.normal(size=(N, FEAT)).astype(np.float32)
  labels = rng.integers(0, 5, N).astype(np.int32)
  model = GraphSAGE(hidden_features=16, out_features=5, num_layers=3,
                    trim=trim)
  tx = optax.adam(1e-2)
  step = SPMDSageTrainStep(mesh, model, tx, ds.get_graph(),
                           ShardedFeature(feats, mesh), labels,
                           fanouts=list(fanouts), batch_size_per_device=bs)
  params = step.init_params(jax.random.key(0))
  return step, params, tx.init(params)


def _three_steps(step, params, opt, bs=4):
  rng = np.random.default_rng(0)
  losses = []
  for it in range(3):
    seeds = rng.permutation(N)[:8 * bs]
    keys = jax.random.split(jax.random.key(it), 8)
    params, opt, loss = step(params, opt, seeds, np.full(8, bs), keys)
    losses.append(np.asarray(loss))
  return np.stack(losses)


def test_trainer_records_layer_rows_and_trains_alike(mesh, hub_graph):
  bs, (k1, k2, _) = 4, (3, 2, 2)
  step, params, opt = _trainer(mesh, hub_graph, trim=True)
  assert step.layer_rows is None     # filled when the program is traced
  got = _three_steps(step, params, opt)
  assert step.layer_rows == (bs + bs * k1 + bs * k1 * k2, bs + bs * k1, bs)
  # groups each layer aggregates by the grouped reduce: one a frontier
  # slot of the hops it keeps
  assert step.layer_groups == step.layer_rows
  assert step.step_traces == 1
  gauges = get_registry().snapshot()['gauges']
  for name, values in (('model_layer_rows', step.layer_rows),
                       ('model_grouped_aggregation', step.layer_groups)):
    for i, n in enumerate(values):
      key = [k for k in gauges if k.startswith(name + '{')
             and f'layer="{i}"' in k and 'fn="train.step"' in k]
      assert key and gauges[key[0]] == n, (name, i, gauges)

  ref_step, ref_params, ref_opt = _trainer(mesh, hub_graph, trim=False)
  want = _three_steps(ref_step, ref_params, ref_opt)
  budget = sample_budget(bs, (3, 2, 2))
  assert ref_step.layer_rows == (budget,) * 3
  assert ref_step.layer_groups == (budget - bs * k1 * k2 * 2,) * 3
  assert np.isfinite(got).all() and (got[0] != got[-1]).all()
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_trainer_without_the_promise_counts_no_groups(mesh, hub_graph):
  """A batch that makes no promise (``hop_fanouts=None``, as a loader
  that gives none hands over) counts 0 groups for every layer."""
  step, _, _ = _trainer(mesh, hub_graph, trim=True)
  batch = step._dummy_batch()
  assert batch.hop_fanouts == (3, 2, 2)
  batch = batch.replace(hop_fanouts=None)
  step._note_layer_rows(batch)        # what a trace of the step does
  assert step.layer_groups == (0, 0, 0)
  gauges = get_registry().snapshot()['gauges']
  keys = [k for k in gauges if k.startswith('model_grouped_aggregation{')
          and 'fn="train.step"' in k]
  assert len(keys) == 3 and all(gauges[k] == 0 for k in keys)


def test_superstep_shares_the_trimmed_body(mesh, hub_graph):
  bs = 4
  step, params, opt = _trainer(mesh, hub_graph, trim=True)
  rng = np.random.default_rng(0)
  seeds_stack = np.stack([rng.permutation(N)[:8 * bs] for _ in range(3)])
  keys = jnp.stack([jax.random.split(jax.random.key(it), 8)
                    for it in range(3)])
  copy = lambda t: jax.tree.map(jnp.array, t)
  _, _, got = step.superstep(*copy((params, opt)), seeds_stack,
                             np.full((3, 8), bs), keys)
  assert step.superstep_traces == 1 and step.step_traces == 0
  assert step.layer_rows == (bs + bs * 3 + bs * 6, bs + bs * 3, bs)
  want = _three_steps(step, params, opt)
  np.testing.assert_array_equal(np.asarray(got), want)
