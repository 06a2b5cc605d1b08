"""What the fused per-batch steps count: node and edge occupancy by hop
against oracles that share nothing with the step, the frontier rows each
hop read (every slot at these sizes: a frontier is one chunk of
``ops/sample.py::HOP_CHUNK``; tests/test_sample_live_rows.py patches the
chunk small), the store's and the
link front's counters in the same flat dict, the 128 newest steps held
on the device, and a read that traces and compiles nothing. On the
CPU."""
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, 'tests', 'chipbench'))

from glt_tpu.obs.device import COUNTER_STEPS
from glt_tpu.parallel.train import LINK_COUNTERS, STORE_COUNTERS

from test_parallel import _tiny_step   # 64 nodes, 64 seeds a device

HOPS = ['edges_by_hop', 'hop_rows_read', 'nodes_by_hop']


def _sage_cell(chips):
  from chipbench.drivers import fused
  import test_chipbench
  _, _, cfg, traffic = test_chipbench.tiny_cell(chips)
  return fused, fused.build(cfg, traffic, chips, 5)


def _walk(s, seeds, key, fanout):
  """``(nodes_by_hop [H + 1], edges_by_hop [H])`` of one chip's batch by
  the benchmark's numpy sampler, which returns a whole sample: the first
  ``h`` hops of it are the sample of the fanout's first ``h`` entries on
  the same stream."""
  from chipbench import reference
  nodes, edges = [np.unique(seeds).size], [0]
  for h in range(1, len(fanout) + 1):
    found, child, _ = reference.sample(s.indptr, s.indices, seeds, key,
                                       fanout[:h])
    nodes.append(found.shape[0])
    edges.append(child.shape[0])
  return np.diff(nodes, prepend=0), np.diff(edges)


@pytest.mark.parametrize('chips', [1, 4])
def test_sage_steps_count_what_the_numpy_sampler_finds(chips):
  driver, s = _sage_cell(chips)
  t = s.trainer
  counted = t.counters()
  warm = s.traffic['warmup_steps']
  assert counted['step'].tolist() == list(range(warm))
  assert counted['step'].dtype == np.int64
  assert sorted(set(counted) - {'step'}) == HOPS + (
      sorted(STORE_COUNTERS) if chips > 1 else ['store_chunks'])
  hops = len(s.fanout)
  assert counted['nodes_by_hop'].shape == (warm, chips, hops + 1)
  assert counted['edges_by_hop'].shape == (warm, chips, hops)
  # a frontier of one chunk or fewer is read whole
  np.testing.assert_array_equal(
      counted['hop_rows_read'],
      np.broadcast_to(t.counter_slots()['hop_rows_read'],
                      (warm, chips, hops)))
  assert all(v.dtype == np.int32 for k, v in counted.items() if k != 'step')
  for step in range(warm):
    seeds, keys = driver.feed(s, step)
    for d in range(chips):
      nodes, edges = _walk(s, np.asarray(seeds[d * s.batch:(d + 1) * s.batch]),
                           jax.random.fold_in(keys[d], d), s.fanout)
      np.testing.assert_array_equal(counted['nodes_by_hop'][step, d], nodes)
      np.testing.assert_array_equal(counted['edges_by_hop'][step, d], edges)
  # distinct seeds: hop 0 is the batch; the sum over hops is node_count,
  # which is what a chip asks its store for
  assert (counted['nodes_by_hop'][..., 0] == s.batch).all()
  if chips > 1:
    np.testing.assert_array_equal(counted['nodes_by_hop'].sum(-1),
                                  counted['store_requests'])


@pytest.mark.parametrize('chips', [1, 4])
def test_sage_slots_are_the_hop_budgets(chips):
  _, s = _sage_cell(chips)
  t = s.trainer
  slots = t.counter_slots()
  b, (k0, k1, k2) = s.batch, s.fanout
  assert slots['nodes_by_hop'].tolist() == [b, b * k0, b * k0 * k1,
                                            b * k0 * k1 * k2]
  assert slots['edges_by_hop'].tolist() == [b * k0, b * k0 * k1,
                                            b * k0 * k1 * k2]
  assert slots['hop_rows_read'].tolist() == [b, b * k0, b * k0 * k1]
  counted = t.counters()
  for name in HOPS:
    assert slots[name].shape == counted[name].shape[2:]
    assert (counted[name] <= slots[name]).all()
  if chips == 1:
    # one shard serves in place, its request slots in one chunk here
    assert sorted(slots) == HOPS + ['store_chunks']
    assert int(slots['store_chunks']) == 1
    assert (counted['store_chunks'] == 1).all()
    return
  budget = int(slots['nodes_by_hop'].sum())
  cap = t.feature.exchange_cap(budget)
  assert (int(slots['store_requests']), int(slots['store_bucket_max']),
          int(slots['store_rounds'])) == (budget, cap, -(-budget // cap))
  # a bucket fuller than its cap drains in more rounds, the same count on
  # every chip: the mesh's fullest bucket over the cap, rounded up
  fullest = counted['store_bucket_max'].max(axis=1, keepdims=True)
  np.testing.assert_array_equal(counted['store_rounds'],
                                np.broadcast_to(-(-fullest // cap), (3, 4)))
  assert (counted['store_rounds'] <= slots['store_rounds']).all()


def test_the_papers100m_step_would_state_the_issues_slots():
  """The hop budgets of the three SAGE cells, from the functions the step
  reads them from (no step of that size is built here)."""
  from glt_tpu.ops.pipeline import edge_hop_offsets, node_hop_offsets
  fanout = [15, 10, 5]
  assert np.diff(node_hop_offsets(1024, fanout), prepend=0).tolist() == [
      1024, 15360, 153600, 768000]
  assert np.diff(edge_hop_offsets(1024, fanout)).tolist() == [
      15360, 153600, 768000]
  # the frontiers: 169,984 slots, of which hops 1 and 2 are read by
  # chunks of their live rows
  from glt_tpu.ops import sample
  frontiers = 1024 * np.cumprod([1] + fanout[:-1])
  assert frontiers.tolist() == [1024, 15360, 153600]
  assert frontiers[0] <= sample.HOP_CHUNK < frontiers[1]


def _typed_cell(name):
  import test_hgt_cell
  import test_rgat_cell
  from chipbench.drivers import hetero_fused, hgt_fused
  driver, cell = {'rgat': (hetero_fused, test_rgat_cell.tiny_cell),
                  'hgt': (hgt_fused, test_hgt_cell.tiny_cell)}[name]
  _, _, cfg, traffic = cell()
  return driver, driver.build(cfg, traffic, 1, 5)


@pytest.mark.parametrize('name', ['rgat', 'hgt'])
def test_typed_steps_count_what_their_kept_sample_holds(name):
  driver, s = _typed_cell(name)
  t = s.trainer
  assert set(t.counter_node_types) == set(t.node_budget)
  assert t.counter_edge_types == tuple(t.edge_budget)
  slots = t.counter_slots()
  hops = len(s.fanout)
  types, rels = len(t.counter_node_types), len(t.counter_edge_types)
  assert slots['nodes_by_hop'].shape == (types, hops + 1)
  assert slots['edges_by_hop'].shape == (rels, hops)
  assert slots['edges_by_hop'].sum(1).tolist() == [
      t.edge_budget[e] for e in t.counter_edge_types]
  # a relation's frontier in a hop is its edge slots over the fanout
  widths = {t._final_key(e): np.abs(k)
            for e, k in t.sampler.num_neighbors.items()}
  np.testing.assert_array_equal(
      slots['hop_rows_read'] * np.stack([widths[e]
                                         for e in t.counter_edge_types]),
      slots['edges_by_hop'])
  # a type no frontier reaches holds one slot and no hop
  assert [max(int(n), 1) for n in slots['nodes_by_hop'].sum(1)] == [
      t.node_budget[k] for k in t.counter_node_types]
  offsets = t._batch_static['edge_hop_offsets_dict']
  # every later step against the sample it kept: the oracle is the
  # structure itself, by type and, for the edges, by hop
  for step in range(3, 6):
    np.asarray(driver.step(s, step))
    kept = jax.tree.map(np.asarray, t.last_sample)
    counted = t.counters()
    assert counted['step'][-1] == step
    nodes, edges = counted['nodes_by_hop'][-1], counted['edges_by_hop'][-1]
    assert nodes.shape == (1,) + slots['nodes_by_hop'].shape
    assert edges.shape == (1,) + slots['edges_by_hop'].shape
    for i, k in enumerate(t.counter_node_types):
      assert nodes[0, i].sum() == kept['node_count'][k][0]
      assert (nodes[0, i] <= slots['nodes_by_hop'][i]).all()
    assert nodes[0, t.counter_node_types.index(s.seed_type), 0] == s.batch
    for i, e in enumerate(t.counter_edge_types):
      mask = kept['edge_mask'][e][0]
      by_hop = [int(mask[a:b].sum()) for a, b in zip(offsets[e][:-1],
                                                    offsets[e][1:])]
      assert edges[0, i].tolist() == by_hop
    np.testing.assert_array_equal(counted['hop_rows_read'][-1, 0],
                                  slots['hop_rows_read'])
    assert sorted(set(counted) - {'step'}) == HOPS + ['store_chunks']
    # a type's request slots are one chunk at this size, gathered where
    # the type holds a node
    assert slots['store_chunks'].tolist() == [1] * types
    assert counted['store_chunks'][-1, 0].tolist() == [
        int(kept['node_count'][k][0] > 0) for k in t.counter_node_types]


def test_a_typed_step_counts_alike_with_and_without_keep_sample():
  import test_typed_build_forms as forms
  edges, feats, labels = forms.typed_graph()
  kept, tx = forms.build_step(edges, feats, labels, 2, 2, keep_sample=True)
  plain, _ = forms.build_step(edges, feats, labels, 2, 2)
  with pytest.raises(RuntimeError, match='no per-batch step has run'):
    plain.counters()
  params = kept.init_params(jax.random.key(0))
  seeds, key = forms.feed(0)
  nv = np.full(1, forms.BATCH)
  for step in (kept, plain):
    step(params, tx.init(params), seeds, nv, key)
  assert plain.last_sample is None
  got, want = plain.counters(), kept.counters()
  assert got.keys() == want.keys() == {'step', *HOPS, 'store_chunks'}
  for k in got:
    np.testing.assert_array_equal(got[k], want[k])
  for i, t in enumerate(kept.counter_node_types):
    assert want['nodes_by_hop'][0, 0, i].sum() == np.asarray(
        kept.last_sample['node_count'][t])[0]


def _link_cell(chips):
  import test_link_cell
  from chipbench.drivers import link_fused
  _, _, cfg, traffic = test_link_cell.tiny_cell()
  return link_fused, link_fused.build(cfg, traffic, chips, 5)


def test_a_link_step_over_four_shards_holds_every_family():
  _, s = _link_cell(4)
  t = s.trainer
  counted = t.counters()
  assert sorted(set(counted) - {'step'}) == sorted(
      HOPS + list(LINK_COUNTERS) + list(STORE_COUNTERS))
  assert counted['seeds'].shape == (3, 4, 4 * s.batch)
  # the hop-0 nodes are the distinct endpoints; the rest as a node step
  np.testing.assert_array_equal(counted['nodes_by_hop'][..., 0],
                                counted['seed_unique'])
  np.testing.assert_array_equal(counted['nodes_by_hop'].sum(-1),
                                counted['store_requests'])
  # the two older reads are views of the newest entry
  link, store = t.link_counters(), t.store_counters()
  assert sorted(link) == sorted(LINK_COUNTERS)
  assert sorted(store) == sorted(STORE_COUNTERS)
  for k, v in {**link, **store}.items():
    np.testing.assert_array_equal(v, counted[k][-1])
  # and they agree with what the driver read after each warm-up step
  for step, got in enumerate(s.counted):
    for k, v in got.items():
      np.testing.assert_array_equal(v, counted[k][step])
  slots = t.counter_slots()
  assert 'seeds' not in slots
  assert (int(slots['negatives_rejected']), int(slots['negatives_padded']),
          int(slots['seed_unique'])) == (5 * s.batch, s.batch, 4 * s.batch)


def test_the_older_reads_keep_their_errors():
  _, node = _sage_cell(1)
  with pytest.raises(RuntimeError, match='no link step has run'):
    node.trainer.link_counters()
  with pytest.raises(RuntimeError, match='serves in place'):
    node.trainer.store_counters()


def test_before_the_first_step_every_read_raises():
  step, *_ = _tiny_step(2)
  with pytest.raises(RuntimeError, match='no per-batch step has run'):
    step.counters()
  with pytest.raises(RuntimeError, match='no per-batch step has run'):
    step.store_counters()
  assert sorted(step.counter_slots()) == sorted(HOPS + list(STORE_COUNTERS))


def test_the_newest_128_steps_are_held_and_a_read_compiles_nothing():
  from glt_tpu.obs.perf import compile_counts
  compiled = []
  jax.monitoring.register_event_duration_secs_listener(
      lambda name, *a, **kw: compiled.append(name)
      if name.endswith('backend_compile_duration') else None)
  step, params, opt, seeds, n_valid, keys = _tiny_step(2)
  for _ in range(COUNTER_STEPS + 2):
    params, opt, loss = step(params, opt, seeds, n_valid, keys)
  # nothing was fetched on the way: what is held is still on the device
  assert all(isinstance(a, jax.Array)
             for _, c in step._counted for a in c.values())
  before = (step.step_traces, step._step_fn._cache_size(),
            sum(compile_counts().values()), len(compiled))
  counted = step.counters()
  slots = step.counter_slots()
  step.store_counters()
  assert (step.step_traces, step._step_fn._cache_size(),
          sum(compile_counts().values()), len(compiled)) == before
  assert before[:2] == (1, 1)
  assert counted['step'].tolist() == list(range(2, COUNTER_STEPS + 2))
  assert counted['nodes_by_hop'].shape == (COUNTER_STEPS, 2, 3)
  # the same seeds and keys every step: the same counts
  assert (counted['nodes_by_hop'] == counted['nodes_by_hop'][0]).all()
  assert (counted['nodes_by_hop'].sum(-1) <= slots['nodes_by_hop'].sum()
          ).all()


def test_the_supersteps_count_nothing():
  step, params, opt, seeds, n_valid, keys = _tiny_step(2)
  params, opt, _ = step(params, opt, seeds, n_valid, keys)
  k = jax.random.split(jax.random.key(2), (2, 2))
  out = step.superstep(params, opt, np.stack([seeds, seeds]),
                       np.stack([n_valid, n_valid]), k)
  assert len(out) == 3 and np.asarray(out[2]).shape == (2, 2)
  assert step.counters()['step'].tolist() == [0]


def test_a_masked_batch_counts_its_valid_seeds_alone():
  step, params, opt, seeds, n_valid, keys = _tiny_step(1)
  step(params, opt, seeds, np.asarray([5]), keys)
  counted = step.counters()
  assert counted['nodes_by_hop'][0, 0, 0] == np.unique(seeds[:5]).size
  assert counted['edges_by_hop'][0, 0, 0] <= 5 * 3
