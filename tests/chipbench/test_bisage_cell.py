"""The user-item cell's own tests: its manifest entries resolve to files,
the configuration holds what it names, the generator is a function of the
seed, the yardstick's arithmetic on a hand-worked case, the reference's
two copies, the driver's weights and the scope window's inputs against the
model's own tree, every new reader silent without its scope or counter,
and a rehearsal of a run on the CPU at a size it holds, right and with a
fault planted."""
import importlib
import json
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import (bisage_scope_window, counter_window, flops_bisage,
                       graphgen_bipartite, run)

CELL = 'bisage-taobao-c1.fused'
NEW = ('bisage_sampler_device_ms', 'bisage_negative_device_ms',
       'bisage_embedding_device_ms', 'bisage_model_device_ms',
       'bisage_step_mfu_pct', 'bisage_step_roofline',
       'bisage_scope_unattributed_pct', 'bisage_node_slot_occupancy_pct',
       'bisage_edge_slot_occupancy_pct',
       'bisage_embedding_rows_touched_pct')
SCOPED, COUNTED = NEW[:4] + NEW[6:7], NEW[7:]


def tiny_cell():
  """The cell at a size the CPU holds: both types and all three
  relations, the counts and the widths cut (a test's own cut, not the
  configuration's)."""
  m, cell, cfg, traffic = run.load_cell(CELL)
  nodes = {'user': 900, 'item': 1500}
  edges = {'user': 9000, 'item': 6000}
  rels = [dict(r, num_edges=9000 if 'user' in (r['src'], r['dst'])
               else edges['item']) for r in cfg['relations']]
  cfg = dict(cfg, num_nodes=nodes, relations=rels, embedding_dim=8,
             hidden_dim=8, out_dim=8,
             limits={'loss_gap': 1e-3, 'grad_gap': 1e-2, 'change_gap': 1e-2},
             table_limits={'table_momentum_gap': 1e-2})
  traffic = dict(traffic, batch_per_chip=16, endpoint_seeds_per_chip=64,
                 fanout=[3, 2])
  return m, cell, cfg, traffic


@pytest.fixture
def tpu_sampler(monkeypatch):
  """The sampler's engines as ``auto`` resolves them on a TPU."""
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')


def test_the_new_entries_resolve_to_files():
  m, cell, cfg, traffic = run.load_cell(CELL)
  assert cell == {'name': CELL, 'config': 'bisage-taobao-c1',
                  'traffic': 'bilink-fused', 'chips': 1,
                  'why': cell['why']}
  # there, not where: a later cell's entries go behind these
  config = {c['name']: c for c in m['configs']}['bisage-taobao-c1']
  assert len(config['source']) <= 200 and len(cell['why']) <= 200
  assert len(config['why']) <= 200
  assert config['source'] == cfg['source']
  assert config['reduced'] == cfg['reduced'] == []
  assert os.path.exists(os.path.join(REPO, config['file']))
  assert traffic['driver'] == 'bilink_fused'
  by_name = {p['name']: p for p in m['per_layer']}
  assert set(NEW) <= set(by_name)
  for name in NEW:
    assert by_name[name]['workloads'] == [CELL]
    assert by_name[name]['moves'] == 'seeds_per_s'
    assert callable(importlib.import_module(
        'chipbench.layers.' + name).read)
  assert by_name['bisage_step_roofline']['unit'] == '%'
  # no other reader lists the cell; the two with no list read any cell
  for p in m['per_layer']:
    assert p['name'] in NEW or CELL not in p.get('workloads', [])
  assert 'workloads' not in by_name['host_ms_per_step']
  assert 'workloads' not in by_name['device_idle_pct']
  driver = importlib.import_module('chipbench.drivers.bilink_fused')
  assert all(callable(getattr(driver, f)) for f in ('build', 'step',
                                                    'verify'))
  for name in ('reference_bisage', 'calibrate_bisage', 'flops_bisage',
               'graphgen_bipartite', 'bisage_scope_window'):
    assert os.path.exists(os.path.join(REPO, 'chipbench', name + '.py'))


def test_the_configuration_holds_what_it_names():
  _, _, cfg, traffic = run.load_cell(CELL)
  assert cfg['num_nodes'] == cfg['published']['num_nodes'] == {
      'user': 987994, 'item': 4162024}
  assert cfg['published']['num_interactions'] == 100150807
  to, rev, sim = cfg['relations']
  assert to['num_edges'] == rev['num_edges'] == round(0.8 * 100150807)
  assert tuple(rev['reverse_of']) == (to['src'], to['name'], to['dst'])
  assert sim['num_edges'] == 10 * cfg['num_nodes']['item']
  assert cfg['num_edges'] == sum(r['num_edges'] for r in cfg['relations'])
  assert (cfg['embedding_dim'], cfg['hidden_dim'], cfg['out_dim'],
          cfg['num_layers']) == (64, 64, 64, 2)
  assert (cfg['dtype'], cfg['matmul_precision'],
          cfg['learning_rate']) == ('float32', 'default', 0.001)
  assert cfg['reduced'] == [] and 'dense' in cfg['optimizer']
  tables, rest = flops_bisage.num_params(cfg)
  assert tables == 329601152 == (cfg['parameters']['embed_user']
                                 + cfg['parameters']['embed_item'])
  assert rest == cfg['parameters']['rest']
  assert tables + rest == graphgen_bipartite.num_weights(cfg)
  for k in ('script', 'relu_after_conv2', 'dataset', 'train_split',
            'multi_edges', 'item_item', 'fanout_and_batch', 'negatives',
            'embedding_init', 'degree_law'):
    assert len(cfg['assumed'][k]) > 40, k
  # the accepted tests hold `limits` to three numbers: the fourth stands
  # beside them
  assert set(cfg['limits']) == {'loss_gap', 'grad_gap', 'change_gap'}
  assert set(cfg['table_limits']) == {'table_momentum_gap'}
  limits = dict(cfg['limits'], **cfg['table_limits'])
  assert all(0 < v < 1 for v in limits.values())
  assert set(limits) < set(cfg['limits_why'])
  assert (traffic['batch_per_chip'], traffic['fanout'],
          traffic['endpoint_seeds_per_chip'], traffic['warmup_steps']) == (
              2048, [8, 4], 8192, 3)
  assert traffic['negatives'] == dict(
      traffic['negatives'], mode='binary', amount=1, strict=True, trials=5,
      padding=True)
  assert 'relations_sampled' in traffic


def test_the_generator_is_a_function_of_the_seed():
  _, _, cfg, _ = tiny_cell()
  a = graphgen_bipartite.graph(cfg, 3_800_000_123)
  b = graphgen_bipartite.graph(cfg, 3_800_000_123)
  c = graphgen_bipartite.graph(cfg, 3_800_000_124)
  assert list(a) == [('user', 'to', 'item'), ('item', 'rev_to', 'user'),
                     ('item', 'to', 'item')]
  for e in a:
    np.testing.assert_array_equal(a[e][0], b[e][0])
    np.testing.assert_array_equal(a[e][1], b[e][1])
    assert a[e][1].shape == c[e][1].shape   # one shape for every seed
    rows = np.repeat(np.arange(a[e][0].shape[0] - 1), np.diff(a[e][0]))
    assert (np.diff(a[e][1])[np.diff(rows) == 0] >= 0).all()   # ascending
    assert a[e][1].max() < cfg['num_nodes'][e[2]]
  assert any((a[e][1] != c[e][1]).any() for e in a)
  # the reverse relation is the transpose, edge for edge
  pairs = lambda csr: np.stack([np.repeat(
      np.arange(csr[0].shape[0] - 1), np.diff(csr[0])), csr[1]], 1)
  fwd = pairs(a[('user', 'to', 'item')])
  rev = pairs(a[('item', 'rev_to', 'user')])[:, ::-1]
  key = lambda p: np.sort(p[:, 0].astype(np.int64) * 10000 + p[:, 1])
  np.testing.assert_array_equal(key(fwd), key(rev))
  # low item ids are popular; a user's mean degree is the configuration's
  assert np.bincount(fwd[:, 1], minlength=1500)[:150].sum() > 0.2 * len(fwd)
  rng = np.random.default_rng(1)
  got = graphgen_bipartite.positive_edges(*a[('user', 'to', 'item')], rng, 64)
  assert {tuple(p) for p in got} <= {tuple(p) for p in fwd}


def test_flops_and_bytes_on_a_hand_worked_case():
  cfg = {'num_nodes': {'user': 10, 'item': 20}, 'embedding_dim': 4,
         'hidden_dim': 4, 'out_dim': 4}
  batch, fanout = 2, [3, 2]
  # seeds 4 users, 4 items; hop 1: users 12, items 24; hop 2: users 48,
  # items (12 + 24) * 2 = 72
  assert flops_bisage.frontiers(batch, fanout) == [
      {'user': 4, 'item': 4}, {'user': 12, 'item': 24},
      {'user': 48, 'item': 72}]
  assert flops_bisage.node_slots(batch, fanout) == {'user': 64, 'item': 100}
  assert flops_bisage.edge_slots(batch, fanout) == (4 + 8) * 3 + (12 + 48) * 2
  conv = 2 * (2 * 4 * 4)            # root and neighbours, a row
  forward = (2 * 28 * conv          # both conv1 over items within a hop
             + 4 * conv * 3         # items' conv2, users' conv2 and conv3
             + 8 * 2 * 4 * 4        # two linear layers over 4 seeds each
             + 4 * 2 * (2 * 4 * 4 + 4))   # the decoder over 4 pairs
  assert flops_bisage.step_flops(cfg, batch, fanout) == 3 * forward
  rest = 5 * (2 * 16 + 4) + 2 * (16 + 4) + (32 + 4) + (4 + 1)
  assert flops_bisage.num_params(cfg) == (30 * 4, rest)
  assert flops_bisage.table_bytes(cfg) == 30 * 4 * 4 * 7
  assert flops_bisage.step_bytes(cfg, batch, fanout) == (
      30 * 4 * 4 * 7 + (100 + 4) * 4 * 4 * 2
      + (2 * 28 + 3 * 4) * 4 * 4 * 2 + 8 * 4 * 4 * 2 + rest * 24)
  least, bound = flops_bisage.least_step_seconds(
      cfg, batch, fanout, {'flops_per_s': 1e15, 'bytes_per_s': 1e3})
  assert bound == 'bytes'
  assert least == flops_bisage.step_bytes(cfg, batch, fanout) / 1e3


def test_the_cells_slots_and_its_least_step():
  _, _, cfg, traffic = run.load_cell(CELL)
  args = (traffic['batch_per_chip'], traffic['fanout'])
  assert flops_bisage.edge_slots(*args) == 98304 + 655360
  assert flops_bisage.node_slots(*args) == {'user': 4096 + 32768 + 262144,
                                            'item': 4096 + 65536 + 393216}
  from chipbench import peaks
  least, bound = flops_bisage.least_step_seconds(cfg, *args,
                                                 peaks.peaks('TPU v5e'))
  # dense Adam's seven passes over 1.32 GB bind: 11.3 ms and a little; a
  # share of a roofline cannot pass 100 %: a step is expected at 55 to 85
  assert bound == 'bytes' and 0.0112 < least < 0.0125


def test_the_references_two_copies_are_one_text():
  with open(os.path.join(REPO, 'chipbench', 'reference_bisage.py')) as f:
    ours = f.read()
  with open(os.path.join(REPO, 'glt_tpu', 'models', 'reference',
                         'bipartite_sage.py')) as f:
    theirs = f.read()
  assert ours == theirs
  assert 'glt_tpu' not in [line.split()[1].split('.')[0]
                           for line in ours.splitlines()
                           if line.startswith(('import ', 'from '))]


def test_the_drivers_weights_are_the_models_tree(tpu_sampler):
  """``graphgen_bipartite.weights`` and the scope window's parameters
  have the tree, the shapes and the types of the model's own ``init`` on
  the step's dummy batch, so the step's compiled program serves them."""
  from chipbench.drivers import bilink_fused
  _, _, cfg, traffic = tiny_cell()
  s = bilink_fused.build(cfg, traffic, 1, 5)
  shape = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
  want = shape(jax.eval_shape(s.trainer.init_params, jax.random.key(0)))
  assert shape(s.params) == want
  assert shape(graphgen_bipartite.weights(6, cfg)) == want
  table = np.asarray(s.params['params']['embed_item']['embedding'])
  assert np.isfinite(table).all() and 0.8 < table.std() < 1.2
  before = bilink_fused.compilations(s)
  params, opt, batches = bisage_scope_window.inputs(s.trainer, cfg, traffic,
                                                    steps=2)
  assert shape(params) == want and shape(opt) == shape(s.opt)
  for pairs, n_valid, key in batches:
    params, opt, loss = s.trainer(params, opt, pairs, n_valid, key)
  assert np.isfinite(np.asarray(loss)).all()
  assert bilink_fused.compilations(s) == before


def test_every_new_reader_is_silent_without_its_scope_or_counter():
  _, _, cfg, traffic = tiny_cell()
  run_ = {'cfg': cfg, 'traffic': traffic, 'trace': {}, 'window': {}}
  read = lambda name: importlib.import_module(
      'chipbench.layers.' + name).read(run_)
  bisage_scope_window._PROFILE[:] = [None]
  counter_window._TAKEN[:] = [None]
  try:
    assert [read(name) for name in SCOPED + COUNTED] == [None] * 8
    # a program with other scopes and other counters: still nothing
    bisage_scope_window._PROFILE[:] = [{
        'stages': {'model_step/forward/HGT/in_b': 1.0}, 'layers': {},
        'busy_ms': 1.0, 'unscoped_ms': 0.0, 'mixed_ms': 0.0}]
    counter_window._TAKEN[:] = [{'store_chunks': {'occupancy_pct': 1.0}}]
    assert [read(name) for name in SCOPED[:4] + COUNTED] == [None] * 7
  finally:
    bisage_scope_window._PROFILE.clear()
    counter_window._TAKEN.clear()


def test_the_embedding_reader_sums_the_tables_own_stages():
  found = {'stages': {
      'model_step/forward/BipartiteSAGE/embed_item': 1.0,
      'model_step/forward/BipartiteSAGE/embed_user': 2.0,
      'model_step/update/tables': 4.0, 'model_step/update/rest': 8.0,
      'model_step/forward/BipartiteSAGE/item_encoder/conv1/lin_nbr': 16.0,
      'model_step/forward/link_loss': 32.0,
      'sampler/negative': 64.0, 'sampler/dedup0/item': 128.0},
           'layers': {'model_step': 63.0, 'sampler': 192.0},
           'busy_ms': 300.0, 'unscoped_ms': 20.0, 'mixed_ms': 25.0}
  bisage_scope_window._PROFILE[:] = [found]
  read = lambda name: importlib.import_module(
      'chipbench.layers.' + name).read({})
  try:
    assert read('bisage_embedding_device_ms') == 7.0
    assert read('bisage_model_device_ms') == 63.0
    assert read('bisage_negative_device_ms') == 64.0
    assert read('bisage_sampler_device_ms') == 192.0
    assert read('bisage_scope_unattributed_pct') == 15.0
  finally:
    bisage_scope_window._PROFILE.clear()


def test_the_traced_readers_on_a_hand_made_run():
  _, _, cfg, traffic = run.load_cell(CELL)
  from chipbench import peaks
  run_ = {'cfg': cfg, 'traffic': traffic, 'device_kind': 'TPU v5e',
          'trace': {'steps': 10, 'top_window_s': 0.7, 'top_busy_s': 0.69}}
  read = lambda name: importlib.import_module(
      'chipbench.layers.' + name).read(run_)
  args = (cfg, traffic['batch_per_chip'], traffic['fanout'])
  peak = peaks.peaks('TPU v5e')
  assert read('bisage_step_mfu_pct') == pytest.approx(
      100 * flops_bisage.step_flops(*args) / 0.07 / peak['flops_per_s'])
  assert 0 < read('bisage_step_mfu_pct') < 1
  assert read('bisage_step_roofline') == pytest.approx(
      100 * flops_bisage.step_bytes(*args) / peak['bytes_per_s'] / 0.069)
  assert 10 < read('bisage_step_roofline') < 25
  counter_window._TAKEN[:] = [{
      'nodes_by_hop': {'occupancy_pct': 40.0},
      'edges_by_hop': {'occupancy_pct': 50.0},
      'embedding_rows': {'occupancy_pct': 6.0}}]
  try:
    assert [read(n) for n in COUNTED] == [40.0, 50.0, 6.0]
  finally:
    counter_window._TAKEN.clear()


def _rehearse(monkeypatch, seconds=0.3):
  """The rest of a run after the look for a chip, on the CPU."""
  cell = tiny_cell()
  monkeypatch.setattr(run, 'load_cell', lambda name: cell)
  return run.run_cell('tiny', 3_800_000_019, seconds, False)


def test_rehearsal_of_a_run_comes_out_correct(monkeypatch, tpu_sampler):
  line = _rehearse(monkeypatch)
  assert line['correct'] is True, line['compared']
  assert line['attempted'] > 3 and line['failed'] == 0
  assert set(line['metrics']) == {'seeds_per_s', 'step_p90_ms', 'setup_s'}
  assert set(line['compared']) == {
      'loss_gap', 'grad_gap', 'change_gap', 'table_momentum_gap',
      'sample_violations', 'negative_violations', 'counter_gap',
      'compilations'}
  for k in ('compilations', 'sample_violations', 'negative_violations',
            'counter_gap'):
    assert line['compared'][k] == {'value': 0, 'limit': 0}, k
  # on the CPU the default precision rounds nothing: the gaps are rounding
  assert line['compared']['grad_gap']['value'] < 1e-4
  assert line['compared']['table_momentum_gap']['value'] < 1e-3
  json.dumps(line)


def _unchanged(call):
  return lambda self, params, opt, seeds, n_valid, key: (
      params, opt, call(self, jax.tree.map(jax.numpy.copy, params),
                        jax.tree.map(jax.numpy.copy, opt), seeds, n_valid,
                        key)[2])


def _half_batch(call):
  return lambda self, params, opt, seeds, n_valid, key: call(
      self, params, opt, seeds, n_valid // 2, key)


@pytest.mark.parametrize('fault', ['unchanged', 'half_batch'])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch,
                                                   tpu_sampler, fault):
  from glt_tpu.distributed import dist_hetero
  call = dist_hetero.DistHeteroTrainStep.__call__
  monkeypatch.setattr(
      dist_hetero.DistHeteroTrainStep, '__call__',
      {'unchanged': _unchanged, 'half_batch': _half_batch}[fault](call))
  line = _rehearse(monkeypatch)
  assert line['correct'] is False, line['compared']


@pytest.mark.parametrize('fault,number', [
    ('lazy_update', 'table_momentum_gap'), ('half_batch', 'grad_gap')])
def test_a_planted_fault_fails_its_number(tpu_sampler, fault, number):
  """The faults of the calibration, planted in the program's place (the
  reference with the fault, held against the reference without): a lazy
  update of the tables leaves the momentum rows where they stood."""
  from chipbench import reference_bisage
  from chipbench.drivers import bilink_fused
  _, _, cfg, traffic = tiny_cell()
  s = bilink_fused.build(cfg, traffic, 1, 5)
  assert all(ids.size for ids in s.watch.values())
  ref = bilink_fused.follow(s)
  good = reference_bisage.compare(bilink_fused.program_readings(s), ref)
  assert max(good.values()) < 1e-3, good
  gaps = reference_bisage.compare(bilink_fused.follow(s, fault=fault), ref)
  assert gaps[number] > dict(cfg['limits'], **cfg['table_limits'])[number], gaps
