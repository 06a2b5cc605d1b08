"""The SEAL cell's own tests: its manifest entries are there and resolve
to files, the configuration holds the recipe's widths, the yardstick's
arithmetic on a hand-worked case, the graph's symmetrisation, the driver's
checks on what a step hands back, a rehearsal of a run on the CPU at a
size it holds (right, and with faults planted), and the readers on a
canned profile."""
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

from chipbench import (counter_window, flops_seal, graphgen, graphgen_seal,
                       reference_link, reference_seal, run,
                       seal_scope_window)
from chipbench.drivers import seal_fused

CELL = 'seal-papers100m-c1.fused'
CONFIG = 'seal-dgcnn-papers100m-c1'
NEW = ('seal_sampler_device_ms', 'seal_enclose_device_ms',
       'seal_drnl_device_ms', 'seal_negative_device_ms',
       'seal_feature_device_ms', 'seal_model_device_ms',
       'seal_pool_device_ms', 'seal_step_mfu_pct', 'seal_step_roofline',
       'seal_scope_unattributed_pct', 'seal_node_slot_occupancy_pct',
       'seal_links_capped_pct')
SCOPED = NEW[:7] + NEW[9:10]


def tiny_cell():
  """The cell at a size the CPU holds (a test's own cut, not the
  configuration's), with the real cell's limits."""
  m, cell, cfg, traffic = run.load_cell(CELL)
  cfg = dict(cfg, num_nodes=20000, num_edges=291000, feature_dim=16,
             hidden_dim=8, sortpool_k=12, max_z=50, conv1d_kernels=[25, 5])
  traffic = dict(traffic, batch_per_chip=8, endpoint_seeds_per_chip=32,
                 links_per_chip=16, fanout=[6], node_slots=14,
                 enclose=dict(tile_budget=24, hub_width=100, hub_pairs=128))
  return m, cell, cfg, traffic


def test_the_new_entries_are_there_and_resolve_to_files():
  m, cell, cfg, traffic = run.load_cell(CELL)
  assert cell == {'name': CELL, 'config': CONFIG, 'traffic': 'seal-fused',
                  'chips': 1, 'why': cell['why']}
  assert len(cell['why']) <= 200
  config = {c['name']: c for c in m['configs']}[CONFIG]
  assert config['source'] == cfg['source'] and len(config['source']) <= 200
  assert 'SEAL_OGB seal_link_pred.py' in config['source']
  assert config['reduced'] == cfg['reduced'] == ['num_nodes', 'num_edges']
  assert os.path.exists(os.path.join(REPO, config['file']))
  assert [w['name'] for w in m['workloads'] if w['config'] == CONFIG] == [
      CELL]
  assert traffic['driver'] == 'seal_fused'
  assert (traffic['batch_per_chip'], traffic['endpoint_seeds_per_chip'],
          traffic['links_per_chip'], traffic['num_hops'], traffic['fanout'],
          traffic['node_slots'], traffic['dispatch'], traffic['run_ahead'],
          traffic['warmup_steps'], traffic['step_program']) == (
              256, 1024, 512, 1, [127], 256, 'per_batch', 1, 3, 'jit_step')
  neg = traffic['negatives']
  assert (neg['mode'], neg['amount'], neg['strict'], neg['trials'],
          neg['padding']) == ('binary', 1, True, 5, True)
  spec = seal_fused.make_spec(cfg, traffic)
  assert spec.node_slots == 256 and spec.max_z == 1000
  by_name = {p['name']: p for p in m['per_layer']}
  for name in NEW:
    assert by_name[name]['workloads'] == [CELL]
    assert by_name[name]['moves'] == 'seeds_per_s'
    assert callable(importlib.import_module(
        'chipbench.layers.' + name).read)
  assert by_name['seal_step_roofline']['layer'] == 'kernels'
  # no accepted reader lists the new cell; the two that read every cell do
  for p in m['per_layer']:
    if not p['name'].startswith('seal_'):
      assert CELL not in p.get('workloads', [])
  assert 'workloads' not in by_name['host_ms_per_step']
  assert 'workloads' not in by_name['device_idle_pct']


def test_the_configuration_holds_the_recipes_widths_on_c1s_graph():
  _, _, cfg, traffic = run.load_cell(CELL)
  _, _, c1, _ = run.load_cell('papers100m-c1.fused')
  for k in ('num_nodes', 'num_edges', 'feature_dim', 'published', 'dtype',
            'matmul_precision', 'optimizer'):
    assert cfg[k] == c1[k], k
  assert (cfg['hidden_dim'], cfg['num_gcn_layers'],
          cfg['sort_key_channels'], cfg['max_z'], cfg['conv1d_channels'],
          cfg['conv1d_kernels'], cfg['mlp_hidden'], cfg['feature_dim'],
          cfg['learning_rate']) == (32, 3, 1, 1000, [16, 32], [97, 5], 128,
                                    128, 1e-4)
  # the first Conv1d's kernel is the concatenated GCN width
  assert cfg['conv1d_kernels'][0] == 32 * 3 + 1
  assert isinstance(cfg['sortpool_k'], int) and cfg['sortpool_k'] >= 10
  assert 'seal_sizes.py --workload ' + CELL in cfg['assumed']['sortpool_k']
  assert set(cfg['limits']) == {'loss_gap', 'grad_gap', 'change_gap'}
  assert 0 < cfg['sort_tolerance'] < 0.01
  for why in cfg['assumed'].values():
    assert len(why) > 20
  model = seal_fused.make_model(cfg)
  assert (model.hidden, model.num_layers, model.k, model.max_z) == (
      32, 3, cfg['sortpool_k'], 1000)
  # bytes resident before any temporary: over a quarter of the chip
  resident = (cfg['num_nodes'] * cfg['feature_dim'] * 4
              + 2 * cfg['num_edges'] * 4 + cfg['num_nodes'] * 4)
  assert 0.30 < resident / 16e9 < 0.33
  assert flops_seal.links(traffic) * flops_seal.nodes_a_link(traffic) \
      == 131072


def test_flops_and_bytes_on_a_hand_worked_case():
  cfg = {'feature_dim': 6, 'hidden_dim': 4, 'num_gcn_layers': 2,
         'sort_key_channels': 1, 'sortpool_k': 12, 'max_z': 10,
         'conv1d_channels': [3, 5], 'conv1d_kernels': [9, 5],
         'mlp_hidden': 7}
  traffic = {'batch_per_chip': 2, 'fanout': [3]}
  # 4 links of 8 nodes; GCN 10 -> 4 -> 4 -> 1; pooled width 9; 12 pooled
  # nodes -> 6 positions -> 2 after the 5-tap convolution
  gcn = 8 * 2 * (10 * 4 + 4 * 4 + 4 * 1)
  head = 12 * 2 * 9 * 3 + 2 * 2 * 5 * 3 * 5 + 2 * 2 * 5 * 7 + 2 * 7
  assert flops_seal.link_flops(cfg, traffic) == gcn + head
  assert flops_seal.step_flops(cfg, traffic) == 3 * 4 * (gcn + head)
  params = (10 * 4 + (10 * 4 + 4) + (4 * 4 + 4) + (4 * 1 + 1)
            + 9 * 3 + 3 + 5 * 3 * 5 + 5 + 2 * 5 * 7 + 7 + 7 + 1)
  assert flops_seal.num_params(cfg) == params
  assert flops_seal.step_bytes(cfg, traffic) == (
      32 * 6 * 4 * 2 + 32 * 4 * 4 * 2 + 32 * (4 + 4 + 1) * 4 * 2
      + params * 4 * 3 * 2)
  least, bound = flops_seal.least_step_seconds(
      cfg, traffic, {'flops_per_s': 1e3, 'bytes_per_s': 1e9})
  assert bound == 'flops'
  _, _, real, real_traffic = run.load_cell(CELL)
  least, bound = flops_seal.least_step_seconds(
      real, real_traffic, {'flops_per_s': 197e12, 'bytes_per_s': 819e9})
  assert bound == 'bytes' and 2e-4 < least < 5e-4
  assert 5e9 < flops_seal.step_flops(real, real_traffic) < 9e9


def test_the_graph_read_undirected():
  indptr, indices = graphgen.csr(5000, 72750, 3_000_000_019)
  ptr, idx, num_edges = graphgen_seal.symmetric_csr(indptr, indices, 5000)
  assert ptr[-1] == num_edges and idx.shape[0] % graphgen_seal.TILE == 0
  # as long as E2 can be, whatever the seed made it: one program a cell
  assert num_edges < idx.shape[0] == -(-2 * 72750 // 128) * 128
  assert (idx[num_edges:] == -1).all() and idx[:num_edges].min() >= 0
  row = np.repeat(np.arange(5000), np.diff(ptr))
  col = idx[:num_edges]
  assert (row != col).all()                                  # no loops
  assert not ((np.diff(col) <= 0) & (np.diff(row) == 0)).any()  # ascending
  fwd = set(zip(row.tolist(), col.tolist()))
  assert fwd == {(c, r) for r, c in fwd}                     # symmetric
  src = np.repeat(np.arange(5000), np.diff(indptr))
  want = {(a, b) for a, b in zip(src.tolist(), indices.tolist()) if a != b}
  assert fwd == want | {(b, a) for a, b in want}             # and no more
  again = graphgen_seal.symmetric_csr(indptr, indices, 5000, chunks=3)
  assert np.array_equal(again[1], idx)


@pytest.fixture(scope='module')
def built():
  _, _, cfg, traffic = tiny_cell()
  return seal_fused.build(cfg, traffic, 1, 3_000_000_019), cfg, traffic


def test_the_drivers_checks_pass_and_catch_what_they_should(built):
  s, cfg, traffic = built
  got = s.counted[0]
  assert seal_fused.sample_violations(s, got) == 0
  assert seal_fused.negative_violations(s, got) == 0
  adj, z, mask, depth = reference_seal.blocks(s.indptr, s.indices,
                                              got['nodes'], cfg['max_z'])
  assert np.array_equal(seal_fused.blocks_of(s, got), adj)
  assert np.array_equal(got['z'], z)
  again = seal_fused.recount(s, 0, got, adj, z, depth)
  assert seal_fused.counter_gap(got, again) == 0
  assert set(again) == {k for k in got if np.asarray(got[k]).size <= 2}
  # positives are edges of the graph as generated, drawn once, no loop
  pairs = s.pairs.reshape(-1, 2)
  assert reference_link.is_edge(*s.directed, pairs[:, 0], pairs[:, 1]).all()
  assert (pairs[:, 0] != pairs[:, 1]).all()
  # a fringe node that is no neighbour, a node twice, a slot moved
  moved = dict(got, nodes=got['nodes'].copy())
  live = int((got['nodes'][0] >= 0).sum())
  moved['nodes'][0, live - 1] = got['nodes'][0, live - 2]
  assert seal_fused.sample_violations(s, moved) >= 1
  moved['nodes'][0, :2] = got['nodes'][0, 1::-1]
  assert seal_fused.sample_violations(s, moved) >= 2
  # a counter off by one
  assert seal_fused.counter_gap(dict(got, tiles_read=got['tiles_read'] + 1),
                                again) == 1
  # a negative that is an edge and was not padded
  swapped = got['seeds'].copy()
  b = traffic['batch_per_chip']
  swapped[b], swapped[3 * b] = swapped[0], swapped[2 * b]
  assert seal_fused.negative_violations(s, dict(got, seeds=swapped)) == 1


def _rehearse(monkeypatch, seconds=0.3):
  """The rest of a run after the look for a chip, on the CPU."""
  cell = tiny_cell()
  monkeypatch.setattr(run, 'load_cell', lambda name: cell)
  return run.run_cell('tiny', 3_100_000_019, seconds, False)


def test_rehearsal_of_a_run_comes_out_correct(monkeypatch):
  line = _rehearse(monkeypatch)
  assert line['correct'] is True, line['compared']
  assert line['attempted'] > 3 and line['failed'] == 0
  assert set(line['metrics']) == {'seeds_per_s', 'step_p90_ms', 'setup_s'}
  zero = {'sample_violations', 'subgraph_violations', 'label_violations',
          'pool_violations', 'negative_violations', 'counter_gap',
          'edges_dropped', 'compilations'}
  assert set(line['compared']) == zero | {'loss_gap', 'grad_gap',
                                         'change_gap'}
  for name in zero:
    assert line['compared'][name] == {'value': 0, 'limit': 0}, name
  assert 'symmetrise_s' in line['setup_parts']


def _unchanged(call):
  return lambda self, params, opt, pairs, n_valid, keys: (
      params, opt, call(self, params, opt, pairs, n_valid, keys)[2])


def _half_batch(call):
  return lambda self, params, opt, pairs, n_valid, keys: call(
      self, params, opt, pairs, n_valid // 2, keys)


@pytest.mark.parametrize('fault', ['unchanged', 'half_batch',
                                   'too_few_probes'])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, fault):
  from glt_tpu.parallel import train
  if fault == 'too_few_probes':
    make = seal_fused.make_spec
    monkeypatch.setattr(
        seal_fused, 'make_spec',
        lambda cfg, traffic: make(cfg, traffic)._replace(tile_budget=2,
                                                         hub_pairs=3))
  else:
    call = train.SPMDSageTrainStep.__call__
    monkeypatch.setattr(
        train.SPMDSageTrainStep, '__call__',
        {'unchanged': _unchanged, 'half_batch': _half_batch}[fault](call))
  line = _rehearse(monkeypatch)
  assert line['correct'] is False, line['compared']
  if fault == 'too_few_probes':   # counted, and the blocks lack edges
    assert line['compared']['edges_dropped']['value'] > 0
    assert line['compared']['subgraph_violations']['value'] > 0


def test_a_program_without_enclosing_subgraphs_fails_at_once(monkeypatch):
  """The parent's step has no ``enclose``: ``build`` exits before it
  makes the graph."""
  from glt_tpu.parallel import train
  init = train.SPMDSageTrainStep.__init__
  monkeypatch.setattr(
      train.SPMDSageTrainStep, '__init__',
      lambda self, mesh, model, tx, graph, feature, labels, fanouts,
      batch_size_per_device, neg_sampling=None: init(
          self, mesh, model, tx, graph, feature, labels, fanouts,
          batch_size_per_device, neg_sampling=neg_sampling))
  monkeypatch.setattr(graphgen, 'csr', lambda *a: pytest.fail('built'))
  _, _, cfg, traffic = tiny_cell()
  with pytest.raises(SystemExit, match='takes no enclose'):
    seal_fused.build(cfg, traffic, 1, 5)


def test_scope_window_inputs_and_the_readers_on_a_canned_profile(built):
  """The profile's inputs have the tree, shapes and types of the driver's
  own, so the step's compiled program serves them; the readers sum what
  lies at or under their scope paths."""
  s, cfg, traffic = built
  before = seal_fused.compilations(s)
  params, opt, batches = seal_scope_window.inputs(s.trainer, cfg, traffic,
                                                  1, steps=2)
  shape = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
  assert shape(params) == shape(s.params)
  assert shape(opt) == shape(s.opt)
  for pairs, n_valid, keys in batches:
    assert pairs.shape == s.pairs[0].shape and pairs.dtype == np.int32
    assert reference_link.is_edge(*s.directed, pairs[:, 0],
                                  pairs[:, 1]).all()
    assert (pairs[:, 0] != pairs[:, 1]).all()
    params, opt, loss = s.trainer(params, opt, pairs, n_valid, keys)
  assert np.isfinite(np.asarray(loss)).all()
  assert seal_fused.compilations(s) == before
  run_ = {'cfg': cfg, 'traffic': traffic, 'device_kind': 'TPU v5 lite',
          'trace': {'steps': 10, 'top_window_s': 0.5, 'top_busy_s': 0.4}}
  seal_scope_window._PROFILE[:] = [
      {'stages': {'sampler/negative': 1.5, 'sampler/enclose/dedup': 1.0,
                  'sampler/enclose/induce': 4.0,
                  'sampler/enclose/induce/hub_pairs': 2.0,
                  'sampler/enclose/drnl': 0.5,
                  'sampler/enclose/sample_hop0': 3.0,
                  'model_step/forward/DGCNN/sort_pool': 0.25,
                  'model_step/forward/DGCNN/sort_pool/bwd': 0.5,
                  'model_step/forward/DGCNN/gcn0': 1.0},
       'layers': {'sampler': 13.0, 'model_step': 2.0,
                  'feature_store': 1.25},
       'unscoped_ms': 1.0, 'mixed_ms': 1.0, 'busy_ms': 20.0}]
  counter_window._TAKEN[:] = [
      {'subgraph_nodes': {'occupancy_pct': 31.5},
       'links_capped': {'occupancy_pct': 18.0}}]
  try:
    layer = lambda name: importlib.import_module(
        'chipbench.layers.' + name).read(run_)
    assert layer('seal_sampler_device_ms') == 13.0
    assert layer('seal_enclose_device_ms') == 7.0
    assert layer('seal_drnl_device_ms') == 0.5
    assert layer('seal_negative_device_ms') == 1.5
    assert layer('seal_feature_device_ms') == 1.25
    assert layer('seal_model_device_ms') == 2.0
    assert layer('seal_pool_device_ms') == 0.75
    assert layer('seal_scope_unattributed_pct') == 10.0
    assert layer('seal_node_slot_occupancy_pct') == 31.5
    assert layer('seal_links_capped_pct') == 18.0
    need = flops_seal.step_flops(cfg, traffic)
    assert layer('seal_step_mfu_pct') == pytest.approx(
        100.0 * need * 20 / 197e12)
    least, _ = flops_seal.least_step_seconds(
        cfg, traffic, {'flops_per_s': 197e12, 'bytes_per_s': 819e9})
    assert layer('seal_step_roofline') == pytest.approx(
        100.0 * least / 0.04)
    # against a program without such a step the readers say nothing
    seal_scope_window._PROFILE[:] = [None]
    counter_window._TAKEN[:] = [None]
    for name in SCOPED + NEW[10:]:
      assert layer(name) is None
  finally:
    seal_scope_window._PROFILE.clear()
    counter_window._TAKEN.clear()


def test_no_live_enclosing_step_means_no_scope_metric(capsys, monkeypatch):
  """Beside no trainer at all the window finds no program."""
  import weakref
  from glt_tpu.obs import device
  monkeypatch.setattr(device, '_LIVE', weakref.WeakSet())
  assert seal_scope_window._take({'cfg': {}, 'traffic': {}, 'chips': 1,
                                  'trace': {}}) is None
  assert ('0 live enclosing-subgraph step programs, not one'
          in capsys.readouterr().err)


def test_the_references_two_copies_are_one_text():
  with open(os.path.join(REPO, 'chipbench', 'reference_seal.py')) as f:
    ours = f.read()
  with open(os.path.join(REPO, 'glt_tpu', 'models', 'reference',
                         'seal.py')) as f:
    assert ours == f.read()
  assert json.load(open(os.path.join(
      REPO, 'chipbench', 'traffic', 'seal-fused.json')))['driver'] == \
      'seal_fused'
