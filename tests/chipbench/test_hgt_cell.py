"""The HGT cell's own tests: its manifest entries resolve to files, the
configuration holds what it names, the yardstick's arithmetic on a
hand-worked case, the reference's two copies, the driver's weights and the
scope window's inputs against the model's own tree, and a rehearsal of a
run on the CPU at a size it holds, right and with a fault planted."""
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

from chipbench import flops_hgt, flops_rgat, hgt_scope_window, run

CELL = 'hgt-igbh-c1.fused'
TWIN = 'rgat-igbh-c1.fused'
NEW = ('hgt_sampler_device_ms', 'hgt_feature_device_ms',
       'hgt_model_device_ms', 'hgt_attention_device_ms', 'hgt_step_mfu_pct',
       'hgt_step_roofline', 'hgt_scope_unattributed_pct')


def tiny_cell():
  """The cell at a size the CPU holds: every type and relation, the
  widths cut (a test's own cut, not the configuration's)."""
  m, cell, cfg, traffic = run.load_cell(CELL)
  nodes = {'paper': 3000, 'author': 3100, 'institute': 5, 'fos': 40,
           'journal': 6, 'conference': 2}
  rels = [dict(r, num_edges=max(nodes[r['src']], nodes[r['dst']]) * 3)
          for r in cfg['relations']]
  cfg = dict(cfg, num_nodes=nodes, relations=rels, feature_dim=16,
             hidden_dim=16, heads=2, num_classes=7,
             limits={'loss_gap': 1e-3, 'grad_gap': 1e-2,
                     'change_gap': 1e-2})
  traffic = dict(traffic, batch_per_chip=4, fanout=[3, 2, 2])
  return m, cell, cfg, traffic


@pytest.fixture
def tpu_sampler(monkeypatch):
  """The sampler's engines as ``auto`` resolves them on a TPU."""
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')


def test_the_new_entries_resolve_to_files():
  m, cell, cfg, traffic = run.load_cell(CELL)
  assert cell == {'name': CELL, 'config': 'hgt-igbh-c1',
                  'traffic': 'hgt-fused', 'chips': 1, 'why': cell['why']}
  # there, not where: a later cell's entries go behind these
  config = {c['name']: c for c in m['configs']}['hgt-igbh-c1']
  assert len(config['source']) <= 200 and len(cell['why']) <= 200
  assert config['source'] == cfg['source']
  assert config['reduced'] == cfg['reduced'] == ['num_nodes', 'num_edges']
  assert os.path.exists(os.path.join(REPO, config['file']))
  assert traffic['driver'] == 'hgt_fused'
  _, _, _, twin = run.load_cell(TWIN)
  assert {k: v for k, v in traffic.items() if k != 'driver'} == {
      k: v for k, v in twin.items() if k != 'driver'}
  by_name = {p['name']: p for p in m['per_layer']}
  assert set(NEW) <= set(by_name)
  for name in NEW:
    assert by_name[name]['workloads'] == [CELL]
    assert by_name[name]['moves'] == 'seeds_per_s'
    assert callable(importlib.import_module(
        'chipbench.layers.' + name).read)
  # no other reader lists the cell; the two with no list read any cell
  for p in m['per_layer']:
    assert p['name'] in NEW or CELL not in p.get('workloads', [])
  assert 'workloads' not in by_name['host_ms_per_step']
  assert 'workloads' not in by_name['device_idle_pct']
  driver = importlib.import_module('chipbench.drivers.hgt_fused')
  assert all(callable(getattr(driver, f)) for f in ('build', 'step',
                                                    'verify'))


def test_the_configuration_holds_what_it_names():
  _, _, cfg, _ = run.load_cell(CELL)
  _, _, twin, _ = run.load_cell(TWIN)
  assert (cfg['feature_dim'], cfg['hidden_dim'], cfg['heads'],
          cfg['num_layers'], cfg['num_classes']) == (1024, 256, 8, 3, 2983)
  assert cfg['model'] == 'hgt' and cfg['dropout'] == 0.0
  assert (cfg['dtype'], cfg['matmul_precision'], cfg['feature_dtype']) == (
      'float32', 'default', 'bfloat16')
  # the graph is the R-GAT configuration's, number for number
  for k in ('published', 'num_nodes', 'relations', 'num_edges',
            'feature_dim', 'feature_dtype', 'num_classes', 'learning_rate',
            'deployment'):
    assert cfg[k] == twin[k], k
  for k in ('graph', 'widths', 'input_and_skip', 'fanout_and_batch'):
    assert cfg['assumed'][k]
  assert '64' in cfg['assumed']['widths'] and '256' in cfg['assumed'][
      'widths']
  assert set(cfg['limits']) == {'loss_gap', 'grad_gap', 'change_gap'}
  assert all(0 < v < 1 for v in cfg['limits'].values())
  assert set(cfg['limits']) < set(cfg['limits_why'])


def test_flops_and_bytes_on_a_hand_worked_case():
  cfg = {'num_nodes': {'a': 0, 'b': 0},
         'relations': [{'name': 'aa', 'src': 'a', 'dst': 'a'},
                       {'name': 'ab', 'src': 'a', 'dst': 'b'},
                       {'name': 'ba', 'src': 'b', 'dst': 'a'}],
         'feature_dim': 8, 'feature_dtype': 'bfloat16', 'hidden_dim': 4,
         'heads': 2, 'num_layers': 2, 'num_classes': 3}
  args = (cfg, 2, [3, 2], 'a')
  # test_rgat_cell's case: budgets a 32, b 18; edge slots 48 (18 + 18 +
  # 12), 12 within one hop; rows needed (8 + 6) and (2 + 0)
  slots = 50
  inputs = slots * 2 * 8 * 4 * 2
  per_edge = 2 * (2 * 4 * 4 + 2 * 4 * 2) + 6 * 4     # d = 2
  per_row = 2 * 2 * 4 * 4
  layer1 = 3 * (48 * per_edge + 14 * per_row)
  layer2 = 3 * (12 * per_edge + 2 * per_row)
  assert flops_hgt.step_flops(*args) == (inputs + layer1 + layer2
                                         + 3 * 2 * 2 * 4 * 3)
  params = (2 * (8 * 4 + 4) + 2 * (2 * (4 * (16 + 4) + 1)
                                   + 3 * (2 * 2 * 2 * 2 + 2)) + 4 * 3 + 3)
  assert flops_hgt.num_params(cfg) == params
  assert flops_hgt.step_bytes(*args) == (
      50 * 8 * 2 * 2 + (50 + 14 + 2) * 4 * 4 * 2 + params * 24)
  least, bound = flops_hgt.least_step_seconds(
      *args, {'flops_per_s': 1e3, 'bytes_per_s': 1e9})
  assert bound == 'flops' and least == flops_hgt.step_flops(*args) / 1e3


def test_the_cells_budgets_are_the_twins_and_the_count_is_the_models():
  _, _, cfg, traffic = run.load_cell(CELL)
  _, _, twin, _ = run.load_cell(TWIN)
  args = (traffic['batch_per_chip'], traffic['fanout'],
          traffic['seed_type'])
  assert flops_rgat.budget_rows(cfg, *args) == flops_rgat.budget_rows(
      twin, *args)
  assert sum(flops_rgat.budget_rows(cfg, *args).values()) == 1588864
  assert sum(flops_rgat.edge_slots(cfg, *args).values()) == 1588800
  from chipbench.drivers import hgt_fused
  flow = hgt_fused.relations(cfg)[1]
  assert flops_hgt.num_params(cfg) == hgt_fused.num_weights(cfg, flow)
  # a share of a peak cannot pass 100 %: the least step is far under what
  # the issue expects a step to take (210 to 390 ms)
  from chipbench import peaks
  least, _ = flops_hgt.least_step_seconds(cfg, *args,
                                          peaks.peaks('TPU v5e'))
  assert 0.005 < least < 0.05


def test_the_references_two_copies_are_one_text():
  with open(os.path.join(REPO, 'chipbench', 'reference_hgt.py')) as f:
    ours = f.read()
  with open(os.path.join(REPO, 'glt_tpu', 'models', 'reference',
                         'hgt.py')) as f:
    theirs = f.read()
  assert ours == theirs
  assert 'glt_tpu' not in [line.split()[1].split('.')[0]
                           for line in ours.splitlines()
                           if line.startswith(('import ', 'from '))]


def test_the_drivers_weights_are_the_models_tree():
  """``hgt_fused.weights`` and the scope window's parameters have the
  tree, the shapes and the types of ``HGT.init`` on the step's own dummy
  batch, so the step's compiled program serves them."""
  from chipbench.drivers import hgt_fused
  _, _, cfg, traffic = tiny_cell()
  s = hgt_fused.build(cfg, traffic, 1, 5)
  shape = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
  want = shape(jax.eval_shape(s.trainer.init_params, jax.random.key(0)))
  assert shape(s.params) == want
  assert shape(hgt_fused.weights(6, cfg, s.flow)) == want
  leaves = jax.tree.leaves(s.params0)
  assert all(np.isfinite(a).all() for a in leaves)
  skip = s.params0['params']['layer0']['skip_paper']
  assert 0.5 < float(skip) < 1.5


def _rehearse(monkeypatch, seconds=0.3):
  """The rest of a run after the look for a chip, on the CPU."""
  cell = tiny_cell()
  monkeypatch.setattr(run, 'load_cell', lambda name: cell)
  return run.run_cell('tiny', 3_300_000_019, seconds, False)


def test_rehearsal_of_a_run_comes_out_correct(monkeypatch, tpu_sampler):
  line = _rehearse(monkeypatch)
  assert line['correct'] is True, line['compared']
  assert line['attempted'] > 3 and line['failed'] == 0
  assert set(line['metrics']) == {'seeds_per_s', 'step_p90_ms', 'setup_s'}
  assert set(line['compared']) == {'loss_gap', 'grad_gap', 'change_gap',
                                   'sample_violations', 'compilations'}
  assert line['compared']['compilations'] == {'value': 0, 'limit': 0}
  assert line['compared']['sample_violations'] == {'value': 0, 'limit': 0}
  # on the CPU the default precision rounds nothing: the gaps are rounding
  assert line['compared']['grad_gap']['value'] < 1e-4


def _unchanged(call):
  return lambda self, params, opt, seeds, n_valid, key: (
      params, opt, call(self, params, opt, seeds, n_valid, key)[2])


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


def test_a_softmax_a_relation_comes_out_not_correct(monkeypatch,
                                                    tpu_sampler):
  """The fault this model is most likely to be given by shared code:
  each relation normalised alone. Planted in the program's place (the
  reference with the fault, held against the reference without)."""
  from chipbench import reference_hgt
  from chipbench.drivers import hgt_fused
  _, _, cfg, traffic = tiny_cell()
  s = hgt_fused.build(cfg, traffic, 1, 5)
  ref = hgt_fused.follow(s)
  assert max(reference_hgt.compare(s.program, ref).values()) < 1e-3
  bad = hgt_fused.follow(s, fault='per_relation_softmax')
  gaps = reference_hgt.compare(bad, ref)
  assert any(gaps[k] > cfg['limits'][k] for k in gaps), gaps


def test_scope_window_inputs_are_the_windows_shapes(tpu_sampler):
  """The profile's inputs have the tree, shapes and types of the
  driver's own, so the step's compiled program serves them."""
  from chipbench.drivers import hgt_fused
  _, cell, cfg, traffic = tiny_cell()
  s = hgt_fused.build(cfg, traffic, 1, 5)
  before = hgt_fused.compilations(s)
  params, opt, batches = hgt_scope_window.inputs(s.trainer, cfg, traffic,
                                                 steps=2)
  shape = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
  assert shape(params) == shape(s.params)
  assert shape(opt) == shape(s.opt)
  for seeds, n_valid, key in batches:
    params, opt, loss = s.trainer(params, opt, seeds, n_valid, key)
  assert np.isfinite(np.asarray(loss)).all()
  assert hgt_fused.compilations(s) == before
  # against a program without the typed scopes the readers say nothing
  run_ = {'cfg': cfg, 'traffic': traffic, 'trace': {}}
  hgt_scope_window._PROFILE[:] = [None]
  try:
    for name in NEW[:4] + NEW[6:]:
      assert importlib.import_module(
          'chipbench.layers.' + name).read(run_) is None
  finally:
    hgt_scope_window._PROFILE.clear()


def test_the_attention_reader_sums_the_models_own_stages():
  found = {'stages': {
      'model_step/forward/HGT/layer0/rel_a__r__b/transform': 1.0,
      'model_step/forward/HGT/layer0/rel_a__r__b/attention': 2.0,
      'model_step/forward/HGT/layer0/softmax/b': 4.0,
      'model_step/forward/HGT/layer0/aggregate/b': 8.0,
      'model_step/forward/HGT/layer0/kqv/b': 16.0,
      'model_step/forward/HGT/in_b': 32.0, 'model_step/update': 64.0,
      'sampler/dedup0/b': 128.0}, 'layers': {'model_step': 127.0}}
  hgt_scope_window._PROFILE[:] = [found]
  try:
    reader = importlib.import_module(
        'chipbench.layers.hgt_attention_device_ms')
    assert reader.read({}) == 15.0
    assert importlib.import_module(
        'chipbench.layers.hgt_model_device_ms').read({}) == 127.0
  finally:
    hgt_scope_window._PROFILE.clear()
