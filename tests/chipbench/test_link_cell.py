"""The link cell's own tests: its manifest entries resolve to files, the
link yardstick's arithmetic on a hand-worked case, the budgets the issue
states, the driver's positive edges, and a rehearsal of a run on the CPU
at a size it holds, right and with a fault planted."""
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

from chipbench import (flops, flops_link, graphgen, link_scope_window,
                       reference_link, run)
from chipbench.drivers import link_fused

CELL = 'link-papers100m-c1.fused'
NEW = ('link_sampler_device_ms', 'link_negative_device_ms',
       'link_feature_device_ms', 'link_model_device_ms',
       'link_step_mfu_pct', 'link_step_roofline',
       'link_scope_unattributed_pct')
SAGE_ONLY = ('step_mfu_pct', 'step_roofline', 'sampler_device_ms',
             'feature_device_ms', 'model_device_ms',
             'scope_unattributed_pct')


def tiny_cell():
  """The cell at a size the CPU holds (a test's own cut, not the
  configuration's), with the real cell's limits."""
  m, cell, cfg, traffic = run.load_cell(CELL)
  cfg = dict(cfg, num_nodes=20000, num_edges=291000, feature_dim=16,
             hidden_dim=32, out_dim=16)
  traffic = dict(traffic, batch_per_chip=8, endpoint_seeds_per_chip=32,
                 fanout=[4, 3, 2])
  return m, cell, cfg, traffic


@pytest.fixture
def tpu_sampler(monkeypatch):
  """The sampler's engines as ``auto`` resolves them on a TPU."""
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')


def test_the_new_entries_resolve_to_files():
  m, cell, cfg, traffic = run.load_cell(CELL)
  assert cell == {'name': CELL, 'config': 'sage-link-papers100m-c1',
                  'traffic': 'link-fused', 'chips': 1, 'why': cell['why']}
  assert m['workloads'][-1] == cell and len(cell['why']) <= 200
  config = m['configs'][-1]
  assert config['name'] == cfg['name'] == 'sage-link-papers100m-c1'
  assert config['source'] == cfg['source'] and len(config['source']) <= 200
  assert config['reduced'] == cfg['reduced'] == ['num_nodes', 'num_edges']
  assert os.path.exists(os.path.join(REPO, config['file']))
  assert traffic['driver'] == 'link_fused'
  assert (traffic['batch_per_chip'], traffic['endpoint_seeds_per_chip'],
          traffic['fanout'], traffic['dispatch'], traffic['run_ahead'],
          traffic['warmup_steps'], traffic['step_program']) == (
              256, 1024, [15, 10, 5], 'per_batch', 1, 3, 'jit_step')
  neg = traffic['negatives']
  assert (neg['mode'], neg['amount'], neg['strict'], neg['trials'],
          neg['padding']) == ('binary', 1, True, 5, True)
  by_name = {p['name']: p for p in m['per_layer']}
  assert tuple(p['name'] for p in m['per_layer'][-len(NEW):]) == NEW
  for name in NEW:
    assert by_name[name]['workloads'] == [CELL]
    assert by_name[name]['moves'] == 'seeds_per_s'
    assert callable(importlib.import_module(
        'chipbench.layers.' + name).read)
  for name in SAGE_ONLY:   # the accepted readers leave the new cell out
    assert by_name[name]['workloads'] == ['papers100m-c1.fused',
                                          'papers100m-c4.fused']
  assert 'workloads' not in by_name['host_ms_per_step']
  assert 'workloads' not in by_name['device_idle_pct']


def test_the_configuration_is_c1s_graph_at_the_recipes_widths():
  _, _, cfg, _ = run.load_cell(CELL)
  _, _, c1, c1_traffic = run.load_cell('papers100m-c1.fused')
  for k in ('num_nodes', 'num_edges', 'feature_dim', 'hidden_dim',
            'num_layers', 'aggregation', 'optimizer', 'learning_rate',
            'dtype', 'matmul_precision', 'published'):
    assert cfg[k] == c1[k], k
  assert (cfg['hidden_dim'], cfg['out_dim']) == (256, 64)
  assert 'num_classes' not in cfg
  assert set(cfg['limits']) == {'loss_gap', 'grad_gap', 'change_gap'}
  for why in cfg['assumed'].values():
    assert len(why) > 20
  # the static budgets equal c1's: the hop loop is seeded with 4 x 256
  _, _, _, traffic = run.load_cell(CELL)
  seeds = flops_link.endpoint_seeds(traffic['batch_per_chip'])
  assert seeds == c1_traffic['batch_per_chip'] == 1024
  assert flops.budget_rows(seeds, traffic['fanout']) == 937984
  assert sum(flops.hop_slots(seeds, traffic['fanout'])[1:]) == 936960
  assert flops.rows_needed(seeds, traffic['fanout']) == [169984, 16384,
                                                         1024]


def test_flops_and_bytes_on_a_hand_worked_case():
  cfg = {'feature_dim': 8, 'hidden_dim': 4, 'out_dim': 6, 'num_layers': 2}
  batch, fanout = 2, [3, 2]
  # 8 endpoint seeds; rows: layer 1 8 + 24 = 32, layer 2 8
  fwd = 32 * 2 * 8 * 4 * 2 + 8 * 2 * 4 * 6 * 2
  loss = 4 * 2 * 6                 # 4 pairs, a dot product of 6
  assert flops_link.loss_flops(cfg, batch) == loss
  assert flops_link.step_flops(cfg, batch, fanout) == 3 * fwd + 3 * loss
  params = 2 * 8 * 4 + 4 + 2 * 4 * 6 + 6
  assert flops_link.step_bytes(cfg, batch, fanout) == (
      (8 + 24 + 48) * 8 * 4 * 2 + (32 * 4 + 8 * 6) * 4 * 2
      + params * 4 * 3 * 2 + 8 * 6 * 4 * 2)
  least, bound = flops_link.least_step_seconds(
      cfg, batch, fanout, {'flops_per_s': 1e3, 'bytes_per_s': 1e9})
  assert bound == 'flops'
  assert least == flops_link.step_flops(cfg, batch, fanout) / 1e3
  _, _, real, traffic = run.load_cell(CELL)
  least, bound = flops_link.least_step_seconds(
      real, traffic['batch_per_chip'], traffic['fanout'],
      {'flops_per_s': 197e12, 'bytes_per_s': 819e9})
  assert bound == 'bytes' and 1.5e-3 < least < 1.8e-3


def test_positive_edges_are_edges_drawn_once():
  indptr, indices = graphgen.csr(20000, 291000, 7)
  a = link_fused.positive_edges(indptr, indices,
                                np.random.default_rng([7, 4]), 4096)
  b = link_fused.positive_edges(indptr, indices,
                                np.random.default_rng([7, 4]), 4096)
  assert a.dtype == np.int32 and a.shape == (4096, 2)
  assert np.array_equal(a, b)
  assert reference_link.is_edge(indptr, indices, a[:, 0], a[:, 1]).all()
  # by edge: sources lean to high out-degree, destinations to low ids
  deg = np.diff(indptr)
  assert deg[a[:, 0]].mean() > 1.5 * deg.mean()
  assert np.median(a[:, 1]) < 0.35 * 20000


def _rehearse(monkeypatch, seconds=0.3):
  """The rest of a run after the look for a chip, on the CPU."""
  cell = tiny_cell()
  monkeypatch.setattr(run, 'load_cell', lambda name: cell)
  return run.run_cell('tiny', 3_100_000_019, seconds, False)


def test_rehearsal_of_a_run_comes_out_correct(monkeypatch, tpu_sampler):
  line = _rehearse(monkeypatch)
  assert line['correct'] is True, line['compared']
  assert line['attempted'] > 3 and line['failed'] == 0
  assert set(line['metrics']) == {'seeds_per_s', 'step_p90_ms', 'setup_s'}
  assert set(line['compared']) == {
      'loss_gap', 'grad_gap', 'change_gap', 'negative_violations',
      'counter_gap', 'compilations'}
  for name in ('negative_violations', 'counter_gap', 'compilations'):
    assert line['compared'][name] == {'value': 0, 'limit': 0}, name


def _unchanged(call):
  return lambda self, params, opt, pairs, n_valid, keys: (
      params, opt, call(self, params, opt, pairs, n_valid, keys)[2])


def _half_batch(call):
  return lambda self, params, opt, pairs, n_valid, keys: call(
      self, params, opt, pairs, n_valid // 2, keys)


def _other_key(call):
  return lambda self, params, opt, pairs, n_valid, keys: call(
      self, params, opt, pairs, n_valid, keys[::-1] if len(keys) > 1
      else jax.random.split(keys[0], 1))


@pytest.mark.parametrize('fault', ['unchanged', 'half_batch', 'other_key'])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch,
                                                   tpu_sampler, fault):
  from glt_tpu.parallel import train
  call = train.SPMDSageTrainStep.__call__
  monkeypatch.setattr(
      train.SPMDSageTrainStep, '__call__',
      {'unchanged': _unchanged, 'half_batch': _half_batch,
       'other_key': _other_key}[fault](call))
  line = _rehearse(monkeypatch)
  assert line['correct'] is False, line['compared']
  if fault == 'other_key':   # other negatives than the reference drew
    assert line['compared']['counter_gap']['value'] > 0


def test_a_negative_that_is_an_edge_is_counted(tpu_sampler):
  _, _, cfg, traffic = tiny_cell()
  s = link_fused.build(cfg, traffic, 1, 5)
  good = s.counted[0]
  seeds = good['seeds'][0]
  assert reference_link.pair_violations(
      s.indptr, s.indices, seeds, good['negatives_padded'][0]) == 0
  batch = traffic['batch_per_chip']
  moved = seeds.copy()
  moved[batch], moved[3 * batch] = seeds[0], seeds[2 * batch]
  assert reference_link.pair_violations(
      s.indptr, s.indices, moved, good['negatives_padded'][0]) == 1
  moved = seeds.copy()
  moved[0] = (seeds[0] + 1) % cfg['num_nodes']   # hardly an edge now
  assert reference_link.pair_violations(
      s.indptr, s.indices, moved, good['negatives_padded'][0]) >= 1


def test_a_program_without_edge_seeds_fails_at_once(monkeypatch):
  """The parent's step has no ``neg_sampling``: ``build`` exits before
  it makes the graph."""
  from glt_tpu.parallel import train
  init = train.SPMDSageTrainStep.__init__
  monkeypatch.setattr(
      train.SPMDSageTrainStep, '__init__',
      lambda self, mesh, model, tx, graph, feature, labels, fanouts,
      batch_size_per_device: init(self, mesh, model, tx, graph, feature,
                                  labels, fanouts, batch_size_per_device))
  monkeypatch.setattr(graphgen, 'csr', lambda *a: pytest.fail('built'))
  _, _, cfg, traffic = tiny_cell()
  with pytest.raises(SystemExit, match='takes no neg_sampling'):
    link_fused.build(cfg, traffic, 1, 5)


def test_scope_window_inputs_are_the_windows_shapes(tpu_sampler):
  """The profile's inputs have the tree, shapes and types of the
  driver's own, so the step's compiled program serves them."""
  _, _, cfg, traffic = tiny_cell()
  s = link_fused.build(cfg, traffic, 1, 5)
  before = link_fused.compilations(s)
  params, opt, batches = link_scope_window.inputs(s.trainer, cfg, traffic,
                                                  1, steps=2)
  shape = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
  assert shape(params) == shape(s.params)
  assert shape(opt) == shape(s.opt)
  for pairs, n_valid, keys in batches:
    assert pairs.shape == s.pairs[0].shape and pairs.dtype == np.int32
    assert reference_link.is_edge(s.indptr, s.indices, pairs[:, 0],
                                  pairs[:, 1]).all()
    params, opt, loss = s.trainer(params, opt, pairs, n_valid, keys)
  assert np.isfinite(np.asarray(loss)).all()
  assert link_fused.compilations(s) == before
  # the stage reader sums what lies at or under a scope path
  run_ = {'cfg': cfg, 'traffic': traffic, 'trace': {}}
  link_scope_window._PROFILE[:] = [
      {'stages': {'sampler/negative': 1.5, 'sampler/negative/x': 0.5,
                  'sampler/negatives': 9.0, 'sampler/dedup0': 2.0},
       'layers': {'sampler': 13.0}, 'unscoped_ms': 1.0, 'mixed_ms': 1.0,
       'busy_ms': 20.0}]
  try:
    layer = lambda name: importlib.import_module(
        'chipbench.layers.' + name).read(run_)
    assert layer('link_negative_device_ms') == 2.0
    assert layer('link_sampler_device_ms') == 13.0
    assert layer('link_feature_device_ms') is None
    assert layer('link_scope_unattributed_pct') == 10.0
    # against a program without a link step the readers say nothing
    link_scope_window._PROFILE[:] = [None]
    for name in NEW[:4] + NEW[6:]:
      assert layer(name) is None
  finally:
    link_scope_window._PROFILE.clear()


def test_no_live_link_step_means_no_scope_metric(tpu_sampler, capsys,
                                                 monkeypatch):
  """Beside a node-seeded trainer alone the window finds no program."""
  import weakref
  from glt_tpu.obs import device
  sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
  from chipbench.drivers import fused
  from test_chipbench import tiny_cell as node_cell
  _, _, cfg, traffic = node_cell(1)
  s = fused.build(cfg, traffic, 1, 5)
  monkeypatch.setattr(device, '_LIVE', weakref.WeakSet([s.trainer]))
  assert link_scope_window._take({'cfg': cfg, 'traffic': traffic,
                                  'chips': 1, 'trace': {}}) is None
  assert '0 live link step programs, not one' in capsys.readouterr().err
