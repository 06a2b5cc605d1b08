"""The typed cell's own tests: its manifest entries resolve to files, the
R-GAT yardstick's arithmetic on a hand-worked case, the typed generator,
the reference's two copies, and a rehearsal of a run on the CPU at a size
it holds, right and with a fault planted."""
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

from chipbench import (flops_rgat, graphgen_hetero, hetero_scope_window,
                       reference_rgat, run)

CELL = 'rgat-igbh-c1.fused'
NEW = ('rgat_step_mfu_pct', 'rgat_step_roofline', 'rgat_sampler_device_ms',
       'rgat_feature_device_ms', 'rgat_model_device_ms',
       'rgat_attention_device_ms', 'rgat_scope_unattributed_pct')
SAGE_ONLY = ('step_mfu_pct', 'step_roofline', 'sampler_device_ms',
             'feature_device_ms', 'model_device_ms',
             'scope_unattributed_pct')


def tiny_cell():
  """The cell at a size the CPU holds: every type and relation, the
  widths cut (a test's own cut, not the configuration's)."""
  m, cell, cfg, traffic = run.load_cell(CELL)
  nodes = {'paper': 3000, 'author': 3100, 'institute': 5, 'fos': 40,
           'journal': 6, 'conference': 2}
  rels = [dict(r, num_edges=max(nodes[r['src']], nodes[r['dst']]) * 3)
          for r in cfg['relations']]
  cfg = dict(cfg, num_nodes=nodes, relations=rels, feature_dim=16,
             hidden_dim=16, heads=2, num_classes=7)
  traffic = dict(traffic, batch_per_chip=4, fanout=[3, 2, 2])
  return m, cell, cfg, traffic


@pytest.fixture
def tpu_sampler(monkeypatch):
  """The sampler's engines as ``auto`` resolves them on a TPU."""
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')


def test_the_new_entries_resolve_to_files():
  m, cell, cfg, traffic = run.load_cell(CELL)
  assert cell == {'name': CELL, 'config': 'rgat-igbh-c1',
                  'traffic': 'hetero-fused', 'chips': 1,
                  'why': cell['why']}
  assert traffic['driver'] == 'hetero_fused' and traffic[
      'batch_per_chip'] == 64 and traffic['fanout'] == [15, 10, 5]
  by_name = {p['name']: p for p in m['per_layer']}
  for name in NEW:
    assert by_name[name]['workloads'] == [CELL]
    assert by_name[name]['moves'] == 'seeds_per_s'
    assert callable(importlib.import_module(
        'chipbench.layers.' + name).read)
  for name in SAGE_ONLY:
    assert by_name[name]['workloads'] == ['papers100m-c1.fused',
                                          'papers100m-c4.fused']
  # the two readers with no list read any cell's window and trace
  assert 'workloads' not in by_name['host_ms_per_step']
  assert 'workloads' not in by_name['device_idle_pct']


def test_the_configuration_holds_the_published_widths():
  _, _, cfg, _ = run.load_cell(CELL)
  assert (cfg['feature_dim'], cfg['hidden_dim'], cfg['heads'],
          cfg['num_layers'], cfg['num_classes']) == (1024, 512, 4, 3, 2983)
  assert cfg['feature_dtype'] == 'bfloat16' and cfg['dtype'] == 'float32'
  assert len(cfg['num_nodes']) == 6 and len(cfg['relations']) == 11
  assert cfg['reduced'] == ['num_nodes', 'num_edges']
  pub = cfg['published']
  factor = pub['num_nodes']['paper'] / cfg['num_nodes']['paper']
  for t, n in cfg['num_nodes'].items():   # one factor for every type
    assert abs(n - pub['num_nodes'][t] / factor) <= 4, t
  for r in cfg['relations']:              # mean out-degrees stay
    name = r.get('reverse_of', r['name'])
    fwd = next(x for x in cfg['relations'] if x['name'] == name)
    want = pub['num_edges'][name] / pub['num_nodes'][fwd['src']]
    assert r['num_edges'] / cfg['num_nodes'][fwd['src']] == pytest.approx(
        want, rel=1e-5), r
  assert cfg['num_edges'] == sum(r['num_edges'] for r in cfg['relations'])


def test_flops_and_bytes_on_a_hand_worked_case():
  cfg = {'num_nodes': {'a': 0, 'b': 0},
         'relations': [{'name': 'aa', 'src': 'a', 'dst': 'a'},
                       {'name': 'ab', 'src': 'a', 'dst': 'b'},
                       {'name': 'ba', 'src': 'b', 'dst': 'a'}],
         'feature_dim': 8, 'feature_dtype': 'bfloat16', 'hidden_dim': 4,
         'heads': 2, 'num_layers': 2, 'num_classes': 3}
  args = (cfg, 2, [3, 2], 'a')
  # frontiers: a = 2, 6, 12 + 12; b = 0, 6, 12
  assert flops_rgat.frontiers(*args) == [
      {'a': 2, 'b': 0}, {'a': 6, 'b': 6}, {'a': 24, 'b': 12}]
  assert flops_rgat.budget_rows(*args) == {'a': 32, 'b': 18}
  assert flops_rgat.edge_slots(*args) == {'aa': 18, 'ab': 18, 'ba': 12}
  assert flops_rgat.edge_slots(*args, hops=1) == {'aa': 6, 'ab': 6,
                                                  'ba': 0}
  assert flops_rgat.rows_needed(*args) == [{'a': 8, 'b': 6},
                                           {'a': 2, 'b': 0}]
  # layer 1: projections 48 edges x 2*8*4 = 3072, once more backward;
  # logits of parents (8 + 8 + 6 rows) x 2*8*2 = 704 and 48 x 6*4 = 1152
  layer1 = 3072 * 2 + (704 + 1152) * 3
  # layer 2: 12 edges x 2*4*4 = 384; parents (2 + 2 + 0) x 2*4*2 = 64,
  # 12 x 24 = 288; the head 2 x 2*4*3 = 48
  layer2 = (384 + 64 + 288) * 3
  assert flops_rgat.step_flops(*args) == layer1 + layer2 + 3 * 48
  params = 3 * (8 * 4 + 8) + 3 * (4 * 4 + 8) + 4 * 3 + 3
  assert flops_rgat.num_params(cfg) == params
  assert flops_rgat.step_bytes(*args) == (
      50 * 8 * 2 * 2 + (14 + 2) * 4 * 4 * 2 + params * 24)
  least, bound = flops_rgat.least_step_seconds(
      *args, {'flops_per_s': 1e3, 'bytes_per_s': 1e9})
  assert bound == 'flops' and least == flops_rgat.step_flops(*args) / 1e3


def test_the_cells_budgets_are_the_issues():
  _, _, cfg, traffic = run.load_cell(CELL)
  args = (cfg, traffic['batch_per_chip'], traffic['fanout'],
          traffic['seed_type'])
  assert flops_rgat.budget_rows(*args) == {
      'paper': 481024, 'author': 298560, 'institute': 57600,
      'fos': 250560, 'journal': 250560, 'conference': 250560}
  assert sum(flops_rgat.edge_slots(*args).values()) == 1588800


def test_typed_graphgen_is_the_seed_and_keeps_mean_degrees():
  _, _, cfg, _ = tiny_cell()
  a = graphgen_hetero.graph(cfg, 3_000_000_019)
  b = graphgen_hetero.graph(cfg, 3_000_000_019)
  c = graphgen_hetero.graph(cfg, 3_000_000_020)
  assert list(a) == [(r['src'], r['name'], r['dst'])
                     for r in cfg['relations']]
  for e in a:
    assert all(np.array_equal(x, y) for x, y in zip(a[e], b[e]))
    assert a[e][1].shape == c[e][1].shape
  assert not np.array_equal(a[('paper', 'cites', 'paper')][1],
                            c[('paper', 'cites', 'paper')][1])
  nodes = cfg['num_nodes']
  for r in cfg['relations']:
    indptr, indices = a[(r['src'], r['name'], r['dst'])]
    assert indptr.shape[0] == nodes[r['src']] + 1 and indptr[0] == 0
    assert indptr[-1] == indices.shape[0] == r['num_edges']   # the mean
    assert indices.min() >= 0 and indices.max() < nodes[r['dst']]
    if 'reverse_of' in r:   # the transpose, edge for edge
      fwd = next(x for x in cfg['relations']
                 if x['name'] == r['reverse_of'])
      fp, fi = a[(fwd['src'], fwd['name'], fwd['dst'])]
      src = np.repeat(np.arange(nodes[fwd['src']]), np.diff(fp))
      back = np.repeat(np.arange(nodes[r['src']]), np.diff(indptr))
      assert sorted(zip(fi.tolist(), src.tolist())) == sorted(
          zip(back.tolist(), indices.tolist()))
  f = graphgen_hetero.Features(nodes, 16, 7, 11)
  assert f.table('paper').dtype == graphgen_hetero.BF16
  assert np.array_equal(f.table('paper')[[3, 2999]],
                        f.rows('paper', [3, 2999]))
  assert not np.array_equal(f.rows('paper', [3]), f.rows('author', [3]))
  far = graphgen_hetero.BASE_ROWS + 3   # the next block is told apart
  assert not np.array_equal(f.rows('paper', [3]), f.rows('paper', [far]))
  assert set(np.unique(f.labels('paper'))) == set(range(7))


def test_the_references_two_copies_are_one_text():
  with open(os.path.join(REPO, 'chipbench', 'reference_rgat.py')) as f:
    ours = f.read()
  with open(os.path.join(REPO, 'glt_tpu', 'models', 'reference',
                         'rgat.py')) as f:
    theirs = f.read()
  assert ours == theirs
  assert 'glt_tpu' not in [line.split()[1].split('.')[0]
                           for line in ours.splitlines()
                           if line.startswith(('import ', 'from '))]


def _rehearse(monkeypatch, seconds=0.3):
  """The rest of a run after the look for a chip, on the CPU."""
  cell = tiny_cell()
  monkeypatch.setattr(run, 'load_cell', lambda name: cell)
  return run.run_cell('tiny', 3_000_000_019, seconds, False)


def test_rehearsal_of_a_run_comes_out_correct(monkeypatch, tpu_sampler):
  line = _rehearse(monkeypatch)
  assert line['correct'] is True, line['compared']
  assert line['attempted'] > 3 and line['failed'] == 0
  assert set(line['metrics']) == {'seeds_per_s', 'step_p90_ms', 'setup_s'}
  assert set(line['compared']) == {'loss_gap', 'grad_gap', 'change_gap',
                                   'sample_violations', 'compilations'}
  assert line['compared']['compilations'] == {'value': 0, 'limit': 0}
  assert line['compared']['sample_violations'] == {'value': 0, 'limit': 0}


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


def test_a_sample_that_is_not_the_graphs_is_counted(monkeypatch,
                                                    tpu_sampler):
  from chipbench.drivers import hetero_fused
  _, cell, cfg, traffic = tiny_cell()
  s = hetero_fused.build(cfg, traffic, 1, 5)
  good = s.sampled[0]
  assert hetero_fused.check_sample(s, good) == 0
  from glt_tpu.typing import reverse_edge_type
  flow = reverse_edge_type(('paper', 'cites', 'paper'))
  child, parent = good['edges'][flow]
  edges = dict(good['edges'])
  edges[flow] = ((child + 1) % good['nodes']['paper'].shape[0], parent)
  moved = dict(good, edges=edges)
  assert hetero_fused.check_sample(s, moved) > 0


def test_from_csr_samples_as_the_plain_constructor_does(tpu_sampler):
  """The stores built from a CSR taken as given (every row kept, no sort)
  give the sample that the partition constructor's stores give."""
  from glt_tpu.distributed import (DistHeteroGraph,
                                   DistHeteroNeighborSampler)
  from glt_tpu.parallel import make_mesh
  from glt_tpu.typing import GraphPartitionData
  _, _, cfg, _ = tiny_cell()
  csr, counts, mesh = graphgen_hetero.graph(cfg, 9), cfg[
      'num_nodes'], make_mesh(1)
  parts = {}
  for e, (indptr, indices) in csr.items():
    src = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    parts[e] = [GraphPartitionData(np.stack([src, indices]),
                                   np.arange(indices.shape[0]))]
  book = {t: np.zeros(n, np.int32) for t, n in counts.items()}
  outs = []
  for graph in (DistHeteroGraph(mesh, counts, parts, book),
                DistHeteroGraph.from_csr(mesh, counts, csr)):
    sampler = DistHeteroNeighborSampler(graph, [3, 2, 2], seed=0)
    outs.append(sampler.sample_from_nodes(
        'paper', np.arange(8, dtype=np.int32), key=jax.random.key(4)))
  for k in ('node', 'node_count', 'row', 'col', 'edge_mask'):
    for e in outs[0][k]:
      assert np.array_equal(outs[0][k][e], outs[1][k][e]), (k, e)


def test_scope_window_inputs_are_the_windows_shapes(tpu_sampler):
  """The profile's inputs have the tree, shapes and types of the
  driver's own, so the step's compiled program serves them."""
  from chipbench.drivers import hetero_fused
  _, cell, cfg, traffic = tiny_cell()
  s = hetero_fused.build(cfg, traffic, 1, 5)
  before = hetero_fused.compilations(s)
  params, opt, batches = hetero_scope_window.inputs(s.trainer, cfg,
                                                    traffic, steps=2)
  shape = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
  assert shape(params) == shape(s.params)
  assert shape(opt) == shape(s.opt)
  for seeds, n_valid, key in batches:
    params, opt, loss = s.trainer(params, opt, seeds, n_valid, key)
  assert np.isfinite(np.asarray(loss)).all()
  assert hetero_fused.compilations(s) == before
  # against a program without the typed scopes the readers say nothing
  run_ = {'cfg': cfg, 'traffic': traffic, 'trace': {}}
  hetero_scope_window._PROFILE[:] = [None]
  try:
    for name in NEW[2:]:
      assert importlib.import_module(
          'chipbench.layers.' + name).read(run_) is None
  finally:
    hetero_scope_window._PROFILE.clear()
