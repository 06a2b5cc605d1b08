"""The benchmark's own tests: the manifest against the contract's
character sets, the yardstick's arithmetic, and ``correct`` shown to
fail, at sizes a CPU holds. No topology is described and no libtpu is
loaded here; times and rates come from the chip alone."""
import importlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import flops, graphgen, reference, run, trace_reduce

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}


def manifest():
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    return json.load(f)


def tiny_cell(chips):
  """A cell at a size the CPU holds, with the real cell's limits."""
  m, cell, cfg, traffic = run.load_cell('papers100m-c1.fused')
  cfg = dict(cfg, num_nodes=20000, num_edges=291000, feature_dim=16,
             hidden_dim=32, num_classes=7)
  traffic = dict(traffic, batch_per_chip=16, fanout=[4, 3, 2])
  return m, dict(cell, chips=chips), cfg, traffic


@pytest.fixture
def tpu_sampler(monkeypatch):
  """The sampler's engines as ``auto`` resolves them on a TPU."""
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')


def test_manifest_keeps_to_the_contract():
  m = manifest()
  assert set(m) == {'command', 'paths', 'run_seconds', 'configs',
                    'workloads', 'end_to_end', 'per_layer'}
  assert 1 <= m['run_seconds'] <= 51
  names = ([c['name'] for c in m['configs']]
           + [w['name'] for w in m['workloads']]
           + [x['name'] for x in m['end_to_end'] + m['per_layer']])
  assert len(set(names)) == len(names)
  for n in names + [w['traffic'] for w in m['workloads']] + [
      p['layer'] for p in m['per_layer']] + [
      k for c in m['configs'] for k in c['reduced']]:
    assert NAME.match(n), n
  for x in m['end_to_end'] + m['per_layer']:
    assert UNIT.match(x['unit']) and x['better'] in ('lower', 'higher')
    assert x['source'] in SOURCES
  for x in m['end_to_end']:
    assert set(x) <= {'name', 'unit', 'better', 'bound', 'source',
                      'workloads'}
    assert 0.01 <= x['bound'] <= 0.1 and x['source'] == 'host_clock'
  for c in m['configs']:
    assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
    assert len(c['source']) <= 200 and len(c['why']) <= 200
    assert c['file'].startswith('chipbench/')
  for w in m['workloads']:
    assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
    assert w['chips'] in (1, 4) and len(w['why']) <= 200
  four = sum(w['chips'] == 4 for w in m['workloads'])
  assert four <= max(1, len(m['workloads']) // 4)
  assert 'setup_s' in names
  assert os.path.getsize(os.path.join(REPO, 'BENCHMARK.json')) < 65536


def test_every_cell_finds_its_files_and_every_metric_its_cells():
  m = manifest()
  cells = {w['name'] for w in m['workloads']}
  e2e = {x['name'] for x in m['end_to_end']}
  for w in m['workloads']:
    _, cell, cfg, traffic = run.load_cell(w['name'])
    assert cfg['name'] == w['config'] and cfg['chips'] == w['chips']
    conf = next(c for c in m['configs'] if c['name'] == w['config'])
    assert cfg['reduced'] == conf['reduced'] and cfg['source'] == conf[
        'source']
    assert not [k for k in conf['reduced'] if k.endswith(('_dim', '_rank'))]
    driver = importlib.import_module('chipbench.drivers.'
                                     + traffic['driver'])
    assert all(hasattr(driver, f) for f in ('build', 'step', 'verify'))
    assert set(cfg['limits']) == {'loss_gap', 'grad_gap', 'change_gap'}
  for p in m['per_layer']:
    assert set(p) <= {'name', 'unit', 'better', 'source', 'layer', 'moves',
                      'workloads'}
    assert p['moves'] in e2e and p['moves'] != 'setup_s'
    assert set(p.get('workloads', cells)) <= cells
    assert callable(importlib.import_module(
        'chipbench.layers.' + p['name']).read)
  layers = {p['layer'] for p in m['per_layer']}
  assert layers <= {'loader', 'sampler', 'feature_store', 'model_step',
                    'kernels', 'collectives', 'device'}


def test_graphgen_is_the_seed_and_nothing_else():
  a = graphgen.csr(5000, 72750, 3_000_000_019)
  b = graphgen.csr(5000, 72750, 3_000_000_019)
  c = graphgen.csr(5000, 72750, 3_000_000_020)
  assert all(np.array_equal(x, y) for x, y in zip(a, b))
  assert c[1].shape == a[1].shape and not np.array_equal(a[1], c[1])
  indptr, indices = a
  assert indptr[0] == 0 and indptr[-1] == 72750 == indices.shape[0]
  assert (np.diff(indptr) >= 0).all()
  assert indices.min() >= 0 and indices.max() < 5000
  row = np.repeat(np.arange(5000), np.diff(indptr))
  assert not ((np.diff(indices) < 0) & (np.diff(row) == 0)).any()
  f = graphgen.Features(5000, 16, 7, 11)
  assert np.array_equal(f.table()[[3, 4999]], f.rows([3, 4999]))
  assert len(np.unique(f.table(), axis=0)) == 5000
  assert set(np.unique(f.labels())) == set(range(7))


def test_sorted_csr_samples_as_the_plain_constructor_does():
  from glt_tpu.data import Graph, Topology
  from glt_tpu.ops.sample import sample_neighbors
  indptr, indices = graphgen.csr(2000, 29100, 5)
  plain = Graph(Topology(indptr=indptr, indices=indices, num_nodes=2000))
  ours = Graph(graphgen.SortedCSR(indptr, indices, 2000))
  assert ours.edge_ids is None and ours.num_edges == plain.num_edges
  seeds = jnp.arange(0, 2000, 7, dtype=jnp.int32)
  outs = [sample_neighbors(g.indptr, g.indices, seeds, 5, jax.random.key(1))
          for g in (plain, ours)]
  assert np.array_equal(outs[0].nbrs, outs[1].nbrs)
  assert np.array_equal(outs[0].mask, outs[1].mask)


def test_reference_agrees_with_the_program_model_on_a_tiny_batch():
  import optax
  from glt_tpu.loader.transform import Batch
  from glt_tpu.models import GraphSAGE
  rng = np.random.default_rng(0)
  n, e, b = 60, 200, 8
  x = rng.standard_normal((n, 16)).astype(np.float32)
  child = rng.integers(0, n, e).astype(np.int32)
  parent = rng.integers(0, n, e).astype(np.int32)
  emask = rng.random(e) < 0.9
  y = rng.integers(0, 7, b).astype(np.int32)
  params = graphgen.weights(3, 16, 32, 7, 3)
  model = GraphSAGE(hidden_features=32, out_features=7, num_layers=3)
  batch = Batch(x=x, row=child, col=parent, edge_mask=emask,
                node=np.arange(n), node_count=n, y=y, batch_size=b,
                edge_hop_offsets=None)

  def loss_fn(p):
    return optax.softmax_cross_entropy_with_integer_labels(
        model.apply(p, batch), y).mean()

  want, gwant = jax.value_and_grad(loss_fn)(params)
  got, ggot = reference.loss_and_grad(params, x, child, parent, emask, y)
  np.testing.assert_allclose(got, want, rtol=1e-5)
  for a, w in zip(jax.tree.leaves(ggot), jax.tree.leaves(gwant)):
    np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-6)


def test_flops_on_the_issues_hand_count():
  cfg = {'feature_dim': 128, 'hidden_dim': 256, 'num_layers': 3,
         'num_classes': 172}
  assert flops.rows_needed(1024, [15, 10, 5]) == [169984, 16384, 1024]
  assert flops.budget_rows(1024, [15, 10, 5]) == 937984
  assert round(flops.step_flops(cfg, 1024, [15, 10, 5]) / 1e9) == 80
  peak = {'flops_per_s': 197e12, 'bytes_per_s': 819e9}
  least, bound = flops.least_step_seconds(cfg, 1024, [15, 10, 5], peak)
  assert bound == 'bytes' and 1.4e-3 < least < 1.9e-3


def test_trace_reduce_on_a_hand_made_event_list():
  ms = 1_000_000
  dev = {'modules': [('jit_step(1)', -3 * ms, 3 * ms),       # cut short
                     ('jit_step(1)', 0, 10 * ms), ('jit_step(1)', 10 * ms,
                                                   10 * ms),
                     ('jit_step(1)', 20 * ms, 4 * ms),        # cut short
                     ('jit_other', 30 * ms, 5 * ms)],
         'ops': [('%fusion.1 = f32[]', -2 * ms, 1 * ms),           # outside
                 ('%fusion.1 = f32[]', 0, 4 * ms),
                 ('%all_to_all.11 = f32[4,8]{1,0} all-to-all(f32[4,8] %fusion.3)',
                  3 * ms, 3 * ms),                            # overlaps 1 ms
                 ('%fusion.1 = f32[] fusion(f32[] %all-reduce.7)', 10 * ms, 6 * ms),
                 ('%all-reduce.7', 18 * ms, 2 * ms),
                 ('%fusion.9', 21 * ms, 2 * ms)]}             # outside
  idle = {'modules': [], 'ops': []}
  host = [('chipbench.wait', 5 * ms, 6 * ms), ('chipbench.dispatch',
                                               16 * ms, 1 * ms)]
  r = trace_reduce.reduce({'devices': {'/device:TPU:0': dev,
                                       '/device:TPU:1': idle},
                           'host': host})
  assert r['steps'] == 2
  assert r['window_s'] == pytest.approx(0.020)
  assert r['busy_s'] == pytest.approx(0.014)       # 6 + 6 + 2 ms
  assert r['top_collective_s'] == pytest.approx(0.005)
  assert r['breakdown']['device_ops'][0] == ['fusion.1', pytest.approx(0.010)]
  assert r['breakdown']['idle_gaps'][0] == ['chipbench.wait',
                                            pytest.approx(0.004)]
  run_ = {'trace': r, 'window': {'host_s': 0.002, 'steps': 2}}
  layer = lambda n: importlib.import_module('chipbench.layers.' + n).read
  assert layer('device_idle_pct')(run_) == pytest.approx(30.0)
  assert layer('collective_ms')(run_) == pytest.approx(2.5)
  assert layer('host_ms_per_step')(run_) == pytest.approx(1.0)
  r['top_collective_s'] = 0.0
  assert layer('collective_ms')(run_) is None      # nothing to read


@pytest.mark.parametrize('where', ['checkout', 'benchmark_only'])
def test_run_py_exits_nonzero_and_prints_no_result(where, tmp_path):
  """Without a TPU, and in a directory that holds only ``BENCHMARK.json``
  and the files under ``paths``."""
  root = REPO
  if where == 'benchmark_only':
    import shutil
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), root)
    for p in manifest()['paths']:
      shutil.copytree(os.path.join(REPO, p), os.path.join(root, p),
                      ignore=shutil.ignore_patterns('__pycache__'))
  env = {k: v for k, v in os.environ.items() if k != 'PYTHONPATH'}
  proc = subprocess.run(
      [sys.executable, os.path.join(root, 'chipbench', 'run.py'),
       '--workload', 'papers100m-c1.fused', '--seed', '3000000019',
       '--seconds', '1', '--trace', '0'],
      env={**env, 'JAX_PLATFORMS': 'cpu'}, cwd=root, capture_output=True,
      text=True, timeout=120)
  assert proc.returncode != 0
  assert proc.stdout == ''


def _rehearse(monkeypatch, chips, seconds=0.3):
  """The rest of a run after the look for a chip, on the CPU."""
  cell = tiny_cell(chips)
  monkeypatch.setattr(run, 'load_cell', lambda name: cell)
  return run.run_cell('tiny', 3_000_000_019, seconds, False)


@pytest.mark.parametrize('chips', [1, 4])
def test_rehearsal_of_a_run_comes_out_correct(monkeypatch, tpu_sampler,
                                              chips):
  line = _rehearse(monkeypatch, chips)
  assert list(line)[-1] == 'compared', list(line)
  assert line['correct'] is True, line['compared']
  assert line['attempted'] > 3 and line['failed'] == 0
  assert set(line['metrics']) == {'seeds_per_s', 'step_p90_ms', 'setup_s'}
  assert line['device']['count'] == chips
  assert line['compared']['compilations'] == {'value': 0, 'limit': 0}


def _unchanged(call):
  return lambda self, params, opt, seeds, n_valid, keys: (
      params, opt, call(self, params, opt, seeds, n_valid, keys)[2])


def _half_batch(call):
  return lambda self, params, opt, seeds, n_valid, keys: call(
      self, params, opt, seeds, n_valid // 2, keys)


@pytest.mark.parametrize('fault,chips', [('unchanged', 1),
                                         ('half_batch', 1),
                                         ('no_exchange', 4)])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch,
                                                   tpu_sampler, fault,
                                                   chips):
  """Each fault a training cell can have, planted under the harness: a
  step that returns its state unchanged, half of the batch left out with
  the mean taken over the rest, the exchange between chips left out."""
  from glt_tpu.parallel import collectives, train
  if fault == 'no_exchange':
    monkeypatch.setattr(collectives, 'all_to_all', lambda x, axis: x)
  else:
    call = train.SPMDSageTrainStep.__call__
    monkeypatch.setattr(train.SPMDSageTrainStep, '__call__',
                        {'unchanged': _unchanged,
                         'half_batch': _half_batch}[fault](call))
  line = _rehearse(monkeypatch, chips)
  assert line['correct'] is False, line['compared']


def test_the_control_in_bfloat16_comes_out_not_correct(tpu_sampler):
  """The reference put in the program's place and computed in bfloat16,
  the nearest precision below the configuration's float32, fails the
  cell's limits; and so does each fault planted in the reference."""
  _, cell, cfg, traffic = tiny_cell(4)
  ip, ix = graphgen.csr(cfg['num_nodes'], cfg['num_edges'], 7)
  feats = graphgen.Features(cfg['num_nodes'], cfg['feature_dim'],
                            cfg['num_classes'], 7)
  params = graphgen.weights(7, cfg['feature_dim'], cfg['hidden_dim'],
                            cfg['num_classes'], cfg['num_layers'])
  seeds = np.random.default_rng(7).permutation(cfg['num_nodes'])[
      :3 * 64].reshape(3, 64)
  keys = jax.random.split(graphgen.jax_key(7, 1), (3, 4))
  follow = lambda **kw: reference.follow(
      ip, ix, feats, params, lambda t: (seeds[t], keys[t]), 3, 4,
      traffic['fanout'], cfg['learning_rate'], rows_per_shard=5000, **kw)
  ref = follow()
  fails = lambda got: any(v > cfg['limits'][k] for k, v in
                          reference.compare(got, ref).items())
  assert not fails(follow())
  assert fails(follow(dtype=jnp.bfloat16))
  assert fails(follow(fault='half_batch'))
  assert fails(follow(fault='no_exchange'))
