"""Smoke tests for the examples (subprocess, CPU backend): examples are
the workload catalog's executable documentation — they must not rot."""
import os
import subprocess
import sys

import pytest

_EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'examples')


def _run(script, *args, timeout=150):
  env = dict(os.environ)
  env['GLT_PLATFORM'] = 'cpu'
  env['PYTHONPATH'] = (os.path.dirname(_EXAMPLES) + os.pathsep
                       + env.get('PYTHONPATH', ''))
  out = subprocess.run(
      [sys.executable, os.path.join(_EXAMPLES, script), *args],
      capture_output=True, text=True, timeout=timeout, env=env,
      cwd=_EXAMPLES)
  assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
  return out.stdout


def test_train_sage_example():
  out = _run('train_sage_products.py', '--epochs', '1',
             '--batch-size', '512', '--fanout', '5,5')
  assert 'test acc:' in out


@pytest.mark.slow
def test_serve_sage_example():
  """Train -> checkpoint -> restore -> serve over the rpc fabric.
  (slow: two jax subprocess cold-starts; the in-process serving path is
  covered by tests/test_serving.py in tier-1)"""
  out = _run('serve_sage_products.py', '--nodes', '4000',
             '--max-steps', '3', '--hidden', '32', '--queries', '8',
             timeout=300)
  assert 'checkpoint saved' in out
  assert 'steady-state recompiles: 0' in out
  assert 'cache_hit=' in out


@pytest.mark.slow
def test_stream_updates_example():
  """Train -> serve -> live edge+feature updates -> cache-coherent
  fresh predictions (slow: a jax subprocess cold-start; the in-process
  stream path is covered by tests/test_stream.py in tier-1)."""
  out = _run('stream_updates.py', '--nodes', '2000',
             '--max-steps', '3', timeout=300)
  assert 'steady-state recompiles across swap: 0' in out
  assert 'fresh predictions for updated nodes:' in out


@pytest.mark.parametrize('entry', [(), ('--fused',)],
                         ids=['loader', 'fused'])
def test_unsup_example(entry):
  out = _run('graph_sage_unsup.py', '--epochs', '1', *entry, timeout=300)
  assert 'loss=' in out
  assert ('fused=1' in out) == bool(entry)


def test_seal_example():
  out = _run('seal_link_pred.py', '--epochs', '1', '--nodes', '120')
  assert 'Loss:' in out and 'Test:' in out


def test_hetero_rgnn_example():
  out = _run(os.path.join('hetero', 'train_rgnn.py'), '--epochs', '1',
             '--conv', 'rsage')
  assert 'loss=' in out


def test_igbh_pipeline_tools(tmp_path):
  """compress_graph --synthesize -> split_seeds: the preprocessing
  chain produces loadable compressed topology + seed splits."""
  import numpy as np
  root = str(tmp_path / 'igbh')
  out = _run(os.path.join('igbh', 'compress_graph.py'),
             '--path', root, '--synthesize', '500', '--bf16')
  assert 'edges -> CSC' in out and 'bf16' in out
  out = _run(os.path.join('igbh', 'split_seeds.py'), '--path', root)
  assert 'train' in out
  ti = np.load(os.path.join(root, 'processed', 'train_idx.npy'))
  vi = np.load(os.path.join(root, 'processed', 'val_idx.npy'))
  assert ti.shape[0] == 300 and vi.shape[0] == 5
  assert len(set(ti.tolist()) & set(vi.tolist())) == 0
  comp = np.load(os.path.join(
      root, 'csc', 'paper__cites__paper', 'compressed.npz'))
  assert comp['indptr'].shape[0] == 501
  assert comp['indices'].shape[0] == 5000


def test_igbh_dist_train_example():
  out = _run(os.path.join('igbh', 'dist_train_rgnn.py'),
             '--papers', '1500', '--epochs', '1',
             '--steps-per-epoch', '2', '--batch-size', '8',
             '--val-batches', '1', '--hidden', '16', '--conv', 'rsage',
             timeout=400)
  assert 'val_acc=' in out and ':::MLLOG' in out and 'done' in out


def test_dist_sage_unsup_example():
  out = _run(os.path.join('distributed', 'dist_sage_unsup.py'),
             '--nodes', '600', '--epochs', '1', '--batch-size', '8',
             timeout=400)
  assert 'loss=' in out


def test_hierarchical_sage_example():
  out = _run(os.path.join('hetero', 'hierarchical_sage.py'),
             '--epochs', '1', '--papers', '1000', '--batch-size', '64',
             timeout=300)
  assert 'loss=' in out


def test_bipartite_sage_unsup_example():
  out = _run(os.path.join('hetero', 'bipartite_sage_unsup.py'),
             '--epochs', '2', '--users', '300', timeout=400)
  assert 'test_auc=' in out


def test_hgt_mag_example():
  out = _run(os.path.join('hetero', 'train_hgt_mag.py'), '--epochs', '1',
             timeout=300)
  assert 'loss=' in out


def test_pai_table_train_example():
  out = _run('pai_table_train.py', '--epochs', '1', timeout=300)
  assert 'loss=' in out


def test_gpt_on_graphs_example():
  """Ego-subgraph -> LLM prompt demo (reference examples/gpt/arxiv.py):
  prompts carry the sampled structure and the seed-pair question."""
  out = _run('gpt_on_graphs.py', '--papers', '300',
             '--num-batches', '1', timeout=300)
  assert 'Papers:' in out and 'Known citations' in out
  assert 'Question: based only on the structure above' in out


def test_trim_example():
  out = _run('train_sage_with_trim.py', '--nodes', '600',
             '--fanout', '5,3', timeout=420)
  assert 'trim=True' in out and 'done' in out
