"""The `pallas` hop engine (ops/pallas_kernels.py::sample_hop).

Acceptance contract (ISSUE 4): the megakernel produces BIT-IDENTICAL
NeighborOutput to the element path in interpret mode — offsets are
drawn from the same jax.random stream outside the kernel, the window
read only changes WHERE values are read from — and the multi-hop
pipeline shows zero steady-state recompiles under the engine. Parity is
asserted on the mask everywhere and on nbrs/eids over masked lanes
(invalid lanes are undefined in every engine, same contract as
tests/test_window_sample.py).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from glt_tpu.ops.sample import sample_neighbors

pytestmark = pytest.mark.pallas

W = 8
K = 4


def _csr(degrees, seed=7):
  rng = np.random.default_rng(seed)
  indptr = np.zeros(len(degrees) + 1, np.int32)
  np.cumsum(degrees, out=indptr[1:])
  num_edges = int(indptr[-1])
  indices = rng.integers(0, len(degrees), num_edges).astype(np.int32)
  return jnp.asarray(indptr), jnp.asarray(indices)


def _padded(arr, w=W):
  return jnp.concatenate([arr, jnp.full((w,), -1, arr.dtype)])


def _assert_identical(a, b):
  np.testing.assert_array_equal(np.asarray(a.mask), np.asarray(b.mask))
  m = np.asarray(a.mask)
  np.testing.assert_array_equal(np.asarray(a.nbrs)[m],
                                np.asarray(b.nbrs)[m])
  np.testing.assert_array_equal(np.asarray(a.eids)[m],
                                np.asarray(b.eids)[m])


@pytest.fixture(scope='module')
def graph():
  # zeros, sub-fanout, mid, exactly W, hubs (> W), tail row whose
  # window crosses the real edge-array end
  degrees = np.array([0, 2, 5, W, 20, 3, 17, 1, W - 1, 6], np.int64)
  return _csr(degrees)


def _run(graph, key, *, engine=None, seed_mask=None, edge_ids=None,
         replace=False, n_hub=2, width=W):
  indptr, indices = graph
  seeds = jnp.arange(indptr.shape[0] - 1, dtype=jnp.int32)
  kw = {}
  if engine is not None:
    kw = dict(window=(width, n_hub), indices_win=_padded(indices, width),
              edge_ids_win=(_padded(edge_ids, width)
                            if edge_ids is not None else None),
              engine=engine, interpret=True)
  return sample_neighbors(indptr, indices, seeds, K, key,
                          seed_mask=seed_mask, edge_ids=edge_ids,
                          replace=replace, **kw)


def test_bit_identical_to_element_path(graph):
  key = jax.random.key(0)
  _assert_identical(_run(graph, key),
                    _run(graph, key, engine='pallas'))


def test_matches_window_engine_exactly(graph):
  key = jax.random.key(1)
  _assert_identical(_run(graph, key, engine='window'),
                    _run(graph, key, engine='pallas'))


def test_edge_ids_and_seed_mask(graph):
  indptr, indices = graph
  key = jax.random.key(2)
  mask = jnp.asarray(np.arange(indptr.shape[0] - 1) % 2 == 0)
  eids = jnp.arange(indices.shape[0], dtype=jnp.int32) * 10
  _assert_identical(
      _run(graph, key, seed_mask=mask, edge_ids=eids),
      _run(graph, key, engine='pallas', seed_mask=mask, edge_ids=eids))


def test_replace_path(graph):
  key = jax.random.key(3)
  _assert_identical(_run(graph, key, replace=True),
                    _run(graph, key, engine='pallas', replace=True))


def test_all_hub_frontier():
  g = _csr(np.full(6, 3 * W, np.int64))
  key = jax.random.key(4)
  _assert_identical(_run(g, key),
                    _run(g, key, engine='pallas', n_hub=6))


def test_zero_hubs_wide_window(graph):
  key = jax.random.key(5)
  _assert_identical(
      _run(graph, key),
      _run(graph, key, engine='pallas', width=32, n_hub=0))


def test_empty_frontier(graph):
  indptr, indices = graph
  out = sample_neighbors(indptr, indices,
                         jnp.zeros((0,), jnp.int32), K,
                         jax.random.key(6), window=(W, 2),
                         indices_win=_padded(indices), engine='pallas',
                         interpret=True)
  assert out.nbrs.shape == (0, K) and out.mask.shape == (0, K)


def test_under_jit(graph):
  key = jax.random.key(7)
  base = _run(graph, key)
  winp = jax.jit(lambda: _run(graph, key, engine='pallas'))()
  _assert_identical(base, winp)


# -- multi-hop pipeline: engine selection + compile discipline ----------

def test_sampler_engine_bit_parity_and_zero_recompiles(monkeypatch):
  from fixtures import ring_dataset
  from glt_tpu.sampler import NeighborSampler
  ds = ring_dataset(num_nodes=40)
  seeds = np.arange(8)
  base = NeighborSampler(ds.get_graph(), [3, 2], seed=0,
                         with_edge=True).sample_from_nodes(seeds)
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0, with_edge=True)
  out = samp.sample_from_nodes(seeds)
  for f in ('node', 'row', 'col', 'edge_mask', 'batch', 'edge'):
    np.testing.assert_array_equal(np.asarray(getattr(base, f)),
                                  np.asarray(getattr(out, f)),
                                  err_msg=f)
  assert samp.num_compiled_fns == 1
  for _ in range(3):   # steady state: the one program serves every call
    samp.sample_from_nodes(seeds)
  assert samp.num_compiled_fns == 1


def test_stream_engine_parity_and_zero_recompiles(monkeypatch):
  """The stream pipeline under GLT_HOP_ENGINE=pallas: base-hop reads go
  through the megakernel, delta overlays keep their fixed windows, and
  overlay refreshes + snapshot swaps stay at zero recompiles
  (StreamSampler.trace_count — same discipline as tests/test_stream.py).
  """
  from fixtures import ring_dataset
  from glt_tpu.stream import (EdgeDeltaBuffer, SnapshotManager,
                              StreamSampler)
  N = 24
  ds = ring_dataset(num_nodes=N)
  mgr = SnapshotManager(ds.get_graph().topo, ds.get_node_feature(),
                        delta_capacity=64)
  seeds = np.arange(6)
  base = StreamSampler(mgr, [3, 2], seed=0).sample_from_nodes(seeds)
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  samp = StreamSampler(mgr, [3, 2], seed=0)
  out = samp.sample_from_nodes(seeds)
  for f in ('node', 'row', 'col', 'edge_mask', 'batch'):
    np.testing.assert_array_equal(np.asarray(getattr(base, f)),
                                  np.asarray(getattr(out, f)),
                                  err_msg=f)
  buf = EdgeDeltaBuffer(capacity=16, num_nodes=N)
  buf.insert_edges([1, 2], [5, 6])
  samp.refresh_overlay(buf)
  traces, fns = samp.trace_count, samp.num_compiled_fns
  for _ in range(3):
    samp.sample_from_nodes(seeds)
  mgr.compact(buf.drain())        # swap: same static shapes
  samp.clear_overlay()
  samp.sample_from_nodes(seeds)
  assert samp.trace_count == traces
  assert samp.num_compiled_fns == fns


def test_two_batch_shapes_share_the_padded_arrays(monkeypatch):
  """Two compiled programs over the same graph (serving buckets trace
  the sampler once per batch size): the window-padded edge arrays must
  come out of window_arrays as CONCRETE arrays even though the one_hop
  closures run at trace time — a staged pad would rebind the graph's
  indices to a tracer that leaks into the second trace (regression for
  the multi-bucket UnexpectedTracerError)."""
  from fixtures import ring_dataset
  from glt_tpu.sampler import NeighborSampler
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  ds = ring_dataset(num_nodes=40)
  samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0)
  out4 = samp.sample_from_nodes(np.arange(4))    # trace 1
  out8 = samp.sample_from_nodes(np.arange(8))    # trace 2: same graph
  assert samp.num_compiled_fns == 2
  assert int(out4.node_count) > 0 and int(out8.node_count) > 0


def test_hetero_engine_bit_parity(monkeypatch):
  from fixtures import hetero_ring_dataset
  from glt_tpu.sampler import NeighborSampler
  from glt_tpu.sampler.base import NodeSamplerInput
  ds = hetero_ring_dataset()
  seeds = NodeSamplerInput(np.arange(6), 'user')
  base = NeighborSampler(ds.graph, [2, 2], seed=0,
                         with_edge=True).sample_from_nodes(seeds)
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  out = NeighborSampler(ds.graph, [2, 2], seed=0,
                        with_edge=True).sample_from_nodes(seeds)
  for t in base.node:
    np.testing.assert_array_equal(np.asarray(base.node[t]),
                                  np.asarray(out.node[t]), err_msg=t)
  for e in base.row:
    for field in ('row', 'col', 'edge_mask', 'edge'):
      np.testing.assert_array_equal(
          np.asarray(getattr(base, field)[e]),
          np.asarray(getattr(out, field)[e]), err_msg=f'{field} {e}')


def test_hop_engine_knob_validation(monkeypatch):
  from glt_tpu.ops.pipeline import hop_engine
  monkeypatch.setenv('GLT_HOP_ENGINE', 'warp')
  with pytest.raises(ValueError):
    hop_engine()
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas')
  assert hop_engine() == 'pallas'
  monkeypatch.delenv('GLT_HOP_ENGINE')
  assert hop_engine() == 'element'
