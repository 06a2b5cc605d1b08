"""Graph storage behaviors: pickling and the lazily placed edge arrays.

Graph objects pickle across process boundaries (mp channel payloads /
checkpoints): device arrays are dropped and placed again on first touch.
The edge arrays are the topology's own, at their own length.
"""
import pickle

import jax
import numpy as np

from fixtures import ring_dataset

from glt_tpu.ops.sample import neighbor_probs


def test_graph_pickle_roundtrip():
  ds = ring_dataset(num_nodes=24)
  g = ds.get_graph()
  g.lazy_init()
  g2 = pickle.loads(pickle.dumps(g))
  # device arrays were dropped from the pickle (re-placed on this
  # process's devices on first touch)
  assert g2._indices is None and not g2._initialized
  np.testing.assert_array_equal(np.asarray(g2.indptr),
                                np.asarray(g.topo.indptr))
  assert g2.num_edges == g.num_edges
  np.testing.assert_array_equal(np.asarray(g2.indices),
                                np.asarray(g.indices))


def test_edge_arrays_keep_the_topology_length():
  ds = ring_dataset(num_nodes=20)
  g = ds.get_graph()
  e = g.num_edges
  assert g.indices.shape[0] == e and g.edge_ids.shape[0] == e
  assert g.indices is g.indices       # one resident copy, placed once
  np.testing.assert_array_equal(np.asarray(g.indices), g.topo.indices)
  np.testing.assert_array_equal(np.asarray(g.edge_ids), g.topo.edge_ids)


def test_sampling_parity_across_a_pickle():
  from glt_tpu.sampler import NeighborSampler
  ds = ring_dataset(num_nodes=30)
  g = ds.get_graph()
  key = jax.random.key(7)
  seeds = np.arange(0, 30, 3)
  before = NeighborSampler(g, [2, 2], with_edge=True,
                           seed=5).sample_from_nodes(seeds, key=key)
  g2 = pickle.loads(pickle.dumps(g))
  after = NeighborSampler(g2, [2, 2], with_edge=True,
                          seed=5).sample_from_nodes(seeds, key=key)
  for k in ('node', 'row', 'col', 'edge'):
    np.testing.assert_array_equal(np.asarray(getattr(before, k)),
                                  np.asarray(getattr(after, k)), k)


def test_neighbor_probs_pad_safe():
  # a capacity-padded indices array (a stream snapshot's) must not
  # count its tail as edges
  ds = ring_dataset(num_nodes=16)
  g = ds.get_graph()
  probs = np.zeros(16, np.float32)
  probs[:4] = 1.0
  want = np.asarray(neighbor_probs(np.asarray(g.topo.indptr),
                                   np.asarray(g.topo.indices),
                                   probs, 2, 16))
  padded = np.concatenate([np.asarray(g.topo.indices),
                           np.full((6,), -1, g.topo.indices.dtype)])
  got = np.asarray(neighbor_probs(g.indptr, padded, probs, 2, 16))
  np.testing.assert_allclose(got, want, rtol=1e-6)
