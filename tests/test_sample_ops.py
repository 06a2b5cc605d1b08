"""Sampling-op tests following the reference strategy (SURVEY.md §4):
tiny graphs where req_num >= degree makes sampling exhaustive and exact,
plus statistical checks for the sub-degree regime."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glt_tpu.data import Topology
from glt_tpu.ops import (
    sample_neighbors, sample_neighbors_weighted, neighbor_probs,
)


@pytest.fixture
def small_csr():
  # 0 -> {1,2,3}; 1 -> {0}; 2 -> {}; 3 -> {1,2}
  ei = np.array([[0, 0, 0, 1, 3, 3], [1, 2, 3, 0, 1, 2]])
  topo = Topology(edge_index=ei, num_nodes=4)
  return topo


def test_exhaustive_when_fanout_geq_degree(small_csr):
  t = small_csr
  out = sample_neighbors(jnp.asarray(t.indptr), jnp.asarray(t.indices),
                         jnp.array([0, 1, 2, 3]), fanout=3,
                         key=jax.random.key(0))
  nbrs = np.asarray(out.nbrs)
  mask = np.asarray(out.mask)
  assert set(nbrs[0][mask[0]]) == {1, 2, 3}
  assert set(nbrs[1][mask[1]]) == {0}
  assert mask[2].sum() == 0
  assert set(nbrs[3][mask[3]]) == {1, 2}
  np.testing.assert_array_equal(np.asarray(out.nbrs_num), [3, 1, 0, 2])


def test_eids_match_adjacency_slots(small_csr):
  t = small_csr
  out = sample_neighbors(jnp.asarray(t.indptr), jnp.asarray(t.indices),
                         jnp.array([3]), fanout=2, key=jax.random.key(1),
                         edge_ids=jnp.asarray(t.edge_ids))
  eids = np.asarray(out.eids)[0]
  mask = np.asarray(out.mask)[0]
  # node 3's edges are original COO positions 4,5 (3->1, 3->2)
  assert set(eids[mask]) == {4, 5}


def test_seed_mask_suppresses(small_csr):
  t = small_csr
  out = sample_neighbors(jnp.asarray(t.indptr), jnp.asarray(t.indices),
                         jnp.array([0, 0]), fanout=3,
                         key=jax.random.key(0),
                         seed_mask=jnp.array([True, False]))
  mask = np.asarray(out.mask)
  assert mask[0].sum() == 3 and mask[1].sum() == 0


def test_without_replacement_distinct():
  # star: node 0 -> 1..20
  n = 21
  ei = np.stack([np.zeros(20, np.int64), np.arange(1, 21)])
  t = Topology(edge_index=ei, num_nodes=n)
  for s in range(20):
    out = sample_neighbors(jnp.asarray(t.indptr), jnp.asarray(t.indices),
                           jnp.array([0]), fanout=5,
                           key=jax.random.key(s))
    nbrs = np.asarray(out.nbrs)[0]
    mask = np.asarray(out.mask)[0]
    assert mask.all()
    assert len(set(nbrs.tolist())) == 5, 'duplicates in WOR sample'
    assert all(1 <= v <= 20 for v in nbrs)


def test_uniformity_of_floyd():
  # node 0 with degree 12, fanout 4; each neighbor should appear with
  # p = 4/12 over many trials
  deg, fan, trials = 12, 4, 3000
  ei = np.stack([np.zeros(deg, np.int64), np.arange(1, deg + 1)])
  t = Topology(edge_index=ei, num_nodes=deg + 1)
  indptr, indices = jnp.asarray(t.indptr), jnp.asarray(t.indices)

  @jax.jit
  def draw(key):
    return sample_neighbors(indptr, indices, jnp.array([0]), fan, key).nbrs

  counts = np.zeros(deg + 1)
  for s in range(trials):
    nbrs = np.asarray(draw(jax.random.key(s)))[0]
    counts[nbrs] += 1
  p = counts[1:] / trials
  np.testing.assert_allclose(p, fan / deg, atol=0.04)


def test_with_replacement():
  ei = np.stack([np.zeros(3, np.int64), np.arange(1, 4)])
  t = Topology(edge_index=ei, num_nodes=4)
  out = sample_neighbors(jnp.asarray(t.indptr), jnp.asarray(t.indices),
                         jnp.array([0]), fanout=8,
                         key=jax.random.key(0), replace=True)
  assert np.asarray(out.mask).all()
  assert set(np.asarray(out.nbrs)[0]) <= {1, 2, 3}


def test_weighted_prefers_heavy_edges():
  deg = 10
  ei = np.stack([np.zeros(deg, np.int64), np.arange(1, deg + 1)])
  w = np.ones(deg, np.float32)
  w[0] = 1000.0  # edge to node 1 dominates
  t = Topology(edge_index=ei, edge_weights=w, num_nodes=deg + 1)
  hits = 0
  for s in range(50):
    out = sample_neighbors_weighted(
        jnp.asarray(t.indptr), jnp.asarray(t.indices),
        jnp.asarray(t.edge_weights), jnp.array([0]), fanout=3,
        key=jax.random.key(s), max_degree=16)
    nbrs = np.asarray(out.nbrs)[0][np.asarray(out.mask)[0]]
    assert len(set(nbrs.tolist())) == len(nbrs)  # WOR
    hits += int(1 in nbrs)
  assert hits >= 49  # dominant edge nearly always present


def test_weighted_exhaustive_small_degree():
  ei = np.array([[0, 0], [1, 2]])
  w = np.array([0.5, 2.0], np.float32)
  t = Topology(edge_index=ei, edge_weights=w, num_nodes=3)
  out = sample_neighbors_weighted(
      jnp.asarray(t.indptr), jnp.asarray(t.indices),
      jnp.asarray(t.edge_weights), jnp.array([0, 1]), fanout=4,
      key=jax.random.key(0), max_degree=4)
  mask = np.asarray(out.mask)
  assert set(np.asarray(out.nbrs)[0][mask[0]]) == {1, 2}
  assert mask[1].sum() == 0


def test_neighbor_probs_hotness():
  # 0 -> {1,2}; seed prob 1.0 at node 0, fanout 1 => each nbr gets 0.5
  ei = np.array([[0, 0], [1, 2]])
  t = Topology(edge_index=ei, num_nodes=3)
  probs = neighbor_probs(jnp.asarray(t.indptr), jnp.asarray(t.indices),
                         jnp.array([1.0, 0.0, 0.0]), fanout=1, num_nodes=3)
  np.testing.assert_allclose(np.asarray(probs), [0.0, 0.5, 0.5])


def test_multihop_sample_many_matches_single():
  from glt_tpu.ops.pipeline import multihop_sample, multihop_sample_many
  ei = np.stack([np.repeat(np.arange(30), 2),
                 np.concatenate([(np.arange(30) + 1) % 30,
                                 (np.arange(30) + 2) % 30])])
  # interleave (v+1, v+2) per v
  rows = np.repeat(np.arange(30), 2)
  cols = np.stack([(np.arange(30) + 1) % 30,
                   (np.arange(30) + 2) % 30], 1).reshape(-1)
  t = Topology(edge_index=np.stack([rows, cols]), num_nodes=30)
  indptr, indices = jnp.asarray(t.indptr.astype(np.int32)), \
      jnp.asarray(t.indices)
  one_hop = lambda ids, f, k, m: sample_neighbors(
      indptr, indices, ids, f, k, seed_mask=m)
  seeds_stack = jnp.asarray([[0, 5], [10, 15], [20, 25]], jnp.int32)
  nv = jnp.full(3, 2, jnp.int32)
  outs = multihop_sample_many(one_hop, seeds_stack, nv, (2,),
                              jax.random.key(0))
  nodes = np.asarray(outs['node'])          # [3, budget]
  counts = np.asarray(outs['node_count'])
  for i, (a, b) in enumerate([(0, 5), (10, 15), (20, 25)]):
    got = set(nodes[i][:counts[i]].tolist())
    expect = {a, b, (a+1) % 30, (a+2) % 30, (b+1) % 30, (b+2) % 30}
    assert got == expect
  # and a single batch alone samples the same neighbourhood
  out2 = multihop_sample(one_hop, jnp.array([7, 8], jnp.int32),
                         jnp.asarray(2), (2,), jax.random.key(1))
  got = set(np.asarray(out2['node'])[:int(out2['node_count'])].tolist())
  assert got == {7, 8, 9, 10}
