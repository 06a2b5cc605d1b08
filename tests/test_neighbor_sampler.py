"""NeighborSampler tests on the deterministic ring fixture (reference
strategy: req_num >= degree makes sampling exhaustive; ring adjacency is
formulaic, test_neighbor_sampler.py:25-80 upstream)."""
import jax.numpy as jnp
import numpy as np
import pytest

from glt_tpu.sampler import NeighborSampler, NodeSamplerInput

from fixtures import ring_dataset, hetero_ring_dataset


@pytest.fixture(scope='module')
def ring():
  return ring_dataset(num_nodes=40)


def _valid_nodes(out):
  n = np.asarray(out.node)
  return n[:int(out.node_count)]


def test_one_hop_exhaustive(ring):
  s = NeighborSampler(ring.get_graph(), [2], seed=7)
  out = s.sample_from_nodes(np.array([0, 10]))
  nodes = _valid_nodes(out)
  # seeds first, then neighbors (v+1, v+2) % 40 in first-occurrence order
  np.testing.assert_array_equal(nodes, [0, 10, 1, 2, 11, 12])
  em = np.asarray(out.edge_mask)
  rows = np.asarray(out.row)[em]   # children
  cols = np.asarray(out.col)[em]   # parents
  got = sorted(zip(cols.tolist(), rows.tolist()))
  # parent label -> child label: 0->{2(=node1),3(=node2)}, 1->{4,5}
  assert got == [(0, 2), (0, 3), (1, 4), (1, 5)]
  np.testing.assert_array_equal(np.asarray(out.num_sampled_nodes), [2, 4])
  np.testing.assert_array_equal(np.asarray(out.num_sampled_edges), [4])


def test_two_hop_ring_closure(ring):
  # nodes 0's 2-hop neighborhood in the ring: {0,1,2,3,4}
  s = NeighborSampler(ring.get_graph(), [2, 2], seed=3)
  out = s.sample_from_nodes(np.array([0]))
  nodes = set(_valid_nodes(out).tolist())
  assert nodes == {0, 1, 2, 3, 4}
  # every valid edge satisfies the ring relation child = (parent+1|2) % 40
  em = np.asarray(out.edge_mask)
  node_arr = np.asarray(out.node)
  child = node_arr[np.asarray(out.row)[em]]
  parent = node_arr[np.asarray(out.col)[em]]
  for p, c in zip(parent, child):
    assert c % 40 in ((p + 1) % 40, (p + 2) % 40)


def test_edge_ids_recoverable(ring):
  s = NeighborSampler(ring.get_graph(), [2], with_edge=True, seed=1)
  out = s.sample_from_nodes(np.array([5]))
  em = np.asarray(out.edge_mask)
  eids = np.asarray(out.edge)[em]
  # node 5's out-edges have eids 10, 11
  assert set(eids.tolist()) == {10, 11}


def test_padded_seed_batch(ring):
  s = NeighborSampler(ring.get_graph(), [2], seed=0)
  seeds = np.array([7, 8, 0, 0])  # last two are padding
  out = s.sample_from_nodes(seeds, n_valid=2)
  nodes = _valid_nodes(out)
  assert set(nodes.tolist()) == {7, 8, 9, 10}
  assert int(np.asarray(out.num_sampled_nodes)[0]) == 2


def test_fanout_smaller_than_degree_distinct(ring):
  s = NeighborSampler(ring.get_graph(), [1], seed=11)
  seen = set()
  for trial in range(30):
    out = s.sample_from_nodes(np.array([0]))
    em = np.asarray(out.edge_mask)
    assert em.sum() == 1
    child = np.asarray(out.node)[np.asarray(out.row)[em][0]]
    assert child in (1, 2)
    seen.add(int(child))
  assert seen == {1, 2}  # both neighbors eventually sampled


def test_weighted_sampler_runs(ring=None):
  ds = ring_dataset(num_nodes=20, weighted=True)
  s = NeighborSampler(ds.get_graph(), [2], with_weight=True, seed=5)
  out = s.sample_from_nodes(np.array([0, 5]))
  nodes = _valid_nodes(out)
  assert set(nodes.tolist()) == {0, 5, 1, 2, 6, 7}


def test_sampler_batches_are_independent(ring):
  # table reset between batches: second batch labels start from scratch
  s = NeighborSampler(ring.get_graph(), [2], seed=2)
  out1 = s.sample_from_nodes(np.array([0]))
  out2 = s.sample_from_nodes(np.array([20]))
  np.testing.assert_array_equal(_valid_nodes(out2), [20, 21, 22])


def test_sample_prob(ring):
  s = NeighborSampler(ring.get_graph(), [2, 2], seed=2)
  probs = np.asarray(s.sample_prob(np.array([0]), 40))
  assert probs[0] == 1.0
  assert probs[1] == 1.0 and probs[2] == 1.0   # deg=2 <= fanout
  assert probs[3] > 0 and probs[4] > 0          # second hop reached
  assert probs[10] == 0.0


def test_subgraph_via_sampler(ring):
  s = NeighborSampler(ring.get_graph(), [2, 2], with_edge=True, seed=0)
  sub = s.subgraph(np.array([0]))
  # nodes {0..4}; induced edges are all (v -> v+1|v+2) pairs within the set
  nodes = np.asarray(sub.nodes)[:int(sub.node_count)]
  assert set(nodes.tolist()) == {0, 1, 2, 3, 4}
  em = np.asarray(sub.edge_mask)
  pairs = {(int(nodes[r]), int(nodes[c]))
           for r, c in zip(np.asarray(sub.rows)[em], np.asarray(sub.cols)[em])}
  assert pairs == {(0, 1), (0, 2), (1, 2), (1, 3), (2, 3), (2, 4), (3, 4)}


# -- hetero -------------------------------------------------------------

@pytest.fixture(scope='module')
def hetero():
  return hetero_ring_dataset(num_users=10, num_items=20)


def test_hetero_sample_out_direction(hetero):
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  s = NeighborSampler(hetero.graph, {u2i: [2, 2], i2i: [2, 2]}, seed=4)
  out = s.sample_from_nodes(NodeSamplerInput(np.array([3]), 'user'))
  # user 3 -> items {6,7}; hop2: i2i from {6,7} -> {7,8,9} (+u2i has no
  # user frontier at hop 2)
  items = np.asarray(out.node['item'])[:int(out.node_count['item'])]
  assert set(items.tolist()) == {6, 7, 8, 9}
  users = np.asarray(out.node['user'])[:int(out.node_count['user'])]
  np.testing.assert_array_equal(users, [3])
  # 'out' direction: keys are reversed types
  rev_u2i = ('item', 'rev_u2i', 'user')
  rev_i2i = ('item', 'i2i', 'item')  # same src/dst type keeps its name
  assert rev_u2i in out.row
  em = np.asarray(out.edge_mask[rev_u2i])
  child_items = np.asarray(out.node['item'])[np.asarray(out.row[rev_u2i])[em]]
  parent_users = np.asarray(out.node['user'])[np.asarray(out.col[rev_u2i])[em]]
  assert set(child_items.tolist()) == {6, 7}
  assert set(parent_users.tolist()) == {3}
  # i2i edges follow the ring relation
  em2 = np.asarray(out.edge_mask[rev_i2i])
  child = np.asarray(out.node['item'])[np.asarray(out.row[rev_i2i])[em2]]
  parent = np.asarray(out.node['item'])[np.asarray(out.col[rev_i2i])[em2]]
  for p, c in zip(parent, child):
    assert c in ((p + 1) % 20, (p + 2) % 20)


def test_hetero_num_sampled_counts(hetero):
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  s = NeighborSampler(hetero.graph, {u2i: [2], i2i: [2]}, seed=4)
  out = s.sample_from_nodes(NodeSamplerInput(np.array([0, 1]), 'user'))
  np.testing.assert_array_equal(
      np.asarray(out.num_sampled_nodes['user']), [2, 0])
  np.testing.assert_array_equal(
      np.asarray(out.num_sampled_nodes['item']), [0, 4])


def test_hetero_sample_prob(hetero):
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  s = NeighborSampler(hetero.graph, {u2i: [2], i2i: [2]}, seed=0)
  probs = s.sample_prob(('user', np.array([3])))
  u = np.asarray(probs['user'])
  it = np.asarray(probs['item'])
  assert u[3] == 1.0 and u.sum() == 1.0      # only the seed user
  # user 3 -> items {6, 7} (deg 2 <= fanout 2 -> prob 1)
  assert it[6] == 1.0 and it[7] == 1.0
  assert it[[0, 1, 2, 3]].sum() == 0.0


# -- fanout = -1 (full neighborhood) ------------------------------------

def _random_var_degree_dataset(n=25, seed=42):
  from glt_tpu.data import Dataset
  rng = np.random.default_rng(seed)
  edges = set()
  for v in range(n):
    for w in rng.choice(n, int(rng.integers(0, 7)), replace=False):
      if int(w) != v:
        edges.add((v, int(w)))
  edges = sorted(edges)
  rows = np.array([e[0] for e in edges], np.int64)
  cols = np.array([e[1] for e in edges], np.int64)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([rows, cols]), num_nodes=n)
  adj = {v: sorted(w for (x, w) in edges if x == v) for v in range(n)}
  return ds, adj


def test_full_neighborhood_two_hop_exact():
  """NeighborSampler([-1, -1]) must reproduce the dense 2-hop expansion
  exactly (reference fanout=-1 semantics, seal_link_pred.py:45-59)."""
  ds, adj = _random_var_degree_dataset()
  s = NeighborSampler(ds.get_graph(), [-1, -1], seed=0)
  seeds = [3, 17]
  out = s.sample_from_nodes(np.array(seeds))

  node = np.asarray(out.node)
  em = np.asarray(out.edge_mask)
  child = node[np.asarray(out.row)]
  parent = node[np.asarray(out.col)]
  offs = out.edge_hop_offsets

  # hop 1: exactly every out-edge of every seed
  got1 = sorted((int(parent[i]), int(child[i]))
                for i in range(offs[0], offs[1]) if em[i])
  want1 = sorted((v, w) for v in seeds for w in adj[v])
  assert got1 == want1

  # hop 2: every out-edge of every node first seen in hop 1
  seen = list(seeds)
  lvl1_new = []
  for i in range(offs[0], offs[1]):
    if em[i] and int(child[i]) not in seen:
      seen.append(int(child[i]))
      lvl1_new.append(int(child[i]))
  got2 = sorted((int(parent[i]), int(child[i]))
                for i in range(offs[1], offs[2]) if em[i])
  want2 = sorted((v, w) for v in lvl1_new for w in adj[v])
  assert got2 == want2

  # node set is the exact 2-hop closure
  closure = set(seeds)
  closure |= {w for v in seeds for w in adj[v]}
  closure |= {w for v in list(closure) for w in adj[v]}
  assert set(node[:int(out.node_count)].tolist()) == closure


def test_full_neighborhood_cap_truncates():
  ds, adj = _random_var_degree_dataset()
  s = NeighborSampler(ds.get_graph(), [-1], seed=0, full_neighbor_cap=2)
  out = s.sample_from_nodes(np.array([3]))
  em = np.asarray(out.edge_mask)
  # window of 2: at most 2 neighbors survive, in adjacency order
  got = sorted(np.asarray(out.node)[np.asarray(out.row)[em]].tolist())
  assert got == sorted(adj[3][:2])


def test_full_neighborhood_mixed_with_sampled_hop():
  """[-1, K] mixes a full hop with a sampled hop."""
  ds, adj = _random_var_degree_dataset()
  s = NeighborSampler(ds.get_graph(), [-1, 1], seed=5)
  out = s.sample_from_nodes(np.array([3]))
  offs = out.edge_hop_offsets
  em = np.asarray(out.edge_mask)
  node = np.asarray(out.node)
  got1 = sorted(node[np.asarray(out.row)[offs[0]:offs[1]]]
                [em[offs[0]:offs[1]]].tolist())
  assert got1 == adj[3]
  # hop 2: each new frontier node contributes at most 1 sampled edge
  parents2 = node[np.asarray(out.col)[offs[1]:offs[2]]][em[offs[1]:offs[2]]]
  cnt = {}
  for p in parents2.tolist():
    cnt[p] = cnt.get(p, 0) + 1
  assert all(c == 1 for c in cnt.values())
