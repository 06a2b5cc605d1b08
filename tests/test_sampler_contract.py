"""The one-type sampler held against the numpy oracle
(tests/sampler_oracle.py) on the path the chip runs.

The inputs are the ones the deleted engine-parity files carried
(test_window_sample.py, test_pallas_hop.py, test_pallas_fused.py, the
window-DMA tests of test_sample_ops.py): those compared an interpreted
kernel with the element read, so nothing independent checked the
element read itself on them. Here every output is checked against the
edge list, on the one hop loop there is (ops/pipeline.py), the one the
chip runs. The typed cases live in tests/test_sampler_contract_typed.py.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from glt_tpu.data import Dataset, Topology
from glt_tpu.ops.pipeline import (hop_fanouts, multihop_sample,
                                  multihop_sample_many)
from glt_tpu.ops.sample import (sample_full_neighbors, sample_neighbors,
                                sample_neighbors_weighted)

from fixtures import ring_dataset, ring_edges
import sampler_oracle
from sampler_oracle import EdgeTable, check_hop

K = 4


def check_multihop(g, seeds, n_valid, fanouts, out, **kw):
  """The oracle, with every batch held to the promise of parent-major
  edge slots that the hop loop gives (``Batch.hop_fanouts``)."""
  kw.setdefault('hop_fanouts', hop_fanouts(kw.get('widths') or fanouts))
  sampler_oracle.check_multihop(g, seeds, n_valid, fanouts, out, **kw)


def _csr(degrees, seed=7):
  rng = np.random.default_rng(seed)
  indptr = np.zeros(len(degrees) + 1, np.int32)
  np.cumsum(degrees, out=indptr[1:])
  indices = rng.integers(0, len(degrees), int(indptr[-1])).astype(np.int32)
  return indptr, indices


# degree 0, 1, < k, = k, > k, and hubs above 96 (the old window width)
MIXED = np.array([0, 1, 2, K, 5, 8, 20, 3, 17, 7, 6, 100, 131], np.int64)

FRONTIERS = {
    # name: (degrees, seeds, seed_mask)
    'mixed_degrees': (MIXED, np.arange(len(MIXED)), None),
    'all_hubs': (np.full(6, 120, np.int64), np.arange(6), None),
    'no_row_above_fanout': (np.array([3, 1, 0, K, 2, K], np.int64),
                            np.arange(6), None),
    'empty_frontier': (MIXED, np.zeros((0,), np.int64), None),
    'masked_and_duplicate_seeds': (
        MIXED, np.array([11, 4, 11, 6, 6, 0, 12, 3, 3, 8]),
        np.arange(10) % 3 != 1),
}


@functools.lru_cache(maxsize=None)
def _one_hop_fn(replace, with_eids, masked):
  """One compiled hop a (replace, eids, mask) form; jit re-specialises
  by frontier shape, so cases of one shape share a program."""
  def fn(indptr, indices, seeds, key, seed_mask, edge_ids):
    return sample_neighbors(
        indptr, indices, seeds, K, key,
        seed_mask=seed_mask if masked else None,
        edge_ids=edge_ids if with_eids else None, replace=replace)
  return jax.jit(fn)


def _hop_inputs(name, with_eids):
  degrees, seeds, seed_mask = FRONTIERS[name]
  indptr, indices = _csr(degrees)
  # ids that are not slots: a shuffled multiple of 3
  eids = (np.random.default_rng(1).permutation(indices.shape[0]) * 3
          ).astype(np.int32) if with_eids else None
  return indptr, indices, seeds.astype(np.int32), seed_mask, eids


@pytest.mark.parametrize('with_eids', [False, True],
                         ids=['slots', 'edge_ids'])
@pytest.mark.parametrize('replace', [False, True],
                         ids=['no_replace', 'replace'])
@pytest.mark.parametrize('name', list(FRONTIERS))
def test_one_hop(name, replace, with_eids):
  indptr, indices, seeds, seed_mask, eids = _hop_inputs(name, with_eids)
  mask_arg = (jnp.asarray(seed_mask) if seed_mask is not None
              else jnp.ones(seeds.shape, bool))
  eid_arg = jnp.asarray(eids if eids is not None
                        else np.zeros_like(indices))
  out = _one_hop_fn(replace, with_eids, seed_mask is not None)(
      jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(seeds),
      jax.random.key(3), mask_arg, eid_arg)
  assert out.nbrs.shape == (seeds.shape[0], K)
  g = EdgeTable.from_csr(indptr, indices, eids)
  check_hop(g, seeds, K, out.nbrs, out.mask, out.eids,
            seed_mask=seed_mask, replace=replace, in_order=True)
  if seeds.shape[0] == 0:
    assert int(out.nbrs_num.sum()) == 0


def test_one_hop_eager_matches_jit():
  indptr, indices, seeds, _, eids = _hop_inputs('mixed_degrees', True)
  args = (jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(seeds),
          K, jax.random.key(5))
  eager = sample_neighbors(*args, edge_ids=jnp.asarray(eids))
  jitted = jax.jit(
      lambda: sample_neighbors(*args, edge_ids=jnp.asarray(eids)))()
  check_hop(EdgeTable.from_csr(indptr, indices, eids), seeds, K,
            eager.nbrs, eager.mask, eager.eids, in_order=True)
  m = np.asarray(eager.mask)
  np.testing.assert_array_equal(m, np.asarray(jitted.mask))
  np.testing.assert_array_equal(np.asarray(eager.nbrs)[m],
                                np.asarray(jitted.nbrs)[m])
  np.testing.assert_array_equal(np.asarray(eager.eids)[m],
                                np.asarray(jitted.eids)[m])


def test_one_hop_empty_graph():
  out = sample_neighbors(jnp.zeros((5,), jnp.int32),
                         jnp.zeros((0,), jnp.int32),
                         jnp.arange(4, dtype=jnp.int32), K,
                         jax.random.key(0))
  assert out.nbrs.shape == (4, K) and not np.asarray(out.mask).any()
  assert (np.asarray(out.eids) == -1).all()


# -- multi-hop ------------------------------------------------------------

def _hub_graph(n=64, e=600, seed=0):
  rng = np.random.default_rng(seed)
  src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
  src[:140] = 5                         # one hub row above 96
  t = Topology(edge_index=np.stack([src, dst]), num_nodes=n)
  return (t.indptr.astype(np.int32), np.asarray(t.indices),
          np.arange(e, dtype=np.int32) * 3 + 1)


def _ring_graph(n=30):
  v = np.arange(n)
  indptr = (2 * np.arange(n + 1)).astype(np.int32)
  indices = np.stack([(v + 1) % n, (v + 2) % n], 1).reshape(-1)
  return indptr, indices.astype(np.int32), \
      np.arange(2 * n, dtype=np.int32)[::-1].copy()


def _star_graph(n=40, sinks=10):
  """Every node below ``n - sinks`` points at nodes 0, 1 and 2 (self
  loops among them); the last ``sinks`` nodes have no edge."""
  src = np.repeat(np.arange(n - sinks), 3)
  dst = np.tile(np.arange(3), n - sinks)
  t = Topology(edge_index=np.stack([src, dst]), num_nodes=n)
  return (t.indptr.astype(np.int32), np.asarray(t.indices),
          np.arange(src.shape[0], dtype=np.int32) * 5 + 2)


MULTIHOP = {
    # name: (graph, seeds, n_valid)
    'hub_graph': (_hub_graph, np.array([5, 0, 5, 17, 63, 2, 2, 9]), 7),
    'ring_duplicate_seeds': (_ring_graph,
                             np.array([3, 3, 29, 0, 3, 29, 12, 12]), 8),
    'n_valid_zero': (_hub_graph, np.array([1, 2, 3, 4, 5, 6, 7, 8]), 0),
    # live seeds without an edge: every hop's frontier is empty
    'isolated_seeds': (_star_graph, np.arange(30, 38), 8),
    # every child of hop 0 is a seed: the later hops find nothing new
    'children_all_seen': (_star_graph, np.array([0, 1, 2, 5, 6, 7, 8, 9]),
                          8),
    # one live slot, the hub: the later hops dedup its fan-out
    'one_live_hub_seed': (_hub_graph, np.array([5, 5, 0, 1, 2, 3, 4, 6]),
                          1),
}


def _multihop(graph, seeds, n_valid, fanouts, with_edge, key,
              replace=False, many=False):
  indptr, indices, eids = (jnp.asarray(a) for a in graph)
  one_hop = lambda ids, f, k, m: sample_neighbors(
      indptr, indices, ids, f, k, seed_mask=m,
      edge_ids=eids if with_edge else None, replace=replace)
  loop = multihop_sample_many if many else multihop_sample
  fn = jax.jit(lambda s, nv, k: loop(one_hop, s, nv, fanouts, k,
                                     with_edge=with_edge))
  out = fn(jnp.asarray(seeds, jnp.int32), jnp.asarray(n_valid, jnp.int32),
           key)
  return jax.tree.map(np.asarray, out)


@pytest.mark.parametrize('with_edge', [False, True],
                         ids=['no_edge', 'with_edge'])
@pytest.mark.parametrize('fanouts', [(3,), (3, 2), (4, 3, 2)],
                         ids=['f3', 'f3_2', 'f4_3_2'])
@pytest.mark.parametrize('name', list(MULTIHOP))
def test_multihop_sort_fused(name, fanouts, with_edge):
  make, seeds, n_valid = MULTIHOP[name]
  graph = make()
  out = _multihop(graph, seeds, n_valid, fanouts, with_edge,
                  jax.random.key(0))
  g = EdgeTable.from_csr(*graph)
  # the seed hop is exact (slot order); later hops hand new labels out
  # in value order
  check_multihop(g, seeds, n_valid, fanouts, out,
                 new_label_order='value')
  if n_valid == 0:
    assert int(out['node_count']) == 0
    assert not out['edge_mask'].any()
  if name == 'isolated_seeds':
    assert int(out['node_count']) == 8 and not out['edge_mask'].any()
  if name == 'children_all_seen':
    np.testing.assert_array_equal(out['num_sampled_nodes'][1:], 0)


REPLACE_CASES = {
    # name: (graph, seeds, n_valid)
    'distinct_seeds': (functools.partial(_hub_graph, seed=5),
                       np.array([1, 2, 3, 4]), 4),
    # the hub's repeats, a ragged tail: picks of one row repeat children
    'hub_seed_repeated_ragged': (functools.partial(_hub_graph, seed=5),
                                 np.array([5, 5, 0, 5]), 3),
    # degree 2 under fanout 4: every row draws its two edges again
    'rows_below_the_fanout': (_ring_graph, np.array([0, 7, 7, 20]), 4),
}


@pytest.mark.parametrize('name', list(REPLACE_CASES))
def test_multihop_with_replacement(name):
  make, seeds, n_valid = REPLACE_CASES[name]
  graph = make()
  out = _multihop(graph, seeds, n_valid, (4, 2), True, jax.random.key(2),
                  replace=True)
  check_multihop(EdgeTable.from_csr(*graph), seeds, n_valid, (4, 2), out,
                 replace=True, new_label_order='value')


def test_multihop_many_sort_fused():
  graph = _hub_graph(seed=7)
  seeds = np.array([[1, 2, 3, 4], [9, 9, 10, 11], [5, 0, 63, 5]])
  n_valid = np.array([4, 3, 4])
  outs = _multihop(graph, seeds, n_valid, (3, 2), True,
                   jax.random.key(4), many=True)
  g = EdgeTable.from_csr(*graph)
  for i in range(seeds.shape[0]):
    check_multihop(g, seeds[i], n_valid[i], (3, 2),
                   {k: v[i] for k, v in outs.items()},
                   new_label_order='value')


# -- weighted and full-neighbourhood hops --------------------------------

def _variable_degree_edges(n=30, seed=11):
  """Degrees 0..6, no self loops, no parallel edges: short rows read
  past their end in a fixed window."""
  rng = np.random.default_rng(seed)
  edges = set()
  for v in range(n):
    for w in rng.choice(n, int(rng.integers(0, 7)), replace=False):
      if int(w) != v:
        edges.add((v, int(w)))
  ei = np.array(sorted(edges)).T
  # weights 0..4: a fifth of the edges can never be drawn
  return n, ei, (np.arange(ei.shape[1]) % 5).astype(np.float32)


def test_full_neighbour_hop_returns_rows_whole_and_in_order():
  n, ei, _ = _variable_degree_edges()
  t = Topology(edge_index=ei, num_nodes=n)
  eids = np.arange(ei.shape[1], dtype=np.int32) * 2
  seeds = np.arange(0, n, 2, dtype=np.int32)
  smask = np.arange(seeds.shape[0]) % 4 != 3
  out = sample_full_neighbors(
      jnp.asarray(t.indptr.astype(np.int32)), jnp.asarray(t.indices),
      jnp.asarray(seeds), 8, seed_mask=jnp.asarray(smask),
      edge_ids=jnp.asarray(eids))
  check_hop(EdgeTable.from_csr(t.indptr, t.indices, eids), seeds, -8,
            out.nbrs, out.mask, out.eids, seed_mask=smask, in_order=True)


def test_full_neighbour_hop_truncates_at_the_window():
  indptr, indices = _csr(MIXED)
  seeds = np.arange(len(MIXED), dtype=np.int32)
  out = sample_full_neighbors(jnp.asarray(indptr), jnp.asarray(indices),
                              jnp.asarray(seeds), 16)
  g = EdgeTable.from_csr(indptr, indices)
  check_hop(g, seeds, -16, out.nbrs, out.mask, out.eids, in_order=True)
  # rows above the window: the first 16 edges, in order
  for s in np.nonzero(MIXED > 16)[0]:
    np.testing.assert_array_equal(
        np.asarray(out.eids)[s], indptr[s] + np.arange(16))


@pytest.mark.parametrize('with_eids', [False, True],
                         ids=['slots', 'edge_ids'])
def test_weighted_hop_picks_lie_in_the_row_and_skip_zero_weights(
    with_eids):
  n, ei, w = _variable_degree_edges()
  t = Topology(edge_index=ei, edge_weights=w, num_nodes=n)
  eids = (np.arange(ei.shape[1], dtype=np.int32) * 7
          if with_eids else None)
  seeds = np.arange(n, dtype=np.int32)
  out = sample_neighbors_weighted(
      jnp.asarray(t.indptr.astype(np.int32)), jnp.asarray(t.indices),
      jnp.asarray(t.edge_weights), jnp.asarray(seeds), 3,
      jax.random.key(7), max_degree=8,
      edge_ids=None if eids is None else jnp.asarray(eids))
  g = EdgeTable.from_csr(t.indptr, t.indices, eids,
                         weights=t.edge_weights)
  check_hop(g, seeds, 3, out.nbrs, out.mask, out.eids, weighted=True)


def _sampler_output(out):
  return {k: (None if getattr(out, k) is None
              else np.asarray(getattr(out, k)))
          for k in ('node', 'node_count', 'row', 'col', 'edge_mask',
                    'edge', 'batch', 'num_sampled_nodes',
                    'num_sampled_edges')} | {
      'seed_labels': np.asarray(out.metadata['seed_labels'])}


@pytest.mark.parametrize('with_edge', [False, True],
                         ids=['no_edge', 'with_edge'])
@pytest.mark.parametrize('case', ['full_variable', 'weighted_variable',
                                  'full_ring', 'weighted_ring'])
def test_sampler_weighted_and_full_neighbourhood(case, with_edge):
  """The graphs and fanouts of the deleted window-DMA parity tests,
  through NeighborSampler, with edge ids and without."""
  from glt_tpu.sampler import NeighborSampler
  weighted = case.startswith('weighted')
  if case.endswith('variable'):
    n, ei, w = _variable_degree_edges()
    w = w + 1 if not weighted else w
    ds = Dataset(edge_dir='out')
    ds.init_graph(edge_index=ei, num_nodes=n, edge_weights=w)
    g = EdgeTable(ei[0], ei[1], weights=w)
    seeds = np.arange(0, n, 4)
    fanouts = [3] if weighted else [-1, -1]
  else:
    n = 30 if weighted else 24
    ds = ring_dataset(num_nodes=n, weighted=weighted)
    rows, cols, eids = ring_edges(n)
    g = EdgeTable(rows, cols, eids,
                  weights=(eids % 7 + 1) if weighted else None)
    seeds = np.arange(0, 30, 3) if weighted else np.array([0, 7, 13])
    fanouts = [2, 2] if weighted else [-1, -1]
  s = NeighborSampler(ds.get_graph(), fanouts, with_edge=with_edge,
                      with_weight=weighted, seed=9)
  out = s.sample_from_nodes(seeds, key=jax.random.key(3))
  internal = [f if f > 0 else -ds.get_graph().topo.max_degree
              for f in fanouts]
  got = _sampler_output(out)
  assert (got['edge'] is None) == (not with_edge)
  check_multihop(g, seeds, seeds.shape[0], internal, got,
                 weighted=weighted, new_label_order='value')
  # the static prefix a model trims its nodes by: seeds, then each
  # hop's lane capacity
  want, cap = [seeds.shape[0]], seeds.shape[0]
  for k in internal:
    cap *= abs(k)
    want.append(want[-1] + cap)
  assert list(out.node_hop_offsets) == want
  # and the promise of parent-major slots the oracle held the batch to
  assert out.hop_fanouts == tuple(abs(k) for k in internal)


# -- row gathers ---------------------------------------------------------

def test_feature_device_gather_serves_table_rows():
  """``Feature.device_gather`` is ``table[ids]`` over the resident
  rows. (-1 and out-of-range requests: tests/test_serving.py and
  test_hbm_spill.py hold ``gather_features``'s zero rows; the sharded
  stores' are below.)"""
  from glt_tpu.data import Feature
  rng = np.random.default_rng(0)
  table = rng.normal(size=(64, 128)).astype(np.float32)
  feat = Feature(table)
  ids = rng.integers(0, 64, 16)
  np.testing.assert_array_equal(
      np.asarray(feat.device_gather(jnp.asarray(ids))), table[ids])


@pytest.mark.parametrize('shards', [1, 4])
def test_sharded_feature_lookup_serves_table_rows_and_zero_rows(shards):
  from glt_tpu.parallel import ShardedFeature, make_mesh
  mesh = make_mesh(shards)
  rng = np.random.default_rng(1)
  table = rng.normal(size=(40, 16)).astype(np.float32)
  sf = ShardedFeature(table, mesh)
  ids = np.array([0, 39, -1, 7, 40, 12, 12, 1000] * shards, np.int32)
  got = np.asarray(sf.lookup(jnp.asarray(ids)))
  ok = (ids >= 0) & (ids < 40)
  want = np.where(ok[:, None], table[np.clip(ids, 0, 39)], 0.0)
  np.testing.assert_array_equal(got, want)


# -- compile discipline ---------------------------------------------------

def test_neighbor_sampler_one_program_a_batch_shape():
  from glt_tpu.sampler import NeighborSampler
  ds = ring_dataset(num_nodes=40)
  samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0, with_edge=True)
  rows, cols, eids = ring_edges(40)
  g = EdgeTable(rows, cols, eids)
  out4 = samp.sample_from_nodes(np.arange(4))
  out8 = samp.sample_from_nodes(np.arange(8))
  assert samp.num_compiled_fns == 2
  for _ in range(3):       # steady state: no further program
    samp.sample_from_nodes(np.arange(8))
    samp.sample_from_nodes(np.arange(4))
  assert samp.num_compiled_fns == 2
  check_multihop(g, np.arange(4), 4, [3, 2], _sampler_output(out4),
                 new_label_order='value')
  check_multihop(g, np.arange(8), 8, [3, 2], _sampler_output(out8),
                 new_label_order='value')


def test_stream_sampler_no_retrace_across_refresh_and_swap():
  from glt_tpu.stream import (EdgeDeltaBuffer, SnapshotManager,
                              StreamSampler)
  n = 24
  ds = ring_dataset(num_nodes=n)
  mgr = SnapshotManager(ds.get_graph().topo, ds.get_node_feature(),
                        delta_capacity=64)
  seeds = np.arange(6)
  samp = StreamSampler(mgr, [3, 2], seed=0)
  out = samp.sample_from_nodes(seeds)
  rows, cols, _ = ring_edges(n)
  out_d = _sampler_output(out)
  out_d['edge'] = None
  check_multihop(EdgeTable(rows, cols), seeds, 6, [3, 2], out_d,
                 widths=samp.num_neighbors)
  buf = EdgeDeltaBuffer(capacity=16, num_nodes=n)
  buf.insert_edges([1, 2], [5, 6])
  samp.refresh_overlay(buf)
  traces, fns = samp.trace_count, samp.num_compiled_fns
  for _ in range(3):
    samp.sample_from_nodes(seeds)
  mgr.compact(buf.drain())              # swap: same static shapes
  samp.clear_overlay()
  after = samp.sample_from_nodes(seeds)
  assert samp.trace_count == traces
  assert samp.num_compiled_fns == fns
  # the compacted snapshot holds the two inserted edges
  out_d = _sampler_output(after)
  out_d['edge'] = None
  check_multihop(
      EdgeTable(np.concatenate([rows, [1, 2]]),
                np.concatenate([cols, [5, 6]])), seeds, 6, [3, 2], out_d,
      widths=samp.num_neighbors)


# -- the oracle itself ----------------------------------------------------

def _good_batch():
  graph = _ring_graph()
  seeds = np.array([3, 3, 29, 0])
  out = {k: np.array(v) for k, v in _multihop(
      graph, seeds, 4, (2, 2), True, jax.random.key(1)).items()}
  return EdgeTable.from_csr(*graph), seeds, out


def _break_child(out):
  out['node'][int(out['row'][0])] = 17      # (3, 17) is no ring edge


def _break_distinct(out):
  out['row'][1], out['edge'][1] = out['row'][0], out['edge'][0]


def _break_masked_lane(out):
  lane = int(np.nonzero(~out['edge_mask'])[0][0])
  out['row'][lane] = 0


def _break_count(out):
  out['num_sampled_nodes'][1] += 1


def _break_seed_label(out):
  out['seed_labels'][1] = 1                  # duplicate seed, own label


def _break_duplicate_node(out):
  out['node'][int(out['node_count']) - 1] = out['node'][0]


def _live_lanes_by_parent(out):
  m, c = out['edge_mask'].astype(bool), out['col']
  return {int(p): np.nonzero(m & (c == p))[0] for p in np.unique(c[m])}


def _break_group(out):
  """Two lanes of different parents change places: every parent keeps
  its picks, ``col`` is no longer constant over a group."""
  (_, a), (_, b) = list(_live_lanes_by_parent(out).items())[:2]
  for k in ('row', 'col', 'edge'):
    out[k][a[0]], out[k][b[0]] = out[k][b[0]], out[k][a[0]]


def _break_one_head(out):
  """A parent's picks move into a wholly masked group of its hop: the
  label then heads two groups with a live lane."""
  m = out['edge_mask'].astype(bool)
  lanes = next(v for v in _live_lanes_by_parent(out).values()
               if v.shape[0] == 2 and v[0] < 8)   # hop 0, fanout 2
  dead = next(g for g in range(0, 8, 2) if not m[g:g + 2].any())
  for k in ('row', 'col', 'edge', 'edge_mask'):
    out[k][dead], out[k][lanes[1]] = out[k][lanes[1]], out[k][dead]
  out['col'][dead + 1] = out['col'][dead]


@pytest.mark.parametrize('breakage', [
    _break_child, _break_distinct, _break_masked_lane, _break_count,
    _break_seed_label, _break_duplicate_node, _break_group,
    _break_one_head],
    ids=lambda f: f.__name__[len('_break_'):])
def test_oracle_refuses_a_broken_batch(breakage):
  g, seeds, out = _good_batch()
  check_multihop(g, seeds, 4, (2, 2), out, new_label_order='value')
  breakage(out)
  with pytest.raises(AssertionError):
    check_multihop(g, seeds, 4, (2, 2), out, new_label_order='value')
