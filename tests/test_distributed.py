"""Distributed (sharded-topology) tests on the 8-device CPU mesh:
partition on disk -> DistDataset load -> DistGraph/DistFeature ->
DistNeighborSampler, asserting exactness against the ring fixture —
the reference's dist test strategy (SURVEY.md §4) without processes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glt_tpu.data import Dataset
from glt_tpu.distributed import (
    DistDataset, DistFeature, DistGraph, DistNeighborSampler,
)
from glt_tpu.parallel import make_mesh
from glt_tpu.partition import RandomPartitioner

from fixtures import ring_edges
from test_parallel import (CHUNK, CHUNK_CASES, bits, chunk_case,
                           chunks_with_a_valid_slot)

N_NODES = 40
N_PARTS = 8


@pytest.fixture(scope='module')
def part_dir(tmp_path_factory):
  root = tmp_path_factory.mktemp('parts')
  rows, cols, eids = ring_edges(N_NODES)
  feats = np.tile(np.arange(N_NODES, dtype=np.float32)[:, None], (1, 8))
  p = RandomPartitioner(str(root), num_parts=N_PARTS, num_nodes=N_NODES,
                        edge_index=np.stack([rows, cols]),
                        node_feat=feats, edge_assign_strategy='by_src')
  p.partition()
  return str(root)


@pytest.fixture(scope='module')
def mesh():
  return make_mesh(N_PARTS)


@pytest.fixture(scope='module')
def dist_datasets(part_dir):
  return [DistDataset().load(part_dir, p) for p in range(N_PARTS)]


def test_dist_dataset_load(dist_datasets):
  ds = dist_datasets[0]
  assert ds.num_partitions == N_PARTS
  g = ds.get_graph()
  # every edge's src is owned by partition 0
  src, _, _ = g.topo.to_coo()
  # local graph stores global ids on the pointer axis? (it stores the
  # partition's edges with original ids)
  feat = ds.get_node_feature()
  owned = np.nonzero(ds.node_pb.table == 0)[0]
  looked = feat[owned]
  np.testing.assert_allclose(looked[:, 0], owned)


def test_dist_graph_shapes(mesh, part_dir):
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  assert dg.num_partitions == N_PARTS
  assert dg.indptr.shape[0] == N_PARTS
  # node_pb covers every node
  pb = np.asarray(dg.node_pb)
  assert pb.shape == (N_NODES,)
  assert set(pb.tolist()) <= set(range(N_PARTS))


def test_dist_feature_lookup(mesh, dist_datasets):
  df = DistFeature.from_dist_datasets(mesh, dist_datasets)
  rng = np.random.default_rng(0)
  ids = rng.integers(0, N_NODES, N_PARTS * 16)
  out = np.asarray(df.lookup(ids))
  np.testing.assert_allclose(out[:, 0], ids)


def test_dist_sampler_one_hop_exact(mesh, part_dir):
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  s = DistNeighborSampler(dg, [2], seed=0)
  # each device seeds two nodes: device p seeds {p, p+8}
  seeds = np.stack([np.arange(N_PARTS), np.arange(N_PARTS) + 8], 1)
  out = s.sample_from_nodes(seeds)
  nodes = np.asarray(out['node'])        # [P, budget]
  counts = np.asarray(out['node_count'])
  for p in range(N_PARTS):
    got = set(nodes[p][:counts[p]].tolist())
    expect = {p, p + 8}
    for v in (p, p + 8):
      expect |= {(v + 1) % N_NODES, (v + 2) % N_NODES}
    assert got == expect, f'device {p}: {got} != {expect}'
    # edges obey ring relation
    em = np.asarray(out['edge_mask'])[p]
    child = nodes[p][np.asarray(out['row'])[p][em]]
    parent = nodes[p][np.asarray(out['col'])[p][em]]
    for pp, cc in zip(parent, child):
      assert cc in ((pp + 1) % N_NODES, (pp + 2) % N_NODES)


def test_dist_sampler_two_hops(mesh, part_dir):
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  s = DistNeighborSampler(dg, [2, 2], seed=1)
  seeds = np.arange(N_PARTS)[:, None]    # one seed per device
  out = s.sample_from_nodes(seeds)
  nodes = np.asarray(out['node'])
  counts = np.asarray(out['node_count'])
  for p in range(N_PARTS):
    got = set(nodes[p][:counts[p]].tolist())
    expect = {p, (p+1) % N_NODES, (p+2) % N_NODES, (p+3) % N_NODES,
              (p+4) % N_NODES}
    assert got == expect


def test_dist_sampler_edge_ids(mesh, part_dir):
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  s = DistNeighborSampler(dg, [2], with_edge=True, seed=2)
  seeds = np.arange(N_PARTS)[:, None]
  out = s.sample_from_nodes(seeds)
  for p in range(N_PARTS):
    em = np.asarray(out['edge_mask'])[p]
    eids = np.asarray(out['edge'])[p][em]
    # node p's out-edges have eids {2p, 2p+1}
    assert set(eids.tolist()) == {2 * p, 2 * p + 1}


def test_dist_loader_and_train_step(mesh, part_dir, dist_datasets):
  import optax
  from glt_tpu.distributed import DistNeighborLoader, DistTrainStep
  from glt_tpu.models import GraphSAGE
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  df = DistFeature.from_dist_datasets(mesh, dist_datasets)
  labels = (np.arange(N_NODES) % 4).astype(np.int32)

  # loader round: each device iterates its own partition's nodes
  per_dev = [np.nonzero(np.asarray(dg.node_pb) == p)[0]
             for p in range(N_PARTS)]
  loader = DistNeighborLoader(dg, [2], input_nodes=per_dev,
                              dist_feature=df, labels=labels,
                              batch_size=2, seed=0)
  b = next(iter(loader))
  nodes = np.asarray(b['node'])
  x = np.asarray(b['x'])
  counts = np.asarray(b['node_count'])
  for p in range(N_PARTS):
    nc = counts[p]
    np.testing.assert_allclose(x[p][:nc, 0], nodes[p][:nc])

  # one-program train step learns on the ring task
  model = GraphSAGE(hidden_features=16, out_features=4, num_layers=1)
  tx = optax.adam(1e-2)
  step = DistTrainStep(dg, df, model, tx, labels, fanouts=[2],
                       batch_size_per_device=4)
  params = step.init_params(jax.random.key(0))
  opt_state = tx.init(params)
  rng = np.random.default_rng(0)
  losses = []
  for it in range(40):
    seeds = np.stack([rng.choice(per_dev[p] if len(per_dev[p]) >= 4
                                 else np.arange(N_NODES), 4)
                      for p in range(N_PARTS)])
    params, opt_state, loss = step(params, opt_state, seeds,
                                   np.full(N_PARTS, 4),
                                   jax.random.key(it))
    losses.append(float(np.asarray(loss)[0]))
  assert losses[-1] < losses[0], f'no learning: {losses[::8]}'


def test_dist_hetero_sampler(tmp_path_factory, mesh):
  from glt_tpu.distributed import DistHeteroGraph, DistHeteroNeighborSampler
  # partition the hetero user/item fixture to disk
  root = str(tmp_path_factory.mktemp('hetero_parts'))
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  nu, ni = 16, 32
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2*u, 2*u+1], 1).reshape(-1) % ni])
  i = np.arange(ni)
  i2i_ei = np.stack([np.repeat(i, 2),
                     np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  RandomPartitioner(root, num_parts=N_PARTS,
                    num_nodes={'user': nu, 'item': ni},
                    edge_index={u2i: u2i_ei, i2i: i2i_ei}).partition()

  dg = DistHeteroGraph.from_dataset_partitions(mesh, root)
  s = DistHeteroNeighborSampler(dg, {u2i: [2, 2], i2i: [2, 2]}, seed=0)
  seeds = (np.arange(N_PARTS) % nu)[:, None]   # one user per device
  out = s.sample_from_nodes('user', seeds)
  items = np.asarray(out['node']['item'])
  users = np.asarray(out['node']['user'])
  icount = np.asarray(out['node_count']['item'])
  for p in range(N_PARTS):
    uu = p % nu
    np.testing.assert_array_equal(
        users[p][:int(np.asarray(out['node_count']['user'])[p])], [uu])
    # hop1 items {2u, 2u+1}; hop2 via i2i: +1, +2 of those
    expect = {2*uu % ni, (2*uu+1) % ni}
    for v in list(expect):
      expect |= {(v+1) % ni, (v+2) % ni}
    got = set(items[p][:icount[p]].tolist())
    assert got == expect, f'dev {p}: {got} != {expect}'
  # reversed etype keys present
  assert ('item', 'rev_u2i', 'user') in out['row']


def test_dist_hetero_multihost_builder_parity(tmp_path_factory, mesh):
  # single-process path of the multihost hetero builder must produce a
  # store whose sampling matches from_dataset_partitions exactly
  from glt_tpu.distributed import (
      DistHeteroGraph, DistHeteroNeighborSampler,
      dist_hetero_graph_from_partitions_multihost,
  )
  root = str(tmp_path_factory.mktemp('hetero_mh_parts'))
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  nu, ni = 16, 32
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2*u, 2*u+1], 1).reshape(-1) % ni])
  i = np.arange(ni)
  i2i_ei = np.stack([np.repeat(i, 2),
                     np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  RandomPartitioner(root, num_parts=N_PARTS,
                    num_nodes={'user': nu, 'item': ni},
                    edge_index={u2i: u2i_ei, i2i: i2i_ei}).partition()
  ref = DistHeteroGraph.from_dataset_partitions(mesh, root)
  got = dist_hetero_graph_from_partitions_multihost(mesh, root)
  assert got.node_counts == ref.node_counts
  for e in ref.graphs:
    a, b = ref.graphs[e], got.graphs[e]
    assert (a.max_rows, a.max_edges, a.max_degree) == \
        (b.max_rows, b.max_edges, b.max_degree), e
    np.testing.assert_array_equal(np.asarray(a.indptr),
                                  np.asarray(b.indptr), str(e))
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices), str(e))
    np.testing.assert_array_equal(np.asarray(a.local_row),
                                  np.asarray(b.local_row), str(e))
  seeds = (np.arange(N_PARTS) % nu)[:, None]
  out_a = DistHeteroNeighborSampler(
      ref, {u2i: [2, 2], i2i: [2, 2]}, seed=0).sample_from_nodes(
          'user', seeds, key=jax.random.key(3))
  out_b = DistHeteroNeighborSampler(
      got, {u2i: [2, 2], i2i: [2, 2]}, seed=0).sample_from_nodes(
          'user', seeds, key=jax.random.key(3))
  for t in out_a['node']:
    np.testing.assert_array_equal(np.asarray(out_a['node'][t]),
                                  np.asarray(out_b['node'][t]), t)


def test_dist_hetero_train_step(tmp_path_factory, mesh):
  import optax
  from glt_tpu.distributed import (
      DistDataset, DistFeature, DistHeteroGraph, DistHeteroTrainStep,
  )
  from glt_tpu.models import RGNN
  from glt_tpu.typing import reverse_edge_type
  root = str(tmp_path_factory.mktemp('hetero_train'))
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  nu, ni = 16, 32
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2*u, 2*u+1], 1).reshape(-1) % ni])
  i = np.arange(ni)
  i2i_ei = np.stack([np.repeat(i, 2),
                     np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  w = max(nu, ni)
  feats = {'user': np.pad(np.eye(nu, dtype=np.float32),
                          ((0, 0), (0, w - nu))),
           'item': np.pad(np.eye(ni, dtype=np.float32),
                          ((0, 0), (0, w - ni)))}
  RandomPartitioner(root, num_parts=N_PARTS,
                    num_nodes={'user': nu, 'item': ni},
                    edge_index={u2i: u2i_ei, i2i: i2i_ei},
                    node_feat=feats).partition()
  dg = DistHeteroGraph.from_dataset_partitions(mesh, root)
  dss = [DistDataset().load(root, p) for p in range(N_PARTS)]
  dfeats = {t: DistFeature.from_dist_datasets(mesh, dss, ntype=t)
            for t in ('user', 'item')}
  labels = {'user': (np.arange(nu) % 3).astype(np.int32)}
  model = RGNN(edge_types=[reverse_edge_type(u2i), i2i],
               hidden_features=16, out_features=3, num_layers=2,
               conv='rsage')
  tx = optax.adam(1e-2)
  step = DistHeteroTrainStep(dg, dfeats, model, tx, labels,
                             {u2i: [2, 2], i2i: [2, 2]},
                             batch_size_per_device=2, seed_type='user',
                             seed=0)
  params = step.init_params(jax.random.key(0))
  opt = tx.init(params)
  rng = np.random.default_rng(0)
  losses = []
  for it in range(30):
    seeds = rng.integers(0, nu, (N_PARTS, 2))
    params, opt, loss = step(params, opt, seeds, np.full(N_PARTS, 2),
                             jax.random.key(it))
    losses.append(float(np.asarray(loss)[0]))
  assert losses[-1] < losses[0], f'no learning: {losses[::6]}'


def test_dist_hetero_train_superstep(tmp_path_factory, mesh):
  """K hetero train batches in ONE donated dispatch (ISSUE 14
  tentpole, program half): superstep loss trajectory bit-identical to
  K sequential per-batch calls on the same key stream, with zero
  steady-state recompiles across repeated supersteps of the same T."""
  import optax
  from glt_tpu.distributed import (
      DistDataset, DistFeature, DistHeteroGraph, DistHeteroTrainStep,
  )
  from glt_tpu.models import RGNN
  from glt_tpu.typing import reverse_edge_type
  root = str(tmp_path_factory.mktemp('hetero_superstep'))
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  nu, ni = 16, 32
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2*u, 2*u+1], 1).reshape(-1) % ni])
  i = np.arange(ni)
  i2i_ei = np.stack([np.repeat(i, 2),
                     np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  w = max(nu, ni)
  feats = {'user': np.pad(np.eye(nu, dtype=np.float32),
                          ((0, 0), (0, w - nu))),
           'item': np.pad(np.eye(ni, dtype=np.float32),
                          ((0, 0), (0, w - ni)))}
  RandomPartitioner(root, num_parts=N_PARTS,
                    num_nodes={'user': nu, 'item': ni},
                    edge_index={u2i: u2i_ei, i2i: i2i_ei},
                    node_feat=feats).partition()
  dg = DistHeteroGraph.from_dataset_partitions(mesh, root)
  dss = [DistDataset().load(root, p) for p in range(N_PARTS)]
  dfeats = {t: DistFeature.from_dist_datasets(mesh, dss, ntype=t)
            for t in ('user', 'item')}
  labels = {'user': (np.arange(nu) % 3).astype(np.int32)}
  # 1 hop / 1 layer: the parity + zero-recompile claims are about the
  # scan lift, not model depth — tier-1 time budget matters here
  model = RGNN(edge_types=[reverse_edge_type(u2i), i2i],
               hidden_features=8, out_features=3, num_layers=1,
               conv='rsage')
  tx = optax.adam(1e-2)

  def build():
    return DistHeteroTrainStep(dg, dfeats, model, tx, labels,
                               {u2i: [2], i2i: [2]},
                               batch_size_per_device=2,
                               seed_type='user', seed=0)

  T = 2
  rng = np.random.default_rng(0)
  seeds = rng.integers(0, nu, (T, N_PARTS, 2))
  keys = jnp.stack([jax.random.split(jax.random.key(t), N_PARTS)
                    for t in range(T)])

  step = build()
  params = step.init_params(jax.random.key(0))
  opt = tx.init(params)
  seq = []
  for t in range(T):
    params, opt, loss = step(params, opt, seeds[t],
                             np.full(N_PARTS, 2), jax.random.key(t))
    seq.append(np.asarray(loss))

  step2 = build()
  params2 = step2.init_params(jax.random.key(0))
  opt2 = tx.init(params2)
  from glt_tpu.obs import get_registry
  compiles0 = get_registry().get('compiles_total',
                                 fn='train.hetero_superstep')
  params2, opt2, loss_ss = step2.superstep(
      params2, opt2, seeds.reshape(T, -1), np.full((T, N_PARTS), 2),
      keys)
  np.testing.assert_array_equal(np.asarray(loss_ss), np.stack(seq))
  assert step2.superstep_traces == 1
  compiles1 = get_registry().get('compiles_total',
                                 fn='train.hetero_superstep')
  assert compiles1 == compiles0 + 1
  params2, opt2, _ = step2.superstep(
      params2, opt2, seeds.reshape(T, -1), np.full((T, N_PARTS), 2),
      keys)
  assert step2.superstep_traces == 1  # steady state: zero recompiles
  # the process-wide counter agrees: one trace served both supersteps
  assert get_registry().get('compiles_total',
                            fn='train.hetero_superstep') == compiles1


def test_dist_weighted_sampling(tmp_path_factory, mesh):
  """Distributed weighted sampling: the dominant-weight edge is sampled
  nearly always (reference parity: weighted sampling works through the
  partitioned path)."""
  root = str(tmp_path_factory.mktemp('wparts'))
  rows, cols, eids = ring_edges(N_NODES)
  w = np.ones(2 * N_NODES, np.float32)
  w[eids % 2 == 0] = 1000.0   # the (v -> v+1) edge dominates
  RandomPartitioner(root, num_parts=N_PARTS, num_nodes=N_NODES,
                    edge_index=np.stack([rows, cols]),
                    edge_weights=w).partition()
  dg = DistGraph.from_dataset_partitions(mesh, root)
  assert dg.edge_weights is not None
  s = DistNeighborSampler(dg, [1], with_weight=True, seed=0)
  hits = total = 0
  for trial in range(12):
    seeds = ((np.arange(N_PARTS) + trial * N_PARTS) % N_NODES)[:, None]
    out = s.sample_from_nodes(seeds)
    nodes = np.asarray(out['node'])
    counts = np.asarray(out['node_count'])
    for p in range(N_PARTS):
      v = int(seeds[p, 0])
      got = set(nodes[p][:counts[p]].tolist()) - {v}
      if got:
        total += 1
        hits += int((v + 1) % N_NODES in got)
  assert total > 50
  assert hits / total > 0.95, f'{hits}/{total}'


def test_dist_hetero_weighted(tmp_path_factory, mesh):
  from glt_tpu.distributed import DistHeteroGraph, DistHeteroNeighborSampler
  root = str(tmp_path_factory.mktemp('hw'))
  i2i = ('item', 'i2i', 'item')
  ni = 32
  i = np.arange(ni)
  ei = np.stack([np.repeat(i, 2),
                 np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  w = np.ones(2 * ni, np.float32)
  w[::2] = 500.0    # (v -> v+1) dominates
  RandomPartitioner(root, num_parts=N_PARTS, num_nodes={'item': ni},
                    edge_index={i2i: ei},
                    edge_weights={i2i: w}).partition()
  dg = DistHeteroGraph.from_dataset_partitions(mesh, root)
  assert dg.graphs[i2i].edge_weights is not None
  s = DistHeteroNeighborSampler(dg, {i2i: [1]}, with_weight=True, seed=0)
  hits = total = 0
  for trial in range(10):
    seeds = ((np.arange(N_PARTS) + trial * N_PARTS) % ni)[:, None]
    out = s.sample_from_nodes('item', seeds)
    nodes = np.asarray(out['node']['item'])
    counts = np.asarray(out['node_count']['item'])
    for p in range(N_PARTS):
      v = int(seeds[p, 0])
      got = set(nodes[p][:counts[p]].tolist()) - {v}
      if got:
        total += 1
        hits += int((v + 1) % ni in got)
  assert total > 40 and hits / total > 0.9, f'{hits}/{total}'


def test_dist_link_neighbor_loader(mesh, part_dir, dist_datasets):
  from glt_tpu.distributed import DistLinkNeighborLoader
  from glt_tpu.sampler import NegativeSampling
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  df = DistFeature.from_dist_datasets(mesh, dist_datasets)
  # per-device edge pools: device p holds the ring edges of its nodes
  pools = []
  for p in range(N_PARTS):
    owned = np.nonzero(np.asarray(dg.node_pb) == p)[0]
    src = np.repeat(owned, 2)
    dst = np.concatenate([(owned + 1) % N_NODES,
                          (owned + 2) % N_NODES]).reshape(2, -1).T.reshape(-1)
    # interleave properly: for each v: (v+1), (v+2)
    dst = np.stack([(owned + 1) % N_NODES, (owned + 2) % N_NODES],
                   1).reshape(-1)
    pools.append(np.stack([src, dst]))
  loader = DistLinkNeighborLoader(
      dg, [2], pools, dist_feature=df,
      neg_sampling=NegativeSampling('binary', amount=1),
      batch_size=4, seed=0)
  batches = list(loader)
  assert len(batches) >= 2
  b = batches[0]
  eli = np.asarray(b['edge_label_index'])      # [P, 2, 8]
  nodes = np.asarray(b['node'])
  for p in range(N_PARTS):
    n_pos = int(np.asarray(b['n_pos'])[p])
    src = nodes[p][eli[p, 0, :n_pos]]
    dst = nodes[p][eli[p, 1, :n_pos]]
    for u, v in zip(src, dst):
      assert v in ((u + 1) % N_NODES, (u + 2) % N_NODES)
    # labels: first batch_size are positives
    lab = np.asarray(b['edge_label'])[p]
    np.testing.assert_array_equal(lab[:4], 1.0)
    np.testing.assert_array_equal(lab[4:], 0.0)
  # features resolve for all sampled nodes
  x = np.asarray(b['x'])
  counts = np.asarray(b['node_count'])
  for p in range(N_PARTS):
    np.testing.assert_allclose(x[p][:counts[p], 0],
                               nodes[p][:counts[p]])


def test_dist_subgraph_loader(mesh, part_dir):
  from glt_tpu.distributed import DistSubGraphLoader
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  loader = DistSubGraphLoader(
      dg, num_hops=2, input_nodes_per_device=[
          np.array([p]) for p in range(N_PARTS)],
      max_degree=2, batch_size=1, seed=0)
  b = next(iter(loader))
  nodes = np.asarray(b['node'])
  counts = np.asarray(b['node_count'])
  for p in range(N_PARTS):
    got = set(nodes[p][:counts[p]].tolist())
    expect = {p, (p+1) % N_NODES, (p+2) % N_NODES, (p+3) % N_NODES,
              (p+4) % N_NODES}
    assert got == expect
    ind = b['induced'][p]
    # induced edges: every ring edge within the 2-hop set, each once
    pairs = {(int(nodes[p][r]), int(nodes[p][c]))
             for r, c in zip(ind['cols'], ind['rows'])}
    # (cols=parent? note: out row=child col=parent in dist outputs too)
    expect_edges = set()
    for v in expect:
      for d in (1, 2):
        if (v + d) % N_NODES in expect:
          expect_edges.add((v, (v + d) % N_NODES))
    assert pairs == expect_edges, (pairs, expect_edges)
    assert len(ind['eids']) == len(set(ind['eids'].tolist()))


def test_dist_strict_negative_sampling(mesh, part_dir):
  from glt_tpu.distributed import DistRandomNegativeSampler
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  s = DistRandomNegativeSampler(dg, trials_num=6, padding=False)
  rows, cols, mask = s.sample(32, key=jax.random.key(0))
  rows, cols, mask = map(np.asarray, (rows, cols, mask))
  assert mask.sum() > 100  # plenty of valid negatives on a sparse ring
  ring = {(v, (v + 1) % N_NODES) for v in range(N_NODES)} | \
         {(v, (v + 2) % N_NODES) for v in range(N_NODES)}
  for p in range(N_PARTS):
    for r, c in zip(rows[p][mask[p]], cols[p][mask[p]]):
      assert (int(r), int(c)) not in ring, (r, c)


def test_dist_strict_negative_rejects_on_dense_graph(tmp_path_factory,
                                                     mesh):
  # complete digraph: strict mode finds nothing without padding
  root = str(tmp_path_factory.mktemp('dense'))
  n = 8
  r, c = np.meshgrid(np.arange(n), np.arange(n), indexing='ij')
  RandomPartitioner(root, num_parts=N_PARTS, num_nodes=n,
                    edge_index=np.stack([r.reshape(-1), c.reshape(-1)])
                    ).partition()
  from glt_tpu.distributed import DistRandomNegativeSampler
  dg = DistGraph.from_dataset_partitions(mesh, root)
  s = DistRandomNegativeSampler(dg, trials_num=4, padding=False)
  _, _, mask = s.sample(16, key=jax.random.key(1))
  assert not np.asarray(mask).any()


def test_dist_link_loader_strict_negatives(mesh, part_dir, dist_datasets):
  from glt_tpu.distributed import DistLinkNeighborLoader
  from glt_tpu.sampler import NegativeSampling
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  pools = []
  for p in range(N_PARTS):
    owned = np.nonzero(np.asarray(dg.node_pb) == p)[0]
    src = np.repeat(owned, 2)
    dst = np.stack([(owned + 1) % N_NODES, (owned + 2) % N_NODES],
                   1).reshape(-1)
    pools.append(np.stack([src, dst]))
  loader = DistLinkNeighborLoader(
      dg, [2], pools,
      neg_sampling=NegativeSampling('binary', amount=1, strict=True),
      batch_size=4, seed=0)
  b = next(iter(loader))
  eli = np.asarray(b['edge_label_index'])
  nodes = np.asarray(b['node'])
  ring = {(v, (v + 1) % N_NODES) for v in range(N_NODES)} | \
         {(v, (v + 2) % N_NODES) for v in range(N_NODES)}
  for p in range(N_PARTS):
    neg_src = nodes[p][eli[p, 0, 4:]]
    neg_dst = nodes[p][eli[p, 1, 4:]]
    for u, v in zip(neg_src, neg_dst):
      assert (int(u), int(v)) not in ring


def test_dist_strict_triplet_negatives(mesh, part_dir):
  from glt_tpu.distributed import DistLinkNeighborLoader
  from glt_tpu.sampler import NegativeSampling
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  pools = []
  for p in range(N_PARTS):
    owned = np.nonzero(np.asarray(dg.node_pb) == p)[0]
    src = np.repeat(owned, 2)
    dst = np.stack([(owned + 1) % N_NODES, (owned + 2) % N_NODES],
                   1).reshape(-1)
    pools.append(np.stack([src, dst]))
  loader = DistLinkNeighborLoader(
      dg, [2], pools,
      neg_sampling=NegativeSampling('triplet', amount=2, strict=True),
      batch_size=4, seed=0)
  b = next(iter(loader))
  nodes = np.asarray(b['node'])
  si = np.asarray(b['src_index'])
  dni = np.asarray(b['dst_neg_index'])
  ring = {(v, (v + 1) % N_NODES) for v in range(N_NODES)} | \
         {(v, (v + 2) % N_NODES) for v in range(N_NODES)}
  for p in range(N_PARTS):
    srcs = nodes[p][si[p]]
    # the emitted (src, dst_neg) pairs themselves must be non-edges
    negs = nodes[p][dni[p].reshape(-1)].reshape(dni[p].shape)
    for i, s in enumerate(srcs):
      ds_ = negs[i] if negs.ndim == 2 else [negs[i]]
      for d in np.atleast_1d(ds_):
        assert (int(s), int(d)) not in ring, (s, d)


def test_dist_strict_negatives_reproducible(mesh, part_dir):
  from glt_tpu.distributed import DistLinkNeighborLoader
  from glt_tpu.sampler import NegativeSampling
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  pools = []
  for p in range(N_PARTS):
    owned = np.nonzero(np.asarray(dg.node_pb) == p)[0]
    src = np.repeat(owned, 2)
    dst = np.stack([(owned + 1) % N_NODES, (owned + 2) % N_NODES],
                   1).reshape(-1)
    pools.append(np.stack([src, dst]))
  def first_batch():
    loader = DistLinkNeighborLoader(
        dg, [2], pools,
        neg_sampling=NegativeSampling('binary', amount=1, strict=True),
        batch_size=4, seed=7)
    b = next(iter(loader))
    return np.asarray(b['node'])[np.arange(N_PARTS)[:, None],
                                 np.asarray(b['edge_label_index'])[:, 0]]
  np.testing.assert_array_equal(first_batch(), first_batch())


# -- distributed edge features (reference dist_neighbor_sampler.py:689-807,
# dist_feature.py:69-452 edge group) --------------------------------------

N_EDGES = 2 * N_NODES


@pytest.fixture(scope='module')
def part_dir_ef(tmp_path_factory):
  """Partitions with value-encoded edge features (row e == [e] * 4)."""
  root = tmp_path_factory.mktemp('parts_ef')
  rows, cols, eids = ring_edges(N_NODES)
  feats = np.tile(np.arange(N_NODES, dtype=np.float32)[:, None], (1, 8))
  efeats = np.tile(np.arange(N_EDGES, dtype=np.float32)[:, None], (1, 4))
  p = RandomPartitioner(str(root), num_parts=N_PARTS, num_nodes=N_NODES,
                        edge_index=np.stack([rows, cols]),
                        node_feat=feats, edge_feat=efeats,
                        edge_assign_strategy='by_src')
  p.partition()
  return str(root)


@pytest.fixture(scope='module')
def dist_datasets_ef(part_dir_ef):
  return [DistDataset().load(part_dir_ef, p) for p in range(N_PARTS)]


def test_dist_edge_feature_lookup(mesh, dist_datasets_ef):
  edf = DistFeature.from_dist_datasets(mesh, dist_datasets_ef,
                                       kind='edge')
  rng = np.random.default_rng(1)
  eids = rng.integers(0, N_EDGES, N_PARTS * 12)
  out = np.asarray(edf.lookup(eids))
  np.testing.assert_allclose(out[:, 0], eids)


def test_dist_loader_edge_attr_value_encoded(mesh, part_dir_ef,
                                             dist_datasets_ef):
  """Sampled eids come back with their value-encoded edge features
  through the SPMD all_to_all path."""
  from glt_tpu.distributed import DistNeighborLoader
  dg = DistGraph.from_dataset_partitions(mesh, part_dir_ef)
  edf = DistFeature.from_dist_datasets(mesh, dist_datasets_ef,
                                       kind='edge')
  loader = DistNeighborLoader(
      dg, [2, 2], input_nodes=[np.arange(p * 5, p * 5 + 5)
                               for p in range(N_PARTS)],
      batch_size=5, edge_feature=edf)
  out = next(iter(loader))
  em = np.asarray(out['edge_mask'])
  ea = np.asarray(out['edge_attr'])
  eids = np.asarray(out['edge'])
  assert em.sum() > 0
  np.testing.assert_allclose(ea[em][:, 0], eids[em])
  # every sampled edge id is a real ring edge id
  assert eids[em].min() >= 0 and eids[em].max() < N_EDGES


class _EdgeSumModel(__import__('flax').linen.Module):
  """Logits from node features + aggregated edge features — nonzero
  grads only possible if edge_attr actually arrives."""
  num_classes: int = 4

  @__import__('flax').linen.compact
  def __call__(self, batch):
    import flax.linen as nn
    n = batch.node.shape[0]
    seg = jnp.where(batch.edge_mask, jnp.clip(batch.col, 0, n - 1), n)
    agg = jax.ops.segment_sum(
        jnp.where(batch.edge_mask[:, None], batch.edge_attr, 0.0),
        seg, n + 1)[:n]
    h = jnp.concatenate([batch.x, agg], axis=-1)
    return nn.Dense(self.num_classes)(h)[:batch.batch_size]


def test_dist_train_step_consumes_edge_features(mesh, part_dir_ef,
                                                dist_datasets_ef):
  import optax
  from glt_tpu.distributed import DistTrainStep
  dg = DistGraph.from_dataset_partitions(mesh, part_dir_ef)
  ndf = DistFeature.from_dist_datasets(mesh, dist_datasets_ef)
  edf = DistFeature.from_dist_datasets(mesh, dist_datasets_ef,
                                       kind='edge')
  labels = np.arange(N_NODES, dtype=np.int32) % 4
  model = _EdgeSumModel()
  tx = optax.sgd(1e-2)
  step = DistTrainStep(dg, ndf, model, tx, labels, fanouts=[2, 2],
                       batch_size_per_device=4, edge_feature=edf)
  params = step.init_params(jax.random.key(0))
  opt_state = tx.init(params)
  seeds = np.arange(N_PARTS * 4) % N_NODES
  p0 = jax.tree.map(np.asarray, params)
  params, opt_state, loss = step(params, opt_state, seeds,
                                 np.full(N_PARTS, 4),
                                 jax.random.key(1))
  loss = np.asarray(jax.block_until_ready(loss))
  assert np.isfinite(loss).all()
  # edge-feature-dependent weights moved -> edge_attr flowed end-to-end
  changed = jax.tree.map(
      lambda a, b: float(np.abs(np.asarray(a) - b).sum()), params, p0)
  assert sum(jax.tree.leaves(changed)) > 0


class _HeteroEdgeProbe(__import__('flax').linen.Module):
  """Seed-user logits from user features + aggregated rev_u2i edge
  features — grads require edge_attr_dict to arrive."""
  num_classes: int = 3

  @__import__('flax').linen.compact
  def __call__(self, batch):
    import flax.linen as nn
    rev = ('item', 'rev_u2i', 'user')
    n = batch.node_dict['user'].shape[0]
    em = batch.edge_mask_dict[rev]
    seg = jnp.where(em, jnp.clip(batch.col_dict[rev], 0, n - 1), n)
    agg = jax.ops.segment_sum(
        jnp.where(em[:, None], batch.edge_attr_dict[rev], 0.0),
        seg, n + 1)[:n]
    h = jnp.concatenate([batch.x_dict['user'], agg], axis=-1)
    return nn.Dense(self.num_classes)(h)[:batch.batch_size]


def test_dist_hetero_edge_features(tmp_path_factory, mesh):
  """Hetero distributed edge features: value-encoded per-etype efeats
  arrive through the SPMD path and feed the train step."""
  import optax
  from glt_tpu.distributed import (
      DistHeteroGraph, DistHeteroNeighborSampler, DistHeteroTrainStep,
  )
  root = str(tmp_path_factory.mktemp('hetero_ef'))
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  nu, ni = 16, 32
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2*u, 2*u+1], 1).reshape(-1) % ni])
  i = np.arange(ni)
  i2i_ei = np.stack([np.repeat(i, 2),
                     np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  feats = {'user': np.tile(np.arange(nu, dtype=np.float32)[:, None],
                           (1, 4)),
           'item': np.tile(np.arange(ni, dtype=np.float32)[:, None],
                           (1, 4))}
  efeats = {u2i: np.tile(np.arange(2*nu, dtype=np.float32)[:, None],
                         (1, 4)),
            i2i: np.tile(np.arange(2*ni, dtype=np.float32)[:, None],
                         (1, 4))}
  RandomPartitioner(root, num_parts=N_PARTS,
                    num_nodes={'user': nu, 'item': ni},
                    edge_index={u2i: u2i_ei, i2i: i2i_ei},
                    node_feat=feats, edge_feat=efeats).partition()
  dg = DistHeteroGraph.from_dataset_partitions(mesh, root)
  dss = [DistDataset().load(root, p) for p in range(N_PARTS)]
  dfeats = {t: DistFeature.from_dist_datasets(mesh, dss, ntype=t)
            for t in ('user', 'item')}
  edfs = {e: DistFeature.from_dist_datasets(mesh, dss, ntype=e,
                                            kind='edge')
          for e in (u2i, i2i)}

  # value assertion through the SPMD sampler path
  s = DistHeteroNeighborSampler(dg, {u2i: [2], i2i: [2]},
                                with_edge=True, seed=0)
  seeds = (np.arange(N_PARTS) % nu)[:, None]
  out = s.sample_from_nodes('user', seeds)
  rev = ('item', 'rev_u2i', 'user')
  eids = np.asarray(out['edge'][rev])
  em = np.asarray(out['edge_mask'][rev])
  looked = np.asarray(edfs[u2i].lookup(
      jnp.maximum(jnp.asarray(eids.reshape(-1)), 0),
      jnp.asarray(em.reshape(-1))))
  np.testing.assert_allclose(looked[em.reshape(-1)][:, 0],
                             eids[em])

  # and end-to-end through the hetero train step
  labels = {'user': (np.arange(nu) % 3).astype(np.int32)}
  model = _HeteroEdgeProbe()
  tx = optax.sgd(1e-2)
  step = DistHeteroTrainStep(dg, dfeats, model, tx, labels,
                             {u2i: [2], i2i: [2]},
                             batch_size_per_device=2, seed_type='user',
                             seed=0, edge_features=edfs)
  params = step.init_params(jax.random.key(0))
  opt = tx.init(params)
  p0 = jax.tree.map(np.asarray, params)
  params, opt, loss = step(params, opt,
                           np.arange(N_PARTS * 2).reshape(N_PARTS, 2)
                           % nu,
                           np.full(N_PARTS, 2), jax.random.key(1))
  loss = np.asarray(jax.block_until_ready(loss))
  assert np.isfinite(loss).all()
  changed = jax.tree.map(
      lambda a, b: float(np.abs(np.asarray(a) - b).sum()), params, p0)
  assert sum(jax.tree.leaves(changed)) > 0


def test_dist_hetero_train_step_weighted(tmp_path_factory, mesh):
  """with_weight reaches the per-etype collective one-hop through the
  hetero train step (passthrough smoke)."""
  import optax
  from glt_tpu.distributed import (
      DistHeteroGraph, DistHeteroTrainStep,
  )
  from glt_tpu.models import RGNN
  from glt_tpu.typing import reverse_edge_type
  root = str(tmp_path_factory.mktemp('hw_train'))
  i2i = ('item', 'i2i', 'item')
  ni = 32
  i = np.arange(ni)
  ei = np.stack([np.repeat(i, 2),
                 np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  w = np.ones(2 * ni, np.float32)
  w[::2] = 500.0
  feats = {'item': np.tile(np.arange(ni, dtype=np.float32)[:, None],
                           (1, 4))}
  RandomPartitioner(root, num_parts=N_PARTS, num_nodes={'item': ni},
                    edge_index={i2i: ei}, edge_weights={i2i: w},
                    node_feat=feats).partition()
  dg = DistHeteroGraph.from_dataset_partitions(mesh, root)
  dss = [DistDataset().load(root, p) for p in range(N_PARTS)]
  dfeats = {'item': DistFeature.from_dist_datasets(mesh, dss,
                                                   ntype='item')}
  labels = {'item': (np.arange(ni) % 3).astype(np.int32)}
  model = RGNN(edge_types=[i2i], hidden_features=8, out_features=3,
               num_layers=1, conv='rsage')
  tx = optax.sgd(1e-2)
  step = DistHeteroTrainStep(dg, dfeats, model, tx, labels, {i2i: [2]},
                             batch_size_per_device=2, seed_type='item',
                             seed=0, with_weight=True)
  assert step.sampler.with_weight
  params = step.init_params(jax.random.key(0))
  opt = tx.init(params)
  _, _, loss = step(params, opt,
                    np.arange(N_PARTS * 2).reshape(N_PARTS, 2) % ni,
                    np.full(N_PARTS, 2), jax.random.key(1))
  assert np.isfinite(np.asarray(jax.block_until_ready(loss))).all()


def test_dist_link_loader_edge_features(mesh, part_dir_ef,
                                        dist_datasets_ef):
  from glt_tpu.distributed import DistLinkNeighborLoader
  from glt_tpu.sampler import NegativeSampling
  dg = DistGraph.from_dataset_partitions(mesh, part_dir_ef)
  edf = DistFeature.from_dist_datasets(mesh, dist_datasets_ef,
                                       kind='edge')
  pools = []
  for p in range(N_PARTS):
    owned = np.nonzero(np.asarray(dg.node_pb) == p)[0]
    src = np.repeat(owned, 2)
    dst = np.stack([(owned + 1) % N_NODES, (owned + 2) % N_NODES],
                   1).reshape(-1)
    pools.append(np.stack([src, dst]))
  loader = DistLinkNeighborLoader(
      dg, [2], pools, neg_sampling=NegativeSampling('binary', amount=1),
      batch_size=4, seed=0, edge_feature=edf)
  b = next(iter(loader))
  em = np.asarray(b['edge_mask'])
  np.testing.assert_allclose(np.asarray(b['edge_attr'])[em][:, 0],
                             np.asarray(b['edge'])[em])


def test_dist_subgraph_loader_edge_features(mesh, part_dir_ef,
                                            dist_datasets_ef):
  from glt_tpu.distributed import DistSubGraphLoader
  dg = DistGraph.from_dataset_partitions(mesh, part_dir_ef)
  edf = DistFeature.from_dist_datasets(mesh, dist_datasets_ef,
                                       kind='edge')
  loader = DistSubGraphLoader(
      dg, num_hops=1,
      input_nodes_per_device=[np.arange(p * 5, p * 5 + 4)
                              for p in range(N_PARTS)],
      batch_size=4, seed=0, edge_feature=edf)
  b = next(iter(loader))
  saw = 0
  for item in b['induced']:
    if item['eids'].shape[0]:
      np.testing.assert_allclose(item['edge_attr'][:, 0], item['eids'])
      saw += item['eids'].shape[0]
  assert saw > 0


# -- repeated and masked seed slots through the collective one-hop -------
# The hop loop feeds the collective one-hop a frontier in no slot order,
# with _BIG in the slots of nodes seen before: two seed slots of one node
# and a masked slot on every device must come out as one label and none.

def test_dist_sampler_repeated_and_masked_seeds(mesh, part_dir):
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  s = DistNeighborSampler(dg, [2, 2], with_edge=True, seed=1)
  seeds = np.stack([[p, p, (p + 9) % N_NODES, (p + 5) % N_NODES]
                    for p in range(N_PARTS)])
  out = s.sample_from_nodes(seeds, np.full(N_PARTS, 3))
  nodes = np.asarray(out['node'])
  counts = np.asarray(out['node_count'])
  for p in range(N_PARTS):
    live = (p, (p + 9) % N_NODES)
    np.testing.assert_array_equal(np.asarray(out['seed_labels'])[p],
                                  [0, 0, 1, -1])
    got = set(nodes[p][:counts[p]].tolist())
    assert got == {(v + d) % N_NODES for v in live for d in range(5)}
    em = np.asarray(out['edge_mask'])[p]
    child = nodes[p][np.asarray(out['row'])[p][em]]
    parent = nodes[p][np.asarray(out['col'])[p][em]]
    for pp, cc in zip(parent, child):
      assert cc in ((pp + 1) % N_NODES, (pp + 2) % N_NODES)
    # hop-0 edge ids are the live seeds' out-edges {2v, 2v+1}
    offs = out['edge_hop_offsets']
    em0 = em[offs[0]:offs[1]]
    eids0 = np.asarray(out['edge'])[p][offs[0]:offs[1]][em0]
    assert sorted(eids0.tolist()) == sorted(
        e for v in live for e in (2 * v, 2 * v + 1))


def test_dist_hetero_sampler_repeated_and_masked_seeds(tmp_path_factory,
                                                       mesh):
  from glt_tpu.distributed import DistHeteroGraph, DistHeteroNeighborSampler
  root = str(tmp_path_factory.mktemp('hetero_parts_repeated'))
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  nu, ni = 16, 32
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2*u, 2*u+1], 1).reshape(-1) % ni])
  i = np.arange(ni)
  i2i_ei = np.stack([np.repeat(i, 2),
                     np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  RandomPartitioner(root, num_parts=N_PARTS,
                    num_nodes={'user': nu, 'item': ni},
                    edge_index={u2i: u2i_ei, i2i: i2i_ei}).partition()
  dg = DistHeteroGraph.from_dataset_partitions(mesh, root)
  s = DistHeteroNeighborSampler(dg, {u2i: [2, 2], i2i: [2, 2]}, seed=0)
  seeds = np.stack([[p % nu, p % nu, (p + 5) % nu, (p + 11) % nu]
                    for p in range(N_PARTS)])
  out = s.sample_from_nodes('user', seeds, np.full(N_PARTS, 3))
  items = np.asarray(out['node']['item'])
  icount = np.asarray(out['node_count']['item'])
  users = np.asarray(out['node']['user'])
  ucount = np.asarray(out['node_count']['user'])
  for p in range(N_PARTS):
    live = (p % nu, (p + 5) % nu)
    np.testing.assert_array_equal(users[p][:ucount[p]], live)
    np.testing.assert_array_equal(np.asarray(out['seed_labels'])[p],
                                  [0, 0, 1, -1])
    expect = {(2 * uu + d) % ni for uu in live for d in (0, 1)}
    for v in list(expect):
      expect |= {(v+1) % ni, (v+2) % ni}
    got = set(items[p][:icount[p]].tolist())
    assert got == expect, f'dev {p}: {got} != {expect}'


def test_dist_feature_lookup_serves_rows_and_zero_rows(mesh,
                                                       dist_datasets):
  # the PB-routed all_to_all lookup is table[ids] (feature row i is
  # [i] * dim here); masked-out and negative requests come back zero
  df = DistFeature.from_dist_datasets(mesh, dist_datasets)
  rng = np.random.default_rng(1)
  ids = rng.integers(0, N_NODES, N_PARTS * 16)
  valid = rng.random(N_PARTS * 16) < 0.75
  ids[::7] = -1
  out = np.asarray(df.lookup(ids, jnp.asarray(valid)))
  ok = valid & (ids >= 0)
  want = np.where(ok[:, None], ids[:, None].astype(out.dtype), 0)
  np.testing.assert_array_equal(out, np.broadcast_to(want, out.shape))


def test_dist_feature_spill_parity(mesh, dist_datasets):
  # beyond-HBM store: cold rows served from host shards must be
  # value-identical to the fully-resident store
  df = DistFeature.from_dist_datasets(mesh, dist_datasets,
                                      split_ratio=0.4)
  assert df._spill
  rng = np.random.default_rng(3)
  ids = rng.integers(0, N_NODES, N_PARTS * 16)
  valid = rng.random(N_PARTS * 16) < 0.75
  out = np.asarray(df.lookup(ids, jnp.asarray(valid)))
  np.testing.assert_allclose(out[valid][:, 0], ids[valid])
  np.testing.assert_allclose(out[~valid], 0.0)


def test_dist_feature_spill_cold_get_roundtrip(mesh, dist_datasets):
  from fixtures import skip_unless_pinned_host
  skip_unless_pinned_host()
  # the rpc-callee surface (legacy host-phase path): cold_get(partition,
  # ids) must serve exactly the rows lookup() would have resolved for
  # that partition. Offloaded stores free this state and refuse.
  df = DistFeature.from_dist_datasets(mesh, dist_datasets,
                                      split_ratio=0.25,
                                      host_offload=False)
  offloaded = DistFeature.from_dist_datasets(mesh, dist_datasets,
                                             split_ratio=0.25)
  with pytest.raises(RuntimeError, match='legacy host-phase'):
    offloaded.cold_get(0, np.arange(2))
  served = 0
  for p, pb in df._host_pb.items():
    if p not in df._host_cold:
      continue
    owned = np.nonzero(pb == p)[0]
    rows = df._host_id2index[p][owned]
    cold_ids = owned[rows >= int(df.hot_counts[p])]
    if cold_ids.size == 0:
      continue
    vals = df.cold_get(p, cold_ids)
    np.testing.assert_allclose(vals[:, 0], cold_ids)
    served += cold_ids.size
  assert served > 0


def test_dist_feature_bucket_cap_parity(mesh, dist_datasets):
  # capped request buckets with drain rounds: value parity vs uncapped,
  # including composition with host spill
  rng = np.random.default_rng(9)
  ids = rng.integers(0, N_NODES, N_PARTS * 16)
  valid = rng.random(N_PARTS * 16) < 0.8
  base = DistFeature.from_dist_datasets(mesh, dist_datasets)
  want = np.asarray(base.lookup(ids, jnp.asarray(valid)))
  capped = DistFeature.from_dist_datasets(mesh, dist_datasets,
                                          bucket_cap=4)  # B=16/device
  got = np.asarray(capped.lookup(ids, jnp.asarray(valid)))
  np.testing.assert_allclose(got, want)
  spilled = DistFeature.from_dist_datasets(mesh, dist_datasets,
                                           split_ratio=0.4,
                                           bucket_cap=4)
  got2 = np.asarray(spilled.lookup(ids, jnp.asarray(valid)))
  np.testing.assert_allclose(got2, want)


def test_dist_hetero_train_step_capped_offloaded_spill(
    tmp_path_factory, mesh):
  from fixtures import skip_unless_pinned_host
  skip_unless_pinned_host()
  """VERDICT r4 next #7: bucket_cap + host-offloaded spill COMBINED in
  the fused hetero train step (IGBH shape: typed stores, rgnn, fused
  sampling+gather+update). The in-program drain makes the combination
  legal; losses must match a fully-resident uncapped run bit-for-bit
  (zeros from undrained or unserved-cold lanes would shift them)."""
  import optax
  from glt_tpu.distributed import (
      DistDataset, DistFeature, DistHeteroGraph, DistHeteroTrainStep,
  )
  from glt_tpu.models import RGNN
  from glt_tpu.typing import reverse_edge_type
  root = str(tmp_path_factory.mktemp('hetero_cap_spill'))
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  nu, ni = 16, 32
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2*u, 2*u+1], 1).reshape(-1) % ni])
  i = np.arange(ni)
  i2i_ei = np.stack([np.repeat(i, 2),
                     np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  # value-encoded features: any lane served as zero changes the loss
  feats = {'user': np.tile(np.arange(nu, dtype=np.float32)[:, None],
                           (1, 8)) + 1.0,
           'item': np.tile(np.arange(ni, dtype=np.float32)[:, None],
                           (1, 8)) + 1.0}
  RandomPartitioner(root, num_parts=N_PARTS,
                    num_nodes={'user': nu, 'item': ni},
                    edge_index={u2i: u2i_ei, i2i: i2i_ei},
                    node_feat=feats).partition()
  dg = DistHeteroGraph.from_dataset_partitions(mesh, root)
  dss = [DistDataset().load(root, p) for p in range(N_PARTS)]
  labels = {'user': (np.arange(nu) % 3).astype(np.int32)}
  model = RGNN(edge_types=[reverse_edge_type(u2i), i2i],
               hidden_features=8, out_features=3, num_layers=2,
               conv='rsage')
  tx = optax.sgd(1e-2)

  def run(**store_kw):
    dfeats = {t: DistFeature.from_dist_datasets(mesh, dss, ntype=t,
                                                **store_kw)
              for t in ('user', 'item')}
    if store_kw:
      assert all(st.cold_array is not None for st in dfeats.values())
      assert all(st.bucket_cap == 4 for st in dfeats.values())
    step = DistHeteroTrainStep(dg, dfeats, model, tx, labels,
                               {u2i: [2, 2], i2i: [2, 2]},
                               batch_size_per_device=2,
                               seed_type='user', seed=0)
    params = step.init_params(jax.random.key(0))
    opt = tx.init(params)
    rng = np.random.default_rng(0)
    losses = []
    for it in range(3):
      seeds = rng.integers(0, nu, (N_PARTS, 2))
      params, opt, loss = step(params, opt, seeds, np.full(N_PARTS, 2),
                               jax.random.key(it))
      losses.append(float(np.asarray(loss)[0]))
    return losses

  base = run()
  combined = run(split_ratio=0.5, bucket_cap=4)
  np.testing.assert_allclose(combined, base, rtol=1e-6)


def test_dist_feature_bucket_cap_post_hoc_before_trace_ok(
    mesh, dist_datasets):
  # the in-program drain needs no retained host books, so a cap set
  # any time BEFORE the first lookup (which bakes it into the trace)
  # is honored exactly — even under worst-case hot-spot overflow
  # (this replaced the old 'routing books' rejection, which guarded
  # the host drain replay that no longer exists)
  df = DistFeature.from_dist_datasets(mesh, dist_datasets)
  df.bucket_cap = 4
  ids = np.zeros(N_PARTS * 16, np.int64)  # hot-spot: forces overflow
  out = np.asarray(df.lookup(ids))
  base = DistFeature.from_dist_datasets(mesh, dist_datasets)
  want = np.asarray(base.lookup(ids))
  np.testing.assert_allclose(out, want)


def test_dist_feature_bucket_cap_mutation_after_trace_rejected(
    mesh, dist_datasets):
  # the first lookup bakes the cap into the shard_map trace; mutating
  # it afterwards would silently keep routing with the old cap — must
  # raise, not silently diverge
  df = DistFeature.from_dist_datasets(mesh, dist_datasets, bucket_cap=4)
  ids = np.arange(N_PARTS * 16, dtype=np.int64) % N_NODES
  df.lookup(ids)
  df.bucket_cap = 8
  with pytest.raises(RuntimeError, match='bucket_cap changed'):
    df.lookup(ids)


def test_dist_feature_host_offload_active_and_parity(mesh, dist_datasets):
  from fixtures import skip_unless_pinned_host
  skip_unless_pinned_host()
  # spilled store auto-builds the pinned-host cold block; lookup parity
  # vs the resident store with NO host phase (cold served in-program)
  df = DistFeature.from_dist_datasets(mesh, dist_datasets,
                                      split_ratio=0.4)
  assert df._spill and df.cold_array is not None
  assert df.cold_array.sharding.memory_kind == 'pinned_host'
  rng = np.random.default_rng(31)
  ids = rng.integers(0, N_NODES, N_PARTS * 16)
  out = np.asarray(df.lookup(ids))
  np.testing.assert_allclose(out[:, 0], ids)
  # explicit opt-out keeps the legacy host-phase path
  legacy = DistFeature.from_dist_datasets(mesh, dist_datasets,
                                          split_ratio=0.4,
                                          host_offload=False)
  assert legacy._spill and legacy.cold_array is None
  np.testing.assert_allclose(np.asarray(legacy.lookup(ids)), out)


def test_dist_train_step_with_host_offloaded_spill(mesh, part_dir,
                                                   dist_datasets):
  from fixtures import skip_unless_pinned_host
  skip_unless_pinned_host()
  # the fused one-program step accepts a spilled store once the cold
  # block is host-offloaded, and trains IDENTICALLY to resident
  import optax
  from glt_tpu.distributed import DistTrainStep
  from glt_tpu.models import GraphSAGE
  dg = DistGraph.from_dataset_partitions(mesh, part_dir)
  labels = (np.arange(N_NODES) % 4).astype(np.int32)
  model = GraphSAGE(hidden_features=16, out_features=4, num_layers=1)
  tx = optax.adam(1e-2)

  def losses(df):
    step = DistTrainStep(dg, df, model, tx, labels, fanouts=[2],
                         batch_size_per_device=4)
    params = step.init_params(jax.random.key(0))
    opt = tx.init(params)
    out = []
    for it in range(3):
      seeds = (np.arange(N_PARTS * 4) * 3) % N_NODES
      params, opt, loss = step(params, opt, seeds, np.full(N_PARTS, 4),
                               jax.random.key(it))
      out.append(float(np.asarray(loss)[0]))
    return out

  spilled = DistFeature.from_dist_datasets(mesh, dist_datasets,
                                           split_ratio=0.4)
  assert spilled.cold_array is not None
  resident = DistFeature.from_dist_datasets(mesh, dist_datasets)
  np.testing.assert_allclose(losses(spilled), losses(resident),
                             rtol=1e-6)


def test_dist_hetero_train_step_with_host_offloaded_spill(
    tmp_path_factory, mesh):
  from fixtures import skip_unless_pinned_host
  skip_unless_pinned_host()
  # the fused hetero (IGBH-path) step trains spilled per-type stores
  # via the pinned-host cold blocks, identically to resident stores
  import optax
  from glt_tpu.distributed import (
      DistDataset, DistHeteroGraph, DistHeteroTrainStep,
  )
  from glt_tpu.models import RGNN
  from glt_tpu.typing import reverse_edge_type
  root = str(tmp_path_factory.mktemp('hetero_spill_train'))
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  nu, ni = 16, 32
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2*u, 2*u+1], 1).reshape(-1) % ni])
  i = np.arange(ni)
  i2i_ei = np.stack([np.repeat(i, 2),
                     np.stack([(i+1) % ni, (i+2) % ni], 1).reshape(-1)])
  w = max(nu, ni)
  feats = {'user': np.pad(np.eye(nu, dtype=np.float32),
                          ((0, 0), (0, w - nu))),
           'item': np.pad(np.eye(ni, dtype=np.float32),
                          ((0, 0), (0, w - ni)))}
  RandomPartitioner(root, num_parts=N_PARTS,
                    num_nodes={'user': nu, 'item': ni},
                    edge_index={u2i: u2i_ei, i2i: i2i_ei},
                    node_feat=feats).partition()
  dg = DistHeteroGraph.from_dataset_partitions(mesh, root)
  dss = [DistDataset().load(root, p) for p in range(N_PARTS)]
  labels = {'user': (np.arange(nu) % 3).astype(np.int32)}
  model = RGNN(edge_types=[reverse_edge_type(u2i), i2i],
               hidden_features=16, out_features=3, num_layers=2,
               conv='rsage')
  tx = optax.adam(1e-2)

  def losses(split):
    dfeats = {t: DistFeature.from_dist_datasets(mesh, dss, ntype=t,
                                                split_ratio=split)
              for t in ('user', 'item')}
    if split is not None and split < 1:
      assert any(st.cold_array is not None for st in dfeats.values())
    step = DistHeteroTrainStep(dg, dfeats, model, tx, labels,
                               {u2i: [2, 2], i2i: [2, 2]},
                               batch_size_per_device=2,
                               seed_type='user', seed=0)
    params = step.init_params(jax.random.key(0))
    opt = tx.init(params)
    out = []
    for it in range(3):
      seeds = (np.arange(N_PARTS * 2).reshape(N_PARTS, 2) * 5) % nu
      params, opt, loss = step(params, opt, seeds, np.full(N_PARTS, 2),
                               jax.random.key(it))
      out.append(float(np.asarray(loss)[0]))
    return out

  np.testing.assert_allclose(losses(0.3), losses(None), rtol=1e-6)


# -- one partition: only the chunks of slots that hold a request are
# gathered ------------------------------------------------------------------

@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('case', CHUNK_CASES)
def test_one_partition_gathers_live_chunks_alone_and_the_plain_gathers_rows(
    case, dtype, monkeypatch):
  from jax.sharding import PartitionSpec as P
  from glt_tpu.parallel import dist_feature
  n, d = 100, 8
  rng = np.random.default_rng(59)
  feats = rng.normal(size=(n - 10, d)).astype(np.float32)
  feats[::9] = -0.0
  # ten ids have no row in the partition (id2index -1): zero rows
  id2index = np.full(n, -1, np.int32)
  id2index[rng.permutation(n)[:n - 10]] = np.arange(n - 10)
  st = DistFeature(make_mesh(1), [(feats, id2index)], np.zeros(n, np.int32),
                   n, dtype=jnp.dtype(dtype))
  assert st.in_place
  ids, valid = chunk_case(case, rng, n)
  b = ids.shape[0]

  def run(chunk):
    def form(f, m, pb, i, v):
      rows, counted = st.lookup_local(f[0], m[0], pb[0], i, v,
                                      counters=True)
      return rows, counted['store_chunks'][None]
    monkeypatch.setattr(dist_feature, 'SERVE_CHUNK', chunk)
    return jax.jit(jax.shard_map(
        form, mesh=st.mesh, in_specs=(P(st.axis),) * 5,
        out_specs=P(st.axis), check_vma=False))(
            st.array, st.id2index, st.feat_pb, jnp.asarray(ids),
            jnp.asarray(valid))

  plain, one = run(b)          # one chunk: the plain gather
  rows, chunks = run(CHUNK)
  assert rows.dtype == plain.dtype == jnp.dtype(dtype)
  np.testing.assert_array_equal(bits(rows), bits(plain))
  local = id2index[np.clip(ids, 0, n - 1)]
  asked = valid & (ids >= 0) & (local >= 0)
  want = np.where(asked[:, None],
                  np.asarray(st.array)[0][np.clip(local, 0, n - 11)],
                  np.zeros((), jnp.dtype(dtype)))
  np.testing.assert_array_equal(bits(rows), bits(want))
  assert int(chunks[0]) == chunks_with_a_valid_slot(valid)
  assert int(one[0]) == int(valid.any())
  # the host-side API goes through the same form
  np.testing.assert_array_equal(
      bits(st.lookup(ids, jnp.asarray(valid))), bits(want))


def test_a_store_that_exchanges_has_no_chunk_counter(mesh, dist_datasets):
  from jax.sharding import PartitionSpec as P
  st = DistFeature.from_dist_datasets(mesh, dist_datasets)
  assert not st.in_place
  with pytest.raises(ValueError, match='it gathers no chunks'):
    jax.shard_map(
        lambda f, m, pb, i, v: st.lookup_local(
            f[0], m[0], pb[0], i, v, counters=True)[0],
        mesh=st.mesh, in_specs=(P(st.axis),) * 5, out_specs=P(st.axis),
        check_vma=False)(st.array, st.id2index, st.feat_pb,
                         jnp.zeros((N_PARTS * 8,), jnp.int32),
                         jnp.ones((N_PARTS * 8,), bool))
