"""The fused sample+assign stage (``sorted_hop_dedup_fused``): every hop
of both hop loops after the seed hop.

The reference fuses the per-hop dedup/assign into one CUDA kernel
(csrc/cuda/random_sampler.cu:59-109). The fused stage does it with one
narrow sort plus a packed scatter; new nodes within a hop get labels in
value order, and the seed hop keeps the exact first-occurrence dedup.
These tests hold it, on draws that are not exhaustive (rows wider than
the fanout, so the oracle checks each row's picks and not a known set),
to the numpy oracle (tests/sampler_oracle.py), by hand on a unit case,
in the typed loop, and inside the SPMD train step on the virtual mesh.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from glt_tpu.data import Topology
from glt_tpu.ops.pipeline import hop_fanouts, multihop_sample
from glt_tpu.ops.sample import sample_neighbors
from glt_tpu.ops.unique import sorted_hop_dedup_fused

from fixtures import ring_edges
from sampler_oracle import EdgeTable, check_multihop, check_multihop_typed


@pytest.fixture(scope='module')
def mesh():
  from glt_tpu.parallel import make_mesh
  return make_mesh(8)


def _run(seeds, n_valid, fanouts, t, key):
  indptr = jnp.asarray(t.indptr.astype(np.int32))
  indices = jnp.asarray(t.indices)
  one_hop = lambda ids, f, k, m: sample_neighbors(
      indptr, indices, ids, f, k, seed_mask=m,
      edge_ids=jnp.arange(indices.shape[0], dtype=jnp.int32))
  out = jax.jit(lambda s, nv, k: multihop_sample(
      one_hop, s, nv, fanouts, k, with_edge=True))(
          jnp.asarray(seeds, jnp.int32), jnp.asarray(n_valid), key)
  return jax.tree.map(np.asarray, out)


def _check(t, seeds, n_valid, fanouts, out):
  check_multihop(EdgeTable.from_csr(t.indptr, t.indices), seeds, n_valid,
                 fanouts, out, new_label_order='value',
                 hop_fanouts=hop_fanouts(fanouts))


@pytest.mark.parametrize('fanouts', [(2,), (3, 2), (2, 2, 2)])
def test_fused_loop_against_the_oracle(fanouts):
  # rows of degree 0 to 9 and a hub of 40: most rows are wider than the
  # fanout, so a hop's picks are a draw; a duplicate seed, a hub seed
  # and two masked slots
  rng = np.random.default_rng(8)
  n = 48
  deg = np.concatenate([[40], rng.integers(0, 10, n - 1)])
  src = np.repeat(np.arange(n), deg)
  dst = rng.integers(0, n, src.shape[0])
  t = Topology(edge_index=np.stack([src, dst]), num_nodes=n)
  seeds = np.array([7, 0, 7, 31, 12, 3], np.int32)
  out = _run(seeds, 4, fanouts, t, jax.random.key(0))
  _check(t, seeds, 4, fanouts, out)
  np.testing.assert_array_equal(out['seed_labels'][:3], [0, 1, 0])


def test_fused_random_graph_invariants():
  # a skewed graph (a few sources hold most edges) and a ragged batch of
  # 32 slots with 27 live and repeats among them: still a VALID sample
  rng = np.random.default_rng(4)
  n, e = 400, 3000
  src = np.minimum(rng.zipf(1.6, e) - 1, n - 1)
  dst = rng.integers(0, n, e)
  t = Topology(edge_index=np.stack([src, dst]), num_nodes=n)
  fanouts = (4, 3)
  seeds = rng.integers(0, 40, 32).astype(np.int32)
  out = _run(seeds, 27, fanouts, t, jax.random.key(1))
  _check(t, seeds, 27, fanouts, out)
  count = int(out['node_count'])
  assert out['num_sampled_nodes'].sum() == count
  assert (out['node'][count:] == -1).all()
  assert (out['seed_labels'][27:] == -1).all()


def test_fused_hop_dedup_unit():
  # hand-checked: seen ids keep labels; NEW ids rank in VALUE order
  # (3 < 9 -> 3 gets label 2, 9 gets label 3); invalid slots -> -1
  u_ids = jnp.array([40, 7], jnp.int32)
  u_labs = jnp.array([0, 1], jnp.int32)
  ids = jnp.array([9, 7, 9, 3, 40, 9], jnp.int32)
  valid = jnp.array([True, True, True, True, True, False])
  d = sorted_hop_dedup_fused(u_ids, u_labs, jnp.asarray(2, jnp.int32),
                             ids, valid)
  labels = np.asarray(d['labels3'])
  np.testing.assert_array_equal(labels, [3, 1, 3, 2, 0, -1])
  assert int(d['new_count']) == 2 and int(d['count2']) == 4
  # exactly one new-head per new id, at a slot holding that id
  nh = np.asarray(d['new_head3'])
  assert nh.sum() == 2
  assert sorted(np.asarray(ids)[nh].tolist()) == [3, 9]
  # append-form seen-set reconstructs the dense node list
  from glt_tpu.ops.unique import sorted_nodes_by_label
  nodes = sorted_nodes_by_label(d['u_ids2'], d['u_labs2'], d['count2'],
                                6)
  np.testing.assert_array_equal(np.asarray(nodes),
                                [40, 7, 3, 9, -1, -1])


@pytest.mark.parametrize('fanouts', [[1], [1, 1]])
def test_fused_hetero_against_the_oracle(fanouts):
  # fanout 1 under degree 2: every typed hop is a draw; a duplicate seed
  # and a masked slot
  from glt_tpu.sampler import NeighborSampler, NodeSamplerInput
  from test_sampler_contract_typed import I2I, U2I, _ring, _traversal_output
  ds, graphs = _ring()
  seeds = np.array([3, 7, 3, 9, 1, 5])
  fanouts = {U2I: fanouts, I2I: fanouts}
  s = NeighborSampler(ds.graph, fanouts, with_edge=True, seed=4)
  got = _traversal_output(s.sample_from_nodes(
      NodeSamplerInput(seeds, 'user'), n_valid=5, key=jax.random.key(5)),
      True)
  check_multihop_typed(graphs, {e: (e[0], e[2]) for e in fanouts}, fanouts,
                       {'user': seeds}, {'user': 5}, got,
                       new_label_order='value')
  np.testing.assert_array_equal(got['seed_labels']['user'],
                                [0, 1, 0, 2, 3, -1])


def test_fused_spmd_train_step_learns(mesh):
  # the fused assign inside the full SPMD training step on the 8-device
  # virtual mesh: compiles, runs, learns (VERDICT r4 next #2's
  # virtual-mesh validation)
  import optax
  from glt_tpu.data import Dataset
  from glt_tpu.models import GraphSAGE
  from glt_tpu.parallel import ShardedFeature, SPMDSageTrainStep
  n = 40
  rows, cols, _ = ring_edges(n)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([rows, cols]), num_nodes=n)
  model = GraphSAGE(hidden_features=16, out_features=4, num_layers=2)
  tx = optax.adam(1e-2)
  sf = ShardedFeature(np.eye(n, dtype=np.float32), mesh)
  step = SPMDSageTrainStep(mesh, model, tx, ds.get_graph(), sf,
                           (np.arange(n) % 4).astype(np.int32),
                           fanouts=[2, 2], batch_size_per_device=4)
  params = step.init_params(jax.random.key(0))
  opt_state = jax.device_put(
      tx.init(params),
      jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
  rng = np.random.default_rng(0)
  losses = []
  for it in range(60):
    seeds = rng.permutation(n)[:32]
    keys = jax.random.split(jax.random.key(it), 8)
    params, opt_state, loss = step(
        params, opt_state, seeds, np.full(8, 4), keys)
    losses.append(float(np.asarray(loss)[0]))
  assert losses[-1] < 0.25, f'did not learn: {losses[::10]}'
