"""Superstep training pipeline: K batches per dispatch.

Parity contract under test: a K-step superstep (one donated lax.scan
dispatch) is BIT-IDENTICAL to K sequential per-batch SPMDSageTrainStep
calls — same RNG stream, same losses, same params — for fully-resident,
host-offloaded-spill and cold-streaming feature stores, with_edge on and
off. Plus the DeviceEpochLoader staging layer and the shared staged-pad
helper.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.data import Dataset, Feature
from glt_tpu.loader import (DeviceEpochLoader, NodeLoader, pad_seed_batch,
                            shard_n_valid, stack_epoch_batches)
from glt_tpu.models import GraphSAGE
from glt_tpu.ops.superstep import superstep
from glt_tpu.parallel import ShardedFeature, SPMDSageTrainStep, make_mesh

from fixtures import ring_edges

N = 64
K = 3
BS = 4  # per device; 8-device mesh -> global batch 32


@pytest.fixture(scope='module')
def mesh():
  return make_mesh(8)


@pytest.fixture(scope='module')
def setting(mesh):
  rng = np.random.default_rng(23)
  src = np.repeat(np.arange(N), 3)
  dst = (src + rng.integers(1, N, src.shape[0])) % N
  feats = rng.normal(size=(N, 8)).astype(np.float32)
  labels = rng.integers(0, 4, N).astype(np.int32)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([src, dst]), num_nodes=N)
  model = GraphSAGE(hidden_features=8, out_features=4, num_layers=2)
  tx = optax.adam(1e-2)
  return ds, model, tx, feats, labels


def _trainer(mesh, setting, sf, **kw):
  ds, model, tx, _, labels = setting
  return SPMDSageTrainStep(mesh, model, tx, ds.get_graph(), sf, labels,
                           fanouts=[3, 2], batch_size_per_device=BS,
                           **kw)


@pytest.fixture(scope='module')
def resident(mesh, setting):
  """Fully-resident trainer + init params/opt (shared: compiles once)."""
  sf = ShardedFeature(setting[3], mesh)
  step = _trainer(mesh, setting, sf)
  params = step.init_params(jax.random.key(0))
  opt = step.tx.init(params)
  return step, params, opt


def _inputs(t=K):
  seeds = np.arange(8 * BS) % N
  seeds_stack = np.broadcast_to(seeds, (t, seeds.shape[0])).copy()
  n_valid = np.full((t, 8), BS)
  keys = jax.random.split(jax.random.key(7), (t, 8))
  return seeds, seeds_stack, n_valid, keys


def _copy(tree):
  return jax.tree.map(jnp.array, tree)


def _run_sequential(step, params, opt, seeds, keys):
  losses = []
  for t in range(keys.shape[0]):
    params, opt, loss = step(params, opt, seeds, np.full(8, BS), keys[t])
    losses.append(np.asarray(loss))
  return params, opt, np.stack(losses)


# -- parity ---------------------------------------------------------------

def test_superstep_matches_sequential_per_batch(resident):
  step, params, opt = resident
  seeds, seeds_stack, n_valid, keys = _inputs()
  p1, o1, ref = _run_sequential(step, *_copy((params, opt)), seeds, keys)
  p2, o2 = _copy((params, opt))
  p2, o2, got = step.superstep(p2, o2, seeds_stack, n_valid, keys)
  np.testing.assert_array_equal(ref, np.asarray(got))
  for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  for a, b in zip(jax.tree.leaves(o1), jax.tree.leaves(o2)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope='module')
def resident_edge(mesh, setting):
  """with_edge=True trainer (sampled edge ids threaded into Batch)."""
  sf = ShardedFeature(setting[3], mesh)
  step = _trainer(mesh, setting, sf, with_edge=True)
  params = step.init_params(jax.random.key(0))
  opt = step.tx.init(params)
  return step, params, opt


def test_superstep_parity_with_edge(resident_edge):
  step, params, opt = resident_edge
  seeds, seeds_stack, n_valid, keys = _inputs()
  _, _, ref = _run_sequential(step, *_copy((params, opt)), seeds, keys)
  _, _, got = step.superstep(*_copy((params, opt)), seeds_stack,
                             n_valid, keys)
  np.testing.assert_array_equal(ref, np.asarray(got))


def test_superstep_cold_streaming_parity_with_edge(mesh, setting,
                                                  resident_edge):
  res_step, params, opt = resident_edge
  sf = ShardedFeature(setting[3], mesh, split_ratio=0.4,
                      host_offload=False)
  step = _trainer(mesh, setting, sf, with_edge=True,
                  cold_streaming=True)
  _, seeds_stack, n_valid, keys = _inputs()
  _, _, ref = res_step.superstep(*_copy((params, opt)), seeds_stack,
                                 n_valid, keys)
  _, _, got = step.superstep(*_copy((params, opt)), seeds_stack,
                             n_valid, keys)
  np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_superstep_parity_offloaded_spill(mesh, setting):
  from fixtures import skip_unless_pinned_host
  skip_unless_pinned_host()
  sf = ShardedFeature(setting[3], mesh, split_ratio=0.4)
  assert sf.cold_array is not None
  step = _trainer(mesh, setting, sf)
  params = step.init_params(jax.random.key(0))
  opt = step.tx.init(params)
  seeds, seeds_stack, n_valid, keys = _inputs()
  _, _, ref = _run_sequential(step, *_copy((params, opt)), seeds, keys)
  _, _, got = step.superstep(*_copy((params, opt)), seeds_stack,
                             n_valid, keys)
  np.testing.assert_array_equal(ref, np.asarray(got))


def test_superstep_cold_streaming_parity(mesh, setting, resident):
  """A host-spilled store with NO in-program cold path trains through
  sample+stage+consume supersteps with results identical to the
  fully-resident fused superstep (same values, same RNG stream)."""
  res_step, params, opt = resident
  sf = ShardedFeature(setting[3], mesh, split_ratio=0.4,
                      host_offload=False)
  assert sf._spill and sf.cold_array is None
  step = _trainer(mesh, setting, sf, cold_streaming=True)
  _, seeds_stack, n_valid, keys = _inputs()
  p1, o1, ref = res_step.superstep(*_copy((params, opt)), seeds_stack,
                                   n_valid, keys)
  p2, o2, got = step.superstep(*_copy((params, opt)), seeds_stack,
                               n_valid, keys)
  np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
  for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # per-batch path cannot resolve cold rows in-program
  with pytest.raises(NotImplementedError):
    step(_copy(params), _copy(opt), np.arange(8 * BS) % N,
         np.full(8, BS), jax.random.split(jax.random.key(0), 8))


def test_cold_streaming_requires_spilled_store(mesh, setting):
  with pytest.raises(ValueError, match='cold_streaming'):
    _trainer(mesh, setting, ShardedFeature(setting[3], mesh),
             cold_streaming=True)


def test_superstep_zero_steady_state_recompiles(resident):
  step, params, opt = resident
  _, seeds_stack, n_valid, keys = _inputs()
  p, o = _copy((params, opt))
  p, o, _ = step.superstep(p, o, seeds_stack, n_valid, keys)
  traces = step.superstep_traces
  for _ in range(2):
    p, o, _ = step.superstep(p, o, seeds_stack, n_valid, keys)
  assert step.superstep_traces == traces  # zero steady-state recompiles
  # a ragged tail length compiles exactly once more
  _, tail_stack, tail_nv, tail_keys = _inputs(t=2)
  p, o, _ = step.superstep(p, o, tail_stack, tail_nv, tail_keys)
  assert step.superstep_traces == traces + 1


def test_run_epoch_engines_agree(mesh, setting, resident):
  """run_epoch over a DeviceEpochLoader: streaming (double-buffered
  stage thread) and fused engines produce identical losses for
  identical stores/keys, including the ragged tail superstep."""
  res_step, params, opt = resident
  sf = ShardedFeature(setting[3], mesh, split_ratio=0.4,
                      host_offload=False)
  stream_step = _trainer(mesh, setting, sf, cold_streaming=True)
  out = {}
  for name, step in [('fused', res_step), ('stream', stream_step)]:
    loader = step.make_epoch_loader(
        np.arange(N), superstep_len=K, shuffle=True,
        rng=np.random.default_rng(5))
    p, o = _copy((params, opt))
    p, o, losses = step.run_epoch(p, o, loader, jax.random.key(11))
    out[name] = np.asarray(losses)
  assert out['fused'].shape == (2, 8)  # 2 batches of 32 seeds over 64
  np.testing.assert_array_equal(out['fused'], out['stream'])
  assert np.isfinite(out['fused']).all()


# -- ops-level builder contract ------------------------------------------

def test_superstep_builder_threads_carry_and_stacks_aux():
  def body(params, opt, seeds, n_valid, key):
    params = params + seeds.sum() * n_valid
    opt = opt + 1
    return params, opt, params * 2

  run = superstep(body)
  p, o, aux = run(jnp.zeros(()), jnp.zeros((), jnp.int32),
                  jnp.arange(6).reshape(3, 2).astype(jnp.float32),
                  jnp.ones((3,)), jnp.zeros((3,)))
  assert int(o) == 3                       # carry threaded through
  np.testing.assert_allclose(np.asarray(aux), [2., 12., 30.])
  assert float(p) == 15.                   # 1 + 5 + 9


# -- DeviceEpochLoader / staged padding ----------------------------------

def test_pad_seed_batch_is_the_node_loader_tail_rule():
  seeds = np.array([7, 3, 9], np.int64)
  padded, n_valid = pad_seed_batch(seeds, 8)
  assert n_valid == 3
  np.testing.assert_array_equal(padded, [7, 3, 9, 9, 9, 9, 9, 9])
  full, nv = pad_seed_batch(np.arange(8), 8)
  assert nv == 8 and np.array_equal(full, np.arange(8))
  with pytest.raises(ValueError):
    pad_seed_batch(np.array([], np.int64), 4)


def test_node_loader_tail_uses_shared_pad(mesh):
  from fixtures import ring_dataset
  ds = ring_dataset(num_nodes=20)
  from glt_tpu.loader import NeighborLoader
  loader = NeighborLoader(ds, [2], input_nodes=np.arange(10),
                          batch_size=8, shuffle=False)
  batches = list(loader)
  assert len(batches) == 2
  tail = batches[1]
  assert tail.metadata['n_valid'] == 2
  # the two valid seeds come through; fill slots (repeats of seed 9)
  # dedup away inside the sampler, which is why n_valid masks them
  np.testing.assert_array_equal(np.asarray(tail.batch)[:2], [8, 9])


def test_stack_epoch_batches_and_shard_n_valid():
  seeds = np.arange(10, dtype=np.int64)
  stack, nv = stack_epoch_batches(seeds, np.arange(10), 4,
                                  drop_last=False)
  assert stack.shape == (3, 4)
  np.testing.assert_array_equal(nv, [4, 4, 2])
  np.testing.assert_array_equal(stack[2], [8, 9, 9, 9])
  stack_d, nv_d = stack_epoch_batches(seeds, np.arange(10), 4,
                                      drop_last=True)
  assert stack_d.shape == (2, 4) and nv_d.tolist() == [4, 4]
  # global count 6 over 2 shards of 4: first shard full, second gets 2
  np.testing.assert_array_equal(
      shard_n_valid(np.array([6, 4]), 2, 4), [[4, 2], [4, 0]])


def test_device_epoch_loader_stages_and_windows():
  rng = np.random.default_rng(3)
  loader = DeviceEpochLoader(np.arange(37), batch_size=8,
                             superstep_len=2, num_shards=2,
                             shuffle=True, rng=rng)
  assert loader.batches_per_epoch == 5 and len(loader) == 3
  windows = list(loader)
  assert [w.length for w in windows] == [2, 2, 1]
  seen = []
  for w in windows:
    assert isinstance(w.seeds, jax.Array)
    assert w.seeds.shape == (w.length, 8)
    assert w.n_valid.shape == (w.length, 2)
    nv = np.asarray(w.n_valid)
    for t in range(w.length):
      valid = np.asarray(w.seeds[t])[:nv[t].sum()]
      seen.extend(valid.tolist())
  # one epoch = every seed exactly once (padding masked by n_valid)
  assert sorted(seen) == list(range(37))
  # tail window: 5 valid in the last batch -> shards get [4, 1]
  np.testing.assert_array_equal(np.asarray(windows[-1].n_valid), [[4, 1]])


def test_device_epoch_loader_shuffle_reproducible():
  a = DeviceEpochLoader(np.arange(16), 4, superstep_len=2, shuffle=True,
                        rng=np.random.default_rng(9))
  b = DeviceEpochLoader(np.arange(16), 4, superstep_len=2, shuffle=True,
                        rng=np.random.default_rng(9))
  for wa, wb in zip(a, b):
    np.testing.assert_array_equal(np.asarray(wa.seeds),
                                  np.asarray(wb.seeds))
  # successive epochs reshuffle
  first = np.asarray(next(iter(a)).seeds)
  second = np.asarray(next(iter(a)).seeds)
  assert not np.array_equal(first, second)


def test_device_epoch_loader_drop_last_superstep():
  loader = DeviceEpochLoader(np.arange(40), 8, superstep_len=3,
                             drop_last_superstep=True)
  windows = list(loader)
  assert [w.length for w in windows] == [3] and len(loader) == 1


# -- cold-row staging -----------------------------------------------------

def test_feature_stage_cold_rows():
  feats = np.arange(40, dtype=np.float32).reshape(10, 4)
  f = Feature(feats, split_ratio=0.5, host_offload=False)
  nodes = np.array([[1, 7, 9, 3], [8, 0, 2, 6]])
  counts = np.array([3, 2])  # trailing slots invalid
  out = f.stage_cold_rows(nodes, counts)
  assert out.shape == (2, 4, 4)
  np.testing.assert_array_equal(out[0, 1], feats[7])  # cold, valid
  np.testing.assert_array_equal(out[0, 2], feats[9])
  np.testing.assert_array_equal(out[0, 0], 0)         # hot lane
  np.testing.assert_array_equal(out[0, 3], 0)         # invalid lane
  np.testing.assert_array_equal(out[1, 0], feats[8])
  np.testing.assert_array_equal(out[1, 2], 0)         # invalid (count 2)


def test_sharded_stage_cold_rows(mesh):
  n, d = 32, 4
  feats = np.arange(n * d, dtype=np.float32).reshape(n, d)
  sf = ShardedFeature(feats, mesh, split_ratio=0.5, host_offload=False)
  assert sf._spill and sf.cold_array is None
  rps, hot = sf.rows_per_shard, sf.hot_count
  # [T=2, 8 shards * B=2 lanes]
  rng = np.random.default_rng(0)
  nodes = rng.integers(0, n, (2, 16))
  counts = np.tile(np.array([2, 2, 1, 2, 2, 0, 2, 2]), (2, 1))
  out = sf.stage_cold_rows(nodes, counts)
  assert out.shape == (2, 16, d)
  for t in range(2):
    for lane in range(16):
      dev, pos = lane // 2, lane % 2
      nid = nodes[t, lane]
      cold = (pos < counts[t, dev]) and (nid % rps >= hot)
      expect = feats[nid] if cold else np.zeros(d)
      np.testing.assert_array_equal(out[t, lane], expect)


def test_sharded_stage_cold_rows_rejects_resident(mesh):
  sf = ShardedFeature(np.eye(8, dtype=np.float32), mesh)
  with pytest.raises(ValueError, match='stage_cold_rows'):
    sf.stage_cold_rows(np.zeros((1, 8), np.int64), np.ones((1, 8)))
