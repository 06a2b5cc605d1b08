"""SPMD layer tests on the 8-device virtual CPU mesh: sharded feature
lookup (all_to_all) exactness and the full distributed train step."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.parallel import ShardedFeature, SPMDSageTrainStep, make_mesh
from glt_tpu.models import GraphSAGE

from fixtures import ring_dataset, ring_edges


@pytest.fixture(scope='module')
def mesh():
  return make_mesh(8)


def test_sharded_feature_lookup_exact(mesh):
  n, d = 100, 8
  feats = np.arange(n * d, dtype=np.float32).reshape(n, d)
  sf = ShardedFeature(feats, mesh)
  assert sf.rows_per_shard == 13  # ceil(100/8)
  rng = np.random.default_rng(0)
  ids = rng.integers(0, n, size=8 * 16)  # 16 requests per device
  out = np.asarray(sf.lookup(ids))
  np.testing.assert_allclose(out, feats[ids])


def test_sharded_feature_lookup_with_invalid(mesh):
  n, d = 64, 4
  feats = np.random.default_rng(1).normal(size=(n, d)).astype(np.float32)
  sf = ShardedFeature(feats, mesh)
  ids = np.tile(np.arange(8), 8)          # 8 per device
  valid = np.tile(np.array([True] * 4 + [False] * 4), 8)
  out = np.asarray(sf.lookup(ids, jnp.asarray(valid)))
  np.testing.assert_allclose(out[valid], feats[ids[valid]])
  np.testing.assert_allclose(out[~valid], 0.0)


def test_sharded_feature_hot_spot(mesh):
  # every device asks for rows owned by shard 0 (worst-case skew)
  n, d = 80, 4
  feats = np.random.default_rng(2).normal(size=(n, d)).astype(np.float32)
  sf = ShardedFeature(feats, mesh)
  ids = np.zeros(8 * 8, dtype=np.int64)  # all ask row 0
  out = np.asarray(sf.lookup(ids))
  np.testing.assert_allclose(out, np.tile(feats[0], (64, 1)))


def test_spmd_train_step_runs_and_learns(mesh):
  n = 40
  rows, cols, _ = ring_edges(n)
  from glt_tpu.data import Dataset
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([rows, cols]), num_nodes=n)
  feats = np.eye(n, dtype=np.float32)
  labels = (np.arange(n) % 4).astype(np.int32)

  model = GraphSAGE(hidden_features=16, out_features=4, num_layers=2)
  tx = optax.adam(1e-2)
  sf = ShardedFeature(feats, mesh)
  step = SPMDSageTrainStep(mesh, model, tx, ds.get_graph(), sf, labels,
                           fanouts=[2, 2], batch_size_per_device=4)
  params = step.init_params(jax.random.key(0))
  opt_state = jax.device_put(
      tx.init(params),
      jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))

  rng = np.random.default_rng(0)
  losses = []
  for it in range(60):
    seeds = rng.permutation(n)[:32]       # 8 devices x 4 seeds
    keys = jax.random.split(jax.random.key(it), 8)
    params, opt_state, loss = step(
        params, opt_state, seeds, np.full(8, 4), keys)
    losses.append(float(np.asarray(loss)[0]))
  assert losses[-1] < 0.25, f'did not learn: {losses[::10]}'


def test_spmd_losses_identical_across_devices(mesh):
  # pmean'd loss must be replicated: all 8 entries equal
  n = 40
  rows, cols, _ = ring_edges(n)
  from glt_tpu.data import Dataset
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([rows, cols]), num_nodes=n)
  model = GraphSAGE(hidden_features=8, out_features=4, num_layers=1)
  tx = optax.sgd(1e-2)
  sf = ShardedFeature(np.eye(n, dtype=np.float32), mesh)
  step = SPMDSageTrainStep(mesh, model, tx, ds.get_graph(), sf,
                           (np.arange(n) % 4).astype(np.int32),
                           fanouts=[2], batch_size_per_device=4)
  params = step.init_params(jax.random.key(1))
  opt_state = tx.init(params)
  keys = jax.random.split(jax.random.key(9), 8)
  _, _, loss = step(params, opt_state, np.arange(32), np.full(8, 4), keys)
  loss = np.asarray(loss)
  np.testing.assert_allclose(loss, loss[0], rtol=1e-6)


def test_sharded_segment_mean_matches_global(mesh):
  """Context-parallel aggregation over a neighbor list sharded across
  the mesh equals the single-device segment mean."""
  from glt_tpu.parallel import sharded_segment_mean
  from jax.sharding import PartitionSpec as P
  rng = np.random.default_rng(0)
  m, d, segs = 8 * 64, 16, 10
  msgs = rng.normal(size=(m, d)).astype(np.float32)
  targets = rng.integers(0, segs, m).astype(np.int32)
  mask = rng.random(m) > 0.2

  fn = jax.shard_map(
      lambda ms, t, mk: sharded_segment_mean(ms, t, mk, segs, 'data'),
      mesh=mesh, in_specs=(P('data'), P('data'), P('data')),
      out_specs=P(), check_vma=False)
  got = np.asarray(fn(jnp.asarray(msgs), jnp.asarray(targets),
                      jnp.asarray(mask)))
  # reference: plain masked mean
  expect = np.zeros((segs, d), np.float32)
  for s in range(segs):
    sel = (targets == s) & mask
    if sel.any():
      expect[s] = msgs[sel].mean(0)
  np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_sharded_segment_mean_scattered_matches_global(mesh):
  """Ring (reduce-scatter) aggregation: each device's segment block
  equals the corresponding slice of the global segment mean."""
  from glt_tpu.parallel import sharded_segment_mean_scattered
  from jax.sharding import PartitionSpec as P
  rng = np.random.default_rng(1)
  m, d, segs = 8 * 64, 16, 16   # 16 segments / 8 devices = 2 per shard
  msgs = rng.normal(size=(m, d)).astype(np.float32)
  targets = rng.integers(0, segs, m).astype(np.int32)
  mask = rng.random(m) > 0.2

  fn = jax.shard_map(
      lambda ms, t, mk: sharded_segment_mean_scattered(
          ms, t, mk, segs, 'data'),
      mesh=mesh, in_specs=(P('data'), P('data'), P('data')),
      out_specs=P('data'), check_vma=False)
  got = np.asarray(fn(jnp.asarray(msgs), jnp.asarray(targets),
                      jnp.asarray(mask)))          # [segs, d] stacked
  expect = np.zeros((segs, d), np.float32)
  for s in range(segs):
    sel = (targets == s) & mask
    if sel.any():
      expect[s] = msgs[sel].mean(0)
  np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_sharded_feature_spill_parity(mesh):
  # host-spill store must be value-identical to the fully-resident one
  n, d = 100, 8
  feats = np.random.default_rng(7).normal(size=(n, d)).astype(np.float32)
  base = ShardedFeature(feats, mesh)
  spill = ShardedFeature(feats, mesh, split_ratio=0.3)
  assert spill._spill and spill.hot_count < spill.rows_per_shard
  rng = np.random.default_rng(8)
  ids = rng.integers(0, n, size=8 * 16)
  valid = rng.random(8 * 16) < 0.8
  a = np.asarray(base.lookup(ids, jnp.asarray(valid)))
  b = np.asarray(spill.lookup(ids, jnp.asarray(valid)))
  np.testing.assert_allclose(a, b)
  np.testing.assert_allclose(b[valid], feats[ids[valid]])
  np.testing.assert_allclose(b[~valid], 0.0)


def test_sharded_feature_spill_all_cold(mesh):
  # split_ratio ~ 0: everything except the forced 1-row hot floor is
  # host-resident; values must still be exact
  n, d = 64, 4
  feats = np.arange(n * d, dtype=np.float32).reshape(n, d)
  spill = ShardedFeature(feats, mesh, split_ratio=0.0)
  assert spill.hot_count == 1
  ids = np.arange(64)
  out = np.asarray(spill.lookup(ids))
  np.testing.assert_allclose(out, feats[ids])


def test_spill_store_without_offload_rejected_by_fused_train_step(mesh):
  # a spilled store WITHOUT the pinned-host cold block cannot resolve
  # cold rows in-jit; the fused step must fail loudly at construction,
  # not train on zero vectors
  n = 40
  rows, cols, _ = ring_edges(n)
  from glt_tpu.data import Dataset
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([rows, cols]), num_nodes=n)
  sf = ShardedFeature(np.eye(n, dtype=np.float32), mesh,
                      split_ratio=0.5, host_offload=False)
  import optax
  model = GraphSAGE(hidden_features=8, out_features=4, num_layers=1)
  with pytest.raises(NotImplementedError, match='host-spilled'):
    SPMDSageTrainStep(mesh, model, optax.sgd(1e-2), ds.get_graph(), sf,
                      (np.arange(n) % 4).astype(np.int32), fanouts=[2],
                      batch_size_per_device=4)


def test_sharded_feature_spill_legacy_host_phase_parity(mesh):
  # host_offload=False keeps the lookup()-host-phase fallback exact
  # (the escape hatch for platforms without memory kinds)
  n, d = 100, 8
  feats = np.random.default_rng(21).normal(size=(n, d)) \
      .astype(np.float32)
  legacy = ShardedFeature(feats, mesh, split_ratio=0.3,
                          host_offload=False)
  assert legacy._spill and legacy.cold_array is None
  ids = np.random.default_rng(22).integers(0, n, size=8 * 16)
  np.testing.assert_allclose(np.asarray(legacy.lookup(ids)), feats[ids])


def test_fused_train_step_with_host_offloaded_spill(mesh):
  from fixtures import skip_unless_pinned_host
  skip_unless_pinned_host()
  # the pinned-host cold block (reference unified_tensor.cu:202-231 UVA
  # analog) lets the fused SPMD step train a spilled store with results
  # IDENTICAL to the device-resident run
  import optax
  from glt_tpu.data import Dataset
  n = 64
  rng = np.random.default_rng(23)
  src = np.repeat(np.arange(n), 3)
  dst = (src + rng.integers(1, n, src.shape[0])) % n
  feats = rng.normal(size=(n, 8)).astype(np.float32)
  labels = rng.integers(0, 4, n).astype(np.int32)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([src, dst]), num_nodes=n)
  model = GraphSAGE(hidden_features=8, out_features=4, num_layers=2)
  tx = optax.adam(1e-2)

  def losses(sf):
    step = SPMDSageTrainStep(mesh, model, tx, ds.get_graph(), sf,
                             labels, fanouts=[3, 2],
                             batch_size_per_device=4)
    params = step.init_params(jax.random.key(0))
    opt = tx.init(params)
    seeds = np.arange(8 * 4) % n
    out = []
    for i in range(2):
      keys = jax.random.split(jax.random.key(1 + i), 8)
      params, opt, loss = step(params, opt, seeds, np.full(8, 4), keys)
      out.append(float(np.asarray(loss)[0]))
    return out

  spilled = ShardedFeature(feats, mesh, split_ratio=0.4)
  assert spilled._spill and spilled.cold_array is not None
  assert (spilled.cold_array.sharding.memory_kind == 'pinned_host')
  np.testing.assert_allclose(losses(spilled),
                             losses(ShardedFeature(feats, mesh)),
                             rtol=1e-6)


def test_sharded_feature_bucket_cap_parity(mesh):
  # capped per-peer buckets + overflow drain must be value-identical
  n, d = 100, 8
  feats = np.random.default_rng(11).normal(size=(n, d)) \
      .astype(np.float32)
  base = ShardedFeature(feats, mesh)
  capped = ShardedFeature(feats, mesh, bucket_cap=4)  # B=16 per device
  rng = np.random.default_rng(12)
  ids = rng.integers(0, n, size=8 * 16)
  valid = rng.random(8 * 16) < 0.8
  a = np.asarray(base.lookup(ids, jnp.asarray(valid)))
  b = np.asarray(capped.lookup(ids, jnp.asarray(valid)))
  np.testing.assert_allclose(a, b)


def test_sharded_feature_bucket_cap_mutation_after_trace_rejected(mesh):
  n, d = 64, 4
  feats = np.arange(n * d, dtype=np.float32).reshape(n, d)
  sf = ShardedFeature(feats, mesh, bucket_cap=4)
  ids = np.arange(8 * 16, dtype=np.int64) % n
  sf.lookup(ids)
  sf.bucket_cap = 2
  with pytest.raises(RuntimeError, match='bucket_cap changed'):
    sf.lookup(ids)


def test_sharded_feature_bucket_cap_hot_spot(mesh):
  # worst-case skew: every device asks shard 0 for its whole batch —
  # the drain must run ceil(B/C) rounds and still be exact
  n, d = 80, 4
  feats = np.random.default_rng(13).normal(size=(n, d)) \
      .astype(np.float32)
  capped = ShardedFeature(feats, mesh, bucket_cap=3)
  ids = np.tile(np.arange(8), 8)  # all rows live on shard 0 (rps=10)
  out = np.asarray(capped.lookup(ids))
  np.testing.assert_allclose(out, feats[ids])


def test_sharded_feature_bucket_cap_with_spill(mesh):
  # capped buckets compose with host spill: overflow drains first, the
  # arithmetic cold phase then fills every cold lane exactly once
  n, d = 96, 4
  feats = np.arange(n * d, dtype=np.float32).reshape(n, d)
  st = ShardedFeature(feats, mesh, split_ratio=0.5, bucket_cap=4)
  rng = np.random.default_rng(14)
  ids = rng.integers(0, n, size=8 * 16)
  out = np.asarray(st.lookup(ids))
  np.testing.assert_allclose(out, feats[ids])


# -- one shard: requests served in place ----------------------------------

def _store_gauge(name):
  from glt_tpu.obs import get_registry
  return get_registry().get(name, default=-1.0,
                            fn='ShardedFeature.lookup_local')


def _in_place_gauge():
  return _store_gauge('feature_store_in_place')


def _both_forms(sf, ids, valid):
  """(in place, exchanged) [B, D] rows of a one-shard store, each
  through its own jitted shard_map."""
  from jax.sharding import PartitionSpec as P
  cold = () if sf.cold_array is None else (sf.cold_array,)

  def run(form):
    fn = jax.jit(jax.shard_map(
        form, mesh=sf.mesh, in_specs=(P(sf.axis),) * (3 + len(cold)),
        out_specs=P(sf.axis), check_vma=False))
    return np.asarray(fn(sf.array, jnp.asarray(ids), jnp.asarray(valid),
                         *cold))

  return (run(lambda shard, i, v, c=None: sf.lookup_local(
              shard, i, v, cold_shard=c)),
          run(lambda shard, i, v, c=None: sf._lookup_exchange(
              shard, i, v, sf.axis, c)))


_IN_PLACE_CASES = {
    'resident': dict(),
    'invalid_negative_out_of_range': dict(bad_ids=True),
    'bucket_cap': dict(store=dict(bucket_cap=5), bad_ids=True),
    'hot_only_spill': dict(store=dict(split_ratio=0.3,
                                      host_offload=False), hot_only=True),
    'hot_only_spill_bucket_cap': dict(
        store=dict(split_ratio=0.3, host_offload=False, bucket_cap=7),
        bad_ids=True, hot_only=True),
    'cold_shard': dict(store=dict(split_ratio=0.3), pinned=True),
}


@pytest.mark.parametrize('case', list(_IN_PLACE_CASES))
def test_one_shard_lookup_in_place_equals_the_exchange_bit_for_bit(case):
  cfg = _IN_PLACE_CASES[case]
  if cfg.get('pinned'):
    from fixtures import skip_unless_pinned_host
    skip_unless_pinned_host()
  n, d, b = 100, 8, 64
  rng = np.random.default_rng(31)
  feats = rng.normal(size=(n, d)).astype(np.float32)
  sf = ShardedFeature(feats, make_mesh(1), **cfg.get('store', {}))
  if cfg.get('pinned'):
    assert sf.cold_array is not None
  ids = rng.integers(0, n, size=b)
  valid = rng.random(b) < 0.8
  if cfg.get('bad_ids'):
    ids[::7] = -3
    ids[3::7] = n + 5
    ids[5::11] = np.iinfo(np.int32).max
    valid[:4] = [True, False, True, True]   # a bad id on a valid lane too
  ids = ids.astype(np.int32)
  in_place, exchanged = _both_forms(sf, ids, valid)
  assert in_place.dtype == exchanged.dtype == np.float32
  np.testing.assert_array_equal(in_place.view(np.uint32),
                                exchanged.view(np.uint32))
  assert _in_place_gauge() == 1.0
  asked = valid & (ids >= 0) & (ids < n)
  rows = feats[np.clip(ids, 0, n - 1)]
  served = asked
  if cfg.get('hot_only'):
    # cold lanes come back zero: the superstep's staged rows and
    # lookup()'s host phase add theirs
    assert sf._spill and sf.hot_count < n
    served = asked & (ids < sf.hot_count)
  np.testing.assert_array_equal(in_place,
                                np.where(served[:, None], rows, 0))
  # the host-side API goes through the same form
  np.testing.assert_array_equal(
      np.asarray(sf.lookup(ids, jnp.asarray(valid))),
      np.where(asked[:, None], rows, 0))


# -- one shard: only the chunks of slots that hold a request are gathered --

CHUNK = 16   # SERVE_CHUNK of the chunk tests (the code's is 8,192)


def chunk_case(case, rng, n, c=CHUNK):
  """``(ids [B] int32, valid [B])`` of one case of the chunked serve."""
  b = {'b_not_a_multiple': 5 * c + 3, 'b_not_a_multiple_prefix': 5 * c + 3,
       'b_less_than_c': c - 3}.get(case, 6 * c)
  ids = rng.integers(0, n, size=b).astype(np.int32)
  valid = np.zeros(b, bool)
  if case == 'one_valid':
    valid[2 * c + 5] = True
  elif case.startswith('prefix_'):
    live = {'c_minus_1': c - 1, 'c': c, 'c_plus_1': c + 1,
            'b': b}[case[len('prefix_'):]]
    valid[:live] = True
  elif case == 'b_not_a_multiple_prefix':
    valid[:2 * c + 1] = True    # the overlapping last chunk stays empty
  elif case in ('b_not_a_multiple', 'b_less_than_c'):
    valid[:] = rng.random(b) < 0.5
    valid[-1] = True            # the last chunk is live
  elif case == 'scattered_with_an_empty_chunk':
    valid[:] = rng.random(b) < 0.3
    valid[2 * c:3 * c] = False
  elif case == 'ids_out_of_range_in_a_live_chunk':
    valid[:c + 4] = True
    ids[1:c + 4:5] = -3
    ids[2:c + 4:5] = n + 5
    ids[3:c + 4:5] = np.iinfo(np.int32).max
  else:
    assert case == 'none_valid'
  return ids, valid


CHUNK_CASES = [
    'none_valid', 'one_valid', 'prefix_c_minus_1', 'prefix_c',
    'prefix_c_plus_1', 'prefix_b', 'b_not_a_multiple',
    'b_not_a_multiple_prefix', 'b_less_than_c',
    'scattered_with_an_empty_chunk', 'ids_out_of_range_in_a_live_chunk']


def chunks_with_a_valid_slot(valid, c=CHUNK):
  return sum(bool(valid[lo:lo + c].any())
             for lo in range(0, valid.shape[0], c))


def bits(rows):
  rows = np.asarray(rows)
  return rows.view({2: np.uint16, 4: np.uint32}[rows.dtype.itemsize])


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('case', CHUNK_CASES)
def test_one_shard_gathers_live_chunks_alone_and_the_plain_gathers_rows(
    case, dtype, monkeypatch):
  from jax.sharding import PartitionSpec as P
  from glt_tpu.parallel import dist_feature
  monkeypatch.setattr(dist_feature, 'SERVE_CHUNK', CHUNK)
  n, d = 100, 8
  rng = np.random.default_rng(53)
  feats = rng.normal(size=(n, d)).astype(np.float32)
  feats[::9] = -0.0
  sf = ShardedFeature(feats, make_mesh(1), dtype=jnp.dtype(dtype))
  ids, valid = chunk_case(case, rng, n)
  b = ids.shape[0]

  def run(form):
    return jax.jit(jax.shard_map(
        form, mesh=sf.mesh, in_specs=(P(sf.axis),) * 3,
        out_specs=P(sf.axis), check_vma=False))(
            sf.array, jnp.asarray(ids), jnp.asarray(valid))

  def chunked(shard, i, v):
    rows, counted = sf.lookup_local(shard, i, v, counters=True)
    return rows, counted['store_chunks'][None]

  rows, chunks = run(chunked)
  plain = run(lambda shard, i, v: sf._serve(
      shard, jnp.where(v, i, -1), sf.axis, None))
  assert rows.dtype == plain.dtype == jnp.dtype(dtype)
  assert rows.shape == (b, d)
  np.testing.assert_array_equal(bits(rows), bits(plain))
  asked = valid & (ids >= 0) & (ids < n)
  want = np.where(asked[:, None], np.asarray(sf.array)[np.clip(ids, 0, n - 1)],
                  np.zeros((), jnp.dtype(dtype)))
  np.testing.assert_array_equal(bits(rows), bits(want))
  assert int(chunks[0]) == chunks_with_a_valid_slot(valid)
  assert sf.serve_chunks(b) == max(1, -(-b // CHUNK))
  # the host-side API goes through the same form
  np.testing.assert_array_equal(
      bits(sf.lookup(ids, jnp.asarray(valid))), bits(want))


@pytest.mark.parametrize('chips', [1, 2, 8])
def test_lookup_local_exchanges_only_over_more_than_one_shard(
    chips, monkeypatch):
  from jax.sharding import PartitionSpec as P
  n, d, b = 96, 4, 16
  feats = np.arange(n * d, dtype=np.float32).reshape(n, d)
  sf = ShardedFeature(feats, make_mesh(chips))
  ids = jnp.asarray(np.arange(chips * b, dtype=np.int32) % n)

  def lowered():
    return jax.jit(jax.shard_map(
        sf.lookup_local, mesh=sf.mesh, in_specs=(P(sf.axis),) * 3,
        out_specs=P(sf.axis), check_vma=False)).lower(
            sf.array, ids, jnp.ones(ids.shape, bool)).as_text()

  text = lowered()
  in_place = chips == 1
  assert text.count('all_to_all') == (0 if in_place else 2), text
  # a request's bucket slot is its owner and its rank in request order:
  # neither form sorts
  assert 'stablehlo.sort' not in text
  assert _in_place_gauge() == float(in_place)
  # up to SERVE_CHUNK slots every stage is one plain pass (uncapped, so
  # with no drain loop either: no loop at all); past it the in-place
  # serve is one chunk loop, and an exchange runs three: the pack, the
  # owner's serve and the stitch
  assert 'stablehlo.while' not in text
  from glt_tpu.parallel import dist_feature
  monkeypatch.setattr(dist_feature, 'SERVE_CHUNK', b // 2)
  assert lowered().count('stablehlo.while') == (1 if in_place else 3)


# -- more shards: buckets of b / P slots and the drain behind them ---------

def _lookup_counted(sf, ids, valid):
  """``lookup_local(counters=True)`` through a jitted shard_map: rows
  [P * b, D] and the counters, a device an entry."""
  from jax.sharding import PartitionSpec as P
  cold = () if sf.cold_array is None else (sf.cold_array,)

  def form(shard, i, v, c=None):
    rows, counted = sf.lookup_local(shard, i, v, cold_shard=c,
                                    counters=True)
    return rows, {k: a[None] for k, a in counted.items()}

  fn = jax.jit(jax.shard_map(
      form, mesh=sf.mesh, in_specs=(P(sf.axis),) * (3 + len(cold)),
      out_specs=P(sf.axis), check_vma=False))
  rows, counted = fn(sf.array, jnp.asarray(ids), jnp.asarray(valid), *cold)
  return np.asarray(rows), {k: np.asarray(a) for k, a in counted.items()}


def _requests(case, rng, p, b, n):
  """(ids [p * b], valid [p * b]) of a drain case, rows_per_shard n / p."""
  rps = n // p
  ids = rng.integers(0, n, size=p * b)
  valid = rng.random(p * b) < 0.36   # a third of the slots are live
  if case == 'generator_skew':       # half on shard 0: floor(N u^2)
    ids = np.floor(n * rng.random(p * b) ** 2).astype(np.int64)
  elif case == 'one_owner':          # every request of every device
    ids = rng.integers(rps, 2 * rps, size=p * b)
    valid[:] = True
  elif case == 'none_valid':
    valid[:] = False
  return ids.astype(np.int32), valid


_DRAIN_CASES = {
    'spread_evenly': dict(),
    'generator_skew': dict(),
    'one_owner': dict(),
    'none_valid': dict(),
    'b_not_a_multiple': dict(b=600),
    'hot_only_spill': dict(store=dict(split_ratio=0.3, host_offload=False),
                           hot_only=True),
    'cold_shard': dict(store=dict(split_ratio=0.3), pinned=True),
}


@pytest.mark.parametrize('case', list(_DRAIN_CASES))
def test_default_store_over_four_shards_drains_exactly(case):
  cfg = _DRAIN_CASES[case]
  if cfg.get('pinned'):
    from fixtures import skip_unless_pinned_host
    skip_unless_pinned_host()
  p, n, d, b = 4, 400, 8, cfg.get('b', 512)
  rng = np.random.default_rng(41)
  feats = rng.normal(size=(n, d)).astype(np.float32)
  sf = ShardedFeature(feats, make_mesh(p), **cfg.get('store', {}))
  assert sf.bucket_cap == 0 and not sf.in_place
  cap = sf.exchange_cap(b)
  assert cap == -(-(-(-b // p)) // 128) * 128 < b   # 128, or 256 of 600
  ids, valid = _requests(case, rng, p, b, n)
  rows, counted = _lookup_counted(sf, ids, valid)
  served = valid
  if cfg.get('hot_only'):
    # cold lanes come back zero, as from the uncapped exchange
    served = valid & (ids % sf.rows_per_shard < sf.hot_count)
    assert served.sum() < valid.sum()
  want = np.where(served[:, None], feats[ids], 0)
  assert rows.dtype == np.float32
  np.testing.assert_array_equal(rows.view(np.uint32), want.view(np.uint32))
  # the counters against a numpy count of the same ids
  per_owner = np.stack([
      np.bincount(ids[lo:lo + b][valid[lo:lo + b]] // sf.rows_per_shard,
                  minlength=p) for lo in range(0, p * b, b)])
  rounds = -(-per_owner.max() // cap)
  np.testing.assert_array_equal(counted['store_rounds'], [rounds] * p)
  np.testing.assert_array_equal(counted['store_bucket_max'],
                                per_owner.max(axis=1))
  np.testing.assert_array_equal(counted['store_requests'],
                                per_owner.sum(axis=1))
  if case == 'one_owner':
    assert rounds == p
  elif case == 'none_valid':
    assert rounds == 0 and not rows.any()
  elif case == 'generator_skew':
    share = per_owner.sum(axis=0) / per_owner.sum()
    assert 0.45 < share[0] < 0.55 and rounds == 1
  assert _store_gauge('feature_store_bucket_cap') == float(cap)
  assert _in_place_gauge() == 0.0
  if sf.cold_array is None and not cfg.get('hot_only'):
    # the host-side API goes through the same program
    np.testing.assert_array_equal(
        np.asarray(sf.lookup(ids, jnp.asarray(valid))), want)


# -- the map between request order and bucket order -------------------------

def _owners(case, rng, p, b):
  """owner [b] of a bucketing case: in [0, p) for a request, p for a pad."""
  owner = rng.integers(0, p, size=b)
  if case == 'live_prefix':        # the four-chip cell's shape in small:
    # a 37 % prefix is live and shard 0 owns half of it
    owner = np.minimum((p * rng.random(b) ** 2).astype(np.int64), p - 1)
    owner[int(0.37 * b):] = p
  elif case == 'scattered_mask':
    owner[rng.random(b) < 0.6] = p
  elif case == 'all_pads':
    owner[:] = p
  elif case == 'one_owner':        # every request on the last owner
    owner[:] = p - 1
  return owner.astype(np.int32)


def _sorted_reference(owner, p, cap, base):
  """What a stable argsort by owner makes of drain round ``base // cap``:
  (order, bucket row, bucket column, packed) in sorted order, and the
  requests an owner."""
  order = np.argsort(owner, kind='stable')
  osort = owner[order]
  counts = np.bincount(np.minimum(osort, p), minlength=p + 1)[:p]
  offsets = np.cumsum(counts) - counts
  pos = np.arange(owner.shape[0]) - offsets[np.minimum(osort, p - 1)] - base
  ok = (osort < p) & (pos >= 0) & (pos < cap)
  return order, osort, pos, ok, counts


_BUCKET_CASES = {
    # case: (b, cap; 0 = b)
    'live_prefix': (4096, 1024),
    'scattered_mask': (1024, 0),
    'all_pads': (640, 256),
    'one_owner': (1024, 256),       # four rounds: P on four owners
    'b_not_a_multiple': (601, 160),  # neither P nor 128 divides b
}


@pytest.mark.parametrize('payload', ['ids', 'pairs', 'bool'])
@pytest.mark.parametrize('p', [1, 2, 4])
@pytest.mark.parametrize('case', list(_BUCKET_CASES))
def test_buckets_are_a_stable_sorts_bit_for_bit(case, p, payload):
  from glt_tpu.parallel.collectives import (bucket_by_owner, bucket_payload,
                                            unbucket)
  b, cap = _BUCKET_CASES[case]
  rng = np.random.default_rng(47)
  owner = _owners(case, rng, p, b)
  eff_cap = cap or b
  values, fill, invalid, resp_shape = {
      'ids': (rng.integers(0, 10_000, b).astype(np.int32), -1, 0,
              (p, eff_cap, 3)),
      'pairs': (rng.integers(0, 10_000, (b, 3)).astype(np.int32), -7, -1,
                (p, eff_cap, 3)),
      'bool': (rng.random(b) < 0.5, False, False, (p, eff_cap)),
  }[payload]
  resp = rng.integers(0, 2, resp_shape) if payload == 'bool' else \
      rng.normal(size=resp_shape)
  resp = resp.astype({'ids': np.float32, 'pairs': np.int32,
                      'bool': bool}[payload])

  first, meta = bucket_by_owner(jnp.asarray(values), jnp.asarray(owner), p,
                                fill_value=fill, capacity=cap)
  _, _, _, _, counts = _sorted_reference(owner, p, eff_cap, 0)
  np.testing.assert_array_equal(np.asarray(meta.counts), counts)
  np.testing.assert_array_equal(np.asarray(meta.owner), owner)
  # the rank is the number of earlier requests with the same owner
  live = owner < p
  earlier = np.array([(owner[:i] == owner[i]).sum() for i in range(b)])
  np.testing.assert_array_equal(np.asarray(meta.rank)[live], earlier[live])

  @jax.jit
  def round_trip(meta, base):   # the offset is traced, as in the drain
    return (bucket_payload(jnp.asarray(values), meta, p, fill_value=fill,
                           capacity=cap, round_offset=base),
            unbucket(jnp.asarray(resp), meta, p, invalid_value=invalid,
                     round_offset=base))

  rounds = max(1, -(-int(counts.max(initial=0)) // eff_cap))
  if case == 'one_owner':
    assert rounds == b // cap == 4   # as many as four owners' worst
  seen = np.zeros(b, bool)
  for k in range(rounds + 1):   # one round past the last packs nothing
    base = k * eff_cap
    order, osort, pos, ok, _ = _sorted_reference(owner, p, eff_cap, base)
    want = np.full((p, eff_cap) + values.shape[1:], fill, values.dtype)
    want[osort[ok], pos[ok]] = values[order][ok]
    stitched = np.full((b,) + resp.shape[2:], invalid, resp.dtype)
    stitched[order[ok]] = resp[osort[ok], pos[ok]]
    got, rows = round_trip(meta, jnp.int32(base))
    assert got.dtype == values.dtype and rows.dtype == resp.dtype
    np.testing.assert_array_equal(np.asarray(got), want)
    if resp.dtype == np.float32:
      np.testing.assert_array_equal(np.asarray(rows).view(np.uint32),
                                    stitched.view(np.uint32))
    else:
      np.testing.assert_array_equal(np.asarray(rows), stitched)
    if k == 0:
      np.testing.assert_array_equal(np.asarray(first), want)
    assert not (seen[order[ok]]).any()
    seen[order[ok]] = True
    assert ok.any() == (k < rounds and live.any())
  np.testing.assert_array_equal(seen, live)   # every request, in one round


_EXCHANGE_CASES = {
    # case: (mask, bucket_cap; 0 = exchange_cap's even share, rounds, b)
    'live_prefix': ('prefix', 0, 1, 520),   # 128 does not divide b
    'scattered_mask': ('scattered', 0, 1, 520),
    'all_pads': ('none', 0, 0, 520),
    'small_cap_several_rounds': ('scattered', 40, None, 520),  # three +
    'one_owner_small_cap': ('one_owner', 100, 6, 520),
    'one_round_holds_all': ('prefix', 10_000, 1, 520),
    # more than two chunks of SERVE_CHUNK request slots a device, and
    # more than two of bucket slots: the pack, serve and stitch loops
    'chunks_live_prefix': ('prefix', 0, 1, 20_000),
    'chunks_scattered_mask': ('scattered', 0, 1, 20_000),
    'chunks_all_pads': ('none', 0, 0, 20_000),
    'chunks_one_owner': ('one_owner', 0, 4, 20_000),
    'chunks_small_cap_several_rounds': ('prefix', 1_000, None, 20_000),
    'chunks_prefix_ends_on_a_chunk': ('two_chunks', 0, 2, 20_000),
}


def _chunks_holding(mask, chunk):
  """Chunks of ``chunk`` consecutive slots of ``mask`` with a True in
  them; up to one chunk of slots is one chunk."""
  n = -(-mask.shape[0] // chunk)
  if n == 1:
    return int(mask.any())
  return int(np.pad(mask, (0, n * chunk - mask.shape[0])).reshape(
      n, chunk).any(axis=1).sum())


def _exchange_chunks(owner, p, cap, rounds, chunk):
  """What a device's pack, serve and stitch gather over the drain, by
  numpy: ``owner`` [p, b] (``p`` for a pad) of every device."""
  rank = np.zeros(owner.shape, np.int64)
  for q in range(p):
    hit = owner == q
    rank = np.where(hit, np.cumsum(hit, axis=1) - 1, rank)
  counts = np.stack([(owner == q).sum(axis=1) for q in range(p)], axis=1)
  got = np.zeros(p, np.int64)
  for k in range(rounds):
    ok = (owner < p) & (rank >= k * cap) & (rank < (k + 1) * cap)
    for d in range(p):
      # device d's buckets hold peer q's requests to d, a prefix each
      held = np.zeros((p, cap), bool)
      for q in range(p):
        held[q, :np.clip(counts[q, d] - k * cap, 0, cap)] = True
      got[d] += (2 * _chunks_holding(ok[d], chunk)
                 + _chunks_holding(held.reshape(-1), chunk))
  return got


@pytest.mark.parametrize('case', list(_EXCHANGE_CASES))
def test_four_shard_lookup_rows_and_counters_for_any_mask_and_cap(case):
  from glt_tpu.parallel.dist_feature import SERVE_CHUNK
  mask, bucket_cap, want_rounds, b = _EXCHANGE_CASES[case]
  p, n, d = 4, 1000, 8
  rng = np.random.default_rng(53)
  feats = rng.normal(size=(n, d)).astype(np.float32)
  feats[::7, 0] = -0.0   # a drain writes rows: a stored -0.0 stays -0.0
  sf = ShardedFeature(feats, make_mesh(p), bucket_cap=bucket_cap)
  cap = sf.exchange_cap(b)
  assert cap == (min(bucket_cap, b) if bucket_cap else
                 -(-(-(-b // p)) // 128) * 128)
  ids = np.floor(n * rng.random(p * b) ** 2).astype(np.int32)
  valid = {
      'prefix': np.tile(np.arange(b) < int(0.37 * b), p),
      'two_chunks': np.tile(np.arange(b) < 2 * SERVE_CHUNK, p),
      'scattered': rng.random(p * b) < 0.4,
      'none': np.zeros(p * b, bool),
      'one_owner': np.ones(p * b, bool),
  }[mask]
  if mask == 'one_owner':
    ids = rng.integers(sf.rows_per_shard, 2 * sf.rows_per_shard,
                       size=p * b).astype(np.int32)
  rows, counted = _lookup_counted(sf, ids, valid)
  # the in-place form's rows: every request's row, the pads zero
  want = np.where(valid[:, None], feats[ids], np.float32(0))
  np.testing.assert_array_equal(rows.view(np.uint32), want.view(np.uint32))
  per_owner = np.stack([
      np.bincount(ids[lo:lo + b][valid[lo:lo + b]] // sf.rows_per_shard,
                  minlength=p) for lo in range(0, p * b, b)])
  rounds = -(-per_owner.max() // cap)
  np.testing.assert_array_equal(counted['store_rounds'], [rounds] * p)
  np.testing.assert_array_equal(counted['store_bucket_max'],
                                per_owner.max(axis=1))
  np.testing.assert_array_equal(counted['store_requests'],
                                per_owner.sum(axis=1))
  assert rounds == want_rounds if want_rounds is not None else rounds >= 3
  owner = np.where(valid, ids // sf.rows_per_shard, p).reshape(p, b)
  np.testing.assert_array_equal(
      counted['store_exchange_chunks'],
      _exchange_chunks(owner, p, cap, rounds, SERVE_CHUNK))
  if b > SERVE_CHUNK and mask in ('prefix', 'two_chunks'):
    # behind a live prefix the loops skip the chunks of pads
    assert (counted['store_exchange_chunks']
            < rounds * sf.exchange_chunks(b)).all()


def _tiny_step(chips):
  from glt_tpu.data import Dataset
  n = 64
  rng = np.random.default_rng(43)
  src = np.repeat(np.arange(n), 3)
  dst = (src + rng.integers(1, n, src.shape[0])) % n
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([src, dst]), num_nodes=n)
  mesh = make_mesh(chips)
  feats = rng.normal(size=(n, 8)).astype(np.float32)
  tx = optax.adam(1e-2)
  step = SPMDSageTrainStep(
      mesh, GraphSAGE(hidden_features=8, out_features=4, num_layers=2), tx,
      ds.get_graph(), ShardedFeature(feats, mesh),
      rng.integers(0, 4, n).astype(np.int32), fanouts=[3, 2],
      batch_size_per_device=64)
  params = step.init_params(jax.random.key(0))
  seeds = rng.integers(0, n, size=chips * 64)
  keys = jax.random.split(jax.random.key(1), chips)
  return step, params, tx.init(params), seeds, np.full(chips, 64), keys


def test_step_hands_back_the_store_counters_over_four_shards():
  step, params, opt, seeds, n_valid, keys = _tiny_step(4)
  with pytest.raises(RuntimeError, match='no per-batch step has run'):
    step.store_counters()
  out = step(params, opt, seeds, n_valid, keys)
  assert len(out) == 3 and np.isfinite(np.asarray(out[2])).all()
  counted = step.store_counters()
  assert sorted(counted) == ['store_bucket_max', 'store_exchange_chunks',
                             'store_requests', 'store_rounds']
  assert all(v.shape == (4,) for v in counted.values())
  b = 64 * (1 + 3 + 6)   # sample_budget(64, [3, 2]) slots a device
  cap = step.feature.exchange_cap(b)
  assert cap == 256 < b
  # one round: the pack, the serve and the stitch, one chunk each here
  slots = step.counter_slots()['store_exchange_chunks']
  assert int(slots) == step.feature.exchange_chunks(b) == 3
  assert (counted['store_exchange_chunks'] == 3).all()
  assert _store_gauge('feature_store_bucket_cap') == 256.0
  # every device found all 64 nodes or fewer, at least its distinct seeds
  assert (counted['store_requests'] <= 64).all()
  assert (counted['store_requests'] >= 30).all()
  assert (counted['store_bucket_max'] <= counted['store_requests']).all()
  assert (counted['store_rounds'] == 1).all()
  # the supersteps keep their outputs and drop the counters
  k = jax.random.split(jax.random.key(2), (2, 4))
  out = step.superstep(out[0], out[1], np.stack([seeds, seeds]),
                       np.stack([n_valid, n_valid]), k)
  assert len(out) == 3 and np.asarray(out[2]).shape == (2, 4)


def test_step_on_one_shard_serves_in_place_and_counts_its_chunks(
    monkeypatch):
  from glt_tpu.parallel import dist_feature
  code_chunk = dist_feature.SERVE_CHUNK
  assert code_chunk > 640

  def two_steps(chunk):
    monkeypatch.setattr(dist_feature, 'SERVE_CHUNK', chunk)
    step, params, opt, seeds, n_valid, keys = _tiny_step(1)
    losses = []
    for _ in range(2):
      params, opt, loss = step(params, opt, seeds, n_valid, keys)
      losses.append(np.asarray(loss))
    return step, losses, jax.tree.map(np.asarray, params)

  b = 64 * (1 + 3 + 6)   # sample_budget(64, [3, 2]) slots
  step, losses, params = two_steps(128)
  assert step.feature.in_place and _in_place_gauge() == 1.0
  counted, slots = step.counters(), step.counter_slots()
  assert sorted(set(counted) - {'step'}) == [
      'edges_by_hop', 'hop_rows_read', 'nodes_by_hop',
      'store_chunks']   # no store_rounds
  assert counted['store_chunks'].shape == (2, 1)
  assert counted['store_chunks'].dtype == np.int32
  assert int(slots['store_chunks']) == 5 == -(-b // 128)
  # the requests are a live prefix of node_count slots: its chunks
  node_count = counted['nodes_by_hop'].sum(-1)
  np.testing.assert_array_equal(counted['store_chunks'],
                                -(-node_count // 128))
  assert (counted['store_chunks'] < 5).all()   # 64 nodes: a chunk is pads
  # what the exchange counts is not there to read
  with pytest.raises(RuntimeError, match='serves in place'):
    step.store_counters()
  # under SERVE_CHUNK slots the requests are one chunk, the parent's
  # plain gather: the training is the same bit for bit
  plain, plain_losses, plain_params = two_steps(code_chunk)
  assert int(plain.counter_slots()['store_chunks']) == 1
  assert (plain.counters()['store_chunks'] == 1).all()
  for got, want in zip(losses, plain_losses):
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
  jax.tree.map(lambda got, want: np.testing.assert_array_equal(
      got.view(np.uint32), want.view(np.uint32)), params, plain_params)
