"""The `pallas_fused` hop engine (ops/pallas_kernels.py::
sample_hop_dedup + dedup_table_insert, routed via
ops/sample.py::FusedHopPlan).

Acceptance contract (ISSUE 10): the fused sample+dedup(+gather)
pipeline is BIT-IDENTICAL to the `sort+fused` engine (GLT_DEDUP=sort
GLT_FUSED_HOP=1) in interpret mode — same labels (new ids in within-hop
value order, seed hop exact), same node list, same counts — with the
documented exception that `edge`/`nbrs` values on MASKED-OUT lanes are
undefined per engine (same contract as tests/test_pallas_hop.py; full
equality holds against a window-read reference, which reads the same
physical slots). Zero steady-state recompiles must hold with the
engine forced, for the plain sampler, the serving engine, and the
stream sampler (which falls back to `pallas` for its overlay hops,
counted in hop_engine_fallbacks_total).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from glt_tpu.data import Topology
from glt_tpu.ops.pipeline import (make_dedup_tables, multihop_sample,
                                  multihop_sample_many, sample_budget)
from glt_tpu.ops.sample import FusedHopPlan, sample_neighbors
from glt_tpu.ops.pallas_kernels import fused_table_slots

from fixtures import ring_dataset

pytestmark = pytest.mark.pallas

W = 8

EXACT_KEYS = ('node', 'node_count', 'row', 'col', 'edge_mask', 'batch',
              'seed_labels', 'seed_count', 'num_sampled_nodes',
              'num_sampled_edges')


def _graph(n=64, e=600, seed=0):
  rng = np.random.default_rng(seed)
  src = rng.integers(0, n, e)
  dst = rng.integers(0, n, e)
  t = Topology(edge_index=np.stack([src, dst]), num_nodes=n)
  indptr = jnp.asarray(t.indptr.astype(np.int32))
  indices = jnp.asarray(t.indices)
  iw = jnp.concatenate([indices, jnp.full((W,), -1, indices.dtype)])
  eids = jnp.arange(indices.shape[0], dtype=jnp.int32) * 3
  ew = jnp.concatenate([eids, jnp.full((W,), -1, eids.dtype)])
  n_hub = int((np.diff(t.indptr) > W).sum())
  return dict(n=n, topo=t, indptr=indptr, indices=indices, iw=iw,
              eids=eids, ew=ew, n_hub=n_hub)


def _plan(g, fanouts, batch, with_edge=False, replace=False,
          **gather_kw):
  return FusedHopPlan(
      g['indptr'], g['indices'], g['iw'], W, g['n_hub'],
      fused_table_slots(sample_budget(batch, list(fanouts))),
      edge_ids=g['eids'] if with_edge else None,
      edge_ids_win=g['ew'] if with_edge else None,
      replace=replace, interpret=True, **gather_kw)


def _ref_sort_fused(g, seeds, nv, fanouts, key, monkeypatch,
                    with_edge=False, window_read=False, replace=False):
  """The reference engine: GLT_DEDUP=sort + GLT_FUSED_HOP=1.
  window_read=True reads neighbor values through the same padded
  windows as the kernel, making even masked-lane junk identical."""
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  kw = {}
  if window_read:
    kw = dict(window=(W, None), indices_win=g['iw'],
              edge_ids_win=g['ew'] if with_edge else None,
              engine='window')
  def one_hop(ids, f, k, m):
    w = dict(kw)
    if window_read:
      w['window'] = (W, min(g['n_hub'], ids.shape[0]))
    return sample_neighbors(
        g['indptr'], g['indices'], ids, f, k, seed_mask=m,
        edge_ids=g['eids'] if with_edge else None, replace=replace, **w)
  table, scratch = make_dedup_tables(g['n'])
  out, _, _ = multihop_sample(one_hop, seeds, nv, fanouts, key, table,
                              scratch, with_edge=with_edge)
  monkeypatch.delenv('GLT_DEDUP')
  monkeypatch.delenv('GLT_FUSED_HOP')
  return jax.tree.map(np.asarray, out)


@pytest.mark.parametrize('with_edge', [False, True])
@pytest.mark.parametrize('fanouts', [(3,), (3, 2)])
def test_multihop_bit_identical_to_sort_fused(monkeypatch, fanouts,
                                              with_edge):
  g = _graph()
  seeds = jnp.asarray(np.array([5, 0, 5, 17, 63, 2, 2, 9], np.int32))
  nv = jnp.asarray(7)
  key = jax.random.key(0)
  ref = _ref_sort_fused(g, seeds, nv, fanouts, key, monkeypatch,
                        with_edge=with_edge)
  table, scratch = make_dedup_tables(g['n'])
  got, _, _ = multihop_sample(
      None, seeds, nv, fanouts, key, table, scratch,
      with_edge=with_edge,
      fused_plan=_plan(g, fanouts, seeds.shape[0], with_edge=with_edge))
  for k in EXACT_KEYS:
    np.testing.assert_array_equal(ref[k], np.asarray(got[k]),
                                  err_msg=k)
  if with_edge:
    m = ref['edge_mask'].astype(bool)
    np.testing.assert_array_equal(ref['edge'][m],
                                  np.asarray(got['edge'])[m])


def test_edge_full_parity_vs_window_reference(monkeypatch):
  # against a window-read reference even the masked-lane junk matches:
  # both engines read the same physical window slots
  g = _graph(seed=3)
  seeds = jnp.asarray(np.arange(10, dtype=np.int32))
  nv = jnp.asarray(10)
  key = jax.random.key(1)
  fanouts = (3, 2)
  ref = _ref_sort_fused(g, seeds, nv, fanouts, key, monkeypatch,
                        with_edge=True, window_read=True)
  table, scratch = make_dedup_tables(g['n'])
  got, _, _ = multihop_sample(
      None, seeds, nv, fanouts, key, table, scratch, with_edge=True,
      fused_plan=_plan(g, fanouts, seeds.shape[0], with_edge=True))
  np.testing.assert_array_equal(ref['edge'], np.asarray(got['edge']))


def test_replace_and_empty_frontier(monkeypatch):
  g = _graph(seed=5)
  fanouts = (4,)
  seeds = jnp.asarray(np.array([1, 2, 3, 4], np.int32))
  key = jax.random.key(2)
  # sampling WITH replacement
  ref = _ref_sort_fused(g, seeds, jnp.asarray(4), fanouts, key,
                        monkeypatch, replace=True)
  table, scratch = make_dedup_tables(g['n'])
  got, _, _ = multihop_sample(
      None, seeds, jnp.asarray(4), fanouts, key, table, scratch,
      fused_plan=_plan(g, fanouts, 4, replace=True))
  for k in EXACT_KEYS:
    np.testing.assert_array_equal(ref[k], np.asarray(got[k]), err_msg=k)
  # fully-masked batch (n_valid = 0): every surface empty/-1, both
  ref0 = _ref_sort_fused(g, seeds, jnp.asarray(0), fanouts, key,
                         monkeypatch)
  got0, _, _ = multihop_sample(
      None, seeds, jnp.asarray(0), fanouts, key, table, scratch,
      fused_plan=_plan(g, fanouts, 4))
  for k in EXACT_KEYS:
    np.testing.assert_array_equal(ref0[k], np.asarray(got0[k]),
                                  err_msg=k)
  assert int(got0['node_count']) == 0


def test_multihop_many_scan_parity(monkeypatch):
  # the lax.scan entry point (bench scan>1): fresh VMEM table per scan
  # step, results identical to per-batch fused calls
  g = _graph(seed=7)
  fanouts = (3, 2)
  seeds = jnp.asarray(
      np.random.default_rng(0).integers(0, g['n'], (3, 6)).astype(
          np.int32))
  nv = jnp.full((3,), 6, jnp.int32)
  key = jax.random.key(4)
  plan = _plan(g, fanouts, 6)
  table, scratch = make_dedup_tables(g['n'])
  outs, _, _ = multihop_sample_many(None, seeds, nv, fanouts, key,
                                    table, scratch, fused_plan=plan)
  k = key
  for t in range(3):
    k, sub = jax.random.split(k)
    one, _, _ = multihop_sample(None, seeds[t], nv[t], fanouts, sub,
                                table, scratch, fused_plan=plan)
    np.testing.assert_array_equal(np.asarray(outs['node'])[t],
                                  np.asarray(one['node']))
    np.testing.assert_array_equal(np.asarray(outs['row'])[t],
                                  np.asarray(one['row']))


# -- sampler / serving / stream wiring ----------------------------------

def test_sampler_forced_engine_parity_and_zero_recompiles(monkeypatch):
  from glt_tpu.sampler import NeighborSampler
  ds = ring_dataset(num_nodes=40)
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  seeds = np.arange(8)
  base = NeighborSampler(ds.get_graph(), [3, 2], seed=0,
                         with_edge=True).sample_from_nodes(seeds)
  monkeypatch.delenv('GLT_DEDUP')
  monkeypatch.delenv('GLT_FUSED_HOP')
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0, with_edge=True)
  out = samp.sample_from_nodes(seeds)
  for f in ('node', 'row', 'col', 'edge_mask', 'batch'):
    np.testing.assert_array_equal(np.asarray(getattr(base, f)),
                                  np.asarray(getattr(out, f)),
                                  err_msg=f)
  m = np.asarray(base.edge_mask).astype(bool)
  np.testing.assert_array_equal(np.asarray(base.edge)[m],
                                np.asarray(out.edge)[m])
  assert samp.num_compiled_fns == 1
  for _ in range(3):   # steady state: the one program serves every call
    samp.sample_from_nodes(seeds)
  assert samp.num_compiled_fns == 1


def test_two_batch_shapes_share_the_padded_arrays(monkeypatch):
  # regression mirror of test_pallas_hop: window_arrays must stay
  # concrete across two trace-time plan builds over the same graph
  from glt_tpu.sampler import NeighborSampler
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  ds = ring_dataset(num_nodes=40)
  samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0)
  out4 = samp.sample_from_nodes(np.arange(4))    # trace 1
  out8 = samp.sample_from_nodes(np.arange(8))    # trace 2: same graph
  assert samp.num_compiled_fns == 2
  assert int(out4.node_count) > 0 and int(out8.node_count) > 0


def test_fused_gather_matches_gather_features(monkeypatch):
  # in-walk gather == post-hoc gather_features, EVERY lane including
  # the -1 padding, and the row_gather override rides the fused path
  from glt_tpu.data.feature import gather_features
  from glt_tpu.sampler import NeighborSampler
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  ds = ring_dataset(num_nodes=40)
  feat = ds.get_node_feature()
  calls = {'n': 0}

  def counting_row_gather(table, rows):
    calls['n'] += 1  # trace-time counter: the override must be USED
    return jnp.take(table, jnp.clip(rows, 0, table.shape[0] - 1),
                    axis=0)

  samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0,
                         fused_feature=feat,
                         row_gather=counting_row_gather)
  out = samp.sample_from_nodes(np.arange(8))
  assert calls['n'] > 0, 'row_gather override never reached'
  fused_x = out.metadata['node_feats']
  ref_x = gather_features(feat, out.node)
  np.testing.assert_array_equal(np.asarray(ref_x), np.asarray(fused_x))


def test_serving_engine_fused_parity_and_zero_recompiles(monkeypatch):
  # the serving call site composes: a fused sampler's node_feats ride
  # gather_features(fused=) into the bucket pipeline; embeddings match
  # the sort+fused engine and warmup compiles stay flat
  from glt_tpu.serving import InferenceEngine
  from glt_tpu.sampler import NeighborSampler
  ds = ring_dataset(num_nodes=40)
  apply_fn = lambda params, batch: batch.x[:, :4] * 2.0

  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  base = InferenceEngine(ds, model=None, params={}, num_neighbors=[3, 2],
                         buckets=(8,), apply_fn=apply_fn, seed=0,
                         cache_capacity=0)
  base.warmup()
  want = base.infer(np.arange(6))
  monkeypatch.delenv('GLT_DEDUP')
  monkeypatch.delenv('GLT_FUSED_HOP')

  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0,
                         fused_feature=ds.get_node_feature())
  eng = InferenceEngine(ds, model=None, params={}, num_neighbors=[3, 2],
                        buckets=(8,), apply_fn=apply_fn,
                        sampler=samp, cache_capacity=0)
  eng.warmup()
  got = eng.infer(np.arange(6))
  np.testing.assert_array_equal(want, got)
  stats = eng.compile_stats()
  for _ in range(4):
    eng.infer(np.arange(6))
  assert eng.compile_stats()['forward_traces'] == \
      stats['forward_traces']
  assert eng.compile_stats()['sampler_compiled_fns'] == \
      stats['sampler_compiled_fns']


def test_stream_forced_engine_fallback_parity_and_counter(monkeypatch):
  # forcing pallas_fused on the stream path must keep working (counted
  # demotion to pallas for the overlay hops) with zero steady-state
  # recompiles across overlay refreshes and snapshot swaps
  from glt_tpu.obs import MetricsRegistry, get_registry, set_registry
  from glt_tpu.stream import (EdgeDeltaBuffer, SnapshotManager,
                              StreamSampler)
  prev = set_registry(MetricsRegistry())
  try:
    N = 24
    ds = ring_dataset(num_nodes=N)
    mgr = SnapshotManager(ds.get_graph().topo, ds.get_node_feature(),
                          delta_capacity=64)
    seeds = np.arange(6)
    # pin the base to the sorted inducer: forcing pallas_fused implies
    # the sort dedup contract, and the sorted EXACT path permutes edge
    # tuples within a hop block vs the table engine (documented) — the
    # comparison must be like-for-like
    monkeypatch.setenv('GLT_DEDUP', 'sort')
    base = StreamSampler(mgr, [3, 2], seed=0).sample_from_nodes(seeds)
    monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
    monkeypatch.setenv('GLT_WINDOW_W', '8')
    samp = StreamSampler(mgr, [3, 2], seed=0)
    out = samp.sample_from_nodes(seeds)
    for f in ('node', 'row', 'col', 'edge_mask', 'batch'):
      np.testing.assert_array_equal(np.asarray(getattr(base, f)),
                                    np.asarray(getattr(out, f)),
                                    err_msg=f)
    fb = get_registry().get('hop_engine_fallbacks_total',
                            requested='pallas_fused',
                            resolved='pallas', reason='stream_overlay')
    assert fb == 1.0
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
    # the demotion is counted once per sampler, not per call
    assert get_registry().get('hop_engine_fallbacks_total',
                              requested='pallas_fused',
                              resolved='pallas',
                              reason='stream_overlay') == 1.0
  finally:
    set_registry(prev)


def test_fallback_counters_for_unservable_shapes(monkeypatch):
  from glt_tpu.obs import MetricsRegistry, get_registry, set_registry
  from glt_tpu.sampler import NeighborSampler
  prev = set_registry(MetricsRegistry())
  try:
    monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
    monkeypatch.setenv('GLT_WINDOW_W', '8')
    ds = ring_dataset(num_nodes=40)
    # weighted sampling cannot fuse
    NeighborSampler(ds.get_graph(), [3], seed=0,
                    with_weight=True).sample_from_nodes(np.arange(4))
    assert get_registry().get('hop_engine_fallbacks_total',
                              requested='pallas_fused',
                              resolved='pallas', reason='weighted') == 1
    # a dedup table past the VMEM sizing knob cannot fuse — but the
    # demoted engine still samples correctly
    monkeypatch.setenv('GLT_FUSED_TABLE_SLOTS', '512')
    samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0)
    out = samp.sample_from_nodes(np.arange(8))
    assert int(out.node_count) > 0
    assert get_registry().get('hop_engine_fallbacks_total',
                              requested='pallas_fused',
                              resolved='pallas',
                              reason='table_overflow') == 1
  finally:
    set_registry(prev)


# -- cross-hop fused walk (ISSUE 13) ------------------------------------
#
# GLT_FUSED_WALK=cross runs the WHOLE walk as one sample_walk_dedup
# kernel (table resident in VMEM across hops); auto resolves to per_hop
# under interpret mode, so every cross-walk test forces the knob.


def test_walk_bit_identical_to_sort_fused(monkeypatch):
  monkeypatch.setenv('GLT_FUSED_WALK', 'cross')
  g = _graph(seed=2)
  seeds = jnp.asarray(np.array([5, 0, 5, 17, 63, 2, 2, 9], np.int32))
  nv = jnp.asarray(7)
  key = jax.random.key(9)
  fanouts = (3, 2)
  ref = _ref_sort_fused(g, seeds, nv, fanouts, key, monkeypatch,
                        with_edge=True)
  table, scratch = make_dedup_tables(g['n'])
  got, _, _ = multihop_sample(
      None, seeds, nv, fanouts, key, table, scratch, with_edge=True,
      fused_plan=_plan(g, fanouts, seeds.shape[0], with_edge=True))
  for k in EXACT_KEYS:
    np.testing.assert_array_equal(ref[k], np.asarray(got[k]),
                                  err_msg=k)
  m = ref['edge_mask'].astype(bool)
  np.testing.assert_array_equal(ref['edge'][m],
                                np.asarray(got['edge'])[m])


@pytest.mark.slow  # interpret-mode walk traces are minutes on 1 CPU;
                   # the pallas-interpret CI job (-m pallas) runs this
def test_walk_full_window_parity_and_replace(monkeypatch):
  # against a window-read reference even masked-lane junk matches (the
  # walk reads the same physical window slots, incl. duplicate-seed
  # rows which keep their REAL windows on hop 1); replace rides the
  # in-kernel replace offset formula
  monkeypatch.setenv('GLT_FUSED_WALK', 'cross')
  g = _graph(seed=3)
  seeds = jnp.asarray(np.arange(10, dtype=np.int32))
  nv = jnp.asarray(10)
  key = jax.random.key(1)
  fanouts = (3, 2)
  ref = _ref_sort_fused(g, seeds, nv, fanouts, key, monkeypatch,
                        with_edge=True, window_read=True)
  table, scratch = make_dedup_tables(g['n'])
  got, _, _ = multihop_sample(
      None, seeds, nv, fanouts, key, table, scratch, with_edge=True,
      fused_plan=_plan(g, fanouts, seeds.shape[0], with_edge=True))
  np.testing.assert_array_equal(ref['edge'], np.asarray(got['edge']))
  # replace draw, plus a fully-masked batch through the walk
  refr = _ref_sort_fused(g, seeds, jnp.asarray(4), (4,), key,
                         monkeypatch, replace=True)
  gotr, _, _ = multihop_sample(
      None, seeds, jnp.asarray(4), (4,), key, table, scratch,
      fused_plan=_plan(g, (4,), seeds.shape[0], replace=True))
  for k in EXACT_KEYS:
    np.testing.assert_array_equal(refr[k], np.asarray(gotr[k]),
                                  err_msg=k)
  got0, _, _ = multihop_sample(
      None, seeds, jnp.asarray(0), fanouts, key, table, scratch,
      fused_plan=_plan(g, fanouts, seeds.shape[0]))
  assert int(got0['node_count']) == 0


@pytest.mark.slow  # see test_walk_full_window_parity_and_replace
def test_walk_scan_entry_parity(monkeypatch):
  # the lax.scan entry point: the walk kernel sits inside the batch
  # scan body; each step's table is kernel-local scratch, so
  # iterations are independent by construction
  monkeypatch.setenv('GLT_FUSED_WALK', 'cross')
  g = _graph(seed=7)
  fanouts = (3, 2)
  seeds = jnp.asarray(
      np.random.default_rng(0).integers(0, g['n'], (3, 6)).astype(
          np.int32))
  nv = jnp.full((3,), 6, jnp.int32)
  key = jax.random.key(4)
  plan = _plan(g, fanouts, 6)
  table, scratch = make_dedup_tables(g['n'])
  outs, _, _ = multihop_sample_many(None, seeds, nv, fanouts, key,
                                    table, scratch, fused_plan=plan)
  k = key
  for t in range(3):
    k, sub = jax.random.split(k)
    one, _, _ = multihop_sample(None, seeds[t], nv[t], fanouts, sub,
                                table, scratch, fused_plan=plan)
    np.testing.assert_array_equal(np.asarray(outs['node'])[t],
                                  np.asarray(one['node']))
    np.testing.assert_array_equal(np.asarray(outs['row'])[t],
                                  np.asarray(one['row']))


def test_walk_fused_gather_and_bf16_plane(monkeypatch):
  # in-walk gather through the cross-hop walk == post-hoc
  # gather_features on every lane; the opt-in bf16 plane narrows the
  # emitted block (values == reference cast) without touching the
  # default path
  from glt_tpu.data.feature import gather_features
  from glt_tpu.sampler import NeighborSampler
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
  monkeypatch.setenv('GLT_FUSED_WALK', 'cross')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  ds = ring_dataset(num_nodes=40)
  feat = ds.get_node_feature()
  samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0,
                         fused_feature=feat)
  out = samp.sample_from_nodes(np.arange(8))
  fused_x = out.metadata['node_feats']
  ref_x = gather_features(feat, out.node)
  np.testing.assert_array_equal(np.asarray(ref_x), np.asarray(fused_x))

  monkeypatch.setenv('GLT_FUSED_FEAT_DTYPE', 'bfloat16')
  samp16 = NeighborSampler(ds.get_graph(), [3, 2], seed=0,
                           fused_feature=feat)
  out16 = samp16.sample_from_nodes(np.arange(8))
  x16 = out16.metadata['node_feats']
  assert x16.dtype == jnp.bfloat16
  np.testing.assert_array_equal(
      np.asarray(ref_x.astype(jnp.bfloat16), dtype=np.float32),
      np.asarray(x16, dtype=np.float32))


def test_walk_stream_zero_recompile_across_refresh_and_swap(
    monkeypatch):
  # the scan-carried walk forced on the stream path: overlay hops
  # demote to pallas (counted once) and the zero-steady-state-
  # recompile contract holds across overlay refreshes AND snapshot
  # swaps, mirroring tests/test_stream.py
  from glt_tpu.obs import MetricsRegistry, get_registry, set_registry
  from glt_tpu.stream import (EdgeDeltaBuffer, SnapshotManager,
                              StreamSampler)
  prev = set_registry(MetricsRegistry())
  try:
    N = 24
    ds = ring_dataset(num_nodes=N)
    mgr = SnapshotManager(ds.get_graph().topo, ds.get_node_feature(),
                          delta_capacity=64)
    seeds = np.arange(6)
    monkeypatch.setenv('GLT_DEDUP', 'sort')
    monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
    monkeypatch.setenv('GLT_FUSED_WALK', 'cross')
    monkeypatch.setenv('GLT_WINDOW_W', '8')
    samp = StreamSampler(mgr, [3, 2], seed=0)
    samp.sample_from_nodes(seeds)
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
    assert get_registry().get('hop_engine_fallbacks_total',
                              requested='pallas_fused',
                              resolved='pallas',
                              reason='stream_overlay') == 1.0
  finally:
    set_registry(prev)


def test_walk_launch_collapse_and_table_gauges(monkeypatch):
  # the O(hops)->O(1) launch collapse is an assertable number: the
  # per-hop program traces hops+1 kernel entries (seed insert + one
  # per hop), the walk exactly one; the fused-table geometry gauges
  # land in the registry at plan build and occupancy under the opt-in
  from glt_tpu.obs import MetricsRegistry, get_registry, set_registry
  from glt_tpu.ops.pallas_kernels import kernel_launch_count
  from glt_tpu.sampler import NeighborSampler
  prev = set_registry(MetricsRegistry())
  try:
    g = _graph(seed=4)
    seeds = jnp.asarray(np.arange(8, dtype=np.int32))
    nv = jnp.asarray(8)
    fanouts = (3, 2)
    table, scratch = make_dedup_tables(g['n'])

    def count_traced_launches(walk_mode):
      monkeypatch.setenv('GLT_FUSED_WALK', walk_mode)
      plan = _plan(g, fanouts, 8)

      def f(s, k):
        out, _, _ = multihop_sample(None, s, nv, fanouts, k, table,
                                    scratch, fused_plan=plan)
        return out['node_count']

      # the counter bumps per NEW trace of a kernel wrapper — an inner
      # jit-cache hit (same kernel, same shapes, earlier test) would
      # silently undercount, so count against a cold cache
      jax.clear_caches()
      before = kernel_launch_count()
      jax.jit(f).lower(seeds, jax.random.key(0))
      return kernel_launch_count() - before

    assert count_traced_launches('per_hop') == len(fanouts) + 1
    assert count_traced_launches('cross') == 1

    monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
    monkeypatch.setenv('GLT_WINDOW_W', '8')
    monkeypatch.setenv('GLT_OBS_TABLE_OCCUPANCY', '1')
    ds = ring_dataset(num_nodes=40)
    samp = NeighborSampler(ds.get_graph(), [3, 2], seed=0)
    out = samp.sample_from_nodes(np.arange(8))
    reg = get_registry()
    slots = reg.get('fused_table_slots')
    assert slots > 0
    assert reg.get('fused_table_vmem_bytes') == 2 * slots * 4
    assert reg.get('fused_table_occupancy_hwm') == float(
        int(out.node_count))
    assert 0 < reg.get('fused_table_occupancy_ratio_hwm') <= 1.0
  finally:
    set_registry(prev)


def test_walk_demotes_to_per_hop_for_slot_eids(monkeypatch):
  # with_edge over a graph WITHOUT an edge-id plane: the eids contract
  # is raw CSR slots, which the walk never materializes — the fused
  # path must quietly stay per-hop and keep the slot contract
  monkeypatch.setenv('GLT_FUSED_WALK', 'cross')
  g = _graph(seed=6)
  seeds = jnp.asarray(np.arange(6, dtype=np.int32))
  nv = jnp.asarray(6)
  key = jax.random.key(3)
  fanouts = (3,)
  plan = FusedHopPlan(
      g['indptr'], g['indices'], g['iw'], W, g['n_hub'],
      fused_table_slots(sample_budget(6, list(fanouts))),
      interpret=True)  # no edge_ids plane
  table, scratch = make_dedup_tables(g['n'])
  got, _, _ = multihop_sample(None, seeds, nv, fanouts, key, table,
                              scratch, with_edge=True,
                              fused_plan=plan)
  ref = _ref_sort_fused(g, seeds, nv, fanouts, key, monkeypatch,
                        with_edge=False)
  for k in EXACT_KEYS:
    np.testing.assert_array_equal(ref[k], np.asarray(got[k]),
                                  err_msg=k)
  assert 'edge' in got  # slot-contract eids still emitted


# -- hetero: one multi-edge-type kernel invocation per hop (ISSUE 14) --
#
# The pallas_fused engine serves HETERO walks: each hop's per-edge-type
# sampling is batched into ONE padded sample_hop_dedup invocation over
# the flat edge-type plane (type-tagged global ids = per-type dedup
# namespaces in one VMEM table). Parity target: the per-edge-type
# sorted reference, GLT_DEDUP=sort GLT_FUSED_HOP=1.

U2I = ('user', 'u2i', 'item')
I2I = ('item', 'i2i', 'item')

HETERO_NODE_KEYS = ('node', 'node_count', 'num_sampled_nodes')
HETERO_EDGE_KEYS = ('row', 'col', 'edge_mask', 'num_sampled_edges')


def _hetero_ref_vs_fused(ds, nn, inputs, nv, monkeypatch, seed=4,
                         with_edge=False, **sampler_kw):
  from glt_tpu.sampler import NeighborSampler
  monkeypatch.delenv('GLT_HOP_ENGINE', raising=False)
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  base = NeighborSampler(
      ds.graph, nn, seed=seed, with_edge=with_edge,
      **sampler_kw)._hetero_sample_from_nodes(inputs, n_valid=nv)
  monkeypatch.delenv('GLT_DEDUP')
  monkeypatch.delenv('GLT_FUSED_HOP')
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  samp = NeighborSampler(ds.graph, nn, seed=seed, with_edge=with_edge,
                         **sampler_kw)
  out = samp._hetero_sample_from_nodes(inputs, n_valid=nv)
  return base, out, samp


def _assert_hetero_identical(base, out, with_edge=False):
  for t in base.node:
    for k in HETERO_NODE_KEYS:
      np.testing.assert_array_equal(
          np.asarray(getattr(base, k)[t]),
          np.asarray(getattr(out, k)[t]), err_msg=f'{k}[{t}]')
  for e in base.row:
    for k in HETERO_EDGE_KEYS:
      np.testing.assert_array_equal(
          np.asarray(getattr(base, k)[e]),
          np.asarray(getattr(out, k)[e]), err_msg=f'{k}[{e}]')
    if with_edge:
      m = np.asarray(base.edge_mask[e]).astype(bool)
      np.testing.assert_array_equal(np.asarray(base.edge[e])[m],
                                    np.asarray(out.edge[e])[m],
                                    err_msg=f'edge[{e}]')
  for t in base.batch:
    np.testing.assert_array_equal(np.asarray(base.batch[t]),
                                  np.asarray(out.batch[t]),
                                  err_msg=f'batch[{t}]')
    np.testing.assert_array_equal(
        np.asarray(base.metadata['seed_labels'][t]),
        np.asarray(out.metadata['seed_labels'][t]),
        err_msg=f'seed_labels[{t}]')


def _hub_hetero_dataset(nu=8, ni=24, hub_deg=14):
  """item 0 is a HUB in i2i (degree > the forced W=8); every other row
  in both types stays far below the window — the hub fix-up must fire
  for exactly one type's segment of the concatenated frontier."""
  from glt_tpu.data import Dataset
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2 * u, 2 * u + 1], 1).reshape(-1) % ni])
  hub_dst = (np.arange(hub_deg) + 1) % ni
  i = np.arange(1, ni)
  i2i_ei = np.stack([
      np.concatenate([np.zeros(hub_deg, np.int64), i]),
      np.concatenate([hub_dst, (i + 1) % ni])])
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index={U2I: u2i_ei, I2I: i2i_ei},
                num_nodes={'user': nu, 'item': ni})
  return ds


@pytest.mark.slow  # two full hetero program traces per param on 1 CPU;
                   # the pallas-interpret CI job (-m pallas) runs it
@pytest.mark.parametrize('with_edge', [False, True])
def test_hetero_bit_identical_to_per_etype_sorted_ref(monkeypatch,
                                                      with_edge):
  from fixtures import hetero_ring_dataset
  ds = hetero_ring_dataset(num_users=10, num_items=20)
  seeds = np.array([3, 0, 3, 7, 9, 1], np.int64)  # duplicate seeds
  base, out, _ = _hetero_ref_vs_fused(
      ds, {U2I: [2, 2], I2I: [2, 2]}, ('user', seeds), 5, monkeypatch,
      with_edge=with_edge)
  _assert_hetero_identical(base, out, with_edge=with_edge)


def test_hetero_hub_rows_in_one_type_only(monkeypatch):
  ds = _hub_hetero_dataset()
  seeds = np.array([3, 0, 3, 7], np.int64)
  base, out, _ = _hetero_ref_vs_fused(
      ds, {U2I: [2, 2], I2I: [3, 2]}, ('user', seeds), 4, monkeypatch)
  _assert_hetero_identical(base, out)


def test_hetero_empty_frontier_and_n_valid_zero(monkeypatch):
  ds = _hub_hetero_dataset()
  seeds = np.array([3, 0, 3, 7], np.int64)
  base, out, _ = _hetero_ref_vs_fused(
      ds, {U2I: [2, 2], I2I: [3, 2]}, ('user', seeds), 0, monkeypatch)
  _assert_hetero_identical(base, out)
  assert all(int(c) == 0 for c in
             jax.tree_util.tree_leaves(out.node_count))


def test_hetero_zero_budget_type_and_empty_etype(monkeypatch):
  from glt_tpu.data import Dataset
  nu, ni = 6, 12
  u = np.arange(nu)
  u2i_ei = np.stack([np.repeat(u, 2),
                     np.stack([2 * u, 2 * u + 1], 1).reshape(-1) % ni])
  # zero-budget type: nothing ever expands INTO 'user', so its caps
  # are 0 past hop 0 and the u2i frontier dies after hop 1
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index={U2I: u2i_ei},
                num_nodes={'user': nu, 'item': ni})
  base, out, _ = _hetero_ref_vs_fused(
      ds, {U2I: [2, 2]}, ('user', np.array([1, 2, 5], np.int64)), 3,
      monkeypatch)
  _assert_hetero_identical(base, out)
  # empty per-type frontier via a zero-EDGE etype: i2i exists in the
  # schema but holds no edges — its segments ride the invocation as
  # all-invalid lanes, exactly the reference's _empty_output chunks
  ds2 = Dataset(edge_dir='out')
  ds2.init_graph(edge_index={U2I: u2i_ei, I2I: np.zeros((2, 0),
                                                        np.int64)},
                 num_nodes={'user': nu, 'item': ni})
  base2, out2, _ = _hetero_ref_vs_fused(
      ds2, {U2I: [2, 2], I2I: [2, 2]},
      ('user', np.array([1, 2, 5], np.int64)), 3, monkeypatch)
  _assert_hetero_identical(base2, out2)


@pytest.mark.slow  # 4 hetero program traces; runs in the -m pallas job
def test_hetero_two_type_seeding_and_mixed_fanouts(monkeypatch):
  from fixtures import hetero_ring_dataset
  ds = hetero_ring_dataset(num_users=10, num_items=20)
  base, out, _ = _hetero_ref_vs_fused(
      ds, {U2I: [2, 2], I2I: [2, 2]},
      {'user': np.array([1, 2, 5], np.int64),
       'item': np.array([0, 7, 7, 3], np.int64)}, 3, monkeypatch)
  _assert_hetero_identical(base, out)
  # per-etype fanouts differ: the K_max offset/validity padding path
  base2, out2, _ = _hetero_ref_vs_fused(
      ds, {U2I: [3, 1], I2I: [1, 2]},
      ('user', np.array([4, 4, 0, 9], np.int64)), 4, monkeypatch)
  _assert_hetero_identical(base2, out2)


def test_hetero_sampler_zero_recompiles_and_honest_fallbacks(
    monkeypatch):
  # hetero is SERVED by the fused family: no `hetero` fallback reason
  # fires for a plain hetero sampler, the one compiled program serves
  # every steady-state call, and the specific reasons (weighted,
  # table_overflow) keep firing with the requested label honest
  from fixtures import hetero_ring_dataset
  from glt_tpu.obs import MetricsRegistry, get_registry, set_registry
  from glt_tpu.sampler import NeighborSampler
  prev = set_registry(MetricsRegistry())
  try:
    monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
    monkeypatch.setenv('GLT_WINDOW_W', '8')
    ds = hetero_ring_dataset(num_users=10, num_items=20)
    samp = NeighborSampler(ds.graph, {U2I: [2, 2], I2I: [2, 2]},
                           seed=0)
    seeds = np.arange(6)
    samp._hetero_sample_from_nodes(('user', seeds))
    assert samp.num_compiled_fns == 1
    for _ in range(3):
      samp._hetero_sample_from_nodes(('user', seeds))
    assert samp.num_compiled_fns == 1
    snap = get_registry().snapshot()
    hetero_fb = [k for k in snap['counters']
                 if 'hop_engine_fallbacks_total' in k
                 and 'hetero' in k]
    assert not hetero_fb, hetero_fb
    # a table past the VMEM sizing knob is a SPECIFIC reason (never
    # the blanket `hetero`), requested label honest
    monkeypatch.setenv('GLT_FUSED_TABLE_SLOTS', '512')
    osamp = NeighborSampler(ds.graph, {U2I: [4, 4], I2I: [4, 4]},
                            seed=0)
    out = osamp._hetero_sample_from_nodes(('user', np.arange(8)))
    assert int(out.node_count['item']) > 0  # demoted engine still works
    assert get_registry().get('hop_engine_fallbacks_total',
                              requested='pallas_fused',
                              resolved='pallas',
                              reason='table_overflow') == 1
  finally:
    set_registry(prev)


@pytest.mark.slow  # two serving warmups (4 program traces); -m pallas job
def test_hetero_serving_parity_and_zero_recompiles(monkeypatch):
  # hetero bucket serving (input_type seeding, HeteroBatch forward):
  # embeddings match the per-etype sorted reference and warmup
  # compiles stay flat with the fused hetero engine forced
  from fixtures import hetero_ring_dataset
  from glt_tpu.serving import InferenceEngine
  ds = hetero_ring_dataset(num_users=10, num_items=20)
  nn = {U2I: [2, 2], I2I: [2, 2]}
  apply_fn = lambda params, batch: \
      batch.x_dict['user'][:batch.batch_size, :4] * 2.0

  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  base = InferenceEngine(ds, model=None, params={}, num_neighbors=nn,
                         buckets=(8,), apply_fn=apply_fn, seed=0,
                         cache_capacity=0, input_type='user')
  base.warmup()
  want = base.infer(np.arange(6))
  monkeypatch.delenv('GLT_DEDUP')
  monkeypatch.delenv('GLT_FUSED_HOP')

  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  eng = InferenceEngine(ds, model=None, params={}, num_neighbors=nn,
                        buckets=(8,), apply_fn=apply_fn, seed=0,
                        cache_capacity=0, input_type='user')
  eng.warmup()
  got = eng.infer(np.arange(6))
  np.testing.assert_array_equal(want, got)
  stats = eng.compile_stats()
  for _ in range(4):
    eng.infer(np.arange(6))
  assert eng.compile_stats()['forward_traces'] == \
      stats['forward_traces']
  assert eng.compile_stats()['sampler_compiled_fns'] == \
      stats['sampler_compiled_fns']


@pytest.mark.slow  # whole-superstep scan trace in interpret; -m pallas job
def test_hetero_superstep_scan_parity_and_one_trace(monkeypatch):
  # K hetero batches in ONE dispatch (multihop_sample_hetero_many):
  # results identical to K per-batch calls on the same key stream,
  # one trace serves every superstep call — the dispatch collapse the
  # bench records as dispatches_per_step 1 -> 1/K
  from fixtures import hetero_ring_dataset
  from glt_tpu.ops.pipeline import (multihop_sample_hetero,
                                    multihop_sample_hetero_many)
  from glt_tpu.sampler import NeighborSampler
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
  monkeypatch.setenv('GLT_WINDOW_W', '8')
  ds = hetero_ring_dataset(num_users=10, num_items=20)
  nn = {U2I: [2, 2], I2I: [2, 2]}
  samp = NeighborSampler(ds.graph, nn, seed=0)
  batch_sizes = {'user': 6}
  trav = samp._traversal_types()
  caps, budgets = samp._hetero_caps(batch_sizes)
  plan = samp._hetero_fused_plan(batch_sizes)
  assert plan is not None
  one_hops = {e: (lambda ids, f, k, m, _e=e: samp._one_hop(
      samp.graph[_e], ids, f, k, m)) for e in samp.edge_types}
  tables = {t: samp._get_tables(t, n)
            for t, n in samp._node_counts.items()}
  T = 3
  seeds = jnp.asarray(np.random.default_rng(0).integers(
      0, 10, (T, 6)).astype(np.int32))
  nv = jnp.full((T,), 6, jnp.int32)
  key = jax.random.key(7)
  traces = {'n': 0}

  @jax.jit
  def run_super(seeds_stack, nv_stack, key, tables):
    traces['n'] += 1  # trace-time side effect only
    return multihop_sample_hetero_many(
        one_hops, trav, samp.num_neighbors, samp.num_hops, caps,
        budgets, {'user': seeds_stack}, {'user': nv_stack}, key,
        tables, fused_plan=plan)

  outs, tables = run_super(seeds, nv, key, tables)
  outs2, tables = run_super(seeds, nv, key, tables)
  assert traces['n'] == 1  # one dispatch per K batches, zero recompile
  k = key
  for t in range(T):
    k, sub = jax.random.split(k)
    one, tables = multihop_sample_hetero(
        one_hops, trav, samp.num_neighbors, samp.num_hops, caps,
        budgets, {'user': seeds[t]}, {'user': nv[t]}, sub, tables,
        fused_plan=plan)
    for ty in one['node']:
      np.testing.assert_array_equal(np.asarray(outs['node'][ty])[t],
                                    np.asarray(one['node'][ty]),
                                    err_msg=f'node[{ty}] step {t}')
    for e in one['row']:
      np.testing.assert_array_equal(np.asarray(outs['row'][e])[t],
                                    np.asarray(one['row'][e]),
                                    err_msg=f'row[{e}] step {t}')


def test_fused_walk_mode_knob(monkeypatch):
  from glt_tpu.ops.pipeline import fused_walk_mode
  monkeypatch.delenv('GLT_FUSED_WALK', raising=False)
  # auto resolves per interpret-default: per_hop on the CPU suite
  assert fused_walk_mode() == 'per_hop'
  monkeypatch.setenv('GLT_FUSED_WALK', 'cross')
  assert fused_walk_mode() == 'cross'
  monkeypatch.setenv('GLT_FUSED_WALK', 'sideways')
  with pytest.raises(ValueError):
    fused_walk_mode()


def test_hop_engine_knob_accepts_pallas_fused(monkeypatch):
  from glt_tpu.ops.pipeline import dedup_engine, hop_engine
  monkeypatch.setenv('GLT_HOP_ENGINE', 'pallas_fused')
  assert hop_engine() == 'pallas_fused'
  # the fused engine implies the sort dedup contract under auto
  monkeypatch.delenv('GLT_DEDUP', raising=False)
  assert dedup_engine() == 'sort'
  monkeypatch.setenv('GLT_HOP_ENGINE', 'warp')
  with pytest.raises(ValueError):
    hop_engine()
