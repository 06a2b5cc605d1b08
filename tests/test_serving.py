"""Serving subsystem: bucketed engine compile discipline, embedding
cache semantics, micro-batcher edge cases, metrics, and the RPC
front-end.

Determinism strategy: engine tests sample with full-neighborhood fanout
(``[-1, -1]``) on the bounded-degree ring fixture, so the sampled
subgraph — and therefore the forward — is exact and padding-invariant
up to float summation order (asserted with allclose)."""
import threading
import time

import numpy as np
import pytest

from fixtures import ring_dataset
from glt_tpu.models import GraphSAGE
from glt_tpu.serving import (
    EmbeddingCache, InferenceEngine, LatencyHistogram, MicroBatcher,
    ServingClient, ServingMetrics, ServingOverloaded, ServingServer,
)

N_NODES = 40
OUT_DIM = 4


@pytest.fixture(scope='module')
def model_and_params():
  import jax
  ds = ring_dataset(num_nodes=N_NODES)
  model = GraphSAGE(hidden_features=16, out_features=OUT_DIM,
                    num_layers=2)
  eng = InferenceEngine(ds, model, None, [-1, -1], buckets=(4,))
  return model, eng.init_params(jax.random.key(0))


def make_engine(model_and_params, buckets=(4, 8), **kw):
  model, params = model_and_params
  return InferenceEngine(ring_dataset(num_nodes=N_NODES), model, params,
                         [-1, -1], buckets=buckets, **kw)


# -- engine: bucketed compilation ----------------------------------------

def test_warmup_compiles_each_bucket_exactly_once(model_and_params):
  eng = make_engine(model_and_params, buckets=(4, 8))
  stats = eng.warmup()
  assert stats['forward_traces'] == {4: 1, 8: 1}
  assert stats['sampler_compiled_fns'] == 2


def test_steady_state_zero_recompiles(model_and_params):
  eng = make_engine(model_and_params, buckets=(4, 8))
  eng.warmup()
  warm = eng.compile_stats()
  # every request size in [1, 8] plus an oversized one (chunked through
  # the largest bucket) must reuse the warmed programs
  for n in list(range(1, 9)) + [13]:
    out = eng.infer(np.arange(n) % N_NODES)
    assert out.shape == (n, OUT_DIM)
  now = eng.compile_stats()
  assert now['forward_traces'] == warm['forward_traces']
  assert now['sampler_compiled_fns'] == warm['sampler_compiled_fns']
  assert now['forward_calls'] > 0


def test_bucket_boundary_padding_correctness(model_and_params):
  """Padded execution equals the unpadded reference at and around the
  bucket boundary (n = B-1, B, 1)."""
  eng = make_engine(model_and_params, buckets=(8,), cache_capacity=0)
  eng.warmup()
  for n in (1, 7, 8):
    ids = (np.arange(n) * 3) % N_NODES
    ref_eng = make_engine(model_and_params, buckets=(n,),
                          cache_capacity=0)
    np.testing.assert_allclose(eng.infer(ids), ref_eng.infer(ids),
                               atol=1e-4)


def test_duplicate_and_empty_requests(model_and_params):
  eng = make_engine(model_and_params)
  eng.warmup()
  ids = np.array([5, 7, 5, 5, 7])
  out = eng.infer(ids)
  np.testing.assert_allclose(out[0], out[2])
  np.testing.assert_allclose(out[0], out[3])
  np.testing.assert_allclose(out[1], out[4])
  single = eng.infer([5])
  np.testing.assert_allclose(out[0], single[0], atol=1e-4)
  empty = eng.infer([])
  assert empty.shape == (0, OUT_DIM)


# -- engine: cache integration -------------------------------------------

def test_cached_lookup_bypasses_forward(model_and_params):
  eng = make_engine(model_and_params)
  eng.warmup()
  first = eng.infer([1, 2, 3])
  calls = eng.forward_calls
  again = eng.infer([1, 2, 3])   # full hit: no sampling, no forward
  assert eng.forward_calls == calls
  np.testing.assert_allclose(first, again)
  assert eng.cache.hit_rate > 0
  # partial hit computes only the missing ids (one more bucket run)
  eng.infer([2, 3, 4])
  assert eng.forward_calls == calls + 1


def test_version_bump_invalidates_cache(model_and_params):
  import jax
  eng = make_engine(model_and_params)
  eng.warmup()
  before = eng.infer([1, 2])
  calls = eng.forward_calls
  # scale params: embeddings must change once the version bumps
  new_params = jax.tree.map(lambda a: a * 2.0, eng.params)
  assert eng.set_params(new_params) == 1
  after = eng.infer([1, 2])
  assert eng.forward_calls == calls + 1  # recomputed, not cache-served
  assert not np.allclose(before, after)


def test_invalidate_nodes_hook(model_and_params):
  eng = make_engine(model_and_params)
  eng.warmup()
  eng.infer([1, 2, 3])
  calls = eng.forward_calls
  assert eng.invalidate_nodes([2]) == 1
  eng.infer([1, 2, 3])
  assert eng.forward_calls == calls + 1  # only node 2 recomputed
  seen = []
  eng.cache.add_invalidation_listener(
      lambda ids, version: seen.append((ids, version)))
  eng.cache.invalidate()
  assert seen == [(None, None)]


# -- embedding cache -----------------------------------------------------

def test_lru_eviction_and_stats():
  c = EmbeddingCache(capacity=2)
  c.insert([1, 2], np.eye(2, dtype=np.float32), version=0)
  assert c.lookup([1], 0)  # touch 1 -> 2 is now LRU
  c.insert([3], np.ones((1, 2), np.float32), version=0)
  assert len(c) == 2
  got = c.lookup([1, 2, 3], 0)
  assert set(got) == {1, 3}  # 2 evicted
  s = c.stats()
  assert s['evictions'] == 1 and s['hits'] == 3 and s['misses'] == 1
  # capacity 0 disables caching
  c0 = EmbeddingCache(capacity=0)
  c0.insert([1], np.ones((1, 2), np.float32), version=0)
  assert len(c0) == 0 and c0.lookup([1], 0) == {}


def test_cache_version_keying():
  c = EmbeddingCache(capacity=8)
  c.insert([1], np.zeros((1, 2), np.float32), version=0)
  assert c.lookup([1], 1) == {}          # other version never hits
  assert 1 in c.lookup([1], 0)
  assert c.invalidate(version=0) == 1
  assert c.lookup([1], 0) == {}
  # id-probe invalidation spans all LIVE versions, and the live-version
  # set shrinks as entries die (no growth across version bumps)
  c.insert([2], np.ones((1, 2), np.float32), version=3)
  c.insert([2], np.ones((1, 2), np.float32), version=4)
  assert c.invalidate(ids=[2]) == 2
  assert len(c._version_counts) == 0
  # cached rows own their memory (no view into the bucket output)
  c.insert([5], np.ones((2, 2), np.float32)[:1], version=0)
  assert c.lookup([5], 0)[5].base is None


# -- micro-batcher edge cases (satellite) --------------------------------

def _echo_handler(calls):
  def handler(ids):
    calls.append(np.asarray(ids).copy())
    return np.asarray(ids, np.float32)[:, None] * 2
  return handler


def test_batcher_merges_concurrent_requests():
  calls = []
  b = MicroBatcher(_echo_handler(calls), max_batch_size=8,
                   max_wait_ms=60.0)
  try:
    f1 = b.submit([1, 2])
    f2 = b.submit([3])
    f3 = b.submit([4, 5, 6, 7, 8])   # fills the batch -> flush now
    np.testing.assert_array_equal(f1.result(timeout=5).ravel(), [2, 4])
    np.testing.assert_array_equal(f2.result(timeout=5).ravel(), [6])
    np.testing.assert_array_equal(
        f3.result(timeout=5).ravel(), [8, 10, 12, 14, 16])
    assert len(calls) == 1 and calls[0].size == 8
  finally:
    b.stop()


def test_batcher_deadline_flush_partial_batch():
  calls = []
  b = MicroBatcher(_echo_handler(calls), max_batch_size=64,
                   max_wait_ms=20.0)
  try:
    t0 = time.monotonic()
    f = b.submit([9])
    np.testing.assert_array_equal(f.result(timeout=5).ravel(), [18])
    waited = time.monotonic() - t0
    assert waited >= 0.015  # the deadline, not an instant flush
    assert len(calls) == 1 and calls[0].size == 1
  finally:
    b.stop()


def test_batcher_empty_flush_on_deadline():
  """All queued requests expire before the flush deadline: the flush
  finds nothing and the handler must NOT be called."""
  calls = []
  b = MicroBatcher(_echo_handler(calls), max_batch_size=64,
                   max_wait_ms=200.0)
  try:
    f = b.submit([1], timeout_ms=10.0)
    with pytest.raises(TimeoutError):
      f.result(timeout=5)
    time.sleep(0.05)
    assert calls == [] and b.depth == 0
  finally:
    b.stop()


def test_batcher_request_timeout_under_slow_handler():
  release = threading.Event()
  def slow(ids):
    release.wait(5)
    return np.asarray(ids, np.float32)[:, None]
  m = ServingMetrics()
  b = MicroBatcher(slow, max_batch_size=1, max_wait_ms=0.0,
                   max_queue=8, metrics=m)
  try:
    b.submit([1])                         # occupies the dispatcher
    f2 = b.submit([2], timeout_ms=30.0)   # expires while queued
    time.sleep(0.06)                      # let the deadline pass...
    release.set()                         # ...then free the dispatcher
    with pytest.raises(TimeoutError):
      f2.result(timeout=5)
    assert m.timeouts == 1
  finally:
    release.set()
    b.stop()


def test_batcher_backpressure():
  release = threading.Event()
  def slow(ids):
    release.wait(5)
    return np.asarray(ids, np.float32)[:, None]
  m = ServingMetrics()
  b = MicroBatcher(slow, max_batch_size=1, max_wait_ms=0.0,
                   max_queue=2, metrics=m)
  try:
    b.submit([1])            # dispatched (stuck in the slow handler)
    time.sleep(0.05)         # let the dispatcher drain the queue
    b.submit([2])
    b.submit([3])            # queue now at capacity (2)
    with pytest.raises(ServingOverloaded):
      b.submit([4])
    assert m.rejected == 1
  finally:
    release.set()
    b.stop()


def test_batcher_oversized_head_request_ships_alone():
  calls = []
  b = MicroBatcher(_echo_handler(calls), max_batch_size=4,
                   max_wait_ms=60.0)
  try:
    f = b.submit(np.arange(10))  # bigger than max_batch: ships whole
    assert f.result(timeout=5).shape == (10, 1)
    assert len(calls) == 1 and calls[0].size == 10
  finally:
    b.stop()


def test_batcher_handler_errors_propagate_and_stop_fails_pending():
  def boom(ids):
    raise ValueError('kaput')
  b = MicroBatcher(boom, max_batch_size=4, max_wait_ms=1.0)
  f = b.submit([1])
  with pytest.raises(ValueError, match='kaput'):
    f.result(timeout=5)
  b.stop()
  with pytest.raises(RuntimeError, match='stopped'):
    b.submit([2])


# -- metrics -------------------------------------------------------------

def test_latency_histogram_percentiles():
  h = LatencyHistogram()
  for ms in range(1, 101):            # 1..100ms uniform
    h.observe(ms / 1e3)
  assert h.count == 100
  assert abs(h.percentile(50) - 0.050) < 0.01
  assert abs(h.percentile(99) - 0.100) < 0.012
  assert h.percentile(100) == h.max
  assert LatencyHistogram().percentile(99) == 0.0


def test_serving_metrics_snapshot():
  m = ServingMetrics()
  m.record_request(0.002, num_ids=3)
  m.record_request(0.004, num_ids=1)
  m.record_batch(4, 8)
  snap = m.snapshot()
  assert snap['requests'] == 2 and snap['ids_served'] == 4
  assert snap['batch_fill_ratio'] == 0.5
  assert 0 < snap['latency_p50_ms'] <= snap['latency_p99_ms']
  assert 'req/s' in m.report()


# -- RPC front-end -------------------------------------------------------

def test_server_client_roundtrip(model_and_params):
  eng = make_engine(model_and_params, buckets=(4, 8))
  with ServingServer(eng, max_wait_ms=1.0,
                     request_timeout_ms=30_000.0) as srv:
    cli = ServingClient(*srv.address)
    try:
      info = cli.ping()
      assert info['ok'] and info['buckets'] == [4, 8]
      ids = np.array([3, 1, 4, 1, 5])
      out = cli.infer(ids)
      assert out.shape == (5, OUT_DIM)
      np.testing.assert_allclose(out, eng.infer(ids))  # cache-served
      # concurrent clients interleave through the batcher
      cli2 = ServingClient(*srv.address)
      futs = [cli.infer_async([7, 8]), cli2.infer_async([9])]
      assert futs[0].result(timeout=30).shape == (2, OUT_DIM)
      assert futs[1].result(timeout=30).shape == (1, OUT_DIM)
      cli2.close()
      # out-of-range ids rejected per-request (never co-batched, never
      # clamped into a wrong-but-cacheable embedding)
      with pytest.raises(ValueError, match='out of range'):
        cli.infer([N_NODES + 7])
      assert cli.invalidate(ids=[3]) == 1
      stats = cli.stats()
      assert stats['requests'] >= 3
      assert stats['engine']['forward_traces'] == {4: 1, 8: 1}
      assert stats['cache']['size'] > 0
      assert stats['latency_p99_ms'] >= stats['latency_p50_ms'] > 0
      # resilience counters surface through ServingClient.stats()
      for key in ('retries', 'reconnects', 'breaker_opens', 'shed',
                  'stale_serves', 'failovers'):
        assert stats[key] == 0, (key, stats[key])
      assert stats['stalled'] is False
    finally:
      cli.close()


# -- degradation tiers (resilience) --------------------------------------

def test_stale_serve_answers_from_cache_while_engine_stalled(
    model_and_params):
  """Engine watchdog + opt-in stale-serve: a wedged forward opens the
  engine circuit; requests are answered from the versioned
  EmbeddingCache (zero-fill for misses) with bounded latency, every
  stale answer counted; the wedged call returning closes the circuit
  and serving resumes through the engine."""
  from glt_tpu.serving import EngineStalledError

  eng = make_engine(model_and_params, buckets=(4,))
  srv = ServingServer(eng, max_wait_ms=1.0, request_timeout_ms=5000.0,
                      stall_timeout_ms=150.0, stale_serve=True)
  try:
    primed_ids = np.array([1, 2, 3])
    primed = srv.infer(primed_ids)          # fills the cache
    # wedge the engine behind the batcher
    gate = threading.Event()
    wedge = threading.Event()
    real = srv.batcher.handler

    def wedging(ids):
      if wedge.is_set():
        gate.wait(timeout=30)
      return real(ids)

    srv.batcher.handler = wedging
    wedge.set()
    t0 = time.monotonic()
    out = srv.infer([1, 2], timeout_ms=3000.0)  # rides the stall
    dt = time.monotonic() - t0
    np.testing.assert_allclose(out, primed[:2], rtol=1e-5)
    assert dt < 2.0, f'stale serve not bounded by the watchdog ({dt}s)'
    assert srv.batcher.stalled
    # while OPEN: immediate stale answers, hits and misses both counted
    out2 = srv.infer([3, 17])
    np.testing.assert_allclose(out2[0], primed[2], rtol=1e-5)
    np.testing.assert_allclose(out2[1], 0)   # true miss: zero-fill
    stats = srv.stats()
    assert stats['stalled'] is True
    assert stats['stale_serves'] >= 3
    assert stats['breaker_opens'] == 1
    assert stats['gauges']['stale_zero_fills'] == 1
    # p99 stays bounded by the deadline: every recorded request was
    # either served fresh (fast) or stale (immediate)
    assert stats['latency_p99_ms'] <= 3000.0
    # release the wedge: circuit closes, engine serves again
    wedge.clear()
    gate.set()
    deadline = time.monotonic() + 10
    while srv.batcher.stalled and time.monotonic() < deadline:
      time.sleep(0.01)
    assert not srv.batcher.stalled
    calls0 = eng.forward_calls
    fresh = srv.infer([11, 12])
    assert fresh.shape == (2, OUT_DIM)
    assert eng.forward_calls > calls0        # really went through
    assert srv.stats()['stalled'] is False
  finally:
    srv.close()


def test_stale_serve_disabled_fails_fast(model_and_params):
  """Without stale_serve the stall surfaces as EngineStalledError —
  fail fast, never a silent zero answer."""
  from glt_tpu.serving import EngineStalledError

  eng = make_engine(model_and_params, buckets=(4,))
  srv = ServingServer(eng, max_wait_ms=1.0, request_timeout_ms=5000.0,
                      stall_timeout_ms=150.0, stale_serve=False)
  try:
    srv.infer([1])
    gate = threading.Event()
    real = srv.batcher.handler
    srv.batcher.handler = lambda ids: (gate.wait(timeout=30), real(ids))[1]
    with pytest.raises(EngineStalledError):
      srv.infer([2], timeout_ms=3000.0)
    gate.set()
  finally:
    srv.close()


def test_update_snapshot_never_serves_mixed_versions():
  """Versioned-consistency regression: while ``update_snapshot`` swaps
  the feature table under the engine lock, a concurrent ``infer`` must
  observe EITHER the old table end-to-end OR the new one — never
  snapshot-v rows for some ids and v-1 rows for others in one response.
  Rows value-encode their version (1000*v + id) so a torn response is
  directly visible in the output."""
  from glt_tpu.stream import SnapshotManager, StreamIngestor, StreamSampler

  dim, n = 8, 40
  ds = ring_dataset(num_nodes=n, feat_dim=dim)
  mgr = SnapshotManager(ds.get_graph().topo, ds.get_node_feature())
  eng = InferenceEngine(ds, None, None, [2], buckets=(8,),
                        apply_fn=lambda p, b: b.x,
                        sampler=StreamSampler(mgr, [2], seed=0))
  ing = StreamIngestor(mgr, sampler=eng.sampler, engine=eng)
  ids = np.array([2, 7, 13, 22, 29, 37])
  errs, seen = [], set()
  stop = threading.Event()

  def hammer():
    try:
      while not stop.is_set():
        before = eng.snapshot_version
        out = eng.infer(ids)
        marks = np.unique(out[:, 0] - ids)  # 1000*v per row
        assert marks.size == 1, f'mixed versions in one infer: {marks}'
        v = int(marks[0]) // 1000
        # monotone: an infer that started at snapshot ``before`` may
        # observe a newer table, never an older one
        assert v >= before, (v, before)
        seen.add(v)
    except Exception as e:
      errs.append(e)

  threads = [threading.Thread(target=hammer) for _ in range(3)]
  try:
    for t in threads:
      t.start()
    for v in range(1, 4):
      rows = 1000.0 * v + np.arange(n, dtype=np.float32)[:, None] \
          * np.ones(dim, np.float32)
      ing.update_features(np.arange(n), rows)
      info = ing.flush()
      assert info['version'] == v
      assert eng.snapshot_version == v
      time.sleep(0.05)
  finally:
    stop.set()
    for t in threads:
      t.join(timeout=10)
  assert not errs, errs
  assert 3 in seen, f'final snapshot never observed: {sorted(seen)}'
