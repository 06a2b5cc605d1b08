"""Performance observability: XLA cost accounting (compiles_total +
instrument_compiled gauges), the bench trajectory +
regression gate, the postmortem flight recorder, SLO burn, and the
exposition/harvest satellites.

Acceptance pins (ISSUE 11): bench_compare exits nonzero on a synthetic
30% throughput regression; an injected engine stall produces a
postmortem dump carrying the stall event, the last spans, and a
registry snapshot."""
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from glt_tpu.obs import (
    FlightRecorder, MetricsRegistry, SloBurnEvaluator, Tracer,
    compile_counts, count_compile, get_registry, get_tracer,
    instrument_compiled, parse_slo_env, set_recorder, set_registry,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, 'benchmarks'))


@pytest.fixture
def registry():
  """Fresh process-global registry, restored afterwards — compile
  counters and the cost gauges land on the global surface."""
  prev = set_registry(MetricsRegistry())
  yield get_registry()
  set_registry(prev)


@pytest.fixture
def recorder(tmp_path):
  """Fresh process-global flight recorder dumping into tmp_path with
  no rate limit, restored afterwards."""
  rec = FlightRecorder(dump_dir=str(tmp_path), min_dump_interval_s=0)
  prev = set_recorder(rec)
  yield rec
  set_recorder(prev)


# -- satellites: exposition escaping + dropped-span counter --------------

#: one exposition line: name{labels} value  (labels optional). The
#: label-value body may contain anything except a raw unescaped quote,
#: backslash, or newline.
_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\["\\n])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\["\\n])*")*\})?'
    r' -?[0-9.eE+-]+$')


def test_prometheus_label_escaping_conformance():
  r = MetricsRegistry()
  nasty = 'a\\b"c\nd'
  r.inc('requests_total', path=nasty, code='200')
  r.set('depth', 2.0, q='say "hi"')
  r.observe('lat_seconds', 0.01, stage='x\\y')
  text = r.to_prometheus()
  for line in text.strip().split('\n'):
    if line.startswith('#'):
      continue
    assert _PROM_LINE.match(line), f'malformed exposition line: {line!r}'
  # the escapes are reversible — the scraper recovers the raw value
  m = re.search(r'path="((?:[^"\\]|\\.)*)"', text)
  unescaped = (m.group(1).replace(r'\n', '\n').replace(r'\"', '"')
               .replace('\\\\', '\\'))
  assert unescaped == nasty


def test_histogram_fraction_above():
  r = MetricsRegistry()
  h = r.histogram('lat')
  for v in (0.01, 0.01, 0.01, 1.0):
    h.observe(v)
  assert h.count_above(0.1) == 1
  assert abs(h.fraction_above(0.1) - 0.25) < 1e-9
  assert h.fraction_above(10.0) == 0.0
  assert r.histogram('empty').fraction_above(0.1) == 0.0


def test_spans_dropped_surfaces_as_counter():
  r = MetricsRegistry()
  t = Tracer(enabled=True, buffer=16, registry=r)
  for i in range(20):
    with t.span(f's{i}'):
      pass
  assert t.dropped == 4
  assert r.snapshot()['counters']['obs_spans_dropped_total'] == 4


# -- XLA cost accounting -------------------------------------------------

def test_compiles_total_counts_traces_not_executions(registry):
  import jax
  import jax.numpy as jnp

  @jax.jit
  def f(x):
    count_compile('test.fn')
    return x * 2

  for _ in range(3):
    f(jnp.ones((4,)))           # one trace, three executions
  assert compile_counts()['test.fn'] == 1
  f(jnp.ones((8,)))             # new shape: one more trace
  assert compile_counts()['test.fn'] == 2


def test_instrument_compiled_publishes_cost_gauges(registry):
  import jax
  import jax.numpy as jnp

  f = jax.jit(lambda x: (x @ x).sum())
  sds = jax.ShapeDtypeStruct((16, 16), jnp.float32)
  out = instrument_compiled('test.mm', f, sds)
  assert out.get('flops', 0) > 0
  gauges = registry.snapshot()['gauges']
  assert gauges['xla_flops{fn="test.mm"}'] > 0
  assert gauges['xla_bytes_accessed{fn="test.mm"}'] > 0
  # a pre-compiled stage also carries memory_analysis -> peak bytes
  out2 = instrument_compiled('test.mm2', f.lower(sds).compile())
  assert out2.get('peak_bytes', 0) > 0
  assert registry.snapshot()['gauges']['xla_peak_bytes{fn="test.mm2"}'] \
      > 0
  # garbage input degrades to {} (best-effort contract), never raises
  assert instrument_compiled('test.bad', object()) == {}


def test_serving_warmup_publishes_costs_opt_in(registry):
  import jax
  from fixtures import ring_dataset
  from glt_tpu.models import GraphSAGE
  from glt_tpu.serving import InferenceEngine
  ds = ring_dataset(num_nodes=24)
  model = GraphSAGE(hidden_features=8, out_features=4, num_layers=2)
  eng = InferenceEngine(ds, model, None, [2, 2], buckets=(4,))
  eng.init_params(jax.random.key(0))
  eng.warmup(publish_costs=True)
  gauges = registry.snapshot()['gauges']
  assert gauges['xla_flops{fn="serve.forward[b4]"}'] > 0
  # the AOT lower is one extra trace per bucket — and only one: the
  # steady state afterwards must stay flat (zero-recompile invariant)
  warm = eng.compile_stats()
  eng.infer(np.arange(3) % 24)
  assert eng.compile_stats()['forward_traces'] == \
      warm['forward_traces']


# -- bench history + regression gate -------------------------------------

def _history_rows(path, values, engine='sort', bench='sampler_headline'):
  from history import append_run
  for v in values:
    append_run(path, bench, v, unit='edges/s', engine=engine,
               scale='s1', device='cpu')


def test_history_append_load_baseline(tmp_path):
  from history import baseline, load_runs
  h = str(tmp_path / 'h.jsonl')
  _history_rows(h, [100.0, 90.0, 110.0, 105.0])
  runs = load_runs(h, bench='sampler_headline', engine='sort',
                   scale='s1', device='cpu')
  assert [r['value'] for r in runs] == [100.0, 90.0, 110.0, 105.0]
  assert baseline(runs, median_of=3) == 105.0   # median of last 3
  assert load_runs(h, engine='other') == []
  assert baseline([], median_of=3) is None
  with open(h, 'a') as f:                       # torn final line
    f.write('{"truncated\n')
  assert len(load_runs(h)) == 4                 # skipped, not fatal


def test_history_rows_from_bench_json_skips_failures():
  from history import rows_from_bench_json
  doc = {'metric': 'x', 'value': 9.0, 'unit': 'edges/s',
         'engine': 'sort', 'backend': 'cpu', 'scale': 's1',
         'engines': {'sort+fused': {'edges_per_sec': 8.0},
                     'lost_engine_error': 'boom'},
         'train_steps_per_sec': {'per_batch': 3.0, 'superstep': 4.0}}
  rows = rows_from_bench_json(doc)
  assert {(r['bench'], r['engine']) for r in rows} == {
      ('sampler_headline', 'sort'), ('sampler_engine', 'sort+fused'),
      ('train_steps_per_sec', 'per_batch'),
      ('train_steps_per_sec', 'superstep')}
  assert rows_from_bench_json({'error': 'probe failed',
                               'value': 0.0}) == []


def test_bench_compare_fails_on_30_percent_regression(tmp_path):
  """The acceptance pin: a synthetically injected 30% throughput
  regression must exit nonzero; the healthy run must exit zero."""
  h = str(tmp_path / 'h.jsonl')
  _history_rows(h, [100.0, 102.0, 98.0])
  base_doc = {'metric': 'x', 'unit': 'edges/s', 'engine': 'sort',
              'backend': 'cpu', 'scale': 's1', 'engines': {}}
  ok = str(tmp_path / 'ok.json')
  bad = str(tmp_path / 'bad.json')
  json.dump(dict(base_doc, value=99.0), open(ok, 'w'))
  json.dump(dict(base_doc, value=70.0), open(bad, 'w'))  # -30% vs 100

  def gate(current):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, 'scripts',
                                      'bench_compare.py'),
         '--history', h, '--current', current, '--threshold', '0.30'],
        capture_output=True, text=True)

  assert gate(ok).returncode == 0
  p = gate(bad)
  assert p.returncode != 0
  assert 'REGRESSION' in p.stderr
  report = json.loads(p.stdout)
  assert report['regressions'][0]['drop_pct'] == 30.0


def test_bench_compare_skips_unbaselined_and_failed_runs(tmp_path):
  sys.path.insert(0, os.path.join(REPO, 'scripts'))
  from bench_compare import compare
  h = str(tmp_path / 'h.jsonl')
  doc = {'metric': 'x', 'value': 50.0, 'unit': 'edges/s',
         'engine': 'sort', 'backend': 'cpu', 'scale': 's1',
         'engines': {}}
  # one recorded run < min_runs: nothing gates yet
  _history_rows(h, [100.0])
  r = compare(h, doc, threshold=0.3, min_runs=2)
  assert not r['regressions'] and r['skipped']
  # a run that failed to measure gates nothing (value 0 is "not
  # measured", per bench.py's own error contract)
  _history_rows(h, [100.0])
  r = compare(h, {'error': 'backend probe failed', 'value': 0.0},
              threshold=0.3)
  assert not r['regressions']
  # ...but with a baseline in place, the same doc WITHOUT an error
  # field gates loudly
  r = compare(h, doc, threshold=0.3)
  assert r['regressions'] and r['regressions'][0]['drop_pct'] == 50.0


# -- flight recorder -----------------------------------------------------

def test_flight_recorder_dump_contents(tmp_path, registry):
  rec = FlightRecorder(dump_dir=str(tmp_path), min_dump_interval_s=0,
                       registry=registry)
  registry.inc('requests_total', 5)
  rec.record('breaker_open', breaker='server:0')
  path = rec.trip('engine_stall', stall_timeout_s=0.15)
  assert path is not None and os.path.exists(path)
  doc = json.load(open(path))
  assert doc['reason'] == 'engine_stall'
  kinds = [e['kind'] for e in doc['events']]
  assert kinds == ['breaker_open', 'engine_stall']
  assert doc['registry']['counters']['requests_total'] == 5
  assert doc['counters_delta']['requests_total'] == 5
  # second dump reports only the movement since the first
  registry.inc('requests_total', 2)
  doc2 = json.load(open(rec.dump('again')))
  assert doc2['counters_delta']['requests_total'] == 2
  assert 'flight_trips_total{reason="engine_stall"}' \
      not in doc2['counters_delta']  # old movement aged out
  snap = registry.snapshot()['counters']
  assert snap['flight_trips_total{reason="engine_stall"}'] == 1
  assert snap['flight_events_total{kind="breaker_open"}'] == 1


def test_flight_recorder_rate_limit_and_ring_bound(tmp_path, registry):
  rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                       min_dump_interval_s=3600, registry=registry)
  assert rec.trip('breaker_open') is not None   # first dump lands
  assert rec.trip('breaker_open') is None       # rate-limited
  assert rec.dumps == 1
  # ...but every trip is still recorded and counted
  assert registry.snapshot()['counters'][
      'flight_trips_total{reason="breaker_open"}'] == 2
  for i in range(40):
    rec.record('evt', i=i)
  assert len(rec.events()) == 16                # bounded ring
  # no dump dir: trips record but never touch the filesystem
  rec2 = FlightRecorder(dump_dir='', registry=registry)
  assert rec2.trip('breaker_open') is None


def test_breaker_open_lands_on_recorder(recorder, registry):
  from glt_tpu.resilience import CircuitBreaker
  b = CircuitBreaker(failure_threshold=2, name='peer:7')
  b.record_failure()
  b.record_failure()
  assert b.state == 'OPEN'
  evts = [e for e in recorder.events() if e['kind'] == 'breaker_open']
  assert evts and evts[-1]['breaker'] == 'peer:7'
  # ...and the trip left a postmortem behind (recorder fixture dir)
  assert recorder.dumps == 1


def test_ingestor_crash_lands_on_recorder(recorder, registry):
  from glt_tpu.stream import (
      CompactionPolicy, SnapshotManager, StreamIngestor,
  )
  from glt_tpu.data import Topology
  topo = Topology(indptr=None,
                  edge_index=np.array([[0, 1], [1, 2]]), num_nodes=4)
  mgr = SnapshotManager(topo, delta_capacity=16)
  ing = StreamIngestor(mgr, policy=CompactionPolicy(max_staleness_s=0),
                       restart_policy='raise')
  ing.start(poll_interval_s=0.01)
  # poison the BACKGROUND tick only (the caller-thread staging path
  # raises synchronously and never reaches the bg-death trip)
  ing.maybe_compact = lambda: (_ for _ in ()).throw(
      RuntimeError('poisoned cut'))
  deadline = time.monotonic() + 10
  while ing._bg_error is None and time.monotonic() < deadline:
    time.sleep(0.01)
  ing.stop(raise_background_error=False)
  evts = [e for e in recorder.events() if e['kind'] == 'ingestor_crash']
  assert evts and 'poisoned cut' in evts[-1]['error']
  assert recorder.dumps >= 1


@pytest.mark.chaos
def test_engine_stall_writes_postmortem(tmp_path, registry):
  """Acceptance: an injected engine stall produces a flight-recorder
  postmortem containing the stall event, the last spans, and a
  registry snapshot."""
  from glt_tpu.serving import EngineStalledError, MicroBatcher
  rec = FlightRecorder(dump_dir=str(tmp_path), min_dump_interval_s=0,
                       registry=registry)
  prev_rec = set_recorder(rec)
  tracer = get_tracer()
  was_enabled = tracer.enabled
  tracer.clear()
  tracer.enable()
  gate = threading.Event()
  entered = threading.Event()

  def handler(ids):
    entered.set()
    gate.wait(timeout=30)
    return np.stack([ids.astype(np.float32)] * 2, axis=1)

  b = MicroBatcher(handler, max_batch_size=8, max_wait_ms=1.0,
                   request_timeout_ms=5000.0, stall_timeout_ms=100.0)
  try:
    with tracer.span('serve.infer'):   # pipeline activity pre-stall
      f = b.submit([1, 2])
    assert entered.wait(timeout=10)
    with pytest.raises(EngineStalledError):
      f.result(timeout=10)
    deadline = time.monotonic() + 10
    while rec.dumps == 0 and time.monotonic() < deadline:
      time.sleep(0.01)
    dumps = sorted(os.listdir(tmp_path))
    assert dumps, 'stall produced no postmortem dump'
    doc = json.load(open(tmp_path / dumps[0]))
    assert doc['reason'] == 'engine_stall'
    stall = [e for e in doc['events'] if e['kind'] == 'engine_stall']
    assert stall and stall[0]['stall_timeout_s'] == 0.1
    assert any(s['name'] == 'serve.infer' for s in doc['spans'])
    assert 'counters' in doc['registry']
    assert registry.snapshot()['counters'][
        'flight_trips_total{reason="engine_stall"}'] >= 1
  finally:
    gate.set()
    b.stop()
    set_recorder(prev_rec)
    tracer.enabled = was_enabled
    tracer.clear()


# -- SLO burn ------------------------------------------------------------

def test_slo_burn_windowed_evaluation(registry):
  ev = SloBurnEvaluator([], registry=registry)
  ev.add('serve_fast', 'serving_latency_seconds', 0.1, objective=0.9)
  for v in (0.01, 0.01, 1.0, 1.0):   # 50% above threshold
    registry.observe('serving_latency_seconds', v)
  burns = ev.evaluate()
  # bad fraction 0.5 against a 10% error budget = burn 5x
  assert abs(burns['serve_fast'] - 5.0) < 1e-6
  assert abs(registry.snapshot()['gauges']
             ['slo_burn{slo="serve_fast"}'] - 5.0) < 1e-6
  # next window: only good traffic -> burn 0 (windowed, not lifetime)
  for _ in range(10):
    registry.observe('serving_latency_seconds', 0.01)
  assert ev.evaluate()['serve_fast'] == 0.0
  # an empty window burns nothing
  assert ev.evaluate()['serve_fast'] == 0.0


def test_slo_burn_trips_recorder_on_fast_burn(tmp_path, registry):
  rec = FlightRecorder(dump_dir=str(tmp_path), min_dump_interval_s=0,
                       registry=registry)
  ev = SloBurnEvaluator([], registry=registry, recorder=rec,
                        trip_above=2.0)
  ev.add('p99', 'lat', 0.1, objective=0.99)
  for _ in range(10):
    registry.observe('lat', 1.0)     # 100% bad: burn 100x
  burns = ev.evaluate()
  assert burns['p99'] > 2.0
  evts = [e for e in rec.events() if e['kind'] == 'slo_burn']
  assert evts and evts[0]['slo'] == 'p99'
  assert rec.dumps == 1


def test_serving_server_publishes_slo_burn():
  """The per-shard wiring: a ServingServer with SLO policies evaluates
  burn on every stats() pull and publishes slo_burn gauges on its own
  registry (shared-registry fleets get per-shard series via
  metrics_name)."""
  import jax
  from fixtures import ring_dataset
  from glt_tpu.models import GraphSAGE
  from glt_tpu.obs import SloPolicy
  from glt_tpu.serving import ServingServer
  ds = ring_dataset(num_nodes=24)
  model = GraphSAGE(hidden_features=8, out_features=4, num_layers=2)
  from glt_tpu.serving import InferenceEngine
  eng = InferenceEngine(ds, model, None, [2, 2], buckets=(4,))
  eng.init_params(jax.random.key(0))
  # threshold below any real latency: every request burns budget
  with ServingServer(eng, slos=[SloPolicy(
      'p99_fast', 'serving_latency_seconds', 1e-6,
      objective=0.99)]) as srv:
    srv.infer(np.arange(3))
    stats = srv.stats()
    assert stats['slo_burn']['p99_fast'] > 1.0
    gauges = srv.metrics.registry.snapshot()['gauges']
    assert gauges['slo_burn{slo="p99_fast"}'] > 1.0
    # quiet window: the burn gauge decays to 0, not to its lifetime avg
    assert srv.stats()['slo_burn']['p99_fast'] == 0.0


def test_parse_slo_env():
  pols = parse_slo_env(
      'serve:serving_latency_seconds:0.25:0.999;'
      'gather:stage_seconds{stage=gather.features}:0.05')
  assert len(pols) == 2
  assert pols[0].name == 'serve' and pols[0].objective == 0.999
  assert pols[1].labels == {'stage': 'gather.features'}
  assert pols[1].objective == 0.99          # default
  assert abs(pols[0].error_budget - 0.001) < 1e-12
  assert parse_slo_env('') == []
  with pytest.raises(ValueError):
    parse_slo_env('just_a_name')


# -- fabric harvest: dead endpoint is a counted miss ---------------------

def test_fabric_harvest_partial_on_dead_endpoint(tmp_path, registry):
  """collect_endpoint_obs/collect_obs raise for the dead peer, but
  export_fabric_trace still merges every reachable peer's spans and
  counts the miss instead of aborting."""
  from glt_tpu.distributed import dist_client
  from glt_tpu.distributed.rpc import RpcClient, RpcServer
  from glt_tpu.obs import collect_endpoint_obs
  from glt_tpu.resilience import RetryPolicy
  srv = RpcServer()
  dead = RpcServer()
  cli_live = RpcClient(srv.host, srv.port, timeout=5,
                       retry=RetryPolicy(max_attempts=1))
  cli_dead = RpcClient(dead.host, dead.port, timeout=5,
                       retry=RetryPolicy(max_attempts=1))
  dead_port = dead.port
  dead.stop()
  # a direct harvest of the dead endpoint raises (callers that want
  # one peer get the real error)...
  with pytest.raises(OSError):
    collect_endpoint_obs('127.0.0.1', dead_port, timeout=2.0)
  saved = (dict(dist_client._clients), dist_client._num_servers,
           dist_client._health, dist_client._metrics)
  try:
    dist_client._clients.clear()
    dist_client._clients.update({0: cli_live, 1: cli_dead})
    dist_client._num_servers = 2
    dist_client._health = None
    dist_client._metrics = None
    assert 'counters' in dist_client.collect_obs(0)['metrics']
    with pytest.raises((ConnectionError, OSError)):
      dist_client.collect_obs(1)
    # ...but the merged export partial-harvests with a counted miss
    tracer = get_tracer()
    was_enabled = tracer.enabled
    tracer.clear()
    tracer.enable()
    try:
      with tracer.span('client.work'):
        pass
      out = str(tmp_path / 'fabric.json')
      assert dist_client.export_fabric_trace(out) == out
    finally:
      tracer.enabled = was_enabled
      tracer.clear()
    doc = json.load(open(out))
    assert any(e.get('name') == 'client.work'
               for e in doc['traceEvents'])
    misses = registry.snapshot()['counters']
    assert misses['obs_harvest_misses_total{server="1"}'] == 1
    assert 'obs_harvest_misses_total{server="0"}' not in misses
  finally:
    dist_client._clients.clear()
    dist_client._clients.update(saved[0])
    dist_client._num_servers = saved[1]
    dist_client._health = saved[2]
    dist_client._metrics = saved[3]
    cli_live.close()
    cli_dead.close()
    srv.stop()
