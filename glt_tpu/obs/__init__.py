"""glt_tpu.obs — the unified observability layer.

One process-wide surface for the three observability primitives every
subsystem (sampling, loaders, serving, stream ingest, resilience,
distributed fabric, parallel train) publishes into:

  * :class:`MetricsRegistry` — thread-safe labeled counters / gauges /
    log-spaced histograms with JSON and Prometheus-text exposition.
    :class:`~glt_tpu.serving.ServingMetrics` is a back-compat view over
    one of these, so serving / stream / resilience counters and the
    pipeline stage timings land on the SAME surface.
  * :class:`Tracer` — host-side spans per pipeline stage (sample hop,
    dedup, feature gather, superstep dispatch, batcher flush,
    compaction) that bridge into device traces via
    ``jax.profiler.TraceAnnotation`` and export as Chrome-trace-event /
    Perfetto-loadable JSON. Trace context propagates over the RPC
    fabric (``distributed.rpc``) so a cross-machine sample + feature
    lookup assembles into one trace.
  * profiling hooks — opt-in device-sync sampling
    (``GLT_OBS_TRACE_SAMPLE``) so steady-state overhead stays
    negligible; everything is host-side, so every zero-recompile
    invariant holds with obs enabled.

Disabled (the default), every hook is a near-free no-op: ``span()``
returns a cached null context manager and per-stage ``stage_seconds``
observations stop (plain registry counters keep counting — exposition
is independent of the tracing knob); the tier-1 overhead test pins the
no-op path below 2% of a sampled epoch.

Three further pieces ride the same registry/tracer surfaces:

  * :mod:`device` — device time by layer from inside a compiled step:
    the layer-named ``jax.named_scope`` convention (``LAYERS``,
    ``scope``), ``reduce_scopes`` from a profiler trace to ms a step by
    layer and stage, and ``scope_profile``, the session that takes the
    trace around a step program's own calls.

  * :mod:`perf` — XLA cost accounting (``compiles_total{fn}``,
    ``xla_flops``/``xla_bytes_accessed``/``xla_peak_bytes`` via the
    :func:`instrument_compiled` seam) and the static gauges the step
    programs set at trace time.
  * :mod:`recorder` — the always-on :class:`FlightRecorder` (bounded
    operational-event ring; resilience trips dump a postmortem JSON
    into ``GLT_OBS_POSTMORTEM_DIR``) and :class:`SloBurnEvaluator`
    (``slo_burn{slo=...}`` gauges over the registry histograms).

Knobs (see docs/observability.md for the full table):

  GLT_OBS_TRACE=1         enable tracing at import time
  GLT_OBS_TRACE_SAMPLE=p  fraction of spans that device-sync on exit
  GLT_OBS_ANNOTATE=0      disable the device TraceAnnotation bridge
  GLT_OBS_BUFFER=n        span ring-buffer capacity (default 65536)
  GLT_OBS_XLA_COST=1      opt-in AOT cost publication at test-pinned
                          compile points (serving warmup)
  GLT_OBS_POSTMORTEM_DIR  flight-recorder postmortem dump directory
  GLT_OBS_POSTMORTEM_MIN_S  floor between trip-initiated dumps
  GLT_OBS_SLO             SLO policies: name:metric:threshold[:obj];...
"""
from .registry import (
    Counter, Gauge, HistogramMetric, LatencyHistogram, MetricsRegistry,
    get_registry, set_registry,
)
from .trace import (
    Span, SpanContext, Tracer, collect_endpoint_obs, get_tracer,
    merge_chrome_traces, save_chrome_trace,
)
from .perf import compile_counts, count_compile, instrument_compiled
from .recorder import (
    FlightRecorder, SloBurnEvaluator, SloPolicy, get_recorder,
    parse_slo_env, set_recorder,
)

__all__ = [
    'Counter', 'Gauge', 'HistogramMetric', 'LatencyHistogram',
    'MetricsRegistry', 'get_registry', 'set_registry',
    'Span', 'SpanContext', 'Tracer', 'get_tracer',
    'collect_endpoint_obs', 'merge_chrome_traces', 'save_chrome_trace',
    'compile_counts', 'count_compile', 'instrument_compiled',
    'FlightRecorder', 'SloBurnEvaluator', 'SloPolicy', 'get_recorder',
    'parse_slo_env', 'set_recorder',
]
