"""Performance accounting: XLA cost and memory analysis.

Everything here publishes into the shared :class:`MetricsRegistry`.
Every jitted compile point already carries a trace-time side effect (the
per-module ``*_traces`` counters the zero-steady-state-recompile tests
assert); :func:`count_compile` generalizes those into ONE process-wide
``compiles_total{fn=...}`` counter, and :func:`instrument_compiled` is
the seam over ``jax.stages.Lowered.cost_analysis()`` /
``jax.stages.Compiled.cost_analysis()`` / ``memory_analysis()`` that
publishes per-program FLOPs, HBM bytes accessed, and peak memory as
``xla_flops{fn}`` / ``xla_bytes_accessed{fn}`` /
``xla_peak_bytes{fn}`` gauges. Lowering is cheap (a re-trace, no
compile) but IS a re-trace: callers whose trace counters are pinned by
tests (serving warmup) gate it behind ``GLT_OBS_XLA_COST``. The chip's
peaks, for a share of a roofline, are the benchmark's
(``chipbench/peaks.py``).

Everything here is host-side and allocation-free in steady state;
nothing touches traced code paths except the deliberate trace-time
``count_compile`` bump (a registry increment, same class of side
effect as the existing ``*_traces`` attribute bumps).
"""
from __future__ import annotations

import logging
from typing import Optional

from ..typing import as_str
from ..utils.env import knob
from .registry import MetricsRegistry, get_registry

logger = logging.getLogger(__name__)


# -- compile accounting ---------------------------------------------------

def count_compile(fn: str,
                  registry: Optional[MetricsRegistry] = None) -> None:
  """Trace-time hook: bump ``compiles_total{fn=...}``. Call it INSIDE a
  jitted function body (next to the existing ``*_traces`` attribute
  bumps) so executions never touch it — the counter then reads as
  "programs compiled/re-traced for this fn", the process-wide
  generalization of the per-module trace-counter asserts."""
  try:
    (registry or get_registry()).counter('compiles_total',
                                         fn=str(fn)).inc()
  except Exception:  # accounting must never break a trace
    pass


def gauge_layer_rows(fn: str, rows,
                     registry: Optional[MetricsRegistry] = None) -> None:
  """Trace-time hook beside :func:`count_compile`: publish the output
  rows each layer of program ``fn``'s model computes, as
  ``model_layer_rows{fn=..., layer=i}``. The node trim of
  models/sage.py engages when a program is traced, so its counter is
  static too: set once a trace, no host work a step."""
  try:
    reg = registry or get_registry()
    for i, n in enumerate(rows):
      if isinstance(n, dict):   # a typed model: rows per node type
        for t, m in n.items():
          reg.set('model_layer_rows', float(m), fn=str(fn), layer=str(i),
                  type=str(t))
      else:
        reg.set('model_layer_rows', float(n), fn=str(fn), layer=str(i))
  except Exception:  # accounting must never break a trace
    pass


def gauge_grouped_aggregation(
    fn: str, groups, registry: Optional[MetricsRegistry] = None) -> None:
  """Trace-time hook beside :func:`gauge_layer_rows`: how many groups of
  adjacent edge slots each layer of program ``fn``'s model aggregates by
  a reshape and a masked reduce, as
  ``model_grouped_aggregation{fn=..., layer=i}``; 0 for a layer that
  scatter-adds every slot (a batch without ``Batch.hop_fanouts``, a
  convolution that does not read it). A typed model gives a dict a
  layer, groups per relation, and the series carry a ``relation`` label
  too. Static: set once a trace."""
  try:
    reg = registry or get_registry()
    for i, n in enumerate(groups):
      if isinstance(n, dict):
        for e, m in n.items():
          reg.set('model_grouped_aggregation', float(m), fn=str(fn),
                  layer=str(i), relation=as_str(e))
      else:
        reg.set('model_grouped_aggregation', float(n), fn=str(fn),
                layer=str(i))
  except Exception:  # accounting must never break a trace
    pass


def gauge_joint_softmax(
    fn: str, relations, registry: Optional[MetricsRegistry] = None) -> None:
  """Trace-time hook beside :func:`gauge_grouped_aggregation`, for a
  typed model whose softmax crosses relations (models/hgt.py): how many
  relations share a parent type's softmax in each layer of program
  ``fn``'s model, as ``model_joint_softmax_relations{fn, layer, type}``;
  0 on a type that no relation reaches. Static: set once a trace."""
  try:
    reg = registry or get_registry()
    for i, n in enumerate(relations):
      for t, m in n.items():
        reg.set('model_joint_softmax_relations', float(m), fn=str(fn),
                layer=str(i), type=str(t))
  except Exception:  # accounting must never break a trace
    pass


def gauge_embedding_rows(
    fn: str, tables: dict,
    registry: Optional[MetricsRegistry] = None) -> None:
  """Trace-time hook beside :func:`gauge_layer_rows`, for a model that
  owns embedding tables (models/bipartite_sage.py): the rows of each
  node type's table among program ``fn``'s parameters, as
  ``model_embedding_rows{fn, type}``. Static: set once a trace."""
  try:
    reg = registry or get_registry()
    for t, n in tables.items():
      reg.set('model_embedding_rows', float(n), fn=str(fn), type=str(t))
  except Exception:  # accounting must never break a trace
    pass


def gauge_budgets(fn: str, node_budget: dict, edge_budget: dict,
                  registry: Optional[MetricsRegistry] = None) -> None:
  """Build-time hook of a typed step program ``fn``: the static padded
  budgets it steps over, as ``node_budget{fn, type}`` (rows of a node
  type's slots) and ``edge_budget{fn, relation}`` (edge slots of a
  relation)."""
  try:
    reg = registry or get_registry()
    for t, n in node_budget.items():
      reg.set('node_budget', float(n), fn=str(fn), type=str(t))
    for e, n in edge_budget.items():
      reg.set('edge_budget', float(n), fn=str(fn), relation=as_str(e))
  except Exception:  # accounting must never break a build
    pass


def gauge_in_place(fn: str, in_place: bool,
                   registry: Optional[MetricsRegistry] = None) -> None:
  """Trace-time hook of a feature store's ``lookup_local``: publish
  ``feature_store_in_place{fn}``, 1 when ``fn`` traced the in-place form
  (one shard owns every row: nothing bucketed, exchanged or stitched), 0
  when it traced the exchange. The choice follows from the mesh, so the
  gauge is static like :func:`gauge_layer_rows`: set once a trace."""
  try:
    (registry or get_registry()).set('feature_store_in_place',
                                     float(in_place), fn=str(fn))
  except Exception:  # accounting must never break a trace
    pass


def gauge_bucket_cap(fn: str, cap: int,
                     registry: Optional[MetricsRegistry] = None) -> None:
  """Trace-time hook of a feature store's exchange: publish
  ``feature_store_bucket_cap{fn}``, the slots of one per-owner request
  bucket in the program ``fn`` traced. Static like
  :func:`gauge_in_place`, beside which it is set: the cap follows from
  the request count, the shard count and ``bucket_cap``."""
  try:
    (registry or get_registry()).set('feature_store_bucket_cap',
                                     float(cap), fn=str(fn))
  except Exception:  # accounting must never break a trace
    pass


def compile_counts(registry: Optional[MetricsRegistry] = None) -> dict:
  """{fn: count} view over ``compiles_total`` — the assertable surface
  (tests pin a label's count flat across steady-state traffic)."""
  snap = (registry or get_registry()).snapshot()['counters']
  out = {}
  for key, v in snap.items():
    if key.startswith('compiles_total{'):
      inner = key[key.index('{') + 1:-1]
      for part in inner.split(','):
        k, _, val = part.partition('=')
        if k == 'fn':
          out[val.strip('"')] = out.get(val.strip('"'), 0) + v
  return out


def xla_cost_enabled() -> bool:
  """Whether opt-in AOT cost publication runs at compile points whose
  trace counters are test-pinned (serving warmup). ``GLT_OBS_XLA_COST=1``
  opts in; default off because the AOT ``lower()`` is an extra trace."""
  return knob('GLT_OBS_XLA_COST', False)


def _flatten_cost(cost) -> dict:
  """Normalize the cost_analysis return shape across jax versions:
  ``Lowered.cost_analysis()`` returns a flat dict, ``Compiled.
  cost_analysis()`` a list of per-module dicts (summed here)."""
  if cost is None:
    return {}
  if isinstance(cost, dict):
    return dict(cost)
  out: dict = {}
  for entry in cost:
    for k, v in (entry or {}).items():
      try:
        out[k] = out.get(k, 0.0) + float(v)
      except (TypeError, ValueError):
        pass
  return out


def instrument_compiled(fn_name: str, stage=None, *args,
                        registry: Optional[MetricsRegistry] = None,
                        aot_compile: bool = False,
                        **kwargs) -> dict:
  """Publish one program's XLA cost/memory analysis as registry gauges.

  ``stage`` is either an already-built ``jax.stages.Lowered`` /
  ``jax.stages.Compiled``, or a jit-wrapped callable — then ``*args`` /
  ``**kwargs`` (arrays or ``jax.ShapeDtypeStruct``\\ s) are lowered
  through it here. Lowering re-traces but never compiles; pass
  ShapeDtypeStructs when the real arguments were donated.

  ``aot_compile=True`` additionally compiles a Lowered stage first:
  ``Lowered.cost_analysis()`` counts the PRE-optimization HLO (every
  unfused intermediate reads as memory traffic), while the Compiled
  analysis reflects the optimized executable and unlocks
  ``memory_analysis()`` — callers quoting roofline evidence
  pay the compile (cheap when the persistent compilation cache already
  holds the program); ambient instrumentation stays lower-only.

  Publishes (all labeled ``fn=fn_name``):

  * ``xla_flops`` — model FLOPs of the program,
  * ``xla_bytes_accessed`` — HBM bytes the program moves,
  * ``xla_peak_bytes`` — argument + output + temp allocation peak
    (only when a ``Compiled`` with ``memory_analysis()`` is in hand —
    lowering alone has no allocation assignment).

  Returns the published numbers (plus whatever raw keys the backend
  reported); ``{}`` on any analysis failure — cost accounting is
  best-effort by contract (some backends return None).
  """
  reg = registry or get_registry()
  try:
    if callable(getattr(stage, 'lower', None)) \
        and not hasattr(stage, 'cost_analysis'):
      stage = stage.lower(*args, **kwargs)
    if aot_compile and callable(getattr(stage, 'compile', None)):
      try:
        stage = stage.compile()
      except Exception as e:  # fall back to the lowered analysis
        logger.debug('aot compile for %s failed (%s); using lowered '
                     'cost analysis', fn_name, e)
    compiled = stage
    cost = _flatten_cost(compiled.cost_analysis())
    out = {}
    if 'flops' in cost:
      out['flops'] = float(cost['flops'])
      reg.set('xla_flops', out['flops'], fn=str(fn_name))
    if 'bytes accessed' in cost:
      out['bytes_accessed'] = float(cost['bytes accessed'])
      reg.set('xla_bytes_accessed', out['bytes_accessed'],
              fn=str(fn_name))
    mem = getattr(compiled, 'memory_analysis', None)
    if callable(mem):
      m = mem()
      if m is not None:
        peak = (getattr(m, 'argument_size_in_bytes', 0)
                + getattr(m, 'output_size_in_bytes', 0)
                + getattr(m, 'temp_size_in_bytes', 0)
                - getattr(m, 'alias_size_in_bytes', 0))
        out['peak_bytes'] = float(peak)
        out['temp_bytes'] = float(getattr(m, 'temp_size_in_bytes', 0))
        reg.set('xla_peak_bytes', out['peak_bytes'], fn=str(fn_name))
    return out
  except Exception as e:  # noqa: BLE001 — accounting is best-effort
    logger.debug('cost analysis for %s unavailable: %s', fn_name, e)
    return {}
