"""Profiling / throughput instrumentation.

The reference measures throughput with manual time.time() +
cuda.synchronize in bench scripts (SURVEY.md §5.1) and has no built-in
tracer. Here timing hooks are first-class: a ThroughputMeter for the
sampled-edges/sec north-star metric and a device-synchronizing Timer.
Traces are ``glt_tpu.obs``'s: host spans by ``obs.Tracer``, device time by
layer by ``obs.device.scope_profile``.
"""
from __future__ import annotations

import time
from typing import Optional

import jax


class Timer:
  """Wall-clock timer that synchronizes outstanding device work.

  ``elapsed`` accumulates across start/stop intervals; each ``stop()``
  consumes the matching ``start()``, so a stop without a running
  interval raises a clear RuntimeError instead of the historical
  ``TypeError: unsupported operand`` on the ``None`` start stamp."""

  def __init__(self):
    self.reset()

  def reset(self):
    self._t0 = None
    self.elapsed = 0.0

  @property
  def running(self) -> bool:
    return self._t0 is not None

  def start(self):
    # re-entrant start (incl. reusing one Timer across `with` blocks)
    # cleanly restarts the interval stamp; accumulated elapsed stays
    self._t0 = time.perf_counter()
    return self

  def stop(self, sync: Optional[jax.Array] = None) -> float:
    if self._t0 is None:
      raise RuntimeError(
          'Timer.stop() without a running interval: call start() (or '
          'enter the context manager) first; each stop() consumes its '
          'start()')
    if sync is not None:
      jax.block_until_ready(sync)
    self.elapsed += time.perf_counter() - self._t0
    self._t0 = None
    return self.elapsed

  def __enter__(self):
    return self.start()

  def __exit__(self, *exc):
    if self._t0 is not None:  # tolerate an explicit stop() in the body
      self.stop()


class ThroughputMeter:
  """Accumulates (count, seconds) and reports rate — the
  'Sampled Edges per secs' metric (benchmarks/api/bench_sampler.py)."""

  def __init__(self, unit: str = 'edges'):
    self.unit = unit
    self.count = 0
    self.seconds = 0.0

  def update(self, count: int, seconds: float):
    self.count += int(count)
    self.seconds += seconds

  @property
  def rate(self) -> float:
    return self.count / self.seconds if self.seconds > 0 else 0.0

  def report(self) -> str:
    # auto-scale the unit: a hard-coded /1e6 printed every sub-million
    # rate (e.g. serving QPS) as '0.00M'
    r = self.rate
    if r >= 1e6:
      return f'{r / 1e6:.2f}M {self.unit}/s'
    if r >= 1e3:
      return f'{r / 1e3:.2f}K {self.unit}/s'
    return f'{r:.2f} {self.unit}/s'
