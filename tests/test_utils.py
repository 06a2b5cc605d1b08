"""Unit tests for utility surfaces: typing conventions, rng manager,
profiling meters, size parsing, mesh helpers."""
import time

import jax
import numpy as np

from glt_tpu.typing import as_str, reverse_edge_type
from glt_tpu.utils import (
    RandomSeedManager, id2idx, merge_dict, parse_size, seed_everything,
)
from glt_tpu.utils.common import CastMixin
from glt_tpu.utils.profile import ThroughputMeter, Timer


def test_reverse_edge_type_conventions():
  assert reverse_edge_type(('u', 'rel', 'i')) == ('i', 'rev_rel', 'u')
  assert reverse_edge_type(('i', 'rev_rel', 'u')) == ('u', 'rel', 'i')
  # same-type relations keep their name
  assert reverse_edge_type(('i', 'link', 'i')) == ('i', 'link', 'i')
  assert as_str(('a', 'b', 'c')) == 'a__b__c'
  assert as_str('node') == 'node'


def test_seed_manager_reproducible_streams():
  m = RandomSeedManager.getInstance()
  m.setSeed(123)
  k1, k2 = m.nextKey(), m.nextKey()
  m.setSeed(123)
  k1b, k2b = m.nextKey(), m.nextKey()
  assert jax.random.key_data(k1).tolist() == \
      jax.random.key_data(k1b).tolist()
  assert jax.random.key_data(k1).tolist() != \
      jax.random.key_data(k2).tolist()
  assert jax.random.key_data(k2).tolist() == \
      jax.random.key_data(k2b).tolist()


def test_id2idx_and_merge_dict():
  out = id2idx(np.array([5, 2, 9]))
  assert out[5] == 0 and out[2] == 1 and out[9] == 2
  d = {}
  merge_dict({'a': 1}, d)
  merge_dict({'a': 2, 'b': 3}, d)
  assert d == {'a': [1, 2], 'b': [3]}


def test_parse_size():
  assert parse_size(1024) == 1024
  assert parse_size('2KB') == 2048
  assert parse_size('1.5MB') == int(1.5 * 1024 ** 2)
  assert parse_size('3g') == 3 * 1024 ** 3
  import pytest
  with pytest.raises(ValueError):
    parse_size('10parsecs')


def test_cast_mixin():
  import dataclasses

  @dataclasses.dataclass
  class Cfg(CastMixin):
    a: int
    b: int = 2

  assert Cfg.cast(None) is None
  c = Cfg.cast({'a': 1, 'b': 5})
  assert (c.a, c.b) == (1, 5)
  assert Cfg.cast((7,)).a == 7
  same = Cfg(3)
  assert Cfg.cast(same) is same


def test_timer_and_meter():
  t = Timer()
  with t:
    time.sleep(0.01)
  assert t.elapsed >= 0.01
  m = ThroughputMeter('edges')
  m.update(1000, 0.5)
  m.update(1000, 0.5)
  assert abs(m.rate - 2000) < 1e-6
  assert 'edges/s' in m.report()


def test_timer_stop_without_start_raises():
  """Historically crashed with `TypeError: unsupported operand` on the
  None start stamp; now a clear RuntimeError."""
  import pytest
  t = Timer()
  with pytest.raises(RuntimeError, match='without a running interval'):
    t.stop()
  # stop() consumes its start(): a second stop is the same clear error
  t.start()
  t.stop()
  with pytest.raises(RuntimeError, match='without a running interval'):
    t.stop()


def test_timer_reentrant_enter_resets_cleanly():
  t = Timer()
  with t:
    time.sleep(0.002)
  first = t.elapsed
  assert not t.running
  with t:  # reuse: restarts the interval, keeps accumulating
    time.sleep(0.002)
  assert t.elapsed >= first + 0.002
  # an explicit stop() inside the body is tolerated by __exit__
  with t:
    t.stop()
  assert not t.running
  # back-to-back start() calls restart the stamp instead of corrupting
  t.reset()
  t.start()
  t.start()
  assert t.stop() < 10.0  # one interval's worth, not garbage


def test_meter_report_auto_scales_unit():
  """Sub-million rates used to print '0.00M edges/s' (hard-coded /1e6);
  the unit now auto-scales across raw / K / M."""
  def at_rate(rate):
    m = ThroughputMeter('req')
    m.update(rate, 1.0)
    return m.report()
  assert at_rate(42) == '42.00 req/s'
  assert at_rate(2_000) == '2.00K req/s'
  assert at_rate(3_500_000) == '3.50M req/s'
  assert at_rate(999) == '999.00 req/s'
  assert ThroughputMeter('req').report() == '0.00 req/s'


def test_prefetch_joins_worker_on_abandon():
  """An abandoned/closed consumer must stop AND JOIN the prefetch
  worker; before the fix the daemon thread (and the batch references it
  held) leaked until process exit."""
  from glt_tpu.utils.prefetch import PrefetchIterator

  def endless():
    i = 0
    while True:
      yield i
      i += 1

  p = PrefetchIterator(endless(), depth=2)
  it = iter(p)
  assert next(it) == 0
  assert p.worker_thread is not None and p.worker_thread.is_alive()
  it.close()  # abandon mid-stream -> generator finally -> stop + join
  assert not p.worker_thread.is_alive()


def test_prefetch_joins_worker_on_exhaustion():
  from glt_tpu.utils.prefetch import prefetch
  p = prefetch(iter(range(5)), depth=2)
  assert list(p) == [0, 1, 2, 3, 4]
  assert not p.worker_thread.is_alive()


def test_mesh_helpers():
  from glt_tpu.parallel import make_mesh, replicated, row_sharded
  mesh = make_mesh(8)
  assert mesh.shape['data'] == 8
  assert replicated(mesh).spec == jax.sharding.PartitionSpec()
  assert row_sharded(mesh).spec == jax.sharding.PartitionSpec('data')


def test_force_backend_guard():
  """The platform-selection guard: idempotent when the requested
  platform is already active; a too-late DIFFERENT platform raises."""
  import pytest
  from glt_tpu.utils.backend import force_backend
  import jax
  jax.devices()  # ensure the (cpu) backend is initialized
  assert force_backend('cpu') == 'cpu'  # idempotent, no error
  with pytest.raises(RuntimeError, match='after backend'):
    force_backend('tpu')
  # env-driven resolution: nothing set -> untouched
  import os
  for v in ('GLT_BENCH_PLATFORM', 'GLT_PLATFORM'):
    assert v not in os.environ or os.environ.pop(v)
  assert force_backend() is None
