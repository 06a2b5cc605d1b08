"""The scope window and its four readers, on the CPU: the arithmetic on a
hand-made profile, the cases in which a reader says nothing, and that the
window's own inputs trace and compile nothing (the traced run's limit of
0 ``compilations``). The profile's numbers come from the chip."""
import importlib
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import run, scope_window
from chipbench.drivers import fused

PROFILE = {'device': '/device:TPU:0', 'steps': 6, 'busy_ms': 200.0,
           'window_ms': 201.0,
           'layers': {'sampler': 40.0, 'feature_store': 60.0,
                      'model_step': 90.0, 'collectives': 6.0},
           'stages': {}, 'mixed_ms': 3.0, 'unscoped_ms': 4.0,
           'top_ops': [], 'mixed_ops': [], 'idle_gaps': []}
READERS = {'sampler_device_ms': 40.0, 'feature_device_ms': 60.0,
           'model_device_ms': 90.0, 'scope_unattributed_pct': 3.5}


def reader(name):
  return importlib.import_module('chipbench.layers.' + name).read


@pytest.fixture(autouse=True)
def fresh_window():
  scope_window._PROFILE.clear()
  yield
  scope_window._PROFILE.clear()


class Program:
  """A live step program that answers with a profile made by hand."""
  mesh = None

  def __init__(self, profile):
    self.profile, self.calls = profile, 0

  def scope_profile(self, params, opt_state, batches):
    self.calls += 1
    return self.profile


def handed(monkeypatch, programs, window_busy_ms=201.0):
  """``run`` as ``run_cell`` hands it to a reader, the window's trace
  reduced to its sums; ``programs`` are what the process holds live."""
  from glt_tpu.obs import device
  monkeypatch.setattr(device, 'live_step_programs', lambda: programs)
  monkeypatch.setattr(scope_window, 'inputs',
                      lambda *a, **kw: (None, None, []))
  return {'cfg': {}, 'traffic': {}, 'chips': 1,
          'trace': {'top_busy_s': 10 * window_busy_ms / 1e3, 'steps': 10}}


@pytest.mark.parametrize('name', sorted(READERS))
def test_a_reader_on_a_hand_made_profile(monkeypatch, name):
  program = Program(PROFILE)
  run_ = handed(monkeypatch, [program])
  assert reader(name)(run_) == pytest.approx(READERS[name])
  for other in READERS:       # one session a process, shared
    reader(other)(run_)
  assert program.calls == 1


@pytest.mark.parametrize('name', sorted(READERS))
def test_a_reader_says_nothing_when_the_busy_times_disagree(
    monkeypatch, capsys, name):
  run_ = handed(monkeypatch, [Program(PROFILE)], window_busy_ms=210.0)
  assert reader(name)(run_) is None
  assert 'differ by more than 3 %' in capsys.readouterr().err


@pytest.mark.parametrize('programs', [[], [Program(PROFILE)] * 2])
def test_a_reader_says_nothing_without_the_one_step_program(
    monkeypatch, capsys, programs):
  run_ = handed(monkeypatch, programs)
  assert reader('sampler_device_ms')(run_) is None
  assert 'live step programs' in capsys.readouterr().err


def test_a_reader_says_nothing_against_a_program_without_scopes(
    monkeypatch, capsys):
  """The parent commit: the benchmark's files over a ``glt_tpu`` that
  has no ``obs.device``. No reader raises; each metric is left out."""
  run_ = handed(monkeypatch, [Program(PROFILE)])
  monkeypatch.setitem(sys.modules, 'glt_tpu.obs.device', None)
  assert [reader(n)(run_) for n in sorted(READERS)] == [None] * 4
  assert 'no glt_tpu.obs.device' in capsys.readouterr().err


def test_a_layer_with_no_op_is_left_out(monkeypatch):
  layers = {k: v for k, v in PROFILE['layers'].items() if k != 'sampler'}
  run_ = handed(monkeypatch, [Program(dict(PROFILE, layers=layers))])
  assert reader('sampler_device_ms')(run_) is None
  assert reader('model_device_ms')(run_) == pytest.approx(90.0)


@pytest.mark.parametrize('chips', [1, 4])
def test_the_scope_window_traces_and_compiles_nothing(monkeypatch, chips):
  """A rehearsal of the traced run's scope window on the CPU: the real
  trainer, the window's real inputs and a real profiler session; only
  the reduction is answered by hand (a CPU trace has no TPU plane)."""
  from glt_tpu.obs import device
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  _, cell, cfg, traffic = run.load_cell('papers100m-c1.fused')
  cfg = dict(cfg, num_nodes=20000, num_edges=291000, feature_dim=16,
             hidden_dim=32, num_classes=7)
  traffic = dict(traffic, batch_per_chip=16, fanout=[4, 3, 2])
  s = fused.build(cfg, traffic, chips, 3_000_000_019)
  for t in range(3, 6):                 # the window
    np.asarray(fused.step(s, t))
  assert fused.compilations(s) == s.compiled_before
  seen = {}

  def reduce_(events, host_spans, step_program):
    seen['host'] = {name for name, _, _ in host_spans}
    return PROFILE

  monkeypatch.setattr(device, 'reduce_scopes', reduce_)
  monkeypatch.setattr(device, 'live_step_programs', lambda: [s.trainer])
  run_ = {'cfg': cfg, 'traffic': traffic, 'chips': chips,
          'trace': {'top_busy_s': 2.0, 'steps': 10}}
  assert reader('model_device_ms')(run_) == pytest.approx(90.0)
  assert 'train.step/dispatch' in seen['host']
  np.asarray(fused.step(s, 6))          # the program is as it was
  assert fused.compilations(s) == s.compiled_before
  compared = fused.verify(s)
  assert compared['compilations'] == (0, 0)
  assert all(v <= limit for v, limit in compared.values()), compared
