"""The counter window and its three readers, on the CPU: the arithmetic on
hand-made counters, the window's own ordinals kept alone, the cases in
which a reader says nothing, the manifest's three entries, and the read
against a real tiny trainer, which traces and compiles nothing. The
occupancies themselves come from the chip."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import counter_window, run

NEW = ('node_slot_occupancy_pct', 'edge_slot_occupancy_pct', 'store_rounds')


def reader(name):
  return importlib.import_module('chipbench.layers.' + name).read


@pytest.fixture(autouse=True)
def fresh_window():
  counter_window._TAKEN.clear()
  yield
  counter_window._TAKEN.clear()


class Trainer:
  """A live step program that holds the newest 128 of ``calls`` steps on
  ``chips`` chips, each with the same counts made by hand."""

  def __init__(self, calls, chips=1, store=False):
    self.calls, self.chips, self.store, self.reads = calls, chips, store, 0

  def counters(self):
    self.reads += 1
    step = np.arange(self.calls, dtype=np.int64)[-128:]
    each = lambda a: np.broadcast_to(
        np.asarray(a, np.int32), (step.shape[0], self.chips)
        + np.shape(a)).copy()
    out = {'step': step, 'nodes_by_hop': each([4, 6, 10, 20]),
           'edges_by_hop': each([8, 12, 40])}
    if self.store:
      out.update(store_rounds=each(1), store_bucket_max=each(30),
                 store_requests=each(40))
      out['store_rounds'][step % 2 == 1] = 2   # every other step drains
    return out

  def counter_slots(self):
    slots = {'nodes_by_hop': [4, 12, 24, 60], 'edges_by_hop': [12, 24, 64]}
    if self.store:
      slots.update(store_rounds=4, store_bucket_max=32, store_requests=100)
    return {k: np.asarray(v, np.int64) for k, v in slots.items()}


def handed(monkeypatch, programs, steps, warmup=3):
  """``run`` as ``run_cell`` hands it to a reader; ``programs`` are what
  the process holds live."""
  from glt_tpu.obs import device
  monkeypatch.setattr(device, 'live_step_programs', lambda: programs)
  return {'traffic': {'warmup_steps': warmup}, 'window': {'steps': steps}}


def test_readers_arithmetic_on_hand_made_counters(monkeypatch, capsys):
  trainer = Trainer(3 + 40 + 8, chips=4, store=True)
  handed_run = handed(monkeypatch, [trainer], steps=40)
  assert reader('node_slot_occupancy_pct')(handed_run) == pytest.approx(
      100 * 40 / 100)
  assert reader('edge_slot_occupancy_pct')(handed_run) == pytest.approx(
      100 * 60 / 100)
  # ordinals 3 .. 42: twenty odd steps of two rounds, twenty even of one
  assert reader('store_rounds')(handed_run) == pytest.approx(1.5)
  assert trainer.reads == 1   # once a process, shared by the readers
  said = [l for l in capsys.readouterr().err.splitlines()
          if l.startswith('chipbench: counters ')]
  assert len(said) == 1
  found = json.loads(said[0][len('chipbench: counters '):])
  assert (found['steps'], found['first_step'], found['last_step']) == (
      40, 3, 42)
  assert found['nodes_by_hop']['mean'] == [4.0, 6.0, 10.0, 20.0]
  assert found['nodes_by_hop']['slots'] == [4, 12, 24, 60]
  assert found['edges_by_hop']['mean'] == [8.0, 12.0, 40.0]
  assert found['store_bucket_max_over_cap'] == {'mean': 30 / 32,
                                                'max': 30 / 32}
  assert found['store_requests']['occupancy_pct'] == pytest.approx(40.0)
  assert found['read_s'] >= 0


@pytest.mark.parametrize('after', [0, 8, 100])
def test_the_windows_own_ordinals_are_kept_alone(after):
  """Warm-up steps before the window and the scope windows' steps after
  it are left out, whatever of the window the trainer still holds."""
  trainer = Trainer(3 + 60 + after)
  counted, slots = counter_window.held(trainer, 3, 60)
  last = 62
  first = max(3, 3 + 60 + after - 128)
  assert counted['step'].tolist() == list(range(first, last + 1))
  assert counted['nodes_by_hop'].shape == (last + 1 - first, 1, 4)
  assert sorted(slots) == ['edges_by_hop', 'nodes_by_hop']


def test_a_long_window_keeps_its_newest_steps():
  trainer = Trainer(3 + 411 + 8)
  counted, _ = counter_window.held(trainer, 3, 411)
  assert counted['step'].tolist() == list(range(294, 414))


@pytest.mark.parametrize('programs,steps', [
    ([], 40),                          # no live step program
    ([Trainer(51), Trainer(51)], 40),  # two: which was the window's?
    ([object()], 40),                  # a program without counters()
    ([Trainer(3 + 15 + 8)], 15),       # fewer than 16 of the window's steps
    ([Trainer(3 + 40 + 120)], 40),     # the window's steps have left
])
def test_readers_say_nothing(monkeypatch, programs, steps):
  handed_run = handed(monkeypatch, programs, steps)
  for name in NEW:
    assert reader(name)(handed_run) is None


def test_a_step_without_counter_slots_is_left_alone():
  class Older:
    def counters(self):
      raise AssertionError('not read')
  assert counter_window.held(Older(), 3, 40) is None


def test_one_chip_has_no_rounds_to_report(monkeypatch):
  handed_run = handed(monkeypatch, [Trainer(51)], steps=40)
  assert reader('store_rounds')(handed_run) is None
  assert reader('node_slot_occupancy_pct')(handed_run) == pytest.approx(40.0)


def test_typed_counters_are_summed_over_types_and_relations(monkeypatch,
                                                            capsys):
  class Typed(Trainer):
    counter_node_types = ('paper', 'author')
    counter_edge_types = (('paper', 'cites', 'paper'),
                          ('author', 'rev_writes', 'paper'))

    def counters(self):
      out = super().counters()
      n = out['step'].shape[0]
      out['nodes_by_hop'] = np.broadcast_to(
          np.asarray([[4, 2, 2], [0, 1, 1]], np.int32), (n, 1, 2, 3))
      out['edges_by_hop'] = np.broadcast_to(
          np.asarray([[3, 1], [2, 0]], np.int32), (n, 1, 2, 2))
      return out

    def counter_slots(self):
      return {'nodes_by_hop': np.asarray([[4, 12, 24], [0, 20, 40]]),
              'edges_by_hop': np.asarray([[12, 24], [24, 0]])}

  handed_run = handed(monkeypatch, [Typed(51)], steps=40)
  assert reader('node_slot_occupancy_pct')(handed_run) == pytest.approx(10.0)
  assert reader('edge_slot_occupancy_pct')(handed_run) == pytest.approx(10.0)
  line = [l for l in capsys.readouterr().err.splitlines()
          if l.startswith('chipbench: counters ')][0]
  found = json.loads(line[len('chipbench: counters '):])
  assert found['node_types'] == ['paper', 'author']
  assert found['edge_types'] == ['paper__cites__paper',
                                 'author__rev_writes__paper']
  assert found['nodes_by_hop']['mean'] == [[4.0, 2.0, 2.0], [0.0, 1.0, 1.0]]


FIVE = ['papers100m-c1.fused', 'papers100m-c4.fused', 'rgat-igbh-c1.fused',
        'link-papers100m-c1.fused', 'hgt-igbh-c1.fused']


def test_the_three_entries_resolve_and_name_cells_that_exist():
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    m = json.load(f)
  cells = {w['name']: w for w in m['workloads']}
  names = [p['name'] for p in m['per_layer']]
  by_name = {p['name']: p for p in m['per_layer']}
  # there and in this order, not where: a later PR's entries go behind
  assert tuple(n for n in names if n in NEW) == NEW
  for name in NEW:
    entry = by_name[name]
    assert set(entry) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    assert entry['source'] == 'program_counter'
    assert entry['moves'] == 'seeds_per_s'
    assert set(entry['workloads']) <= set(cells)
    assert callable(reader(name))
  for name in NEW[:2]:
    assert by_name[name]['workloads'][:5] == FIVE
    assert (by_name[name]['layer'], by_name[name]['unit'],
            by_name[name]['better']) == ('sampler', '%', 'higher')
  rounds = by_name['store_rounds']
  # only a store over more than one chip exchanges
  assert rounds['workloads'][0] == 'papers100m-c4.fused'
  assert all(cells[c]['chips'] > 1 for c in rounds['workloads'])
  assert (rounds['layer'], rounds['unit'], rounds['better']) == (
      'feature_store', 'rounds', 'lower')
  before = {p['layer'] for p in m['per_layer'][:names.index(NEW[0])]}
  assert {by_name[n]['layer'] for n in NEW} <= before


@pytest.mark.parametrize('chips', [1, 4])
def test_the_window_of_a_real_tiny_trainer(monkeypatch, chips):
  """Three warm-up steps, a window of twenty, eight more as the scope
  windows drive them: the readers see the twenty, and reading traces and
  compiles nothing (the traced run's limit of 0 ``compilations``)."""
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  from chipbench.drivers import fused
  from test_chipbench import tiny_cell
  _, _, cfg, traffic = tiny_cell(chips)
  s = fused.build(cfg, traffic, chips, 11)
  for t in range(3, 3 + 20 + 8):
    loss = fused.step(s, t)
  np.asarray(loss)
  before = fused.compilations(s)
  handed_run = handed(monkeypatch, [s.trainer], steps=20)
  nodes = reader('node_slot_occupancy_pct')(handed_run)
  edges = reader('edge_slot_occupancy_pct')(handed_run)
  rounds = reader('store_rounds')(handed_run)
  assert fused.compilations(s) == before == s.compiled_before
  assert 0 < nodes <= 100 and 0 < edges <= 100
  found = counter_window.taken(handed_run)
  assert (found['steps'], found['first_step'], found['last_step']) == (
      20, 3, 22)
  b, (k0, k1, k2) = s.batch, s.fanout
  assert found['nodes_by_hop']['slots'] == [b, b * k0, b * k0 * k1,
                                            b * k0 * k1 * k2]
  assert found['nodes_by_hop']['mean'][0] == b   # distinct seeds
  if chips == 1:
    assert rounds is None
  else:
    assert 1 <= rounds <= int(s.trainer.counter_slots()['store_rounds'])
    assert found['store_requests']['occupancy_pct'] == pytest.approx(nodes)
