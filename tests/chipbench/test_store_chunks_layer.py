"""``store_chunks_gathered_pct``, on the CPU: the arithmetic on hand-made
counters (one chip, and by node type), the cases in which the reader says
nothing (a trainer whose step has no such counter: the parent's, and a
store that exchanges), the manifest's entry, and the read against a real
tiny trainer. The share itself comes from the chip."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import counter_window

from test_counter_layers import Trainer, handed, reader

NAME = 'store_chunks_gathered_pct'
ONE_CHIP = ['papers100m-c1.fused', 'rgat-igbh-c1.fused',
            'link-papers100m-c1.fused', 'hgt-igbh-c1.fused']


@pytest.fixture(autouse=True)
def fresh_window():
  counter_window._TAKEN.clear()
  yield
  counter_window._TAKEN.clear()


class InPlace(Trainer):
  """A one-chip step whose store gathers ``chunks`` of ``slots`` chunks a
  step: scalars, or one entry a node type."""

  def __init__(self, calls, chunks, slots):
    super().__init__(calls)
    self.chunks, self.slots = chunks, slots

  def counters(self):
    out = super().counters()
    n = out['step'].shape[0]
    out['store_chunks'] = np.broadcast_to(
        np.asarray(self.chunks, np.int32), (n, 1) + np.shape(self.chunks))
    return out

  def counter_slots(self):
    return dict(super().counter_slots(),
                store_chunks=np.asarray(self.slots, np.int64))


def test_the_share_of_chunks_gathered(monkeypatch, capsys):
  # c1's shape: 115 chunks of 8,192 over 937,984 slots, 41 of them live
  handed_run = handed(monkeypatch, [InPlace(51, 41, 115)], steps=40)
  assert reader(NAME)(handed_run) == pytest.approx(100 * 41 / 115)
  line = [l for l in capsys.readouterr().err.splitlines()
          if l.startswith('chipbench: counters ')][0]
  found = json.loads(line[len('chipbench: counters '):])
  assert found['store_chunks'] == {
      'mean': 41.0, 'slots': 115, 'max': 41,
      'occupancy_pct': pytest.approx(100 * 41 / 115)}


def test_typed_chunks_are_summed_over_the_node_types(monkeypatch):
  # six types, each at least a chunk: 6 + 2 + 1 + 1 + 1 + 1 of 59 + 37
  # + 31 x 3 + 8
  handed_run = handed(monkeypatch, [InPlace(
      51, [6, 2, 1, 1, 1, 1], [59, 37, 31, 31, 31, 8])], steps=40)
  assert reader(NAME)(handed_run) == pytest.approx(100 * 12 / 197)


@pytest.mark.parametrize('trainer', [
    Trainer(51),                        # the parent's step: no such counter
    Trainer(51, chips=4, store=True),   # a store that exchanges
    object()])                          # a program without counters()
def test_without_the_counter_the_reader_says_nothing(monkeypatch, trainer):
  assert reader(NAME)(handed(monkeypatch, [trainer], steps=40)) is None


def test_the_entry_names_the_four_one_chip_cells():
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    m = json.load(f)
  cells = {w['name']: w for w in m['workloads']}
  entry = {p['name']: p for p in m['per_layer']}[NAME]
  assert entry == {
      'name': NAME, 'unit': '%', 'better': 'lower',
      'source': 'program_counter', 'layer': 'feature_store',
      'moves': 'seeds_per_s', 'workloads': entry['workloads']}
  assert entry['workloads'][:4] == ONE_CHIP
  assert all(cells[c]['chips'] == 1 for c in entry['workloads'])
  assert 'papers100m-c4.fused' not in entry['workloads']
  assert callable(reader(NAME))


@pytest.mark.parametrize('chips', [1, 4])
def test_the_window_of_a_real_tiny_trainer(monkeypatch, chips):
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  from chipbench.drivers import fused
  from test_chipbench import tiny_cell
  _, _, cfg, traffic = tiny_cell(chips)
  s = fused.build(cfg, traffic, chips, 13)
  for t in range(3, 3 + 20):
    loss = fused.step(s, t)
  np.asarray(loss)
  before = fused.compilations(s)
  share = reader(NAME)(handed(monkeypatch, [s.trainer], steps=20))
  assert fused.compilations(s) == before == s.compiled_before
  if chips == 1:
    # the tiny cell's request slots are one chunk, and it holds the seeds
    assert share == pytest.approx(100.0)
  else:
    assert share is None
