"""``seal_tiles_matched_pct``, on the CPU: the arithmetic on a hand-made
counter window, the cases in which the reader says nothing (a step
without the counter: the parent's; a program without counters), the
manifest's entry resolving to its file, and the read against the real
tiny cell's trainer, which traces and compiles nothing. The share itself
comes from the chip."""
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

NAME = 'seal_tiles_matched_pct'
CELL = 'seal-papers100m-c1.fused'


@pytest.fixture(autouse=True)
def fresh_window():
  counter_window._TAKEN.clear()
  yield
  counter_window._TAKEN.clear()


class Enclosing(Trainer):
  """A step that matched ``matched`` of ``budget`` tiles, every step."""

  def __init__(self, calls, matched, budget, with_counter=True):
    super().__init__(calls)
    self.matched, self.budget = matched, budget
    self.with_counter = with_counter

  def counters(self):
    out = super().counters()
    each = lambda v: np.full((out['step'].shape[0], self.chips), v, np.int32)
    out['tiles_read'] = each(self.matched - 1500)
    if self.with_counter:
      out['tiles_matched'] = each(self.matched)
    return out

  def counter_slots(self):
    slots = dict(super().counter_slots(), tiles_read=np.int64(self.budget))
    if self.with_counter:
      slots['tiles_matched'] = np.int64(self.budget)
    return slots


def test_the_share_of_the_budgeted_tiles_matched(monkeypatch, capsys):
  # the cell's: 512 links x 512 tiles, 35 chunks of 2,048 a step
  handed_run = handed(monkeypatch, [Enclosing(51, 71680, 262144)], steps=40)
  assert reader(NAME)(handed_run) == pytest.approx(100 * 71680 / 262144)
  line = [l for l in capsys.readouterr().err.splitlines()
          if l.startswith('chipbench: counters ')][0]
  found = json.loads(line[len('chipbench: counters '):])
  assert found['tiles_matched'] == {
      'mean': 71680.0, 'slots': 262144, 'max': 71680,
      'occupancy_pct': pytest.approx(100 * 71680 / 262144)}
  assert found['tiles_read']['mean'] == 70180.0


@pytest.mark.parametrize('trainer', [
    Enclosing(51, 71680, 262144, with_counter=False),   # the parent's step
    Trainer(51),                        # a step that encloses nothing
    object()])                          # a program without counters()
def test_without_the_counter_the_reader_says_nothing(monkeypatch, trainer):
  assert reader(NAME)(handed(monkeypatch, [trainer], steps=40)) is None


def test_the_entry_names_the_one_cell_that_encloses():
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    m = json.load(f)
  # the entry is there, wherever later PRs append theirs
  assert {p['name']: p for p in m['per_layer']}[NAME] == {
      'name': NAME, 'unit': '%', 'better': 'lower',
      'source': 'program_counter', 'layer': 'sampler',
      'moves': 'seeds_per_s', 'workloads': [CELL]}
  assert CELL in {w['name'] for w in m['workloads']}
  assert os.path.exists(os.path.join(REPO, 'chipbench', 'layers',
                                     NAME + '.py'))
  assert callable(reader(NAME))


def test_the_window_of_the_real_tiny_cell(monkeypatch):
  from glt_tpu.ops import subgraph
  from chipbench.drivers import seal_fused
  import test_seal_cell
  # 16 links x 24 tiles: blocks of 8 and chunks of 64, so that the loop
  # takes trips
  monkeypatch.setattr(subgraph, 'MATCH_BLOCK', 8)
  monkeypatch.setattr(subgraph, 'MATCH_CHUNK', 64)
  _, _, cfg, traffic = test_seal_cell.tiny_cell()
  s = seal_fused.build(cfg, traffic, 1, 5)
  for t in range(3, 3 + 20):
    loss = seal_fused.step(s, t)
  np.asarray(loss)
  before = seal_fused.compilations(s)
  share = reader(NAME)(handed(monkeypatch, [s.trainer], steps=20))
  assert seal_fused.compilations(s) == before
  newest = {k: np.asarray(v) for k, v in s.trainer._counted[-1][1].items()
            if k in ('tiles_matched', 'tiles_read')}
  slots = s.trainer.counter_slots()
  assert slots['tiles_matched'] == slots['tiles_read'] == 16 * 24
  assert (newest['tiles_matched'] % 64 == 0).all()
  assert (newest['tiles_read'] <= newest['tiles_matched']).all()
  assert (newest['tiles_matched'] - newest['tiles_read'] < 64 + 16 * 8).all()
  assert 0 < share <= 100 * (16 * 24 + 63) // 64 * 64 / (16 * 24)
