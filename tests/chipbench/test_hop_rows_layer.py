"""``hop_rows_read_pct``, on the CPU: the arithmetic on hand-made counters
(by hop, and by relation and hop), the cases in which the reader says
nothing (a trainer whose step has no such counter: the parent's, and the
enclosing-subgraph step's), the manifest's entry, and the read against
real tiny trainers, which traces and compiles nothing. The share itself
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

NAME = 'hop_rows_read_pct'
LISTED = ['papers100m-c1.fused', 'papers100m-c4.fused',
          'rgat-igbh-c1.fused', 'link-papers100m-c1.fused',
          'hgt-igbh-c1.fused']


@pytest.fixture(autouse=True)
def fresh_window():
  counter_window._TAKEN.clear()
  yield
  counter_window._TAKEN.clear()


class Hops(Trainer):
  """A step whose hops read ``rows`` of ``slots`` frontier rows a step:
  one entry a hop, or a relation a row."""

  def __init__(self, calls, rows, slots, chips=1):
    super().__init__(calls, chips=chips)
    self.rows, self.slots = rows, slots

  def counters(self):
    out = super().counters()
    n = out['step'].shape[0]
    out['hop_rows_read'] = np.broadcast_to(
        np.asarray(self.rows, np.int32),
        (n, self.chips) + np.shape(self.rows))
    return out

  def counter_slots(self):
    return dict(super().counter_slots(),
                hop_rows_read=np.asarray(self.slots, np.int64))


def test_the_share_of_frontier_rows_read(monkeypatch, capsys):
  # c1's frontiers; hop 0 whole, hop 1 in 3 chunks of 4,096, hop 2 in 15
  rows, slots = [1024, 12288, 61440], [1024, 15360, 153600]
  handed_run = handed(monkeypatch, [Hops(51, rows, slots)], steps=40)
  assert reader(NAME)(handed_run) == pytest.approx(
      100 * sum(rows) / sum(slots))
  line = [l for l in capsys.readouterr().err.splitlines()
          if l.startswith('chipbench: counters ')][0]
  found = json.loads(line[len('chipbench: counters '):])
  assert found['hop_rows_read'] == {
      'mean': [float(r) for r in rows], 'slots': slots, 'max': rows,
      'occupancy_pct': pytest.approx(100 * sum(rows) / sum(slots))}


def test_every_chip_counts(monkeypatch):
  handed_run = handed(monkeypatch, [Hops(51, [4, 8], [4, 32], chips=4)],
                      steps=40)
  assert reader(NAME)(handed_run) == pytest.approx(100 * 12 / 36)


def test_typed_rows_are_summed_over_relations_and_hops(monkeypatch):
  # three relations; the last is read in hop 0 alone
  rows = [[64, 960, 8192], [64, 960, 4096], [64, 0, 0]]
  slots = [[64, 960, 48000], [64, 960, 48000], [64, 0, 0]]
  handed_run = handed(monkeypatch, [Hops(51, rows, slots)], steps=40)
  assert reader(NAME)(handed_run) == pytest.approx(
      100 * np.sum(rows) / np.sum(slots))


@pytest.mark.parametrize('trainer', [
    Trainer(51),                        # the parent's step: no such counter
    Trainer(51, chips=4, store=True),   # the same over four chips
    object()])                          # a program without counters()
def test_without_the_counter_the_reader_says_nothing(monkeypatch, trainer):
  assert reader(NAME)(handed(monkeypatch, [trainer], steps=40)) is None


def test_the_entry_names_the_cells_whose_trainer_holds_the_counter():
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    m = json.load(f)
  cells = {w['name'] for w in m['workloads']}
  assert m['per_layer'][-1]['name'] == NAME   # appended, nothing moved
  entry = m['per_layer'][-1]
  assert entry == {
      'name': NAME, 'unit': '%', 'better': 'lower',
      'source': 'program_counter', 'layer': 'sampler',
      'moves': 'seeds_per_s', 'workloads': LISTED}
  assert set(LISTED) <= cells
  # the enclosing-subgraph step's one hop is left as it is: no counter.
  # The user-item cell's trainer holds the counter (its frontiers are 80
  # % live, so every hop takes the plain read and it reads 100), but that
  # cell's accepted test lets no reader list it but its own
  # (tests/chipbench/test_bisage_cell.py)
  assert cells - set(LISTED) == {'seal-papers100m-c1.fused',
                                 'bisage-taobao-c1.fused'}
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
  # the tiny cell's frontiers are one chunk each: every slot is read
  assert share == pytest.approx(100.0)


def test_the_window_of_a_tiny_trainer_that_reads_by_chunks(monkeypatch):
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  from glt_tpu.ops import sample
  from chipbench.drivers import fused
  from test_chipbench import tiny_cell
  monkeypatch.setattr(sample, 'HOP_CHUNK', 8)
  _, _, cfg, traffic = tiny_cell(1)
  s = fused.build(cfg, traffic, 1, 13)
  # a quarter of the seeds: every later frontier is mostly pads (at the
  # tiny cell's full batch nearly every slot is live, and the live count
  # hands each hop to the plain read)
  s.n_valid = s.n_valid // 4
  for t in range(3, 3 + 20):
    loss = fused.step(s, t)
  np.asarray(loss)
  share = reader(NAME)(handed(monkeypatch, [s.trainer], steps=20))
  counted, slots = s.trainer.counters(), s.trainer.counter_slots()
  assert 0 < share < 60
  assert share == pytest.approx(
      100 * counted['hop_rows_read'][3:].mean(axis=(0, 1)).sum()
      / slots['hop_rows_read'].sum())


def test_the_enclosing_subgraph_step_has_no_such_counter(monkeypatch):
  from chipbench.drivers import seal_fused
  import test_seal_cell
  _, _, cfg, traffic = test_seal_cell.tiny_cell()
  s = seal_fused.build(cfg, traffic, 1, 5)
  assert 'hop_rows_read' not in s.trainer.counter_slots()
  assert 'hop_rows_read' not in s.trainer._counted[-1][1]
