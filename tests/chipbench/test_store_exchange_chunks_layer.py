"""``store_exchange_chunks_pct``, on the CPU: the arithmetic on hand-made
counters, the cases in which the reader says nothing (a trainer whose step
has no such counter: the parent's exchange, and a store that serves in
place), the manifest's entry, and the read against a real tiny trainer on
four chips, which traces and compiles nothing. The share itself comes
from the chip."""
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

NAME = 'store_exchange_chunks_pct'


@pytest.fixture(autouse=True)
def fresh_window():
  counter_window._TAKEN.clear()
  yield
  counter_window._TAKEN.clear()


class Exchange(Trainer):
  """A four-chip step whose exchange gathers ``chunks`` (one entry a chip)
  of the ``slots`` chunks one round's slots are cut into."""

  def __init__(self, calls, chunks, slots):
    super().__init__(calls, chips=4, store=True)
    self.chunks, self.slots = chunks, slots

  def counters(self):
    out = super().counters()
    n = out['step'].shape[0]
    out['store_exchange_chunks'] = np.broadcast_to(
        np.asarray(self.chunks, np.int32), (n, 4)).copy()
    return out

  def counter_slots(self):
    return dict(super().counter_slots(),
                store_exchange_chunks=np.asarray(self.slots, np.int64))


def test_the_share_of_chunks_the_exchange_gathered(monkeypatch, capsys):
  # c4's shape: 115 chunks of request slots twice and 115 of bucket
  # slots; 43 + 43 of request order and 40 to 90 of bucket order
  chunks = [43 + 43 + 90, 43 + 43 + 40, 43 + 43 + 41, 43 + 43 + 45]
  handed_run = handed(monkeypatch, [Exchange(51, chunks, 345)], steps=40)
  assert reader(NAME)(handed_run) == pytest.approx(
      100 * np.mean(chunks) / 345)
  line = [l for l in capsys.readouterr().err.splitlines()
          if l.startswith('chipbench: counters ')][0]
  found = json.loads(line[len('chipbench: counters '):])
  assert found['store_exchange_chunks']['slots'] == 345
  assert found['store_exchange_chunks']['max'] == 176


@pytest.mark.parametrize('trainer', [
    Trainer(51, chips=4, store=True),   # the parent's exchange: no counter
    Trainer(51),                        # a store that serves in place
    object()])                          # a program without counters()
def test_without_the_counter_the_reader_says_nothing(monkeypatch, trainer):
  assert reader(NAME)(handed(monkeypatch, [trainer], steps=40)) is None


def test_the_entry_names_the_four_chip_cell_alone():
  with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
    m = json.load(f)
  cells = {w['name']: w for w in m['workloads']}
  entry = {p['name']: p for p in m['per_layer']}[NAME]
  assert entry == {
      'name': NAME, 'unit': '%', 'better': 'lower',
      'source': 'program_counter', 'layer': 'feature_store',
      'moves': 'seeds_per_s', 'workloads': ['papers100m-c4.fused']}
  assert cells['papers100m-c4.fused']['chips'] == 4
  assert callable(reader(NAME))


@pytest.mark.parametrize('chips', [1, 4])
def test_the_window_of_a_real_tiny_trainer(monkeypatch, chips):
  from chipbench.drivers import fused
  from test_chipbench import tiny_cell
  _, _, cfg, traffic = tiny_cell(chips)
  s = fused.build(cfg, traffic, chips, 17)
  for t in range(3, 3 + 20):
    loss = fused.step(s, t)
  np.asarray(loss)
  before = fused.compilations(s)
  share = reader(NAME)(handed(monkeypatch, [s.trainer], steps=20))
  assert fused.compilations(s) == before == s.compiled_before
  if chips == 1:
    assert share is None
    return
  # the tiny cell's request and bucket slots are one chunk each, a round
  # gathers one to three of them, and its buckets drain in more rounds
  counted, slots = counter_window.held(s.trainer, 3, 20)
  got = counted['store_exchange_chunks']
  assert int(slots['store_exchange_chunks']) == 3
  assert ((got >= 1) & (got <= 3 * counted['store_rounds'])).all()
  assert share == pytest.approx(100 * got.mean() / 3)
