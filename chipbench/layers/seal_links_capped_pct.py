"""Layer ``sampler``: 100 x the step's ``links_capped`` counter (links
with an endpoint wider than the fanout, whose fringe is a sample and
not the whole neighbourhood) over the ``2B`` links, mean over the window's
held steps (``chipbench/counter_window.py``)."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'links_capped' not in found:
    return None
  return found['links_capped']['occupancy_pct']
