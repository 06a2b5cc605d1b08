"""Layer ``sampler``: the share of the step's padded edge slots that hold
a sampled edge: 100 x the sum of the step's ``edges_by_hop`` counter (the
sampler's own count of valid slots a hop) over the edge budget, all hops,
relations and chips, mean over the window's steps that the trainer still
holds (``chipbench/counter_window.py``)."""
from chipbench import counter_window


def read(run):
  return counter_window.occupancy_pct(run, 'edges_by_hop')
