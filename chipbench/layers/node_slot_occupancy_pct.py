"""Layer ``sampler``: the share of the step's padded node slots that hold
a node: 100 x the sum of the step's ``nodes_by_hop`` counter (the
sampler's own count of node rows new at each hop) over the node budget,
all hops, types and chips, mean over the window's steps that the trainer
still holds (``chipbench/counter_window.py``). It is also the share of
the feature store's request slots that are live and of the model's input
rows that are real."""
from chipbench import counter_window


def read(run):
  return counter_window.occupancy_pct(run, 'nodes_by_hop')
