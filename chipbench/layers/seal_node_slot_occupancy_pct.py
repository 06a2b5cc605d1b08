"""Layer ``sampler``: 100 x the step's ``subgraph_nodes`` counter (live
node slots, all links) over the ``2B x S`` node slots, mean over the
window's steps that the trainer still holds
(``chipbench/counter_window.py``). Also the share of the store's request
slots and of the model's rows that are real."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'subgraph_nodes' not in found:
    return None
  return found['subgraph_nodes']['occupancy_pct']
