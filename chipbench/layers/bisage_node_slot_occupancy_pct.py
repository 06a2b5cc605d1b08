"""Layer ``sampler``: ``node_slot_occupancy_pct`` for the user-item cell:
100 x the step's ``nodes_by_hop`` counter over the node budget, both
types and all hops, mean over the window's steps that the trainer still
holds (``chipbench/counter_window.py``). Also the share of the embedding
take's rows that are real."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'nodes_by_hop' not in found:
    return None
  return found['nodes_by_hop']['occupancy_pct']
