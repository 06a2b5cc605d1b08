"""Layer ``sampler``: ``edge_slot_occupancy_pct`` for the user-item cell:
100 x the step's ``edges_by_hop`` counter over the edge slots, the three
relations and both hops, mean over the window's steps that the trainer
still holds (``chipbench/counter_window.py``)."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'edges_by_hop' not in found:
    return None
  return found['edges_by_hop']['occupancy_pct']
