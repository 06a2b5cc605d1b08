"""Layer ``sampler``: 100 x the step's ``tiles_matched`` counter (the
tiles of ``indices`` the induction gathered and matched against their
links' node slots: the batch's live tiles in whole chunks of its loop)
over the ``2B`` links' tile budgets, mean over the window's held steps
(``chipbench/counter_window.py``). A step without the counter (the
parent's, which matched every budgeted tile) says nothing."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'tiles_matched' not in found:
    return None
  return found['tiles_matched']['occupancy_pct']
