"""Layer ``sampler``: the share of the hops' frontier slots whose rows
were read: 100 x the step's ``hop_rows_read`` counter (the frontier rows a
hop read ``indptr`` and ``indices`` for: its live rows in whole chunks,
or every slot where it took the plain read) over the frontiers' slots,
every hop, relation and chip, mean over the window's steps that the
trainer still holds (``chipbench/counter_window.py``). A step without the
counter (the parent's; the enclosing-subgraph step) says nothing."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'hop_rows_read' not in found:
    return None
  return found['hop_rows_read']['occupancy_pct']
