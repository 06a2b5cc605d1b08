"""Layer ``model_step``: the share of the embedding tables' rows that a
step reads: 100 x the step's ``embedding_rows`` counter (distinct rows of
each table under the plan's trim: every live item slot, the live users of
the seed prefix) over the tables' rows, mean over the window's steps that
the trainer still holds (``chipbench/counter_window.py``). Dense Adam
updates every row whatever this reads: the smaller it is, the more of the
update moves rows that no batch touched."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'embedding_rows' not in found:
    return None
  return found['embedding_rows']['occupancy_pct']
