"""Layer ``feature_store``: the share of the store's chunks of request
slots that held a valid request and were gathered: 100 x the step's
``store_chunks`` counter over the chunks its request slots are cut into,
every node type, mean over the window's steps that the trainer still
holds (``chipbench/counter_window.py``). Only where the store serves in
place (one chip): over more chips it exchanges and counts its rounds."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'store_chunks' not in found:
    return None
  return found['store_chunks']['occupancy_pct']
