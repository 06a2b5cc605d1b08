"""Layer ``feature_store``: the share of the exchange's chunks of slots
that its pack, its owner's serve and its stitch gathered: 100 x the
step's ``store_exchange_chunks`` counter (the chunks of ``SERVE_CHUNK``
request or bucket slots that held a request of a drain round, summed over
the three loops and the rounds) over the chunks one round's slots are cut
into, every chip, mean over the window's steps that the trainer still
holds (``chipbench/counter_window.py``). Only where the store exchanges
(four chips); a step without the counter (the parent's, or a store that
serves in place) says nothing."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'store_exchange_chunks' not in found:
    return None
  return counter_window.occupancy_pct(run, 'store_exchange_chunks')
