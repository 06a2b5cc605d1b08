"""Layer ``feature_store``: exchange rounds a step that the store's drain
ran (the step's ``store_rounds`` counter, the same on every chip: the
mesh's fullest per-owner bucket over the bucket's cap, rounded up), mean
over the window's steps that the trainer still holds
(``chipbench/counter_window.py``). Only where the store exchanges: on one
chip it serves in place and counts nothing."""
from chipbench import counter_window


def read(run):
  found = counter_window.taken(run)
  if found is None or 'store_rounds' not in found:
    return None
  return found['store_rounds']['mean']
