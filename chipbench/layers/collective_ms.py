"""Layer ``collectives``: summed device duration per step of the
collective operations (all-to-all, all-reduce, all-gather,
collective-permute, reduce-scatter), on the busiest device. A trace with
no collective in it gives nothing."""


def read(run):
  tr = run['trace']
  if not tr['top_collective_s']:
    return None
  return tr['top_collective_s'] * 1e3 / tr['steps']
