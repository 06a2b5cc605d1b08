"""What the window's own steps counted, for the occupancy readers.

Every per-batch fused step returns, beside its loss, how many of its
padded node and edge slots held work (``nodes_by_hop``, ``edges_by_hop``;
over more than one chip also the feature store's ``store_rounds``,
``store_bucket_max``, ``store_requests``), and the trainer keeps the
newest steps' counts on the device (``counters()`` reads them,
``counter_slots()`` the budgets they are read against). The trainer is
the one the window drove, found through
``glt_tpu.obs.device.live_step_programs`` as ``scope_window.py`` finds it.
A step's ordinal counts the trainer's per-batch calls from 0, so the
window's own steps are ``[warmup_steps, warmup_steps + steps)``: the
warm-up steps before them and the scope windows' steps after them are
left out. The read fetches small arrays and runs no program, so nothing
is traced or compiled after the window opened. It runs once a process,
and the readers share what it found.

Against a program whose step has no ``counters()``, or where fewer than
``MIN_STEPS`` of the window's steps are still held, every reader returns
``None`` and the line leaves its metric out.
"""
import json
import sys
import time

MIN_STEPS = 16     # of the window's own steps, or the readers say nothing

_TAKEN = []        # [summary or None], once a process


def held(trainer, first, steps):
  """``(counted, slots)``: ``trainer.counters()`` cut to the steps whose
  ordinal lies in ``[first, first + steps)`` and its ``counter_slots()``;
  ``None`` where the trainer has no counters or holds too few of them."""
  if not (hasattr(trainer, 'counters') and hasattr(trainer,
                                                   'counter_slots')):
    return None
  counted = trainer.counters()
  keep = (counted['step'] >= first) & (counted['step'] < first + steps)
  if int(keep.sum()) < MIN_STEPS:
    return None
  return {k: v[keep] for k, v in counted.items()}, trainer.counter_slots()


def summary(trainer, counted, slots):
  """Means over the held steps and the chips, the slots beside them."""
  from glt_tpu.typing import as_str
  out = {'steps': int(counted['step'].shape[0]),
         'first_step': int(counted['step'][0]),
         'last_step': int(counted['step'][-1])}
  if hasattr(trainer, 'counter_node_types'):
    out['node_types'] = [as_str(t) for t in trainer.counter_node_types]
    out['edge_types'] = [as_str(e) for e in trainer.counter_edge_types]
  for name, count in counted.items():
    if name not in slots:
      continue
    mean = count.mean(axis=(0, 1))
    out[name] = {'mean': mean.tolist(), 'slots': slots[name].tolist(),
                 'max': count.max(axis=(0, 1)).tolist(),
                 'occupancy_pct': 100.0 * float(mean.sum())
                                  / float(slots[name].sum())}
  if 'store_bucket_max' in out:
    cap = float(slots['store_bucket_max'])
    out['store_bucket_max_over_cap'] = {
        'mean': out['store_bucket_max']['mean'] / cap,
        'max': out['store_bucket_max']['max'] / cap}
  return out


def _take(run):
  from glt_tpu.obs.device import live_step_programs
  programs = live_step_programs()
  if len(programs) != 1:
    print(f'chipbench: counter window: {len(programs)} live step '
          'programs, not one; no counter metric', file=sys.stderr)
    return None
  t0 = time.perf_counter()
  first, steps = run['traffic']['warmup_steps'], run['window']['steps']
  found = held(programs[0], first, steps)
  if found is None:
    print('chipbench: counter window: this program\'s step has no '
          f'counters(), or holds fewer than {MIN_STEPS} of the steps '
          f'[{first}, {first + steps}); no counter metric', file=sys.stderr)
    return None
  taken = summary(programs[0], *found)
  taken['read_s'] = time.perf_counter() - t0
  print('chipbench: counters ' + json.dumps(taken), file=sys.stderr)
  return taken


def taken(run):
  if not _TAKEN:
    _TAKEN.append(_take(run))
  return _TAKEN[0]


def occupancy_pct(run, name):
  """100 x the counts of ``name`` over its slots: every hop, type and
  relation, every chip, the window's held steps; or ``None``."""
  found = taken(run)
  return None if found is None else found[name]['occupancy_pct']
