"""From a profiler trace to the numbers the per-layer readers take.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote with
``jax.profiler.ProfileData`` into plain lists of ``(name, start_ns,
duration_ns)``: for every TPU plane its ``XLA Ops`` and ``XLA Modules``
lines, and from the host plane the benchmark's own spans
(``chipbench.*``, written by ``run.py`` with ``TraceAnnotation``).
``reduce`` needs nothing of JAX, so a test can hand it a list made by
hand.

The stretch that counts is steady and made of whole steps: the trace's
first step program began before the trace did and its last was still
running when it stopped, and both are cut short, so the stretch runs from
the start of the second to the end of the last but one, on each device.
Busy is the union of the intervals in which an operation ran.
"""
import glob
import os
import re

_KINDS = '(all-to-all|all-reduce|all-gather|collective-permute|reduce-scatter)'
# an event of `XLA Ops` is named by its HLO text, "%all_to_all.11 = f32[..]
# all-to-all(...)": the opcode before its operands says what it is, and the
# instruction's own name (JAX's, with underscores) where the text is cut
_COLLECTIVE = re.compile(r'\b' + _KINDS + r'(-start|-done)?\(|^%?'
                         + _KINDS.replace('-', '[-_]') + r'\b')


def is_collective(name):
  return bool(_COLLECTIVE.search(name))


def load(trace_dir):
  from jax.profiler import ProfileData
  paths = sorted(glob.glob(os.path.join(
      trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
  if not paths:
    raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
  data = ProfileData.from_file(paths[-1])
  out = {'devices': {}, 'host': []}
  for plane in data.planes:
    if plane.name.startswith('/device:TPU:'):
      dev = out['devices'].setdefault(plane.name, {'ops': [], 'modules': []})
      for line in plane.lines:
        key = {'XLA Ops': 'ops', 'XLA Modules': 'modules'}.get(line.name)
        if key:
          dev[key] = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events]
    elif plane.name.startswith('/host:'):
      for line in plane.lines:
        out['host'] += [(e.name, e.start_ns, e.duration_ns)
                        for e in line.events
                        if e.name.startswith('chipbench.')]
  return out


def union_ns(intervals):
  """Total length of the union of (start, end) intervals."""
  total, cur_s, cur_e = 0, None, None
  for s, e in sorted(intervals):
    if cur_e is None or s > cur_e:
      if cur_e is not None:
        total += cur_e - cur_s
      cur_s, cur_e = s, e
    elif e > cur_e:
      cur_e = e
  return total + (cur_e - cur_s if cur_e is not None else 0)


def _gaps(intervals, lo, hi):
  """Idle (start, end) gaps of the union of intervals inside [lo, hi]."""
  out, edge = [], lo
  for s, e in sorted(intervals):
    if s > edge:
      out.append((edge, s))
    edge = max(edge, e)
  if hi > edge:
    out.append((edge, hi))
  return out


def _covering(host, s, e):
  """The host span that covers most of [s, e], by name."""
  best, most = 'no_span', 0
  for name, hs, hd in host:
    cover = min(e, hs + hd) - max(s, hs)
    if cover > most:
      best, most = name, cover
  return best


def reduce(trace, step_program='jit_step'):
  """``step_program``: how the step's compiled module is named in the
  trace (the traffic file says)."""
  per_dev = []
  for name, dev in sorted(trace['devices'].items()):
    steps = sorted((s, s + d) for n, s, d in dev['modules']
                   if n.startswith(step_program))[1:-1]
    if not steps:
      continue
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    ops = [(n, s, s + d) for n, s, d in dev['ops'] if s >= lo and s + d <= hi]
    busy = union_ns([(s, e) for _, s, e in ops])
    coll = sum(e - s for n, s, e in ops
               if is_collective(n))
    per_dev.append({'name': name, 'steps': len(steps), 'window': hi - lo,
                    'busy': busy, 'collective': coll, 'ops': ops,
                    'lo': lo, 'hi': hi})
  if not per_dev:
    raise ValueError('the trace holds no step program on any TPU plane')
  top = max(per_dev, key=lambda d: d['busy'])
  by_op = {}
  for n, s, e in top['ops']:
    key = n.lstrip('%').split(' ')[0]
    by_op[key] = by_op.get(key, 0) + (e - s)
  gaps = sorted(_gaps([(s, e) for _, s, e in top['ops']], top['lo'],
                      top['hi']), key=lambda g: g[0] - g[1])[:5]
  return {
      'busy_s': sum(d['busy'] for d in per_dev) / len(per_dev) / 1e9,
      'window_s': sum(d['window'] for d in per_dev) / len(per_dev) / 1e9,
      'steps': top['steps'],
      'top_busy_s': top['busy'] / 1e9, 'top_window_s': top['window'] / 1e9,
      'top_collective_s': top['collective'] / 1e9,
      'breakdown': {
          'device_ops': [[n, t / 1e9] for n, t in sorted(
              by_op.items(), key=lambda kv: -kv[1])[:10]],
          'idle_gaps': [[_covering(trace['host'], s, e), (e - s) / 1e9]
                        for s, e in gaps]},
  }

