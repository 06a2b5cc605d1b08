"""Device time by layer, read back from inside the compiled step.

The convention, defined here once: the **first** component of a
``jax.named_scope`` path is a layer token of ``LAYERS`` (the tokens of
``PERF.md`` and ``BENCHMARK.json``), its children name the stage:
``sampler/dedup0``, ``feature_store/bucket``, ``model_step/forward/conv1``.
Scopes are trace-time metadata: XLA keeps them as the ``op_name`` of
every HLO instruction, and a profiler trace names every device event by
its instruction. ``layer_of`` reads one ``op_name``, ``reduce_scopes``
sums a trace's events by layer and stage, and ``scope_profile`` takes the
trace: one short profiler session around a step program's own calls.
"""
import collections
import contextlib
import re
import weakref

LAYERS = ('sampler', 'feature_store', 'model_step', 'collectives')


def scope(layer, *stages):
  """``jax.named_scope``s nested as one context manager: a layer token,
  then the stage."""
  import jax
  assert layer in LAYERS, layer
  stack = contextlib.ExitStack()
  for name in (layer,) + stages:
    stack.enter_context(jax.named_scope(name))
  return stack


_LIVE = weakref.WeakSet()


def register_step_program(step):
  """A step program (``SPMDSageTrainStep``) names itself when built."""
  _LIVE.add(step)


def live_step_programs():
  """The step programs alive in this process: what a console lists, and
  how a reader reaches the trainer that a benchmark's window drove."""
  return list(_LIVE)


# -- what a step counted --------------------------------------------------

COUNTER_STEPS = 128   # per-batch steps whose counters a step program holds


class StepCounters:
  """What the fused per-batch steps count, held and read one way
  (``SPMDSageTrainStep``, ``DistHeteroTrainStep``). Every per-batch
  program returns ``(loss, counters)``, ``counters`` one flat dict of
  small integer arrays with a leading device axis; ``__call__`` keeps
  the newest ``COUNTER_STEPS`` dicts as they come, on the device, with
  the ordinal of the call (one append a step: no fetch, no wait).
  The supersteps count nothing."""

  def _init_counters(self):
    self._counted = collections.deque(maxlen=COUNTER_STEPS)
    self._calls = 0   # per-batch calls so far: the next step's ordinal

  def _keep_counters(self, counters):
    self._counted.append((self._calls, counters))
    self._calls += 1

  def _newest_counters(self, names) -> dict:
    import numpy as np
    newest = self._counted[-1][1]
    return {k: np.asarray(newest[k]) for k in names if k in newest}

  def counters(self) -> dict:
    """``{'step': int64 [n], name: ndarray [n, devices, ...]}``: what the
    newest ``n <= COUNTER_STEPS`` per-batch steps counted, oldest first;
    ``step`` is a step's ordinal among this trainer's per-batch calls,
    from 0. A read fetches every held array and so waits for the newest
    step (read at an epoch's end or every so many steps, not every
    step); it traces and compiles nothing. Before the first step it
    raises. Every step counts ``nodes_by_hop`` (node rows new at each
    hop, the seeds' first: ``[H + 1]``, by type ``[T, H + 1]`` in the
    order of ``counter_node_types``; the sum is the batch's
    ``node_count``) and ``edges_by_hop`` (valid edge slots of each hop:
    ``[H]``, by relation ``[R, H]`` in the order of
    ``counter_edge_types``, 0 for a hop a relation is not read in) and
    ``hop_rows_read`` (frontier rows a hop read ``indptr`` and
    ``indices`` for, ``ops/sample.py::sample_neighbors``: the live
    rows in whole chunks, or every slot where it took the plain read;
    in ``edges_by_hop``'s shape; an enclosing-subgraph step has none); a
    link step also what ``link_counters`` names, a step whose store
    exchanges also what ``store_counters`` names, one whose store serves
    in place ``store_chunks`` (the chunks of request slots it gathered:
    a scalar, by type ``[T]``)."""
    import jax
    import numpy as np
    if not self._counted:
      raise RuntimeError('no per-batch step has run')
    steps, held = zip(*self._counted)
    held = jax.device_get(list(held))
    out = {'step': np.asarray(steps, np.int64)}
    for name in held[-1]:
      out[name] = np.stack([h[name] for h in held])
    return out

  def counter_slots(self) -> dict:
    """``{name: int64 slots}``: the static budget each counter is read
    against, in the counter's shape less the step and device axes, so
    that ``counters()[name].sum() / (n * devices * slots.sum())`` is the
    share of a budget that held work. An entry with no budget (a link
    step's ``seeds``) has none."""
    raise NotImplementedError


# -- one op_name ----------------------------------------------------------

_CALL = re.compile(r'(\w+)\(([^()]*)\)')
_FUNCTION = '\0'   # where a path enters a jitted helper: no scope below
# path components that are the program's control flow, not scopes
_FLOW = frozenset(('shard_map', 'while', 'body', 'cond', 'branch',
                   'closed_call', 'checkpoint', 'remat'))


def layer_of(op_name):
  """``(layer, stage, backward)`` of one HLO ``op_name`` path.

  ``jit(step)/shard_map/model_step/transpose(jvp(forward))/GraphSAGE/
  conv1/conv1/lin_nbr/dot_general`` is ``('model_step',
  'model_step/forward/GraphSAGE/conv1/lin_nbr', True)``: transform
  wrappers (``jvp(``, ``transpose(``, ``vmap(``) are stripped, and a
  ``transpose`` marks the op as backward; the layer is the first
  component that is a token of ``LAYERS``; the stage is the scopes
  below it, down to the primitive or the first jitted helper
  (``jit(_take)``), control flow and a component repeated left out. A
  path with no layer token gives ``(None, None, backward)``."""
  backward = 'transpose(' in op_name
  path, before = op_name, None
  while path != before:
    before, path = path, _CALL.sub(
        lambda m: _FUNCTION if m.group(1) in ('jit', 'pjit')
        else m.group(2), path)
  parts = path.split('/')
  at = next((i for i, p in enumerate(parts) if p in LAYERS), None)
  if at is None:
    return None, None, backward
  below = parts[at + 1:]
  below = (below[:below.index(_FUNCTION)] if _FUNCTION in below
           else below[:-1])
  stage = [parts[at]]
  for p in below:
    if p not in _FLOW and p != stage[-1]:
      stage.append(p)
  return parts[at], '/'.join(stage), backward


# -- the HLO module a trace carries ---------------------------------------

def _varint(buf, i):
  out = shift = 0
  while True:
    byte = buf[i]
    i += 1
    out |= (byte & 0x7f) << shift
    shift += 7
    if byte < 0x80:
      return out, i


def _fields(buf):
  """``(field number, value)`` of one protobuf message: an int for a
  varint, the bytes for a length-delimited or a fixed field."""
  i, n = 0, len(buf)
  while i < n:
    key, i = _varint(buf, i)
    kind = key & 7
    if kind == 0:
      value, i = _varint(buf, i)
    else:
      size, i = _varint(buf, i) if kind == 2 else ({1: 8, 5: 4}[kind], i)
      value = buf[i:i + size]
      i += size
    yield key >> 3, value


def _all(buf, number):
  """Every value of one field of a message."""
  return [v for f, v in _fields(buf) if f == number]


def _ints(value):
  """A repeated integer field's entry, packed or not."""
  if isinstance(value, int):
    return [value]
  out, i = [], 0
  while i < len(value):
    v, i = _varint(value, i)
    out.append(v)
  return out


def _text(buf):
  return bytes(buf).decode('utf-8', 'replace')


# instructions that compute nothing: XLA shares one constant among all its
# users and keeps the first user's op_name on it, so inside a fusion their
# scope says nothing about whose work the fusion does
_NO_WORK = frozenset(('constant', 'parameter', 'broadcast', 'iota',
                      'bitcast', 'tuple', 'get-tuple-element'))


def hlo_scopes(hlo_proto):
  """``{instruction name: [op_name, ...]}`` of a serialized ``HloProto``:
  the instruction's own ``op_name`` first, then, for a fusion or any
  other instruction that calls computations, those of the instructions
  it calls that do work. Field numbers are ``xla/service/hlo.proto``'s."""
  module = _all(hlo_proto, 1)[0]   # HloProto.hlo_module
  comps = {}    # computation id -> [(name, opcode, op_name, called ids)]
  for comp in _all(module, 3):   # HloModuleProto.computations
    cid, instrs = None, []
    for g, v in _fields(comp):
      if g == 5:   # HloComputationProto.id
        cid = v
      elif g == 2:   # .instructions
        name, opcode, op_name, called = '', '', '', []
        for h, w in _fields(v):
          if h == 1:   # HloInstructionProto.name
            name = _text(w)
          elif h == 2:   # .opcode
            opcode = _text(w)
          elif h == 7:   # .metadata, OpMetadata.op_name
            op_name = ''.join(map(_text, _all(w, 2)))
          elif h == 38:   # .called_computation_ids
            called += _ints(w)
        instrs.append((name, opcode, op_name, called))
    comps[cid] = instrs
  inner = {}

  def called_names(cid):
    if cid not in inner:
      inner[cid] = [n for _, opcode, op, called in comps.get(cid, ())
                    for n in [op] * bool(op and opcode not in _NO_WORK)
                    + [m for c in called for m in called_names(c)]]
    return inner[cid]

  return {name: [op] * bool(op) + [m for c in called
                                   for m in called_names(c)]
          for instrs in comps.values() for name, _, op, called in instrs}


# -- from a trace to device time by layer ---------------------------------

_KINDS = '(all-to-all|all-reduce|all-gather|collective-permute|reduce-scatter)'
# an `XLA Ops` event is named by its HLO text, "%all_to_all.11 = f32[..]
# all-to-all(...)": the opcode before the operands, or the instruction's
# own name (JAX's, with underscores) where the text is cut
_COLLECTIVE = re.compile(r'\b' + _KINDS + r'(-start|-done)?\(|^%?'
                         + _KINDS.replace('-', '[-_]') + r'\b')


def instruction(event_name):
  """The HLO instruction's name of an ``XLA Ops`` event."""
  return event_name.lstrip('%').split(' ')[0]


def _classify(name, op_names):
  """``(layer, stage, mixed)`` of one device op. A collective is the
  ``collectives`` layer's whatever scope it sits in, so that the layers
  partition the busy time; a fusion is its root's (the first scoped
  ``op_name``) and mixed when its instructions span layers."""
  scoped = [s for s in map(layer_of, op_names) if s[0]]
  layer, stage, backward = scoped[0] if scoped else (None, None, False)
  mixed = len({s[0] for s in scoped}) > 1
  if _COLLECTIVE.search(name):
    kind = re.sub(r'[.\d]+$', '', instruction(name))
    stage = stage if layer == 'collectives' else 'collectives/' + (
        stage or kind)
    return 'collectives', stage, False
  if layer is None:
    return 'unscoped', 'unscoped', False
  return layer, stage + '/bwd' * backward, mixed


def _self_times(ops):
  """``[(op, ns)]``: each op's duration less that of the ops nested in
  it (a ``while`` holds its body's ops), so that a sum counts no
  nanosecond twice."""
  out, open_ = [], []
  for op in sorted(ops, key=lambda o: (o[1], -o[2])):
    while open_ and op[1] >= open_[-1][0][1] + open_[-1][0][2]:
      out.append(tuple(open_.pop()))
    if open_:
      open_[-1][1] -= op[2]
    open_.append([op, op[2]])
  return out + [tuple(o) for o in open_]


def _union(intervals):
  """``[(start, end)]`` merged, in order."""
  out = []
  for s, e in sorted(intervals):
    if out and s <= out[-1][1]:
      out[-1][1] = max(out[-1][1], e)
    else:
      out.append([s, e])
  return out


def _covering(host_spans, s, e):
  """The host span that covers most of [s, e]; of equals the shortest,
  which is the innermost."""
  best = ('no_span', 0, 0)
  for name, hs, hd in host_spans:
    cover = min(e, hs + hd) - max(s, hs)
    if cover > 0 and (cover, -hd) > (best[1], -best[2]):
      best = (name, cover, hd)
  return best[0]


def reduce_scopes(events, host_spans, step_program='jit_step'):
  """Device time by layer and stage, in ms a step, on the busiest device.

  ``events``: ``{device: {'modules': [(name, start_ns, dur_ns)], 'ops':
  [(name, start_ns, dur_ns, [op_name, ...])]}}``, an op's ``op_name``s
  as ``hlo_scopes`` lists them. ``host_spans``: ``[(name, start_ns,
  dur_ns)]`` on the same clock. Whole steps only: a trace's first and
  last ``step_program`` events are cut short and dropped.
  """
  top = None
  for device, dev in sorted(events.items()):
    steps = sorted((s, s + d) for n, s, d in dev['modules']
                   if n.startswith(step_program))[1:-1]
    if not steps:
      continue
    lo, hi = steps[0][0], steps[-1][1]
    ops = [o for o in dev['ops'] if o[1] >= lo and o[1] + o[2] <= hi]
    busy = _union((o[1], o[1] + o[2]) for o in ops)
    busy_ns = sum(e - s for s, e in busy)
    if top is None or busy_ns > top['busy_ns']:
      top = {'device': device, 'steps': len(steps), 'lo': lo, 'hi': hi,
             'ops': ops, 'busy': busy, 'busy_ns': busy_ns}
  if top is None:
    raise ValueError(f'the trace holds no whole {step_program!r} step')
  per_step = 1e-6 / top['steps']
  layers, stages, by_op = {}, {}, {}
  mixed = 0
  for (name, _, _, op_names), ns in _self_times(top['ops']):
    layer, stage, is_mixed = _classify(name, op_names)
    layers[layer] = layers.get(layer, 0) + ns
    stages[stage] = stages.get(stage, 0) + ns
    mixed += ns * is_mixed
    key = (instruction(name), stage, is_mixed)
    by_op[key] = by_op.get(key, 0) + ns
  ranked = sorted(by_op.items(), key=lambda kv: -kv[1])
  edges = [top['lo']] + [x for s, e in top['busy'] for x in (s, e)] + [
      top['hi']]
  gaps = sorted(zip(edges[::2], edges[1::2]), key=lambda g: g[0] - g[1])
  ms = lambda d: {k: v * per_step for k, v in sorted(d.items())}
  unscoped = layers.pop('unscoped', 0)
  return {
      'device': top['device'], 'steps': top['steps'],
      'busy_ms': top['busy_ns'] * per_step,
      'window_ms': (top['hi'] - top['lo']) * per_step,
      'layers': ms(layers), 'stages': ms(stages),
      'mixed_ms': mixed * per_step, 'unscoped_ms': unscoped * per_step,
      'top_ops': [[n, st, ns * per_step] for (n, st, _), ns in ranked[:12]],
      'mixed_ops': [[n, st, ns * per_step]
                    for (n, st, m), ns in ranked if m][:5],
      'idle_gaps': [[_covering(host_spans, s, e), (e - s) * 1e-6]
                    for s, e in gaps[:5] if e > s]}


def load_profile(session_dir, step_program='jit_step'):
  """``(events, host_spans)`` for ``reduce_scopes`` from the newest
  ``.xplane.pb`` under ``session_dir``: every TPU plane's ``XLA Ops`` and
  ``XLA Modules`` lines and all host spans through
  ``jax.profiler.ProfileData``; the ``op_name``s from the HLO protos of
  ``step_program`` that a session opened with ``enable_hlo_proto``
  keeps in its ``/host:metadata`` plane."""
  import glob
  import os
  from jax.profiler import ProfileData
  path = sorted(glob.glob(os.path.join(
      session_dir, 'plugins', 'profile', '*', '*.xplane.pb')))[-1]
  with open(path, 'rb') as f:
    raw = memoryview(f.read())
  scopes = {}
  for plane in _all(raw, 1):   # XSpace.planes; XPlane.name is field 2
    if [_text(v) for v in _all(plane, 2)] != ['/host:metadata']:
      continue
    for entry in _all(plane, 4):   # XPlane.event_metadata: a map entry
      meta = _all(entry, 2)[0]     # its value, an XEventMetadata
      if not ''.join(map(_text, _all(meta, 2))).startswith(step_program):
        continue                   # XEventMetadata.name
      for stat in _all(meta, 5):   # .stats; XStat.bytes_value: an HloProto
        for proto in _all(stat, 6):
          scopes.update(hlo_scopes(proto))
  events, host_spans = {}, []
  for plane in ProfileData.from_file(path).planes:
    if plane.name.startswith('/device:TPU:'):
      dev = events.setdefault(plane.name, {'ops': [], 'modules': []})
      for line in plane.lines:
        if line.name == 'XLA Modules':
          dev['modules'] = [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
        elif line.name == 'XLA Ops':
          dev['ops'] = [(e.name, e.start_ns, e.duration_ns,
                         scopes.get(instruction(e.name), ()))
                        for e in line.events]
    elif plane.name.startswith('/host:'):
      host_spans += [(e.name, e.start_ns, e.duration_ns)
                     for line in plane.lines for e in line.events]
  return events, host_spans


def scope_profile(step, params, opt_state, batches,
                  step_program='jit_step'):
  """One profiler session of its own around ``step(params, opt_state,
  *batch)`` for every batch of ``batches``, one step ahead as a
  training loop drives it, and what ``reduce_scopes`` makes of it. The
  process's ``Tracer`` is on for the session's length (no span syncs),
  so that the step program's host spans land in the trace, on the
  profiler's clock; it is left as it was found."""
  import shutil
  import tempfile
  import jax
  import numpy as np
  from .trace import get_tracer
  tracer = get_tracer()
  was = tracer.enabled, tracer._sample
  session_dir = tempfile.mkdtemp(prefix='glt_scope_profile_')
  options = jax.profiler.ProfileOptions()
  options.python_tracer_level = 0
  options.enable_hlo_proto = True   # the op_names ride in the trace
  try:
    tracer.enable(sample=0.0)
    jax.profiler.start_trace(session_dir, profiler_options=options)
    try:
      pending = None
      for batch in batches:
        params, opt_state, loss = step(params, opt_state, *batch)
        if pending is not None:
          with tracer.span('scope_profile.wait'):
            np.asarray(pending)
        pending = loss
      if pending is not None:
        np.asarray(pending)   # the fence: every step is on the trace
    finally:
      jax.profiler.stop_trace()
    events, host_spans = load_profile(session_dir, step_program)
    # an idle gap is named after the program's own span, not after one
    # of the runtime's that happens to lie inside it
    ours = {s.name for s in tracer.spans()}
    return reduce_scopes(events, [h for h in host_spans if h[0] in ours],
                         step_program)
  finally:
    tracer.enabled, tracer._sample = was
    shutil.rmtree(session_dir, ignore_errors=True)
