"""The benchmark's command: one run of one cell.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: the cell in
``BENCHMARK.json``, its configuration in ``chipbench/configs/<config>.json``,
its traffic in ``chipbench/traffic/<traffic>.json``, the driver the traffic
names in ``chipbench/drivers/<driver>.py`` and one reader for each
per-layer metric in ``chipbench/layers/<metric>.py``. A driver gives
``build(cfg, traffic, chips, seed) -> state`` (all set-up, warm-up
included), ``step(state, t) -> loss on the device`` and
``verify(state) -> {name: (value, limit)}``.

There is no CPU mode: without a TPU, or with fewer chips than the cell
asks for, the run exits nonzero before it builds anything.
"""
import time

T_START = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TRACE_DIR = os.path.join(ROOT, '.chipbench_trace')
TRACE_SECONDS = 5.0   # the traced stretch of a --trace 1 window


def load_cell(name):
  """(cell, configuration's file, traffic's file) by the cell's name."""
  with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    manifest = json.load(f)
  cell = {w['name']: w for w in manifest['workloads']}[name]
  config = {c['name']: c for c in manifest['configs']}[cell['config']]
  with open(os.path.join(ROOT, config['file'])) as f:
    cfg = json.load(f)
  with open(os.path.join(ROOT, 'chipbench', 'traffic',
                         cell['traffic'] + '.json')) as f:
    traffic = json.load(f)
  return manifest, cell, cfg, traffic


def require_chips(chips):
  """The first JAX contact of the process."""
  import jax
  if jax.default_backend() != 'tpu':
    sys.exit(f'chipbench: default backend is {jax.default_backend()!r}, '
             'not tpu; the benchmark has no CPU mode')
  if jax.local_device_count() < chips:
    sys.exit(f'chipbench: the cell asks for {chips} chips, '
             f'{jax.local_device_count()} found')


def place_compile_cache():
  import jax
  from glt_tpu.utils.backend import configure_compile_cache
  # cache every program, the sub-second ones of set-up too
  jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
  jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
  return configure_compile_cache()


def window(driver, state, first_step, seconds, trace):
  """Drive steps for ``seconds``: step t+1 is dispatched before step t's
  loss is waited for, so the stamp of a completion does not serialise
  host and device. Returns completion stamps and the host's own time."""
  import jax
  import numpy as np
  stamps, waited = [], 0.0
  tracing, traced = ('wait' if trace else 'off'), None
  profiler_s = 0.0   # starting and stopping the profiler is not the loop's
  t0 = time.perf_counter()
  pending = driver.step(state, first_step)
  t = first_step + 1
  while True:
    now = time.perf_counter()
    if tracing == 'wait' and now - t0 > 1.0:
      shutil.rmtree(TRACE_DIR, ignore_errors=True)
      options = jax.profiler.ProfileOptions()
      options.python_tracer_level = 0
      options.enable_hlo_proto = False
      jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
      tracing, traced = 'on', time.perf_counter()
      profiler_s += traced - now
    more = now - t0 < seconds
    nxt = driver.step(state, t) if more else None
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation('chipbench.wait'):
      np.asarray(pending)
    stamps.append(time.perf_counter())
    waited += stamps[-1] - w0
    if tracing == 'on' and (stamps[-1] - traced > TRACE_SECONDS
                            or nxt is None):
      jax.profiler.stop_trace()
      tracing = 'off'
      profiler_s += time.perf_counter() - stamps[-1]
    if nxt is None:
      break
    pending, t = nxt, t + 1
  stamps = np.asarray(stamps)
  return {'t0': t0, 'stamps': stamps, 'steps': len(stamps),
          'seconds': stamps[-1] - t0,
          'host_s': stamps[-1] - t0 - waited - profiler_s}


def end_to_end(win, seeds_per_step, setup_s):
  import numpy as np
  gaps = np.diff(win['stamps'])
  return {'seeds_per_s': (win['steps'] * seeds_per_step / win['seconds'],
                          'seeds/s'),
          'step_p90_ms': (float(np.percentile(gaps, 90)) * 1e3, 'ms'),
          'setup_s': (setup_s, 's')}


def per_layer(manifest, cell, run):
  out = {}
  for m in manifest['per_layer']:
    if cell['name'] not in m.get('workloads', [cell['name']]):
      continue
    reader = importlib.import_module('chipbench.layers.' + m['name'])
    value = reader.read(run)
    if value is not None:
      out[m['name']] = (float(value), m['unit'])
  return out


def run_cell(name, seed, seconds, trace):
  """Everything after the look for a chip; returns the result's line."""
  import jax
  manifest, cell, cfg, traffic = load_cell(name)
  driver = importlib.import_module('chipbench.drivers.' + traffic['driver'])
  state = driver.build(cfg, traffic, cell['chips'], seed)
  setup_s = time.perf_counter() - T_START
  win = window(driver, state, traffic['warmup_steps'], seconds, trace)
  devices = jax.local_devices()[:cell['chips']]
  dev = devices[0]
  device = {'platform': dev.platform, 'kind': dev.device_kind,
            'count': cell['chips'],
            'memory_peak_bytes': max(
                (d.memory_stats() or {}).get('peak_bytes_in_use', 0)
                for d in devices)}
  if trace:
    from chipbench import trace_reduce
    reduced = trace_reduce.reduce(trace_reduce.load(TRACE_DIR),
                                  traffic['step_program'])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    device['busy_s'] = reduced['busy_s']
    device['window_s'] = reduced['window_s']
    metrics = per_layer(manifest, cell, {
        'cfg': cfg, 'traffic': traffic, 'chips': cell['chips'],
        'device_kind': dev.device_kind, 'window': win, 'trace': reduced})
  else:
    metrics = end_to_end(win, cell['chips'] * traffic['batch_per_chip'],
                         setup_s)
  compared = driver.verify(state)
  correct = all(v == v and v <= limit for v, limit in compared.values())
  line = {'correct': bool(correct), 'attempted': win['steps'],
          'failed': 0,
          'metrics': {k: {'value': v, 'unit': u}
                      for k, (v, u) in metrics.items()},
          'device': device}
  if trace:
    line['breakdown'] = reduced['breakdown']
  line['setup_parts'] = getattr(state, 'parts', {})
  line['compared'] = {k: {'value': v, 'limit': limit}
                      for k, (v, limit) in compared.items()}
  return line


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seed', type=int, required=True)
  ap.add_argument('--seconds', type=float, required=True)
  ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
  args = ap.parse_args(argv)
  _, cell, _, _ = load_cell(args.workload)
  require_chips(cell['chips'])
  place_compile_cache()
  line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
  for k, c in line['compared'].items():
    print(f"chipbench: compared {k} = {c['value']!r} limit {c['limit']!r}",
          file=sys.stderr)
  sys.stderr.flush()
  print(json.dumps(line), flush=True)


if __name__ == '__main__':
  main()
