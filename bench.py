"""Headline benchmark: neighbor-sampling + induction throughput per chip.

Protocol mirrors the reference's benchmarks/api/bench_sampler.py
("Sampled Edges per secs: {} M" over ogbn-products, batch 1024, fanout
[15,10,5]): here on a synthetic products-scale graph (2.45M nodes, ~62M
directed edges) generated in-process since datasets are not downloadable
in this environment. The measured quantity is identical: valid sampled
edges per second of wall-clock, steady state, one chip.

``vs_baseline`` compares against an A100 running the reference's CUDA
sampler on the same protocol. Upstream commits no number (BASELINE.md);
we use 2.0e8 edges/s as the assumed A100 figure (order-of-magnitude from
the reference's scale_up figure) until a measured value is available.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Run modes
---------
``python bench.py``         supervisor: keeps JAX out of the parent (a
                            parent that touched JAX would hold the chip)
                            and runs the measurement in one child at a
                            time under a HARD TOTAL BUDGET. Prints the
                            child's JSON line and exits 0 only when a
                            measurement was made; otherwise it prints a
                            line with ``value: 0.0`` and an ``error``
                            field and exits nonzero.
``python bench.py --run``   worker: the actual measurement. Fails at
                            once unless the backend is a TPU, or the
                            CPU was asked for by name
                            (``GLT_BENCH_PLATFORM=cpu``).

Env knobs: GLT_BENCH_BUDGET total wall-clock seconds for the supervisor
(default 900), GLT_BENCH_TIMEOUT seconds per measurement attempt
(default: fit budget), GLT_BENCH_SCAN (batches fused per device call,
default 4), GLT_BENCH_PLATFORM (force a jax platform, e.g. ``cpu``).
"""
import json
import os
import subprocess
import sys
import time

A100_ASSUMED_EDGES_PER_SEC = 2.0e8

# protocol shapes; the GLT_BENCH_* overrides exist for smoke-testing
# the bench itself at toy scale — headline runs use the defaults
NUM_NODES = int(os.environ.get('GLT_BENCH_NODES', 2_450_000))
NUM_EDGES = int(os.environ.get('GLT_BENCH_EDGES', 62_000_000))
BATCH = int(os.environ.get('GLT_BENCH_BATCH', 1024))
FANOUT = (15, 10, 5)
WARMUP = 3
ITERS = int(os.environ.get('GLT_BENCH_ITERS', 30))


def _emit(value, vs_baseline, **extra):
  print(json.dumps({
      'metric': 'sampled_edges_per_sec_per_chip',
      'value': value,
      'unit': 'edges/s',
      'vs_baseline': vs_baseline,
      **extra,
  }))
  sys.stdout.flush()


def run_worker():
  import numpy as np
  import jax
  from glt_tpu.utils.backend import (configure_compile_cache,
                                     force_backend)
  asked = force_backend()
  configure_compile_cache()
  import jax.numpy as jnp
  from glt_tpu.data import Topology
  from glt_tpu.ops.pipeline import make_dedup_tables, multihop_sample
  from glt_tpu.ops.sample import sample_neighbors

  dev = jax.devices()[0]
  print(f'# backend: {dev.platform} ({dev.device_kind})', file=sys.stderr)
  if dev.platform != 'tpu' and asked != dev.platform:
    # the metric is per CHIP: a run that found no chip measures nothing
    sys.exit(f'bench.py: backend is {dev.platform!r}, not tpu, and no '
             'GLT_BENCH_PLATFORM asked for it')

  rng = np.random.default_rng(0)
  # out-degrees ~Poisson(25) (products' mean); in-degrees skewed via a
  # squared-uniform draw so dedup and gathers see hub nodes
  src = rng.integers(0, NUM_NODES, NUM_EDGES, dtype=np.int64)
  dst = (rng.random(NUM_EDGES) ** 2 * NUM_NODES).astype(np.int64) \
      % NUM_NODES
  topo = Topology(indptr=None, edge_index=np.stack([src, dst]),
                  num_nodes=NUM_NODES)
  del src, dst
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  indices = jnp.asarray(topo.indices)

  win_state = {}

  def resolved_hop_engine():
    """The hop engine the current env ACTUALLY selects (post-fallback:
    GLT_HOP_ENGINE=pallas without an importable pallas resolves to
    'window'; pallas_fused whose dedup table would blow the VMEM knob
    resolves to 'pallas') — both the hop closure and the engines{}
    labels read this, so the recorded label never claims an engine that
    didn't run. Legacy GLT_WINDOW_HOP=1 maps to 'window'."""
    from glt_tpu.ops.pipeline import hop_engine, sample_budget
    if 'GLT_HOP_ENGINE' in os.environ:
      eng = hop_engine()
      if eng == 'pallas_fused':
        from glt_tpu.ops.pallas_kernels import (fused_table_max_slots,
                                                fused_table_slots)
        if fused_table_slots(sample_budget(BATCH, list(FANOUT))) \
            > fused_table_max_slots():
          from glt_tpu.ops.pipeline import count_engine_fallback
          count_engine_fallback('pallas_fused', 'pallas',
                                'table_overflow')
          return 'pallas'
      return eng
    if os.environ.get('GLT_WINDOW_HOP', '0') in ('1', 'true'):
      return 'window'
    return 'element'

  def make_one_hop():
    """Build (hop closure, fused plan) under the CURRENT env. The
    W-padded indices copy and the true hub count are built once and
    shared across engine passes; the fused plan routes multihop_sample
    through the pallas_fused kernel family (the hop closure is then
    unused but kept so every engine shares one call shape)."""
    eng = resolved_hop_engine()
    if eng == 'element':
      return (lambda ids, fanout, key, mask: sample_neighbors(
          indptr, indices, ids, fanout, key, seed_mask=mask)), None
    win_w = int(os.environ.get('GLT_WINDOW_W', '96'))
    if win_state.get('w') != win_w:
      # hub capacity from the graph's true hub count (host, once) so
      # results stay bit-identical to the element path (ops/sample.py)
      win_state['w'] = win_w
      win_state['n_hub'] = int((np.diff(topo.indptr) > win_w).sum())
      win_state['iw'] = jnp.concatenate(
          [indices, jnp.full((win_w,), -1, indices.dtype)])
    n_hub, iw = win_state['n_hub'], win_state['iw']
    print(f'# hop engine: {eng} W={win_w} n_hub={n_hub}',
          file=sys.stderr)
    interp = False
    if eng in ('pallas', 'pallas_fused'):
      from glt_tpu.ops.pallas_kernels import interpret_default
      interp = interpret_default()
    if eng == 'pallas_fused':
      from glt_tpu.ops.pallas_kernels import fused_table_slots
      from glt_tpu.ops.pipeline import sample_budget
      from glt_tpu.ops.sample import FusedHopPlan
      plan = FusedHopPlan(
          indptr, indices, iw, win_w, n_hub,
          fused_table_slots(sample_budget(BATCH, list(FANOUT))),
          interpret=interp)
      return (lambda ids, fanout, key, mask: sample_neighbors(
          indptr, indices, ids, fanout, key, seed_mask=mask,
          window=(win_w, min(n_hub, ids.shape[0])), indices_win=iw,
          engine='pallas', interpret=interp)), plan
    return (lambda ids, fanout, key, mask: sample_neighbors(
        indptr, indices, ids, fanout, key, seed_mask=mask,
        window=(win_w, min(n_hub, ids.shape[0])), indices_win=iw,
        engine=eng, interpret=interp)), None

  import functools
  scan = max(int(os.environ.get('GLT_BENCH_SCAN', '4')), 1)

  from glt_tpu.ops.pipeline import checksum_outputs as checksum
  from glt_tpu.utils.rng import make_key

  seed_pool = rng.integers(0, NUM_NODES, (ITERS + WARMUP, scan, BATCH))

  def measure():
    """Build + time the pipeline under the CURRENT env (GLT_DEDUP /
    GLT_FUSED_HOP / GLT_HOP_ENGINE are read at trace time, so each
    call re-jits). Returns per-engine stats: steady-state edges/s,
    compile/trace wall-time of the first dispatch, the number of
    re-traces observed during the timed loop (must be 0 — any recompile
    in steady state is a shape-stability bug), and — when the cost
    analysis is available — the program's HBM bytes + FLOPs per
    dispatch (the numerators of the per-engine roofline cell)."""
    one_hop, fused_plan = make_one_hop()
    traces = {'n': 0}

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def sample_batch(seeds, key, table, scratch):
      traces['n'] += 1  # trace-time side effect; executions never bump
      if scan > 1:
        from glt_tpu.ops.pipeline import multihop_sample_many
        outs, table, scratch = multihop_sample_many(
            one_hop, seeds, jnp.full(scan, BATCH, jnp.int32), FANOUT,
            key, table, scratch, fused_plan=fused_plan)
        return (outs['num_sampled_edges'].sum(), checksum(outs), table,
                scratch)
      out, table, scratch = multihop_sample(
          one_hop, seeds[0], jnp.asarray(BATCH), FANOUT, key, table,
          scratch, fused_plan=fused_plan)
      return (out['num_sampled_edges'].sum(), checksum(out), table,
              scratch)

    table, scratch = make_dedup_tables(NUM_NODES)
    # GLT_PRNG=rbg swaps threefry for the XLA RngBitGenerator-backed
    # implementation (same knob the samplers honor, utils/rng.py)
    keys = jax.random.split(make_key(0), ITERS + WARMUP)
    # arg avals captured BEFORE the loop: table/scratch are donated, so
    # the roofline's AOT re-lower below must run on ShapeDtypeStructs
    arg_sds = tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype)
        for a in (jnp.zeros((scan, BATCH), jnp.int32), keys[0], table,
                  scratch))
    t_c0 = time.time()
    edges, sig, table, scratch = sample_batch(
        jnp.asarray(seed_pool[0], jnp.int32), keys[0], table, scratch)
    jax.block_until_ready((edges, sig))
    compile_s = time.time() - t_c0   # trace + compile + first run
    for i in range(1, WARMUP):
      edges, sig, table, scratch = sample_batch(
          jnp.asarray(seed_pool[i], jnp.int32), keys[i], table, scratch)
    jax.block_until_ready((edges, sig))
    traces_warm = traces['n']
    edge_counts, sigs = [], []
    t0 = time.time()
    for i in range(WARMUP, WARMUP + ITERS):
      edges, sig, table, scratch = sample_batch(
          jnp.asarray(seed_pool[i], jnp.int32), keys[i], table, scratch)
      edge_counts.append(edges)  # stay async: no host sync in the loop
      sigs.append(sig)
    jax.block_until_ready((edge_counts[-1], sigs[-1]))
    dt = time.time() - t0
    total_edges = int(np.sum([int(e) for e in edge_counts]))
    out = {
        'edges_per_sec': total_edges / dt,
        'compile_s': compile_s,
        'steady_recompiles': traces['n'] - traces_warm,
        'edges_per_dispatch': total_edges / ITERS,
    }
    if os.environ.get('GLT_BENCH_ROOFLINE', '1') != '0':
      # XLA cost accounting for THIS engine's program (obs.perf): the
      # AOT lower re-traces (after steady_recompiles was read — it
      # never pollutes that stat); aot_compile so the roofline quotes
      # the OPTIMIZED executable's bytes/FLOPs, not pre-fusion HLO —
      # the persistent compilation cache (configured above) makes the
      # second compile of the just-jitted program cheap
      try:
        from glt_tpu.obs.perf import instrument_compiled
        cost = instrument_compiled('bench.sample_batch', sample_batch,
                                   *arg_sds, aot_compile=True)
        if 'bytes_accessed' in cost:
          out['hbm_bytes_per_dispatch'] = cost['bytes_accessed']
        if 'flops' in cost:
          out['flops_per_dispatch'] = cost['flops']
        if 'kernel_launches' in cost:
          # HLO custom-call count (TPU) / trace-time pallas_call count
          # (interpret): the O(hops)->O(1) launch collapse of the
          # cross-hop walk is a recorded number, not a claim
          out['kernel_launches_per_dispatch'] = cost['kernel_launches']
      except Exception as e:  # cost accounting is best-effort
        print(f'# cost analysis unavailable: {e}', file=sys.stderr)
    return out

  # Engine self-selection: race the dedup variants (sort vs sort+fused)
  # and the hop-read engines when the knobs were not forced and the
  # budget hint leaves room — the headline then reports the best
  # measured variant, and `engines{}` records every contender's
  # edges/s + compile wall-time + steady-state recompile count. The
  # pallas engines only race where Mosaic compiles them (a TPU
  # backend) unless GLT_HOP_ENGINE forces one; a contender that fails
  # to compile is recorded as `<label>_error`.
  from glt_tpu.ops.pipeline import dedup_engine, fused_hops
  t_start = time.time()
  worker_budget = float(os.environ.get('GLT_BENCH_WORKER_BUDGET', '0'))
  engines = {}

  def hop_suffix():
    eng = resolved_hop_engine()
    return '' if eng == 'element' else '+' + eng

  base_label = (dedup_engine() + ('+fused' if fused_hops() else '')
                + hop_suffix())
  res = engines[base_label] = measure()
  eps = res['edges_per_sec']
  first_cost = time.time() - t_start
  engine_envs = {base_label: {}}  # per-contender env, for the
                                  # per-engine stage-breakdown pass

  def room_for_another():
    return (not worker_budget
            or time.time() - t_start + first_cost * 1.5 + 30
            < worker_budget)

  def race(label, env):
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
      engines[label] = measure()
      engine_envs[label] = dict(env)
    except Exception as e:  # keep the measured headline on any failure
      engines[label + '_error'] = str(e)[:200]
    finally:
      for k, v in saved.items():
        if v is None:
          os.environ.pop(k, None)
        else:
          os.environ[k] = v

  if (dedup_engine() == 'sort' and not fused_hops()
      and 'GLT_FUSED_HOP' not in os.environ
      and resolved_hop_engine() != 'pallas_fused'  # knob is inert there
      and room_for_another()):
    # hop_suffix() rides along: under a forced hop engine the raced
    # pass still runs that engine, and the label must say so
    race('sort+fused' + hop_suffix(), {'GLT_FUSED_HOP': '1'})
  if ('GLT_HOP_ENGINE' not in os.environ
      and os.environ.get('GLT_WINDOW_HOP', '0') not in ('1', 'true')
      and dev.platform == 'tpu' and room_for_another()):
    # ride the best dedup config measured so far, PINNING the fused
    # knob explicitly — auto-fusing would otherwise silently stay on
    # and the label would misattribute the fused delta to pallas
    if ('sort+fused' in engines and base_label != 'sort+fused'
        and isinstance(engines['sort+fused'], dict)):
      ride_fused = (engines['sort+fused']['edges_per_sec']
                    > engines[base_label]['edges_per_sec'])
    else:
      ride_fused = fused_hops()  # what the base run actually used
    label = (dedup_engine() + ('+fused' if ride_fused else '')
             + '+pallas')
    race(label, {'GLT_HOP_ENGINE': 'pallas',
                 'GLT_FUSED_HOP': '1' if ride_fused else '0'})
    from glt_tpu.ops.pallas_kernels import (fused_table_max_slots,
                                            fused_table_slots)
    from glt_tpu.ops.pipeline import sample_budget
    fused_fits = (fused_table_slots(sample_budget(BATCH, list(FANOUT)))
                  <= fused_table_max_slots())
    if fused_fits and room_for_another():
      # the fully-fused pipeline: sample + dedup in one kernel, the
      # sort+fused label contract implemented in VMEM. The walk knob
      # is PINNED per contender so each label names the form that
      # actually ran: per-hop kernels vs the cross-hop walk
      race('sort+pallas_fused', {'GLT_HOP_ENGINE': 'pallas_fused',
                                 'GLT_FUSED_HOP': '1',
                                 'GLT_FUSED_WALK': 'per_hop'})
      if room_for_another():
        # the cross-hop walk: ONE kernel for the whole multi-hop
        # walk, dedup table resident in VMEM across hop boundaries
        race('sort+pallas_walk', {'GLT_HOP_ENGINE': 'pallas_fused',
                                  'GLT_FUSED_HOP': '1',
                                  'GLT_FUSED_WALK': 'cross'})
    elif not fused_fits:
      # racing a demoted engine would just re-measure pallas under a
      # misleading label; record the reason instead
      engines['sort+pallas_fused_skipped'] = (
          'dedup table exceeds GLT_FUSED_TABLE_SLOTS at this batch')
  best = max((v['edges_per_sec'], k) for k, v in engines.items()
             if isinstance(v, dict))
  eps, chosen = best

  # Roofline cells (obs.perf): measure the device's HBM-stream + matmul
  # ceilings ONCE (disk-cached per device kind), then restate every
  # raced contender's edges/s as % of the MEASURED ceiling plus its
  # HBM bytes and FLOPs per edge — the self-grounding restatement every
  # perf claim in the trajectory rides on. Never fatal to the headline.
  if os.environ.get('GLT_BENCH_ROOFLINE', '1') != '0':
    try:
      from glt_tpu.obs.perf import device_ceilings, roofline_report
      ceilings = device_ceilings(dev)
      print(f"# roofline ceilings [{ceilings['device_kind']}]: "
            f"hbm={ceilings['hbm_bytes_per_sec']:.3e} B/s "
            f"matmul={ceilings['flops_per_sec']:.3e} FLOP/s",
            file=sys.stderr)
      for label, rec in engines.items():
        if not isinstance(rec, dict):
          continue
        epd = rec.get('edges_per_dispatch') or 0.0
        # the cell is emitted only when it can be WHOLE (CI asserts a
        # present cell carries all three fields): both cost numbers
        # and a nonzero edge count — a degraded cost pass or a
        # zero-edge run records no cell rather than absurd per-edge
        # numbers
        if (epd <= 0 or 'hbm_bytes_per_dispatch' not in rec
            or 'flops_per_dispatch' not in rec):
          continue
        rec['roofline'] = roofline_report(
            rec['edges_per_sec'],
            bytes_per_item=rec['hbm_bytes_per_dispatch'] / epd,
            flops_per_item=rec['flops_per_dispatch'] / epd,
            ceilings=ceilings, item='edge')
    except Exception as e:  # keep the measured headline regardless
      print(f'# roofline unavailable: {e}', file=sys.stderr)

  # End-to-end train-step throughput, per-batch vs superstep engines
  # side by side (PR: superstep training pipeline) — the growth bench
  # trajectory then tracks training-loop wins, not just sampler
  # throughput. Small fixed shapes independent of the headline knobs;
  # budget-guarded and never fatal to the headline line.
  train_ab = None
  if os.environ.get('GLT_BENCH_TRAIN_AB', '1') != '0':
    spent = time.time() - t_start
    # conservative margin: the A/B takes ~30s on an idle box but the
    # worker is HARD-KILLED at its budget (losing the already-measured
    # headline), so only run it with several-x headroom
    if not worker_budget or worker_budget - spent > 240:
      try:
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), 'benchmarks'))
        from bench_train import measure_engines
        d = measure_engines(supersteps=8)['detail']
        train_ab = {
            'per_batch': d['per_batch_steps_per_sec'],
            'superstep': d['superstep_steps_per_sec'],
            'speedup': d['speedup'],
            'superstep_k': d['superstep_k'],
            'batch': d['batch_size'],
        }
      except Exception as e:  # keep the measured headline regardless
        train_ab = {'error': str(e)[:200]}

  # Fused-walk smoke duel: per-hop vs cross-hop at a fixed toy
  # protocol, on every backend (interpret off-TPU) — the launch
  # collapse and byte delta land in the JSON even when the full-scale
  # contenders can only race on TPU. Runs BEFORE the stage-breakdown
  # passes so the walk's acceptance cells get budget priority on slow
  # runners. Budget-guarded; skip is recorded so CI can tell "didn't
  # fit" from "broke".
  fused_walk_duel = None
  if os.environ.get('GLT_BENCH_WALK_DUEL', '1') != '0':
    spent = time.time() - t_start
    # the duel's dominant cost is two whole-program compiles; in
    # interpret mode the cross-form compile alone was measured >100 s
    # on a slow core (BENCH_r06), so the guard must reflect the real
    # cost or it admits a duel it cannot finish inside the budget
    from glt_tpu.ops.pallas_kernels import interpret_default
    duel_cost = 420 if interpret_default() else 150
    if not worker_budget or worker_budget - spent > duel_cost:
      try:
        fused_walk_duel, duel_entries = measure_fused_walk_duel()
        # roofline cells for the duel entries ride the same ceilings
        if os.environ.get('GLT_BENCH_ROOFLINE', '1') != '0':
          try:
            from glt_tpu.obs.perf import device_ceilings, \
                roofline_report
            ceilings = device_ceilings(dev)
            for rec in duel_entries.values():
              epd = rec.get('edges_per_dispatch') or 0.0
              if (epd <= 0 or 'hbm_bytes_per_dispatch' not in rec
                  or 'flops_per_dispatch' not in rec):
                continue
              rec['roofline'] = roofline_report(
                  rec['edges_per_sec'],
                  bytes_per_item=rec['hbm_bytes_per_dispatch'] / epd,
                  flops_per_item=rec['flops_per_dispatch'] / epd,
                  ceilings=ceilings, item='edge')
          except Exception as e:
            print(f'# duel roofline unavailable: {e}', file=sys.stderr)
        engines.update(duel_entries)
      except Exception as e:  # never fatal to the headline
        fused_walk_duel = {'error': str(e)[:200]}
    else:
      fused_walk_duel = {'skipped': 'bench budget exhausted'}

  # Hetero multi-edge-type race (ISSUE 14 acceptance cells): sorted
  # per-edge-type reference vs the fused multi-edge-type engine,
  # per-batch vs superstep — seeds/s, the dispatches_per_step collapse
  # and per-dispatch cost cells, keyed under their own history bench so
  # hetero numbers never pollute homo baselines. Budget-guarded; a
  # skip is recorded so CI can tell "didn't fit" from "broke".
  hetero = None
  if os.environ.get('GLT_BENCH_HETERO', '1') != '0':
    spent = time.time() - t_start
    from glt_tpu.ops.pallas_kernels import interpret_default
    het_cost = 300 if interpret_default() else 120
    if not worker_budget or worker_budget - spent > het_cost:
      try:
        hetero = measure_hetero_race()
      except Exception as e:  # never fatal to the headline
        hetero = {'error': str(e)[:200]}
    else:
      hetero = {'skipped': 'bench budget exhausted'}

  # Per-stage time breakdown (the obs layer): run a short instrumented
  # sample->gather epoch with tracing + full device-sync sampling, then
  # report each stage's share next to the headline. Fixed smoke-scale
  # protocol independent of the headline knobs; budget-guarded, never
  # fatal. GLT_OBS_DUMP=<dir> additionally writes the registry snapshot
  # and a Perfetto-loadable trace JSON there (the CI smoke-bench
  # artifacts). Each raced contender additionally gets its OWN
  # breakdown (same protocol, smaller batch so the fused engine's
  # dedup table engages at smoke scale) so a fusion delta in the
  # headline is attributable stage-by-stage: the fused engine should
  # show gather.features self-time collapsing into sample.multihop.
  stage_breakdown = None
  if os.environ.get('GLT_BENCH_OBS', '1') != '0':
    spent = time.time() - t_start
    if not worker_budget or worker_budget - spent > 120:
      try:
        stage_breakdown = measure_stage_breakdown(
            dump_dir=os.environ.get('GLT_OBS_DUMP'))
      except Exception as e:  # keep the measured headline regardless
        stage_breakdown = {'error': str(e)[:200]}
    for label, env in engine_envs.items():
      if not isinstance(engines.get(label), dict):
        continue
      spent = time.time() - t_start
      if worker_budget and worker_budget - spent < 90:
        break
      saved = {k: os.environ.get(k) for k in env}
      os.environ.update(env)
      try:
        engines[label]['stage_breakdown'] = measure_stage_breakdown(
            batches=4, batch_size=256)
      except Exception as e:
        engines[label]['stage_breakdown'] = {'error': str(e)[:200]}
      finally:
        for k, v in saved.items():
          if v is None:
            os.environ.pop(k, None)
          else:
            os.environ[k] = v

  # what the samplers' `auto` runs here
  auto_engine = None
  if 'GLT_HOP_ENGINE' not in os.environ:
    from glt_tpu.ops.pipeline import hop_engine
    auto_engine = hop_engine()

  def engine_record(v):
    if not isinstance(v, dict):
      return v
    rec = {'edges_per_sec': round(v['edges_per_sec'], 1),
           'compile_s': round(v['compile_s'], 2),
           'steady_recompiles': v['steady_recompiles']}
    for k in ('kernel_launches_per_dispatch', 'hbm_bytes_per_dispatch',
              'flops_per_dispatch', 'scale', 'roofline',
              'stage_breakdown'):
      if k in v:
        rec[k] = v[k]
    return rec

  winner = engines.get(chosen)
  _emit(round(eps, 1), round(eps / A100_ASSUMED_EDGES_PER_SEC, 4),
        backend=dev.platform, scan=scan, iters=ITERS, batch=BATCH,
        scale=f'N{NUM_NODES}_E{NUM_EDGES}_B{BATCH}_S{scan}',
        engine=chosen, auto_engine=auto_engine,
        engines={k: engine_record(v) for k, v in engines.items()},
        roofline=(winner.get('roofline')
                  if isinstance(winner, dict) else None),
        train_steps_per_sec=train_ab,
        stage_breakdown=stage_breakdown,
        fused_walk_duel=fused_walk_duel,
        hetero=hetero)


def measure_stage_breakdown(batches: int = 8, num_nodes: int = 100_000,
                            num_edges: int = 1_000_000,
                            feat_dim: int = 16,
                            batch_size: int = 1024,
                            dump_dir=None):
  """Instrumented sample->dedup->gather pass over a smoke-scale graph:
  glt_tpu.obs tracing on, device-sync sampling at 1.0 so every span
  covers real compute, per-stage times aggregated from the registry's
  ``stage_seconds`` histograms. Returns {stage: {total_ms, mean_ms,
  count}} plus the warmup compile wall time."""
  import numpy as np
  from glt_tpu.data import Dataset
  from glt_tpu.loader import NeighborLoader
  from glt_tpu.obs import MetricsRegistry, get_tracer, set_registry

  rng = np.random.default_rng(7)
  src = rng.integers(0, num_nodes, num_edges, dtype=np.int64)
  dst = rng.integers(0, num_nodes, num_edges, dtype=np.int64)
  ds = Dataset()
  ds.init_graph(edge_index=np.stack([src, dst]), num_nodes=num_nodes)
  ds.init_node_features(
      rng.random((num_nodes, feat_dim)).astype(np.float32))
  seeds = rng.integers(0, num_nodes, (batches + 1) * batch_size)

  tracer = get_tracer()
  was_enabled, prev_sample = tracer.enabled, tracer._sample
  prev_registry = set_registry(MetricsRegistry())  # isolated aggregation
  tracer.enable(sample=1.0)
  try:
    loader = NeighborLoader(ds, list(FANOUT), seeds,
                            batch_size=batch_size, seed=0)
    it = iter(loader)
    t0 = time.time()
    next(it)  # first batch pays trace+compile; keep it out of the stats
    warm_s = time.time() - t0
    tracer.clear()
    set_registry(MetricsRegistry())  # drop warmup-batch observations
    for _ in range(batches):
      next(it)
    from glt_tpu.obs import get_registry
    snap = get_registry().snapshot()
    out = {'warmup_compile_s': round(warm_s, 2), 'batches': batches,
           'batch_size': batch_size}
    # spans NEST (loader.batch encloses sample.multihop and
    # gather.features), so raw per-stage totals double-count; report
    # self time (own duration minus direct children) so the stage
    # shares sum to ~wall — total_ms stays alongside for the
    # enclosing-span view
    events = tracer.events()
    child_dur = {}
    for e in events:
      p = e['args'].get('parent_id')
      if p is not None:
        child_dur[p] = child_dur.get(p, 0) + e['dur']
    stages = {}
    for e in events:
      s = stages.setdefault(e['name'],
                            {'total_ms': 0.0, 'self_ms': 0.0,
                             'count': 0})
      s['total_ms'] += e['dur'] / 1e3
      s['self_ms'] += (e['dur']
                       - child_dur.get(e['args']['span_id'], 0)) / 1e3
      s['count'] += 1
    out['stages'] = {
        name: {'total_ms': round(s['total_ms'], 2),
               'self_ms': round(s['self_ms'], 2),
               'mean_ms': round(s['total_ms'] / max(s['count'], 1), 3),
               'count': s['count']}
        for name, s in sorted(stages.items())
    }
    if dump_dir:
      with open(os.path.join(dump_dir, 'obs_registry.json'), 'w') as f:
        json.dump(snap, f, indent=2)
      tracer.save(os.path.join(dump_dir, 'obs_trace.json'))
    return out
  finally:
    set_registry(prev_registry)
    tracer.enabled = was_enabled
    tracer._sample = prev_sample
    tracer.clear()


def walk_hbm_model(batch, fanouts, slots, width, num_edges, planes=1):
  """Analytic HBM bytes per dispatch for the two fused-walk forms —
  the DELTA-relevant terms only (both forms share the XLA epilogue:
  relabel sorts, output concatenation). ``per_hop`` pays, per hop
  boundary, a full table-plane round trip, a fresh read of the padded
  edge-array operand, and the XLA-side table-label rewrite; ``cross``
  pays the edge operand once and stages only the [S_h, K_h] int32
  frontier per boundary. This model makes the expected ratio visible
  in the bench JSON on every backend — interpret-mode cost analysis
  measures the EMULATION of the kernels (dynamic-update-slice traffic
  of the discharged state machine), so the measured interpret ratio
  reflects the harness, not the Mosaic dataflow; the measured TPU
  cells are the decisive evidence."""
  table = 2 * slots * 4                     # both planes, bytes
  arr = (num_edges + width) * 4 * planes
  rows, s = [], batch
  for k in fanouts:
    rows.append(s)
    s *= k
  win = sum(r * width * 4 * planes for r in rows)
  m = sum(r * k * 4 for r, k in zip(rows, fanouts))
  hops = len(fanouts)
  per_hop = (2 * table                      # seed insert: planes in+out
             + hops * 2 * table             # per-hop planes in+out
             + hops * arr                   # edge operand per launch
             + win                          # window DMA reads
             + hops * (3 * table // 2))     # XLA relabel table rewrite
  cross = (arr                              # edge operand once
           + win                            # window DMA reads
           + 2 * m                          # frontier staging in+out
           + 2 * m)                         # per-hop indptr pair reads
  return dict(per_hop_bytes=per_hop, cross_bytes=cross,
              ratio=round(cross / max(per_hop, 1), 4))


def measure_fused_walk_duel(num_nodes: int = 20_000,
                            num_edges: int = 200_000,
                            iters: int = 3):
  """Per-hop vs cross-hop fused walk at a fixed smoke protocol (3-hop
  walk, its own toy graph), on WHATEVER backend the bench runs:
  interpret mode off-TPU, compiled Mosaic on TPU. Each form is traced
  once, AOT-compiled once, cost-analyzed (bytes/FLOPs/kernel launches
  per dispatch) and executed ``iters`` times for edges/s — so the
  O(hops)->O(1) launch collapse and the table-residency byte delta are
  recorded numbers in the BENCH JSON, next to the analytic
  ``hbm_model`` that states what the delta SHOULD be (see
  ``walk_hbm_model`` for why the interpret-mode measured ratio is the
  harness, not the kernel). Returns (duel_dict, engine_entries)."""
  import numpy as np
  import jax
  import jax.numpy as jnp
  from glt_tpu.data import Topology
  from glt_tpu.obs.perf import instrument_compiled
  from glt_tpu.ops.pallas_kernels import (fused_table_slots,
                                          interpret_default,
                                          kernel_launch_count)
  from glt_tpu.ops.pipeline import (make_dedup_tables, multihop_sample,
                                    sample_budget)
  from glt_tpu.ops.sample import FusedHopPlan

  interp = interpret_default()
  # interpret-mode tracing cost scales with block*sum(fanouts) unrolled
  # probe-inserts, so the off-TPU smoke protocol uses smaller fanouts;
  # both forms always run the SAME protocol, which is what the ratio
  # needs
  batch = int(os.environ.get('GLT_BENCH_DUEL_BATCH',
                             '64' if interp else '256'))
  fan = tuple(int(x) for x in os.environ.get(
      'GLT_BENCH_DUEL_FANOUT',
      '5,4,3' if interp else '15,10,5').split(','))
  width = max(int(os.environ.get('GLT_WINDOW_W', '96')), 8)

  rng = np.random.default_rng(11)
  src = rng.integers(0, num_nodes, num_edges, dtype=np.int64)
  dst = (rng.random(num_edges) ** 2 * num_nodes).astype(np.int64) \
      % num_nodes
  topo = Topology(edge_index=np.stack([src, dst]),
                  num_nodes=num_nodes)
  indptr = jnp.asarray(topo.indptr.astype(np.int32))
  indices = jnp.asarray(topo.indices)
  iw = jnp.concatenate([indices, jnp.full((width,), -1,
                                          indices.dtype)])
  n_hub = int((np.diff(topo.indptr) > width).sum())
  slots = fused_table_slots(sample_budget(batch, list(fan)))
  plan = FusedHopPlan(indptr, indices, iw, width, n_hub, slots,
                      interpret=interp)
  table, scratch = make_dedup_tables(num_nodes)
  from glt_tpu.utils.rng import make_key
  seeds = jnp.asarray(
      rng.integers(0, num_nodes, batch).astype(np.int32))
  keys = jax.random.split(make_key(3), iters + 1)
  scale = f'N{num_nodes}_E{num_edges}_B{batch}_F{",".join(map(str, fan))}'

  entries = {}
  saved = {k: os.environ.get(k) for k in
           ('GLT_HOP_ENGINE', 'GLT_FUSED_HOP', 'GLT_FUSED_WALK')}
  try:
    for mode, label in (('per_hop', 'sort+pallas_fused_smoke'),
                        ('cross', 'sort+pallas_walk_smoke')):
      os.environ.update({'GLT_HOP_ENGINE': 'pallas_fused',
                         'GLT_FUSED_HOP': '1',
                         'GLT_FUSED_WALK': mode})

      def f(seeds, key, table, scratch):
        out, table, scratch = multihop_sample(
            None, seeds, jnp.asarray(batch), fan, key, table, scratch,
            fused_plan=plan)
        return (out['num_sampled_edges'].sum(),
                out['node_count'], table, scratch)

      t0 = time.time()
      launches0 = kernel_launch_count()
      lowered = jax.jit(f).lower(seeds, keys[0], table, scratch)
      launches = kernel_launch_count() - launches0
      compiled = lowered.compile()
      compile_s = time.time() - t0
      cost = instrument_compiled(f'bench.walk_duel.{mode}', compiled)
      if 'kernel_launches' not in cost and launches:
        cost['kernel_launches'] = launches
      try:  # TPU ground truth: Mosaic kernel entries in the lowered HLO
        hlo = lowered.as_text().count('tpu_custom_call')
        if hlo:
          cost['kernel_launches'] = hlo
      except Exception:
        pass
      edges, _, t2, s2 = compiled(seeds, keys[0], table, scratch)
      jax.block_until_ready(edges)   # warmup dispatch
      t1 = time.time()
      counts = []
      for it in range(iters):
        e_i, _, t2, s2 = compiled(seeds, keys[it + 1], t2, s2)
        counts.append(e_i)
      jax.block_until_ready(counts[-1])
      dt = time.time() - t1
      total = int(np.sum([int(c) for c in counts]))
      entries[label] = {
          'edges_per_sec': round(total / dt, 1),
          'compile_s': round(compile_s, 2),
          # one AOT executable served the whole timed loop: shape-
          # stable by construction, and no re-trace was observed
          'steady_recompiles': 0,
          'edges_per_dispatch': total / iters,
          'scale': scale,
      }
      if 'bytes_accessed' in cost:
        entries[label]['hbm_bytes_per_dispatch'] = cost[
            'bytes_accessed']
      if 'flops' in cost:
        entries[label]['flops_per_dispatch'] = cost['flops']
      if 'kernel_launches' in cost:
        entries[label]['kernel_launches_per_dispatch'] = cost[
            'kernel_launches']
  finally:
    for k, v in saved.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v

  duel = {'scale': scale, 'interpret': interp,
          'hbm_model': walk_hbm_model(batch, fan, slots, width,
                                      num_edges)}
  ph = entries.get('sort+pallas_fused_smoke', {})
  cr = entries.get('sort+pallas_walk_smoke', {})
  if 'hbm_bytes_per_dispatch' in ph and 'hbm_bytes_per_dispatch' in cr:
    duel['measured_bytes_ratio'] = round(
        cr['hbm_bytes_per_dispatch'] / max(ph['hbm_bytes_per_dispatch'],
                                           1.0), 4)
  if 'kernel_launches_per_dispatch' in ph \
      and 'kernel_launches_per_dispatch' in cr:
    duel['kernel_launches'] = {
        'per_hop': ph['kernel_launches_per_dispatch'],
        'cross': cr['kernel_launches_per_dispatch']}
  return duel, entries


def measure_hetero_race(iters: int = 3, supersteps: int = 4):
  """Hetero multi-edge-type sampling race (ISSUE 14 acceptance cells):
  the per-edge-type sorted reference vs the fused multi-edge-type
  kernel engine, per-batch vs superstep, at a fixed smoke protocol on
  WHATEVER backend the bench runs (interpret off-TPU, compiled Mosaic
  on TPU — the driver's TPU round produces the decisive seeds/s
  against the 174 seeds/s VERDICT baseline).

  Records per contender: seeds/s, compile_s, steady_recompiles,
  dispatches_per_step (1.0 per-batch; 1/K for the superstep — the
  recorded DISPATCH COLLAPSE), and — when cost analysis is available —
  bytes/FLOPs/kernel launches per dispatch plus a roofline cell
  (item='seed'). Keyed in benchmarks/history.py under its own
  ``hetero_sampler`` bench + its own scale string, so hetero numbers
  never enter a homo baseline window. Returns (hetero_dict)."""
  import functools
  import numpy as np
  import jax
  import jax.numpy as jnp
  from glt_tpu.data import Dataset
  from glt_tpu.obs.perf import instrument_compiled
  from glt_tpu.ops.pallas_kernels import interpret_default
  from glt_tpu.ops.pipeline import (multihop_sample_hetero,
                                    multihop_sample_hetero_many)
  from glt_tpu.sampler import NeighborSampler
  from glt_tpu.utils.rng import make_key

  interp = interpret_default()
  # interpret-mode fused tracing cost scales with the unrolled
  # probe-insert loops, so the off-TPU smoke protocol stays toy-sized;
  # every contender runs the SAME protocol, which is what the ratios
  # need
  nu = int(os.environ.get('GLT_BENCH_HET_USERS',
                          '2000' if interp else '200000'))
  ni = int(os.environ.get('GLT_BENCH_HET_ITEMS',
                          '4000' if interp else '400000'))
  batch = int(os.environ.get('GLT_BENCH_HET_BATCH',
                             '32' if interp else '512'))
  fan = [int(x) for x in os.environ.get(
      'GLT_BENCH_HET_FANOUT', '3,2' if interp else '10,5').split(',')]
  k_scan = max(int(os.environ.get('GLT_BENCH_HET_SCAN',
                                  str(supersteps))), 2)
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  rng = np.random.default_rng(17)
  u2i_ei = np.stack([np.repeat(np.arange(nu, dtype=np.int64), 4),
                     rng.integers(0, ni, 4 * nu, dtype=np.int64)])
  # skewed in-degrees so the per-type dedup namespaces see real load
  i2i_src = np.repeat(np.arange(ni, dtype=np.int64), 4)
  i2i_dst = ((rng.random(4 * ni) ** 2) * ni).astype(np.int64) % ni
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index={u2i: u2i_ei,
                            i2i: np.stack([i2i_src, i2i_dst])},
                num_nodes={'user': nu, 'item': ni})
  nn = {u2i: list(fan), i2i: list(fan)}
  scale = (f'U{nu}_I{ni}_B{batch}_'
           f'F{",".join(map(str, fan))}_K{k_scan}')

  def _checksum(out):
    acc = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(out):
      acc = acc + leaf.sum(dtype=jnp.float32)
    return acc

  seed_pool = rng.integers(0, nu, (iters + 2, k_scan, batch))
  entries = {}
  saved = {k: os.environ.get(k) for k in
           ('GLT_HOP_ENGINE', 'GLT_FUSED_HOP', 'GLT_DEDUP',
            'GLT_FUSED_WALK')}
  try:
    for label, env, use_plan, scan in (
        ('hetero_sorted',
         {'GLT_DEDUP': 'sort', 'GLT_FUSED_HOP': '1'}, False, 1),
        ('hetero_pallas_fused',
         {'GLT_HOP_ENGINE': 'pallas_fused'}, True, 1),
        ('hetero_pallas_fused_superstep',
         {'GLT_HOP_ENGINE': 'pallas_fused'}, True, k_scan)):
      for k in saved:
        os.environ.pop(k, None)
      os.environ.update(env)
      samp = NeighborSampler(ds.graph, nn, seed=0)
      trav = samp._traversal_types()
      caps, budgets = samp._hetero_caps({'user': batch})
      plan = samp._hetero_fused_plan({'user': batch}) if use_plan \
          else None
      if use_plan and plan is None:
        entries[label + '_skipped'] = (
            'fused hetero plan unavailable (see '
            'hop_engine_fallbacks_total)')
        continue
      one_hops = {e: (lambda ids, f, k, m, _e=e: samp._one_hop(
          samp.graph[_e], ids, f, k, m)) for e in samp.edge_types}
      tables = {t: samp._get_tables(t, n)
                for t, n in samp._node_counts.items()}
      traces = {'n': 0}

      if scan > 1:
        @functools.partial(jax.jit, donate_argnums=(2,))
        def fn(seeds_stack, key, tables):
          traces['n'] += 1  # trace-time side effect only
          outs, tables = multihop_sample_hetero_many(
              one_hops, trav, samp.num_neighbors, samp.num_hops,
              caps, budgets, {'user': seeds_stack},
              {'user': jnp.full((seeds_stack.shape[0],), batch,
                                jnp.int32)},
              key, tables, fused_plan=plan)
          edges = sum(v.sum() for v in
                      outs['num_sampled_edges'].values())
          return edges, _checksum(outs), tables
      else:
        @functools.partial(jax.jit, donate_argnums=(2,))
        def fn(seeds_stack, key, tables):
          traces['n'] += 1  # trace-time side effect only
          out, tables = multihop_sample_hetero(
              one_hops, trav, samp.num_neighbors, samp.num_hops,
              caps, budgets, {'user': seeds_stack[0]},
              {'user': jnp.asarray(batch)}, key, tables,
              fused_plan=plan)
          edges = sum(v.sum() for v in
                      out['num_sampled_edges'].values())
          return edges, _checksum(out), tables

      keys = jax.random.split(make_key(5), iters + 2)
      arg_sds = (jax.ShapeDtypeStruct((k_scan, batch), jnp.int32),
                 jax.ShapeDtypeStruct(keys[0].shape, keys[0].dtype),
                 jax.tree_util.tree_map(
                     lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     tables))
      t0 = time.time()
      edges, sig, tables = fn(
          jnp.asarray(seed_pool[0], jnp.int32), keys[0], tables)
      jax.block_until_ready((edges, sig))
      compile_s = time.time() - t0
      edges, sig, tables = fn(
          jnp.asarray(seed_pool[1], jnp.int32), keys[1], tables)
      jax.block_until_ready((edges, sig))
      traces_warm = traces['n']
      counts, sigs = [], []
      t1 = time.time()
      for it in range(iters):
        e_i, s_i, tables = fn(
            jnp.asarray(seed_pool[it + 2], jnp.int32), keys[it + 2],
            tables)
        counts.append(e_i)
        sigs.append(s_i)
      jax.block_until_ready((counts[-1], sigs[-1]))
      dt = time.time() - t1
      steps = iters * scan  # batches consumed during the timed loop
      total_edges = int(np.sum([int(c) for c in counts]))
      rec = {
          'seeds_per_sec': round(batch * steps / dt, 1),
          'edges_per_sec': round(total_edges / dt, 1),
          'compile_s': round(compile_s, 2),
          'steady_recompiles': traces['n'] - traces_warm,
          'dispatches_per_step': round(1.0 / scan, 4),
          'seeds_per_dispatch': batch * scan,
          'scale': scale,
      }
      if os.environ.get('GLT_BENCH_ROOFLINE', '1') != '0':
        try:
          cost = instrument_compiled(f'bench.hetero.{label}', fn,
                                     *arg_sds, aot_compile=True)
          if 'bytes_accessed' in cost:
            rec['hbm_bytes_per_dispatch'] = cost['bytes_accessed']
          if 'flops' in cost:
            rec['flops_per_dispatch'] = cost['flops']
          if 'kernel_launches' in cost:
            rec['kernel_launches_per_dispatch'] = cost[
                'kernel_launches']
        except Exception as e:
          print(f'# hetero cost analysis unavailable: {e}',
                file=sys.stderr)
      entries[label] = rec
  finally:
    for k, v in saved.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v

  het = {'scale': scale, 'interpret': interp,
         'baseline_seeds_per_sec_r5': 174.0,
         'engines': entries}
  pb = entries.get('hetero_pallas_fused', {})
  ss = entries.get('hetero_pallas_fused_superstep', {})
  if 'dispatches_per_step' in pb and 'dispatches_per_step' in ss:
    het['dispatches_per_step'] = {
        'per_batch': pb['dispatches_per_step'],
        'superstep': ss['dispatches_per_step']}
  if 'seeds_per_sec' in ss:
    het['vs_r5_baseline'] = round(ss['seeds_per_sec'] / 174.0, 2)
  # roofline cells: restate each contender's seeds/s against the
  # measured device ceilings (same whole-cell rule as the homo race)
  if os.environ.get('GLT_BENCH_ROOFLINE', '1') != '0':
    try:
      from glt_tpu.obs.perf import device_ceilings, roofline_report
      import jax as _jax
      ceilings = device_ceilings(_jax.devices()[0])
      for rec in entries.values():
        if not isinstance(rec, dict):
          continue
        spd = rec.get('seeds_per_dispatch') or 0
        if (spd <= 0 or 'hbm_bytes_per_dispatch' not in rec
            or 'flops_per_dispatch' not in rec):
          continue
        rec['roofline'] = roofline_report(
            rec['seeds_per_sec'],
            bytes_per_item=rec['hbm_bytes_per_dispatch'] / spd,
            flops_per_item=rec['flops_per_dispatch'] / spd,
            ceilings=ceilings, item='seed')
    except Exception as e:
      print(f'# hetero roofline unavailable: {e}', file=sys.stderr)
  return het


def _dump_obs_on_failure():
  """GLT_OBS_DUMP artifacts on the worker's FAILURE path: the success
  path writes them from measure_stage_breakdown, but a crashed run is
  exactly the one whose registry counters and last spans matter —
  without this the postmortem evidence dies with the process. Also
  leaves a flight-recorder postmortem when GLT_OBS_POSTMORTEM_DIR is
  configured."""
  dump_dir = os.environ.get('GLT_OBS_DUMP')
  try:
    from glt_tpu.obs import get_recorder, get_registry, get_tracer
    if dump_dir:
      with open(os.path.join(dump_dir, 'obs_registry.json'), 'w') as f:
        json.dump(get_registry().snapshot(), f, indent=2)
      get_tracer().save(os.path.join(dump_dir, 'obs_trace.json'))
      print(f'# worker failed; obs artifacts dumped to {dump_dir}',
            file=sys.stderr)
    get_recorder().trip('bench_worker_failure')
  except Exception as e:  # the dump must never mask the real error
    print(f'# obs failure dump failed: {e}', file=sys.stderr)


def _append_history(line: str) -> None:
  """GLT_BENCH_HISTORY=<path>: append the emitted headline JSON to the
  bench trajectory (benchmarks/history.py) — the series
  scripts/bench_compare.py gates against."""
  hist = os.environ.get('GLT_BENCH_HISTORY')
  if not hist:
    return
  try:
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'benchmarks'))
    from history import append_bench_json
    rows = append_bench_json(hist, json.loads(line))
    print(f'# appended {len(rows)} series to {hist}', file=sys.stderr)
  except Exception as e:  # trajectory bookkeeping is never fatal
    print(f'# bench history append failed: {e}', file=sys.stderr)


def _child(mode, timeout):
  """Run a child in its own process group; on timeout SIGKILL the whole
  group (subprocess.run's TimeoutExpired kills only the direct child —
  a surviving grandchild would both hold the TPU and keep the stdout
  pipe open, hanging the supervisor in communicate())."""
  import signal
  proc = subprocess.Popen(
      [sys.executable, os.path.abspath(__file__), mode],
      stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
      start_new_session=True)
  try:
    out, err = proc.communicate(timeout=timeout)
  except subprocess.TimeoutExpired:
    try:
      os.killpg(proc.pid, signal.SIGKILL)  # pgid == pid (new session)
    except (ProcessLookupError, PermissionError):
      proc.kill()
    try:
      proc.communicate(timeout=10)
    except Exception:
      pass
    return None, f'timeout after {timeout:.0f}s'
  proc.stdout, proc.stderr = out, err
  return proc, None


def run_supervisor():
  t0 = time.time()
  budget = float(os.environ.get('GLT_BENCH_BUDGET', '900'))
  deadline = t0 + budget
  last_err = 'unknown'

  def remaining():
    return deadline - time.time()

  env_timeout = os.environ.get('GLT_BENCH_TIMEOUT')
  while remaining() > 120:
    timeout = remaining() - 30
    if env_timeout:
      timeout = min(timeout, float(env_timeout))
    # budget hint: lets the worker decide whether the fused-engine
    # second pass fits before its own kill deadline
    os.environ['GLT_BENCH_WORKER_BUDGET'] = str(int(timeout))
    proc, err = _child('--run', timeout)
    if proc is None:
      last_err = err
      print(f'# measurement: {last_err}', file=sys.stderr)
      if not env_timeout:
        break  # the attempt consumed the whole remaining budget
      continue  # short-capped attempt: budget remains, retry
    line = next((l for l in reversed(proc.stdout.splitlines())
                 if l.startswith('{')), None)
    if proc.returncode == 0 and line:
      print(line)
      _append_history(line)
      return 0
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-8:]
    last_err = (f'rc={proc.returncode}: ' + ' | '.join(tail))[:800]
    print(f'# measurement failed: {last_err}', file=sys.stderr)
    break  # a failed worker fails the same way again
  # value 0.0 + error field + nonzero exit: "not measured", never
  # "measured as 0"
  _emit(0.0, 0.0, error=f'not measured within {budget:.0f}s budget: '
        f'{last_err}')
  return 1


if __name__ == '__main__':
  if '--run' in sys.argv:
    try:
      run_worker()
    except BaseException:
      _dump_obs_on_failure()
      raise
  else:
    sys.exit(run_supervisor())
