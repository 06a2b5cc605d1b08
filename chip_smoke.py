"""chip_smoke.py: GraphSAGE-products training, end to end, on the chip.

The quickest proof that the system still starts on a TPU. It drives the
repo's main path (BASELINE.json config 1: 2.45M nodes, ~61M directed
edges, 100-wide float32 features fully resident, 47 classes, fanout
15,10,5, batch 1024, 3 layers, hidden 256, adam 1e-3) through the entry
points a user calls, at full width, with random weights made from a
seed:

  loader phase  Dataset -> NeighborLoader -> jitted flax/optax step, as
                examples/train_sage_products.py --scale full does;
  fused phase   SPMDSageTrainStep over make_mesh(all local chips) with a
                ShardedFeature: per-batch steps, then supersteps of K=4,
                and the two compared (the trainer's own contract is
                that a superstep equals K per-batch steps).

There is no CPU mode. Without a TPU the script exits nonzero before it
builds anything, and nothing here catches an error: a phase that fails
ends the run with a traceback and no result line. The step times it
prints are information for the benchmark PR, not a metric.

Last stdout line: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
import gc
import json
import os
import sys
import time

NUM_NODES = 2_450_000
FANOUT = [15, 10, 5]
BATCH = 1024
HIDDEN = 256
LOADER_STEPS = 20
FUSED_STEPS = 8        # per-batch steps: one first dispatch + 7 steady
SUPERSTEP_K = 4
SUPERSTEPS = 3         # one first dispatch + 2 steady


def say(**fields):
  print(json.dumps(fields), flush=True)


def check(ok, *why):
  """An assertion that ``python -O`` cannot strip."""
  if not ok:
    raise AssertionError(*why)


def bytes_in_use():
  import jax
  return [d.memory_stats()['bytes_in_use'] for d in jax.local_devices()]


def timed_window(n_steps, run_step, unit='step'):
  """Dispatch ``n_steps`` steps back to back, then fence twice: first
  ``block_until_ready`` on the last loss, then a host readback of every
  loss. Returns (losses as numpy, ms per ``unit`` to each fence). The two
  agree when ``block_until_ready`` really waits for the device."""
  import jax
  import numpy as np
  losses = []
  t0 = time.perf_counter()
  for i in range(n_steps):
    losses.append(run_step(i))
  jax.block_until_ready(losses[-1])
  t_bur = time.perf_counter()
  host = np.stack([np.asarray(l) for l in losses])
  t_read = time.perf_counter()
  return host, {f'ms_per_{unit}_block_until_ready':
                round((t_bur - t0) * 1e3 / n_steps, 2),
                f'ms_per_{unit}_host_readback':
                round((t_read - t0) * 1e3 / n_steps, 2)}


def loader_phase(ds, num_classes):
  """NeighborLoader + jitted train step, 20 steps."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  import optax
  from glt_tpu.loader import NeighborLoader
  from glt_tpu.models import GraphSAGE
  from glt_tpu.typing import Split

  loader = NeighborLoader(ds, FANOUT, input_nodes=ds.get_split(Split.train),
                          batch_size=BATCH, shuffle=True, drop_last=True,
                          seed=0)
  model = GraphSAGE(hidden_features=HIDDEN, out_features=num_classes,
                    num_layers=len(FANOUT))
  tx = optax.adam(1e-3)

  @jax.jit
  def step(params, opt, batch):
    def loss_fn(p):
      logits = model.apply(p, batch)
      mask = jnp.arange(logits.shape[0]) < batch.metadata['n_valid']
      l = optax.softmax_cross_entropy_with_integer_labels(logits, batch.y)
      return jnp.where(mask, l, 0).sum() / jnp.maximum(mask.sum(), 1)
    loss, g = jax.value_and_grad(loss_fn)(params)
    up, opt = tx.update(g, opt)
    return optax.apply_updates(params, up), opt, loss

  batches = iter(loader)
  state = {}

  def run_step(_):
    state['params'], state['opt'], loss = step(
        state['params'], state['opt'], next(batches))
    return loss

  t0 = time.perf_counter()
  batch = next(batches)            # compiles the sampler
  state['params'] = model.init(jax.random.key(0), batch)
  state['opt'] = tx.init(state['params'])
  state['params'], state['opt'], first = step(
      state['params'], state['opt'], batch)
  first = float(first)             # compiles the step; readback fence
  first_s = time.perf_counter() - t0
  sampler = loader.sampler
  compiled = (sampler.num_compiled_fns, step._cache_size())
  rest, steady = timed_window(LOADER_STEPS - 1, run_step)
  losses = np.concatenate([[first], rest])

  check(np.isfinite(losses).all(), f'non-finite loss: {losses}')
  check(losses[-5:].mean() < losses[:5].mean(),
        f'loss did not fall over {LOADER_STEPS} steps: {losses}')
  check(compiled == (1, 1), compiled)
  check((sampler.num_compiled_fns, step._cache_size()) == compiled,
        'recompiled after step 1', sampler.num_compiled_fns,
        step._cache_size())
  say(phase='loader', steps=LOADER_STEPS,
      first_dispatch_s=round(first_s, 1), **steady,
      loss_first5=round(float(losses[:5].mean()), 4),
      loss_last5=round(float(losses[-5:].mean()), 4),
      sampler_compiled_fns=sampler.num_compiled_fns,
      step_jit_cache=step._cache_size())


def step_hlo(step, params, opt, seeds, n_valid, keys):
  """Optimized HLO text of the trainer's per-batch program, lowered
  with the arguments ``SPMDSageTrainStep.__call__`` passes."""
  import jax
  import jax.numpy as jnp
  from glt_tpu.parallel import row_sharded
  sh = row_sharded(step.mesh, step.axis)
  return step._step_fn.lower(
      params, opt,
      jax.device_put(jnp.asarray(seeds, jnp.int32), sh),
      jax.device_put(jnp.asarray(n_valid, jnp.int32), sh), keys,
      step.feature.array, step.labels, step._indptr,
      step._indices).compile().as_text()


def fused_phase(ds, feats, num_classes):
  """SPMDSageTrainStep over every local chip: per-batch, then superstep."""
  import jax
  import numpy as np
  import optax
  from glt_tpu.models import GraphSAGE
  from glt_tpu.parallel import (ShardedFeature, SPMDSageTrainStep,
                                make_mesh)
  from glt_tpu.typing import Split

  n_dev = jax.local_device_count()
  mesh = make_mesh(n_dev)
  model = GraphSAGE(hidden_features=HIDDEN, out_features=num_classes,
                    num_layers=len(FANOUT))
  tx = optax.adam(1e-3)
  sf = ShardedFeature(feats, mesh)
  step = SPMDSageTrainStep(mesh, model, tx, ds.get_graph(), sf,
                           ds.get_node_label(), fanouts=FANOUT,
                           batch_size_per_device=BATCH)
  params0 = step.init_params(jax.random.key(0))
  opt0 = tx.init(params0)

  total = SUPERSTEP_K * SUPERSTEPS
  check(FUSED_STEPS <= total)
  rng = np.random.default_rng(1)
  seeds = rng.choice(ds.get_split(Split.train), (total, n_dev * BATCH),
                     replace=False)
  n_valid = np.full((total, n_dev), BATCH)
  keys = jax.random.split(jax.random.key(1), (total, n_dev))

  # per-batch: one dispatch per step (params are not donated here)
  state = {'params': params0, 'opt': opt0}

  def per_batch(t):
    state['params'], state['opt'], loss = step(
        state['params'], state['opt'], seeds[t], n_valid[t], keys[t])
    return loss

  t0 = time.perf_counter()
  first = np.asarray(per_batch(0))
  pb_first_s = time.perf_counter() - t0
  rest, pb_steady = timed_window(FUSED_STEPS - 1,
                                 lambda i: per_batch(i + 1))
  pb_losses = np.concatenate([first[None], rest])     # [steps, n_dev]
  check(pb_losses.shape == (FUSED_STEPS, n_dev), pb_losses.shape)
  check(np.isfinite(pb_losses).all(), pb_losses)
  check(step.step_traces == 1, step.step_traces)

  # superstep: K batches per donated dispatch, from the same initial
  # state, seeds and keys
  state = {'params': params0, 'opt': opt0}

  def superstep(s):
    w = slice(s * SUPERSTEP_K, (s + 1) * SUPERSTEP_K)
    state['params'], state['opt'], loss = step.superstep(
        state['params'], state['opt'], seeds[w], n_valid[w], keys[w])
    return loss

  t0 = time.perf_counter()
  first = np.asarray(superstep(0))
  ss_first_s = time.perf_counter() - t0
  rest, ss_steady = timed_window(SUPERSTEPS - 1,
                                 lambda i: superstep(i + 1), 'superstep')
  ss_losses = np.concatenate([first[None], rest]).reshape(total, n_dev)
  check(np.isfinite(ss_losses).all(), ss_losses)
  check(step.superstep_traces == 1, step.superstep_traces)
  # the trainer's own contract: a superstep is K per-batch steps
  np.testing.assert_allclose(ss_losses[:FUSED_STEPS], pb_losses,
                             rtol=1e-3, atol=1e-4)

  say(phase='fused', n_dev=n_dev, batch_size_per_device=BATCH,
      per_batch=dict(steps=FUSED_STEPS,
                     first_dispatch_s=round(pb_first_s, 1), **pb_steady),
      superstep=dict(k=SUPERSTEP_K, supersteps=SUPERSTEPS,
                     first_dispatch_s=round(ss_first_s, 1), **ss_steady),
      step_traces=step.step_traces,
      superstep_traces=step.superstep_traces,
      loss_per_device_last=[round(float(x), 4) for x in pb_losses[-1]],
      superstep_vs_per_batch_max_abs_diff=float(
          np.abs(ss_losses[:FUSED_STEPS] - pb_losses).max()))

  # is the work on every chip? (trivially true on one)
  in_use = bytes_in_use()
  shards = len(sf.array.addressable_shards)
  check(shards == n_dev, shards, n_dev)
  check(min(in_use) > 0 and max(in_use) <= 2 * min(in_use), in_use)
  placement = dict(bytes_in_use_per_device=in_use, feature_shards=shards)
  if n_dev > 1:
    hlo = step_hlo(step, state['params'], state['opt'], seeds[0],
                   n_valid[0], keys[0])
    check('all-to-all' in hlo, 'no all-to-all in the compiled step')
    placement['all_to_all_ops_in_step_hlo'] = hlo.count('all-to-all(')
  say(phase='placement', **placement)


def main():
  import jax  # first JAX contact of the process
  if jax.default_backend() != 'tpu':
    sys.exit(f'chip_smoke: default backend is {jax.default_backend()!r},'
             ' not tpu; this check has no CPU mode')
  root = os.path.dirname(os.path.abspath(__file__))
  sys.path.insert(0, os.path.join(root, 'examples'))
  import importlib.metadata as md
  import numpy as np
  from glt_tpu.utils.backend import configure_compile_cache
  from common import synthetic_products

  t_start = time.perf_counter()
  dev = jax.devices()[0]
  device = {'platform': dev.platform, 'kind': dev.device_kind,
            'count': len(jax.devices())}
  say(phase='start', device=device,
      local_device_count=jax.local_device_count(),
      versions={p: md.version(p) for p in ('jax', 'jaxlib', 'libtpu')},
      compile_cache_dir=configure_compile_cache())

  t0 = time.perf_counter()
  ds, num_classes = synthetic_products(num_nodes=NUM_NODES)
  say(phase='dataset', num_nodes=ds.get_graph().num_nodes,
      num_edges=ds.get_graph().num_edges,
      build_s=round(time.perf_counter() - t0, 1))

  loader_phase(ds, num_classes)
  # hand the table over: the loader path's resident copy (on chip 0)
  # goes, and the same rows are sharded over the mesh. The sampler and
  # its compiled programs form a reference cycle; collecting it now
  # makes the per-chip memory report below about the fused trainer.
  feats = np.asarray(ds.get_node_feature().device_part)
  ds.node_features = None
  gc.collect()
  say(phase='handover', bytes_in_use_per_device=bytes_in_use())
  fused_phase(ds, feats, num_classes)

  say(phase='done', total_s=round(time.perf_counter() - t_start, 1))
  print(json.dumps({'ok': True, 'device': device}), flush=True)


if __name__ == '__main__':
  main()
