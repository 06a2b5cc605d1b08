"""Driver ``fused``: ``SPMDSageTrainStep.__call__`` over the cell's chips,
one dispatch per step, as ``chip_smoke.py::fused_phase`` drives it.

``build`` makes the data and the trainer from the seed; ``start`` takes the
first ``warmup_steps`` steps through ``step``, the window's own call and
feed; those steps compile the cell's one program, and their losses, the
optimizer's state after the first and the parameters after the last are
the program's side of ``correct``. ``verify`` frees the device and lets
``chipbench/reference.py`` follow the same steps.
"""
import gc
import time
import types

import numpy as np

from chipbench import graphgen, reference

MAX_STEPS = 2048   # batches drawn from the seed; the feed wraps after them


def build(cfg, traffic, chips, seed):
  import jax
  import optax
  from glt_tpu.data import Graph
  from glt_tpu.models import GraphSAGE
  from glt_tpu.parallel import (ShardedFeature, SPMDSageTrainStep,
                                make_mesh)
  _watch_compiles()
  s = types.SimpleNamespace()
  s.parts, mark = {}, time.perf_counter()

  def part(name):
    nonlocal mark
    s.parts[name] = time.perf_counter() - mark
    mark = time.perf_counter()

  s.cfg, s.traffic, s.chips = cfg, traffic, chips
  s.fanout, s.batch = list(traffic['fanout']), traffic['batch_per_chip']
  n = cfg['num_nodes']
  s.indptr, s.indices = graphgen.csr(n, cfg['num_edges'], seed)
  part('graph_s')
  s.feats = graphgen.Features(n, cfg['feature_dim'], cfg['num_classes'],
                              seed)
  table = s.feats.table()
  part('features_s')
  mesh = make_mesh(chips)
  s.tx = optax.adam(cfg['learning_rate'])
  model = GraphSAGE(hidden_features=cfg['hidden_dim'],
                    out_features=cfg['num_classes'],
                    num_layers=cfg['num_layers'])
  feature = ShardedFeature(table, mesh)
  del table
  jax.block_until_ready(feature.array)
  part('feature_upload_s')
  s.rows_per_shard = feature.rows_per_shard
  graph = Graph(graphgen.SortedCSR(s.indptr, s.indices, n))
  s.trainer = SPMDSageTrainStep(
      mesh, model, s.tx, graph, feature, s.feats.labels(),
      fanouts=s.fanout, batch_size_per_device=s.batch)
  jax.block_until_ready((s.trainer._indices, s.trainer.labels))
  part('trainer_s')
  start(s, seed)
  part('warm_up_s')
  return s


def start(s, seed):
  """Seeds, keys and weights from ``seed``, then the warm-up steps. The
  graph and the trainer stay, so a calibration can start many times."""
  import jax
  cfg, chips, n = s.cfg, s.chips, s.cfg['num_nodes']
  per_step = chips * s.batch
  steps = min(MAX_STEPS, n // per_step)
  rng = np.random.default_rng([int(seed), 4])
  s.seeds = rng.permutation(n)[:steps * per_step].astype(np.int32).reshape(
      steps, per_step)
  s.keys = jax.random.split(graphgen.jax_key(seed, 1), (steps, chips))
  s.n_valid = np.full((chips,), s.batch, np.int32)
  s.params0 = graphgen.weights(seed, cfg['feature_dim'], cfg['hidden_dim'],
                               cfg['num_classes'], cfg['num_layers'])
  s.params, s.opt = s.params0, s.tx.init(s.params0)
  losses, first_opt = [], None
  for t in range(s.traffic['warmup_steps']):
    losses.append(np.asarray(step(s, t)))
    first_opt = s.opt if first_opt is None else first_opt
  first_grad = jax.tree.map(lambda m: np.asarray(m) / (1 - reference.B1),
                            first_opt[0].mu)
  host = lambda tree: jax.tree.map(np.asarray, tree)
  s.replicas = _replica_gap(s.params, losses)
  s.program = reference.readings([l[0] for l in losses], first_grad,
                                 host(s.params0), host(s.params))
  s.params0 = host(s.params0)
  s.compiled_before = compilations(s)


def feed(s, t):
  t %= s.seeds.shape[0]
  return s.seeds[t], s.keys[t]


def step(s, t):
  """Dispatch step ``t``; returns the loss, still on the device."""
  import jax
  with jax.profiler.TraceAnnotation('chipbench.dispatch'):
    seeds, keys = feed(s, t)
    s.params, s.opt, loss = s.trainer(s.params, s.opt, seeds, s.n_valid,
                                      keys)
  return loss


def _replica_gap(params, losses):
  """What ``pmean`` leaves on every chip: the widest difference between
  two chips' copies of a parameter or of a step's loss. Exact: 0."""
  import jax
  gap = max(float(np.ptp(l)) for l in losses)
  for leaf in jax.tree.leaves(params):
    copies = [np.asarray(sh.data) for sh in leaf.addressable_shards]
    gap = max([gap] + [float(np.abs(c - copies[0]).max())
                       for c in copies[1:]])
  return gap


_COMPILES = []   # every compilation JAX reports in this process


def _watch_compiles():
  import jax
  if not _COMPILES:
    _COMPILES.append(0)
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: _COMPILES.append(name)
        if name.endswith('backend_compile_duration') else None)


def compilations(s):
  """How often anything was traced or compiled so far, by four counts:
  the trainer's own, the program's counter, the step's jit cache and
  JAX's compile events. ``verify`` compares it with what it was when the
  window opened."""
  from glt_tpu.obs.perf import compile_counts
  return (s.trainer.step_traces + s.trainer._step_fn._cache_size()
          + sum(compile_counts().values()) + len(_COMPILES))


def verify(s):
  """{name: (value, limit)} of every number compared. Frees the device
  first: the reference runs where the program's state was."""
  compiled = compilations(s) - s.compiled_before
  s.trainer = s.params = s.opt = None
  gc.collect()
  steps = s.traffic['warmup_steps']
  ref = reference.follow(
      s.indptr, s.indices, s.feats, s.params0, lambda t: feed(s, t), steps,
      s.chips, s.fanout, s.cfg['learning_rate'])
  gaps = reference.compare(s.program, ref)
  limits = s.cfg['limits']
  out = {k: (v, limits[k]) for k, v in gaps.items()}
  out['replica_gap'] = (s.replicas, 0.0)
  out['compilations'] = (compiled, 0)
  return out
