"""Driver ``link_fused``: ``SPMDSageTrainStep.__call__`` given a
``NegativeSampling``, over the cell's chips, one dispatch per step: the
link-prediction step of upstream's unsupervised-GraphSAGE recipe. A seed
is a positive edge of the graph; its negatives are drawn inside the
step's program.

``build`` makes the data and the trainer from the seed; ``start`` takes
the first ``warmup_steps`` steps through ``step``, the window's own call
and feed; those steps compile the cell's one program, and their losses,
the optimizer's state after the first, the parameters after the last and
what each step counted are the program's side of ``correct``. ``verify``
frees the device and lets ``chipbench/reference_link.py`` follow the same
steps on the program's random stream, at the precision the configuration
states (float32 whose matmuls round their operands as the backend's
default precision does): it draws the negatives and the neighbours again
in numpy, so the comparison is of the arithmetic, and the seeds and the
counters the program handed back (the step is built with ``keep_seeds``)
are held to the reference's and to the CSR (every positive an edge,
every unpadded negative a non-edge).

Against a program whose step takes no edge seeds (no ``neg_sampling``)
``build`` exits nonzero before it makes anything.
"""
import gc
import inspect
import sys
import time
import types

import numpy as np

from chipbench import graphgen, reference_link
from chipbench.drivers import fused

MAX_STEPS = 2048   # batches drawn from the seed; the feed wraps after them


def build(cfg, traffic, chips, seed):
  import jax
  import optax
  from glt_tpu.data import Graph
  from glt_tpu.models import GraphSAGE
  from glt_tpu.parallel import (ShardedFeature, SPMDSageTrainStep,
                                make_mesh)
  if 'neg_sampling' not in inspect.signature(
      SPMDSageTrainStep.__init__).parameters:
    sys.exit('chipbench: link_fused: this program\'s SPMDSageTrainStep '
             'takes no neg_sampling: it cannot run an edge-seeded cell')
  from glt_tpu.parallel import train
  from glt_tpu.sampler import NegativeSampling
  neg = traffic['negatives']
  assert neg['trials'] == train.NEG_TRIALS and neg['padding'], neg
  fused._watch_compiles()
  s = types.SimpleNamespace()
  s.parts, mark = {}, time.perf_counter()

  def part(name):
    nonlocal mark
    s.parts[name] = time.perf_counter() - mark
    mark = time.perf_counter()

  s.cfg, s.traffic, s.chips = cfg, traffic, chips
  s.fanout, s.batch = list(traffic['fanout']), traffic['batch_per_chip']
  assert traffic['endpoint_seeds_per_chip'] == 4 * s.batch
  n = cfg['num_nodes']
  s.indptr, s.indices = graphgen.csr(n, cfg['num_edges'], seed)
  part('graph_s')
  s.feats = graphgen.Features(n, cfg['feature_dim'], 2, seed)
  table = s.feats.table()
  part('features_s')
  mesh = make_mesh(chips)
  s.tx = optax.adam(cfg['learning_rate'])
  model = GraphSAGE(hidden_features=cfg['hidden_dim'],
                    out_features=cfg['out_dim'],
                    num_layers=cfg['num_layers'])
  feature = ShardedFeature(table, mesh)
  del table
  jax.block_until_ready(feature.array)
  part('feature_upload_s')
  graph = Graph(graphgen.SortedCSR(s.indptr, s.indices, n))
  s.trainer = SPMDSageTrainStep(
      mesh, model, s.tx, graph, feature, None, fanouts=s.fanout,
      batch_size_per_device=s.batch,
      neg_sampling=NegativeSampling(neg['mode'], neg['amount'],
                                    neg['strict']), keep_seeds=True)
  jax.block_until_ready(s.trainer._indices)
  part('trainer_s')
  start(s, seed)
  part('warm_up_s')
  return s


def positive_edges(indptr, indices, rng, count):
  """``[count, 2]`` int32 ``(src, dst)``: edges of the CSR drawn without
  replacement, uniformly over the edges (so a source is drawn by its
  out-degree and a destination by its in-degree)."""
  eid = rng.choice(indices.shape[0], size=count, replace=False)
  src = np.searchsorted(indptr, eid, side='right') - 1
  return np.stack([src, indices[eid]], axis=1).astype(np.int32)


def start(s, seed):
  """Pairs, keys and weights from ``seed``, then the warm-up steps. The
  graph and the trainer stay, so a calibration can start many times."""
  import jax
  cfg, chips = s.cfg, s.chips
  per_step = chips * s.batch
  steps = min(MAX_STEPS, cfg['num_edges'] // per_step)
  rng = np.random.default_rng([int(seed), 4])
  s.pairs = positive_edges(s.indptr, s.indices, rng,
                           steps * per_step).reshape(steps, per_step, 2)
  s.keys = jax.random.split(graphgen.jax_key(seed, 1), (steps, chips))
  s.n_valid = np.full((chips,), s.batch, np.int32)
  s.params0 = graphgen.weights(seed, cfg['feature_dim'], cfg['hidden_dim'],
                               cfg['out_dim'], cfg['num_layers'])
  s.params, s.opt = s.params0, s.tx.init(s.params0)
  losses, first_opt, s.counted = [], None, []
  for t in range(s.traffic['warmup_steps']):
    losses.append(np.asarray(step(s, t)))
    s.counted.append(s.trainer.link_counters())
    first_opt = s.opt if first_opt is None else first_opt
  first_grad = jax.tree.map(
      lambda m: np.asarray(m) / (1 - reference_link.B1), first_opt[0].mu)
  host = lambda tree: jax.tree.map(np.asarray, tree)
  s.program = reference_link.readings([l[0] for l in losses], first_grad,
                                      host(s.params0), host(s.params))
  s.params0 = host(s.params0)
  s.compiled_before = compilations(s)


def feed(s, t):
  t %= s.pairs.shape[0]
  return s.pairs[t], s.keys[t]


def step(s, t):
  """Dispatch step ``t``; returns the loss, still on the device."""
  import jax
  with jax.profiler.TraceAnnotation('chipbench.dispatch'):
    pairs, keys = feed(s, t)
    s.params, s.opt, loss = s.trainer(s.params, s.opt, pairs, s.n_valid,
                                      keys)
  return loss


def stated_operands(cfg):
  """What the reference's matmuls round their operands to, to compute at
  the precision ``cfg`` states."""
  assert (cfg['dtype'], cfg['matmul_precision']) == ('float32', 'default')
  return reference_link.default_operands()


# traces and compiles are counted as the node cell's driver counts them
compilations = fused.compilations


def counter_gap(counted, batches, chips):
  """How far what the program's warm-up steps handed back is from what
  the reference drew on the same keys: endpoint seeds that differ, plus
  the distances of the three counters."""
  gap = 0
  for t, got in enumerate(counted):
    for d in range(chips):
      ref = batches[t * chips + d]
      gap += int((got['seeds'][d] != ref['seeds']).sum())
      gap += abs(int(got['negatives_padded'][d]) - int(ref['padded'].sum()))
      gap += abs(int(got['negatives_rejected'][d]) - ref['rejected'])
      gap += abs(int(got['seed_unique'][d])
                 - int(np.unique(ref['seeds']).size))
  return gap


def verify(s):
  """{name: (value, limit)} of every number compared. Frees the device
  first: the reference runs where the program's state was."""
  compiled = compilations(s) - s.compiled_before
  s.trainer = s.params = s.opt = None
  gc.collect()
  steps = s.traffic['warmup_steps']
  ref = reference_link.follow(
      s.indptr, s.indices, s.feats.rows, s.params0, lambda t: feed(s, t),
      steps, s.chips, s.fanout, s.cfg['learning_rate'],
      s.cfg['num_nodes'], operands=stated_operands(s.cfg))
  gaps = reference_link.compare(s.program, ref)
  limits = s.cfg['limits']
  out = {k: (v, limits[k]) for k, v in gaps.items()}
  out['negative_violations'] = (sum(
      reference_link.pair_violations(
          s.indptr, s.indices, got['seeds'][d], got['negatives_padded'][d])
      for got in s.counted for d in range(s.chips)), 0)
  out['counter_gap'] = (counter_gap(s.counted, ref['batches'], s.chips), 0)
  out['compilations'] = (compiled, 0)
  return out
