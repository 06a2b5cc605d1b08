"""Driver ``hetero_fused``: ``DistHeteroTrainStep.__call__`` over the
cell's chips, one dispatch per step: typed sampling, per-type dedup and
gather, R-GAT forward and backward and Adam in one device program.

``build`` makes the model first and reads the step's static counters
before anything is dispatched, so a program without the typed step's
``head``, ``scope_profile`` or budgets fails at once and cleanly. ``start``
takes the first ``warmup_steps`` steps through ``step``, the window's own
call and feed; their losses, the optimizer's state after the first and the
parameters after the last are the program's side of ``correct``.

The reference does not follow the program's random stream. Instead the
step hands back the structure it sampled and trained on
(``keep_sample``: the warm-up batches' nodes and edges are outputs of the
step's own program, so no second program is compiled); ``verify`` checks
every sampled edge against the CSR and the fanout, and
``chipbench/reference_rgat.py`` computes loss, first gradient and the
parameters' change on those batches. Its batches are padded to shapes
that follow from the budgets alone, so its program is compiled on a
thread of its own while the step's is (``_compile_reference``): a minute
of ``verify`` that a cold run no longer waits for.
"""
import gc
import sys
import threading
import time
import types

import numpy as np

from chipbench import graphgen, graphgen_hetero, reference_rgat
# the fused driver's count of JAX's compile events serves both drivers
from chipbench.drivers import fused as fused_driver

MAX_STEPS = 2048   # batches drawn from the seed; the feed wraps after them


def _fail(why):
  sys.exit(f'chipbench: hetero_fused: {why}')


def relations(cfg):
  """Stored (traversal) relations, and the message-flow keys the model's
  parameters are named by, in the configuration's order."""
  from glt_tpu.typing import reverse_edge_type
  stored = [(r['src'], r['name'], r['dst']) for r in cfg['relations']]
  return stored, [reverse_edge_type(e) for e in stored]


def make_model(cfg, stored_flow):
  from glt_tpu.models import RGNN
  try:
    return RGNN(edge_types=stored_flow, hidden_features=cfg['hidden_dim'],
                out_features=cfg['num_classes'],
                num_layers=cfg['num_layers'], conv='rgat',
                heads=cfg['heads'], dropout=0.0, head=True,
                remat=cfg['remat_relations'])
  except TypeError as e:
    _fail(f'this program\'s RGNN cannot be built as the configuration '
          f'asks ({e})')


def build(cfg, traffic, chips, seed):
  import jax
  import jax.numpy as jnp
  import optax
  from glt_tpu.distributed import (DistFeature, DistHeteroGraph,
                                   DistHeteroTrainStep)
  from glt_tpu.parallel import make_mesh
  if chips != 1:
    _fail('one chip only: the typed stores are built as one partition')
  if not hasattr(DistHeteroTrainStep, 'scope_profile'):
    _fail('this program\'s DistHeteroTrainStep has no scope_profile and '
          'no static budgets; the cell needs both')
  fused_driver._watch_compiles()
  s = types.SimpleNamespace()
  s.parts, mark = {}, time.perf_counter()

  def part(name):
    nonlocal mark
    s.parts[name] = time.perf_counter() - mark
    mark = time.perf_counter()

  s.cfg, s.traffic, s.chips = cfg, traffic, chips
  s.fanout, s.batch = list(traffic['fanout']), traffic['batch_per_chip']
  s.seed_type = traffic['seed_type']
  s.stored, s.flow = relations(cfg)
  model = make_model(cfg, s.flow)
  counts = cfg['num_nodes']
  s.csr = graphgen_hetero.graph(cfg, seed)
  part('graph_s')
  s.feats = graphgen_hetero.Features(counts, cfg['feature_dim'],
                                     cfg['num_classes'], seed)
  part('features_s')
  mesh = make_mesh(chips)
  book = {t: np.zeros(n, np.int32) for t, n in counts.items()}
  stores = {}
  for t, n in counts.items():
    stores[t] = DistFeature(mesh, [(s.feats.table(t), np.arange(n))],
                            book[t], n)
    assert stores[t].array.dtype == jnp.bfloat16, stores[t].array.dtype
  jax.block_until_ready([st.array for st in stores.values()])
  part('feature_upload_s')
  graph = DistHeteroGraph.from_csr(mesh, counts, s.csr)
  s.tx = optax.adam(cfg['learning_rate'])
  s.trainer = DistHeteroTrainStep(
      graph, stores, model, s.tx,
      {s.seed_type: s.feats.labels(s.seed_type)},
      {e: s.fanout for e in s.stored}, batch_size_per_device=s.batch,
      seed_type=s.seed_type, seed=0, keep_sample=True)
  s.node_budget = dict(s.trainer.node_budget)
  s.edge_budget = dict(s.trainer.edge_budget)
  print('chipbench: hetero_fused: node_budget '
        f'{s.node_budget}; edge slots {sum(s.edge_budget.values())}',
        file=sys.stderr)
  jax.block_until_ready([g.indices for g in graph.graphs.values()])
  part('trainer_s')
  s.reference = {}
  s.reference_thread = threading.Thread(target=_compile_reference,
                                        args=(s,), daemon=True)
  s.reference_thread.start()
  start(s, seed)
  part('warm_up_s')
  return s


# The share of its slots that the ahead-compiled reference is sized for. A
# batch fills about a tenth of them (a numpy walk of the cell's graph: 45 to
# 48 thousand papers of 481,024 slots, 92 to 98 thousand edges of
# 1,588,800), and the reference at the full budgets would not fit the chip.
AHEAD_SHARE = 0.25


def reference_shapes(s, batches=None):
  """Rows a type and edges a relation of the reference's padded batches,
  one row more than is real for the padding's own. Without ``batches``,
  what the ahead-compiled program is sized for: ``AHEAD_SHARE`` of the
  budgets (and no more rows than a type has); with them, what they need."""
  if batches is not None:
    rows = {t: max(b['nodes'][t].shape[0] for b in batches) + 1
            for t in batches[0]['nodes']}
    return rows, {e: max(b['edges'][e][0].shape[0] for b in batches)
                  for e in batches[0]['edges']}
  share = lambda n: int(np.ceil(n * AHEAD_SHARE))
  rows = {t: min(share(b), s.cfg['num_nodes'][t]) + 1
          for t, b in s.node_budget.items()}
  return rows, {e: share(n) for e, n in s.edge_budget.items()}


def fits(s, shapes):
  """Whether every warm-up batch goes into batches of ``shapes``."""
  rows, slots = shapes
  return all(b['nodes'][t].shape[0] < rows[t] for b in s.sampled
             for t in rows) and all(
                 b['edges'][e][0].shape[0] <= slots[e] for b in s.sampled
                 for e in slots)


def _compile_reference(s):
  """On its own thread, beside the step's compilation: the reference's
  program for ``reference_shapes``. Where it fails, ``verify`` says why
  and has the reference compile as it would without."""
  import jax
  cfg = s.cfg
  try:
    rows, slots = reference_shapes(s)
    sds = jax.ShapeDtypeStruct
    f = cfg['hidden_dim'] // cfg['heads']
    tree = {}
    for i in range(cfg['num_layers']):
      a = cfg['feature_dim'] if i == 0 else cfg['hidden_dim']
      tree[f'layer{i}'] = {reference_rgat.relation_name(e): {
          'proj': {'kernel': sds((a, cfg['hidden_dim']), np.float32)},
          'att_src': sds((cfg['heads'], f), np.float32),
          'att_dst': sds((cfg['heads'], f), np.float32)} for e in s.flow}
    tree['head'] = {
        'kernel': sds((cfg['hidden_dim'], cfg['num_classes']), np.float32),
        'bias': sds((cfg['num_classes'],), np.float32)}
    s.reference['program'] = reference_rgat.compiled(
        {'params': tree},
        {t: sds((n, cfg['feature_dim']), np.float32)
         for t, n in rows.items()},
        {e: (sds((n,), np.int32), sds((n,), np.int32))
         for e, n in slots.items()},
        sds((s.batch,), np.int32), seed_type=s.seed_type,
        num_layers=cfg['num_layers'], heads=cfg['heads'])
  except Exception as e:   # told by verify
    s.reference['error'] = e


def start(s, seed):
  """Seeds, keys and weights from ``seed``, then the warm-up steps and the
  sampler's account of them. The graph and the trainer stay, so a
  calibration can start many times."""
  import jax
  cfg = s.cfg
  n = cfg['num_nodes'][s.seed_type]
  steps = min(MAX_STEPS, n // s.batch)
  rng = np.random.default_rng([int(seed), 4])
  s.seeds = rng.permutation(n)[:steps * s.batch].astype(np.int32).reshape(
      steps, s.batch)
  s.keys = jax.random.split(graphgen.jax_key(seed, 1), steps)
  s.n_valid = np.full((1,), s.batch, np.int32)
  s.params0 = graphgen_hetero.weights(
      seed, s.flow, cfg['feature_dim'], cfg['hidden_dim'], cfg['heads'],
      cfg['num_classes'], cfg['num_layers'])
  s.params, s.opt = s.params0, s.tx.init(s.params0)
  losses, first_opt, s.sampled = [], None, []
  for t in range(s.traffic['warmup_steps']):
    losses.append(np.asarray(step(s, t)))
    s.sampled.append(sampled(s, t))
    first_opt = s.opt if first_opt is None else first_opt
  first_grad = jax.tree.map(
      lambda m: np.asarray(m) / (1 - reference_rgat.B1), first_opt[0].mu)
  host = lambda tree: jax.tree.map(np.asarray, tree)
  s.program = reference_rgat.readings([l[0] for l in losses], first_grad,
                                      host(s.params0), host(s.params))
  s.params0 = host(s.params0)
  s.reference_thread.join()    # nothing compiles once the window is open
  s.compiled_before = compilations(s)


def feed(s, t):
  t %= s.seeds.shape[0]
  return s.seeds[t], s.keys[t]


def step(s, t):
  """Dispatch step ``t``; returns the loss, still on the device."""
  import jax
  with jax.profiler.TraceAnnotation('chipbench.dispatch'):
    seeds, key = feed(s, t)
    s.params, s.opt, loss = s.trainer(s.params, s.opt, seeds, s.n_valid,
                                      key)
  return loss


def sampled(s, t):
  """The batch that step ``t``, just taken, sampled and trained on, on
  the host: per type the global ids (real rows only), per message-flow
  relation the (child, parent) labels of the real edges."""
  seeds, _ = feed(s, t)
  out = s.trainer.last_sample
  count = {k: int(np.asarray(v)[0]) for k, v in out['node_count'].items()}
  nodes = {k: np.asarray(v)[0][:count[k]] for k, v in out['node'].items()}
  edges = {}
  for e in out['row']:
    ok = np.asarray(out['edge_mask'][e])[0]
    edges[e] = (np.asarray(out['row'][e])[0][ok],
                np.asarray(out['col'][e])[0][ok])
  return {'seeds': seeds, 'nodes': nodes, 'edges': edges}


def _edge_keys(s, stored, width):
  """``parent * width + child`` of every edge of a stored relation,
  ascending; made once a relation."""
  cache = vars(s).setdefault('edge_keys', {})
  if stored not in cache:
    indptr, indices = s.csr[stored]   # parent -> child
    cache[stored] = np.repeat(
        np.arange(indptr.shape[0] - 1, dtype=np.int64),
        np.diff(indptr)) * width + indices
  return cache[stored]


def check_sample(s, batch):
  """Every sampled edge is an edge of the graph, no parent holds more
  children within a relation and hop than the fanout allows, the seeds
  lead their type, and no type's rows repeat or pass its budget. Returns
  the number of violations."""
  from glt_tpu.typing import reverse_edge_type
  bad = int(not np.array_equal(batch['nodes'][s.seed_type][:s.batch],
                               batch['seeds']))
  for t, ids in batch['nodes'].items():
    bad += int(np.unique(ids).shape[0] != ids.shape[0])
    bad += int(ids.shape[0] > s.node_budget[t])
  for flow, (child, parent) in batch['edges'].items():
    src_t, _, dst_t = flow          # child's type, parent's type
    width = s.cfg['num_nodes'][src_t]
    p = batch['nodes'][dst_t][parent].astype(np.int64)
    c = batch['nodes'][src_t][child]
    # membership: (p, c) among the graph's edges, which ascend row by row
    flat = _edge_keys(s, reverse_edge_type(flow), width)
    at = np.minimum(np.searchsorted(flat, p * width + c),
                    flat.shape[0] - 1)
    bad += int((flat[at] != p * width + c).sum())
    bad += int(parent.shape[0] > s.edge_budget[flow])
    # a parent is expanded once, in one hop, so its children within the
    # relation number at most the largest fanout
    bad += int((np.bincount(parent).max(initial=0) > max(s.fanout)))
  return bad


def compilations(s):
  """How often anything was traced or compiled so far: the trainer's own
  count, the program's counter, the step's jit cache and JAX's compile
  events. ``verify`` compares it with what it was when the window
  opened."""
  from glt_tpu.obs.perf import compile_counts
  return (s.trainer.step_traces + s.trainer._step_fn.jitted._cache_size()
          + sum(compile_counts().values()) + len(fused_driver._COMPILES))


def reference_batches(s, dtype=np.float32):
  """The warm-up batches as the reference takes them, one at a time:
  feature rows made again from the seed, and all padded to one shape by
  an isolated component (a row of nought that only its own edges reach),
  so that the reference compiles once: the ahead-compiled program's shape
  where every batch goes into it, else the least that holds them."""
  ahead = reference_shapes(s)
  top_n, top_e = ahead if fits(s, ahead) else reference_shapes(s, s.sampled)
  for b in s.sampled:
    x = {}
    for t, ids in b['nodes'].items():
      rows = np.zeros((top_n[t], s.cfg['feature_dim']), dtype)
      rows[:ids.shape[0]] = s.feats.rows(t, ids)
      x[t] = rows
    edges = {}
    for e, (child, parent) in b['edges'].items():
      pad = top_e[e] - child.shape[0]
      edges[e] = (
          np.concatenate([child, np.full(pad, top_n[e[0]] - 1, np.int32)]),
          np.concatenate([parent, np.full(pad, top_n[e[2]] - 1, np.int32)]))
    yield {'x': x, 'edges': edges, 'seed_type': s.seed_type,
           'y': s.feats.labels(s.seed_type, b['seeds']).astype(np.int32)}


def verify(s):
  """{name: (value, limit)} of every number compared. Frees the device
  first: the reference runs where the program's state was."""
  compiled = compilations(s) - s.compiled_before
  s.trainer = s.params = s.opt = None
  gc.collect()
  cfg = s.cfg
  t0 = time.perf_counter()
  bad = sum(check_sample(s, b) for b in s.sampled)
  t1 = time.perf_counter()
  # the program compiled ahead, where it was and the batches go into it;
  # else the reference compiles now, for the shape they need
  program = s.reference.get('program')
  if program is None or not fits(s, reference_shapes(s)):
    print('chipbench: hetero_fused: verify: the reference compiles now ('
          f"{s.reference.get('error', 'a batch passes the ahead shape')})",
          file=sys.stderr)
    program = None
  ref, _, _ = reference_rgat.follow(
      s.params0, reference_batches(s), cfg['num_layers'], cfg['heads'],
      cfg['learning_rate'], program=program)
  print(f'chipbench: hetero_fused: verify: sample check {t1 - t0:.1f} s, '
        f'reference {time.perf_counter() - t1:.1f} s', file=sys.stderr)
  gaps = reference_rgat.compare(s.program, ref)
  floor = float(np.median(list(ref['grad'].values())))
  leaf = max(ref['grad'], key=lambda k: abs(
      s.program['grad'][k] - ref['grad'][k]) / max(ref['grad'][k], floor))
  print(f'chipbench: hetero_fused: grad_gap is leaf {leaf}, norm '
        f"{ref['grad'][leaf] / floor:.2f} of the median leaf's",
        file=sys.stderr)
  out = {k: (v, cfg['limits'][k]) for k, v in gaps.items()}
  out['sample_violations'] = (bad, 0)
  out['compilations'] = (compiled, 0)
  return out
