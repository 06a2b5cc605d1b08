"""Driver ``hgt_fused``: ``DistHeteroTrainStep.__call__`` over one chip,
one dispatch per step, with the Heterogeneous Graph Transformer as the
model: the typed sampling, per-type dedup and gather of the
``hetero_fused`` driver, HGT's forward and backward and Adam in one device
program.

What serves both typed models is that driver's, by import: the
relations' two namings, the feed, the dispatch, the sample the step hands
back (``keep_sample``) and its check against the CSR, the count of
compilations, the reference's padded batches. What is closed over
``RGNN``'s parameter tree there is carried here: the model, the weights,
``start`` (its readings are the HGT reference's), ``verify`` and the
reference's ahead-compilation: its batches are padded to shapes that
follow from the budgets alone, so ``chipbench/reference_hgt.py``'s
program is compiled on a thread of its own while the step's is, two
minutes of ``verify`` that a cold run does not wait for.

``build`` makes the model first and reads the step's static counters
before anything is dispatched, so a program without ``HGT``'s plan, or
whose typed step has no ``scope_profile`` or budgets, fails at once and
cleanly.
"""
import gc
import sys
import threading
import time
import types

import numpy as np

from chipbench import graphgen, graphgen_hetero, reference_hgt
from chipbench.drivers import fused as fused_driver
from chipbench.drivers.hetero_fused import (MAX_STEPS, check_sample,
                                            compilations, feed, fits,
                                            reference_batches,
                                            reference_shapes, relations,
                                            sampled, step)

__all__ = ['build', 'step', 'verify', 'start', 'feed', 'compilations']


def _fail(why):
  sys.exit(f'chipbench: hgt_fused: {why}')


def make_model(cfg, flow):
  try:
    from glt_tpu.models import HGT
    model = HGT(node_types=list(cfg['num_nodes']), edge_types=flow,
                hidden_features=cfg['hidden_dim'],
                out_features=cfg['num_classes'],
                num_layers=cfg['num_layers'], heads=cfg['heads'],
                remat=cfg['remat_relations'])
  except (ImportError, TypeError) as e:
    _fail(f'this program\'s HGT cannot be built as the configuration '
          f'asks ({e})')
  if not hasattr(model, 'layer_joint_relations'):
    _fail('this program\'s HGT has no plan of its layers (no trim, no '
          'groups, no joint softmax over parent-major slots)')
  return model


def leaves(cfg, flow):
  """``[(path, shape, scale, centre)]`` of ``HGT``'s parameter tree in
  the configuration's order: kernels normal with variance 1 / fan_in,
  biases at a tenth, ``A_r`` and ``M_r`` with variance 1 / d (glorot's),
  the priors and the skips a tenth around their initial 1."""
  hidden, heads = cfg['hidden_dim'], cfg['heads']
  d, out = hidden // heads, []
  dense = lambda at, a, b: [(at + ('kernel',), (a, b), a ** -0.5, 0.0),
                            (at + ('bias',), (b,), 0.1, 0.0)]
  for t in cfg['num_nodes']:
    out += dense((f'in_{t}',), cfg['feature_dim'], hidden)
  for i in range(cfg['num_layers']):
    for t in cfg['num_nodes']:
      for n in 'kqva':
        out += dense((f'layer{i}', f'{n}_{t}'), hidden, hidden)
      out.append(((f'layer{i}', f'skip_{t}'), (), 0.1, 1.0))
    for e in flow:
      name = reference_hgt.relation_name(e)
      out += [((f'layer{i}', 'watt_' + name), (heads, d, d), d ** -0.5, 0.0),
              ((f'layer{i}', 'wmsg_' + name), (heads, d, d), d ** -0.5, 0.0),
              ((f'layer{i}', 'prior_' + name), (heads,), 0.1, 1.0)]
  return out + dense(('head',), hidden, cfg['num_classes'])


def tree_of(cfg, flow, flat):
  """``{'params': tree}`` from one flat vector of unit normals (numpy or
  jax), cut into ``leaves``."""
  tree, lo = {}, 0
  for path, shape, scale, centre in leaves(cfg, flow):
    n = int(np.prod(shape, dtype=np.int64))
    node = tree
    for k in path[:-1]:
      node = node.setdefault(k, {})
    node[path[-1]] = flat[lo:lo + n].reshape(shape) * scale + centre
    lo += n
  return {'params': tree}


def num_weights(cfg, flow):
  return sum(int(np.prod(shape, dtype=np.int64))
             for _, shape, _, _ in leaves(cfg, flow))


def weights(seed, cfg, flow):
  """The model's weights from ``seed``, made on the device in one jitted
  call from ONE normal draw (``graphgen_hetero.weights``'s reason: a draw
  a leaf is a hundred unrolled Threefry programs)."""
  import jax
  import jax.numpy as jnp
  size = num_weights(cfg, flow)
  make = jax.jit(lambda key: tree_of(
      cfg, flow, jax.random.normal(key, (size,), jnp.float32)))
  return make(graphgen.jax_key(seed, 0))


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
  print('chipbench: hgt_fused: node_budget '
        f'{s.node_budget}; edge slots {sum(s.edge_budget.values())}',
        file=sys.stderr)
  jax.block_until_ready([g.indices for g in graph.graphs.values()])
  part('trainer_s')
  s.reference = {}
  s.reference_thread = threading.Thread(target=_compile_reference,
                                        args=(s,), daemon=True)
  s.reference_thread.start()
  start(s, seed)
  print('chipbench: hgt_fused: layer_groups '
        f'{[sum(g.values()) for g in s.trainer.layer_groups]}; '
        f'joint softmax {s.trainer.layer_joint_relations}',
        file=sys.stderr)
  part('warm_up_s')
  return s


def _compile_reference(s):
  """On its own thread, beside the step's compilation: the reference's
  program for ``hetero_fused.reference_shapes``, at the precision the
  configuration states. Where it fails, ``verify`` says why and has the
  reference compile as it would without."""
  import jax
  cfg = s.cfg
  try:
    rows, slots = reference_shapes(s)
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: tree_of(cfg, s.flow, np.zeros(
        num_weights(cfg, s.flow), np.float32)))
    s.reference['program'] = reference_hgt.compiled(
        params,
        {t: sds((n, cfg['feature_dim']), np.float32)
         for t, n in rows.items()},
        {e: (sds((n,), np.int32), sds((n,), np.int32))
         for e, n in slots.items()},
        sds((s.batch,), np.int32), seed_type=s.seed_type,
        num_layers=cfg['num_layers'], heads=cfg['heads'],
        operands=stated_operands(cfg))
  except Exception as e:   # told by verify
    s.reference['error'] = e


def start(s, seed):
  """Seeds, keys and weights from ``seed``, then the warm-up steps and the
  sampler's account of them (``hetero_fused.start`` with this model's
  weights and this reference's readings). The graph and the trainer
  stay, so a calibration can start many times."""
  import jax
  cfg = s.cfg
  n = cfg['num_nodes'][s.seed_type]
  steps = min(MAX_STEPS, n // s.batch)
  rng = np.random.default_rng([int(seed), 4])
  s.seeds = rng.permutation(n)[:steps * s.batch].astype(np.int32).reshape(
      steps, s.batch)
  s.keys = jax.random.split(graphgen.jax_key(seed, 1), steps)
  s.n_valid = np.full((1,), s.batch, np.int32)
  s.params0 = weights(seed, cfg, s.flow)
  s.params, s.opt = s.params0, s.tx.init(s.params0)
  losses, first_opt, s.sampled = [], None, []
  for t in range(s.traffic['warmup_steps']):
    losses.append(np.asarray(step(s, t)))
    s.sampled.append(sampled(s, t))
    first_opt = s.opt if first_opt is None else first_opt
  first_grad = jax.tree.map(
      lambda m: np.asarray(m) / (1 - reference_hgt.B1), first_opt[0].mu)
  host = lambda tree: jax.tree.map(np.asarray, tree)
  s.program = reference_hgt.readings([l[0] for l in losses], first_grad,
                                     host(s.params0), host(s.params))
  s.params0 = host(s.params0)
  s.reference_thread.join()    # nothing compiles once the window is open
  s.compiled_before = compilations(s)


def follow(s, **kw):
  """The reference's readings on the warm-up batches of the last
  ``start``, at the precision the configuration states unless ``kw``
  says otherwise."""
  cfg = s.cfg
  kw.setdefault('operands', stated_operands(cfg))
  return reference_hgt.follow(
      s.params0, reference_batches(s), cfg['num_layers'], cfg['heads'],
      cfg['learning_rate'], **kw)[0]


def stated_operands(cfg):
  """What the configuration's precision rounds a matmul's operands to on
  the backend at hand: float32 at the default precision is bfloat16
  operands on a TPU and nothing on a CPU."""
  assert (cfg['dtype'], cfg['matmul_precision']) == ('float32', 'default')
  return reference_hgt.default_operands()


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
    print('chipbench: hgt_fused: verify: the reference compiles now ('
          f"{s.reference.get('error', 'a batch passes the ahead shape')})",
          file=sys.stderr)
    program = None
  ref = follow(s, program=program)
  print(f'chipbench: hgt_fused: verify: sample check {t1 - t0:.1f} s, '
        f'reference {time.perf_counter() - t1:.1f} s', file=sys.stderr)
  gaps = reference_hgt.compare(s.program, ref)
  size = {k: float(np.linalg.norm(a)) for k, a in ref['grad'].items()}
  floor = float(np.median(list(size.values())))
  leaf = max(size, key=lambda k: float(np.linalg.norm(
      s.program['grad'][k] - ref['grad'][k])) / max(size[k], floor))
  print(f'chipbench: hgt_fused: grad_gap is leaf {leaf}, norm '
        f'{size[leaf] / floor:.2f} of the median leaf\'s', file=sys.stderr)
  out = {k: (v, cfg['limits'][k]) for k, v in gaps.items()}
  out['sample_violations'] = (bad, 0)
  out['compilations'] = (compiled, 0)
  return out
