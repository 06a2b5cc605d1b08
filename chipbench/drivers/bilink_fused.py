"""Driver ``bilink_fused``: ``DistHeteroTrainStep.__call__`` seeded by
typed edges, over one chip, one dispatch per step: user-item link
prediction over learnable id embeddings (upstream's
``bipartite_sage_unsup`` recipe). A seed is a positive ``(user, item)``
edge; its negatives are drawn inside the step's program; the two
embedding tables are parameters of the model under dense Adam.

``build`` makes the graph and the trainer from the seed; ``start`` takes
the first ``warmup_steps`` steps through ``step``, the window's own call
and feed: they compile the cell's one program. The step's program owns
its state (it donates ``params`` and ``opt_state``), so ``start`` copies
what ``correct`` reads of a state before the next step consumes it: each
step's loss, the first gradient (Adam's first moment after one step; the
tables' as float32 on the host), the tables' rows that the first batch
touched as they stand after the first and after the second step, and the
parameters after the last.

``verify`` does not follow the sampler's random stream. The step hands
back what it sampled and drew (``keep_sample``, ``keep_seeds``): every
sampled edge is held to the CSR and the fanout, every positive is an edge
and every unpadded negative a non-edge, the negatives and their counters
are drawn again in numpy from the step's key, the other counters are
counted again, and ``chipbench/reference_bisage.py`` computes loss, first
gradient, three dense Adam steps and the momentum rows on those batches,
on the device the trainer left, at the precision the configuration
states. The weights before the first step are made again from the seed.

Against a program without the typed link model or whose typed step takes
no edge seeds ``build`` exits nonzero before it makes anything.
"""
import gc
import inspect
import sys
import time
import types

import numpy as np

from chipbench import graphgen, graphgen_bipartite, reference_bisage
from chipbench.drivers import fused as fused_driver
from chipbench.drivers.hetero_fused import compilations

MAX_STEPS = 1024   # batches drawn from the seed; the feed wraps after them
SEED_RELATION = ('user', 'to', 'item')
TABLES = {'user': 'embed_user', 'item': 'embed_item'}


def _fail(why):
  sys.exit(f'chipbench: bilink_fused: {why}')


def flow_of(cfg):
  """Message-flow keys of the relations the encoders read: items into
  users, items into items."""
  from glt_tpu.typing import reverse_edge_type
  return (reverse_edge_type(SEED_RELATION),
          reverse_edge_type(('item', 'to', 'item')))


def make_model(cfg):
  try:
    from glt_tpu.models import BipartiteSAGE
  except ImportError as e:
    _fail(f'this program has no typed link model ({e})')
  item_user, item_item = flow_of(cfg)
  return BipartiteSAGE(num_nodes=cfg['num_nodes'], item_user=item_user,
                       item_item=item_item,
                       hidden_features=cfg['hidden_dim'],
                       out_features=cfg['out_dim'])


def build(cfg, traffic, chips, seed):
  import jax
  import optax
  from glt_tpu.distributed import DistHeteroGraph, DistHeteroTrainStep
  from glt_tpu.parallel import make_mesh
  if chips != 1:
    _fail('one chip only: the typed stores are built as one partition')
  if 'neg_sampling' not in inspect.signature(
      DistHeteroTrainStep.__init__).parameters:
    _fail('this program\'s DistHeteroTrainStep takes no neg_sampling: it '
          'cannot run a cell seeded by typed edges')
  from glt_tpu.distributed import dist_hetero
  from glt_tpu.sampler import NegativeSampling
  model = make_model(cfg)
  neg = traffic['negatives']
  assert neg['trials'] == dist_hetero.NEG_TRIALS and neg['padding'], neg
  assert cfg['embedding_dim'] == cfg['hidden_dim'], cfg
  fused_driver._watch_compiles()
  s = types.SimpleNamespace()
  s.parts, mark = {}, time.perf_counter()

  def part(name):
    nonlocal mark
    s.parts[name] = time.perf_counter() - mark
    mark = time.perf_counter()

  s.cfg, s.traffic, s.chips = cfg, traffic, chips
  s.fanout, s.batch = list(traffic['fanout']), traffic['batch_per_chip']
  assert traffic['endpoint_seeds_per_chip'] == 4 * s.batch
  s.stored = graphgen_bipartite.relations(cfg)
  assert tuple(traffic['seed_relation']) == SEED_RELATION in s.stored
  s.csr = graphgen_bipartite.graph(cfg, seed)
  part('graph_s')
  mesh = make_mesh(chips)
  graph = DistHeteroGraph.from_csr(mesh, cfg['num_nodes'], s.csr)
  s.tx = optax.adam(cfg['learning_rate'])
  s.trainer = DistHeteroTrainStep(
      graph, {}, model, s.tx, None, {e: s.fanout for e in s.stored},
      batch_size_per_device=s.batch, seed_type=SEED_RELATION, seed=0,
      keep_sample=True, keep_seeds=True,
      neg_sampling=NegativeSampling(neg['mode'], neg['amount'],
                                    neg['strict']))
  s.node_budget = dict(s.trainer.node_budget)
  s.edge_budget = dict(s.trainer.edge_budget)
  print(f'chipbench: bilink_fused: node_budget {s.node_budget}; edge '
        f'slots {sum(s.edge_budget.values())}', file=sys.stderr)
  jax.block_until_ready([g.indices for g in graph.graphs.values()])
  part('trainer_s')
  start(s, seed)
  print('chipbench: bilink_fused: layer_rows '
        f'{s.trainer.layer_rows}; layer_groups '
        f'{[sum(g.values()) for g in s.trainer.layer_groups]}',
        file=sys.stderr)
  part('warm_up_s')
  return s


def table_rows(params, ids, budget):
  """{table: rows of it at ``ids[type]``}, on the host; the ids padded to
  the type's budget so that one small program a table serves every
  seed."""
  out = {}
  for t, name in TABLES.items():
    at = np.zeros(budget[t], np.int32)
    at[:ids[t].shape[0]] = ids[t]
    table = params['params'][name]['embedding']
    out[name] = np.asarray(table[at])[:ids[t].shape[0]]
  return out


def start(s, seed):
  """Pairs, keys and weights from ``seed``, then the warm-up steps and
  what ``correct`` reads of them. The graph and the trainer stay, so a
  calibration can start many times."""
  import jax
  cfg = s.cfg
  indptr, indices = s.csr[SEED_RELATION]
  steps = min(MAX_STEPS, indices.shape[0] // s.batch)
  rng = np.random.default_rng([int(seed), 4])
  s.pairs = graphgen_bipartite.positive_edges(
      indptr, indices, rng, steps * s.batch).reshape(steps, s.batch, 2)
  s.keys = jax.random.split(graphgen.jax_key(seed, 1), steps)
  s.n_valid = np.full((1,), s.batch, np.int32)
  s.seed = seed
  s.params = graphgen_bipartite.weights(seed, cfg)
  s.opt = s.tx.init(s.params)
  losses, first_grad, s.sampled, s.counted, rows = [], None, [], [], []
  for t in range(s.traffic['warmup_steps']):
    losses.append(np.asarray(step(s, t)))
    s.sampled.append(sampled(s, t))
    s.counted.append(s.trainer.link_counters())
    if first_grad is None:
      first_grad = jax.tree.map(
          lambda m: np.asarray(m) / np.float32(1 - reference_bisage.B1),
          s.opt[0].mu)
      touched = s.sampled[0]['nodes']
    if t < 2:   # the first batch's rows after the first two steps
      rows.append(table_rows(s.params, touched, s.node_budget))
  # rows the first step touched and the second did not: they move in the
  # second step by the first's momentum
  s.watch, moved = {}, {}
  for ty, name in TABLES.items():
    keep = ~np.isin(touched[ty], s.sampled[1]['nodes'][ty])
    s.watch[name] = touched[ty][keep]
    moved[name] = rows[1][name][keep] - rows[0][name][keep]
  after = jax.tree.map(np.asarray, s.params)
  # the trainer holds its newest steps' counters only: the warm-up's are
  # read now, the newest of them last
  s.by_hop = {k: v[-len(losses):] for k, v in s.trainer.counters().items()}
  s.node_types = list(s.trainer.counter_node_types)
  s.edge_types = list(s.trainer.counter_edge_types)
  s.program = dict(loss=[float(l[0]) for l in losses], grad=first_grad,
                   after=after, moved=moved)
  s.compiled_before = compilations(s)


def feed(s, t):
  t %= s.pairs.shape[0]
  return s.pairs[t], s.keys[t]


def step(s, t):
  """Dispatch step ``t``; returns the loss, still on the device."""
  import jax
  with jax.profiler.TraceAnnotation('chipbench.dispatch'):
    pairs, key = feed(s, t)
    s.params, s.opt, loss = s.trainer(s.params, s.opt, pairs, s.n_valid,
                                      key)
  return loss


def sampled(s, t):
  """The batch that step ``t``, just taken, sampled and trained on, on
  the host: per type the global ids (real rows only), per message-flow
  relation the (child, parent) labels of the real edges."""
  out = s.trainer.last_sample
  count = {k: int(np.asarray(v)[0]) for k, v in out['node_count'].items()}
  nodes = {k: np.asarray(v)[0][:count[k]] for k, v in out['node'].items()}
  edges = {}
  for e in out['row']:
    ok = np.asarray(out['edge_mask'][e])[0]
    edges[e] = (np.asarray(out['row'][e])[0][ok],
                np.asarray(out['col'][e])[0][ok])
  return {'pairs': feed(s, t)[0], 'nodes': nodes, 'edges': edges}


def stated_operands(cfg):
  """What the reference's matmuls round their operands to, to compute at
  the precision ``cfg`` states."""
  assert (cfg['dtype'], cfg['matmul_precision']) == ('float32', 'default')
  return reference_bisage.default_operands()


def _edge_keys(s, stored):
  """``row * width + col`` of every edge of a stored relation, ascending;
  made once a relation."""
  cache = vars(s).setdefault('edge_keys', {})
  if stored not in cache:
    indptr, indices = s.csr[stored]
    width = s.cfg['num_nodes'][stored[2]]
    cache[stored] = (np.repeat(
        np.arange(indptr.shape[0] - 1, dtype=np.int64),
        np.diff(indptr)) * width + indices, width)
  return cache[stored]


def is_edge(s, stored, rows, cols):
  """[n] bool: is ``rows[i] -> cols[i]`` an edge of the stored relation?"""
  flat, width = _edge_keys(s, stored)
  want = np.asarray(rows, np.int64) * width + np.asarray(cols, np.int64)
  at = np.minimum(np.searchsorted(flat, want), flat.shape[0] - 1)
  return flat[at] == want


def ends_of(s, got):
  """``(users [2B], items [2B])`` of the ``[4B]`` seeds a step handed
  back: ``[src; neg_src]`` and ``[dst; neg_dst]``."""
  return np.asarray(got['seeds'][0]).reshape(2, 2 * s.batch)


def check_sample(s, batch, got):
  """Every sampled edge is an edge of the graph, no parent holds more
  children within a relation than the largest fanout, the distinct seeds
  lead their types, and no type's rows repeat or pass its budget. Returns
  the number of violations."""
  from glt_tpu.typing import reverse_edge_type
  bad = 0
  for t, ends in zip(('user', 'item'), ends_of(s, got)):
    lead = batch['nodes'][t][:np.unique(ends).shape[0]]
    bad += int(not np.array_equal(np.sort(lead), np.unique(ends)))
  for t, ids in batch['nodes'].items():
    bad += int(np.unique(ids).shape[0] != ids.shape[0])
    bad += int(ids.shape[0] > s.node_budget[t])
  for flow, (child, parent) in batch['edges'].items():
    p = batch['nodes'][flow[2]][parent]
    c = batch['nodes'][flow[0]][child]
    bad += int((~is_edge(s, reverse_edge_type(flow), p, c)).sum())
    bad += int(parent.shape[0] > s.edge_budget[flow])
    bad += int(np.bincount(parent).max(initial=0) > max(s.fanout))
  return bad


def pair_violations(s, batch, got):
  """Positives the step took that are not the feed's or no edge of the
  seed relation, plus the distance of the negatives that are edges from
  ``negatives_padded`` (an unpadded negative is a non-edge, a padded one
  carries a proposal that was an edge)."""
  users, items = ends_of(s, got)
  b = s.batch
  bad = int((np.stack([users[:b], items[:b]], 1) != batch['pairs']).sum())
  bad += int((~is_edge(s, SEED_RELATION, users[:b], items[:b])).sum())
  return bad + abs(int(is_edge(s, SEED_RELATION, users[b:], items[b:]).sum())
                   - int(got['negatives_padded'][0]))


def drawn_again(s, t):
  """The negatives of step ``t`` from the step's key, in numpy: what
  ``ops/negative.py`` draws (uniform proposals of the relation's two id
  spaces over the trials, the first round that is no edge, the last
  round's where none is). ``(users [B], items [B], rejected, padded)``."""
  import jax
  import jax.numpy as jnp
  from glt_tpu.distributed import dist_hetero
  key = jax.random.fold_in(jax.random.split(feed(s, t)[1], 1)[0], 0)
  kr, kc = jax.random.split(jax.random.split(key)[0])
  shape = (dist_hetero.NEG_TRIALS, s.batch)
  n = s.cfg['num_nodes']
  rows = np.asarray(jax.random.randint(kr, shape, 0, n['user'], jnp.int32))
  cols = np.asarray(jax.random.randint(kc, shape, 0, n['item'], jnp.int32))
  ok = ~is_edge(s, SEED_RELATION, rows.reshape(-1),
                cols.reshape(-1)).reshape(shape)
  first = np.where(ok.any(axis=0), ok.argmax(axis=0), shape[0] - 1)
  pick = lambda a: a[first, np.arange(s.batch)]
  return pick(rows), pick(cols), int((~ok).sum()), int((~ok.any(0)).sum())


def counter_gap(s, t, batch, got):
  """How far what step ``t`` counted is from the counts made again: the
  negatives and their two counters drawn again from the step's key, the
  distinct seeds, the nodes by hop against the rows handed back, the
  edges by hop against the real edges, the tables' rows read."""
  users, items = ends_of(s, got)
  b, counted = s.batch, s.by_hop
  neg_users, neg_items, rejected, padded = drawn_again(s, t)
  gap = int((users[b:] != neg_users).sum() + (items[b:] != neg_items).sum())
  gap += abs(int(got['negatives_rejected'][0]) - rejected)
  gap += abs(int(got['negatives_padded'][0]) - padded)
  unique = [np.unique(users).shape[0], np.unique(items).shape[0]]
  gap += int(np.abs(np.asarray(got['seed_unique'][0]) - unique).sum())
  by_hop = counted['nodes_by_hop'][t, 0]
  for i, ty in enumerate(s.node_types):
    gap += abs(int(by_hop[i].sum()) - batch['nodes'][ty].shape[0])
  gap += int(np.abs(by_hop[[s.node_types.index(ty)
                            for ty in ('user', 'item')], 0]
                    - unique).sum())
  for i, e in enumerate(s.edge_types):
    gap += abs(int(counted['edges_by_hop'][t, 0, i].sum())
               - batch['edges'][e][0].shape[0])
  read = dict(zip(s.node_types, counted['embedding_rows'][t, 0]))
  gap += abs(int(read['item']) - batch['nodes']['item'].shape[0])
  gap += abs(int(read['user'])
             - min(batch['nodes']['user'].shape[0], 2 * b))
  return gap


def reference_batches(s):
  """The warm-up batches as the reference takes them, all padded to one
  shape by an isolated component (a node of id 0 in a row of its own that
  only its own edges reach, so the reference compiles once): the pairs by
  their labels, found among the rows handed back."""
  item_user, item_item = flow_of(s.cfg)
  top = {t: max(b['nodes'][t].shape[0] for b in s.sampled) + 1
         for t in TABLES}
  slots = {e: max(b['edges'][e][0].shape[0] for b in s.sampled)
           for e in (item_user, item_item)}
  pad = lambda a, n, with_: np.concatenate(
      [a, np.full(n - a.shape[0], with_, a.dtype)])
  for b, got in zip(s.sampled, s.counted):
    edges = {}
    for name, e in (('item_user', item_user), ('item_item', item_item)):
      child, parent = b['edges'][e]
      edges[name] = (pad(child, slots[e], top[e[0]] - 1),
                     pad(parent, slots[e], top[e[2]] - 1))
    at = []
    for t, ends in zip(('user', 'item'), ends_of(s, got)):
      order = np.argsort(b['nodes'][t])
      # an end that is not among the rows (a step that seeded too few)
      # reads some other row: the comparison then fails, as it should
      at.append(order[np.minimum(
          np.searchsorted(b['nodes'][t][order], ends),
          order.shape[0] - 1)].astype(np.int32))
    yield {'nodes': {t: pad(b['nodes'][t], top[t], 0) for t in TABLES},
           'edges': edges, 'pairs': tuple(at),
           'y': np.repeat(np.float32([1, 0]), s.batch),
           'weight': np.ones(2 * s.batch, np.float32)}


def follow(s, weights=None, **kw):
  """The reference's readings on the warm-up batches of the last
  ``start``, from the seed's weights (made again unless given), at the
  precision the configuration states unless ``kw`` says otherwise."""
  kw.setdefault('operands', stated_operands(s.cfg))
  if weights is None:
    weights = graphgen_bipartite.weights(s.seed, s.cfg)
  return reference_bisage.follow(weights, reference_batches(s),
                                 s.cfg['learning_rate'], watch=s.watch, **kw)


def program_readings(s, weights=None):
  """The program's side as the reference's ``readings`` has it: the
  parameters' change from the seed's weights (made again unless given)."""
  p = s.program
  if weights is None:
    weights = graphgen_bipartite.weights(s.seed, s.cfg)
  return reference_bisage.readings(p['loss'], p['grad'], weights,
                                   p['after'], p['moved'])


def worst_leaf(program, ref):
  """Of two sides' readings: the small leaf whose first gradient differs
  most, its gap and its norm over the median leaf's, for the stderr."""
  norm = reference_bisage._norm
  size = {k: norm(a) for k, a in ref['grad'].items()}
  floor = float(np.median(list(size.values())))
  gap = {k: norm(np.asarray(program['grad'][k]) - np.asarray(a))
         / max(size[k], floor) for k, a in ref['grad'].items()
         if np.size(a) < 1 << 24}   # the tables' gap is compare's own
  leaf = max(gap, key=gap.get)
  return leaf, gap[leaf], size[leaf] / floor


def verify(s):
  """{name: (value, limit)} of every number compared. Frees the device
  first: the reference runs where the program's state was."""
  compiled = compilations(s) - s.compiled_before
  s.trainer = s.params = s.opt = None
  gc.collect()
  t0 = time.perf_counter()
  sample_bad = sum(check_sample(s, b, g)
                   for b, g in zip(s.sampled, s.counted))
  pair_bad = sum(pair_violations(s, b, g)
                 for b, g in zip(s.sampled, s.counted))
  gap = sum(counter_gap(s, t, b, g) for t, (b, g) in enumerate(
      zip(s.sampled, s.counted)))
  t1 = time.perf_counter()
  weights = graphgen_bipartite.weights(s.seed, s.cfg)
  ref, program = follow(s, weights), program_readings(s, weights)
  del weights
  gaps = reference_bisage.compare(program, ref)
  print(f'chipbench: bilink_fused: verify: checks {t1 - t0:.1f} s, '
        f'reference {time.perf_counter() - t1:.1f} s; worst small leaf of '
        'the gradient %s %.3g (norm %.2f of the median leaf\'s)'
        % worst_leaf(program, ref), file=sys.stderr)
  limits = dict(s.cfg['limits'], **s.cfg['table_limits'])
  out = {k: (v, limits[k]) for k, v in gaps.items()}
  out['sample_violations'] = (sample_bad, 0)
  out['negative_violations'] = (pair_bad, 0)
  out['counter_gap'] = (gap, 0)
  out['compilations'] = (compiled, 0)
  return out
