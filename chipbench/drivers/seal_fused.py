"""Driver ``seal_fused``: ``SPMDSageTrainStep.__call__`` given a
``NegativeSampling`` and an ``EncloseSpec``, on one chip, one dispatch per
step: SEAL link prediction (enclosing subgraphs extracted, induced and
DRNL-labelled inside the step program, DGCNN with sort pooling over the
batch of graphs). A seed is a positive edge of the graph as generated;
its negative is drawn inside the step's program; the graph is read
undirected (``chipbench/graphgen_seal.py``).

``build`` makes the data and the trainer from the seed; ``start`` takes
the first ``warmup_steps`` steps through ``step``, the window's own call
and feed; those steps compile the cell's one program, and their losses,
the optimizer's state after the first, the parameters after the last and
what each step counted, drew and extracted (the step is built with
``keep_seeds`` and ``keep_sample``) are the program's side of
``correct``. ``verify`` frees the device and holds, on the host with
numpy: the node sets to the CSR and the hop's contract, each link's
block to the graph's edges among its nodes less the link, the labels to
a queue-based search, the order of each graph's readout to the
reference's own sort keys, the negatives to the CSR, every counter to a
recount; then ``chipbench/reference_seal.py`` trains the same steps on
the node sets the program drew, at the precision the configuration
states, and loss, first gradient and the parameters' change are held to
the calibrated limits.

Against a program whose step takes no ``enclose`` ``build`` exits nonzero
before it makes anything.
"""
import gc
import inspect
import sys
import time
import types

import numpy as np

from chipbench import graphgen, graphgen_seal, reference_link, reference_seal
from chipbench.drivers import fused
from chipbench.drivers.link_fused import positive_edges

MAX_STEPS = 2048   # batches drawn from the seed; the feed wraps after them
TILE = graphgen_seal.TILE


def make_spec(cfg, traffic):
  from glt_tpu.ops.subgraph import EncloseSpec
  spec = EncloseSpec(fanout=traffic['fanout'][0], max_z=cfg['max_z'],
                     **traffic['enclose'])
  assert spec.node_slots == traffic['node_slots']
  return spec


def make_model(cfg):
  from glt_tpu.models.dgcnn import DGCNN
  return DGCNN(hidden=cfg['hidden_dim'], num_layers=cfg['num_gcn_layers'],
               k=cfg['sortpool_k'],
               conv1d_channels=tuple(cfg['conv1d_channels']),
               mlp_hidden=cfg['mlp_hidden'], max_z=cfg['max_z'])


def build(cfg, traffic, chips, seed):
  import jax
  import optax
  from glt_tpu.data import Graph
  from glt_tpu.parallel import (ShardedFeature, SPMDSageTrainStep,
                                make_mesh)
  if 'enclose' not in inspect.signature(
      SPMDSageTrainStep.__init__).parameters:
    sys.exit('chipbench: seal_fused: this program\'s SPMDSageTrainStep '
             'takes no enclose: it cannot run an enclosing-subgraph cell')
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
  s.batch, s.spec = traffic['batch_per_chip'], make_spec(cfg, traffic)
  assert chips == 1 and traffic['links_per_chip'] == 2 * s.batch
  n = cfg['num_nodes']
  s.directed = graphgen.csr(n, cfg['num_edges'], seed)
  part('graph_s')
  s.indptr, s.indices, s.num_edges = graphgen_seal.symmetric_csr(
      *s.directed, n)
  part('symmetrise_s')
  s.feats = graphgen.Features(n, cfg['feature_dim'], 2, seed)
  table = s.feats.table()
  part('features_s')
  mesh = make_mesh(chips)
  s.tx = optax.adam(cfg['learning_rate'])
  feature = ShardedFeature(table, mesh)
  del table
  jax.block_until_ready(feature.array)
  part('feature_upload_s')
  graph = Graph(graphgen.SortedCSR(s.indptr, s.indices, n))
  s.trainer = SPMDSageTrainStep(
      mesh, make_model(cfg), s.tx, graph, feature, None,
      fanouts=traffic['fanout'], batch_size_per_device=s.batch,
      neg_sampling=NegativeSampling(neg['mode'], neg['amount'],
                                    neg['strict']),
      keep_seeds=True, enclose=s.spec, keep_sample=True)
  jax.block_until_ready(s.trainer._indices)
  # the trainer holds the undirected graph; the scope window draws its
  # positives as the window's are drawn, from the edges as generated
  s.trainer.chipbench_directed = s.directed
  part('trainer_s')
  start(s, seed)
  part('warm_up_s')
  return s


def weights(seed, cfg):
  """DGCNN's weights in flax's tree, made on the device in one jitted
  call: the label embedding standard normal (PyTorch's ``Embedding``),
  kernels normal with variance 1/fan_in, biases normal at a tenth."""
  import jax
  hidden, k = cfg['hidden_dim'], cfg['sortpool_k']
  c1, c2 = cfg['conv1d_channels']
  total = hidden * cfg['num_gcn_layers'] + 1
  dense_dim = (k // 2 - cfg['conv1d_kernels'][1] + 1) * c2
  kernels = {
      ('gcn0', 'lin', 'kernel'): (hidden + cfg['feature_dim'], hidden),
      ('gcn_key', 'lin', 'kernel'): (hidden, 1),
      ('conv1', 'kernel'): (total, 1, c1),
      ('conv2', 'kernel'): (cfg['conv1d_kernels'][1], c1, c2),
      ('mlp0', 'kernel'): (dense_dim, cfg['mlp_hidden']),
      ('mlp1', 'kernel'): (cfg['mlp_hidden'], 1)}
  for i in range(1, cfg['num_gcn_layers']):
    kernels[(f'gcn{i}', 'lin', 'kernel')] = (hidden, hidden)

  @jax.jit
  def make(key):
    tree = {'z_embed': {'embedding': jax.random.normal(
        jax.random.fold_in(key, 99), (cfg['max_z'], hidden))}}
    for i, (path, shape) in enumerate(sorted(kernels.items())):
      kk, kb = jax.random.split(jax.random.fold_in(key, i))
      node = tree.setdefault(path[0], {})
      fan_in = int(np.prod(shape[:-1]))
      kernel = jax.random.normal(kk, shape) / np.sqrt(fan_in)
      if len(path) == 3:
        node[path[1]] = {'kernel': kernel}
      else:
        node['kernel'] = kernel
      node['bias'] = jax.random.normal(kb, shape[-1:]) * 0.1
    return {'params': tree}

  return make(graphgen.jax_key(seed, 0))


def positive_pairs(directed, rng, count):
  """``[count, 2]`` positive edges of the graph as generated (``directed``:
  its CSR), drawn as the link cell draws them, a self-loop (which is no
  link) left out."""
  pairs = positive_edges(*directed, rng, count + count // 64 + 64)
  pairs = pairs[pairs[:, 0] != pairs[:, 1]]
  assert pairs.shape[0] >= count
  return pairs[:count]


def start(s, seed):
  """Pairs, keys and weights from ``seed``, then the warm-up steps. The
  graph and the trainer stay, so a calibration can start many times."""
  import jax
  cfg, chips = s.cfg, s.chips
  per_step = chips * s.batch
  steps = min(MAX_STEPS, cfg['num_edges'] // per_step)
  rng = np.random.default_rng([int(seed), 4])
  s.pairs = positive_pairs(s.directed, rng, steps * per_step).reshape(
      steps, per_step, 2)
  s.keys = jax.random.split(graphgen.jax_key(seed, 1), (steps, chips))
  s.n_valid = np.full((chips,), s.batch, np.int32)
  s.params0 = weights(seed, cfg)
  s.params, s.opt = s.params0, s.tx.init(s.params0)
  losses, first_opt, s.counted = [], None, []
  for t in range(s.traffic['warmup_steps']):
    losses.append(np.asarray(step(s, t)))
    newest = s.trainer._counted[-1][1]
    s.counted.append({k: np.asarray(v)[0] for k, v in newest.items()})
    first_opt = s.opt if first_opt is None else first_opt
  first_grad = jax.tree.map(
      lambda m: np.asarray(m) / (1 - reference_seal.B1), first_opt[0].mu)
  host = lambda tree: jax.tree.map(np.asarray, tree)
  s.program = reference_seal.readings([l[0] for l in losses], first_grad,
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
  return reference_seal.default_operands()


# traces and compiles are counted as the node cell's driver counts them
compilations = fused.compilations


def batches(s):
  """The warm-up steps as the reference takes them: the node sets the
  program drew, the links' labels, every link valid, and the order its
  readout kept each graph's nodes in (a sort is a choice: ``verify`` holds
  the order to the reference's own keys, and the arithmetic to the
  reference given the order)."""
  ones = np.ones(s.batch, np.float32)
  return [dict(nodes=got['nodes'], y=np.concatenate([ones, 0 * ones]),
               weight=np.concatenate([ones, ones]), order=got['pool_order'])
          for got in s.counted]


def blocks_of(s, got):
  """A step's ``[L, S, S]`` bool blocks from its packed rows."""
  return np.unpackbits(got['adj_bits'], axis=-1,
                       count=s.spec.node_slots).astype(bool)


def sample_violations(s, got):
  """How far a step's node sets are from the hop's contract: slots 0 and
  1 the link's ends, every other live node a neighbour of one of them in
  the CSR and in the set once, a row no wider than the fanout in the set
  whole, the live slots a prefix."""
  links, k = 2 * s.batch, s.spec.fanout
  ends = got['seeds'].reshape(2, links)
  bad = 0
  for l in range(links):
    nodes = got['nodes'][l]
    n = int((nodes >= 0).sum())
    bad += int((nodes[n:] >= 0).sum()) + int(n < 2)
    bad += int(nodes[0] != ends[0, l]) + int(nodes[1] != ends[1, l])
    fringe = nodes[2:n]
    rows = [s.indices[s.indptr[e]:s.indptr[e + 1]] for e in ends[:, l]]
    bad += int((~(np.isin(fringe, rows[0])
                  | np.isin(fringe, rows[1]))).sum())
    bad += int(np.unique(fringe).size != fringe.size)
    bad += int(np.isin(fringe, ends[:, l]).sum())
    for row in rows:
      if row.shape[0] <= k:
        bad += int((~np.isin(row, nodes[:n])).sum())
  return bad


def is_edge(s, rows, cols):
  return reference_link.is_edge(s.indptr, s.indices, rows, cols)


def negative_violations(s, got):
  """Positives that are no edge of the graph, plus the distance of the
  negatives that are edges from ``negatives_padded``."""
  src, nsrc, dst, ndst = got['seeds'].astype(np.int64).reshape(4, -1)
  return (int((~is_edge(s, src, dst)).sum())
          + abs(int(is_edge(s, nsrc, ndst).sum())
                - int(got['negatives_padded'])))


def tile_rule(indptr, nodes, hub_width, tile_budget):
  """The extraction's budget rule again, in numpy, for node sets ``nodes
  [L, S]`` (-1 padded): ``(tiles, unread)``, the tiles of 128 entries each
  link's read members span and, ``[L, S]`` bool, its live members that are
  not read (wider than ``hub_width``, or past the link's ``tile_budget``
  in slot order)."""
  live = nodes >= 0
  at = np.maximum(nodes, 0)
  deg = np.where(live, indptr[at + 1] - indptr[at], 0)
  span = np.where((deg > 0) & (deg <= hub_width),
                  (indptr[at] % TILE + deg + TILE - 1) // TILE, 0)
  read = (span > 0) & (np.cumsum(span, axis=1) <= tile_budget)
  return np.where(read, span, 0).sum(1), live & (deg > 0) & ~read


def recount(s, t, got, adj, z, depth):
  """Every counter of step ``t`` counted again on the host from what the
  step handed back (``adj``, ``z``, ``depth``: the reference's blocks of
  the step's node sets): ``{name: value}``."""
  import jax
  from glt_tpu.parallel import dist_feature
  spec, links = s.spec, 2 * s.batch
  live = got['nodes'] >= 0
  tiles, unread = tile_rule(s.indptr, got['nodes'], spec.hub_width,
                            spec.tile_budget)
  h = unread.sum(1)
  pairs = int((h * (h - 1) // 2).sum())
  ends = got['seeds'].reshape(2, links)
  wide = (s.indptr[ends + 1] - s.indptr[ends] > spec.fanout).any(0)
  key = jax.random.fold_in(feed(s, t)[1][0], 0)
  _, _, padded, rejected = reference_link.negatives(
      s.indptr, s.indices, jax.random.split(key)[0], s.batch,
      s.cfg['num_nodes'])
  chunk = dist_feature.SERVE_CHUNK
  flat = live.reshape(-1)
  flat = np.concatenate([flat, np.zeros(-flat.size % chunk, bool)])
  return dict(
      nodes_by_hop=[2 * links, int(live.sum()) - 2 * links],
      subgraph_nodes=int(live.sum()), subgraph_edges=int(adj.sum()),
      links_capped=int(wide.sum()),
      tiles_read=int(tiles.sum()),
      hub_members=int(unread.sum()),
      hub_pairs_probed=min(pairs, spec.hub_pairs),
      edges_dropped=max(pairs - spec.hub_pairs, 0),
      drnl_rounds=int(depth.max()),
      drnl_unreachable=int((live & (z == 0)).sum()),
      seed_unique=int(np.unique(got['seeds']).size),
      negatives_rejected=int(rejected), negatives_padded=int(padded.sum()),
      store_chunks=int(flat.reshape(-1, chunk).any(1).sum())
      if flat.size > chunk else 1)


def counter_gap(got, again):
  """The distance of a step's counters from their recount."""
  return sum(int(np.abs(np.asarray(got[name], np.int64)
                        - np.asarray(value, np.int64)).sum())
             for name, value in again.items())


def verify(s):
  """{name: (value, limit)} of every number compared. Frees the device
  first: the reference runs where the program's state was."""
  compiled = compilations(s) - s.compiled_before
  s.trainer = s.params = s.opt = None
  gc.collect()
  ref = reference_seal.follow(
      s.indptr, s.indices, s.feats.rows, s.params0, batches(s),
      s.cfg['learning_rate'], s.cfg['sortpool_k'], s.cfg['max_z'],
      operands=stated_operands(s.cfg))
  gaps = reference_seal.compare(s.program, ref)
  limits = s.cfg['limits']
  out = {k: (v, limits[k]) for k, v in gaps.items()}
  zero = dict(sample_violations=0, subgraph_violations=0,
              label_violations=0, pool_violations=0,
              negative_violations=0, counter_gap=0, edges_dropped=0)
  for t, (got, (adj, z, _, depth)) in enumerate(zip(s.counted,
                                                    ref['blocks'])):
    zero['sample_violations'] += sample_violations(s, got)
    zero['subgraph_violations'] += int((blocks_of(s, got) != adj).sum())
    zero['label_violations'] += int((got['z'] != z).sum())
    zero['pool_violations'] += reference_seal.sort_violations(
        got['pool_order'], ref['keys'][t], s.cfg['sort_tolerance'])
    zero['negative_violations'] += negative_violations(s, got)
    zero['counter_gap'] += counter_gap(got, recount(s, t, got, adj, z,
                                                    depth))
    zero['edges_dropped'] += int(got['edges_dropped'])
  out.update({k: (v, 0) for k, v in zero.items()})
  out['compilations'] = (compiled, 0)
  return out
