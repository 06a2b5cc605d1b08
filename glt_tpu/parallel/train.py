"""SPMD data-parallel training step: the DDP + distributed-feature loop
as one shard_map program.

Reference architecture being replaced (SURVEY.md §2.3): DDP/NCCL gradient
allreduce + per-rank sampling workers + RPC feature lookup. TPU-native
formulation: a single shard_map over the 'data' mesh axis where each
device (1) samples its own seed shard against the replicated topology,
(2) resolves features from the row-sharded feature table via the
all_to_all exchange in ShardedFeature, (3) computes grads, (4) psums —
the NCCL allreduce riding ICI. Params/optimizer state stay replicated.

Two execution modes share one batch body:

  * per-batch (``__call__``): one dispatch per batch — one Python loop
    iteration, one host->device seed transfer, one jit dispatch each.
  * superstep (``superstep`` / ``run_epoch``): K batches per donated
    dispatch via lax.scan (ops/superstep.py), consuming seed stacks the
    DeviceEpochLoader staged on device once per epoch. Bit-identical to
    K sequential per-batch calls (same RNG stream, same op sequence) —
    the scan only amortizes the per-batch host round-trips. The hetero
    sibling — per-edge-type collective sampling + RGNN update, the same
    scan lift (ops/superstep.py) — is
    ``glt_tpu.distributed.DistHeteroTrainStep.superstep``.

The per-batch body has two fronts and two losses, chosen when the step
is built. Given labelled node seeds it trains a classifier. Given a
``NegativeSampling`` it is the link-prediction step of the reference's
unsupervised GraphSAGE recipe (``LinkNeighborLoader`` + binary
negatives + dot-product BCE, examples/graph_sage_unsup.py) in the same
one donated program: ``[B, 2]`` positive pairs in, strict negatives
drawn from the step's key (ops/negative.py), the ``4B`` endpoints as
the seeds of the one hop loop, the endpoints' embeddings read off the
seed prefix, so the node trim and the grouped aggregation engage as on
the node path. The supersteps and the streaming consume refuse edge
seeds.

Given an ``EncloseSpec`` as well, the link step is SEAL's
(examples/seal_link_pred.py): the batch is not a fan-out tree but ``2B``
small graphs, one a link. In the same one program: the link step's
positives and strict negatives, one hop from the ``4B`` endpoints, then
for every link on its own the dedup into ``S`` node slots, the exact
induced edges among them as a dense ``[S, S]`` block with the link itself
taken out (``ops/subgraph.py::enclosing_subgraphs``: the members' rows
read tile by tile in one loop over the tiles the batch's links hold, not
the tiles they are budgeted; the step counts both, ``tiles_read`` and
``tiles_matched``), DRNL
(``ops/drnl.py::drnl_dense``), the store's gather of the live slots, a
model that reads out a graph (``models/dgcnn.py``) and a loss a link.

For host-spilled features WITHOUT the pinned-host cold block
(``cold_array is None``) the fused body cannot resolve cold rows
in-program; ``cold_streaming=True`` instead splits each superstep into a
sampling scan and a consume scan: the host gathers the sampled cold rows
(``ShardedFeature.stage_cold_rows``) and ``device_put``s them between the
two, and ``run_epoch`` runs that stage phase for superstep N+1 on a
prefetch thread while the chip executes superstep N (double buffering —
``split_ratio < 1`` no longer serializes host gathers against compute).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..data import Graph
from ..ops.drnl import drnl_dense
from ..ops.negative import random_negative_sample
from ..ops.pipeline import edge_hop_offsets, hop_fanouts, \
    multihop_sample, node_hop_offsets, sample_budget
from ..ops.sample import sample_neighbors
from ..ops.subgraph import EncloseSpec, enclosing_subgraphs, pad_to_tiles
from ..ops.superstep import scan_consume, superstep as build_superstep
from ..loader.transform import Batch
from ..obs.device import StepCounters, register_step_program, scope
from ..sampler.base import NegativeSampling
from .mesh import replicate

#: rounds of proposals a strict negative gets before it is padded with
#: the last round's (reference RandomNegativeSampler.sample's default)
NEG_TRIALS = 5

#: of a step's counters, what only a link step's front counts and what
#: only a store that exchanges counts (``link_counters``,
#: ``store_counters``)
LINK_COUNTERS = ('negatives_rejected', 'negatives_padded', 'seed_unique',
                 'seeds')
STORE_COUNTERS = ('store_rounds', 'store_bucket_max', 'store_requests',
                  'store_exchange_chunks')


def _node_loss(bs):
  """Mean softmax cross-entropy of the ``bs`` seed rows over ``batch.y``,
  the rows past ``n_valid`` left out."""
  def loss(logits, batch, n_valid):
    mask = jnp.arange(bs) < n_valid
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch.y)
    return (jnp.where(mask, losses, 0).sum()
            / jnp.maximum(mask.sum(), 1))
  return loss


def _link_loss(num_pos):
  """Mean binary cross-entropy with logits over ``num_pos`` positive
  pairs and their ``num_pos`` negatives: a pair's logit is the dot
  product of its endpoints' embeddings, read off the seed rows by
  ``edge_label_index`` (the seed slots' labels); a pair past
  ``n_valid`` and its negative are left out of the mean."""
  def loss(emb, batch, n_valid):
    with jax.named_scope('link_loss'):
      src, dst = jnp.maximum(batch.metadata['edge_label_index'], 0)
      logit = (jnp.take(emb, src, axis=0)
               * jnp.take(emb, dst, axis=0)).sum(-1)
      mask = jnp.tile(jnp.arange(num_pos) < n_valid, 2)
      losses = optax.sigmoid_binary_cross_entropy(
          logit, batch.metadata['edge_label'])
      return (jnp.where(mask, losses, 0).sum()
              / jnp.maximum(mask.sum(), 1))
  return loss


def _graph_loss(num_pos):
  """Mean binary cross-entropy with logits over the ``2 * num_pos`` links
  of an enclosing-subgraph step, a logit a graph; a pair past ``n_valid``
  and its negative are left out of the mean."""
  def loss(out, batch, n_valid):
    logit, order = out
    with jax.named_scope('link_loss'):
      mask = jnp.tile(jnp.arange(num_pos) < n_valid, 2)
      losses = optax.sigmoid_binary_cross_entropy(logit,
                                                  batch['edge_label'])
      return (jnp.where(mask, losses, 0).sum()
              / jnp.maximum(mask.sum(), 1)), order
  return loss


class _OnSubgraphs:
  """A graph model (``models.DGCNN``) taken as ``_sage_update`` takes a
  model: ``apply(params, batch)`` over an enclosing-subgraph step's
  batch, a dict of ``x [L, S, F]``, ``z [L, S]``, ``adj [L, S, S]`` and
  ``node_mask [L, S]``; one logit a graph, and the readout's order (the
  ``k`` node slots a graph was pooled from, in the order it kept them)."""

  def __init__(self, model):
    self.model = model

  def _call(self, fn, first, batch, **kw):
    return fn(first, batch['x'], z=batch['z'], adj=batch['adj'],
              node_mask=batch['node_mask'], **kw)

  def init(self, key, batch):
    return self._call(self.model.init, key, batch)

  def apply(self, params, batch):
    return self._call(self.model.apply, params, batch, with_order=True)


def _count_distinct(ids, mask):
  """Distinct ids among the masked, by one sort."""
  big = jnp.iinfo(ids.dtype).max
  xs = jnp.sort(jnp.where(mask, ids, big))
  head = jnp.concatenate([jnp.ones((1,), bool), xs[1:] != xs[:-1]])
  return (head & (xs != big)).sum(dtype=jnp.int32)


def _sage_update(model, tx, axis, loss_of, params, opt_state, batch,
                 n_valid, has_aux=False):
  """Forward/backward + DDP pmean + optimizer update for one batch —
  the training tail shared by the per-batch, fused-superstep and
  streaming-consume bodies (identical op sequence = loss parity).
  ``loss_of(seed_rows, batch, n_valid)`` is the task's loss over what
  the model gives for the seed rows (:func:`_node_loss`,
  :func:`_link_loss`). With ``has_aux`` it gives ``(loss, aux)`` and
  ``aux`` is returned last, no gradient through it."""
  def loss_fn(p):
    with jax.named_scope('forward'):
      return loss_of(model.apply(p, batch), batch, n_valid)

  with scope('model_step'):
    loss, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(params)
    if has_aux:
      loss, aux = loss
  with scope('collectives', 'grad_sync'):
    # DDP allreduce (mean over devices), riding ICI
    grads = jax.lax.pmean(grads, axis)
    loss = jax.lax.pmean(loss, axis)
  with scope('model_step', 'update'):
    updates, opt_state = tx.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
  return (params, opt_state, loss, aux) if has_aux else (
      params, opt_state, loss)


class SPMDSageTrainStep(StepCounters):
  """Builds and runs the sharded sample+train step.

  Args:
    mesh: the device mesh (axis 'data').
    model: a flax module consuming a Batch (e.g. models.GraphSAGE).
    tx: optax optimizer.
    graph: replicated Graph (HBM-resident topology on every chip; the
      sharded-topology variant lives in glt_tpu.distributed).
    feature: a ShardedFeature row-sharded over the mesh.
    labels: [N] label array (replicated); None for a link step.
    fanouts: per-hop fanouts.
    batch_size_per_device: seed count per device per step: seed nodes,
      or positive pairs for a link step.
    with_edge: also thread sampled edge ids through the pipeline into
      ``Batch.edge`` (edge-feature consumers).
    cold_streaming: opt-in — accept a host-spilled store WITHOUT the
      pinned-host cold block by staging cold rows per superstep (see
      module docstring). Only the superstep path serves such stores;
      per-batch ``__call__`` raises. Without it, such stores are
      rejected at construction exactly as before.
    neg_sampling: a ``NegativeSampling`` (or what casts to one) makes
      this a link-prediction step, as ``LinkNeighborLoader`` takes it:
      ``__call__`` is handed ``[n_dev * B, 2]`` positive ``(src, dst)``
      pairs where it is handed node ids, draws ``B`` negatives a device
      inside the program (binary mode, amount 1; ``strict`` rejects
      edges of the graph over ``NEG_TRIALS`` rounds and pads with the
      last round's proposal), seeds the hop loop with the ``4B``
      endpoints ``[src; neg_src; dst; neg_dst]`` (``sample_from_edges``'s
      order) and trains the dot-product BCE of the model's seed rows.
      ``labels`` is not read. Per-batch only: the supersteps and
      ``cold_streaming`` raise. What only this path counts rides the
      step's counters (:meth:`counters`, :meth:`link_counters`).
    keep_seeds: a link step also hands back the ``[4B]`` endpoint seeds
      it drew and expanded (among its counters), for a check of the
      negatives themselves against the graph; off, the program has no
      such output.
    enclose: an ``ops.subgraph.EncloseSpec`` makes the link step SEAL's:
      ``fanouts`` is the one hop ``[enclose.fanout]``, ``model`` reads
      out graphs (``models.DGCNN`` with ``max_z``), the graph is read
      undirected (a symmetric CSR) and ``__call__`` takes and returns
      what the link step does. Of the ``2B`` links a device the first
      ``B`` are the positives. Link ``l`` holds ``S = 2 + 2 * fanout``
      node slots: its source, its destination, then the distinct
      neighbours the hop took of either. Its edges are every edge of the
      graph among its nodes less the link itself: never fewer, unless
      ``edges_dropped`` says so (``EncloseSpec``'s budgets). The step
      counts, beside a link step's counters: ``nodes_by_hop`` ``[2]``
      (endpoint slots, fringe nodes), ``subgraph_nodes``,
      ``subgraph_edges`` (directed), ``edges_dropped``, ``links_capped``
      (links with an endpoint wider than the fanout), ``tiles_read``,
      ``tiles_matched`` (the tiles the induction's loop gathered and
      matched: ``tiles_read`` in whole chunks), ``hub_members``,
      ``hub_pairs_probed``, ``drnl_rounds``,
      ``drnl_unreachable`` and the store's.
    keep_sample: an enclosing-subgraph step also hands back what it
      extracted, among its counters: ``nodes [2B, S]`` (-1 padded), ``z
      [2B, S]`` and ``adj_bits [2B, S, ceil(S / 8)]`` (``numpy.packbits``
      of the blocks' rows), for a check against the CSR; and
      ``pool_order [2B, k]``, the node slots the model's readout kept of
      each graph, in its order: a sort is a choice, not arithmetic, so a
      comparison of the arithmetic takes the choice as made and checks it
      on its own.

  Every per-batch step also says how full its padded budgets were:
  :meth:`counters` reads what the newest steps counted,
  :meth:`counter_slots` the slots the counts are read against.
  """

  def __init__(self, mesh: Mesh, model, tx, graph: Graph, feature,
               labels, fanouts: Sequence[int],
               batch_size_per_device: int, axis: str = 'data',
               with_edge: bool = False, cold_streaming: bool = False,
               neg_sampling=None, keep_seeds: bool = False,
               enclose: EncloseSpec = None, keep_sample: bool = False):
    from .dist_feature import require_device_resident
    self.neg_sampling = NegativeSampling.cast(neg_sampling)
    self._link = self.neg_sampling is not None
    self._keep_seeds = bool(keep_seeds)
    self._enclose, self._keep_sample = enclose, bool(keep_sample)
    if enclose is not None:
      if not self._link:
        raise ValueError('an enclosing-subgraph step is a link step: '
                         'give it a neg_sampling')
      if list(fanouts) != [enclose.fanout]:
        raise ValueError(f'an enclosing-subgraph step takes one hop at '
                         f'its spec\'s fanout {enclose.fanout}; got '
                         f'fanouts={list(fanouts)}')
      model = _OnSubgraphs(model)
    if self._link:
      if not self.neg_sampling.is_binary() \
          or self.neg_sampling.amount != 1:
        raise NotImplementedError(
            f'SPMDSageTrainStep draws binary negatives, one a positive, '
            f'in its program; got {self.neg_sampling}: triplet mode and '
            f'other amounts run through LinkNeighborLoader')
      if cold_streaming:
        raise NotImplementedError(
            'cold_streaming runs through the supersteps, which take no '
            'edge seeds')
    self._streaming = bool(cold_streaming)
    if not self._streaming:
      require_device_resident(feature, 'SPMDSageTrainStep')
    elif not getattr(feature, '_spill', False) \
        or getattr(feature, 'cold_array', None) is not None:
      raise ValueError(
          'cold_streaming=True needs a host-spilled store without a '
          'pinned-host cold block (split_ratio < 1, host_offload=False)')
    self.mesh = mesh
    self.model = model
    self.tx = tx
    self.graph = graph
    self.feature = feature
    self.fanouts = list(fanouts)
    self.bs = batch_size_per_device
    #: seed slots of the hop loop a device: a link step seeds it with
    #: both endpoints of every positive and of every negative pair
    self.seed_slots = 4 * self.bs if self._link else self.bs
    self.axis = axis
    self.with_edge = bool(with_edge)
    #: the static fields of every Batch the step builds: the hop
    #: boundaries of the edge slots and of the node labels (the sampler
    #: hands labels out hop by hop), by which the model trims both, and
    #: the widths of the groups of adjacent slots that share a parent,
    #: by which it sums a parent's children without a scatter over the
    #: slots (None where the hop loop does not keep slot order)
    self._batch_static = dict(
        batch_size=self.seed_slots,
        edge_hop_offsets=tuple(
            edge_hop_offsets(self.seed_slots, self.fanouts)),
        node_hop_offsets=tuple(
            node_hop_offsets(self.seed_slots, self.fanouts)),
        hop_fanouts=hop_fanouts(self.fanouts))
    graph.lazy_init()
    # a link step reads no label: one element stands in as the argument
    self.labels = jax.device_put(
        jnp.zeros((1,), jnp.int32) if self._link else labels,
        NamedSharding(mesh, P()))
    # one-time replication of the topology over the mesh: these ride
    # the step as jit ARGUMENTS (as closed-over constants, hundreds of
    # MB of topology would be baked into the compiled program), and
    # pre-committing the replicated sharding here keeps the per-step
    # call from re-broadcasting them each execution
    self._indptr = jax.device_put(graph.indptr, NamedSharding(mesh, P()))
    self._indices = jax.device_put(
        graph.indices if enclose is None else pad_to_tiles(graph.indices),
        NamedSharding(mesh, P()))
    #: times each program was TRACED (trace-time side effect; executions
    #: never bump these) — zero-steady-state-recompile assertions read
    #: them. A fresh T (e.g. an epoch's ragged tail superstep) traces
    #: once more by design.
    self.step_traces = 0
    self.superstep_traces = 0
    #: output rows each layer of the model computes in the step, filled
    #: when a program is traced (static: the node trim engages at trace
    #: time); None before, and for a model that does not say
    self.layer_rows = None
    #: groups of adjacent edge slots each layer aggregates by a reshape
    #: and a masked reduce (0: the layer scatter-adds every slot);
    #: static and filled like ``layer_rows``
    self.layer_groups = None
    self._init_counters()
    self._step_fn = self._build()
    self._superstep_fn = self._build_superstep()
    if self._streaming:
      self._sample_fn = self._build_sample_superstep()
      self._consume_fn = self._build_consume_superstep()
    register_step_program(self)

  def init_params(self, key) -> dict:
    batch = self._dummy_batch()
    params = self.model.init(key, batch)
    return replicate(params, self.mesh)

  def _dummy_batch(self):
    if self._enclose is not None:
      links, s = 2 * self.bs, self._enclose.node_slots
      return dict(x=jnp.zeros((links, s, self.feature.feature_dim)),
                  z=jnp.zeros((links, s), jnp.int32),
                  adj=jnp.zeros((links, s, s), bool),
                  node_mask=jnp.zeros((links, s), bool))
    budget = sample_budget(self.seed_slots, self.fanouts)
    ecap = self._batch_static['edge_hop_offsets'][-1]
    return Batch(
        x=jnp.zeros((budget, self.feature.feature_dim)),
        row=jnp.zeros((ecap,), jnp.int32),
        col=jnp.zeros((ecap,), jnp.int32),
        edge_mask=jnp.zeros((ecap,), bool),
        node=jnp.zeros((budget,), jnp.int32),
        node_count=jnp.zeros((), jnp.int32),
        y=jnp.zeros((self.seed_slots,), jnp.int32),
        **self._batch_static)

  def _note_layer_rows(self, batch: Batch) -> None:
    """Trace-time side effect, as ``step_traces``: what the node trim
    leaves each layer to compute and how many groups of edge slots it
    aggregates by the grouped reduce, on the attributes and as the
    gauges ``model_layer_rows{fn="train.step", layer=i}`` and
    ``model_grouped_aggregation{fn="train.step", layer=i}``."""
    from ..obs.perf import gauge_grouped_aggregation, gauge_layer_rows
    rows_of = getattr(self.model, 'layer_rows', None)
    if rows_of is not None:
      self.layer_rows = rows_of(batch)
      gauge_layer_rows('train.step', self.layer_rows)
    groups_of = getattr(self.model, 'layer_groups', None)
    if groups_of is not None:
      self.layer_groups = tuple(
          sum(s for _, s, _ in g) for g in groups_of(batch))
      gauge_grouped_aggregation('train.step', self.layer_groups)

  # -- shared per-batch body ----------------------------------------------

  def _link_seeds(self, indptr, indices, pairs, n_valid, key):
    """The link step's front, inside the ``sampler`` scope: ``B``
    negatives from ``key`` (as ``sample_from_edges`` draws them:
    uniform pairs, ``NEG_TRIALS`` rounds, the first round that is no
    edge of the graph, the last round's proposal where none is), then
    the seeds ``[src; neg_src; dst; neg_dst]``, their mask (a pair past
    ``n_valid`` and its negative seed nothing) and the labels of the
    ``2B`` pairs. Returns ``(seeds [4B], seed_mask [4B], edge_label
    [2B], counters)``."""
    bs, num_nodes = self.bs, self.graph.num_nodes
    with jax.named_scope('negative'):
      neg = random_negative_sample(
          indptr, indices, bs, NEG_TRIALS, key, num_nodes, num_nodes,
          strict=self.neg_sampling.strict, padding=True)
    seeds = jnp.concatenate(
        [pairs[:, 0], neg.rows, pairs[:, 1], neg.cols]).astype(jnp.int32)
    seed_mask = jnp.tile(jnp.arange(bs) < n_valid, 4)
    edge_label = jnp.concatenate(
        [jnp.ones((bs,), jnp.float32), jnp.zeros((bs,), jnp.float32)])
    return seeds, seed_mask, edge_label, dict(
        negatives_rejected=neg.rejected, negatives_padded=neg.padded)

  def _make_batch_body(self, feat_shard, labels, indptr, indices,
                       cold_shard):
    """The body of ONE training step as seen from inside shard_map:
    sample -> gather -> forward/backward -> pmean -> update. Shared
    verbatim by the per-batch step and the superstep scan so the two
    engines stay bit-identical. Its last output is ``(loss, counters)``,
    ``counters`` one flat dict of what the step counted
    (:meth:`counters` names every entry): the sampler's new nodes,
    valid edges and frontier rows read by hop, a link step's negatives
    and distinct seeds, and what the store counted (its exchange, or in
    place the chunks of request slots it gathered)."""
    if self._enclose is not None:
      return self._make_enclose_body(feat_shard, indptr, indices,
                                     cold_shard)
    feature, model, tx, axis = self.feature, self.model, self.tx, self.axis
    fanouts, bs = self.fanouts, self.bs
    with_edge, link = self.with_edge, self._link
    loss_of = _link_loss(bs) if link else _node_loss(bs)
    one_hop = lambda ids, fanout, k, mask: sample_neighbors(
        indptr, indices, ids, fanout, k, seed_mask=mask)

    def body(params, opt_state, seeds, n_valid, key):
      counted = {}
      with scope('sampler'):
        key = jax.random.fold_in(key[0], jax.lax.axis_index(axis))
        seed_mask = meta = None
        if link:
          kneg, key = jax.random.split(key)
          seeds, seed_mask, edge_label, counted = self._link_seeds(
              indptr, indices, seeds, n_valid[0], kneg)
        out = multihop_sample(
            one_hop, seeds, n_valid[0], fanouts, key,
            with_edge=with_edge, seed_mask=seed_mask)
        counted.update(nodes_by_hop=out['num_sampled_nodes'],
                       edges_by_hop=out['num_sampled_edges'],
                       hop_rows_read=out['hop_rows_read'])
        if link:
          # a seed slot's label is its endpoint's row of the model's
          # output: the labels of the seeds are the first ones
          meta = dict(
              edge_label_index=out['seed_labels'].reshape(2, -1),
              edge_label=edge_label)
          counted['seed_unique'] = out['seed_count']
          if self._keep_seeds:
            counted['seeds'] = seeds
      with scope('feature_store'):
        node_valid = jnp.arange(out['node'].shape[0]) < out['node_count']
        x, store_stats = feature.lookup_local(
            feat_shard, jnp.maximum(out['node'], 0), node_valid,
            axis_name=axis, cold_shard=cold_shard, counters=True)
        counted.update(store_stats)
        y = None if link else jnp.take(
            labels, jnp.maximum(out['batch'], 0)[:bs])
      batch = Batch(
          x=x, row=out['row'], col=out['col'], edge_mask=out['edge_mask'],
          node=out['node'], node_count=out['node_count'], y=y,
          edge=out.get('edge'), metadata=meta, **self._batch_static)
      self._note_layer_rows(batch)
      params, opt_state, loss = _sage_update(
          model, tx, axis, loss_of, params, opt_state, batch, n_valid[0])
      return params, opt_state, (loss, counted)

    return body

  def _make_enclose_body(self, feat_shard, indptr, indices, cold_shard):
    """``_make_batch_body`` for an enclosing-subgraph step: the link
    step's front as it is, then a batch of ``2B`` graphs where the
    fan-out tree stood (the module's text): every link is deduped on
    its own."""
    feature, model, tx, axis = self.feature, self.model, self.tx, self.axis
    spec, bs = self._enclose, self.bs
    links, s, k = 2 * bs, spec.node_slots, spec.fanout
    loss_of = _graph_loss(bs)

    def body(params, opt_state, pairs, n_valid, key):
      with scope('sampler'):
        key = jax.random.fold_in(key[0], jax.lax.axis_index(axis))
        kneg, key = jax.random.split(key)
        seeds, seed_mask, edge_label, counted = self._link_seeds(
            indptr, indices, pairs, n_valid[0], kneg)
        counted['seed_unique'] = _count_distinct(seeds, seed_mask)
        if self._keep_seeds:
          counted['seeds'] = seeds
        with jax.named_scope('enclose'):
          with jax.named_scope('sample_hop0'):
            _, sub = jax.random.split(key)
            hop = sample_neighbors(indptr, indices, seeds, k, sub,
                                   seed_mask=seed_mask)
            wide = (jnp.take(indptr, seeds + 1) - jnp.take(indptr, seeds)
                    > k).reshape(2, links).any(0)
          link_mask = seed_mask[:links]
          out = enclosing_subgraphs(
              indptr, indices, seeds.reshape(2, links),
              hop.nbrs.reshape(2, links, k), hop.mask.reshape(2, links, k),
              link_mask, spec)
          with jax.named_scope('drnl'):
            z, rounds, unreachable = drnl_dense(
                out['adj'], out['node_mask'], spec.max_z)
        ends = 2 * link_mask.sum(dtype=jnp.int32)
        counted.update(
            nodes_by_hop=jnp.stack([ends, out['subgraph_nodes'] - ends]),
            links_capped=(wide & link_mask).sum(dtype=jnp.int32),
            drnl_rounds=rounds, drnl_unreachable=unreachable,
            **{name: out[name] for name in (
                'subgraph_nodes', 'subgraph_edges', 'edges_dropped',
                'tiles_read', 'tiles_matched', 'hub_members',
                'hub_pairs_probed')})
        if self._keep_sample:
          counted.update(nodes=out['nodes'], z=z,
                         adj_bits=jnp.packbits(out['adj'], axis=-1))
      with scope('feature_store'):
        x, store_stats = feature.lookup_local(
            feat_shard, jnp.maximum(out['nodes'], 0).reshape(-1),
            out['node_mask'].reshape(-1), axis_name=axis,
            cold_shard=cold_shard, counters=True)
        counted.update(store_stats)
        # the rows' only reader is a matmul at the default precision,
        # which on a TPU rounds them to bfloat16. Left to the compiler,
        # that rounding moves in front of the gather: the WHOLE table
        # converted every step (9.3 ms and 2 GB on the benchmark's 8 M
        # rows, read on the chip; a barrier alone does not stop it). An
        # integer view behind a barrier does: the gather's rows have a
        # reader that needs all 32 bits, and no value changes
        x = jax.lax.bitcast_convert_type(jax.lax.optimization_barrier(
            jax.lax.bitcast_convert_type(x, jnp.int32)), x.dtype)
      batch = dict(x=x.reshape(links, s, -1), z=z, adj=out['adj'],
                   node_mask=out['node_mask'], edge_label=edge_label)
      params, opt_state, loss, order = _sage_update(
          model, tx, axis, loss_of, params, opt_state, batch, n_valid[0],
          has_aux=True)
      if self._keep_sample:
        counted['pool_order'] = order
      return params, opt_state, (loss, counted)

    return body

  def _build(self):
    def device_step(params, opt_state, seeds, n_valid, key, feat_shard,
                    labels, indptr, indices, *cold_shard):
      body = self._make_batch_body(
          feat_shard, labels, indptr, indices,
          cold_shard[0] if cold_shard else None)
      params, opt_state, aux = body(params, opt_state, seeds, n_valid,
                                    key)
      return params, opt_state, jax.tree.map(lambda a: a[None], aux)

    offloaded = self.feature.cold_array is not None
    fn = jax.shard_map(
        device_step, mesh=self.mesh,
        in_specs=(P(), P(), P(self.axis), P(self.axis), P(self.axis),
                  P(self.axis), P(), P(), P())
        + ((P(self.axis),) if offloaded else ()),
        out_specs=(P(), P(), P(self.axis)),
        check_vma=False)

    @jax.jit
    def step(params, opt_state, seeds, n_valid, keys, feat_array, labels,
             indptr, indices, *cold):
      # feat/cold/labels/topology ride as explicit args: (a) committed
      # shardings — incl. the cold block's pinned_host memory kind —
      # are preserved (a closed-over array would be re-laid-out as a
      # default-memory constant), and (b) a closed-over array becomes a
      # jit CONSTANT: hundreds of MB of topology baked into the
      # compiled program
      self.step_traces += 1  # trace-time side effect only
      from ..obs.perf import count_compile
      count_compile('train.step')
      return fn(params, opt_state, seeds, n_valid, keys, feat_array,
                labels, indptr, indices, *cold)

    return step

  # -- superstep: K batches per donated dispatch --------------------------

  def _build_superstep(self):
    """The fused superstep program: lax.scan of the per-batch body with
    params/opt-state in the carry. Unsupported for
    streaming stores (cold rows are not in-program resolvable there);
    ``superstep()`` routes those through sample+stage+consume."""
    if self._streaming or self._link:
      return None
    axis = self.axis

    def device_superstep(params, opt_state, seeds_stack, n_valid_stack,
                         keys, feat_shard, labels, indptr, indices,
                         *cold_shard):
      # per-device views: seeds_stack [T, bs], n_valid_stack [T, 1],
      # keys [T, 1]
      body = self._make_batch_body(
          feat_shard, labels, indptr, indices,
          cold_shard[0] if cold_shard else None)

      def loss_alone(*args):
        # a scanned batch keeps its loss and drops what it counted
        *state, (loss, _) = body(*args)
        return (*state, loss)

      run = build_superstep(loss_alone)
      params, opt_state, losses = run(params, opt_state, seeds_stack,
                                      n_valid_stack, keys)
      return params, opt_state, losses[:, None]

    offloaded = self.feature.cold_array is not None
    stacked = P(None, self.axis)
    fn = jax.shard_map(
        device_superstep, mesh=self.mesh,
        in_specs=(P(), P(), stacked, stacked, stacked, P(self.axis), P(),
                  P(), P())
        + ((P(self.axis),) if offloaded else ()),
        out_specs=(P(), P(), stacked),
        check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, seeds_stack, n_valid_stack, keys,
             feat_array, labels, indptr, indices, *cold):
      self.superstep_traces += 1  # trace-time side effect only
      from ..obs.perf import count_compile
      count_compile('train.superstep')
      return fn(params, opt_state, seeds_stack, n_valid_stack, keys,
                feat_array, labels, indptr, indices, *cold)

    return step

  def _stacked_put(self, seeds_stack, n_valid_stack, keys):
    """Commit superstep inputs to the [T, shard] layout. Inputs the
    DeviceEpochLoader already staged (committed, correct sharding) pass
    through without a copy."""
    sh = NamedSharding(self.mesh, P(None, self.axis))
    seeds = jax.device_put(jnp.asarray(seeds_stack, jnp.int32), sh)
    n_valid = jax.device_put(jnp.asarray(n_valid_stack, jnp.int32), sh)
    keys = jax.device_put(keys, sh)
    return seeds, n_valid, keys

  def superstep(self, params, opt_state, seeds_stack, n_valid_stack,
                keys):
    """Run T training steps in ONE donated dispatch.

    seeds_stack: [T, n_dev * bs] shard-major per batch;
    n_valid_stack: [T, n_dev]; keys: [T, n_dev] PRNG keys (batch t on
    device d consumes keys[t, d], exactly as T sequential ``__call__``\\ s
    consuming ``keys[t]`` would). Params/opt-state are DONATED — reuse
    the returned ones. Returns (params, opt_state, loss [T, n_dev]).
    """
    self._refuse_edge_seeds('superstep')
    seeds, n_valid, keys = self._stacked_put(seeds_stack, n_valid_stack,
                                             keys)
    params, opt_state = replicate((params, opt_state), self.mesh)
    from ..obs import get_registry, get_tracer
    tracer = get_tracer()
    if self._streaming:
      with tracer.span('train.superstep', streaming=True,
                       k=int(seeds.shape[0])):
        staged = self._sample_and_stage(seeds, n_valid, keys)
        out = self._consume(params, opt_state, staged, n_valid)
      if tracer.enabled:
        get_registry().set('train_superstep_traces',
                           float(self.superstep_traces))
      return out
    extra = ((self.feature.cold_array,)
             if self.feature.cold_array is not None else ())
    _synced = {}
    with tracer.span('train.superstep', k=int(seeds.shape[0]),
                     sync=lambda: _synced.get('loss')):
      params, opt_state, loss = self._superstep_fn(
          params, opt_state, seeds, n_valid, keys, self.feature.array,
          self.labels, self._indptr, self._indices, *extra)
      _synced['loss'] = loss
    if tracer.enabled:
      # re-trace visibility on the shared surface: the zero-steady-
      # state-recompile asserts read the attributes; dashboards read
      # these gauges
      get_registry().set('train_superstep_traces',
                         float(self.superstep_traces))
    return params, opt_state, loss

  # -- cold-row streaming: sample scan + host stage + consume scan --------

  def _build_sample_superstep(self):
    """Sampling-only scan (the multihop_sample_many shape, but under
    shard_map with the per-device key fold): produces the stacked
    sampler outputs the consume scan and the host cold-stager read."""
    axis, fanouts, bs = self.axis, self.fanouts, self.bs
    with_edge = self.with_edge

    def device_sample(seeds_stack, n_valid_stack, keys, indptr, indices):
      one_hop = lambda ids, fanout, k, mask: sample_neighbors(
          indptr, indices, ids, fanout, k, seed_mask=mask)

      def body(carry, x):
        seeds, n_valid, key = x
        with scope('sampler'):
          key = jax.random.fold_in(key[0], jax.lax.axis_index(axis))
          out = multihop_sample(one_hop, seeds, n_valid[0], fanouts, key,
                                with_edge=with_edge)
        keep = dict(node=out['node'], node_count=out['node_count'][None],
                    row=out['row'], col=out['col'],
                    edge_mask=out['edge_mask'])
        if with_edge:
          keep['edge'] = out['edge']
        return carry, keep

      _, outs = jax.lax.scan(body, None,
                             (seeds_stack, n_valid_stack, keys))
      return outs

    stacked = P(None, self.axis)
    fn = jax.shard_map(
        device_sample, mesh=self.mesh,
        in_specs=(stacked, stacked, stacked, P(), P()),
        out_specs=stacked,
        check_vma=False)

    @jax.jit
    def sample(seeds_stack, n_valid_stack, keys, indptr, indices):
      self.superstep_traces += 1  # trace-time side effect only
      from ..obs.perf import count_compile
      count_compile('train.sample_superstep')
      return fn(seeds_stack, n_valid_stack, keys, indptr, indices)

    return sample

  def _build_consume_superstep(self):
    """Scan of gather+forward/backward+update over pre-sampled batches:
    hot rows resolve through the all_to_all lookup (cold lanes zero),
    the staged cold rows add in elementwise — the in-scan equivalent of
    ShardedFeature._resolve_cold_sharded's host merge."""
    feature, model, tx = self.feature, self.model, self.tx
    axis, bs = self.axis, self.bs
    budget = sample_budget(bs, self.fanouts)
    with_edge = self.with_edge

    def device_consume(params, opt_state, outs, cold_x, n_valid_stack,
                       feat_shard, labels):
      def body(carry, x):
        params, opt_state = carry
        out, cold_t, n_valid = x
        node_count = out['node_count'][0]
        with scope('feature_store'):
          node_valid = jnp.arange(budget) < node_count
          xh = feature.lookup_local(
              feat_shard, jnp.maximum(out['node'], 0), node_valid,
              axis_name=axis)
          x_feat = xh + cold_t.astype(xh.dtype)
          y = jnp.take(labels, jnp.maximum(out['node'], 0)[:bs])
        batch = Batch(
            x=x_feat, row=out['row'], col=out['col'],
            edge_mask=out['edge_mask'], node=out['node'],
            node_count=node_count, y=y, edge=out.get('edge'),
            **self._batch_static)
        self._note_layer_rows(batch)
        params, opt_state, loss = _sage_update(
            model, tx, axis, _node_loss(bs), params, opt_state, batch,
            n_valid[0])
        return (params, opt_state), loss

      run = scan_consume(body)
      (params, opt_state), losses = run(
          (params, opt_state), (outs, cold_x, n_valid_stack))
      return params, opt_state, losses[:, None]

    stacked = P(None, self.axis)
    fn = jax.shard_map(
        device_consume, mesh=self.mesh,
        in_specs=(P(), P(), stacked, stacked, stacked, P(self.axis),
                  P()),
        out_specs=(P(), P(), stacked),
        check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def consume(params, opt_state, outs, cold_x, n_valid_stack,
                feat_array, labels):
      self.superstep_traces += 1  # trace-time side effect only
      from ..obs.perf import count_compile
      count_compile('train.consume_superstep')
      return fn(params, opt_state, outs, cold_x, n_valid_stack,
                feat_array, labels)

    return consume

  def _sample_and_stage(self, seeds, n_valid, keys):
    """Dispatch the sampling scan, then host-gather + upload the cold
    rows for every sampled node stack. run_epoch calls this from the
    prefetch thread so the host gather for superstep N+1 overlaps the
    chip executing superstep N."""
    outs = self._sample_fn(seeds, n_valid, keys, self._indptr,
                           self._indices)
    cold = self.feature.stage_cold_rows(
        np.asarray(outs['node']), np.asarray(outs['node_count']))
    cold_x = jax.device_put(
        cold, NamedSharding(self.mesh, P(None, self.axis)))
    return outs, cold_x

  def _consume(self, params, opt_state, staged, n_valid):
    outs, cold_x = staged
    params, opt_state, loss = self._consume_fn(
        params, opt_state, outs, cold_x, n_valid, self.feature.array,
        self.labels)
    return params, opt_state, loss

  # -- epoch drivers ------------------------------------------------------

  def _refuse_edge_seeds(self, entry: str) -> None:
    if self._link:
      raise NotImplementedError(
          f'SPMDSageTrainStep.{entry} takes node seeds only: a link '
          f'step (neg_sampling given) runs per batch, through __call__')

  def make_epoch_loader(self, seeds, superstep_len: int = 8,
                        shuffle: bool = True, drop_last: bool = False,
                        drop_last_superstep: bool = False,
                        rng=None):
    """A DeviceEpochLoader pre-committed to this trainer's mesh layout
    (seed stacks [T, n_dev*bs] sharded on the batch axis)."""
    self._refuse_edge_seeds('make_epoch_loader')
    from ..loader.device_epoch import DeviceEpochLoader
    n_dev = self.mesh.shape[self.axis]
    sh = NamedSharding(self.mesh, P(None, self.axis))
    return DeviceEpochLoader(
        seeds, batch_size=n_dev * self.bs, superstep_len=superstep_len,
        num_shards=n_dev, shuffle=shuffle, drop_last=drop_last,
        drop_last_superstep=drop_last_superstep, rng=rng, sharding=sh,
        n_valid_sharding=sh)

  def run_epoch(self, params, opt_state, loader, key,
                stream_depth: int = 1):
    """Drive one epoch of supersteps from a DeviceEpochLoader.

    Non-streaming stores run the fused superstep per window. Streaming
    stores double-buffer: the sample+stage phase (device sampling scan,
    host cold-row gather, device_put) for window N+1 runs on a prefetch
    thread while the consume scan for window N executes — the host
    gather no longer serializes against compute. Returns
    (params, opt_state, losses [T_total, n_dev]).
    """
    self._refuse_edge_seeds('run_epoch')
    n_dev = self.mesh.shape[self.axis]

    def keyed():
      k = key
      for ss in loader:
        k, sub = jax.random.split(k)
        yield ss, jax.random.split(sub, (ss.length, n_dev))

    losses = []
    if self._streaming:
      from ..utils.prefetch import prefetch

      def staged():
        for ss, keys in keyed():
          seeds, n_valid, keys = self._stacked_put(ss.seeds, ss.n_valid,
                                                   keys)
          yield self._sample_and_stage(seeds, n_valid, keys), n_valid

      for stage, n_valid in prefetch(staged(), depth=max(1,
                                                         stream_depth)):
        params, opt_state, loss = self._consume(params, opt_state,
                                                stage, n_valid)
        losses.append(loss)
    else:
      for ss, keys in keyed():
        params, opt_state, loss = self.superstep(
            params, opt_state, ss.seeds, ss.n_valid, keys)
        losses.append(loss)
    if not losses:  # empty epoch (e.g. drop_last_superstep ate it all)
      return params, opt_state, jnp.zeros((0, n_dev))
    return params, opt_state, jnp.concatenate(losses, axis=0)

  # -- per-batch path -----------------------------------------------------

  def __call__(self, params, opt_state, seeds, n_valid_per_device, keys):
    """seeds: [n_dev * bs] shard-major (a link step: [n_dev * bs, 2]
    positive ``(src, dst)`` pairs, each an edge of the graph);
    n_valid_per_device: [n_dev]; keys: [n_dev] PRNG keys. Returns
    (params, opt_state, loss[n_dev])."""
    if self._streaming:
      raise NotImplementedError(
          'cold_streaming stores run through superstep()/run_epoch(); '
          'the per-batch step cannot resolve host-spilled rows '
          'in-program')
    from ..obs import get_registry, get_tracer
    tracer = get_tracer()
    _synced = {}
    # the children are where the host spends a step's time; an idle gap
    # of the device is named after the one that covers it
    with tracer.span('train.step', sync=lambda: _synced.get('loss')):
      with tracer.span('train.step/put'):
        seeds = jax.device_put(
            jnp.asarray(seeds, jnp.int32),
            NamedSharding(self.mesh, P(self.axis)))
        n_valid = jax.device_put(
            jnp.asarray(n_valid_per_device, jnp.int32),
            NamedSharding(self.mesh, P(self.axis)))
        params, opt_state = replicate((params, opt_state), self.mesh)
      extra = ((self.feature.cold_array,)
               if self.feature.cold_array is not None else ())
      with tracer.span('train.step/dispatch'):
        params, opt_state, (loss, counted) = self._step_fn(
            params, opt_state, seeds, n_valid, keys, self.feature.array,
            self.labels, self._indptr, self._indices, *extra)
      self._keep_counters(counted)
      _synced['loss'] = loss
    if tracer.enabled:
      get_registry().set('train_step_traces', float(self.step_traces))
    return params, opt_state, loss

  def counter_slots(self) -> dict:
    """The contract of :meth:`StepCounters.counter_slots`: hop by hop
    the node slots (``node_hop_offsets``), the edge slots
    (``edge_hop_offsets``) and the frontier's slots of a device's
    batch; a link step's
    ``NEG_TRIALS x B`` proposals, ``B`` negatives and ``4B`` seed slots;
    over more than one shard the drain's most rounds, a per-owner
    bucket's ``exchange_cap(b)`` slots, the ``b`` request slots and the
    chunks one round's pack, serve and stitch are cut into; on one
    shard the chunks the ``b`` request slots are served in."""
    static = self._batch_static
    if self._enclose is not None:
      # a link's slots: 2 endpoints and the fringe; its block's entries
      # off the diagonal less the link itself; its tiles; a batch's pairs
      spec, links = self._enclose, 2 * self.bs
      s = spec.node_slots
      b = links * s
      slots = dict(
          nodes_by_hop=[2 * links, b - 2 * links], subgraph_nodes=b,
          subgraph_edges=links * (s * (s - 1) - 2), links_capped=links,
          tiles_read=links * spec.tile_budget,
          tiles_matched=links * spec.tile_budget, hub_members=b,
          hub_pairs_probed=spec.hub_pairs, drnl_unreachable=b)
    else:
      edges = np.diff(static['edge_hop_offsets'])
      # a hop's frontier holds a slot for every ``K`` of its edge slots
      slots = dict(
          nodes_by_hop=np.diff(static['node_hop_offsets'], prepend=0),
          edges_by_hop=edges, hop_rows_read=edges // np.abs(self.fanouts))
      b = static['node_hop_offsets'][-1]
    if self._link:
      slots.update(negatives_rejected=NEG_TRIALS * self.bs,
                   negatives_padded=self.bs, seed_unique=self.seed_slots)
    if self.feature.in_place:
      slots.update(store_chunks=self.feature.serve_chunks(b))
    else:
      cap = self.feature.exchange_cap(b)
      slots.update(store_rounds=-(-b // cap), store_bucket_max=cap,
                   store_requests=b,
                   store_exchange_chunks=self.feature.exchange_chunks(b))
    return {k: np.asarray(v, np.int64) for k, v in slots.items()}

  def link_counters(self) -> dict:
    """What the newest link step counted, a device an entry, read back
    from the device (it waits for that step): a view of
    :meth:`counters`' newest entry. ``negatives_rejected`` (proposals of
    the ``NEG_TRIALS x B`` that were edges of the graph),
    ``negatives_padded`` (pairs of the ``B`` with no round that was no
    edge: they carry the last round's proposal, an edge),
    ``seed_unique`` (distinct endpoints among the valid of the ``4B``:
    the hop-0 node count) and, from a step built with ``keep_seeds``,
    ``seeds`` (``[4B]`` node ids, ``[src; neg_src; dst; neg_dst]``)."""
    if not self._link or not self._counted:
      raise RuntimeError('no link step has run')
    return self._newest_counters(LINK_COUNTERS)

  def store_counters(self) -> dict:
    """What the feature store's exchange counted in the newest per-batch
    step, a device an entry, read back from the device (it waits for
    that step): a view of :meth:`counters`' newest entry.
    ``store_rounds`` (exchange rounds the drain ran, the same on every
    device: ``ceil`` of the mesh's fullest per-owner bucket over the
    bucket's cap), ``store_bucket_max`` (requests in the device's
    fullest bucket), ``store_requests`` (its valid requests: the
    batch's ``node_count``) and ``store_exchange_chunks`` (the chunks
    of request and bucket slots its pack, serve and stitch gathered,
    over the rounds). Only where the store exchanges: on one
    shard it serves in place, the step has no such output, and this
    raises."""
    if self.feature.in_place:
      raise RuntimeError(
          'the feature store is on one shard and serves in place: '
          'nothing is bucketed or exchanged, so nothing is counted')
    if not self._counted:
      raise RuntimeError('no per-batch step has run')
    return self._newest_counters(STORE_COUNTERS)

  def scope_profile(self, params, opt_state, batches) -> dict:
    """Device time by layer of the per-batch step, from a profiler
    session of its own: drives ``self(params, opt_state, *batch)`` over
    ``batches`` (an iterable of ``(seeds, n_valid, keys)``) and returns
    what ``obs.device.reduce_scopes`` makes of the trace. The state it
    is given is stepped and thrown away."""
    from ..obs.device import scope_profile
    return scope_profile(self, params, opt_state, batches,
                         step_program='jit_step')
