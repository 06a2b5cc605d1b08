"""Collocated distributed training: sample + feature exchange + DDP step
as ONE SPMD program over sharded topology and features.

This is the TPU equivalent of the reference's worker-mode deployment
(DistNeighborLoader + MpDistSamplingWorkerOptions + DDP,
examples/distributed/dist_train_sage_supervised.py): what the reference
does with sampling subprocesses, shm channels, rpc feature lookups and a
NCCL allreduce is here a single jitted shard_map step — sampling
collectives, feature all_to_all, gradient pmean all riding ICI.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..loader.transform import Batch
from ..ops.pipeline import edge_hop_offsets, multihop_sample
from ..parallel.mesh import replicate
from .dist_feature import DistFeature
from .dist_graph import DistGraph
from .dist_neighbor_sampler import make_dist_one_hop


class DistTrainStep:
  """One-program distributed train step over DistGraph + DistFeature.

  Args:
    dist_graph / dist_feature: the sharded stores (same mesh/axis).
    model: flax module over Batch.
    tx: optax optimizer.
    labels: [N] global labels (replicated; label lookups are cheap).
    fanouts, batch_size_per_device: sampling shape.
    edge_feature: optional edge-feature DistFeature (id space = global
      edge ids); when given, sampling emits eids and the batch carries
      ``edge_attr`` gathered through the same all_to_all path — the
      reference's efeat collate (dist_neighbor_sampler.py:689-807).
  """

  def __init__(self, dist_graph: DistGraph, dist_feature: DistFeature,
               model, tx, labels, fanouts: Sequence[int],
               batch_size_per_device: int,
               edge_feature: Optional[DistFeature] = None):
    from ..parallel.dist_feature import require_device_resident
    require_device_resident(dist_feature, 'DistTrainStep features')
    require_device_resident(edge_feature, 'DistTrainStep edge features')
    self.g = dist_graph
    self.f = dist_feature
    self.ef = edge_feature
    self.model = model
    self.tx = tx
    self.fanouts = list(fanouts)
    self.bs = int(batch_size_per_device)
    self.mesh = dist_graph.mesh
    self.axis = dist_graph.axis
    self.labels = jax.device_put(
        np.asarray(labels), NamedSharding(self.mesh, P()))
    self._step_fn = self._build()

  def _dummy_batch(self) -> Batch:
    from ..ops.pipeline import sample_budget
    budget = sample_budget(self.bs, self.fanouts)
    ecap = edge_hop_offsets(self.bs, self.fanouts)[-1]
    return Batch(
        x=jnp.zeros((budget, self.f.feature_dim)),
        row=jnp.zeros((ecap,), jnp.int32),
        col=jnp.zeros((ecap,), jnp.int32),
        edge_mask=jnp.zeros((ecap,), bool),
        node=jnp.zeros((budget,), jnp.int32),
        node_count=jnp.zeros((), jnp.int32),
        y=jnp.zeros((self.bs,), jnp.int32),
        edge=(jnp.zeros((ecap,), jnp.int32)
              if self.ef is not None else None),
        edge_attr=(jnp.zeros((ecap, self.ef.feature_dim))
                   if self.ef is not None else None),
        batch_size=self.bs,
        edge_hop_offsets=tuple(edge_hop_offsets(self.bs, self.fanouts)))

  def init_params(self, key):
    params = self.model.init(key, self._dummy_batch())
    return replicate(params, self.mesh)

  def _build(self):
    g, f, ef = self.g, self.f, self.ef
    model, tx, axis, bs = self.model, self.tx, self.axis, self.bs
    fanouts = self.fanouts
    offs = tuple(edge_hop_offsets(bs, fanouts))
    n_parts = g.num_partitions
    with_edge = ef is not None

    f_off = f.cold_array is not None
    ef_off = ef is not None and ef.cold_array is not None

    def device_step(params, opt_state, indptr, indices, geids, local_row,
                    node_pb, feats, id2index, feat_pb, labels, seeds,
                    n_valid, key, *rest):
      rest = list(rest)
      fcold = rest.pop(0) if f_off else None
      efeats, eid2index, efeat_pb = \
          (rest[:3] if with_edge else (None,) * 3)
      efcold = rest[3] if ef_off else None
      shards = dict(indptr=indptr[0], indices=indices[0],
                    edge_ids=geids[0], local_row=local_row[0],
                    node_pb=node_pb)
      one_hop = make_dist_one_hop(shards, g.num_nodes, n_parts,
                                  g.max_rows, axis)
      my_key = jax.random.fold_in(key[0], jax.lax.axis_index(axis))
      out = multihop_sample(one_hop, seeds, n_valid[0], fanouts, my_key,
                            with_edge=with_edge)
      node_valid = jnp.arange(out['node'].shape[0]) < out['node_count']
      x = f.lookup_local(feats[0], id2index[0], feat_pb[0],
                         jnp.maximum(out['node'], 0), node_valid,
                         axis_name=axis,
                         cold_shard=fcold[0] if f_off else None)
      edge_attr = None
      if with_edge:
        # the efeat collate of the reference loop, as one more
        # all_to_all over the sampled global edge ids
        edge_attr = ef.lookup_local(
            efeats[0], eid2index[0], efeat_pb[0],
            jnp.maximum(out['edge'], 0), out['edge_mask'],
            axis_name=axis,
            cold_shard=efcold[0] if ef_off else None)
      y = jnp.take(labels, jnp.maximum(out['batch'], 0)[:bs])
      batch = Batch(x=x, row=out['row'], col=out['col'],
                    edge_mask=out['edge_mask'], node=out['node'],
                    node_count=out['node_count'], y=y, batch_size=bs,
                    edge=out.get('edge'), edge_attr=edge_attr,
                    edge_hop_offsets=offs)

      def loss_fn(p):
        logits = model.apply(p, batch)
        mask = jnp.arange(bs) < n_valid[0]
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, y)
        return (jnp.where(mask, losses, 0).sum()
                / jnp.maximum(mask.sum(), 1))

      loss, grads = jax.value_and_grad(loss_fn)(params)
      grads = jax.lax.pmean(grads, axis)
      loss = jax.lax.pmean(loss, axis)
      updates, opt_state = tx.update(grads, opt_state, params)
      params = optax.apply_updates(params, updates)
      return params, opt_state, loss[None]

    sp = P(self.axis)
    extra = ((sp,) if f_off else ()) \
        + ((sp, sp, sp) if with_edge else ()) \
        + ((sp,) if ef_off else ())
    fn = jax.shard_map(
        device_step, mesh=self.mesh,
        in_specs=(P(), P(), sp, sp, sp, sp, P(), sp, sp, sp, P(), sp, sp,
                  sp) + extra,
        out_specs=(P(), P(), sp),
        check_vma=False)

    # global arrays enter as jit ARGUMENTS (closure constants cannot
    # span processes in multi-host runs)
    step = jax.jit(fn)

    def run(params, opt_state, seeds, n_valid, keys):
      eargs = ((f.cold_array,) if f_off else ()) \
          + ((ef.array, ef.id2index, ef.feat_pb) if with_edge else ()) \
          + ((ef.cold_array,) if ef_off else ())
      return step(params, opt_state, g.indptr, g.indices, g.edge_ids,
                  g.local_row, g.node_pb, f.array, f.id2index,
                  f.feat_pb, self.labels, seeds, n_valid, keys, *eargs)

    return run

  def __call__(self, params, opt_state, seeds, n_valid_per_device, key):
    n_dev = self.mesh.shape[self.axis]
    shard = NamedSharding(self.mesh, P(self.axis))
    seeds = jax.device_put(
        jnp.asarray(np.asarray(seeds).reshape(-1), jnp.int32), shard)
    nv = jax.device_put(
        jnp.asarray(n_valid_per_device, jnp.int32), shard)
    keys = jax.random.split(key, n_dev)
    params, opt_state = replicate((params, opt_state), self.mesh)
    params, opt_state, loss = self._step_fn(params, opt_state, seeds, nv,
                                            keys)
    return params, opt_state, loss
