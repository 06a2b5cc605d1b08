"""User-item link prediction over learnable id embeddings: the model of
the reference's examples/hetero/bipartite_sage_unsup.py (PyG's script of
that name, on Taobao), on the typed models' one plan (models/plan.py).

The nodes have no features. A node is its id, read through one embedding
table a node type; the tables are ordinary parameters of the model,
trained by the optimizer with everything else. Three message relations:
items into users (``item_user``), items into items (``item_item``), and
users into items, which neither encoder reads.

  item encoder  ``SAGEConv`` twice over ``item_item``, relu after each,
                then a linear layer;
  user encoder  ``conv1`` over ``item_item`` into items, ``conv2`` over
                ``item_user`` from the items' embeddings into users,
                ``conv3`` over ``item_user`` from ``conv1``'s items into
                ``conv2``'s users, relu after each, then a linear layer;
  decoder       ``lin2(relu(lin1([z_user[row] ; z_item[col]])))`` for the
                pairs of ``metadata['edge_label_index']``: their logits.

Under the plan's promises (``DistHeteroTrainStep``'s batches carry all
three) the first layers compute the items within one hop of a seed, the
second the seeds alone, the users' table is read at the seed users only,
and ``SAGEConv`` reduces a parent's children over the fanout axis. A
loader's batch (``LinkNeighborLoader`` over a typed edge) promises none
of it and computes every row over segments: the same values at the seeds.
"""
from __future__ import annotations

from typing import Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..loader.transform import HeteroBatch
from ..typing import EdgeType, NodeType
from . import plan
from .conv import SAGEConv

NUM_LAYERS = 2   # the depth of both encoders in hops


class IdEmbedding(nn.Module):
  """A table of ``num_embeddings`` rows read by node id: N(0, 1) at the
  start, as ``torch.nn.Embedding``. ``ids`` are distinct over the first
  ``count`` slots (a batch's ``node_dict`` and ``node_count_dict``); a
  slot past them reads zeros. The take says so (``unique_indices``: a
  dead slot is sent past the end, each to an index of its own), and its
  transpose adds every row of the cotangent into the table's dense
  gradient once."""
  num_embeddings: int
  features: int

  @nn.compact
  def __call__(self, ids: jax.Array, count: jax.Array) -> jax.Array:
    table = self.param('embedding', nn.initializers.normal(1.0),
                       (self.num_embeddings, self.features), jnp.float32)
    slot = jnp.arange(ids.shape[0], dtype=ids.dtype)
    live = (slot < count) & (ids >= 0) & (ids < self.num_embeddings)
    at = jnp.where(live, ids, self.num_embeddings + slot)
    return table.at[at].get(mode='fill', fill_value=0.0,
                            unique_indices=True)


class ItemEncoder(nn.Module):
  hidden_features: int
  out_features: int

  @nn.compact
  def __call__(self, x_item, item_item):
    """``item_item``: ``(row, col, mask, num_out, groups)`` of the
    relation under each layer's plan."""
    for i, (row, col, mask, num_out, groups) in enumerate(item_item):
      x_item = nn.relu(SAGEConv(self.hidden_features, name=f'conv{i + 1}')(
          x_item, row, col, mask, num_out, None, groups))
    return nn.Dense(self.out_features, name='lin')(x_item)


class UserEncoder(nn.Module):
  hidden_features: int
  out_features: int

  @nn.compact
  def __call__(self, x_item, x_user, item_item, item_user):
    """``item_item`` under the first layer's plan and ``item_user`` under
    the second's, as ``ItemEncoder`` takes a relation. Both convolutions
    over ``item_user`` write the rows of the seed users."""
    conv = lambda i: SAGEConv(self.hidden_features, name=f'conv{i}')
    row, col, mask, num_out, groups = item_item
    item_x = nn.relu(conv(1)(x_item, row, col, mask, num_out, None, groups))
    row, col, mask, num_out, groups = item_user
    user_x = nn.relu(conv(2)(x_item, row, col, mask, num_out, x_user,
                             groups))
    user_x = nn.relu(conv(3)(item_x, row, col, mask, num_out, user_x,
                             groups))
    return nn.Dense(self.out_features, name='lin')(user_x)


class EdgeDecoder(nn.Module):
  hidden_features: int

  @nn.compact
  def __call__(self, z_src, z_dst, edge_label_index):
    row, col = jnp.maximum(edge_label_index, 0)
    z = jnp.concatenate([jnp.take(z_src, row, axis=0),
                         jnp.take(z_dst, col, axis=0)], axis=-1)
    z = nn.relu(nn.Dense(self.hidden_features, name='lin1')(z))
    return nn.Dense(1, name='lin2')(z)[:, 0]


class BipartiteSAGE(nn.Module):
  """``num_nodes``: the rows of each type's table. ``item_user`` and
  ``item_item`` are the batch's message-flow keys of the two relations
  the encoders read (``reverse_edge_type`` of the stored relations of a
  graph sampled along out-edges). Returns the logits of the pairs of
  ``batch.metadata['edge_label_index']`` (users' rows, items' rows)."""
  num_nodes: Dict[NodeType, int]
  item_user: EdgeType
  item_item: EdgeType
  user_type: NodeType = 'user'
  item_type: NodeType = 'item'
  hidden_features: int = 64
  out_features: int = 64
  trim: bool = True

  #: the top-level collections of parameters that are embedding tables:
  #: the typed step updates them under a scope of their own
  table_params = ('embed_user', 'embed_item')

  @property
  def embedding_tables(self) -> Dict[NodeType, int]:
    """Rows of each type's table, for the step's gauge."""
    return {self.user_type: int(self.num_nodes[self.user_type]),
            self.item_type: int(self.num_nodes[self.item_type])}

  def layer_plan(self, batch: HeteroBatch):
    return plan.layer_plan(batch, NUM_LAYERS, self.trim)

  def layer_rows(self, batch: HeteroBatch):
    """``[{type: output rows}]`` a layer, as the step's counter reads."""
    return plan.layer_rows(self.layer_plan(batch), batch)

  def layer_groups(self, batch: HeteroBatch):
    """``[{relation: groups}]`` a layer, of the relations a layer reads:
    items into items in both, items into users in the second."""
    reads = ((self.item_item,), (self.item_item, self.item_user))
    return [{e: sum(s for _, s, _ in (groups or {}).get(e, ()))
             for e in read}
            for read, (_, _, groups) in zip(reads, self.layer_plan(batch))]

  def embedding_slots(self, batch: HeteroBatch) -> Dict[NodeType, int]:
    """Node slots of each type whose table rows the model reads: every
    item slot, and of the users the rows the second layer writes (the
    seed users under the node trim, else all)."""
    rows = self.layer_plan(batch)[-1][1]
    users = batch.node_dict[self.user_type].shape[0]
    return {self.item_type: batch.node_dict[self.item_type].shape[0],
            self.user_type: users if rows is None else rows[self.user_type]}

  @nn.compact
  def __call__(self, batch: HeteroBatch) -> jax.Array:
    user, item = self.user_type, self.item_type
    n_user = self.embedding_slots(batch)[user]
    x_item = IdEmbedding(self.num_nodes[item], self.hidden_features,
                         name='embed_item')(
                             batch.node_dict[item],
                             batch.node_count_dict[item])
    x_user = IdEmbedding(self.num_nodes[user], self.hidden_features,
                         name='embed_user')(
                             batch.node_dict[user][:n_user],
                             batch.node_count_dict[user])

    def relation(e, ends, rows, groups, dst):
      cut = lambda d: plan.cut_edges(d, ends)[e]
      return (cut(batch.row_dict), cut(batch.col_dict),
              cut(batch.edge_mask_dict),
              None if rows is None else rows[dst],
              None if groups is None else groups.get(e))

    first, second = self.layer_plan(batch)
    ii = [relation(self.item_item, *layer, item) for layer in (first, second)]
    iu = relation(self.item_user, *second, user)
    z_item = ItemEncoder(self.hidden_features, self.out_features,
                         name='item_encoder')(x_item, ii)
    z_user = UserEncoder(self.hidden_features, self.out_features,
                         name='user_encoder')(x_item, x_user, ii[0], iu)
    return EdgeDecoder(self.out_features, name='decoder')(
        z_user, z_item, batch.metadata['edge_label_index'])
