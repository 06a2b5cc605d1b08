"""DGCNN — the SEAL link-prediction model (sort-pool readout).

Reference: examples/seal_link_pred.py:151-193 (stacked GCNConvs ->
global_sort_pool(k) -> Conv1d/MaxPool1d stack -> MLP -> 1 logit), and
SEAL_OGB's form of it (``max_z``: an embedding of the DRNL label
concatenated with the node's features). One module, two ways in, one
parameter tree:

* ONE padded subgraph as edge slots (``x [N, F]``, ``row``, ``col``,
  ``edge_mask``, ``node_mask``): what a loader's batch holds;
  ``jax.vmap`` batches it.
* a batch of ``L`` subgraphs as dense blocks (``x [L, S, F]``, ``adj
  [L, S, S]``, ``node_mask [L, S]``): what the fused step extracts
  (``ops/subgraph.py::enclosing_subgraphs``); every product of the GCN
  stack is then a batched matmul, and the readout is one ``top_k`` a
  graph.

Exactness: the model sees the edges it is given. The loader path's
extraction is exact only where its ``max_degree`` window bounds every
member's row, and truncates silently past it; the fused step's is exact
inside its budgets and counts what they cannot hold
(``ops/subgraph.py``'s text has both contracts). Ties of the sort key
go to the lower slot (``lax.top_k``); a graph with fewer than ``k``
nodes is padded with zero rows, as PyG's ``global_sort_pool`` pads.
"""
from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from .conv import DenseGCNConv, GCNConv


class _NodeConv1d(nn.Module):
  """``Conv1d(1, C, F, F)`` over the pooled ``[.., k * F]`` sequence:
  one window a node, so a product ``[.., k, F] x [F, C]``. The
  parameters are ``nn.Conv``'s (``kernel [F, 1, C]``, ``bias [C]``)."""
  features: int

  @nn.compact
  def __call__(self, pooled):
    kernel = self.param('kernel', nn.initializers.lecun_normal(),
                        (pooled.shape[-1], 1, self.features))
    bias = self.param('bias', nn.initializers.zeros, (self.features,))
    return pooled @ kernel[:, 0, :] + bias


class DGCNN(nn.Module):
  """See the module's text for the two ways in.

  Args:
    hidden: GCN hidden width (reference: 32).
    num_layers: number of hidden GCN layers (reference: 3); one extra
      1-channel conv provides the sort key.
    k: sort-pool size (static; reference computes the 60th-percentile
      subgraph size — pass that in).
    max_z: 0: ``x`` is the node input as it stands (the upstream
      example's one-hot labels). Positive: SEAL_OGB's input,
      ``Embedding(max_z, hidden)`` of the integer labels ``z``
      concatenated in front of the features ``x``.
  """
  hidden: int = 32
  num_layers: int = 3
  k: int = 30
  conv1d_channels: Sequence[int] = (16, 32)
  mlp_hidden: int = 128
  max_z: int = 0

  @nn.compact
  def __call__(self, x, row=None, col=None, edge_mask=None, node_mask=None,
               deterministic: bool = True, *, z=None, adj=None,
               with_order: bool = False):
    # the conv1d/maxpool stack needs floor((k-2)/2+1) - 5 + 1 >= 1
    # (the reference enforces the same with k = max(10, percentile))
    assert self.k >= 10, 'DGCNN sort-pool k must be >= 10'
    dense = adj is not None
    if dense:
      adj = adj.astype(jnp.float32)
      conv = lambda width, name, h: DenseGCNConv(width, name=name)(h, adj)
    else:
      conv = lambda width, name, h: GCNConv(width, name=name)(
          h, row, col, edge_mask)
    h = x
    if self.max_z:
      h = jnp.concatenate(
          [nn.Embed(self.max_z, self.hidden, name='z_embed')(z), x],
          axis=-1)
    # GCN stack; tanh and channel-concat as the reference does
    xs = []
    for i in range(self.num_layers):
      h = jnp.tanh(conv(self.hidden, f'gcn{i}', h))
      xs.append(h)
    xs.append(jnp.tanh(conv(1, 'gcn_key', h)))
    h = jnp.concatenate(xs, axis=-1)        # [.., N, hidden*L + 1]
    if not dense:
      h, node_mask = h[None], node_mask[None]
    h = jnp.where(node_mask[..., None], h, 0.0)

    with jax.named_scope('sort_pool'):
      # global_sort_pool: the k nodes with the largest sort key, in
      # descending order (invalid nodes sink to the bottom); the take is
      # a 0/1 product at ``highest``, which rounds nothing and whose
      # transpose is a product too
      keyv = jnp.where(node_mask, h[..., -1], -jnp.inf)
      _, top = jax.lax.top_k(keyv, self.k)  # [L, k]
      pick = (top[..., None] == jnp.arange(h.shape[1])) \
          & node_mask[:, None, :]
      pooled = jnp.einsum('lkn,lnf->lkf', pick.astype(h.dtype), h,
                          precision=jax.lax.Precision.HIGHEST)

    with jax.named_scope('conv1d'):
      # Conv1d(1, C1, F, F) reads one node a step
      y = nn.relu(_NodeConv1d(self.conv1d_channels[0],
                              name='conv1')(pooled))      # [L, k, C1]
      y = nn.max_pool(y, window_shape=(2,), strides=(2,))
      y = nn.Conv(self.conv1d_channels[1], kernel_size=(5,), strides=(1,),
                  padding='VALID', name='conv2')(y)
      y = nn.relu(y).reshape(y.shape[0], -1)              # dense_dim

    with jax.named_scope('mlp'):
      y = nn.relu(nn.Dense(self.mlp_hidden, name='mlp0')(y))
      y = nn.Dropout(0.5, deterministic=deterministic)(y)
      y = nn.Dense(1, name='mlp1')(y)[:, 0]               # [L] logits
    if with_order:                  # also the slots the readout kept
      return (y, top) if dense else (y[0], top[0])
    return y if dense else y[0]
