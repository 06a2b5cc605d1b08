"""Heterogeneous Graph Transformer (HGT): Hu, Dong, Wang, Sun, WWW 2020
(arXiv:2003.01332), in the form of PyG's ``HGTConv`` and of the reference's
examples/hetero/train_hgt_mag.py (+_mp variant): one input linear a node
type, a stack of ``HGTConv`` layers, a linear head on the seed type.

One layer, ``H`` heads of ``d`` (``F = H d``), node ``v`` of type
``tau(v)``, relation ``r`` from child type to parent type:

  ``K_v = W_K[tau(v)] h_v + b``, ``Q_v``, ``V_v`` likewise, each ``[H, d]``;
  for a sampled edge from child ``u`` to parent ``v`` in relation ``r``:
  ``k = K_u^h A_r^h``, ``m = V_u^h M_r^h`` (``A_r``, ``M_r``: ``[H, d, d]``),
  ``a = (Q_v^h . k) mu_r^h / sqrt(d)``;
  ``alpha = exp(a - max) / sum``, **max and sum over every valid sampled
  edge into ``v``, all relations together** (the paper's eq. 3), exactly;
  ``g_v = concat_h sum alpha m``, and 0 for a parent with no valid child;
  ``o_v = W_O[tau(v)] gelu(g_v) + b``;
  ``h'_v = sigmoid(s[tau(v)]) o_v + (1 - sigmoid(s[tau(v)])) h_v``.

What differs from the paper, and why: the learned gated skip (``s``, one
scalar a type, initialised to 1) stands where the paper has a plain
residual, as in PyG and in the authors' pyHGT; the input is
``relu(W_in[tau] x + b)`` as the reference's script has it; the gelu is
the exact (erf) one, PyTorch's default; no dropout, no layer norm (PyG's
``HGTConv`` has neither) and no relative temporal encoding (the graphs
here carry no timestamps). ``mu`` and ``s`` start at 1, ``A`` and ``M``
glorot (PyG's).

One algorithm for every caller, chosen from the batch's static promise
as ``GATConv``'s is. With ``HeteroBatch.hop_fanouts_dict`` (the typed
step's batches: a relation's edge slots are parent-major, and the
relations into one type expand the same frontier, so their groups line
up parent by parent) a relation is a closed unit that reduces its own
``[K, S]`` slots over the fanout axis to a running maximum, a sum of
exponentials and a weighted sum of messages a group, and the relations
of a parent type are joined by the softmax's own rescaling (``exp(t_r -
T)``, the flash-attention identity): the exact joint softmax, with one
take of the children's rows a relation (all its hop blocks in one) and
one placing a parent type. ``K``, ``V`` and the ``A_r``, ``M_r``
products are computed per edge slot from the taken rows, in the order of
the equations (so that the matrix unit rounds what the reference
rounds): on the typed step's budgets a type has as many node slots as
edge slots read them, and no ``[n, 2F]`` array of every type is kept for
the backward pass. Without the promise (a loader's batch): ``K``, ``V``
per node, and two passes of ``segment_max`` / ``segment_sum`` over the
edges of all relations. The heads stay in the minor ``F`` lanes
throughout: a per-head sum or broadcast is a product with a constant 0/1
``[F, H]`` matrix at ``highest`` precision (exact), and ``A_r``, ``M_r``
are applied as block-diagonal ``[F, F]`` matrices, since ``[E, 8, 32]``
would tile to four times its size on a TPU.

The model honours what ``RGNN`` honours (models/plan.py): the edge trim,
the per-type node trim, the groups under the edge trim, ``remat`` (a
relation's unit is computed again in the backward pass, one unit at a
time, and the takes' transposes add into one accumulator a node type:
:func:`_relation_groups_again`), ``layer_rows`` and ``layer_groups`` for
the step's counters, and ``layer_joint_relations`` beside them.
"""
from __future__ import annotations

import functools
import itertools
from typing import NamedTuple, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..loader.transform import HeteroBatch
from ..typing import EdgeType, NodeType, as_str
from . import plan
from .conv import (_group_children, _group_parents, _group_rows,
                   _place_groups)

_EXACT = jax.lax.Precision.HIGHEST


class _Linear(nn.Module):
  """The parameters of a dense layer, handed out as arrays."""
  features: int

  @nn.compact
  def __call__(self, in_features):
    return (self.param('kernel', nn.initializers.lecun_normal(),
                       (in_features, self.features)),
            self.param('bias', nn.initializers.zeros, (self.features,)))


@jax.custom_vjp
def _relu(x):
  """``relu`` whose gradient reads the output: ``nn.relu`` keeps its
  float32 input for the backward pass, a second ``[n, F]`` array of every
  type beside the rows the layers read (1.6 GB at the typed cell's
  budgets). ``y != 0``, not ``y > 0``: XLA rewrites ``max(x, 0) > 0`` to
  ``x > 0`` and keeps ``x`` again."""
  return jnp.maximum(x, 0)


_relu.defvjp(lambda x: (jnp.maximum(x, 0),) * 2,
             lambda y, g: (jnp.where(y != 0, g, 0),))


def _head_selector(heads, f, dtype):
  """``[F, H]``, 1 where lane ``c`` belongs to head ``h``."""
  return (jnp.arange(f)[:, None] // (f // heads)
          == jnp.arange(heads)[None, :]).astype(dtype)


def _block_diagonal(w):
  """``[H, d, d]`` as the ``[F, F]`` matrix that applies ``w[h]`` to the
  lanes of head ``h``."""
  h, d, _ = w.shape
  eye = jnp.eye(h, dtype=w.dtype)
  return (eye[:, None, :, None] * w[:, :, None, :]).reshape(h * d, h * d)


def _apply_heads(x, w):
  """``x[..., h] @ w[h]`` a head: ``[..., F] x [H, d, d] -> [..., F]``."""
  return x @ _block_diagonal(w)


def _logits(products, sel, scale):
  """Per head the sum of ``q * key`` times ``scale``: ``[..., F] ->
  [..., H]``."""
  return jnp.dot(products, sel, precision=_EXACT) * scale


def _lanes(w, sel):
  """A value a head on each of the head's lanes: ``[..., H] -> [..., F]``."""
  return jnp.dot(w, sel.T, precision=_EXACT)


def _children(h_src, row, ok, groups):
  """A relation's children over all its hop blocks: the taken rows
  ``[E, F_in]``, block after block and ``[K, S]`` within a block (from
  the 2-D rows, a masked slot reading the row of its own position:
  models/conv.py::_group_children), each block's ``[K, S]`` mask, and the
  indices taken. One take a relation, so one transposed scatter-add."""
  n = h_src.shape[0]
  found = [_group_children(row, ok, group, n) for group in groups]
  idx = jnp.concatenate([i for i, _ in found])
  return (jnp.take(h_src, idx, axis=0, mode='clip'),
          [live for _, live in found], idx)


def _relation_stats(src, lives, q_blocks, wk, bk, wv, bv, att, msg, scale,
                    groups, heads):
  """One relation from its children's taken rows: per hop block the
  running maximum ``[S, H]`` of a group's logits, the sum ``[S, H]`` of
  their exponentials under it, and the sum ``[S, F]`` of the messages so
  weighted. ``q_blocks``: the parents' queries a block, ``[S, F]``. The
  matrix products run once over the slots of all blocks, the reduces
  over the fanout axis a block at a time."""
  f = wk.shape[1]
  sel = _head_selector(heads, f, wk.dtype)
  ends = list(itertools.accumulate(s * k for _, s, k in groups))
  cut = lambda a: [a[hi - s * k:hi].reshape((k, s) + a.shape[1:])
                   for hi, (_, s, k) in zip(ends, groups)]
  with jax.named_scope('transform'):
    key = _apply_heads(src @ wk + bk, att)                   # [E, F]
    val = _apply_heads(src @ wv + bv, msg)
  with jax.named_scope('attention'):
    logit = _logits(jnp.concatenate(
        [(k_b * q).reshape(-1, f) for k_b, q in zip(cut(key), q_blocks)]),
                    sel, scale)                              # [E, H]
    tops, zs = [], []
    for logit_b, live in zip(cut(logit), lives):             # [K, S, H]
      live = live[:, :, None]
      top = jnp.where(live, logit_b, -jnp.inf).max(axis=0)
      # the softmax does not depend on what is subtracted
      top = jax.lax.stop_gradient(jnp.where(jnp.isfinite(top), top, 0.0))
      # a masked slot's own logit may overflow: it is -inf before exp
      zs.append(jnp.exp(jnp.where(live, logit_b - top, -jnp.inf)))
      tops.append(top)
    weights = _lanes(jnp.concatenate(
        [z.reshape(-1, heads) for z in zs]), sel)            # [E, F]
    return [(top, z.sum(axis=0), (v_b * w_b).sum(axis=0))
            for top, z, v_b, w_b in zip(tops, zs, cut(val), cut(weights))]


def _relation_groups(h_src, q_blocks, row, ok, wk, bk, wv, bv, att, msg,
                     scale, groups, heads):
  """One relation over parent-major edge slots (``groups`` as
  models/conv.py::grouped_aggregate's), a closed unit: the take of its
  children's rows, then :func:`_relation_stats`. Hands ``h_src`` on, for
  the next unit that reads the same rows."""
  with jax.named_scope('transform'):
    src, lives, _ = _children(h_src, row, ok, groups)
  return _relation_stats(src, lives, q_blocks, wk, bk, wv, bv, att, msg,
                         scale, groups, heads), h_src


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12))
def _relation_groups_again(h_src, q_blocks, row, ok, wk, bk, wv, bv, att,
                           msg, scale, groups, heads):
  """:func:`_relation_groups` with nothing kept for the backward pass but
  its arguments. Two things ``jax.checkpoint`` would not do (PERF.md,
  section 6, PR 33: 9.6 GB of temporaries at the typed cell's shapes
  with it). The unit is computed again only when its cotangents are
  there (an ``optimization_barrier`` ties the two; a recomputed forward
  depends on primal values alone, and XLA's scheduler ran those of every
  relation ahead of the first backward pass). And the take's transpose
  adds into the cotangent that the rows handed on have gathered from the
  units after this one: one accumulator a node type, where each relation
  and hop block scatter-added into zeros of its own and was summed
  later."""
  return _relation_groups(h_src, q_blocks, row, ok, wk, bk, wv, bv, att,
                          msg, scale, groups, heads)


def _again_fwd(*args):
  return _relation_groups(*args), args[:11]


def _again_bwd(groups, heads, saved, cts):
  saved, (ct_stats, ct_rows) = jax.lax.optimization_barrier((saved, cts))
  h_src, q_blocks, row, ok = saved[:4]
  with jax.named_scope('transform'):
    src, lives, idx = _children(h_src, row, ok, groups)
  _, vjp = jax.vjp(
      lambda src, *rest: _relation_stats(src, lives, *rest, groups, heads),
      src, q_blocks, *saved[4:])
  d_src, d_q, *d_rest = vjp(ct_stats)
  with jax.named_scope('transform'):
    ct_rows = ct_rows.at[idx].add(d_src)
  # labels and the mask take no cotangent
  return (ct_rows, d_q, None, None, *d_rest)


_relation_groups_again.defvjp(_again_fwd, _again_bwd)


class Relation(NamedTuple):
  """One relation into a parent type, as the two forms below take it:
  its name, the rows its children are read from (``[n, F]``: the source
  type's own for the grouped form, which projects per edge slot with
  ``key_lin`` and ``val_lin`` = ``(kernel, bias)``; for the segment form
  the source type's keys and values, ``(K, V)``, and no linears), the
  children's and parents' labels and the mask of its edge slots, ``A_r``,
  ``M_r`` (``[H, d, d]``) and ``mu_r`` (``[H]``); ``src_type`` names the
  rows for the grouped form's ``chain``."""
  name: str
  src: object
  row: jax.Array
  col: jax.Array
  mask: jax.Array
  att: jax.Array
  msg: jax.Array
  prior: jax.Array
  key_lin: tuple = ()
  val_lin: tuple = ()
  src_type: object = None


def _scale(r, d, dtype):
  return r.prior / jnp.sqrt(jnp.asarray(d, dtype))


def grouped_joint_attention(q, relations, blocks, heads, remat=False,
                            scope='dst', chain=None):
  """``g`` ``[m, F]`` of one parent type over parent-major edge slots:
  each relation's unit (:func:`_relation_groups`), the units of one hop
  joined group by group into one softmax, the results placed at their
  parents once. ``q``: the parents' queries ``[m, F]``; ``blocks[i]``:
  relation ``i``'s static ``(offset, S, K, hop)`` blocks
  (models/plan.py::group_hops); blocks of one ``hop`` share their
  parents. ``chain`` (a dict, empty at first, handed from call to call
  within a layer): the units run one after another, forward and
  backward, each behind an ``optimization_barrier`` with the one before
  it (left to itself XLA's scheduler has the units of every relation in
  flight at once, a quarter of a gigabyte an array each at the typed
  cell's shapes: PERF.md, section 6, PR 33), and a unit reads the rows
  of its ``src_type`` as the unit before it that read them handed them
  on, so that their cotangent gathers in one array."""
  m, f = q.shape
  sel = _head_selector(heads, f, q.dtype)
  hops = sorted({g[3] for mine in blocks for g in mine})
  if not hops:
    return jnp.zeros((m, f), q.dtype)
  oks = [r.mask & (r.row >= 0) & (r.row < r.src.shape[0]) & (r.col >= 0)
         & (r.col < m) for r in relations]
  parents, kept = {}, {}
  for hop in hops:
    at = [(r, ok, g) for r, ok, mine in zip(relations, oks, blocks)
          for g in mine if g[3] == hop]
    if len({g[1] for _, _, g in at}) != 1:
      raise ValueError(
          f'hop_fanouts_dict: the relations into {scope!r} do not share '
          f'hop {hop}\'s parents: {[(r.name, g) for r, _, g in at]}')
    for _, ok, (off, s, k, _) in at:
      live = ok[off:off + s * k].reshape(s, k).any(axis=1)
      kept[hop] = kept[hop] | live if hop in kept else live
    parents[hop] = _group_parents(at[0][0].col, at[0][2][:3])
  all_parents = jnp.concatenate([parents[p] for p in hops])
  all_kept = jnp.concatenate([kept[p] for p in hops])
  with jax.named_scope('softmax'), jax.named_scope(scope):
    q_groups = q.at[_group_rows(all_parents, all_kept, m)].get(
        mode='fill', fill_value=0.0, unique_indices=True)   # [sum S, F]
    q_of, lo = {}, 0
    for hop in hops:
      s = parents[hop].shape[0]
      q_of[hop], lo = q_groups[lo:lo + s], lo + s
  unit = _relation_groups_again if remat else _relation_groups
  stats, state = {}, {} if chain is None else chain
  for r, ok, mine in zip(relations, oks, blocks):
    if not mine:
      continue
    src = state.get(('rows', r.src_type), r.src)
    if 'after' in state:   # not before the unit before it is done
      src, _ = jax.lax.optimization_barrier((src, state['after']))
    with jax.named_scope('rel_' + r.name):
      got, handed_on = unit(
          src, [q_of[g[3]] for g in mine], r.row, ok, *r.key_lin,
          *r.val_lin, r.att, r.msg, _scale(r, f // heads, q.dtype),
          tuple(g[:3] for g in mine), heads)
    state['after'] = got
    if r.src_type is not None:
      state['rows', r.src_type] = handed_on
    for g, one in zip(mine, got):
      stats.setdefault(g[3], []).append(one)
  vals = []
  for hop in hops:
    tops, zs, us = zip(*stats[hop])
    with jax.named_scope('softmax'), jax.named_scope(scope):
      # a relation with no live slot in a group (z 0) stays out of it
      top = jnp.stack([jnp.where(z > 0, t_r, -jnp.inf)
                       for t_r, z in zip(tops, zs)]).max(axis=0)
      top = jax.lax.stop_gradient(jnp.where(jnp.isfinite(top), top, 0.0))
      rescale = [jnp.where(z > 0, jnp.exp(t_r - top), 0.0)
                 for t_r, z in zip(tops, zs)]                 # [S, H]
      denom = sum(w * z for w, z in zip(rescale, zs))
    with jax.named_scope('aggregate'), jax.named_scope(scope):
      total = sum(_lanes(w, sel) * u for w, u in zip(rescale, us))
      vals.append(total / _lanes(jnp.maximum(denom, 1e-16), sel))
  with jax.named_scope('aggregate'), jax.named_scope(scope):
    return _place_groups(jnp.concatenate(vals), all_parents, all_kept, m)


def segment_joint_attention(q, relations, heads, scope='dst'):
  """The same ``g`` with no promise of the edge slots' order: a maximum
  and two sums over the segments of the edges of all relations into the
  type, from keys and values per node (``r.src = (K, V)``)."""
  m, f = q.shape
  sel = _head_selector(heads, f, q.dtype)
  edges = []
  for r in relations:
    keys, values = r.src
    n = keys.shape[0]
    ok = r.mask & (r.row >= 0) & (r.row < n) & (r.col >= 0) & (r.col < m)
    with jax.named_scope('rel_' + r.name):
      with jax.named_scope('transform'):
        at = jnp.clip(r.row, 0, n - 1)
        key = _apply_heads(jnp.take(keys, at, axis=0), r.att)
        val = _apply_heads(jnp.take(values, at, axis=0), r.msg)
      with jax.named_scope('attention'):
        logit = _logits(
            key * jnp.take(q, jnp.clip(r.col, 0, m - 1), axis=0), sel,
            _scale(r, f // heads, q.dtype))                    # [E, H]
    edges.append((logit, val, jnp.where(ok, r.col, m), ok[:, None]))
  if not edges:
    return jnp.zeros((m, f), q.dtype)
  with jax.named_scope('softmax'), jax.named_scope(scope):
    top = jnp.stack([jax.ops.segment_max(
        jnp.where(ok, logit, -jnp.inf), seg, m + 1)
                     for logit, _, seg, ok in edges]).max(axis=0)
    top = jax.lax.stop_gradient(jnp.where(jnp.isfinite(top), top, 0.0))
    zs = [jnp.exp(jnp.where(ok, logit - top[seg], -jnp.inf))
          for logit, _, seg, ok in edges]
    denom = sum(jax.ops.segment_sum(z, seg, m + 1)
                for z, (_, _, seg, _) in zip(zs, edges))
  with jax.named_scope('aggregate'), jax.named_scope(scope):
    total = sum(jax.ops.segment_sum(val * _lanes(z, sel), seg, m + 1)
                for z, (_, val, seg, _) in zip(zs, edges))
    return (total / _lanes(jnp.maximum(denom, 1e-16), sel))[:m]


class HGTConv(nn.Module):
  """One HGT layer over a typed batch's rows and padded edge lists.

  ``num_out[t]`` (static): output rows to compute for type ``t``
  (``None`` or a type left out: every row); children are read from every
  row given. ``groups[e]`` (static): relation ``e``'s ``(offset, S, K,
  hop)`` blocks, the producer's promise that its slots are parent-major
  and that the blocks of one ``hop`` into one type share their parents
  (module docstring); ``None``: no promise, the segment form.
  """
  node_types: Sequence[NodeType]
  edge_types: Sequence[EdgeType]
  out_features: int
  heads: int = 2
  remat: bool = False

  @nn.compact
  def __call__(self, x_dict, row_dict, col_dict, mask_dict, num_out=None,
               groups=None):
    h, f = self.heads, self.out_features
    assert f % h == 0, (f, h)
    d = f // h
    types = [t for t in self.node_types if t in x_dict]
    rows_of = lambda t: (num_out or {}).get(t, x_dict[t].shape[0])
    lin = {(n, t): _Linear(f, name=f'{n}_{t}')(x_dict[t].shape[-1])
           for t in types for n in 'kqva'}
    skip = {t: self.param(f'skip_{t}', nn.initializers.ones, ())
            for t in types}
    project = lambda n, t, x: x @ lin[n, t][0] + lin[n, t][1]
    # the rows written: sliced once, so their gradient is padded once
    top = {t: x_dict[t][:rows_of(t)] for t in types}
    src, q_dict = dict(x_dict), {}
    for t in types:
      with jax.named_scope('kqv'), jax.named_scope(t):
        q_dict[t] = project('q', t, top[t])
        if groups is None:   # keys and values per node
          src[t] = (project('k', t, x_dict[t]), project('v', t, x_dict[t]))
    into = {t: [] for t in types}
    for etype in self.edge_types:
      s, _, t = etype
      if etype not in row_dict or s not in types or t not in types:
        continue
      name = as_str(etype)
      glorot = nn.initializers.glorot_uniform()
      into[t].append((etype, Relation(
          name, src[s], row_dict[etype], col_dict[etype], mask_dict[etype],
          self.param(f'watt_{name}', glorot, (h, d, d)),
          self.param(f'wmsg_{name}', glorot, (h, d, d)),
          self.param(f'prior_{name}', nn.initializers.ones, (h,)),
          lin['k', s], lin['v', s], s)))
    out, chain = {}, {}
    for t in types:
      relations = [r for _, r in into[t]]
      if groups is None:
        g = segment_joint_attention(q_dict[t], relations, h, t)
      else:
        g = grouped_joint_attention(
            q_dict[t], relations, [groups.get(e, ()) for e, _ in into[t]],
            h, self.remat, t, chain)
      with jax.named_scope('out'), jax.named_scope(t):
        o = project('a', t, nn.gelu(g, approximate=False))
        if top[t].shape[-1] == f:   # PyG: the skip where the widths agree
          gate = nn.sigmoid(skip[t])
          o = gate * o + (1 - gate) * top[t]
        out[t] = o
    return out


class HGT(nn.Module):
  """HGT stack with input projections per node type and a task head on
  the seed type (the train_hgt_mag topology), under the typed models'
  one plan (models/plan.py): the edge trim, the per-type node trim and
  the groups of ``RGNN``. ``remat``: a relation's unit is computed again
  in the backward pass instead of keeping its per-slot keys, values and
  messages."""
  node_types: Sequence[NodeType]
  edge_types: Sequence[EdgeType]
  hidden_features: int
  out_features: int
  num_layers: int = 2
  heads: int = 2
  trim: bool = True
  remat: bool = False

  def layer_plan(self, batch: HeteroBatch, return_all: bool = False):
    """Per layer ``(edge_ends, rows, groups)`` (models/plan.py)."""
    return plan.layer_plan(batch, self.num_layers, self.trim, return_all)

  def layer_rows(self, batch: HeteroBatch, return_all: bool = False):
    """``[{type: output rows}]`` a layer, as the step's counter reads."""
    return plan.layer_rows(self.layer_plan(batch, return_all), batch)

  def layer_groups(self, batch: HeteroBatch):
    """``[{relation: groups}]`` a layer: the groups of adjacent edge
    slots a relation's unit reduces over the fanout axis, 0 where the
    layer runs the segment form."""
    return plan.layer_groups(self.layer_plan(batch), self.edge_types,
                             batch)

  def layer_joint_relations(self, batch: HeteroBatch):
    """``[{type: relations}]`` a layer: how many relations share a
    parent type's softmax (those with an edge slot under the layer's
    edge trim), 0 on a type that no relation reaches."""
    out = []
    for ends, _, _ in self.layer_plan(batch):
      offs = batch.edge_hop_offsets_dict if ends is not None else None
      # a hop of the relation that is not empty ends under the trim
      reads = lambda e: offs is None or e not in offs or any(
          0 < end <= ends[e] for end in offs[e][1:])
      out.append({t: sum(1 for e in self.edge_types
                         if e in batch.row_dict and e[2] == t
                         and e[0] in batch.x_dict and reads(e))
                  for t in self.node_types if t in batch.x_dict})
    return out

  @nn.compact
  def __call__(self, batch: HeteroBatch, train: bool = False,
               return_all: bool = False):
    x_dict = {t: _relu(nn.Dense(self.hidden_features, name=f'in_{t}')(x))
              for t, x in batch.x_dict.items()}
    offs = batch.edge_hop_offsets_dict
    for i, (ends, rows, groups) in enumerate(
        self.layer_plan(batch, return_all)):
      cut = lambda d: plan.cut_edges(d, ends)
      x_dict = HGTConv(node_types=list(self.node_types),
                       edge_types=list(self.edge_types),
                       out_features=self.hidden_features,
                       heads=self.heads, remat=self.remat,
                       name=f'layer{i}')(
                           x_dict, cut(batch.row_dict),
                           cut(batch.col_dict), cut(batch.edge_mask_dict),
                           rows, plan.group_hops(groups, offs))
    if return_all:
      return x_dict
    seeds = x_dict[batch.input_type][:batch.batch_size]
    return nn.Dense(self.out_features, name='head')(seeds)
