"""What a typed model computes in each of its layers, from a typed
batch's static promises alone: the typed step's contract with a model.

``models/rgnn.py::RGNN`` and ``models/hgt.py::HGT`` both read one plan,
and ``distributed/dist_hetero.py::DistHeteroTrainStep`` reads the models'
``layer_rows`` and ``layer_groups`` (by ``getattr``) for its counters.
Three promises of a ``HeteroBatch``, each optional:

``edge_hop_offsets_dict``
    layer ``i`` reads the edge slots of hops ``[0, num_hops - i)`` of a
    relation only: the reference's ``trim_to_layer``
    (examples/hetero/hierarchical_sage.py), as static slices;
``node_hop_offsets_dict``
    labels are hop-compact per type, so layer ``i`` computes output rows
    only for the nodes a later layer reads, as models/sage.py does for one
    type;
``hop_fanouts_dict``
    a relation's edge slots are parent-major: static ``(offset, S, K)``
    triples (models/conv.py), of which a layer keeps those under its edge
    trim.
"""
from __future__ import annotations


def groups_under(groups, end, etype):
  """The ``(offset, S, K)`` triples whose block lies within the first
  ``end`` edge slots (``None``: all of them)."""
  if end is None:
    return tuple(groups)
  kept = tuple(g for g in groups if g[0] + g[1] * g[2] <= end)
  if any(g[0] < end for g in groups[len(kept):]):
    raise ValueError(f'hop_fanouts_dict[{etype}] {groups} has a block '
                     f'across the edge trim at slot {end}')
  return kept


def layer_plan(batch, num_layers: int, trim: bool = True,
               return_all: bool = False):
  """Per layer ``(edge_ends, rows, groups)``: ``edge_ends[e]`` leading
  edge slots are read (``None``: all), ``rows[t]`` output rows are
  computed (``None``: every row of the input), ``groups[e]`` are the
  ``(offset, S, K)`` triples of ``hop_fanouts_dict`` that lie under
  ``edge_ends[e]`` (``None``: no promise). Static, from the batch's
  hop offsets alone."""
  offs = batch.edge_hop_offsets_dict if trim else None
  noffs = (batch.node_hop_offsets_dict
           if offs and not return_all else None)
  fans = batch.hop_fanouts_dict
  num_hops = (max(len(v) for v in offs.values()) - 1) if offs else 0
  plan = []
  for i in range(num_layers):
    if not offs:
      plan.append((None, None, fans))
      continue
    # layer i still feeds num_layers-1-i later propagations, so hop
    # h is useful iff h <= num_layers - i (clamped to sampled hops)
    keep = max(min(num_hops, num_layers - i), 1)
    hop_ends = {e: v[min(keep, len(v) - 1)] for e, v in offs.items()}
    # what is read is non-empty, for XLA
    ends = {e: max(v, 1) for e, v in hop_ends.items()}
    rows = None
    if noffs:
      out_hops = min(num_hops, num_layers - 1 - i)
      rows = {t: max(v[min(out_hops, len(v) - 1)], 1)
              for t, v in noffs.items()}
    plan.append((ends, rows, fans and {
        e: groups_under(g, hop_ends.get(e), e)
        for e, g in fans.items()}))
  return plan


def layer_rows(plan, batch):
  """``[{type: output rows}]`` a layer, as the step's counter reads."""
  return [rows if rows is not None else
          {t: x.shape[0] for t, x in batch.x_dict.items()}
          for _, rows, _ in plan]


def layer_groups(plan, edge_types, batch):
  """``[{relation: groups}]`` a layer: the groups of adjacent edge slots
  a relation's convolution reduces over the fanout axis, 0 where it
  aggregates over segments."""
  return [{e: sum(s for _, s, _ in (groups or {}).get(e, ()))
           for e in edge_types if e in batch.row_dict}
          for _, _, groups in plan]


def cut_edges(d, ends):
  """A relation-keyed dict of edge buffers under a layer's edge trim."""
  return d if ends is None else {
      e: v[:ends[e]] if e in ends else v for e, v in d.items()}


def group_hops(groups, offsets):
  """``{relation: ((offset, S, K, hop), ...)}``: a plan's groups with the
  hop each block belongs to, read off the relation's hop offsets
  (``edge_hop_offsets_dict``: block ``hop`` starts at ``offsets[hop]``);
  without offsets a block's place in its tuple. The relations into one
  node type expand the same frontier, so blocks of one hop share their
  parents, group by group: what a softmax across relations leans on
  (models/hgt.py). ``None`` stays ``None``."""
  if groups is None:
    return None
  out = {}
  for e, blocks in groups.items():
    offs = (offsets or {}).get(e)
    # empty hops repeat an offset: a block is the last hop that starts there
    out[e] = tuple(
        tuple(g) + (i if offs is None else max(
            h for h in range(len(offs) - 1) if offs[h] == g[0]),)
        for i, g in enumerate(blocks))
  return out
