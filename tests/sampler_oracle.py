"""A numpy-only oracle for sampler outputs.

It knows what a sampled hop and a sampled multi-hop batch must look like
from the graph alone: it imports neither jax nor ``glt_tpu.ops``, draws
no random numbers and follows no engine's order. A sampler's output is
handed over as numpy arrays and every claim of the contract is checked
against the edge list:

* one hop: every unmasked ``(parent, child[, eid])`` is an edge with
  that id; a row yields ``min(deg, k)`` distinct picks without
  replacement, ``k`` picks with replacement where ``deg > 0``, its
  positive-weight edges first under weights, the whole row in order for
  a full-neighbourhood hop;
* multi-hop: ``node[:node_count]`` holds no duplicate, labels are handed
  out hop by hop (the ``node_hop_offsets`` prefix property), edge slots
  parent-major where the hop loop promises it (``hop_fanouts``), every node
  new at hop ``h`` is a parent at hop ``h + 1``, masked lanes carry -1,
  the per-hop counts equal the counts recomputed from the edges,
  ``seed_labels`` maps duplicate seeds to one label, ``batch`` is the
  node list's prefix;
* typed: the same per relation and per node type, with per-relation
  fanouts and the static frontier capacities recomputed here.

A failed claim raises ``AssertionError`` naming the hop, relation and
row.
"""
from __future__ import annotations

from collections import Counter

import numpy as np


class EdgeTable:
  """A directed multigraph as a list of ``(src, dst, eid)``; ``eid``
  defaults to the edge's position. Rows keep the list's order."""

  def __init__(self, src, dst, eid=None, weights=None):
    self.src = np.asarray(src, np.int64).reshape(-1)
    self.dst = np.asarray(dst, np.int64).reshape(-1)
    n = self.src.shape[0]
    self.eid = (np.arange(n, dtype=np.int64) if eid is None
                else np.asarray(eid, np.int64).reshape(-1))
    assert self.dst.shape[0] == n and self.eid.shape[0] == n
    assert np.unique(self.eid).shape[0] == n, 'edge ids must be unique'
    self.weights = (None if weights is None
                    else np.asarray(weights, np.float64).reshape(-1))
    self._pos_of_eid = {int(e): i for i, e in enumerate(self.eid)}
    self._rows = {}
    for i, s in enumerate(self.src):
      self._rows.setdefault(int(s), []).append(i)

  @classmethod
  def from_csr(cls, indptr, indices, edge_ids=None, weights=None):
    indptr = np.asarray(indptr, np.int64)
    src = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    return cls(src, np.asarray(indices)[:src.shape[0]], edge_ids, weights)

  def positions(self, v) -> list:
    return self._rows.get(int(v), [])

  def degree(self, v) -> int:
    return len(self.positions(v))


def expected_picks(g: EdgeTable, v, fanout, *, live=True, replace=False,
                   weighted=False) -> int:
  """How many lanes a frontier row of node ``v`` must fill."""
  if not live:
    return 0
  pos = g.positions(v)
  if fanout < 0:                       # full neighbourhood, window |k|
    return min(len(pos), -fanout)
  if weighted:
    return min(int(sum(g.weights[p] > 0 for p in pos)), fanout)
  if replace:
    return fanout if pos else 0
  return min(len(pos), fanout)


def check_picks(g: EdgeTable, v, children, eids, *, distinct=True,
                weighted=False, where=''):
  """Every pick of parent ``v`` is one of its edges; without
  replacement no edge is picked twice. ``eids`` may be None: the picks
  are then held against the row's multiset of neighbours."""
  pos = g.positions(v)
  children = [int(c) for c in children]
  if eids is not None:
    eids = [int(e) for e in eids]
    assert len(eids) == len(children), where
    for c, e in zip(children, eids):
      assert e in g._pos_of_eid, f'{where}: edge id {e} is no edge'
      p = g._pos_of_eid[e]
      assert int(g.src[p]) == int(v) and int(g.dst[p]) == c, (
          f'{where}: edge id {e} is ({g.src[p]}, {g.dst[p]}), '
          f'the sample says ({v}, {c})')
      if weighted:
        assert g.weights[p] > 0, f'{where}: picked zero-weight edge {e}'
    if distinct:
      assert len(set(eids)) == len(eids), (
          f'{where}: row {v} picked an edge twice: {eids}')
    return
  row = Counter(int(g.dst[p]) for p in pos
                if not weighted or g.weights[p] > 0)
  got = Counter(children)
  for c, n in got.items():
    assert c in row, f'{where}: ({v}, {c}) is no edge'
    if distinct:
      assert n <= row[c], (
          f'{where}: row {v} picked neighbour {c} {n} times, the row '
          f'holds it {row[c]} times')


def check_hop(g: EdgeTable, seeds, fanout, nbrs, mask, eids=None, *,
              seed_mask=None, replace=False, weighted=False,
              in_order=False):
  """One hop, ``[S, |fanout|]`` outputs. ``in_order`` asks that a row
  whose degree does not exceed the window is returned whole, in
  adjacency order (uniform exhaustive rows and full-neighbourhood
  hops)."""
  seeds = np.asarray(seeds).reshape(-1)
  nbrs, mask = np.asarray(nbrs), np.asarray(mask).astype(bool)
  width = abs(fanout)
  assert nbrs.shape == (seeds.shape[0], width), nbrs.shape
  assert mask.shape == nbrs.shape, mask.shape
  if eids is not None:
    eids = np.asarray(eids)
    assert eids.shape == nbrs.shape, eids.shape
  for s, v in enumerate(seeds):
    live = seed_mask is None or bool(np.asarray(seed_mask)[s])
    want = expected_picks(g, v, fanout, live=live, replace=replace,
                          weighted=weighted)
    m = mask[s]
    where = f'row {s} (node {v})'
    assert int(m.sum()) == want, (
        f'{where}: {int(m.sum())} picks, expected {want}')
    check_picks(g, v, nbrs[s][m], None if eids is None else eids[s][m],
                distinct=not replace, weighted=weighted, where=where)
    pos = g.positions(v)
    if in_order and live and not replace and not weighted \
        and len(pos) <= width:
      assert m[:want].all(), f'{where}: picks are not a prefix'
      np.testing.assert_array_equal(
          nbrs[s][:want], g.dst[pos], err_msg=f'{where}: order')
      if eids is not None:
        np.testing.assert_array_equal(
            eids[s][:want], g.eid[pos], err_msg=f'{where}: eid order')


def _unique_in_order(ids):
  seen, out = set(), []
  for i in ids:
    i = int(i)
    if i not in seen:
      seen.add(i)
      out.append(i)
  return out


def _check_seed_hop(seeds, n_valid, node, batch, seed_labels, first_count,
                    where=''):
  """The seed hop of one node type: distinct valid seeds open the node
  list in order of first appearance; duplicates share a label; invalid
  slots carry -1; ``batch`` is the list's prefix."""
  seeds = np.asarray(seeds).reshape(-1)
  n_valid = int(n_valid)
  uniq = _unique_in_order(seeds[:n_valid])
  assert int(first_count) == len(uniq), (
      f'{where}: seed hop counts {int(first_count)} nodes, the seeds '
      f'hold {len(uniq)} distinct')
  np.testing.assert_array_equal(node[:len(uniq)], uniq,
                                err_msg=f'{where}: seed prefix')
  np.testing.assert_array_equal(
      np.asarray(batch), node[:seeds.shape[0]],
      err_msg=f'{where}: batch is not the node prefix')
  seed_labels = np.asarray(seed_labels)
  assert seed_labels.shape == seeds.shape, where
  for i, s in enumerate(seeds):
    if i < n_valid:
      lab = int(seed_labels[i])
      assert 0 <= lab < len(uniq) and int(node[lab]) == int(s), (
          f'{where}: seed slot {i} ({s}) has label {lab}')
    else:
      assert int(seed_labels[i]) == -1, (
          f'{where}: invalid seed slot {i} has label {seed_labels[i]}')
  return len(uniq)


def _check_new_labels(node, lo, n_new, first_seen, order, where):
  """Labels ``lo .. lo + n_new - 1`` were handed out at this hop.
  ``order``: 'value' (ascending ids, the sort+fused engine), 'slot'
  (order of first appearance over the hop's lanes) or None."""
  fresh = [int(x) for x in node[lo:lo + n_new]]
  if order == 'value':
    assert fresh == sorted(fresh), f'{where}: new labels not by value'
  elif order == 'slot':
    assert fresh == first_seen, f'{where}: new labels not by slot'
  else:
    assert sorted(fresh) == sorted(first_seen), where


def _check_groups(c, m, k, heads, lo, frontier, order, where):
  """One hop's block as ``Batch.hop_fanouts`` promises it: groups of
  ``k`` adjacent lanes under one parent label each, no label at the head
  of two groups with a live lane (``heads`` runs over the whole batch),
  and the parent the label of the frontier slot the group was drawn
  for: the table engine's frontier is the new labels in order (``lo``
  on), the fused sort's (``order='value'``) the lanes of the hop before
  (``frontier``: their child labels, -1 where masked), where a lane
  that found no new node leaves its whole group masked."""
  assert k > 0 and c.shape[0] % k == 0, where
  c, m = c.reshape(-1, k), m.reshape(-1, k)
  assert (c == c[:, :1]).all(), (
      f'{where}: col changes inside a group of {k} lanes')
  parent, live = c[:, 0], m.any(axis=1)
  for p in parent[live]:
    assert int(p) not in heads, (
        f'{where}: label {int(p)} heads two groups with a live lane')
    heads.add(int(p))
  if order == 'slot':
    assert (parent[live] == lo + np.nonzero(live)[0]).all(), (
        f'{where}: a group\'s parent is not its frontier slot\'s label')
  elif order == 'value' and frontier is not None:
    assert frontier.shape[0] == parent.shape[0], where
    assert (parent[live] == frontier[live]).all(), (
        f'{where}: a group\'s parent is not its frontier slot\'s label')


def edge_offsets(batch_size, widths):
  offs, cap = [0], batch_size
  for k in widths:
    cap *= abs(k)
    offs.append(offs[-1] + cap)
  return offs


def check_multihop(g: EdgeTable, seeds, n_valid, fanouts, out, *,
                   replace=False, weighted=False, new_label_order=None,
                   widths=None, hop_fanouts=None):
  """A one-type multi-hop batch. ``out``: numpy arrays under the
  sampler's names (``row`` child labels, ``col`` parent labels).
  ``widths``: lanes a frontier row owns at each hop where that is more
  than ``|fanout|`` (the stream sampler appends its insert window).
  ``hop_fanouts``: the producer's promise of parent-major edge slots
  (``Batch.hop_fanouts``) where it gave one; the batch is held to it."""
  seeds = np.asarray(seeds).reshape(-1)
  batch_size = seeds.shape[0]
  node = np.asarray(out['node'])
  nc = int(out['node_count'])
  row, col = np.asarray(out['row']), np.asarray(out['col'])
  emask = np.asarray(out['edge_mask']).astype(bool)
  edge = np.asarray(out['edge']) if out.get('edge') is not None else None
  hop_nodes = np.asarray(out['num_sampled_nodes'])
  hop_edges = np.asarray(out['num_sampled_edges'])
  offs = edge_offsets(batch_size, widths or fanouts)
  budget = batch_size + sum(offs[h + 1] - offs[h]
                            for h in range(len(fanouts)))
  assert node.shape[0] == budget, (node.shape, budget)
  assert row.shape[0] == offs[-1] == col.shape[0] == emask.shape[0]
  assert hop_nodes.shape[0] == len(fanouts) + 1
  assert hop_edges.shape[0] == len(fanouts)
  assert len(set(node[:nc].tolist())) == nc, 'node list holds a duplicate'
  assert (node[:nc] >= 0).all()

  cum = _check_seed_hop(seeds, n_valid, node, out['batch'],
                        out['seed_labels'], hop_nodes[0], 'seeds')
  if 'seed_count' in out:
    assert int(out['seed_count']) == cum
  lo, hi = 0, cum                      # the frontier's label range
  prefix = batch_size
  if hop_fanouts is not None:
    assert tuple(hop_fanouts) == tuple(abs(k) for k in widths or fanouts)
  heads = set()                        # labels that head a live group
  prev_children = None                 # the last hop's lanes: this frontier
  for h, k in enumerate(fanouts):
    where = f'hop {h}'
    sl = slice(offs[h], offs[h + 1])
    r, c, m = row[sl], col[sl], emask[sl]
    if hop_fanouts is not None:
      _check_groups(c, m, hop_fanouts[h], heads, lo, prev_children,
                    new_label_order, where)
      prev_children = np.where(m, r, -1)
    e = edge[sl] if edge is not None else None
    assert (r[~m] == -1).all(), f'{where}: a masked lane carries a label'
    assert int(hop_edges[h]) == int(m.sum()), (
        f'{where}: num_sampled_edges {int(hop_edges[h])}, '
        f'{int(m.sum())} lanes are live')
    assert ((c[m] >= lo) & (c[m] < hi)).all(), (
        f'{where}: a parent label lies outside the frontier '
        f'[{lo}, {hi})')
    first_seen = _unique_in_order(
        node[x] for x in r[m] if x >= cum)
    n_new = len(first_seen)
    assert int(hop_nodes[h + 1]) == n_new, (
        f'{where}: num_sampled_nodes {int(hop_nodes[h + 1])}, the '
        f'edges bring {n_new} new nodes')
    assert ((r[m] >= 0) & (r[m] < cum + n_new)).all(), where
    for p in range(lo, hi):
      lanes = m & (c == p)
      v = int(node[p])
      want = expected_picks(g, v, k, replace=replace, weighted=weighted)
      assert int(lanes.sum()) == want, (
          f'{where}: parent {v} (label {p}) has {int(lanes.sum())} '
          f'picks, expected {want}')
      check_picks(g, v, node[r[lanes]],
                  None if e is None else e[lanes],
                  distinct=not replace, weighted=weighted,
                  where=f'{where}, parent {v}')
    _check_new_labels(node, cum, n_new, first_seen, new_label_order,
                      where)
    # the node_hop_offsets prefix: nodes within h + 1 hops sit under
    # the static budget of the first h + 1 hops
    prefix += offs[h + 1] - offs[h]
    assert cum + n_new <= prefix, where
    lo, hi, cum = cum, cum + n_new, cum + n_new
  assert cum == nc, f'node_count {nc}, the hops count {cum}'


def typed_caps(node_types, trav, fanouts, batch_sizes, num_hops):
  """Static frontier capacity of each node type at each hop."""
  caps = [{t: int(batch_sizes.get(t, 0)) for t in node_types}]
  for h in range(num_hops):
    nxt = {t: 0 for t in node_types}
    for e, (row_t, col_t) in trav.items():
      nxt[col_t] += caps[h][row_t] * abs(fanouts[e][h])
    caps.append(nxt)
  return caps


def check_multihop_typed(graphs, trav, fanouts, seeds, n_valid, out, *,
                         replace=False, new_label_order=None):
  """A typed multi-hop batch.

  graphs: {relation: EdgeTable} in traversal orientation (``src`` is
    the type a hop expands from).
  trav: {relation: (expand_from_type, neighbour_type)}.
  fanouts: {relation: [k per hop]}.
  seeds / n_valid: {type: ...} for the seeded types.
  out: numpy arrays, ``node`` / ``node_count`` / ``batch`` /
    ``seed_labels`` / ``num_sampled_nodes`` by type, ``row`` (child
    labels) / ``col`` (parent labels) / ``edge_mask`` / ``edge`` /
    ``num_sampled_edges`` by relation.

  Every batch is also held to ``HeteroBatch.hop_fanouts_dict``'s promise
  (ops/pipeline.py::hetero_hop_fanouts), which all three typed loops
  keep: inside a relation's buffer a hop's block is groups of ``|k|``
  adjacent lanes under one parent label, and no label heads two groups
  with a live lane.
  """
  num_hops = len(next(iter(fanouts.values())))
  types = sorted({t for rc in trav.values() for t in rc})
  assert sorted(out['node']) == types, (sorted(out['node']), types)
  batch_sizes = {t: np.asarray(s).shape[0] for t, s in seeds.items()}
  caps = typed_caps(types, trav, fanouts, batch_sizes, num_hops)
  node = {t: np.asarray(out['node'][t]) for t in types}
  nc = {t: int(out['node_count'][t]) for t in types}
  for t in types:
    budget = max(1, sum(c[t] for c in caps))
    assert node[t].shape[0] == budget, (t, node[t].shape, budget)
    assert len(set(node[t][:nc[t]].tolist())) == nc[t], (
        f'type {t}: node list holds a duplicate')
    assert np.asarray(out['num_sampled_nodes'][t]).shape[0] \
        == num_hops + 1, t

  cum, frontier = {}, {}
  for t in types:
    first = int(np.asarray(out['num_sampled_nodes'][t])[0])
    if t in seeds:
      cum[t] = _check_seed_hop(
          seeds[t], n_valid[t], node[t], out['batch'][t],
          out['seed_labels'][t], first, f'seeds of {t}')
    else:
      assert first == 0, f'type {t} has no seeds and counts {first}'
      cum[t] = 0
    frontier[t] = (0, cum[t])

  cursor = {e: 0 for e in trav}        # lanes of a relation used so far
  hop_index = {e: 0 for e in trav}     # active hops of a relation so far
  heads = {e: set() for e in trav}     # labels at the head of a live group
  for h in range(num_hops):
    first_seen = {t: [] for t in types}
    for e, (row_t, col_t) in trav.items():
      k = fanouts[e][h]
      if caps[h][row_t] == 0 or k == 0:
        continue
      where = f'hop {h}, relation {e}'
      width = caps[h][row_t] * abs(k)
      sl = slice(cursor[e], cursor[e] + width)
      cursor[e] += width
      r = np.asarray(out['row'][e])[sl]
      c = np.asarray(out['col'][e])[sl]
      m = np.asarray(out['edge_mask'][e]).astype(bool)[sl]
      ed = (np.asarray(out['edge'][e])[sl]
            if out.get('edge') is not None else None)
      assert r.shape[0] == width, f'{where}: the block is short'
      # a typed frontier is no run of new labels, so only the groups
      _check_groups(c, m, abs(k), heads[e], None, None, None, where)
      assert (r[~m] == -1).all(), (
          f'{where}: a masked lane carries a label')
      got = int(np.asarray(out['num_sampled_edges'][e])[hop_index[e]])
      hop_index[e] += 1
      assert got == int(m.sum()), (
          f'{where}: num_sampled_edges {got}, {int(m.sum())} live')
      lo, hi = frontier[row_t]
      assert ((c[m] >= lo) & (c[m] < hi)).all(), (
          f'{where}: a parent label lies outside [{lo}, {hi})')
      for p in range(lo, hi):
        lanes = m & (c == p)
        v = int(node[row_t][p])
        want = expected_picks(graphs[e], v, k, replace=replace)
        assert int(lanes.sum()) == want, (
            f'{where}: parent {v} has {int(lanes.sum())} picks, '
            f'expected {want}')
        check_picks(graphs[e], v, node[col_t][r[lanes]],
                    None if ed is None else ed[lanes],
                    distinct=not replace, where=f'{where}, parent {v}')
      for x in r[m]:
        if x >= cum[col_t]:
          first_seen[col_t].append(int(node[col_t][x]))
    for t in types:
      fresh = _unique_in_order(first_seen[t])
      got = int(np.asarray(out['num_sampled_nodes'][t])[h + 1])
      assert got == len(fresh), (
          f'hop {h}, type {t}: num_sampled_nodes {got}, the edges '
          f'bring {len(fresh)} new nodes')
      _check_new_labels(node[t], cum[t], len(fresh), fresh,
                        new_label_order, f'hop {h}, type {t}')
      assert cum[t] + len(fresh) <= sum(c[t] for c in caps[:h + 2]), (
          f'hop {h}, type {t}: labels leave the hop prefix')
      frontier[t] = (cum[t], cum[t] + len(fresh))
      cum[t] += len(fresh)
  for e in trav:
    lanes = np.asarray(out['row'][e]).shape[0] if e in out['row'] else 0
    assert lanes == cursor[e], (
        f'relation {e}: {lanes} lanes, the capacities give {cursor[e]}')
  for t in types:
    assert cum[t] == nc[t], (
        f'type {t}: node_count {nc[t]}, the hops count {cum[t]}')
