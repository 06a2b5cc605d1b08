"""The fused SEAL step: ``SPMDSageTrainStep`` given a ``NegativeSampling``
and an ``EncloseSpec``. Held to the plain reference
(``models/reference/seal.py``) on seeded weights at a size the CPU holds,
on a graph with planted hubs wider than the extraction's window and than
the fanout; the batched extraction and DRNL to numpy on the node sets the
step hands back; the loader path's one-set extraction and edge-slot model
to the fused step by one loss on the same links. The programs of the
steps built without an ``EncloseSpec`` are pinned text for text by
``tests/test_typed_programs.py``."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.data import Dataset
from glt_tpu.models.dgcnn import DGCNN
from glt_tpu.models.reference import seal
from glt_tpu.ops.drnl import drnl_dense, drnl_node_labeling
from glt_tpu.ops.subgraph import (TILE, EncloseSpec, enclosing_subgraphs,
                                  induced_subgraph, pad_to_tiles)
from glt_tpu.ops.unique import unique_rows
from glt_tpu.parallel import ShardedFeature, SPMDSageTrainStep, make_mesh
from glt_tpu.sampler import NegativeSampling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, DIM, BATCH, LR, MAX_Z, K = 300, 8, 8, 1e-3, 50, 10
HUBS = (0, 1, 2)
# hubs are 150 wide: over the fanout (5) and over hub_width (40), so they
# are never read and their edges to one another come from the probes
SPEC = EncloseSpec(fanout=5, tile_budget=16, hub_width=40, hub_pairs=256,
                   max_z=MAX_Z)
BINARY = NegativeSampling('binary', 1, strict=True)


def hub_dataset(seed=0):
  """A random undirected graph (both directions, coalesced, no loops)
  with three planted hubs joined to one another."""
  rng = np.random.default_rng(seed)
  pairs = [rng.integers(0, N, (900, 2)), [[0, 1], [1, 2], [0, 2]]]
  for h in HUBS:
    pairs.append(np.stack([np.full(N // 2, h),
                           rng.choice(N, N // 2, replace=False)], 1))
  pairs = np.concatenate(pairs)
  pairs = pairs[pairs[:, 0] != pairs[:, 1]]
  pairs = np.unique(np.concatenate([pairs, pairs[:, ::-1]]), axis=0)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=pairs.T, num_nodes=N)
  ds.init_node_features(rng.standard_normal((N, DIM), dtype=np.float32))
  return ds


def csr(ds):
  topo = ds.get_graph().topo
  return np.asarray(topo.indptr), np.asarray(topo.indices)


def make_step(ds, spec=SPEC, neg=BINARY):
  mesh = make_mesh(1)
  table = np.asarray(ds.get_node_feature()[np.arange(N)])
  model = DGCNN(hidden=8, k=K, max_z=MAX_Z)
  tx = optax.adam(LR)
  step = SPMDSageTrainStep(
      mesh, model, tx, ds.get_graph(), ShardedFeature(table, mesh), None,
      [spec.fanout], BATCH, neg_sampling=neg, keep_seeds=True,
      enclose=spec, keep_sample=True)
  return step, model, tx, table


def positives(ds, count, seed, through=None):
  """``[count, 2]`` distinct edges of the graph, seeded; ``through``: with
  that node as the source (a hub's links hold the other hubs)."""
  indptr, indices = csr(ds)
  lo, hi = (0, indices.shape[0]) if through is None else (
      indptr[through], indptr[through + 1])
  eid = lo + np.random.default_rng(seed).choice(hi - lo, count,
                                                replace=False)
  src = np.searchsorted(indptr, eid, side='right') - 1
  return np.stack([src, indices[eid]], 1).astype(np.int32)


def blocks_of(counted, spec=SPEC):
  return np.unpackbits(counted['adj_bits'], axis=-1,
                       count=spec.node_slots).astype(bool)


@pytest.fixture(scope='module')
def run():
  """Three steps of the fused step on the hub graph (the first batch's
  sources are a hub), with what every step handed back."""
  ds = hub_dataset()
  step, model, tx, table = make_step(ds)
  params = step.init_params(jax.random.key(3))
  host = lambda tree: jax.tree.map(np.asarray, tree)
  p, opt, p0 = params, tx.init(params), host(params)
  losses, first_grad, counted = [], None, []
  for t in range(3):
    pairs = positives(ds, BATCH, 5 + t, through=0 if t == 0 else None)
    keys = jax.random.split(jax.random.key(100 + t), 1)
    p, opt, loss = step(p, opt, pairs, np.array([BATCH], np.int32), keys)
    losses.append(float(np.asarray(loss)[0]))
    newest = step.counters()
    counted.append({k: v[-1, 0] for k, v in newest.items() if k != 'step'})
    if first_grad is None:
      first_grad = jax.tree.map(
          lambda m: np.asarray(m) / (1 - seal.B1), opt[0].mu)
  return dict(ds=ds, step=step, model=model, table=table, params=p0,
              prog=seal.readings(losses, first_grad, p0, host(p)),
              losses=losses, counted=counted)


def reference_batches(counted, with_order=False):
  ones = np.ones(BATCH, np.float32)
  return [dict(nodes=c['nodes'], y=np.concatenate([ones, 0 * ones]),
               weight=np.concatenate([ones, ones]),
               **(dict(order=c['pool_order']) if with_order else {}))
          for c in counted]


# float32 on the CPU against float32 at ``highest``: the two differ by the
# order of sums (a dense product where the reference normalises the block
# first, a 0/1 product where it takes rows). Read: loss 8.7e-8, the first
# gradient's worst leaf 1.2e-6, the parameters' change 7.3e-6 (Adam
# divides by sqrt(v), so a small leaf's rounding is magnified). Each limit
# has ten times of room or more; bfloat16 (3.6e-3, 0.26, 0.042) and both
# planted faults fail them (the test below)
LIMITS = {'loss_gap': 2e-6, 'grad_gap': 2e-5, 'change_gap': 2e-4}


@pytest.mark.parametrize('with_order', [False, True])
def test_the_fused_step_against_the_reference(run, with_order):
  """Against the reference's own sort, and with the readout's order
  taken as the program made it (what a chip run compares: there two keys
  a rounding apart may swap) and held to the reference's keys."""
  indptr, indices = csr(run['ds'])
  ref = seal.follow(indptr, indices, lambda ids: run['table'][ids],
                    run['params'],
                    reference_batches(run['counted'], with_order), LR, K,
                    MAX_Z)
  gaps = seal.compare(run['prog'], ref)
  assert all(gaps[k] <= LIMITS[k] for k in LIMITS), gaps
  assert set(ref['grad']) == set(run['prog']['grad'])   # leaf by leaf
  assert run['step'].step_traces == 1
  for counted, keys in zip(run['counted'], ref['keys']):
    order = counted['pool_order']
    assert order.shape == (2 * BATCH, K)
    assert seal.sort_violations(order, keys, 1e-5) == 0
    assert seal.sort_violations(order[:, ::-1], keys, 1e-5) > 0
    by_other = np.argsort(counted['nodes'], axis=1)[:, :K]
    assert seal.sort_violations(by_other, keys, 1e-5) > 0


@pytest.mark.parametrize('control', ['half_batch', 'no_labels', 'bfloat16'])
def test_the_reference_fails_a_planted_fault(run, control):
  indptr, indices = csr(run['ds'])
  follow = lambda **kw: seal.follow(
      indptr, indices, lambda ids: run['table'][ids], run['params'],
      reference_batches(run['counted']), LR, K, MAX_Z, **kw)
  kw = dict(dtype=jnp.bfloat16) if control == 'bfloat16' else dict(
      fault=control)
  gaps = seal.compare(follow(**kw), follow())
  assert any(gaps[k] > LIMITS[k] for k in LIMITS), gaps
  # ``no_labels`` moves the loss itself: a comparison that sees DRNL
  if control == 'no_labels':
    assert gaps['loss_gap'] > 1e-3


def test_every_links_block_is_the_graphs_edges_among_its_nodes(run):
  indptr, indices = csr(run['ds'])
  hub_hub = 0
  for t, counted in enumerate(run['counted']):
    adj, z, mask, depth = seal.blocks(indptr, indices, counted['nodes'],
                                      MAX_Z)
    got = blocks_of(counted)
    assert np.array_equal(got, adj), t       # equal as sets, every link
    assert not got[:, :2, :2].any()          # the link itself, both ways
    assert np.array_equal(got, got.transpose(0, 2, 1))
    assert np.array_equal(counted['z'], z)   # DRNL, label for label
    assert counted['drnl_rounds'] == depth.max()
    assert counted['drnl_unreachable'] == (mask & (z == 0)).sum()
    assert counted['subgraph_edges'] == adj.sum()
    assert counted['subgraph_nodes'] == mask.sum()
    assert counted['edges_dropped'] == 0
    hubs = np.isin(counted['nodes'], HUBS)
    hub_hub += int((got & hubs[:, :, None] & hubs[:, None, :]).sum())
    # by brute force too, one link a step
    nodes = counted['nodes'][t]
    nodes = nodes[nodes >= 0]
    for i, u in enumerate(nodes):
      row = indices[indptr[u]:indptr[u + 1]]
      want = np.isin(nodes, row) & (nodes != u)
      if i < 2:
        want[:2] = False
      assert np.array_equal(got[t, i, :nodes.size], want)
  # edges between two hubs (neither is ever read) came from the probes
  assert hub_hub > 0
  assert sum(c['hub_pairs_probed'] for c in run['counted']) > 0


def test_the_node_sets_keep_the_hops_contract(run):
  indptr, indices = csr(run['ds'])
  for counted in run['counted']:
    ends = counted['seeds'].reshape(2, 2 * BATCH)
    capped = 0
    for l in range(2 * BATCH):
      nodes = counted['nodes'][l]
      n = int((nodes >= 0).sum())
      assert (nodes[n:] == -1).all()
      assert nodes[0] == ends[0, l] and nodes[1] == ends[1, l]
      fringe = nodes[2:n]
      assert np.unique(fringe).size == fringe.size
      assert not np.isin(fringe, ends[:, l]).any()
      rows = [indices[indptr[e]:indptr[e + 1]] for e in ends[:, l]]
      assert (np.isin(fringe, rows[0]) | np.isin(fringe, rows[1])).all()
      for row in rows:      # a row no wider than the fanout, taken whole
        if row.size <= SPEC.fanout:
          assert np.isin(row, nodes[:n]).all()
      capped += any(row.size > SPEC.fanout for row in rows)
    assert counted['links_capped'] == capped
    assert counted['nodes_by_hop'].tolist() == [
        4 * BATCH, counted['subgraph_nodes'] - 4 * BATCH]
    assert counted['seed_unique'] == np.unique(counted['seeds']).size


@pytest.mark.parametrize('budget,dropped', [
    (dict(), False),
    (dict(hub_pairs=2), True),                     # too few probes
    (dict(tile_budget=2, hub_pairs=4), True),      # too few tiles, too
    (dict(tile_budget=2, hub_pairs=4096), False),  # the probes make it up
])
def test_the_dropped_edges_counter(budget, dropped):
  """What the tile budget leaves unread is probed; only the pairs past
  ``hub_pairs`` can be lost, and they are counted."""
  ds = hub_dataset()
  indptr, indices = csr(ds)
  spec = SPEC._replace(**budget)
  rng = np.random.default_rng(9)
  pairs = np.concatenate([positives(ds, 4, 21, through=0),
                          positives(ds, 4, 22)])
  nbrs = np.zeros((2, 8, spec.fanout), np.int32)
  mask = np.zeros((2, 8, spec.fanout), bool)
  for e in range(2):
    for l in range(8):
      row = indices[indptr[pairs[l, e]]:indptr[pairs[l, e] + 1]]
      take = row if row.size <= spec.fanout else rng.choice(
          row, spec.fanout, replace=False)
      nbrs[e, l, :take.size], mask[e, l, :take.size] = take, True
  out = jax.jit(lambda *a: enclosing_subgraphs(*a, spec))(
      jnp.asarray(indptr, jnp.int32), pad_to_tiles(jnp.asarray(indices)),
      jnp.asarray(pairs.T), jnp.asarray(nbrs), jnp.asarray(mask),
      jnp.ones(8, bool))
  adj, _, _, _ = seal.blocks(indptr, indices, np.asarray(out['nodes']),
                             MAX_Z)
  missing = int((adj & ~np.asarray(out['adj'])).sum())
  assert not (np.asarray(out['adj']) & ~adj).any()   # never an edge more
  if dropped:
    assert int(out['edges_dropped']) > 0
  else:
    assert int(out['edges_dropped']) == 0 and missing == 0
  assert int(out['hub_pairs_probed']) <= spec.hub_pairs
  assert int(out['tiles_read']) <= 8 * spec.tile_budget


RING, REACH = 300, 64


def ring_csr():
  """Node ``i`` joined to ``i - 64 .. i + 64`` around a ring: every row is
  128 entries at a multiple of 128, exactly one tile, so a link's tiles
  are its members and any 12 nodes in a row are all joined."""
  cols = (np.arange(RING)[:, None]
          + np.r_[-REACH:0, 1:REACH + 1][None, :]) % RING
  return ((2 * REACH * np.arange(RING + 1)).astype(np.int32),
          np.sort(cols, axis=1).reshape(-1).astype(np.int32))


def ring_links(fringe):
  """Link ``l`` joins nodes ``20 l`` and ``20 l + 1``; ``fringe[l] = (a,
  b)``: its source brings the ``a`` nodes under it, its destination the
  ``b`` over it: ``2 + a + b`` members, distinct."""
  ends = np.stack([20 * np.arange(len(fringe)),
                   20 * np.arange(len(fringe)) + 1])
  nbrs = np.zeros((2, len(fringe), SPEC.fanout), np.int64)
  mask = np.zeros(nbrs.shape, bool)
  for l, (a, b) in enumerate(fringe):
    nbrs[0, l, :a] = ends[0, l] - 1 - np.arange(a)
    nbrs[1, l, :b] = ends[1, l] + 1 + np.arange(b)
    mask[0, l, :a], mask[1, l, :b] = True, True
  return (ends.astype(np.int32), (nbrs % RING).astype(np.int32), mask)


def dense_counts(indptr, nodes, spec):
  """What the dense form (every budgeted tile gathered and matched: the
  extraction before its loop over live tiles) counted, link by link in
  numpy: a member's row spans whole tiles, members are read in slot order
  while the link's budget lasts, pairs of unread members are probed."""
  tiles, hubs, pairs = [], 0, 0
  for row in np.asarray(nodes):
    used = upto = unread = 0
    for u in row[row >= 0]:
      start, deg = int(indptr[u]), int(indptr[u + 1] - indptr[u])
      span = (-(-(start % TILE + deg) // TILE)
              if 0 < deg <= spec.hub_width else 0)
      upto += span
      if span and upto <= spec.tile_budget:
        used = upto
      elif deg:
        unread += 1
    tiles.append(used)
    hubs += unread
    pairs += unread * (unread - 1) // 2
  return dict(tiles=tiles, tiles_read=sum(tiles), hub_members=hubs,
              hub_pairs_probed=min(pairs, spec.hub_pairs),
              edges_dropped=max(pairs - spec.hub_pairs, 0))


FULL = [(5, 5)] * 8
LIVE_TILE_CASES = {
    # every member is wider than hub_width: no row is read, every edge
    # comes from a probe
    'no_live_tile_every_member_a_hub': dict(
        fringe=FULL, spec=dict(hub_width=100, hub_pairs=1024), chunks=0),
    'no_live_tile_every_link_masked': dict(
        fringe=FULL, links=[], chunks=0),
    'one_live_tile': dict(
        fringe=FULL, links=[3], spec=dict(tile_budget=1), chunks=1),
    # 8 links of 8 tiles, a block each: two chunks of 32 to the tile; a
    # ninth tile in the last link is one past the edge
    'the_list_ends_at_a_chunks_edge': dict(fringe=[(3, 3)] * 8, chunks=2),
    'the_list_ends_one_past_a_chunks_edge': dict(
        fringe=[(3, 3)] * 7 + [(3, 4)], chunks=3),
    # the worst case: the loop serves all the budget holds, exactly
    'every_tile_of_every_link_live': dict(fringe=FULL, chunks=4),
    'a_link_past_its_budget_beside_links_under_it': dict(
        fringe=[(5, 5), (1, 1), (3, 3), (5, 5), (0, 0), (2, 2), (3, 4),
                (5, 5)], spec=dict(tile_budget=8), chunks=None),
    # the constant as it stands: the whole budget is under one chunk
    'the_chunk_as_it_stands': dict(fringe=FULL, chunk=None, chunks=1),
}


@pytest.mark.parametrize('case', sorted(LIVE_TILE_CASES))
def test_the_induction_reads_the_live_tiles_only(case, monkeypatch):
  """The loop over the batch's live tiles, at every shape of its list:
  the blocks are the reference's entry by entry, the counters the dense
  form's, and ``tiles_matched`` is the chunks run times the chunk."""
  from glt_tpu.ops import subgraph
  given = LIVE_TILE_CASES[case]
  spec = SPEC._replace(**dict(dict(tile_budget=12, hub_width=128,
                                   hub_pairs=1024), **given.get('spec', {})))
  if given.get('chunk', 32) is not None:
    # blocks of 8 tiles, 4 to a chunk: the loop takes trips at this size
    monkeypatch.setattr(subgraph, 'MATCH_BLOCK', 8)
    monkeypatch.setattr(subgraph, 'MATCH_CHUNK', given.get('chunk', 32))
  indptr, indices = ring_csr()
  ends, nbrs, mask = ring_links(given['fringe'])
  links = np.isin(np.arange(8), given.get('links', np.arange(8)))
  out = jax.jit(lambda *a: enclosing_subgraphs(*a, spec))(
      jnp.asarray(indptr), pad_to_tiles(jnp.asarray(indices)),
      jnp.asarray(ends), jnp.asarray(nbrs), jnp.asarray(mask),
      jnp.asarray(links))
  nodes = np.asarray(out['nodes'])
  assert ((nodes >= 0).sum(1) == [
      (2 + a + b) * on for (a, b), on in zip(given['fringe'], links)]).all()
  adj, _, _, _ = seal.blocks(indptr, indices, nodes, MAX_Z)
  assert np.array_equal(np.asarray(out['adj']), adj)
  # any 12 nodes in a row of the ring are all joined: the link alone is out
  n = (nodes >= 0).sum(1)
  assert adj.sum() == (n * (n - 1) - 2 * links).sum()
  want = dense_counts(indptr, nodes, spec)
  for name in ('tiles_read', 'hub_members', 'hub_pairs_probed',
               'edges_dropped'):
    assert int(out[name]) == want[name], name
  assert want['edges_dropped'] == 0
  if case == 'every_tile_of_every_link_live':
    assert want['tiles_read'] == 8 * spec.tile_budget
  if case.startswith('a_link_past'):
    assert want['hub_members'] > 0 and 0 < want['tiles_read'] < 64
    assert sorted(set(want['tiles'])) == [2, 4, 6, 8]
  # the list holds a link's tiles in whole blocks, the loop whole chunks
  block = subgraph.MATCH_BLOCK
  chunk = min(subgraph.MATCH_CHUNK // block,
              8 * -(-spec.tile_budget // block)) * block
  chunks = -(-sum(-(-n // block) for n in want['tiles']) * block // chunk)
  assert int(out['tiles_matched']) == chunks * chunk
  if given['chunks'] is not None:
    assert chunks == given['chunks']


def test_a_negative_that_is_an_edge_loses_its_link_too():
  """A padded (non-strict) negative may be an edge of the graph: its
  link is taken out of its subgraph as a positive's is."""
  ds = hub_dataset()
  indptr, indices = csr(ds)
  pair = positives(ds, 1, 33)          # an edge, handed in as any link
  nbrs = np.zeros((2, 1, SPEC.fanout), np.int32)
  mask = np.zeros((2, 1, SPEC.fanout), bool)
  for e in range(2):
    row = indices[indptr[pair[0, e]]:indptr[pair[0, e] + 1]][:SPEC.fanout]
    nbrs[e, 0, :row.size], mask[e, 0, :row.size] = row, True
  out = enclosing_subgraphs(
      jnp.asarray(indptr, jnp.int32), pad_to_tiles(jnp.asarray(indices)),
      jnp.asarray(pair.T), jnp.asarray(nbrs), jnp.asarray(mask),
      jnp.ones(1, bool), SPEC)
  adj = np.asarray(out['adj'])
  assert not adj[0, 0, 1] and not adj[0, 1, 0]
  assert adj[0, 0].any() and adj[0, 1].any()   # its other edges stay
  assert pad_to_tiles(jnp.asarray(indices)).shape[0] % TILE == 0


def test_drnl_on_a_hand_made_subgraph():
  """Source 0, destination 1; the path 0-2-3-4-1 (longer than 2), node 5
  hangs off the destination alone (the masked endpoint cuts it off the
  source), node 6 is isolated, slot 7 is padding."""
  adj = np.zeros((1, 8, 8), bool)
  for u, v in [(0, 2), (2, 3), (3, 4), (4, 1), (1, 5)]:
    adj[0, u, v] = adj[0, v, u] = True
  mask = np.arange(8)[None, :] < 7
  z, rounds, unreachable = drnl_dense(jnp.asarray(adj), jnp.asarray(mask),
                                      MAX_Z)
  want, depth = seal.drnl(adj[0, :7, :7], MAX_Z)
  assert np.array_equal(np.asarray(z)[0, :7], want)
  # d(2) = (1, 3), d(3) = (2, 2), d(4) = (3, 1)
  assert want.tolist() == [1, 1, 4, 5, 4, 0, 0]
  assert int(rounds) == depth == 3 and int(unreachable) == 2
  assert np.asarray(z)[0, 7] == 0
  # the edge-slot form that the loader path calls agrees (it clips at
  # max_z, not under it: the labels here are far from either)
  row, col = np.nonzero(adj[0])
  slots = drnl_node_labeling(jnp.asarray(row), jnp.asarray(col),
                             jnp.ones(row.size, bool), 8, 0, 1, MAX_Z)
  assert np.array_equal(np.asarray(slots)[:7], want)
  # a label past the vocabulary is clipped to its last row
  assert int(drnl_dense(jnp.asarray(adj), jnp.asarray(mask), 4)[0].max()) == 3


def test_unique_rows_dedups_a_row_on_its_own():
  ids = jnp.asarray([[7, 7, 3, 7, 3, 9, 5], [1, 2, 2, 1, 4, 4, 6]])
  valid = jnp.asarray([[1, 1, 1, 1, 1, 0, 1], [1, 1, 1, 1, 0, 1, 1]], bool)
  uniq, count = unique_rows(ids, valid, fixed=2)
  # the two fixed slots stay as they stand, equal or not; 9 is masked
  assert np.asarray(uniq).tolist() == [[7, 7, 3, 5, -1, -1, -1],
                                       [1, 2, 4, 6, -1, -1, -1]]
  assert np.asarray(count).tolist() == [4, 4]
  uniq, count = unique_rows(ids, valid)
  assert np.asarray(uniq)[0].tolist() == [7, 3, 5, -1, -1, -1, -1]


def test_sort_pooling_short_graphs_and_tied_keys():
  """Fewer than ``k`` live nodes: zero rows fill the readout; tied keys
  (two nodes alike in everything) go to the lower slot: the dense model
  and the reference give one logit."""
  model = DGCNN(hidden=8, k=K, max_z=MAX_Z)
  rng = np.random.default_rng(4)
  s, links = 12, 3
  x = rng.standard_normal((links, s, DIM)).astype(np.float32)
  adj = np.zeros((links, s, s), bool)
  mask = np.zeros((links, s), bool)
  for l, n in enumerate((4, 12, 7)):       # 4 and 7 are under k = 10
    mask[l, :n] = True
    a = rng.random((n, n)) < 0.4
    a = np.triu(a, 1)
    adj[l, :n, :n] = a | a.T
  # link 1: nodes 2 and 3 are twins (same features, same neighbours)
  x[1, 3] = x[1, 2]
  adj[1, 3] = adj[1, 2]
  adj[1, :, 3] = adj[1, :, 2]
  adj[1, 2, 3] = adj[1, 3, 2] = adj[1, 2, 2] = adj[1, 3, 3] = False
  z = np.where(mask, rng.integers(1, 6, (links, s)), 0).astype(np.int32)
  z[1, 3] = z[1, 2]
  x = x * mask[..., None]
  params = model.init(jax.random.key(0), jnp.asarray(x), z=jnp.asarray(z),
                      adj=jnp.asarray(adj), node_mask=jnp.asarray(mask))
  got = model.apply(params, jnp.asarray(x), z=jnp.asarray(z),
                    adj=jnp.asarray(adj), node_mask=jnp.asarray(mask))
  with jax.default_matmul_precision('highest'):
    want = seal._logits(params, x, z, adj, mask, K, jnp.float32, None)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                             atol=2e-6)


def test_the_loader_paths_pieces_give_the_fused_steps_loss(run):
  """The one-set extraction (``induced_subgraph``, what
  ``NeighborSampler.subgraph`` and ``SubGraphLoader`` call), the edge-slot
  DRNL and ``DGCNN``'s one-subgraph form, a link at a time on the nodes
  the fused step drew, with its parameters: one loss."""
  ds, counted = run['ds'], run['counted'][0]
  graph = ds.get_graph()
  graph.lazy_init()
  s, width = SPEC.node_slots, int(graph.topo.max_degree)
  logits = []
  for l in range(2 * BATCH):
    nodes = counted['nodes'][l]
    sub = induced_subgraph(graph.indptr, graph.indices, jnp.asarray(nodes),
                           jnp.asarray(nodes >= 0), node_capacity=s,
                           max_degree=width, with_edge=False)
    assert np.array_equal(np.asarray(sub.nodes), nodes)
    row, col = np.asarray(sub.rows), np.asarray(sub.cols)
    keep = np.asarray(sub.edge_mask) & ~((row < 2) & (col < 2))
    z = drnl_node_labeling(jnp.asarray(row), jnp.asarray(col),
                           jnp.asarray(keep), s, 0, 1, MAX_Z - 1)
    assert np.array_equal(np.asarray(z), counted['z'][l])
    x = run['table'][np.maximum(nodes, 0)] * (nodes >= 0)[:, None]
    logits.append(run['model'].apply(
        run['params'], jnp.asarray(x), jnp.asarray(row), jnp.asarray(col),
        jnp.asarray(keep), jnp.asarray(nodes >= 0), z=z))
  y = np.concatenate([np.ones(BATCH), np.zeros(BATCH)])
  loss = float(optax.sigmoid_binary_cross_entropy(
      jnp.stack(logits), jnp.asarray(y)).mean())
  assert abs(loss - run['losses'][0]) <= 2e-6 * abs(loss), (
      loss, run['losses'][0])


def test_the_step_says_what_it_counts_and_refuses_what_it_cannot(run):
  step = run['step']
  slots = step.counter_slots()
  links, s = 2 * BATCH, SPEC.node_slots
  assert slots['nodes_by_hop'].tolist() == [2 * links, links * (s - 2)]
  assert slots['subgraph_nodes'] == links * s
  assert slots['tiles_read'] == links * SPEC.tile_budget
  assert slots['tiles_matched'] == links * SPEC.tile_budget
  for counted in run['counted']:
    assert 0 < counted['tiles_read'] <= counted['tiles_matched']
  assert slots['hub_pairs_probed'] == SPEC.hub_pairs
  for name in ('negatives_rejected', 'negatives_padded', 'seed_unique',
               'links_capped', 'hub_members', 'drnl_unreachable',
               'store_chunks', 'subgraph_edges'):
    assert name in slots and name in run['counted'][0], name
  assert set(step.link_counters()) == {
      'negatives_rejected', 'negatives_padded', 'seed_unique', 'seeds'}
  with pytest.raises(NotImplementedError, match='node seeds only'):
    step.superstep(None, None, None, None, None)
  ds = run['ds']
  mesh = make_mesh(1)
  feature = ShardedFeature(run['table'], mesh)
  build = lambda **kw: SPMDSageTrainStep(
      mesh, run['model'], optax.adam(LR), ds.get_graph(), feature, None,
      batch_size_per_device=BATCH, enclose=SPEC, **kw)
  with pytest.raises(ValueError, match='is a link step'):
    build(fanouts=[SPEC.fanout])
  with pytest.raises(ValueError, match='one hop'):
    build(fanouts=[5, 5], neg_sampling=BINARY)


def test_the_references_two_copies_are_one_text():
  with open(os.path.join(REPO, 'chipbench', 'reference_seal.py')) as f:
    ours = f.read()
  with open(os.path.join(REPO, 'glt_tpu', 'models', 'reference',
                         'seal.py')) as f:
    theirs = f.read()
  assert ours == theirs
  assert 'glt_tpu' not in [line.split()[1].split('.')[0]
                           for line in ours.splitlines()
                           if line.startswith(('import ', 'from '))]
