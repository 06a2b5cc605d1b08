"""The sort-merge inducer of the hop loops (ops/pipeline.py) held to the
numpy oracle (tests/sampler_oracle.py): labels, node list, batch, seed
labels and counts are the reference inducer's (inducer.cu:33-133) but
for the order in which a later hop hands out its new labels (ascending
ids), and edge slots stay where the hop drew them. The seed hop's exact
dedup and the later hops' fused one are held to each other on
adversarial inputs below."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from glt_tpu.data import Topology
from glt_tpu.ops.pipeline import hop_fanouts, multihop_sample
from glt_tpu.ops.sample import sample_neighbors
from glt_tpu.ops.unique import sorted_hop_dedup, sorted_nodes_by_label

from sampler_oracle import EdgeTable, check_multihop, check_multihop_typed


def _run(seeds, n_valid, fanouts, indptr, indices, key, with_edge=False):
  one_hop = lambda ids, f, k, m: sample_neighbors(
      indptr, indices, ids, f, k, seed_mask=m,
      edge_ids=jnp.arange(indices.shape[0], dtype=jnp.int32))
  out = jax.jit(lambda s, nv, k: multihop_sample(
      one_hop, s, nv, fanouts, k, with_edge=with_edge))(seeds, n_valid, key)
  return jax.tree.map(np.asarray, out)


@pytest.mark.parametrize('fanouts', [(2,), (3, 2), (2, 2, 2)])
def test_ring_batch_against_the_oracle(fanouts):
  # ring graph: deg 2 everywhere, heavy cross-hop overlap (the hard case
  # for seen-set exclusion), a duplicate seed and a masked slot
  n = 24
  rows = np.repeat(np.arange(n), 2)
  cols = np.stack([(np.arange(n) + 1) % n, (np.arange(n) + 2) % n],
                  1).reshape(-1)
  t = Topology(edge_index=np.stack([rows, cols]), num_nodes=n)
  indptr = jnp.asarray(t.indptr.astype(np.int32))
  indices = jnp.asarray(t.indices)
  seeds = np.array([5, 0, 5, 17], np.int32)
  out = _run(jnp.asarray(seeds), jnp.asarray(3), fanouts, indptr, indices,
             jax.random.key(0), with_edge=True)
  # fanout >= degree: every hop is exhaustive, so the oracle knows each
  # row's picks whole
  check_multihop(EdgeTable.from_csr(t.indptr, t.indices), seeds, 3,
                 fanouts, out, new_label_order='value',
                 hop_fanouts=hop_fanouts(fanouts))
  assert int(out['seed_count']) == 2
  np.testing.assert_array_equal(out['seed_labels'], [0, 1, 0, -1])


def test_random_graph_invariants():
  rng = np.random.default_rng(3)
  n, e = 500, 4000
  src = rng.integers(0, n, e)
  dst = rng.integers(0, n, e)
  t = Topology(edge_index=np.stack([src, dst]), num_nodes=n)
  indptr = jnp.asarray(t.indptr.astype(np.int32))
  indices = jnp.asarray(t.indices)
  fanouts = (4, 3)
  seeds = jnp.asarray(rng.integers(0, n, 32).astype(np.int32))
  out = _run(seeds, jnp.asarray(32), fanouts, indptr, indices,
             jax.random.key(1))

  count = int(out['node_count'])
  nodes = out['node']
  # node list: unique ids, -1 padded exactly past count
  assert len(set(nodes[:count].tolist())) == count
  assert (nodes[count:] == -1).all()
  # every valid edge references in-range labels; child label's node id is
  # a real neighbor of the parent label's node id
  m = out['edge_mask'].astype(bool)
  row_l = out['row'][m]
  col_l = out['col'][m]
  assert (row_l >= 0).all() and (row_l < count).all()
  assert (col_l >= 0).all() and (col_l < count).all()
  ip = np.asarray(t.indptr)
  ix = np.asarray(t.indices)
  for child, parent in zip(row_l[:200], col_l[:200]):
    p, ch = nodes[parent], nodes[child]
    assert ch in ix[ip[p]:ip[p + 1]]
  # hop-blocked labels: hop h's new nodes occupy one contiguous range
  nsn = out['num_sampled_nodes']
  assert nsn.sum() == count
  # seeds keep the first labels
  sl = out['seed_labels']
  assert (sl >= 0).all() and (sl < int(out['seed_count'])).all()
  np.testing.assert_array_equal(nodes[sl], np.asarray(seeds))
  # and the whole batch against the oracle
  check_multihop(EdgeTable.from_csr(t.indptr, t.indices), np.asarray(seeds),
                 32, fanouts, out, new_label_order='value',
                 hop_fanouts=hop_fanouts(fanouts))


@pytest.mark.parametrize('fanouts', [[2], [2, 2]])
def test_hetero_ring_batch_against_the_oracle(fanouts):
  # exhaustive fanouts (deg 2 everywhere): the oracle knows every pick
  from glt_tpu.sampler import NeighborSampler, NodeSamplerInput
  from test_sampler_contract_typed import I2I, U2I, _ring, _traversal_output
  ds, graphs = _ring()
  seeds = np.array([3, 7, 3, 9])
  fanouts = {U2I: fanouts, I2I: fanouts}
  s = NeighborSampler(ds.graph, fanouts, with_edge=True, seed=4)
  got = _traversal_output(s.sample_from_nodes(
      NodeSamplerInput(seeds, 'user'), key=jax.random.key(5)), True)
  check_multihop_typed(graphs, {e: (e[0], e[2]) for e in fanouts}, fanouts,
                       {'user': seeds}, {'user': 4}, got,
                       new_label_order='value')
  np.testing.assert_array_equal(got['seed_labels']['user'], [0, 1, 0, 2])


def test_sorted_hop_dedup_unit():
  # tiny hand-checked case incl. seen-set reuse and duplicates
  u_ids = jnp.array([40, 7], jnp.int32)       # labels 0, 1 already taken
  u_labs = jnp.array([0, 1], jnp.int32)
  ids = jnp.array([9, 7, 9, 3, 40, 9], jnp.int32)
  valid = jnp.array([True, True, True, True, True, False])
  d = sorted_hop_dedup(u_ids, u_labs, jnp.asarray(2, jnp.int32), ids,
                       valid)
  lab_by_pos = {int(p): int(l) for p, l in zip(d['pos3'], d['labels3'])}
  # first occurrences: 9 -> 2 (slot 0), 3 -> 3 (slot 3); 7 -> 1, 40 -> 0
  assert lab_by_pos[0] == 2 and lab_by_pos[2] == 2 and lab_by_pos[5] == -1
  assert lab_by_pos[1] == 1
  assert lab_by_pos[3] == 3
  assert lab_by_pos[4] == 0
  assert int(d['new_count']) == 2 and int(d['count2']) == 4
  # ids stay aligned with their slots through the permutation
  assert all(int(i) == int(ids[p]) or not bool(valid[p])
             for p, i in zip(d['pos3'], d['ids3']))
  nodes = sorted_nodes_by_label(d['u_ids2'], d['u_labs2'], d['count2'],
                                6)
  np.testing.assert_array_equal(np.asarray(nodes),
                                [40, 7, 9, 3, -1, -1])


def test_cumsum_i32_exact():
  from glt_tpu.ops.scan import cumsum_i32
  rng = np.random.default_rng(0)
  for m in (7, 512, 513, 70_001):
    x = rng.integers(0, 3, m).astype(np.int32)
    got = np.asarray(jax.jit(cumsum_i32)(jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.cumsum(x))


# -- sorted_hop_dedup_fused vs sorted_hop_dedup: adversarial inputs -----
#
# The fused variant relaxes ONE property (new labels in within-hop VALUE
# order instead of first-occurrence slot order); everything else —
# counts, seen-id labels, the label<->id bijection, exactly-one-head-
# per-new-id — must hold bit-for-bit on the inputs most likely to break
# a single-sort formulation: all-duplicate hops, empty frontiers,
# hub-only frontiers (few distinct ids, massive duplication), and a
# seen set landing EXACTLY on its capacity.

def _dedup_pair(u_ids, u_labs, count, ids, valid):
  from glt_tpu.ops.unique import sorted_hop_dedup_fused
  u_ids = jnp.asarray(u_ids, jnp.int32)
  u_labs = jnp.asarray(u_labs, jnp.int32)
  count = jnp.asarray(count, jnp.int32)
  ids = jnp.asarray(ids, jnp.int32)
  valid = jnp.asarray(valid, bool)
  exact, fused = jax.jit(lambda *a: (sorted_hop_dedup(*a),
                                     sorted_hop_dedup_fused(*a)))(
      u_ids, u_labs, count, ids, valid)
  return (jax.tree.map(np.asarray, exact), jax.tree.map(np.asarray, fused))


def _assert_fused_parity(exact, fused, ids, valid, count, budget):
  ids = np.asarray(ids)
  valid = np.asarray(valid)
  m = ids.shape[0]
  assert int(exact['count2']) == int(fused['count2'])
  assert int(exact['new_count']) == int(fused['new_count'])
  # exact path returns per-element arrays permuted; map back via pos3
  exact_slot_labels = np.full((m,), -1, np.int64)
  exact_slot_labels[exact['pos3']] = exact['labels3']
  # seen ids (label < count) keep labels bit-identically; new ids may
  # permute within the hop but must stay a consistent bijection
  seen = valid & (exact_slot_labels >= 0) & (exact_slot_labels < count)
  np.testing.assert_array_equal(exact_slot_labels[seen],
                                fused['labels3'][seen])
  np.testing.assert_array_equal(fused['labels3'][~valid],
                                np.full((~valid).sum(), -1))
  # exactly one head per new id, placed on a slot holding that id
  nh = fused['new_head3']
  assert nh.sum() == int(fused['new_count'])
  head_ids = ids[nh]
  assert len(set(head_ids.tolist())) == len(head_ids)
  # bijection: every valid slot of one id maps to ONE label, ascending
  # label order == ascending id order for the new ids (value order)
  new_pairs = sorted(zip(fused['labels3'][nh].tolist(),
                         head_ids.tolist()))
  assert [p[1] for p in new_pairs] == sorted(head_ids.tolist())
  for lab, _id in new_pairs:
    sel = valid & (ids == _id)
    assert (fused['labels3'][sel] == lab).all()
  # both seen-set forms reconstruct the same dense node list
  na = sorted_nodes_by_label(jnp.asarray(exact['u_ids2']),
                             jnp.asarray(exact['u_labs2']),
                             jnp.asarray(exact['count2']), budget)
  nf = sorted_nodes_by_label(jnp.asarray(fused['u_ids2']),
                             jnp.asarray(fused['u_labs2']),
                             jnp.asarray(fused['count2']), budget)
  cnt = int(exact['count2'])
  assert set(np.asarray(na)[:cnt].tolist()) == \
      set(np.asarray(nf)[:cnt].tolist())
  assert (np.asarray(na)[cnt:] == -1).all()
  assert (np.asarray(nf)[cnt:] == -1).all()


def test_fused_dedup_all_duplicate_hop():
  # every element the SAME fresh id: one new label, one head, the rest
  # resolve to it; a masked copy must not create a second head
  u_ids = np.array([50, 60], np.int32)
  u_labs = np.array([0, 1], np.int32)
  ids = np.full((16,), 7, np.int32)
  valid = np.ones((16,), bool)
  valid[3] = False
  exact, fused = _dedup_pair(u_ids, u_labs, 2, ids, valid)
  _assert_fused_parity(exact, fused, ids, valid, 2, budget=8)
  assert int(fused['new_count']) == 1
  # the head sits on the FIRST valid slot (first-occurrence contract)
  assert fused['new_head3'].argmax() == 0


def test_fused_dedup_all_duplicate_of_seen_id():
  # all-duplicate hop of an id the seen set already holds: zero new
  # labels, zero heads, every valid slot returns the stored label
  u_ids = np.array([7, 9], np.int32)
  u_labs = np.array([0, 1], np.int32)
  ids = np.full((12,), 9, np.int32)
  valid = np.ones((12,), bool)
  exact, fused = _dedup_pair(u_ids, u_labs, 2, ids, valid)
  _assert_fused_parity(exact, fused, ids, valid, 2, budget=4)
  assert int(fused['new_count']) == 0
  assert (fused['labels3'] == 1).all()


def test_fused_dedup_empty_frontier():
  # fully-masked hop (the n_valid=0 batch): nothing changes
  u_ids = np.array([3], np.int32)
  u_labs = np.array([0], np.int32)
  ids = np.array([5, 6, 7, 5], np.int32)
  valid = np.zeros((4,), bool)
  exact, fused = _dedup_pair(u_ids, u_labs, 1, ids, valid)
  _assert_fused_parity(exact, fused, ids, valid, 1, budget=4)
  assert int(fused['new_count']) == 0
  assert (fused['labels3'] == -1).all()
  assert not fused['new_head3'].any()


def test_fused_dedup_hub_frontier():
  # a frontier made entirely of hub expansions: FEW distinct ids, each
  # repeated many times, half already seen — worst case for head
  # detection and run grouping
  rng = np.random.default_rng(0)
  hubs = np.array([100, 200, 300, 400], np.int32)
  u_ids = np.array([100, 200], np.int32)     # two hubs already seen
  u_labs = np.array([0, 1], np.int32)
  ids = rng.choice(hubs, size=64).astype(np.int32)
  valid = rng.random(64) < 0.8
  exact, fused = _dedup_pair(u_ids, u_labs, 2, ids, valid)
  _assert_fused_parity(exact, fused, ids, valid, 2, budget=8)


def test_fused_dedup_capacity_exactly_full():
  # the seen set lands EXACTLY on the node budget: every label in
  # [0, budget) assigned, reconstruction leaves no -1 padding, and the
  # next hop (all-seen) must still resolve every label correctly
  budget = 8
  u_ids = np.array([10, 11, 12], np.int32)
  u_labs = np.array([0, 1, 2], np.int32)
  ids = np.array([20, 21, 22, 23, 24, 20, 21, 24], np.int32)  # 5 new
  valid = np.ones((8,), bool)
  exact, fused = _dedup_pair(u_ids, u_labs, 3, ids, valid)
  _assert_fused_parity(exact, fused, ids, valid, 3, budget=budget)
  assert int(fused['count2']) == budget
  nodes = np.asarray(sorted_nodes_by_label(
      jnp.asarray(fused['u_ids2']), jnp.asarray(fused['u_labs2']),
      jnp.asarray(fused['count2']), budget))
  assert (nodes >= 0).all()
  # follow-up hop over the full table: all seen, labels exact
  exact2, fused2 = _dedup_pair(fused['u_ids2'], fused['u_labs2'],
                               budget, ids, valid)
  _assert_fused_parity(exact2, fused2, ids, valid, budget,
                       budget=budget)
  assert int(fused2['new_count']) == 0
