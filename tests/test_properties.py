"""Property-based tests (hypothesis): the hop loop's inducer must give
the reference inducer's labels on arbitrary inputs, and sampling
invariants must hold for any degree distribution —
the randomized counterpart of the fixture-exact tests (reference test
strategy, SURVEY.md §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    'hypothesis', reason='property tests need hypothesis (optional '
    'test dependency; the fixture-exact tests cover the same paths)')
from hypothesis import given, settings, strategies as st  # noqa: E402

from glt_tpu.ops.sample import sample_full_neighbors, sample_neighbors
from glt_tpu.ops.unique import (
    ordered_unique, sorted_hop_dedup, sorted_hop_dedup_fused,
    sorted_nodes_by_label,
)

ids_strategy = st.lists(
    st.tuples(st.integers(0, 19), st.booleans()), min_size=1,
    max_size=40)


def _py_ordered_unique(ids, valid):
  seen, uniq, inv = {}, [], []
  for x, ok in zip(ids, valid):
    if not ok:
      inv.append(-1)
      continue
    if x not in seen:
      seen[x] = len(uniq)
      uniq.append(x)
    inv.append(seen[x])
  return uniq, inv


@settings(max_examples=60, deadline=None)
@given(ids_strategy)
def test_ordered_unique_matches_python(pairs):
  ids = np.array([p[0] for p in pairs], np.int32)
  valid = np.array([p[1] for p in pairs])
  cap = ids.shape[0]
  uniq, count, inv = ordered_unique(jnp.asarray(ids), jnp.asarray(valid),
                                    cap)
  want_uniq, want_inv = _py_ordered_unique(ids.tolist(), valid.tolist())
  assert int(count) == len(want_uniq)
  np.testing.assert_array_equal(np.asarray(uniq)[:len(want_uniq)],
                                want_uniq)
  np.testing.assert_array_equal(np.asarray(inv), want_inv)


@jax.jit
def _seed_then_fused(a_ids, a_ok, b_ids, b_ok):
  empty = jnp.zeros((0,), jnp.int32)
  d = sorted_hop_dedup(empty, empty, jnp.zeros((), jnp.int32), a_ids, a_ok)
  f = sorted_hop_dedup_fused(d['u_ids2'], d['u_labs2'], d['count2'], b_ids,
                             b_ok)
  f['nodes'] = sorted_nodes_by_label(f['u_ids2'], f['u_labs2'], f['count2'],
                                     a_ids.shape[0] + b_ids.shape[0])
  return d, f


@settings(max_examples=40, deadline=None)
@given(ids_strategy, ids_strategy)
def test_seed_hop_then_fused_hop_match_python(pairs_a, pairs_b):
  """The hop loop's two dedups in their order (ops/pipeline.py): the
  exact seed hop, then a fused hop over the seen-set it left. The seed
  hop's labels are first-occurrence; the fused hop keeps every seen id's
  label and hands the new ids ``count..`` in ascending id order; the node
  list is the one bijection behind both."""
  def pad(pairs):
    # to the strategy's 40 slots with invalid ones: one program for
    # every example
    pairs = pairs + [(0, False)] * (40 - len(pairs))
    return (np.array([p[0] for p in pairs], np.int32),
            np.array([p[1] for p in pairs]))

  (a_ids, a_ok), (b_ids, b_ok) = pad(pairs_a), pad(pairs_b)
  d, f = _seed_then_fused(jnp.asarray(a_ids), jnp.asarray(a_ok),
                          jnp.asarray(b_ids), jnp.asarray(b_ok))
  lab_a = np.full(a_ids.shape[0], -1)
  lab_a[np.asarray(d['pos3'])] = np.asarray(d['labels3'])
  want_uniq, want_inv = _py_ordered_unique(a_ids.tolist(), a_ok.tolist())
  np.testing.assert_array_equal(np.where(a_ok, lab_a, -1), want_inv)
  assert int(d['count2']) == len(want_uniq)

  label = dict(zip(want_uniq, range(len(want_uniq))))
  new = sorted({int(x) for x, ok in zip(b_ids, b_ok)
                if ok and int(x) not in label})
  label.update(zip(new, range(len(want_uniq), len(want_uniq) + len(new))))
  want_b = [label[int(x)] if ok else -1 for x, ok in zip(b_ids, b_ok)]
  np.testing.assert_array_equal(np.asarray(f['labels3']), want_b)
  assert int(f['new_count']) == len(new)
  heads = np.asarray(f['new_head3'])
  assert sorted(b_ids[heads].tolist()) == new
  nodes = np.asarray(f['nodes'])
  want_nodes = want_uniq + new
  np.testing.assert_array_equal(nodes[:len(want_nodes)], want_nodes)
  assert (nodes[len(want_nodes):] == -1).all()


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 6), min_size=1, max_size=12),
       st.integers(1, 8), st.integers(0, 2**31 - 1))
def test_sample_neighbors_invariants(degrees, fanout, seed):
  """For ANY degree multiset: samples are real neighbors, distinct, and
  exhaustive-in-order when degree <= fanout."""
  indptr = np.concatenate([[0], np.cumsum(degrees)]).astype(np.int32)
  e = int(indptr[-1])
  indices = np.arange(e, dtype=np.int32) * 7 % 100  # arbitrary ids
  seeds = np.arange(len(degrees), dtype=np.int32)
  out = sample_neighbors(jnp.asarray(indptr), jnp.asarray(indices),
                         jnp.asarray(seeds), fanout,
                         jax.random.key(seed))
  nbrs = np.asarray(out.nbrs)
  mask = np.asarray(out.mask)
  for v, deg in enumerate(degrees):
    got = nbrs[v][mask[v]]
    adj = indices[indptr[v]:indptr[v + 1]]
    assert got.shape[0] == min(deg, fanout)
    if deg <= fanout:
      np.testing.assert_array_equal(got, adj)   # exhaustive, in order
    else:
      # all sampled slots hold real neighbors at distinct offsets
      eids = np.asarray(out.eids)[v][mask[v]]
      assert len(set(eids.tolist())) == fanout  # WOR: distinct slots
      assert all(x in adj for x in got)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 5), min_size=1, max_size=10),
       st.integers(1, 6))
def test_full_neighbors_is_exact(degrees, window_extra):
  indptr = np.concatenate([[0], np.cumsum(degrees)]).astype(np.int32)
  e = int(indptr[-1])
  indices = (np.arange(e, dtype=np.int32) * 3 + 1) % 50
  seeds = np.arange(len(degrees), dtype=np.int32)
  window = max(degrees) + window_extra if degrees else window_extra
  window = max(window, 1)
  out = sample_full_neighbors(jnp.asarray(indptr), jnp.asarray(indices),
                              jnp.asarray(seeds), window)
  nbrs = np.asarray(out.nbrs)
  mask = np.asarray(out.mask)
  for v, deg in enumerate(degrees):
    np.testing.assert_array_equal(nbrs[v][mask[v]],
                                  indices[indptr[v]:indptr[v + 1]])
