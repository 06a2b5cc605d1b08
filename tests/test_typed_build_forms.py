"""The forms that shorten the typed step's build give what the forms they
stand in for give: the dedup's unstable sort and take-based fill-forward,
the node list by scatter, the blocked running maximum, the one-partition
paths of the one-hop and of the feature lookup, and ``keep_sample``, which
hands back the batch the step trained on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glt_tpu.ops.scan import cummax_i32
from glt_tpu.ops.unique import (_fill_forward, _fill_forward_take,
                                sorted_hop_dedup, sorted_hop_dedup_fused,
                                sorted_nodes_by_label)

from test_rgat_step import BATCH, COUNTS, feed, typed_graph
import test_rgat_step
from glt_tpu.typing import reverse_edge_type


def build_step(edges, feats, labels, layers, hops, keep_sample=False):
  """``test_rgat_step.build_step`` with the trainer's ``keep_sample``."""
  made = test_rgat_step.DistHeteroTrainStep

  def trainer(*args, **kw):
    return made(*args, keep_sample=keep_sample, **kw)

  test_rgat_step.DistHeteroTrainStep = trainer
  try:
    return test_rgat_step.build_step(edges, feats, labels, layers, hops,
                                     head=True)
  finally:
    test_rgat_step.DistHeteroTrainStep = made


@pytest.mark.parametrize('n', [1, 5, 512, 513, 1024, 5000])
def test_blocked_running_maximum_is_the_running_maximum(n):
  x = np.random.default_rng(n).integers(-1000, 1000, n).astype(np.int32)
  np.testing.assert_array_equal(np.asarray(cummax_i32(jnp.asarray(x))),
                                np.maximum.accumulate(x))


@pytest.mark.parametrize('n', [7, 600, 3000])
def test_fill_forward_by_take_is_the_scan(n):
  rng = np.random.default_rng(n)
  hd = rng.random(n) < 0.3
  hd[0] = True
  a, b = (rng.integers(0, 99, n).astype(np.int32) for _ in range(2))
  want = _fill_forward(jnp.asarray(hd), jnp.asarray(a), jnp.asarray(b))
  got = _fill_forward_take(jnp.asarray(hd), jnp.asarray(a), jnp.asarray(b))
  for w, g in zip(want, got):
    np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def _seen_after_seeds(rng, num_ids):
  seeds = rng.permutation(num_ids)[:40].astype(np.int32)
  mask = rng.random(40) < 0.9
  empty = (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
           jnp.zeros((), jnp.int32))
  d = sorted_hop_dedup(*empty, jnp.asarray(seeds), jnp.asarray(mask))
  return d['u_ids2'], d['u_labs2'], d['count2']


@jax.jit
def _both_dedups(*args):
  return (sorted_hop_dedup_fused(*args),
          sorted_hop_dedup_fused(*args, fast_compile=True))


@pytest.mark.parametrize('seed', range(4))
@pytest.mark.parametrize('num_ids,m', [(300, 700), (50, 900), (5000, 64)])
def test_fast_compile_dedup_is_the_dedup_bit_for_bit(seed, num_ids, m):
  """Three hops on one seen-set: duplicates within a hop, ids seen before,
  invalid slots, and the node list cut short of and past the count."""
  rng = np.random.default_rng([seed, num_ids, m])
  seen = _seen_after_seeds(rng, num_ids)
  budget = 40
  for _ in range(3):
    ids = jnp.asarray(rng.integers(0, num_ids, m).astype(np.int32))
    ok = jnp.asarray(rng.random(m) < 0.8)
    slow, fast = _both_dedups(*seen, ids, ok)
    assert slow.keys() == fast.keys()
    for k in slow:
      np.testing.assert_array_equal(np.asarray(slow[k]),
                                    np.asarray(fast[k]), err_msg=k)
    seen = (slow['u_ids2'], slow['u_labs2'], slow['count2'])
    budget += m
    for cut in (budget, 30, int(seen[2]) + 1):
      np.testing.assert_array_equal(
          np.asarray(sorted_nodes_by_label(*seen, cut)),
          np.asarray(sorted_nodes_by_label(*seen, cut, fast_compile=True)))


def test_keep_sample_hands_back_the_batch_the_step_trained_on():
  """``last_sample`` is what the sampler's own program draws on the step's
  key, field for field, and a step built without it keeps nothing and
  trains alike."""
  edges, feats, labels = typed_graph()
  kept, tx = build_step(edges, feats, labels, 2, 2, keep_sample=True)
  plain, _ = build_step(edges, feats, labels, 2, 2)
  params = kept.init_params(jax.random.key(0))
  seeds, key = feed(0)
  nv = np.full(1, BATCH)
  _, _, loss_kept = kept(params, tx.init(params), seeds, nv, key)
  _, _, loss_plain = plain(params, tx.init(params), seeds, nv, key)
  assert plain.last_sample is None
  np.testing.assert_array_equal(np.asarray(loss_kept),
                                np.asarray(loss_plain))
  want = kept.sampler.sample_from_nodes('a', seeds, key=key)
  got = kept.last_sample
  assert set(got) == {'node', 'node_count', 'row', 'col', 'edge_mask'}
  for field, by_key in got.items():
    assert by_key.keys() == want[field].keys(), field
    for k, v in by_key.items():
      np.testing.assert_array_equal(np.asarray(v),
                                    np.asarray(want[field][k]),
                                    err_msg=f'{field} {k}')


def test_one_partition_one_hop_draws_edges_of_the_graph():
  """No bucketing on one partition: every sampled edge is the graph's, no
  parent holds more children than the fanout, the seeds lead."""
  edges, feats, labels = typed_graph(3)
  step, _ = build_step(edges, feats, labels, 2, 3)
  have = {e: set(map(tuple, ei.T)) for e, ei in edges.items()}
  for t in range(3):
    seeds, key = feed(t)
    out = step.sampler.sample_from_nodes('a', seeds, key=key)
    nodes = {k: np.asarray(v)[0] for k, v in out['node'].items()}
    np.testing.assert_array_equal(nodes['a'][:BATCH], seeds)
    drawn = 0
    for flow, row in out['row'].items():
      ok = np.asarray(out['edge_mask'][flow])[0]
      child = nodes[flow[0]][np.asarray(row)[0][ok]]
      parent_label = np.asarray(out['col'][flow])[0][ok]
      parent = nodes[flow[2]][parent_label]
      stored = reverse_edge_type(flow)
      assert set(zip(parent, child)) <= have[stored], flow
      # a parent is expanded once, by at most the largest fanout
      assert np.bincount(parent_label, minlength=1).max() <= 3
      drawn += int(ok.sum())
    assert drawn > 0


def test_one_partition_lookup_reads_the_rows():
  """The lookup on one partition is a plain read: the rows of the ids,
  noughts where a slot is invalid."""
  edges, feats, labels = typed_graph()
  step, _ = build_step(edges, feats, labels, 2, 2)
  store = step.features['b']
  ids = np.array([3, 29, 0, 3, 11, 7], np.int32)
  valid = np.array([1, 1, 0, 1, 1, 0], bool)
  got = np.asarray(store.lookup(ids, valid))
  np.testing.assert_array_equal(got, feats['b'][ids] * valid[:, None])
  assert COUNTS['b'] == feats['b'].shape[0]
