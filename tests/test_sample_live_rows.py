"""``sample_neighbors`` over the frontier's live rows alone: with
``HOP_CHUNK`` patched small, the chunked read gives the plain read's
``mask`` everywhere and its ``nbrs`` / ``eids`` under it, bit for bit;
it counts the rows it read; the live count alone chooses between the two
(one ``lax.cond``); a frontier of one chunk traces to the plain read; and
the hop loops (one type and typed) hand back the plain read's batch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glt_tpu.ops import sample
from glt_tpu.ops.sample import sample_neighbors

import test_sampler_contract as homo
import test_sampler_contract_typed as typed
from sampler_oracle import (EdgeTable, check_hop, check_multihop,
                            check_multihop_typed)

CHUNK, FANOUT = 16, 4
SLOTS = 3 * CHUNK + 7    # three chunks and a ragged tail
DEAD = np.iinfo(np.int32).max


def _graph(seed=0, n=300):
  """Degrees 0 to 11 around a fanout of 4, ids that are no identity."""
  rng = np.random.default_rng(seed)
  deg = rng.integers(0, 12, n)
  indptr = np.zeros(n + 1, np.int32)
  indptr[1:] = np.cumsum(deg)
  e = int(indptr[-1])
  return (indptr, rng.integers(0, n, e).astype(np.int32),
          (rng.permutation(e) * 3 + 1).astype(np.int32))


def _frontier(share, seed=1, n=300):
  """A scattered mask of about ``share`` live slots; a dead slot holds
  the hop loops' MAX."""
  rng = np.random.default_rng(seed)
  live = (np.ones(SLOTS, bool) if share == 1.0
          else rng.random(SLOTS) < share)
  ids = np.where(live, rng.integers(0, n, SLOTS), DEAD).astype(np.int32)
  return ids, live


def _plain(graph, ids, live, key, with_eids, replace):
  """The read of every slot: what the parent of the chunked read ran."""
  indptr, indices, eids = (jnp.asarray(a) for a in graph)
  return sample._read_rows(
      indptr, indices, eids if with_eids else None, jnp.asarray(ids),
      jnp.asarray(live),
      lambda: sample._hop_uniforms(key, ids.shape[0], FANOUT, replace),
      FANOUT, replace)


def _read(graph, ids, live, key, with_eids, replace):
  indptr, indices, eids = (jnp.asarray(a) for a in graph)
  return jax.jit(lambda i, m, k: sample_neighbors(
      indptr, indices, i, FANOUT, k, seed_mask=m,
      edge_ids=eids if with_eids else None, replace=replace))(
          jnp.asarray(ids), jnp.asarray(live), key)


def _expected_rows(live):
  read = -(-int(live.sum()) // CHUNK) * CHUNK
  return read if read <= int(sample.HOP_LIVE_SHARE * SLOTS) else SLOTS


@pytest.fixture
def small_chunk(monkeypatch):
  monkeypatch.setattr(sample, 'HOP_CHUNK', CHUNK)


@pytest.mark.parametrize('replace', [False, True], ids=['floyd', 'replace'])
@pytest.mark.parametrize('with_eids', [False, True],
                         ids=['slots', 'edge_ids'])
@pytest.mark.parametrize('share', [0.0, 0.05, 0.37, 1.0])
def test_the_chunked_read_is_the_plain_read_under_the_mask(
    small_chunk, share, with_eids, replace):
  graph = _graph()
  ids, live = _frontier(share)
  key = jax.random.key(7)
  want = jax.tree.map(np.asarray,
                      _plain(graph, ids, live, key, with_eids, replace))
  got = _read(graph, ids, live, key, with_eids, replace)
  mask = np.asarray(got.mask)
  np.testing.assert_array_equal(mask, want[1])
  np.testing.assert_array_equal(np.asarray(got.nbrs)[mask], want[0][mask])
  np.testing.assert_array_equal(np.asarray(got.eids)[mask], want[2][mask])
  assert got.nbrs.dtype == want[0].dtype and got.eids.dtype == want[2].dtype
  assert not mask[~live].any()
  # and the rows are the graph's: the oracle knows nothing of either read
  check_hop(EdgeTable.from_csr(graph[0], graph[1],
                               graph[2] if with_eids else None),
            np.where(live, ids, 0), FANOUT, np.asarray(got.nbrs), mask,
            np.asarray(got.eids), seed_mask=live, replace=replace)
  # what it read: the live rows in whole chunks, or every slot
  assert int(got.rows_read) == _expected_rows(live)
  if share == 1.0:
    assert int(got.rows_read) == SLOTS
  elif share < 0.5:
    assert int(got.rows_read) == -(-int(live.sum()) // CHUNK) * CHUNK


@pytest.mark.parametrize('live_rows,chunked', [
    (0, True), (1, True), (CHUNK, True), (CHUNK + 1, True),
    (SLOTS - 8, False), (SLOTS, False)])
def test_the_live_count_alone_chooses_the_read(small_chunk, live_rows,
                                               chunked):
  """A frontier whose live rows fill every chunk takes the plain read at
  run time: one program, one ``cond`` on the live count."""
  graph = _graph(3)
  rng = np.random.default_rng(live_rows)
  live = np.zeros(SLOTS, bool)
  live[rng.permutation(SLOTS)[:live_rows]] = True
  ids = np.where(live, rng.integers(0, 300, SLOTS), DEAD).astype(np.int32)
  got = _read(graph, ids, live, jax.random.key(1), False, False)
  want = -(-live_rows // CHUNK) * CHUNK if chunked else SLOTS
  assert int(got.rows_read) == want == _expected_rows(live)


def _primitives(jaxpr, found=None):
  found = set() if found is None else found
  for eqn in jaxpr.eqns:
    found.add(eqn.primitive.name)
    for sub in jax.core.jaxprs_in_params(eqn.params):
      _primitives(sub, found)
  return found


@pytest.mark.parametrize('replace', [False, True], ids=['floyd', 'replace'])
def test_a_frontier_of_one_chunk_traces_to_the_plain_read(replace):
  """At the code's own ``HOP_CHUNK``: hop 0 everywhere, and the SEAL
  cell's 1,024 endpoints."""
  graph = _graph()
  indptr, indices, _ = (jnp.asarray(a) for a in graph)
  slots = min(sample.HOP_CHUNK, 1024)
  ids, live = jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), bool)
  key = jax.random.key(0)
  read = jax.make_jaxpr(lambda i, m, k: tuple(sample_neighbors(
      indptr, indices, i, FANOUT, k, seed_mask=m, replace=replace))[:3])(
          ids, live, key)
  plain = jax.make_jaxpr(lambda i, m, k: sample._read_rows(
      indptr, indices, None, i, m,
      lambda: jax.random.uniform(k, (slots, FANOUT) if replace
                                 else (FANOUT, slots)),
      FANOUT, replace))(ids, live, key)
  assert str(read) == str(plain)
  assert not _primitives(read.jaxpr) & {'while', 'cond'}
  out = sample_neighbors(indptr, indices, ids, FANOUT, key, seed_mask=live)
  assert out.rows_read == slots and isinstance(out.rows_read, int)
  # one slot more and the program holds both reads
  wide = jax.make_jaxpr(lambda i, m, k: tuple(sample_neighbors(
      indptr, indices, i, FANOUT, k, seed_mask=m)))(
          jnp.zeros((sample.HOP_CHUNK + 1,), jnp.int32),
          jnp.ones((sample.HOP_CHUNK + 1,), bool), key)
  assert {'while', 'cond'} <= _primitives(wide.jaxpr)


def test_without_a_mask_every_row_is_live_and_read():
  graph = _graph()
  indptr, indices, _ = (jnp.asarray(a) for a in graph)
  ids = jnp.arange(SLOTS, dtype=jnp.int32)
  out = sample_neighbors(indptr, indices, ids, FANOUT, jax.random.key(2))
  assert out.rows_read == SLOTS


# -- the hop loops ----------------------------------------------------------

BATCH_KEYS = ('node', 'node_count', 'row', 'col', 'edge_mask', 'batch',
              'seed_labels', 'num_sampled_nodes', 'num_sampled_edges')


@pytest.mark.parametrize('with_edge', [False, True],
                         ids=['no_edge', 'with_edge'])
@pytest.mark.parametrize('name', list(homo.MULTIHOP))
def test_the_hop_loop_hands_back_the_plain_reads_batch(
    monkeypatch, name, with_edge):
  make, seeds, n_valid = homo.MULTIHOP[name]
  graph, fanouts = make(), (4, 3, 2)
  key = jax.random.key(5)
  want = homo._multihop(graph, seeds, n_valid, fanouts, with_edge, key)
  monkeypatch.setattr(sample, 'HOP_CHUNK', 4)
  got = homo._multihop(graph, seeds, n_valid, fanouts, with_edge, key)
  for k in BATCH_KEYS:
    np.testing.assert_array_equal(got[k], want[k], err_msg=k)
  if with_edge:
    np.testing.assert_array_equal(got['edge'][got['edge_mask']],
                                  want['edge'][want['edge_mask']])
  check_multihop(EdgeTable.from_csr(*graph), seeds, n_valid, fanouts, got,
                 new_label_order='value')
  # frontiers of 8, 32 and 96 slots: hop 0's rows may fill both of its
  # chunks, the later hops' live rows are few
  slots = np.asarray([8, 32, 96])
  np.testing.assert_array_equal(want['hop_rows_read'], slots)
  assert (got['hop_rows_read'] <= slots).all()
  assert (got['hop_rows_read'] % 4 == 0).all()
  live = np.concatenate([[got['num_sampled_nodes'][0]],
                         got['num_sampled_nodes'][1:-1]])
  assert (got['hop_rows_read'] >= live).all()
  if n_valid:
    assert got['hop_rows_read'][2] < 96


@pytest.mark.parametrize('name', list(typed.CASES))
def test_the_typed_hop_loop_hands_back_the_plain_reads_batch(
    monkeypatch, name):
  from glt_tpu.sampler import NeighborSampler
  from glt_tpu.sampler.base import NodeSamplerInput
  make, fanouts, seeds, n_valid = typed.CASES[name]
  ds, graphs = make()
  seeds = {t: np.asarray(s, np.int64) for t, s in seeds.items()}
  inputs = (seeds if len(seeds) > 1
            else NodeSamplerInput(*reversed(next(iter(seeds.items())))))

  def sampled():
    samp = NeighborSampler(ds.graph, fanouts, seed=4, with_edge=True)
    return typed._traversal_output(
        samp.sample_from_nodes(inputs, n_valid=n_valid), True)

  want = sampled()
  monkeypatch.setattr(sample, 'HOP_CHUNK', 2)
  got = sampled()
  for k in BATCH_KEYS:
    for part in want[k]:
      np.testing.assert_array_equal(got[k][part], want[k][part],
                                    err_msg=f'{k} {part}')
  for e, mask in want['edge_mask'].items():
    np.testing.assert_array_equal(got['edge'][e][mask],
                                  want['edge'][e][mask])
  check_multihop_typed(graphs, {e: (e[0], e[2]) for e in fanouts}, fanouts,
                       seeds, {t: n_valid for t in seeds}, got,
                       new_label_order='value')


# -- the fused steps --------------------------------------------------------

def _bits(tree):
  return [np.asarray(a).view(np.uint32) for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize('chips', [1, 2])
def test_the_sage_step_trains_alike_and_counts_the_rows_it_read(
    monkeypatch, chips):
  from test_parallel import _tiny_step   # frontiers of 64 and 192 slots

  def two_steps(chunk):
    monkeypatch.setattr(sample, 'HOP_CHUNK', chunk)
    step, params, opt, seeds, n_valid, keys = _tiny_step(chips)
    losses = []
    for _ in range(2):
      params, opt, loss = step(params, opt, seeds, n_valid, keys)
      losses.append(np.asarray(loss))
    return step, losses, params

  code_chunk = sample.HOP_CHUNK
  step, losses, params = two_steps(16)
  plain, plain_losses, plain_params = two_steps(code_chunk)
  for got, want in zip(_bits((losses, params)),
                       _bits((plain_losses, plain_params))):
    np.testing.assert_array_equal(got, want)
  counted, slots = step.counters(), step.counter_slots()
  assert slots['hop_rows_read'].tolist() == [64, 192]
  for name in ('nodes_by_hop', 'edges_by_hop'):
    np.testing.assert_array_equal(counted[name], plain.counters()[name])
  np.testing.assert_array_equal(
      plain.counters()['hop_rows_read'],
      np.broadcast_to([64, 192], (2, chips, 2)))
  rows, new = counted['hop_rows_read'], counted['nodes_by_hop']
  assert rows.shape == (2, chips, 2) and rows.dtype == np.int32
  # hop 1 expands hop 0's new nodes alone: whole chunks of them
  np.testing.assert_array_equal(rows[..., 1], -(-new[..., 1] // 16) * 16)
  assert (rows[..., 1] < 192).all()


def test_the_typed_step_trains_alike_and_counts_the_rows_it_read(
    monkeypatch):
  import test_typed_build_forms as forms
  from test_rgat_step import train
  edges, feats, labels = forms.typed_graph()

  def two_steps(chunk):
    monkeypatch.setattr(sample, 'HOP_CHUNK', chunk)
    step, tx = forms.build_step(edges, feats, labels, 2, 2,
                                keep_sample=True)
    losses, _, params = train(step, tx,
                              step.init_params(jax.random.key(0)), steps=2)
    return step, losses, params

  code_chunk = sample.HOP_CHUNK
  step, losses, params = two_steps(4)
  plain, plain_losses, plain_params = two_steps(code_chunk)
  assert losses == plain_losses
  for got, want in zip(_bits(params), _bits(plain_params)):
    np.testing.assert_array_equal(got, want)
  for got, want in zip(jax.tree.leaves(step.last_sample),
                       jax.tree.leaves(plain.last_sample)):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
  counted, slots = step.counters(), step.counter_slots()
  np.testing.assert_array_equal(
      plain.counters()['hop_rows_read'],
      np.broadcast_to(slots['hop_rows_read'],
                      (2, 1) + slots['hop_rows_read'].shape))
  for name in ('nodes_by_hop', 'edges_by_hop'):
    np.testing.assert_array_equal(counted[name], plain.counters()[name])
  rows = counted['hop_rows_read']
  assert rows.shape == (2, 1) + slots['hop_rows_read'].shape
  assert (rows <= slots['hop_rows_read']).all()
  assert rows.sum() < 2 * slots['hop_rows_read'].sum()   # it engaged
  # a hop read whole chunks of its live rows, or every slot
  assert ((rows % 4 == 0) | (rows == slots['hop_rows_read'])).all()
