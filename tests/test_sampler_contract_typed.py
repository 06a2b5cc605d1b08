"""The typed sampler held against the numpy oracle
(tests/sampler_oracle.py), through ``NeighborSampler`` on the one typed
hop loop (ops/pipeline.py), the one the typed cells run.

The cases are the typed inputs of the deleted tests/test_pallas_fused.py,
which compared an interpreted kernel with this path; several were
``slow`` under the interpreter and are not here. One-type cases:
tests/test_sampler_contract.py.
"""
import numpy as np
import pytest

from glt_tpu.data import Dataset
from glt_tpu.sampler import NeighborSampler
from glt_tpu.sampler.base import NodeSamplerInput
from glt_tpu.typing import reverse_edge_type

from fixtures import hetero_ring_dataset, ring_edges
from sampler_oracle import EdgeTable, check_multihop_typed

U2I = ('user', 'u2i', 'item')
I2I = ('item', 'i2i', 'item')


def _u2i_edges(nu, ni):
  u = np.arange(nu)
  return np.stack([np.repeat(u, 2),
                   np.stack([2 * u, 2 * u + 1], 1).reshape(-1) % ni])


def _dataset(edge_index, num_nodes):
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=edge_index, num_nodes=num_nodes)
  return ds, {e: EdgeTable(ei[0], ei[1]) for e, ei in edge_index.items()}


def _hub_rows_in_one_type(nu=8, ni=24, hub_deg=120):
  """item 0 is a hub of i2i (above the old window of 96, with parallel
  edges); every other row of both relations is short."""
  hub_dst = (np.arange(hub_deg) + 1) % ni
  i = np.arange(1, ni)
  i2i = np.stack([np.concatenate([np.zeros(hub_deg, np.int64), i]),
                  np.concatenate([hub_dst, (i + 1) % ni])])
  return _dataset({U2I: _u2i_edges(nu, ni), I2I: i2i},
                  {'user': nu, 'item': ni})


def _ring():
  ds = hetero_ring_dataset(num_users=10, num_items=20)
  rows, cols, eids = ring_edges(20)
  u2i = _u2i_edges(10, 20)
  return ds, {U2I: EdgeTable(u2i[0], u2i[1], np.arange(20)),
              I2I: EdgeTable(rows, cols, eids)}


def _no_relation_into_user():
  # nothing expands INTO 'user': its budget is the seeds alone, and the
  # u2i frontier is empty after hop 0
  return _dataset({U2I: _u2i_edges(6, 12)}, {'user': 6, 'item': 12})


def _empty_relation():
  return _dataset({U2I: _u2i_edges(6, 12),
                   I2I: np.zeros((2, 0), np.int64)},
                  {'user': 6, 'item': 12})


CASES = {
    # name: (dataset, fanouts, seeds by type, n_valid)
    'hub_rows_in_one_type': (
        _hub_rows_in_one_type, {U2I: [2, 2], I2I: [3, 2]},
        {'user': [3, 0, 3, 7]}, 4),
    'n_valid_zero': (
        _hub_rows_in_one_type, {U2I: [2, 2], I2I: [3, 2]},
        {'user': [3, 0, 3, 7]}, 0),
    'zero_budget_type': (
        _no_relation_into_user, {U2I: [2, 2]}, {'user': [1, 2, 5]}, 3),
    'empty_relation': (
        _empty_relation, {U2I: [2, 2], I2I: [2, 2]},
        {'user': [1, 2, 5]}, 3),
    'duplicate_seeds_and_padding': (
        _ring, {U2I: [2, 2], I2I: [2, 2]},
        {'user': [3, 0, 3, 7, 9, 1]}, 5),
    'two_seed_types': (
        _ring, {U2I: [2, 2], I2I: [2, 2]},
        {'user': [1, 2, 5], 'item': [0, 7, 7, 3]}, 3),
    'mixed_fanouts': (
        _ring, {U2I: [3, 1], I2I: [1, 2]}, {'user': [4, 4, 0, 9]}, 4),
    'three_hops': (
        _ring, {U2I: [2, 1, 1], I2I: [2, 2, 1]}, {'user': [8, 2]}, 2),
}


def _traversal_output(out, with_edge):
  """HeteroSamplerOutput -> numpy arrays keyed by traversal relation
  (``edge_dir='out'`` files a relation under its reverse)."""
  by_rel = lambda d: {reverse_edge_type(k): np.asarray(v)
                      for k, v in d.items()}
  by_type = lambda d: {t: np.asarray(v) for t, v in d.items()}
  return dict(
      node=by_type(out.node), node_count=by_type(out.node_count),
      batch=by_type(out.batch),
      seed_labels=by_type(out.metadata['seed_labels']),
      num_sampled_nodes=by_type(out.num_sampled_nodes),
      row=by_rel(out.row), col=by_rel(out.col),
      edge_mask=by_rel(out.edge_mask),
      edge=by_rel(out.edge) if with_edge else None,
      num_sampled_edges=by_rel(out.num_sampled_edges))


@pytest.mark.parametrize('with_edge', [False, True],
                         ids=['no_edge', 'with_edge'])
@pytest.mark.parametrize('name', list(CASES))
def test_typed_multihop_sort_fused(name, with_edge):
  make, fanouts, seeds, n_valid = CASES[name]
  ds, graphs = make()
  seeds = {t: np.asarray(s, np.int64) for t, s in seeds.items()}
  samp = NeighborSampler(ds.graph, fanouts, seed=4, with_edge=with_edge)
  inputs = (seeds if len(seeds) > 1
            else NodeSamplerInput(*reversed(next(iter(seeds.items())))))
  out = samp.sample_from_nodes(inputs, n_valid=n_valid)
  got = _traversal_output(out, with_edge)
  trav = {e: (e[0], e[2]) for e in fanouts}
  check_multihop_typed(graphs, trav, fanouts, seeds,
                       {t: n_valid for t in seeds}, got,
                       new_label_order='value')
  # the static offsets the sampler hands to the per-layer trim
  offs = out.metadata['edge_hop_offsets']
  for e in got['row']:
    assert offs[reverse_edge_type(e)][-1] == got['row'][e].shape[0]
  if n_valid == 0:
    assert all(int(c) == 0 for c in got['node_count'].values())
  assert samp.num_compiled_fns == 1
  samp.sample_from_nodes(inputs, n_valid=n_valid)
  assert samp.num_compiled_fns == 1


@pytest.mark.parametrize('name', ['two_seed_types', 'hub_rows_in_one_type'])
def test_typed_multihop_with_replacement(name):
  """Draws with replacement: ``k`` lanes a row of positive degree,
  children repeating inside a group, and the dedup across them."""
  make, fanouts, seeds, n_valid = CASES[name]
  ds, graphs = make()
  seeds = {t: np.asarray(s, np.int64) for t, s in seeds.items()}
  inputs = (seeds if len(seeds) > 1
            else NodeSamplerInput(*reversed(next(iter(seeds.items())))))
  out = NeighborSampler(ds.graph, fanouts, seed=4, with_edge=True,
                        replace=True).sample_from_nodes(inputs,
                                                        n_valid=n_valid)
  check_multihop_typed(graphs, {e: (e[0], e[2]) for e in fanouts},
                       fanouts, seeds, {t: n_valid for t in seeds},
                       _traversal_output(out, True), replace=True,
                       new_label_order='value')


@pytest.mark.parametrize('name', ['two_seed_types',
                                  'duplicate_seeds_and_padding',
                                  'three_hops'])
def test_a_permuted_hop_block_breaks_the_promise(name):
  """``hop_fanouts_dict``'s promise is held of the typed loop's batch,
  and a batch that is right in all but the order of one hop block's
  lanes (the same edges, rolled by one lane) is refused for it."""
  make, fanouts, seeds, n_valid = CASES[name]
  ds, graphs = make()
  seeds = {t: np.asarray(s, np.int64) for t, s in seeds.items()}
  inputs = (seeds if len(seeds) > 1
            else NodeSamplerInput(*reversed(next(iter(seeds.items())))))
  out = NeighborSampler(ds.graph, fanouts, seed=4, with_edge=True
                        ).sample_from_nodes(inputs, n_valid=n_valid)
  got = _traversal_output(out, True)
  check = lambda: check_multihop_typed(
      graphs, {e: (e[0], e[2]) for e in fanouts}, fanouts, seeds,
      {t: n_valid for t in seeds}, got, new_label_order='value')
  check()
  lo, hi = out.metadata['edge_hop_offsets'][reverse_edge_type(I2I)][1:3]
  assert hi - lo > 2 and got['edge_mask'][I2I][lo:hi].any()
  for name in ('row', 'col', 'edge_mask', 'edge'):
    block = got[name][I2I].copy()
    block[lo:hi] = np.roll(block[lo:hi], 1)
    got[name][I2I] = block
  with pytest.raises(AssertionError, match='col changes inside a group'):
    check()
