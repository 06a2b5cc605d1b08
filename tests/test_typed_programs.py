"""The six tiny step programs are pinned: the R-GAT step, with the
promise of parent-major edge slots and with it withheld, the three SAGE
steps and the enclosing-subgraph step lower to the programs they were
when a PR last meant to change them, so a PR that changes a cell's
program knows it."""
import hashlib
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARENT_STABLEHLO = {
    # sha256 of the tiny cells' step programs as StableHLO (no locations),
    # on the hop loop the chip runs: a PR that means to change one
    # of these programs reads the hash anew and says so. All five were
    # read anew by the PR that gave every per-batch step the counters
    # output: ``(loss, counters)`` with the sampler's ``nodes_by_hop`` and
    # ``edges_by_hop`` (one reduce of a mask a hop and relation, which
    # lowering dropped while nothing read them), the link front's and the
    # exchanging store's counters in the same dict. Before it they were
    # c1 796cd17c...1f391096, c4 f4a4b6de...9e409b5347 (the PR of the
    # store's per-owner buckets of ceil(b / P) slots), link
    # 4a6baa93...456f93fe, typed 196fde82...e6904d19 and withheld
    # 3c10d6a0...4b14d519 (the parent of the PR that brought HGT).
    # The four one-chip programs were read anew by the PR of the store's
    # chunked serve (one more counter out, ``store_chunks``: at this size
    # a type's request slots are one chunk and the gather is the plain
    # one); before it they were c1 8a3479ca...e1a8f991, link
    # 5753ec09...d933d72e12, typed 27b86ebe...ba05094a and withheld
    # 94b4a39e...f1685307. c4's exchange is that PR's parent's, text for
    # text: its hash stands.
    # c4 alone was read anew by PR 39, which took the stable argsort by
    # owner out of the four-shard exchange (a request's bucket slot is its
    # owner and its rank in request order: one blocked running count an
    # owner, the pack's scatter straight to that slot, one gather back);
    # before it c4 was 53ee57ed...d1e6937c. The four one-chip programs
    # never bucket: their hashes stand.
    # All five were read anew by PR 41, which gave every per-batch step
    # whose sampler walks hops one more counter out, ``hop_rows_read``
    # (the frontier rows each hop read ``indptr`` and ``indices`` for).
    # At these sizes every frontier is one chunk of
    # ``ops/sample.py::HOP_CHUNK`` or fewer, so each hop traces to the
    # plain read it was, the counter is a stack of constants (the
    # frontiers' slots) and the text differs by that output and the
    # numbering behind it alone (the parent's texts diffed against these:
    # nothing else). Before it they were c1 77dfcba4...b1c27162, c4
    # d0fff173...424ed731, link 3b9a9823...195b22a2, typed
    # 0b627d6e...c4fa8de0 and withheld 6eb0e928...ec114b53.
    # All six were read anew by PR 43, which took the dedup state out of
    # every step: the one inducer keeps its seen-set in batch-sized
    # arrays, so the ``(1,)`` int32 placeholders each program took,
    # donated and handed back (a pair a device on the SAGE steps, a pair
    # a node type on the typed steps) are gone. The parent's texts
    # diffed against these differ by those parameters and results, their
    # unstacking slices and restacking broadcasts, and the numbering
    # behind them alone. Before it they were c1 d8421d94...679acddb, c4
    # 52c9d8d6...4101d017f, link 6c3771c0...acf941d0, typed
    # 4042ca19...16418285 and withheld 057c64d7...25c3b24d.
    # c4 alone was read anew when the exchange came to pack, serve and
    # stitch chunk by chunk and to write each drain round's rows into
    # the drain's carry (no add), with one more counter out,
    # ``store_exchange_chunks``; before it c4 was b63edad1...5dc9235. The
    # one-chip programs never bucket: their hashes stand.
    'c1': 'dce74e7ff2cf5074c5831346f1ba4a4cdb92024bd1aa5f330cbc342f8b7a475e',
    'c4': '5b94e094cca91237558de9c495441fb416992817e9e831a399aaa1fb81eb8b49',
    'link': '1c1c110293a8a9850b47d426523db8f4ecb44936624c130be98115b31280f9e2',
    'typed': 'bfb0c9233e41fc3965a2fb0a795e8e8e60c546c56fea4c053b9ab979228f28a6',
    'typed_withheld':
        'c9b3cd74961ff6028d06cf0b6ee05cee865ca10a0702fbcb02cda24f1788e4c4',
    # the enclosing-subgraph step, pinned by PR 41 at its parent's text
    # (read on both trees): its body walks no hop loop and is left as it
    # is, and its one hop's 4B endpoints are one chunk, so
    # ``sample_neighbors`` traces to the plain read it was. Read anew by
    # PR 42, the one PR whose change is this step's: the induction of
    # ``ops/subgraph.py::enclosing_subgraphs`` is a loop over the batch's
    # live tiles and the step counts ``tiles_matched``; before it
    # 8dee044f...04858d05. The five above are that PR's parent's, and so
    # are the HGT and user-item cells' tiny steps (hashed on both trees by
    # a scratch script: CHANGES.md). PR 43 read it anew with the five
    # above; before it 68a54cdf...ac22cf7.
    'seal': '2366498f95e06fe7ff95e551a2615b095a9e485e390ab530c565fb9182837665',
}


def _sage_text(driver, cell, chips):
  from jax.sharding import NamedSharding, PartitionSpec as P
  _, _, cfg, traffic = cell
  s = driver.build(cfg, traffic, chips, 5)
  t = s.trainer
  rows = NamedSharding(t.mesh, P(t.axis))
  seeds, keys = driver.feed(s, 0)
  return t._step_fn.lower(
      s.params, s.opt,
      jax.device_put(np.asarray(seeds, np.int32), rows),
      jax.device_put(s.n_valid, rows), keys, t.feature.array, t.labels,
      t._indptr, t._indices).as_text()


def _typed_text(withheld):
  from jax.sharding import NamedSharding, PartitionSpec as P
  from chipbench.drivers import hetero_fused
  import test_rgat_cell
  _, _, cfg, traffic = test_rgat_cell.tiny_cell()
  s = hetero_fused.build(cfg, traffic, 1, 5)
  t = s.trainer
  if withheld:
    t._batch_static['hop_fanouts_dict'] = None
    t._step_fn = t._build()
  run = t._step_fn
  cells = dict(zip(run.__code__.co_freevars,
                   (c.cell_contents for c in run.__closure__)))
  shards, feat_shards, efeat_shards = cells['payloads']()
  shard = NamedSharding(t.mesh, P(t.axis))
  seeds, key = hetero_fused.feed(s, 0)
  return run.jitted.lower(
      s.params, s.opt, shards, feat_shards, efeat_shards, t.labels,
      jax.device_put(np.asarray(seeds, np.int32).reshape(-1), shard),
      jax.device_put(np.asarray(s.n_valid, np.int32), shard),
      jax.random.split(key, 1)).as_text()


@pytest.mark.parametrize('name', sorted(PARENT_STABLEHLO))
def test_the_other_cells_tiny_steps_lower_to_the_parents(name):
  """R-GAT's typed step, with the promise and with it withheld, the three
  SAGE steps and the enclosing-subgraph step lower to the StableHLO
  pinned above."""
  sys.path.insert(0, REPO)
  sys.path.insert(0, os.path.join(REPO, 'tests', 'chipbench'))
  if name.startswith('typed'):
    text = _typed_text(name == 'typed_withheld')
  else:
    from chipbench.drivers import fused, link_fused, seal_fused
    import test_chipbench
    import test_link_cell
    import test_seal_cell
    text = {'c1': lambda: _sage_text(fused, test_chipbench.tiny_cell(1), 1),
            'c4': lambda: _sage_text(fused, test_chipbench.tiny_cell(4), 4),
            'link': lambda: _sage_text(link_fused, test_link_cell.tiny_cell(),
                                       1),
            'seal': lambda: _sage_text(seal_fused, test_seal_cell.tiny_cell(),
                                       1)}[name]()
  assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STABLEHLO[name]


def _scoped_primitives(jaxpr, prefix=''):
  """(name stack, primitive) of every equation, through every inner
  jaxpr (shard_map, while, pjit)."""
  out = []
  for eqn in jaxpr.eqns:
    stack = '/'.join(x for x in (prefix, str(eqn.source_info.name_stack))
                     if x)
    out.append((stack, eqn.primitive.name))
    for value in eqn.params.values():
      for sub in value if isinstance(value, (tuple, list)) else (value,):
        inner = getattr(sub, 'jaxpr', sub)
        if hasattr(inner, 'eqns'):
          out += _scoped_primitives(inner, stack)
  return out


@pytest.mark.parametrize('bucket_cap', [0, 10_000], ids=['drain', 'one_round'])
def test_the_four_shard_exchange_holds_no_sort_and_stitches_by_one_gather(
    bucket_cap):
  """The map between request order and bucket order is no permutation
  (PR 39): the exchange's program has no sort, its one scatter is the
  pack's, and the stitch is one gather with nothing scattered behind
  it. So an argsort, a ``bincount`` by scatter-add or an
  ``.at[order].set`` cannot come back unnoticed."""
  import jax.numpy as jnp
  from jax.sharding import PartitionSpec as P
  from glt_tpu.parallel import ShardedFeature, make_mesh
  n, d, b = 96, 4, 600
  sf = ShardedFeature(np.arange(n * d, dtype=np.float32).reshape(n, d),
                      make_mesh(4), bucket_cap=bucket_cap)
  def counted(shard, i, v):
    rows, counters = sf.lookup_local(shard, i, v, counters=True)
    return rows, {k: c[None] for k, c in counters.items()}

  exchange = jax.shard_map(
      counted, mesh=sf.mesh, in_specs=(P(sf.axis),) * 3, out_specs=P(sf.axis),
      check_vma=False)
  args = (sf.array, jnp.asarray(np.arange(4 * b, dtype=np.int32) % n),
          jnp.ones(4 * b, bool))
  text = jax.jit(exchange).lower(*args).as_text()
  assert 'stablehlo.sort' not in text
  assert text.count('"stablehlo.scatter"') == 1
  assert ('stablehlo.while' in text) == (bucket_cap == 0)
  ops = _scoped_primitives(jax.make_jaxpr(exchange)(*args).jaxpr)
  store = [(stack, name) for stack, name in ops if 'feature_store' in stack]
  assert not [op for op in store if 'sort' in op[1]]
  assert [stack for stack, name in store if name.startswith('scatter')] == [
      'feature_store/bucket']
  assert [name for stack, name in store
          if stack == 'feature_store/unbucket' and
          name in ('gather', 'dynamic_slice', 'scatter', 'scatter-add')] == [
              'gather']
