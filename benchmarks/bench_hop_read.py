"""One hop's read over a padded frontier: the plain read against the
live-rows read, on one device.

``ops/sample.py::sample_neighbors`` reads ``indptr`` twice and ``indices``
``K`` times for every frontier slot; the sort engine's hop loops keep the
frontier slot-aligned, so at hop 2 of the GraphSAGE cells 62 % of the
slots are no new head (degree 0, every lane clipped to ONE address), and
86 % in the typed cells. ``--forms`` times, at a cell's hop-2 shape and
live share (defaults: ``papers100m-c1.fused``: 153,600 slots, fanout 5,
37.9 % live and scattered, 8 M rows, 116.4 M edges; ``--cell rgat``: the
R-GAT cell's frontier of papers over ``cites``: 48,000 slots, 13.5 %
live, 1.6 M rows, 23.7 M edges, with ``edge_ids``):

  * ``plain``: the read of every slot, as the parent of PR 41 has it;
  * ``plain_spread``: the same with each dead slot on a row of its own
    (what ROADMAP S1c would have given: no two dead lanes on one address);
  * ``library``: ``sample_neighbors`` as the tree has it, with
    ``HOP_CHUNK`` patched to 1,024 / 2,048 / 4,096 / 8,192;
  * the candidate ways back to slot order (``--backs``) at ``--form-chunks``:
    the chunk's rows scattered to their slots inside the loop (``scatter``:
    a row scatter; ``flat``: an element scatter into the flat planes), or
    kept in a prefix and brought back after it (``gather``: the library's
    row gather by rank; ``gather_t``, ``gather_flat``: other layouts of the
    prefix; ``expand<block>``: no gather at all, a one-hot product a block
    of slots on the matrix unit), and the chunk's draws made in place
    (``draws_at``) where the library draws all and takes its columns;
  * ``--shares``: ``plain`` and ``library`` over live shares from 10 to
    100 %, for the share at which the plain read is the faster one
    (``HOP_LIVE_SHARE``).

A cell without edge ids hands back ``nbrs`` and ``mask`` alone, as its
step does (no driver asks for ``with_edge``), so the compiler drops the
``eids`` planes here as it does there.
Every form is held to the plain read (``mask`` everywhere, ``nbrs`` and
``eids`` under it) before it is timed. One JSON line. A time is the host
clock around ``--iters`` dispatches that end in ``block_until_ready``; it
means something on a chip only (``--cpu`` is for rehearsal).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

CELLS = {
    # slots, fanout, live share, rows, edges, edge_ids
    'c1': (153_600, 5, 0.379, 8_000_000, 116_400_000, False),
    'c1_hop1': (15_360, 10, 0.537, 8_000_000, 116_400_000, False),
    'rgat': (48_000, 5, 0.135, 1_600_000, 23_740_107, True),
}
CHUNKS = (1024, 2048, 4096, 8192)


def _plain(sample, indptr, indices, edge_ids, seeds, mask, key, fanout):
  return sample._read_rows(
      indptr, indices, edge_ids, seeds, mask,
      lambda: sample._hop_uniforms(key, seeds.shape[0], fanout, False),
      fanout, False)


def _expand_by_rank(compact_t, rank, seed_mask, block):
  """``[K, M]`` int32 columns in rank order -> ``[S, K]`` rows in slot
  order, 0 in a dead slot, with no gather of elements: ranks rise with
  the slots, so the live slots of a block of ``block`` slots hold ranks
  of one window of ``block`` from the live count before it. The window
  lies in two adjacent tiles of ``block`` ranks (one row gather of two
  tiles a block: a ``dynamic_slice`` a block lowers to a loop of as many
  trips), and the block picks its columns by a one-hot product on the
  matrix unit, a byte of the int32 at a time (bfloat16 holds 0 to 255
  exactly, and one term of a sum is not 0)."""
  import jax.numpy as jnp
  k, m = compact_t.shape
  s = rank.shape[0]
  nb, tiles = -(-s // block), -(-m // block) + 1
  rank_b = jnp.pad(rank, (0, nb * block - s)).reshape(nb, block)
  mask_b = jnp.pad(seed_mask, (0, nb * block - s)).reshape(nb, block)
  base = rank_b[:, 0] + 1 - mask_b[:, 0].astype(jnp.int32)
  tile = base // block
  local = rank_b - (tile * block)[:, None]               # in [0, 2 block)
  comp = jnp.pad(compact_t, ((0, 0), (0, tiles * block - m)))
  comp = comp.reshape(k, tiles, block).transpose(1, 0, 2)   # [tiles, K, b]
  window = jnp.take(comp, jnp.stack([tile, tile + 1], 1), axis=0,
                    mode='clip')                         # [nb, 2, K, b]
  window = window.transpose(0, 2, 1, 3).reshape(nb, k, 2 * block)
  parts = jnp.concatenate([(window >> sh) & 255 for sh in (0, 8, 16, 24)],
                          axis=1).astype(jnp.bfloat16)   # [nb, 4K, 2b]
  pick = ((local[:, None, :] == jnp.arange(2 * block)[None, :, None])
          & mask_b[:, None, :]).astype(jnp.bfloat16)     # [nb, rank, slot]
  got = jnp.einsum('bkj,bji->bki', parts, pick,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
  out = (got[:, :k] | (got[:, k:2 * k] << 8) | (got[:, 2 * k:3 * k] << 16)
         | (got[:, 3 * k:] << 24))
  return out.transpose(0, 2, 1).reshape(nb * block, k)[:s]


def _uniforms_at(key, flat):
  """``jax.random.uniform(key, shape).reshape(-1)[flat]`` with no other
  element drawn: under the partitionable threefry (JAX's default) an
  element's bits are the two words of ``threefry_2x32`` on its flat
  index, xored."""
  import jax
  import jax.numpy as jnp
  from jax.extend.random import threefry_2x32
  words = jax.random.key_data(key)
  at = flat.reshape(-1).astype(jnp.uint32)
  out = threefry_2x32((words[0], words[1]),
                      jnp.concatenate([jnp.zeros_like(at), at]))
  bits = out[:at.shape[0]] ^ out[at.shape[0]:]
  ones = np.float32(1.0).view(np.uint32)
  return (jax.lax.bitcast_convert_type((bits >> 9) | ones, jnp.float32)
          - 1.0).reshape(flat.shape)


def _chunked(sample, indptr, indices, edge_ids, seeds, seed_mask, key,
             fanout, chunk, back, draws_at=False):
  """``sample._read_live_rows`` (no ``replace``) with the way back to
  slot order open: the chunk's rows scattered to their slots inside the
  loop (``scatter``: a row scatter; ``flat``: an element scatter into
  the flat planes), or kept in a prefix and brought back after it
  (``gather``: the library's row gather by rank; ``gather_t``: the
  prefix kept ``[K, rows]``; ``gather_flat``: kept as ``K`` flat planes
  of one array, which no layout pads; ``expand<block>``:
  ``_expand_by_rank``).
  ``draws_at``: a chunk's draws made in place (``_uniforms_at``) where
  the library draws all and takes the chunk's columns."""
  import jax
  import jax.numpy as jnp
  from glt_tpu.ops.scan import cumsum_i32
  s, c, k_ = seeds.shape[0], chunk, fanout
  n = -(-s // c)
  live = seed_mask.sum(dtype=jnp.int32)
  chunks = (live + (c - 1)) // c
  rank = cumsum_i32(seed_mask) - 1
  pos = jnp.arange(n * c, dtype=jnp.int32)
  order = pos.at[jnp.where(seed_mask, rank, n * c)].set(pos[:s],
                                                        mode='drop')
  u = None if draws_at else jax.random.uniform(key, (k_, s))
  lane = jnp.arange(k_, dtype=jnp.int32)
  eid_dtype = edge_ids.dtype if edge_ids is not None else jnp.int32
  in_loop = back in ('scatter', 'flat')
  across = back == 'gather_t' or back.startswith('expand')
  rows_out = s if in_loop else n * c
  shape = ((rows_out * k_,) if back in ('flat', 'gather_flat')
           else (k_, rows_out) if across else (rows_out, k_))

  def read(carry):
    k, nbrs, eids, lanes = carry
    slot = jax.lax.dynamic_slice(order, (k * c,), (c,))
    row_live = k * c + pos[:c] < live
    rows = jnp.where(row_live, jnp.take(seeds, slot, mode='clip'), slot)
    if draws_at:
      draws = lambda: _uniforms_at(
          key, lane[:, None] * s + jnp.minimum(slot, s - 1)[None, :])
    else:
      draws = lambda: jnp.take(u, slot, axis=1, mode='clip')
    got, mask, got_eids = sample._read_rows(
        indptr, indices, edge_ids, rows, row_live, draws, k_, False)
    count = mask.sum(axis=1, dtype=jnp.int32)
    if back == 'gather_flat':
      for j in range(k_):
        at = (j * rows_out + k * c,)
        nbrs = jax.lax.dynamic_update_slice(nbrs, got[:, j], at)
        eids = jax.lax.dynamic_update_slice(eids, got_eids[:, j], at)
      return (k + 1, nbrs, eids,
              jax.lax.dynamic_update_slice(lanes, count, (k * c,)))
    if not in_loop:
      at = (0, k * c) if across else (k * c, 0)
      if across:
        got, got_eids = got.T, got_eids.T
      return (k + 1, jax.lax.dynamic_update_slice(nbrs, got, at),
              jax.lax.dynamic_update_slice(eids, got_eids, at),
              jax.lax.dynamic_update_slice(lanes, count, (k * c,)))
    to = jnp.where(row_live, slot, s)
    lanes = lanes.at[to].set(count, mode='drop')
    if back == 'flat':
      to = jnp.where(row_live[:, None], slot[:, None] * k_ + lane[None],
                     s * k_).reshape(-1)
      got, got_eids = got.reshape(-1), got_eids.reshape(-1)
    return (k + 1, nbrs.at[to].set(got, mode='drop'),
            eids.at[to].set(got_eids, mode='drop'), lanes)

  _, nbrs, eids, lanes = jax.lax.while_loop(
      lambda carry: carry[0] < chunks, read,
      (jnp.int32(0), jnp.zeros(shape, indices.dtype),
       jnp.zeros(shape, eid_dtype), jnp.zeros((rows_out,), jnp.int32)))
  if not in_loop:
    # a dead slot takes a row of its own behind the live ones' chunks
    row = jnp.where(seed_mask, rank, pos[:s])
    lanes = jnp.where(seed_mask, jnp.take(lanes, row), 0)
    if back == 'gather':
      nbrs, eids = jnp.take(nbrs, row, axis=0), jnp.take(eids, row, axis=0)
    elif back == 'gather_t':
      nbrs, eids = (jnp.take(nbrs, row, axis=1).T,
                    jnp.take(eids, row, axis=1).T)
    elif back == 'gather_flat':
      at = row[:, None] + lane[None, :] * rows_out
      nbrs, eids = jnp.take(nbrs, at), jnp.take(eids, at)
    else:
      block = int(back[len('expand'):])
      nbrs, eids = (_expand_by_rank(a, rank, seed_mask, block)
                    for a in (nbrs, eids))
  mask = lane[None, :] < lanes.reshape(-1)[:s, None]
  return nbrs.reshape(s, k_), mask, eids.reshape(s, k_)


def bench_forms(args):
  import jax
  import jax.numpy as jnp
  from glt_tpu.ops import sample

  s, fanout, share, n, e, with_eids = CELLS[args.cell]
  if args.scale != 1.0:
    s, n, e = (max(64, int(v * args.scale)) for v in (s, n, e))
  share = args.live if args.live is not None else share
  rng = np.random.default_rng(args.seed)
  # the benchmark's degree law (chipbench/graphgen.py): Pareto, shape 4/3
  raw = np.minimum((1.0 - rng.random(n)) ** -0.75, 2000.0)
  deg = np.floor(raw * (e / raw.sum())).astype(np.int64)
  deg[:e - int(deg.sum())] += 1
  indptr = np.zeros(n + 1, np.int32)
  np.cumsum(deg, out=indptr[1:])
  indptr = jnp.asarray(indptr)
  key = jax.random.key(args.seed % (2 ** 31))
  indices = jax.random.randint(key, (e,), 0, n, jnp.int32)
  edge_ids = (jnp.arange(e, dtype=jnp.int32)[::-1] if with_eids else None)
  dead = np.iinfo(np.int32).max

  def frontier(live_share):
    m = rng.random(s) < live_share
    ids = np.floor(n * rng.random(s) ** 2).astype(np.int32)
    return jnp.asarray(np.where(m, ids, dead)), jnp.asarray(m)

  def ms(fn, *a):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*a))
    for _ in range(args.warmup):
      jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(args.iters):
      out = fn(*a)
    jax.block_until_ready(out)
    return out, round((time.perf_counter() - t0) / args.iters * 1e3, 3)

  def same(got, want):
    m = np.asarray(want[1])
    return bool((np.asarray(got[1]) == m).all() and all(
        (np.asarray(got[i])[m] == np.asarray(want[i])[m]).all()
        for i in ((0, 2) if with_eids else (0,))))

  # a cell without edge ids never reads ``eids``: it is not handed back,
  # and the compiler drops its planes as it does in the cell's step
  kept = (lambda o: o) if with_eids else (lambda o: (o[0], o[1]))

  def library(chunk):
    def f(ip, ix, ei, sd, m, k):
      out = sample.sample_neighbors(ip, ix, sd, fanout, k, seed_mask=m,
                                    edge_ids=ei)
      return (*kept((out.nbrs, out.mask, out.eids)), out.rows_read)
    def run(*a):
      old = sample.HOP_CHUNK, sample.HOP_LIVE_SHARE
      sample.HOP_CHUNK, sample.HOP_LIVE_SHARE = chunk, args.share_limit
      try:
        return ms(f, *a)
      finally:
        sample.HOP_CHUNK, sample.HOP_LIVE_SHARE = old
    return run

  seeds, mask = frontier(share)
  operands = (indptr, indices, edge_ids, seeds, mask, key)
  out = {}
  plain = lambda ip, ix, ei, sd, m, k: kept(_plain(
      sample, ip, ix, ei, sd, m, k, fanout))
  want, out['plain_ms'] = ms(plain, *operands)
  spread = jnp.where(mask, seeds, jnp.arange(s, dtype=jnp.int32) % n)
  got, t = ms(plain, indptr, indices, edge_ids, spread, mask, key)
  out['plain_spread_ms'] = t if same(got, want) else 'wrong'
  if not args.shares:
    for chunk in CHUNKS:
      got, t = library(chunk)(*operands)
      out[f'library.{chunk}_ms'] = t if same(got, want) else 'wrong'
      out[f'library.{chunk}_rows_read'] = int(got[-1])
    for chunk in args.form_chunks:
      for back in args.backs:
        for at in (False, True):
          if at and back not in args.draws_at_with:
            continue
          got, t = ms(
              lambda ip, ix, ei, sd, m, k: kept(_chunked(
                  sample, ip, ix, ei, sd, m, k, fanout, chunk, back, at)),
              *operands)
          out[f'{back}{".draws_at" if at else ""}.{chunk}_ms'] = (
              t if same(got, want) else 'wrong')
  else:
    for pct in (10, 20, 30, 40, 50, 60, 70, 80, 90, 100):
      sd, m = frontier(pct / 100)
      if pct == 100:
        m = jnp.ones_like(m)
      a = (indptr, indices, edge_ids, sd, m, key)
      w, out[f'plain@{pct}_ms'] = ms(plain, *a)
      got, t = library(args.chunk)(*a)
      out[f'library@{pct}_ms'] = t if same(got, w) else 'wrong'
      out[f'library@{pct}_rows_read'] = int(got[-1])

  dev = jax.devices()[0]
  print(json.dumps({
      'metric': 'hop_read_forms', 'value': out['plain_ms'], 'unit': 'ms',
      'detail': dict(out, cell=args.cell, slots=s, fanout=fanout,
                     live=int(np.asarray(mask).sum()), rows=n, edges=e,
                     edge_ids=with_eids, iters=args.iters,
                     backend=dev.platform, device_kind=dev.device_kind),
  }))


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--forms', action='store_true',
                  help='time the plain read, the library\'s and the '
                       'candidate forms at each chunk (one device)')
  ap.add_argument('--shares', action='store_true',
                  help='with --forms: the plain read and the library\'s '
                       'over live shares of 10 to 100 %% instead')
  ap.add_argument('--cell', choices=sorted(CELLS), default='c1')
  ap.add_argument('--live', type=float, default=None,
                  help='live share of the frontier (default: the cell\'s)')
  ap.add_argument('--chunk', type=int, default=1024,
                  help='--shares: HOP_CHUNK of the library\'s read')
  ap.add_argument('--share-limit', type=float, default=2.0,
                  help='HOP_LIVE_SHARE while the library\'s read is timed '
                       '(2.0: it never hands a hop to the plain read)')
  ap.add_argument('--backs', type=lambda v: v.split(','),
                  default=['scatter', 'flat', 'gather', 'gather_t',
                           'gather_flat', 'expand128', 'expand256'],
                  help='the candidate ways back to slot order')
  ap.add_argument('--draws-at-with', type=lambda v: v.split(','),
                  default=['gather_flat', 'expand128'],
                  help='the ways back also timed with the draws made in '
                       'place')
  ap.add_argument('--form-chunks', type=lambda v: [int(c) for c in
                                                   v.split(',')],
                  default=[1024, 4096],
                  help='the chunks the candidate forms are timed at')
  ap.add_argument('--scale', type=float, default=1.0,
                  help='shrink slots, rows and edges (rehearsal)')
  ap.add_argument('--seed', type=int, default=0)
  ap.add_argument('--iters', type=int, default=20)
  ap.add_argument('--warmup', type=int, default=2)
  ap.add_argument('--cpu', action='store_true',
                  default=os.environ.get('GLT_BENCH_PLATFORM') == 'cpu')
  args = ap.parse_args()
  if not args.forms:
    ap.error('--forms is the one mode')
  from glt_tpu.utils.backend import configure_compile_cache, force_backend
  if args.cpu:
    force_backend('cpu')
  configure_compile_cache()
  return bench_forms(args)


if __name__ == '__main__':
  main()
