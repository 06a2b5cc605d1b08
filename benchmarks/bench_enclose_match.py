"""The induction of a batch of enclosing subgraphs: the dense form over
every budgeted tile against forms that match the live tiles only, on one
device.

``ops/subgraph.py::enclosing_subgraphs`` gives each of ``L`` links a
budget of ``tile_budget`` tiles of ``TILE`` entries of ``indices``; until
PR 42 it gathered and matched all ``L x tile_budget`` of them against the
link's ``S`` node slots, though a link's live tiles are a prefix of its
budget (26 % of it on ``seal-papers100m-c1.fused``). ``--forms`` times, at
the cell's shapes (``L`` 512, ``tile_budget`` 512, ``S`` 256, 8 M rows,
232.8 M entries), from the node sets to ``seen`` (the ``[L, S, S]`` counts
that ``adj`` is made of; the pairs' probes left out):

  * ``dense``: the form the parent of PR 42 had;
  * ``library``: ``ops/subgraph.py`` as the tree has it;
  * candidate loops over a batch-wide list of the live tiles, a link's
    tiles rounded up to whole blocks of ``block`` tiles (1: a flat list of
    tiles), ``chunk`` tiles a trip, by the way a chunk's matches reach
    ``seen``: ``add_rows`` (a row scatter-add of ``[C, S]`` into ``[L x S,
    S]`` inside the loop), ``plane_rows`` / ``plane_window`` (written as a
    contiguous ``[T, S]`` plane inside the loop, gathered back to ``[L,
    TL, S]`` after it, row by row or a link's window at once, then the
    dense form's product on the matrix unit), ``plane_put`` (a chunk's
    blocks scattered to their places in ``[L, TL, S]`` inside the loop),
    ``block_mxu`` (a block's ``owner^T x match`` is an ``[S, S]`` product,
    scatter-added to ``seen[link]`` by block); and by where a list slot
    learns its tile and its owner's row (``meta``: inside the loop from
    its link's ``lo`` and ``hi``, or before it for every budgeted slot, as
    the dense form does);
  * both layouts of the compare: ``entries`` (a tile's 128 entries in the
    lanes, reduced: the dense form's) and ``members`` (the link's ``S``
    node slots in the lanes, the entries OR-ed over a major axis).

The first phase runs them on the cell's own mix (links drawn over edges of
a graph with the benchmark's degree law, one hop of at most 127 taken of
each end); the second takes the fastest form over the chunk sizes and,
beside ``dense``, over live shares of 10 to 100 % of the budget (and the
cell's 26 %). Every
form is held to ``dense``'s ``seen`` on the same inputs, entry by entry,
before its time is kept. One JSON line; a time is the host clock around
``--iters`` dispatches that end in ``block_until_ready`` and means
something on a chip only (``--cpu --scale 0.02`` rehearses).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

TILE = 128
#: links, tile budget, node slots, hub width, rows, entries of ``indices``
CELL = (512, 512, 256, 2048, 8_000_000, 232_800_000)
FANOUT = 127

#: (way, block, chunk, lanes, meta) of the first phase: the rows ISSUE 42
#: asks for, then the forms the builder added
FORMS = [f + ('chunk',) for f in (
    [('add_rows', 1, c, 'entries') for c in (1024, 2048, 4096)]
    + [('plane_rows', 1, c, 'entries') for c in (1024, 2048, 4096)]
    + [('block_mxu', 32, 2048, 'entries'), ('block_mxu', 64, 2048, 'entries'),
       ('block_mxu', 32, 4096, 'entries')]
    + [(w, b, 2048, 'members') for w, b in (
        ('add_rows', 1), ('plane_rows', 1), ('block_mxu', 32))]
    + [('plane_window', 8, 2048, 'entries'), ('plane_window', 8, 2048, 'members'),
       ('plane_window', 32, 2048, 'members'), ('plane_window', 1, 2048, 'members'),
       ('plane_put', 8, 2048, 'members'), ('plane_put', 32, 2048, 'members'),
       ('add_rows', 8, 2048, 'members')])] + [
    ('plane_window', 8, 2048, 'members', 'dense'),
    ('plane_window', 1, 2048, 'members', 'dense'),
    ('plane_put', 8, 2048, 'members', 'dense'),
    ('plane_window', 8, 2048, 'entries', 'dense'),
    ('plane_put', 16, 1024, 'members', 'dense'),
    ('plane_put', 32, 1024, 'members', 'dense'),
    ('plane_put', 64, 1024, 'members', 'dense')]


def budget(indptr, nodes, live, tl, hub_width):
  """``enclosing_subgraphs``' budget arithmetic: a member's row, the
  tiles it spans and its place among its link's tiles."""
  import jax.numpy as jnp
  at = jnp.maximum(nodes, 0)
  start = jnp.take(indptr, at, mode='clip').astype(jnp.int32)
  deg = jnp.where(
      live, jnp.take(indptr, at + 1, mode='clip').astype(jnp.int32) - start,
      0)
  span = jnp.where((deg > 0) & (deg <= hub_width),
                   (start % TILE + deg + TILE - 1) // TILE, 0)
  hi = jnp.cumsum(span, axis=1)
  read = (span > 0) & (hi <= tl)
  hi = jnp.where(read, hi, 0)
  return start, deg, jnp.where(read, hi - span, 0), hi


def dense_seen(indices, start, deg, lo, hi, member, tl):
  """The parent's: every budgeted tile gathered, masked and matched."""
  import jax.numpy as jnp
  num_links = start.shape[0]
  t = jnp.arange(tl, dtype=jnp.int32)[None, :, None]
  owner = (lo[:, None, :] <= t) & (t < hi[:, None, :])     # [L, TL, S]
  of_owner = lambda a: jnp.sum(jnp.where(owner, a[:, None, :], 0), axis=-1)
  tile = of_owner(start // TILE - lo) + t[:, :, 0]
  row_lo, row_hi = of_owner(start), of_owner(start + deg)
  tiles = jnp.take(
      indices.reshape(-1, TILE),
      jnp.clip(tile, 0, indices.shape[0] // TILE - 1).reshape(-1),
      axis=0).reshape(num_links, tl, TILE)
  pos = tile[..., None] * TILE + jnp.arange(TILE, dtype=jnp.int32)
  vals = jnp.where((pos >= row_lo[..., None]) & (pos < row_hi[..., None]),
                   tiles, -1)
  match = (vals[:, :, None, :] == member[:, None, :, None]).any(-1)
  return jnp.einsum('lti,ltj->lij', owner.astype(jnp.bfloat16),
                    match.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)


def live_seen(indices, start, deg, lo, hi, member, tl, *, way, block, chunk,
              lanes, meta='chunk'):
  """``(seen, tiles matched)`` by one loop over the batch's live tiles
  (the module's text says what ``way``, ``block``, ``chunk`` and ``lanes``
  choose). ``meta``: where a list slot learns its tile and its owner's
  row: ``chunk``, inside the loop from its link's rows of ``lo`` and
  ``hi`` (``[C, S]`` compares a chunk); ``dense``, before the loop for
  every budgeted slot, as the dense form does (``[L, TL, S]`` masked
  sums), the loop gathering a block's slots of that."""
  import jax
  import jax.numpy as jnp
  num_links, s = start.shape
  b, c = block, chunk
  g, tlp = c // b, -(-tl // b) * b
  nt = indices.shape[0] // TILE
  rows = indices.reshape(nt, TILE)
  nb = (hi.max(axis=1) + (b - 1)) // b         # a link's live blocks
  bend = jnp.cumsum(nb)
  chunks = (bend[-1] + (g - 1)) // g
  cap = -(-num_links * tlp // c) * c           # the most the list can hold
  stride = max(nt // cap, 1)
  inner = jnp.arange(b, dtype=jnp.int32)
  lane = jnp.arange(TILE, dtype=jnp.int32)
  slot = jnp.arange(s, dtype=jnp.int32)
  # a block of the list finds its link by counting the links that end
  # before it, and its place in the link by their blocks
  q = jnp.arange(cap // b, dtype=jnp.int32)
  # (a block past the list's end falls behind the last link's blocks)
  before = bend[None, :-1] <= q[:, None]                     # [cap / B, L - 1]
  link_of = before.sum(-1).astype(jnp.int32)
  first_of = q - jnp.sum(jnp.where(before, nb[None, :-1], 0), axis=-1)
  if meta == 'dense':
    t = jnp.arange(tlp, dtype=jnp.int32)[None, :, None]
    owner = (lo[:, None, :] <= t) & (t < hi[:, None, :])    # [L, TL, S]
    of_owner = lambda a: jnp.sum(jnp.where(owner, a[:, None, :], 0), axis=-1)
    # tile, row_lo, row_hi of every budgeted slot, a block a row; one
    # more row of zeros for the list's blocks past its end
    table = jnp.concatenate(
        [a.reshape(-1, b) for a in (of_owner(start // TILE - lo) + t[:, :, 0],
                                    of_owner(start), of_owner(start + deg))],
        axis=1)                                             # [L TL / B, 3 B]
    table = jnp.concatenate([table, jnp.zeros((1, 3 * b), jnp.int32)])
    row_of = jnp.where(q < bend[-1], link_of * (tlp // b) + first_of,
                       num_links * (tlp // b))
  else:
    assert meta == 'chunk', meta
    # what a block reads of its link, one row gather a chunk
    table = jnp.stack([lo, hi, start, start + deg, start // TILE - lo],
                      axis=1)                               # [L, 5, S]

  def matched(k):
    link = jax.lax.dynamic_slice(link_of, (k * g,), (g,))
    first = jax.lax.dynamic_slice(first_of, (k * g,), (g,))
    mem = jnp.take(member, link, axis=0, mode='clip')       # [G, S]
    if meta == 'dense':
      own = None
      got = jnp.take(table, jax.lax.dynamic_slice(row_of, (k * g,), (g,)),
                     axis=0, mode='clip')                   # [G, 3 B]
      tile, row_lo, row_hi = got[:, :b], got[:, b:2 * b], got[:, 2 * b:]
    else:
      lo_r, hi_r, start_r, end_r, base_r = jnp.moveaxis(
          jnp.take(table, link, axis=0, mode='clip'), 1, 0)  # [G, S] each
      t = (first * b)[:, None] + inner[None, :]             # [G, B]
      own = ((lo_r[:, None, :] <= t[..., None])
             & (t[..., None] < hi_r[:, None, :]))           # [G, B, S]
      of_owner = lambda a: jnp.sum(jnp.where(own, a[:, None, :], 0), axis=-1)
      row_lo, row_hi = of_owner(start_r), of_owner(end_r)
      tile = of_owner(base_r) + t
    # a slot with no owner reads a tile of its own and matches nothing
    spare = ((k * c + jnp.arange(c, dtype=jnp.int32)) * stride
             % nt).reshape(g, b)
    tile = jnp.where(row_hi > row_lo, tile, spare)
    vals = jnp.take(rows, tile.reshape(-1), axis=0,
                    mode='clip').reshape(g, b, TILE)
    pos = tile[..., None] * TILE + lane
    vals = jnp.where((pos >= row_lo[..., None]) & (pos < row_hi[..., None]),
                     vals, -1)
    if lanes == 'entries':
      match = (vals[:, :, None, :] == mem[:, None, :, None]).any(-1)
    else:
      match = (vals[:, :, :, None] == mem[:, None, None, :]).any(2)
    return link, first, own, match                          # [G, B, S]

  def owners_product(plane):
    t = jnp.arange(tlp, dtype=jnp.int32)[None, :, None]
    owner = (lo[:, None, :] <= t) & (t < hi[:, None, :])
    return jnp.einsum('lti,ltj->lij', owner.astype(jnp.bfloat16), plane,
                      preferred_element_type=jnp.float32)

  if way == 'add_rows':
    init = jnp.zeros((num_links * s, s), jnp.float32)
    def add(k, seen):
      link, _, own, match = matched(k)
      row = jnp.where(own.any(-1), link[:, None] * s
                      + jnp.sum(jnp.where(own, slot, 0), axis=-1),
                      num_links * s)
      return seen.at[row.reshape(-1)].add(
          match.reshape(c, s).astype(jnp.float32), mode='drop')
    done = lambda seen: seen.reshape(num_links, s, s)
  elif way == 'block_mxu':
    init = jnp.zeros((num_links, s, s), jnp.float32)
    def add(k, seen):
      link, _, own, match = matched(k)
      return seen.at[link].add(jnp.einsum(
          'gbi,gbj->gij', own.astype(jnp.bfloat16),
          match.astype(jnp.bfloat16), preferred_element_type=jnp.float32))
    done = lambda seen: seen
  elif way == 'plane_put':
    init = jnp.zeros((num_links, tlp // b, b, s), jnp.bfloat16)
    def add(k, plane):
      link, first, _, match = matched(k)
      return plane.at[link, first].set(
          match.astype(jnp.bfloat16), mode='drop', unique_indices=True)
    done = lambda plane: owners_product(plane.reshape(num_links, tlp, s))
  else:
    init = jnp.zeros((cap, s), jnp.bfloat16)
    bbase = bend - nb
    def add(k, plane):
      match = matched(k)[3].reshape(c, s).astype(jnp.bfloat16)
      return jax.lax.dynamic_update_slice(plane, match, (k * c, 0))
    if way == 'plane_rows':
      def done(plane):
        at = bbase[:, None] * b + jnp.arange(tlp, dtype=jnp.int32)
        return owners_product(jnp.take(plane, at.reshape(-1), axis=0)
                              .reshape(num_links, tlp, s))
    else:
      assert way == 'plane_window', way
      def done(plane):
        return owners_product(jax.vmap(
            lambda at: jax.lax.dynamic_slice(plane, (at, 0), (tlp, s)))(
                bbase * b))

  _, acc = jax.lax.while_loop(
      lambda carry: carry[0] < chunks,
      lambda carry: (carry[0] + 1, add(*carry)), (jnp.int32(0), init))
  return done(acc), chunks * c


def make_graph(rng, n, e):
  """``indptr`` by the benchmark's degree law (``chipbench/graphgen.py``:
  Pareto, shape 4/3) with rows up to tens of thousands wide, ``indices``
  uniform: the times do not hang on the entries' values."""
  raw = np.minimum((1.0 - rng.random(n)) ** -0.75, 5000.0)
  deg = np.floor(raw * (e / raw.sum())).astype(np.int64)
  deg[:e - int(deg.sum())] += 1
  indptr = np.zeros(n + 1, np.int64)
  np.cumsum(deg, out=indptr[1:])
  indices = rng.integers(0, n, e, dtype=np.int32)
  return indptr.astype(np.int32), indices


def cell_links(rng, indptr, indices, num_links, s):
  """Node sets as the cell's step makes them: half the links over edges
  (an end drawn by degree), half over uniform pairs; each end's row, or
  ``FANOUT`` of it; deduped in order."""
  n, e = indptr.shape[0] - 1, indices.shape[0]
  nodes = np.full((num_links, s), -1, np.int32)
  for l in range(num_links):
    if l < num_links // 2:
      eid = int(rng.integers(e))
      ends = [int(np.searchsorted(indptr, eid, side='right')) - 1,
              int(indices[eid])]
    else:
      ends = [int(v) for v in rng.integers(0, n, 2)]
    got = list(ends)
    for u in ends:
      row = indices[indptr[u]:indptr[u + 1]]
      got.extend(row if row.size <= FANOUT
                 else rng.choice(row, FANOUT, replace=False))
    fringe = [v for v in dict.fromkeys(int(v) for v in got[2:])
              if v not in ends]
    got = (ends + fringe)[:s]
    nodes[l, :len(got)] = got
  return nodes


def filled_links(rng, indptr, indices, num_links, s, tl, hub_width, share):
  """Node sets whose read members span ``share`` of the tile budget,
  every link alike: members picked by the tiles they span, each followed
  by the first entry of its row (so that tiles hold members)."""
  start, deg = indptr[:-1].astype(np.int64), np.diff(indptr)
  span = np.where((deg > 0) & (deg <= hub_width),
                  (start % TILE + deg + TILE - 1) // TILE, 0)
  top = int(span.max())
  by_span = [np.flatnonzero(span == k) for k in range(top + 1)]
  want = int(round(share * tl))
  nodes = np.full((num_links, s), -1, np.int32)
  for l in range(num_links):
    got, left = [], want
    while left > 0 and len(got) < s:
      need = -(-left // (s - len(got)))
      k = int(np.clip(round(need * rng.uniform(0.6, 1.6)), need,
                      min(left, top)))
      while by_span[k].size == 0:
        k -= 1
      u = int(rng.choice(by_span[k]))
      got.append(u)
      left -= k
      v = int(indices[indptr[u]])
      if len(got) < s and 0 < span[v] <= left and v not in got:
        got.append(v)
        left -= int(span[v])
    nodes[l, :len(got)] = got
  return nodes


def bench_forms(args):
  import jax
  import jax.numpy as jnp
  from glt_tpu.ops import subgraph
  num_links, tl, s, hub_width, n, e = CELL
  if args.scale != 1.0:
    num_links = max(8, int(num_links * args.scale * 4))
    tl = max(16, int(tl * args.scale * 4) // 8 * 8)
    n, e = int(n * args.scale), int(e * args.scale) // TILE * TILE
  rng = np.random.default_rng(args.seed)
  indptr_h, indices_h = make_graph(rng, n, e)
  indptr, indices = jnp.asarray(indptr_h), jnp.asarray(indices_h)

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

  def operands(nodes_h):
    nodes = jnp.asarray(nodes_h)
    return indptr, indices, nodes, nodes >= 0

  def form(fn):
    def run(ip, ix, nodes, live):
      start, deg, lo, hi = budget(ip, nodes, live, tl, hub_width)
      return fn(ix, start, deg, lo, hi, jnp.where(live, nodes, -2), tl), \
          hi.max(axis=1).sum()
    return run

  dense = form(lambda *a: (dense_seen(*a), jnp.int32(num_links * tl)))
  same = jax.jit(lambda a, b: (a == b).all())
  scaled = lambda c: c if args.scale == 1.0 else max(
      64, int(c * args.scale * 4) // 64 * 64)

  def candidate(way, block, chunk, lanes, meta):
    chunk = scaled(chunk)
    block = min(block, chunk)
    return form(lambda *a: live_seen(*a, way=way, block=block, chunk=chunk,
                                     lanes=lanes, meta=meta))

  def library(*a):
    return subgraph.live_tiles_seen(*a)

  def timed(name, fn, ops, want, out):
    try:
      ((seen, matched), read), t = ms(fn, *ops)
    except Exception as err:            # a form the compiler refuses
      out[name] = f'failed: {type(err).__name__}: {str(err)[:200]}'
      return None
    ok = bool(same(seen, want))
    out[name] = t if ok else 'wrong'
    out[name + '.tiles'] = [int(matched), int(read)]
    return t if ok else None

  out = {}
  cell = operands(cell_links(rng, indptr_h, indices_h, num_links, s))
  ((want, _), read), out['dense'] = ms(dense, *cell)
  out['cell_live_share'] = float(read) / (num_links * tl)
  out['seen_entries'] = int((want > 0).sum())
  names = {}
  if hasattr(subgraph, 'live_tiles_seen'):
    timed('library', form(library), cell, want, out)
  for spec in FORMS:
    name = '{}.b{}.c{}.{}.{}'.format(*spec)
    if args.only and not any(part in name for part in args.only):
      continue
    names[name] = spec
    t = timed(name, candidate(*spec), cell, want, out)
    print(name, out[name], file=sys.stderr, flush=True)
  best = min((n_ for n_ in names if isinstance(out[n_], float)),
             key=lambda n_: out[n_])
  out['best'] = best
  way, block, _, lanes, meta = names[best]
  for chunk in (512, 1024, 2048, 4096, 8192, 16384):
    name = '{}.b{}.c{}.{}.{}'.format(way, block, chunk, lanes, meta)
    if name not in out:
      timed(name, candidate(way, block, chunk, lanes, meta), cell, want, out)
      names[name] = (way, block, chunk, lanes, meta)
  best = min((n_ for n_ in names if isinstance(out[n_], float)),
             key=lambda n_: out[n_])
  out['best_chunk'] = best
  fn = candidate(*names[best])
  for pct in (10, 20, 26, 30, 40, 50, 60, 70, 80, 90, 100):
    ops = operands(filled_links(rng, indptr_h, indices_h, num_links, s, tl,
                                hub_width, pct / 100))
    ((want, _), read), out[f'dense@{pct}'] = ms(dense, *ops)
    out[f'share@{pct}'] = float(read) / (num_links * tl)
    timed(f'best@{pct}', fn, ops, want, out)
    if hasattr(subgraph, 'live_tiles_seen'):
      timed(f'library@{pct}', form(library), ops, want, out)

  dev = jax.devices()[0]
  line = json.dumps({
      'metric': 'enclose_match_forms', 'value': out['dense'], 'unit': 'ms',
      'detail': dict(out, links=num_links, tile_budget=tl, node_slots=s,
                     rows=n, entries=e, iters=args.iters,
                     backend=dev.platform, device_kind=dev.device_kind)})
  print(line)
  if args.out:
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, 'w') as f:
      f.write(line + '\n')


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--forms', action='store_true',
                  help='time the dense form, the library\'s and the '
                       'candidate loops (one device)')
  ap.add_argument('--only', type=lambda v: v.split(','), default=None,
                  help='the forms of the first phase to run, by parts of '
                       'their names (default: all)')
  ap.add_argument('--scale', type=float, default=1.0,
                  help='shrink links, budget, rows and entries (rehearsal)')
  ap.add_argument('--seed', type=int, default=0)
  ap.add_argument('--iters', type=int, default=20)
  ap.add_argument('--warmup', type=int, default=2)
  ap.add_argument('--out', default=None,
                  help='also write the JSON line to this file')
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
