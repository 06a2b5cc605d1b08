"""StreamSampler — delta-aware multi-hop sampling over versioned
snapshots.

Same contract as :class:`~glt_tpu.sampler.neighbor_sampler.
NeighborSampler` (homogeneous node sampling), with two structural
differences that make live updates compile-stable:

  1. The graph arrays are **jit arguments**, not closure constants: the
     compiled multihop program is keyed only on the seed batch shape,
     so a snapshot swap (same padded capacities) or a delta-overlay
     refresh re-runs the SAME executable — zero steady-state
     recompiles, asserted by tests via :attr:`num_compiled_fns` /
     :attr:`trace_count`.
  2. Every hop is a :func:`~glt_tpu.ops.delta.delta_one_hop`: base
     sample + tombstone mask + a fixed-capacity per-node insert window,
     so the effective hop width is ``abs(fanout) + delta_window``
     (static). Capacity math (frontier budgets, edge hop offsets) uses
     the effective widths throughout.

Reads follow the manager's RCU protocol: each ``sample_from_nodes``
acquires the current snapshot, samples against its arrays, and releases
it — compaction never yanks device buffers from under an in-flight
sample.

Not supported (assert-guarded): hetero graphs, weighted sampling, and
``with_edge`` (delta edges have no stable compressed slot until
compaction folds them into the CSR).
"""
from __future__ import annotations

import logging
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.delta import delta_one_hop
from ..ops.pipeline import edge_hop_offsets, multihop_sample
from ..sampler.base import BaseSampler, NodeSamplerInput, SamplerOutput
from ..utils import as_numpy
from ..utils.rng import RandomSeedManager, make_key
from .snapshot import SnapshotManager

logger = logging.getLogger(__name__)


class StreamSampler(BaseSampler):
  """Multi-hop sampling over a :class:`SnapshotManager`.

  Args:
    manager: snapshot chain + overlay builder.
    num_neighbors: [K_1..K_h]; -1 = full neighborhood inside
      ``full_neighbor_cap`` (resolved ONCE at construction — the window
      is a compile-shape constant, so size it for the max degree the
      stream is expected to reach, not just the startup graph's).
    delta_window: per-node insert-overlay window per hop (static). A
      frontier node with more pending inserts than this truncates until
      compaction.
    tombstone_window: per-node delete-overlay window (defaults to
      ``delta_window``).
    edge_dir: must match the manager's base layout ('out' = CSR).
    seed: RNG seed (defaults to the process RandomSeedManager).
  """

  def __init__(self, manager: SnapshotManager,
               num_neighbors: Sequence[int],
               *, delta_window: int = 8,
               tombstone_window: Optional[int] = None,
               replace: bool = False,
               edge_dir: Optional[str] = None,
               full_neighbor_cap: Optional[int] = None,
               seed: Optional[int] = None):
    self.manager = manager
    self.is_hetero = False
    self.with_edge = False
    self.replace = replace
    self.delta_window = int(delta_window)
    self.tombstone_window = int(
        delta_window if tombstone_window is None else tombstone_window)
    assert self.delta_window >= 0 and self.tombstone_window >= 0
    layout_dir = 'out' if manager.layout == 'CSR' else 'in'
    if edge_dir is None:
      edge_dir = layout_dir
    assert edge_dir == layout_dir, (
        f'edge_dir {edge_dir!r} needs a '
        f'{"CSR" if edge_dir == "out" else "CSC"} base, manager holds '
        f'{manager.layout}')
    self.edge_dir = edge_dir

    base = manager.current().topo
    self._base_fanouts = []
    for f in num_neighbors:
      f = int(f)
      if f == -1:
        # default headroom: one delta epoch's worth of per-node inserts
        # lands in the base at compaction, so the startup max degree
        # alone would truncate right after the first insert-heavy swap
        cap = int(full_neighbor_cap
                  or base.max_degree + self.delta_window)
        assert cap > 0, 'graph has no edges; fanout=-1 is meaningless'
        self._base_fanouts.append(-cap)
      else:
        assert f > 0, f'fanout must be positive or -1, got {f}'
        self._base_fanouts.append(f)
    self._full_cap = min((abs(f) for f in self._base_fanouts if f < 0),
                         default=None)
    self._trunc_warned_version = -1
    # effective pipeline widths: every hop appends the insert window.
    # negative encoding: the pipeline treats these as fixed windows
    # (capacity math via abs), never as uniform-sample fanouts.
    self.num_neighbors = [-(abs(f) + self.delta_window)
                          for f in self._base_fanouts]
    self.num_hops = len(self._base_fanouts)

    self._base_key = make_key(
        seed if seed is not None
        else RandomSeedManager.getInstance().getSeed())
    self._step = 0
    self._fn_cache = {}
    #: times any multihop program was traced (trace-time side effect;
    #: flat in steady state even across snapshot swaps)
    self.trace_count = 0
    self._overlay = manager.empty_overlay()

  # -- compile discipline ------------------------------------------------

  @property
  def num_compiled_fns(self) -> int:
    """Compiled multihop programs, one per seed-shape signature (the
    serving engine's zero-recompile assertions read this, exactly as
    with NeighborSampler)."""
    return sum(1 for k in self._fn_cache if k[0] == 'homo')

  # -- live-update hooks -------------------------------------------------

  def set_overlay(self, overlay: dict) -> None:
    """Install freshly built delta overlays (manager.build_overlay).
    Takes effect on the next sample call; in-flight calls finish on the
    arrays they captured."""
    self._overlay = overlay

  def refresh_overlay(self, buffer) -> None:
    self.set_overlay(self.manager.build_overlay(buffer))

  def clear_overlay(self) -> None:
    self.set_overlay(self.manager.empty_overlay())

  # -- sampling ----------------------------------------------------------

  def _next_key(self) -> jax.Array:
    self._step += 1
    return jax.random.fold_in(self._base_key, self._step)

  def _build_fn(self, batch_size: int):
    eff = list(self.num_neighbors)
    base = list(self._base_fanouts)

    def fn(arrays, seeds, n_valid, key):
      self.trace_count += 1  # trace-time only; executions never bump
      from ..obs.perf import count_compile
      count_compile('stream.sample')  # compiles_total{fn=...}
      hop = {'i': 0}

      def one_hop(ids, _eff_fanout, sub, mask):
        f = base[hop['i']]
        hop['i'] += 1
        return delta_one_hop(
            arrays['indptr'], arrays['indices'],
            arrays['ins_indptr'], arrays['ins_indices'],
            arrays['del_indptr'], arrays['del_indices'],
            ids, f, sub, mask,
            ins_window=self.delta_window,
            del_window=self.tombstone_window,
            replace=self.replace)

      return multihop_sample(one_hop, seeds, n_valid, eff, key,
                             with_edge=False)

    return jax.jit(fn)

  def sample_from_nodes(self, inputs, **kwargs) -> SamplerOutput:
    """Delta-merged multi-hop sampling from seed nodes; same output
    contract as NeighborSampler.sample_from_nodes (homogeneous)."""
    if isinstance(inputs, NodeSamplerInput):
      seeds = as_numpy(inputs.node)
    else:
      seeds = as_numpy(inputs)
    n_valid = kwargs.get('n_valid', seeds.shape[0])
    batch_size = seeds.shape[0]
    snap = self.manager.acquire()
    try:
      cache_key = ('homo', batch_size)
      if cache_key not in self._fn_cache:
        self._fn_cache[cache_key] = self._build_fn(batch_size)
      if (self._full_cap is not None
          and snap.max_degree > self._full_cap
          and snap.version != self._trunc_warned_version):
        self._trunc_warned_version = snap.version
        logger.warning(
            'snapshot v%d max degree %d exceeds the static full-'
            'neighborhood window %d: hub rows truncate. Rebuild the '
            'sampler with a larger full_neighbor_cap.',
            snap.version, snap.max_degree, self._full_cap)
      arrays = dict(snap.arrays)
      arrays.update(self._overlay)
      out = self._fn_cache[cache_key](
          arrays, jnp.asarray(seeds.astype(np.int32)),
          jnp.asarray(n_valid), kwargs.get('key', self._next_key()))
    finally:
      self.manager.release(snap)
    return SamplerOutput(
        node=out['node'], node_count=out['node_count'],
        row=out['row'], col=out['col'], edge_mask=out['edge_mask'],
        edge=None, batch=out['batch'],
        num_sampled_nodes=out['num_sampled_nodes'],
        num_sampled_edges=out['num_sampled_edges'],
        edge_hop_offsets=edge_hop_offsets(batch_size,
                                          self.num_neighbors),
        metadata={'seed_labels': out['seed_labels'],
                  'seed_count': out['seed_count'],
                  'snapshot_version': snap.version},
    )

  def sample_from_edges(self, inputs, **kwargs):
    raise NotImplementedError(
        'StreamSampler serves node-anchored inference; link sampling '
        'stays on NeighborSampler (train-time, frozen snapshots)')
