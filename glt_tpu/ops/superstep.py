"""Superstep: K full training steps inside one scanned dispatch.

The per-batch training loop pays one Python iteration, one host->device
seed transfer, and one jit dispatch per batch (loader/node_loader.py,
parallel/train.py). :func:`glt_tpu.ops.pipeline.multihop_sample_many`
already shows that scanning K *sampling* batches in one dispatch
amortizes that overhead; this module generalizes the same lax.scan
pattern to the WHOLE training step — sample -> feature gather ->
forward/backward -> optimizer update — with params and optimizer state
threaded through the carry. Seed batches are staged on device up front
as a [T, B] stack (loader.DeviceEpochLoader), so steady state is one
dispatch per T batches and zero host round-trips on the hot path.
PyTorch-Direct (arxiv 2101.07956) and GPU-initiated direct-storage
sampling (arxiv 2306.16384) teach the same lesson on GPUs.

The hop loop carries no state from one batch to the next
(:func:`~glt_tpu.ops.pipeline.multihop_sample`), which makes scan
iterations independent: a T-step superstep is bit-identical to T
sequential calls of the same body with the same key stream.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax

# body of one training step:
#   (params, opt_state, seeds, n_valid, key) -> (params, opt_state, aux)
BatchStepFn = Callable[..., Tuple]


def superstep(batch_step: BatchStepFn, unroll: int = 1):
  """Lift a per-batch training body into a multi-batch lax.scan.

  Args:
    batch_step: one full training step (sample -> gather -> grad ->
      update). ``aux`` is any pytree (typically the loss). Seeds may be
      one array or a per-type dict (the hetero step's), stacked per
      leaf.
    unroll: forwarded to ``lax.scan`` (TPU sampling A/Bs found modest
      unrolling neutral; the knob exists for re-measurement).

  Returns ``run(params, opt_state, seeds_stack [T, B], n_valid_stack
  [T, ...], keys [T, ...]) -> (params, opt_state, aux_stack)`` where
  ``aux_stack`` carries the per-batch aux values stacked on a leading
  [T] axis. The leading axis of the three stacked inputs must agree;
  each scan iteration consumes one slice.
  """

  def run(params, opt_state, seeds_stack, n_valid_stack, keys):
    def step(carry, x):
      params, opt_state = carry
      seeds, n_valid, key = x
      params, opt_state, aux = batch_step(params, opt_state, seeds,
                                          n_valid, key)
      return (params, opt_state), aux

    (params, opt_state), aux = jax.lax.scan(
        step, (params, opt_state), (seeds_stack, n_valid_stack, keys),
        unroll=unroll)
    return params, opt_state, aux

  return run


def scan_consume(consume_step: Callable, unroll: int = 1):
  """Scan a pre-staged consume body: ``consume_step(carry, x) ->
  (carry, aux)`` over stacked inputs whose sampling already ran (the
  cold-row streaming pipeline stages sampler outputs and cold feature
  rows for superstep N+1 while the chip executes superstep N; the
  consume scan's carry is params/opt alone)."""

  def run(carry, xs):
    return jax.lax.scan(consume_step, carry, xs, unroll=unroll)

  return run
