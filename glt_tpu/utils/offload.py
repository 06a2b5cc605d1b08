"""Shared host-offload placement policy.

One decision, three stores (data.Feature, parallel.ShardedFeature,
distributed.DistFeature): spilled cold rows default to a PINNED-HOST
jax array served in-program (the UVA analog, reference
unified_tensor.cu:202-231), opt out with GLT_HOST_OFFLOAD=0 or
host_offload=False, and an EXPLICIT host_offload=True must surface
placement failures instead of silently degrading to the host phase.
"""
from __future__ import annotations

from typing import Optional

from .env import knob


def offload_requested(host_offload: Optional[bool],
                      spilled: bool) -> bool:
  """Resolve the tri-state flag: None = auto (on when spilled unless
  GLT_HOST_OFFLOAD=0)."""
  if host_offload is None:
    return spilled and knob('GLT_HOST_OFFLOAD', True)
  return bool(host_offload)


def pinned_host_supported(device=None) -> bool:
  """Capability probe: can this backend place arrays in pinned host
  memory at all? Distinguishes 'the platform cannot offload' (fall back
  / skip) from 'offload regressed on a platform that can' (fail loudly)
  — graft dryruns and platform-conditional tests key off it."""
  import jax
  dev = device or jax.devices()[0]
  return any(m.kind == 'pinned_host' for m in dev.addressable_memories())


def maybe_pin_host(build_fn, host_offload: Optional[bool]):
  """Run ``build_fn()`` (which must place an array in pinned host
  memory) tolerating platforms without memory kinds: auto mode returns
  None on failure (caller keeps its host-phase path), an explicit
  ``host_offload=True`` re-raises."""
  try:
    return build_fn()
  except Exception:
    if host_offload:  # explicitly asked for: do not mask the failure
      raise
    return None
