"""Layer ``device``: the share of the user-item step's busy time that the
scope window could not give to one layer: operations with no layer scope,
and fusions whose instructions span layers. How far the
``bisage_*_device_ms`` can be trusted."""
from chipbench import bisage_scope_window


def read(run):
  found = bisage_scope_window.profile(run)
  if found is None:
    return None
  return 100.0 * (found['unscoped_ms'] + found['mixed_ms']) / found['busy_ms']
