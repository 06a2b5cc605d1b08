"""Layer ``device``: the share of the SEAL step's busy time that the
scope window could not give to one layer: operations with no layer scope,
and fusions whose instructions span layers. How far the
``seal_*_device_ms`` can be trusted."""
from chipbench import seal_scope_window


def read(run):
  found = seal_scope_window.profile(run)
  if found is None:
    return None
  return 100.0 * (found['unscoped_ms'] + found['mixed_ms']) / found['busy_ms']
