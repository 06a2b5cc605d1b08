"""Layer ``feature_store``: device ms a step of the store's gather of the
live node slots' rows (``feature_store/serve``: 512 short prefixes of
256 slots each, served in chunks)."""
from chipbench import seal_scope_window


def read(run):
  return seal_scope_window.layer_ms(run, 'feature_store')
