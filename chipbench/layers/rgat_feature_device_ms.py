"""Layer ``feature_store``: device ms a step of the operations under the
typed step's ``feature_store`` scope (every type's bucket, row gather and
stitch), from ``chipbench/hetero_scope_window.py``."""
from chipbench import hetero_scope_window


def read(run):
  return hetero_scope_window.layer_ms(run, 'feature_store')
