"""Layer ``feature_store``: device ms a step of the operations under the
link step's ``feature_store`` scope, from
``chipbench/link_scope_window.py``: the gather of every sampled node's
row."""
from chipbench import link_scope_window


def read(run):
  return link_scope_window.layer_ms(run, 'feature_store')
