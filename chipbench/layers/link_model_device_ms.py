"""Layer ``model_step``: device ms a step of the operations under the
link step's ``model_step`` scope, from ``chipbench/link_scope_window.py``:
GraphSAGE forward and backward, the link loss, the update."""
from chipbench import link_scope_window


def read(run):
  return link_scope_window.layer_ms(run, 'model_step')
