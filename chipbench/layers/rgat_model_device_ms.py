"""Layer ``model_step``: device ms a step of the operations under the
typed step's ``model_step`` scope (R-GAT forward, backward and the
optimizer's update), from ``chipbench/hetero_scope_window.py``."""
from chipbench import hetero_scope_window


def read(run):
  return hetero_scope_window.layer_ms(run, 'model_step')
