"""Layer ``model_step``: device ms a step of the operations under the
program's ``model_step`` scope, on the busiest chip, from the scope window
(``chipbench/scope_window.py``). Forward, backward and the optimizer's update."""
from chipbench import scope_window


def read(run):
  return scope_window.layer_ms(run, 'model_step')
