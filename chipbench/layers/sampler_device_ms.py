"""Layer ``sampler``: device ms a step of the operations under the
program's ``sampler`` scope, on the busiest chip, from the scope window
(``chipbench/scope_window.py``). Sampling, dedup and relabel of every hop."""
from chipbench import scope_window


def read(run):
  return scope_window.layer_ms(run, 'sampler')
