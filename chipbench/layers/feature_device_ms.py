"""Layer ``feature_store``: device ms a step of the operations under the
program's ``feature_store`` scope, on the busiest chip, from the scope window
(``chipbench/scope_window.py``). Bucketing, the row gather and the stitch;
the exchange's collectives are ``collective_ms``'s."""
from chipbench import scope_window


def read(run):
  return scope_window.layer_ms(run, 'feature_store')
