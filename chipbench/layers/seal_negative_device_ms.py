"""Layer ``sampler``: device ms a step of the ``sampler/negative`` scope:
the link cells' strict negatives (``ops/negative.py``), here against
the undirected graph."""
from chipbench import seal_scope_window


def read(run):
  return seal_scope_window.stage_ms(run, 'sampler/negative')
