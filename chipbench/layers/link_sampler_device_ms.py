"""Layer ``sampler``: device ms a step of the operations under the link
step's ``sampler`` scope, from ``chipbench/link_scope_window.py``: the
negative sampler, then sampling, dedup and relabel of every hop from the
pairs' endpoints."""
from chipbench import link_scope_window


def read(run):
  return link_scope_window.layer_ms(run, 'sampler')
