"""Layer ``sampler``: device ms a step of the operations under the link
step's ``sampler/negative`` scope: the proposals, the membership test
against the CSR (``ops/negative.py::edge_in_csr``) and the selection.
The rest of ``link_sampler_device_ms`` is the hop loop."""
from chipbench import link_scope_window


def read(run):
  return link_scope_window.stage_ms(run, 'sampler/negative')
