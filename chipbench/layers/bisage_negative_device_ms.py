"""Layer ``sampler``: device ms a step of the operations under the
edge-seeded typed step's ``sampler/negative`` scope: the proposals over
the seed relation's two id spaces, the membership test against its CSR
(``ops/negative.py::edge_in_csr``) and the selection. The rest of
``bisage_sampler_device_ms`` is the typed hop loop."""
from chipbench import bisage_scope_window


def read(run):
  return bisage_scope_window.stage_ms(run, 'sampler', 'negative')
