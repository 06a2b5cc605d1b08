"""Layer ``sampler``: device ms a step of every operation under the SEAL
step's ``sampler`` scope: negatives, the hop, and per link the dedup,
the induced block, the hub pairs' probes and DRNL."""
from chipbench import seal_scope_window


def read(run):
  return seal_scope_window.layer_ms(run, 'sampler')
