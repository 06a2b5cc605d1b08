"""Layer ``sampler``: device ms a step of DRNL
(``sampler/enclose/drnl``): two searches a link over the dense blocks,
run to the batch's fixpoint."""
from chipbench import seal_scope_window


def read(run):
  return seal_scope_window.stage_ms(run, 'sampler/enclose/drnl')
