"""Layer ``sampler``: device ms a step of the extraction itself: the
per-link dedup (``sampler/enclose/dedup``) and the induced blocks
(``sampler/enclose/induce``: the members' rows read tile by tile and
matched against the link's node set, and under ``hub_pairs`` the probes
of the pairs of unread members)."""
from chipbench import seal_scope_window


def read(run):
  return seal_scope_window.stage_ms(run, 'sampler/enclose/dedup',
                                    'sampler/enclose/induce')
