"""Layer ``model_step``: device ms a step of what only HGT's attention
has: the stages under every relation's ``rel_<s>__<r>__<d>`` scope (the
take of the children's rows, keys, values and the ``A_r``, ``M_r``
products, logits, exponentials, weighted sum), the ``softmax`` that joins
a parent type's relations and the ``aggregate`` that places the result;
forward, recomputed forward and backward, from
``chipbench/hgt_scope_window.py``. The rest of ``hgt_model_device_ms`` is
the input linears, the queries, the output linears, the head and the
update."""
from chipbench import hgt_scope_window


def read(run):
  return hgt_scope_window.stage_ms(run, 'model_step', 'softmax', 'aggregate',
                                   prefixes=('rel_',))
