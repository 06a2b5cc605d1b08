"""Layer ``model_step``: device ms a step of what the embedding tables
cost: the stages under the model's ``embed_user`` and ``embed_item`` scopes
(the take by node id forward, its transpose backward: the scatter-add of
the rows' gradient into the tables' dense gradient) and the dense Adam
over the tables under ``update/tables``; from
``chipbench/bisage_scope_window.py``. The rest of
``bisage_model_device_ms`` is the encoders, the decoder, the loss and the
other parameters' update."""
from chipbench import bisage_scope_window


def read(run):
  return bisage_scope_window.stage_ms(run, 'model_step', 'embed_user',
                                      'embed_item', 'update/tables')
