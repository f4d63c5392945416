"""local_step_ms_per_round: device time a round spends in the clients'
local update.

Layer: local step (``rlhf/local.py``: the ``local_step/grads`` PPO
gradients, ``local_step/mgda`` resolve, ``local_step/adam`` and
``local_step/critic_kl`` scopes).  Moves: rounds_per_s.  Source:
device_trace (leaf-op time under ``local_step/*``, ``layer_time.py``)
over the rounds in the traced window.
"""
import layer_time

LAYER = "local step"
MOVES = "rounds_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return layer_time.ms_per_round(ctx, "local_step/", prefix=True)
