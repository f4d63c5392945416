"""expert_ms_per_round: device time a round spends in the experts.

Layer: expert layer (``models/moe.py``: the ``moe/experts`` scope, the
three expert matmuls and the combine, in every phase that runs the
model: prefill, decode, the reference forward, the loss forward and its
backward).  Moves: rounds_per_s.  Source: device_trace (leaf-op time
under ``moe/experts`` by the program's op-to-layer map,
``moe_time.py``) over the rounds in the traced window.
"""
import moe_time

LAYER = "expert layer"
MOVES = "rounds_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return moe_time.ms_per_round(ctx, layer="moe/experts")
