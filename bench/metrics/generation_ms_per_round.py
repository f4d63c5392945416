"""generation_ms_per_round: device time a round spends generating.

Layer: generation (``rlhf/sampling.py``: the ``generate/prefill`` and
``generate/decode`` scopes, vmapped over clients).  Moves: rounds_per_s.
Source: device_trace (leaf-op time under ``generate/*``, by the
program's op-to-layer map, ``layer_time.py``) over the rounds in the
traced window.
"""
import layer_time

LAYER = "generation"
MOVES = "rounds_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return layer_time.ms_per_round(ctx, "generate/", prefix=True)
