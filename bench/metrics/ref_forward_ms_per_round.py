"""ref_forward_ms_per_round: device time a round spends in the frozen
reference's forward and its token log-probabilities.

Layer: reference forward (the ``ref_forward`` scope of the round
program).  Moves: rounds_per_s.  Source: device_trace (leaf-op time
under ``ref_forward``, ``layer_time.py``) over the rounds in the traced
window.
"""
import layer_time

LAYER = "reference forward"
MOVES = "rounds_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return layer_time.ms_per_round(ctx, "ref_forward")
