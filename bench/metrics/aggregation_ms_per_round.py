"""aggregation_ms_per_round: device time a round spends in the server's
weighted average of the decoded uploads.

Layer: aggregation (``engine._jit_flat_aggregate``, the program
``flat_aggregate``; the ``aggregate`` scope inside a fused round).
Moves: rounds_per_s.  Source: device_trace (that program's time on the
"XLA Modules" line, or leaf-op time under the scope, ``layer_time.py``)
over the rounds in the traced window.
"""
import layer_time

LAYER = "aggregation"
MOVES = "rounds_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return layer_time.ms_per_round(ctx, "aggregate")
