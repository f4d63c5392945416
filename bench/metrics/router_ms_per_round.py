"""router_ms_per_round: device time a round spends routing tokens.

Layer: router (``models/moe.py``: the ``moe/route`` scope, the router
matmul, softmax and top-k, the gates, the dispatch and combine weights
and the dispatch einsum, in every phase that runs the model).  Moves:
rounds_per_s.  Source: device_trace (leaf-op time under ``moe/route``,
``moe_time.py``) over the rounds in the traced window.
"""
import moe_time

LAYER = "router"
MOVES = "rounds_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return moe_time.ms_per_round(ctx, layer="moe/route")
