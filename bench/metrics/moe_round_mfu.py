"""moe_round_mfu: the whole round's share of the chip's bf16 peak, for a
sparse-expert model.

Layer: whole round (``fed/engine.py`` round, all of its programs).
Moves: rounds_per_s.  Source: device_trace (the traced window's length
and the rounds completed in it).

Model FLOPs of a round (``moe_flops.round_flops``: the router and the
``top_k`` routed experts of each token, attention and LoRA as
``flops.py`` counts them, no rematerialised work and no unrouted expert)
times the rounds in the traced window, over the window's length and the
bf16 peak of ``device_kind``.  ``round_mfu`` counts a dense FFN of
``d_ff`` instead, which is not this model's work.
"""
import moe_flops
from peaks import peaks

LAYER = "whole round"
MOVES = "rounds_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    tr = ctx.get("trace") or {}
    model = ctx.get("model") or {}
    if not tr.get("window_s") or not tr.get("rounds") or "moe" not in model:
        return None
    rate = (moe_flops.round_flops(model, ctx["workload"]) * tr["rounds"]
            / tr["window_s"])
    return 100.0 * rate / peaks(ctx["device_kind"])["bf16_flops"]
