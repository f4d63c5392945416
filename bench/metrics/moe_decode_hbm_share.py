"""moe_decode_hbm_share: the decode scan's share of the chip's HBM
bandwidth, for a sparse-expert model.

Layer: generation (every op whose phase is ``generate/decode``: the
scan over ``max_new`` one-token steps, the expert layer's ops inside it
included).  Moves: rounds_per_s.  Source: device_trace (leaf-op time by
phase, ``moe_time.py``).

Decode steps in the window (rounds x K x max_new) times the bytes one
step must read (``moe_bytes.step_bytes``: attention, every held expert,
the router, norms and head once, every client's float32 adapters and
KV cache, the embedding rows), over the time of the ops whose phase is
``generate/decode`` and the peak HBM bandwidth of ``device_kind``.  A
step multiplies C x B rows by each weight it reads, far below the
v5e's 240 FLOPs per byte: decode is bound by bandwidth, and this is its
roofline share.
"""
import decode_bytes
import moe_bytes
import moe_time
from peaks import peaks

LAYER = "generation"
MOVES = "rounds_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    s = moe_time.seconds(ctx, phase="generate/decode")
    if not s or not ctx.get("rounds") or "moe" not in ctx["model"]:
        return None
    wl = ctx["workload"]
    moved = (ctx["rounds"] * decode_bytes.steps_per_round(wl)
             * sum(moe_bytes.step_bytes(ctx["model"], wl).values()))
    return 100.0 * moved / s / peaks(ctx["device_kind"])["hbm_bytes_per_s"]
