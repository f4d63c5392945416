"""decode_hbm_share: the decode scan's share of the chip's HBM bandwidth.

Layer: generation (the ``generate/decode`` scope: the scan over
``max_new`` one-token steps, all clients at once).  Moves: rounds_per_s.
Source: device_trace (leaf-op time under ``generate/decode``,
``layer_time.py``).

Decode steps in the window (rounds x K x max_new) times the bytes one
step must read (``decode_bytes.step_bytes``: the frozen weights once,
every client's adapters in float32, every client's KV cache, the
embedding rows), over the time under ``generate/decode`` and the peak
HBM bandwidth of ``device_kind`` (``peaks.py``).  A step does one
multiply-add per weight and row, C x B rows (8 in both cells): C x B
FLOPs per bfloat16 weight byte, against the v5e's 240 FLOPs per byte of
bandwidth, so decode is bound by bandwidth, and this is its roofline
share.
"""
import decode_bytes
import layer_time
from peaks import peaks

LAYER = "generation"
MOVES = "rounds_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    t = layer_time.times(ctx)
    s = (t or {}).get("generate/decode")
    if not s or not ctx.get("rounds"):
        return None
    wl = ctx["workload"]
    moved = (ctx["rounds"] * decode_bytes.steps_per_round(wl)
             * sum(decode_bytes.step_bytes(ctx["model"], wl).values()))
    return 100.0 * moved / s / peaks(ctx["device_kind"])["hbm_bytes_per_s"]
