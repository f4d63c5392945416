"""round_mfu: the whole round's share of the chip's bf16 peak.

Layer: whole round (``fed/engine.py`` round, all of its programs).
Moves: rounds_per_s.  Source: device_trace (the traced window's length
and the rounds completed in it).

Model FLOPs of a round (``flops.round_flops``: prefill, decode, the
reference forward, the loss forward and M backward pulls, no
rematerialised work) times the rounds completed in the traced window,
over the window's length and the bf16 peak of ``device_kind``.
"""
from peaks import peaks

LAYER = "whole round"
MOVES = "rounds_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    tr = ctx["trace"]
    if not tr["window_s"] or not tr["rounds"]:
        return None
    rate = ctx["flops_per_round"] * tr["rounds"] / tr["window_s"]
    return 100.0 * rate / peaks(ctx["device_kind"])["bf16_flops"]
