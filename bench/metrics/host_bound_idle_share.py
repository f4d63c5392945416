"""host_bound_idle_share: the share of the traced window in which no
program runs on the device.

Layer: round engine (the host's work between dispatches: Python, eager
ops, enqueue).  Moves: rounds_per_s.  Source: device_trace (the
"XLA Modules" line, one event per program run, one chip).

1 - (device time of all programs on the "XLA Modules" line) / window.
``device_idle_share`` minus this is the idle time inside running
programs (bubbles between a scan's iterations), which no fusion of
dispatches on the host can close.
"""
LAYER = "round engine"
MOVES = "rounds_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    tr = ctx.get("trace") or {}
    if not tr.get("window_s") or not tr.get("modules"):
        return None
    return 100.0 * (1.0 - sum(tr["modules"].values()) / tr["window_s"])
