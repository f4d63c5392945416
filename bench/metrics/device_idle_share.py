"""device_idle_share: the share of the traced window with no operation
running on the device.

Layer: device.  Moves: rounds_per_s.  Source: device_trace.

1 - (union of the TPU plane's op intervals in the window) / window.
"""
LAYER = "device"
MOVES = "rounds_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    tr = ctx["trace"]
    if not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
