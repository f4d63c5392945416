"""codec_ms_per_round: device time a round spends in the uplink codec.

Layer: codec (the ``+ef`` stacked round trip: error feedback and the
Pallas quantize kernel, the program ``ef_roundtrip_stacked`` on the
per-round path, the ``uplink_codec`` scope inside a fused round).
Moves: rounds_per_s.  Source: device_trace (that program's time on the
"XLA Modules" line, or leaf-op time under the scope, ``layer_time.py``)
over the rounds in the traced window.
"""
import layer_time

LAYER = "codec"
MOVES = "rounds_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(ctx):
    return layer_time.ms_per_round(ctx, "uplink_codec")
