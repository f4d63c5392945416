"""Comms codec subsystem: measured-byte uplink/downlink compression.

Turns the engine's raw f32 pytree traffic into encoded ``Payload``s with
exact wire-byte accounting, optional per-client error feedback, and
Pallas-kernel hot paths (see README.md in this package).

    from repro.comms import make_codec
    codec = make_codec("int8+ef")
    payload, state = codec.encode(delta_tree, state, key=key)
    delta2 = codec.decode(payload)          # payload.nbytes on the wire

Analytic per-round models live in repro.core.comms; this package is the
measured counterpart wired through repro.fed.engine.

A codec implements one flat-vector transform; the base class derives
from it the traced round trip (``encode_decode_traced*`` /
``roundtrip_traced*`` with explicit array state) and, as one jitted
program of that round trip, the host Payload boundary above — see
codec.py and README.md.
"""
from repro.comms.codec import (Codec, DeltaCodec, ErrorFeedback,
                               IdentityCodec, Payload, flat_to_tree,
                               tree_to_flat)
from repro.comms.lowrank import LowRankCodec
from repro.comms.quantize import QuantizeCodec
from repro.comms.registry import available, make_codec
from repro.comms.sparsify import TopKCodec

__all__ = [
    "Codec", "DeltaCodec", "ErrorFeedback", "IdentityCodec", "Payload",
    "QuantizeCodec", "TopKCodec", "LowRankCodec",
    "available", "make_codec", "tree_to_flat", "flat_to_tree",
]
