"""Blockwise int8 / int4 stochastic quantization codecs.

The flat vector is padded to (R, BLOCK) groups; each group carries one f32
scale.  int8 transmits the codes raw (1 byte/param); int4 packs two codes
per byte, so the wire cost is 0.5 byte/param + 4/BLOCK bytes of scales.
Stochastic rounding (uniform uint32 offsets) keeps the quantizer unbiased,
which is what lets FedAvg of C decoded uploads concentrate around the true
mean; pass ``stochastic=False`` for deterministic round-to-nearest.

Hot paths run through the Pallas kernels in repro/kernels/quantize.py
(interpret-mode on CPU, native on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.comms.codec import Codec
from repro.kernels import ops
from repro.kernels.quantize import BLOCK, _DET_BITS


def _to_blocks(flat: jnp.ndarray):
    d = flat.size
    rows = -(-d // BLOCK)
    pad = rows * BLOCK - d
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, BLOCK)


def pack_int4(codes: jnp.ndarray) -> jnp.ndarray:
    """int8 codes in [-7, 7] -> uint8, two nibbles per byte."""
    u = (codes.astype(jnp.int32) + 8).astype(jnp.uint8)      # [1, 15]
    lo, hi = u[..., 0::2], u[..., 1::2]
    return (lo << 4) | hi


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    lo = (packed >> 4).astype(jnp.int32) - 8
    hi = (packed & 0xF).astype(jnp.int32) - 8
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], -1).astype(jnp.int8)


class QuantizeCodec(Codec):
    """bits=8 -> raw int8 codes; bits=4 -> nibble-packed uint8 codes."""

    def __init__(self, bits: int = 8, stochastic: bool = True,
                 use_pallas: bool = True):
        if bits not in (4, 8):
            raise ValueError(f"quantize bits must be 4 or 8, got {bits}")
        self.bits = bits
        self.qmax = 7 if bits == 4 else 127
        self.stochastic = stochastic
        self.use_pallas = use_pallas
        self.name = f"int{bits}"

    def encode_flat(self, flat, *, key=None):
        x2 = _to_blocks(flat)
        if self.stochastic and key is not None:
            rbits = jax.random.bits(key, x2.shape, jnp.uint32)
        else:
            rbits = jnp.full(x2.shape, _DET_BITS, jnp.uint32)
        codes, scales = ops.quantize(x2, rbits, self.qmax,
                                     use_pallas=self.use_pallas)
        if self.bits == 4:
            codes = pack_int4(codes)
        return {"codes": codes, "scales": scales}, {"bits": self.bits}

    def decode_flat(self, payload):
        codes = payload.arrays["codes"]
        if payload.meta["bits"] == 4:
            codes = unpack_int4(codes)
        x2 = ops.dequantize(codes, payload.arrays["scales"],
                            use_pallas=self.use_pallas)
        return x2.reshape(-1)

    def bits_per_param(self, d: int) -> float:
        return self.bits + 32.0 / BLOCK

    def nbytes_static(self, d: int) -> int:
        # padded (rows, BLOCK) codes (int8: 1 byte, int4: packed nibbles)
        # + one f32 scale per row — exactly the measured Payload layout
        rows = -(-d // BLOCK)
        code_bytes = rows * (BLOCK if self.bits == 8 else BLOCK // 2)
        return code_bytes + rows * 4

    def meta_static(self, d: int):
        return {"bits": self.bits}

    # -- stacked traced pair: one kernel over all clients' blocks --------
    def encode_decode_traced_stacked(self, flats, states=(), *, keys=None):
        """The C clients' padded (rows, BLOCK) groups concatenate along
        the row axis into ONE quantize and ONE dequantize kernel.  Blocks
        are quantized row-independently and each client's rounding bits
        come from its own key, so row c is bit-identical to a per-client
        ``encode_decode_traced``.  The wire buffers (int4 packed) stay in
        the concatenated-row layout ``split_stacked_arrays`` slices."""
        flats = jax.lax.optimization_barrier(flats)
        c, d = flats.shape
        rows = -(-d // BLOCK)
        pad = rows * BLOCK - d
        x = jnp.pad(flats, ((0, 0), (0, pad))) if pad else flats
        x = x.reshape(c * rows, BLOCK)
        if self.stochastic and keys is not None:
            rbits = jax.vmap(
                lambda k: jax.random.bits(k, (rows, BLOCK), jnp.uint32)
            )(keys).reshape(c * rows, BLOCK)
        else:
            rbits = jnp.tile(jnp.full((rows, BLOCK), _DET_BITS,
                                      jnp.uint32), (c, 1))
        codes, scales = ops.quantize(x, rbits, self.qmax,
                                     use_pallas=self.use_pallas)
        decoded = ops.dequantize(codes, scales, use_pallas=self.use_pallas)
        decoded = jax.lax.optimization_barrier(
            decoded.reshape(c, rows * BLOCK)[:, :d])
        wire = pack_int4(codes) if self.bits == 4 else codes
        return {"codes": wire, "scales": scales}, decoded, states

    def split_stacked_arrays(self, arrays, c, d):
        """Slice the concatenated-row codes/scales into per-client wire
        buffers, in-graph — identical layout (and bytes) to per-client
        encodes."""
        rows = -(-d // BLOCK)
        return [{"codes": arrays["codes"][i * rows:(i + 1) * rows],
                 "scales": arrays["scales"][i * rows:(i + 1) * rows]}
                for i in range(c)]
