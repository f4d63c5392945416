"""Pallas TPU kernels for the comms codec hot paths (uplink compression).

quantize    — blockwise symmetric int8/int4 quantization with stochastic
              rounding.  The flat adapter delta is reshaped to (R, BLOCK)
              groups; each group gets one f32 scale (absmax / qmax) so the
              dequantization error is bounded by one quantization step per
              element.  Random bits are *passed in* as a uint32 array
              rather than drawn with ``pltpu.prng_random_bits`` so the
              identical kernel body validates under ``interpret=True`` on
              CPU (the in-kernel PRNG has no CPU lowering); on TPU the
              bits land in VMEM alongside the block.  The offset is the
              top 24 bits scaled by 2**-24 (shift, int32, f32: the TPU
              has no uint32 -> f32 cast).  Deterministic round-to-nearest
              is the special case bits == 2**31 (offset exactly 0.5).
dequantize  — codes * scale back to f32.
abs_threshold_count / abs_threshold_mask
            — the two reductions behind threshold-refinement top-k
              selection (bisection on the magnitude threshold, then a
              dense mask).  O(d) streaming passes, the top-k hot path at
              production d where a full sort is memory-bound.

jnp oracles live in ref.py; dispatch wrappers in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 1024          # elements per quantization group (one (8,128) tile)
ROWS_PER_STEP = 256   # grid tile: (256, 1024) f32 = 1 MB working set

_DET_BITS = jnp.uint32(2 ** 31)    # uint32 whose [0,1) image is exactly 0.5
_INV_2_24 = float(2.0 ** -24)


def _pad_rows(x2: jnp.ndarray, block_rows: int):
    rows = x2.shape[0]
    pad = -(-rows // block_rows) * block_rows - rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, rows


# ------------------------------------------------------------- quantize
def _quantize_kernel(x_ref, bits_ref, codes_ref, scale_ref, *, qmax):
    x = x_ref[...].astype(jnp.float32)                      # (R, B)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # jit rewrites `/ qmax` as `* (1/qmax)` and eager code does not; the
    # explicit multiply keeps kernel and oracle bit-identical everywhere
    scale = jnp.where(absmax > 0, absmax * (1.0 / qmax), 1.0)
    # top 24 bits -> [0, 1) on a 2**-24 grid; 2**31 -> exactly 0.5
    r = (bits_ref[...] >> 8).astype(jnp.int32).astype(jnp.float32) * _INV_2_24
    q = jnp.clip(jnp.floor(x / scale + r), -qmax, qmax)
    codes_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("qmax", "block_rows",
                                             "interpret"))
def quantize(x2: jnp.ndarray, bits: jnp.ndarray, qmax: int = 127,
             block_rows: int = ROWS_PER_STEP, interpret: bool = False):
    """(R, BLOCK) f32 + (R, BLOCK) uint32 -> ((R, BLOCK) int8, (R, 1) f32).

    bits drive the rounding offset: uniform uint32 gives unbiased
    stochastic rounding, the constant 2**31 gives round-to-nearest.
    """
    rows, b = x2.shape
    block_rows = min(block_rows, rows)
    x2, rows = _pad_rows(x2, block_rows)
    bits, _ = _pad_rows(bits, block_rows)
    codes, scales = pl.pallas_call(
        functools.partial(_quantize_kernel, qmax=qmax),
        grid=(x2.shape[0] // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, b), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, b), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, b), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, jnp.int8),
                   jax.ShapeDtypeStruct((x2.shape[0], 1), jnp.float32)],
        interpret=interpret,
    )(x2, bits)
    return codes[:rows], scales[:rows]


# ----------------------------------------------------------- dequantize
def _dequantize_kernel(codes_ref, scale_ref, o_ref):
    o_ref[...] = codes_ref[...].astype(jnp.float32) * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def dequantize(codes: jnp.ndarray, scales: jnp.ndarray,
               block_rows: int = ROWS_PER_STEP, interpret: bool = False):
    """(R, BLOCK) int8 + (R, 1) f32 -> (R, BLOCK) f32."""
    rows, b = codes.shape
    block_rows = min(block_rows, rows)
    codes, rows = _pad_rows(codes, block_rows)
    scales, _ = _pad_rows(scales, block_rows)
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(codes.shape[0] // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, b), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(codes.shape, jnp.float32),
        interpret=interpret,
    )(codes, scales)
    return out[:rows]


# ------------------------------------------------- top-k threshold ops
def _count_kernel(x_ref, t_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    hit = (jnp.abs(x) >= t_ref[0, 0]).astype(jnp.float32)
    o_ref[...] += jnp.sum(hit)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def abs_threshold_count(x2: jnp.ndarray, thresh: jnp.ndarray,
                        block_rows: int = ROWS_PER_STEP,
                        interpret: bool = False) -> jnp.ndarray:
    """Scalar count of |x| >= thresh over the whole (R, BLOCK) array.

    f32 accumulator — exact for counts < 2**24 (adapter-scale d).
    """
    rows, b = x2.shape
    block_rows = min(block_rows, rows)
    x2, rows = _pad_rows(x2, block_rows)
    t = jnp.reshape(thresh.astype(jnp.float32), (1, 1))
    out = pl.pallas_call(
        _count_kernel,
        grid=(x2.shape[0] // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, b), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(x2, t)
    # padded rows are zeros: they only count when thresh == 0
    pad_hits = jnp.where(t[0, 0] <= 0.0,
                         jnp.float32(x2.shape[0] * b - rows * b), 0.0)
    return out[0, 0] - pad_hits


def _mask_kernel(x_ref, t_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.where(jnp.abs(x) >= t_ref[0, 0], x, 0.0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def abs_threshold_mask(x2: jnp.ndarray, thresh: jnp.ndarray,
                       block_rows: int = ROWS_PER_STEP,
                       interpret: bool = False) -> jnp.ndarray:
    """Zero out entries with |x| < thresh (dense top-k mask pass)."""
    rows, b = x2.shape
    block_rows = min(block_rows, rows)
    x2, rows = _pad_rows(x2, block_rows)
    t = jnp.reshape(thresh.astype(jnp.float32), (1, 1))
    out = pl.pallas_call(
        _mask_kernel,
        grid=(x2.shape[0] // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, b), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        interpret=interpret,
    )(x2, t)
    return out[:rows]
