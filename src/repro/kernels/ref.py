"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gram(x: jnp.ndarray) -> jnp.ndarray:
    """(M, d) -> (M, M) Gram matrix, f32 accumulation."""
    xf = x.astype(jnp.float32)
    return xf @ xf.T


def flash_attention(q, k, v, *, causal: bool = True,
                    sliding_window: int = 0) -> jnp.ndarray:
    """q: (B, Sq, Hq, Dh); k, v: (B, Skv, Hkv, Dh) -> (B, Sq, Hq, Dh).

    Naive materialised softmax attention (the oracle).
    """
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    qpk = hq // hkv
    kx = jnp.repeat(k, qpk, axis=2)
    vx = jnp.repeat(v, qpk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kx.astype(jnp.float32)) * dh ** -0.5
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= qp >= kp
    if sliding_window:
        mask &= qp - kp < sliding_window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vx.astype(jnp.float32))
    return o.astype(q.dtype)


def rmsnorm(x: jnp.ndarray, g: jnp.ndarray, eps: float = 1e-5
            ) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g)


def quantize(x2: jnp.ndarray, bits: jnp.ndarray, qmax: int = 127):
    """Blockwise symmetric quantization oracle.

    x2: (R, B) f32; bits: (R, B) uint32 rounding offsets (2**31 = exactly
    round-to-nearest).  Returns ((R, B) int8 codes, (R, 1) f32 scales).
    """
    x = x2.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # jit rewrites `/ qmax` as `* (1/qmax)` and eager code does not; the
    # explicit multiply keeps kernel and oracle bit-identical everywhere
    scale = jnp.where(absmax > 0, absmax * (1.0 / qmax), 1.0)
    r = (bits >> 8).astype(jnp.int32).astype(jnp.float32) * (2.0 ** -24)
    q = jnp.clip(jnp.floor(x / scale + r), -qmax, qmax)
    return q.astype(jnp.int8), scale


def dequantize(codes: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    return codes.astype(jnp.float32) * scales


def abs_threshold_count(x2: jnp.ndarray, thresh) -> jnp.ndarray:
    return jnp.sum(jnp.abs(x2.astype(jnp.float32)) >= thresh
                   ).astype(jnp.float32)


def abs_threshold_mask(x2: jnp.ndarray, thresh) -> jnp.ndarray:
    x = x2.astype(jnp.float32)
    return jnp.where(jnp.abs(x) >= thresh, x, 0.0)


def ssd_scan(x, bmat, cmat, dt, da):
    """Exact SSD recurrence oracle (per-step scan).

    x: (BH, S, hd); bmat/cmat: (BH, S, ds); dt/da: (BH, S).
    h_t = exp(da_t) h_{t-1} + dt_t * x_t B_t^T;  y_t = C_t . h_t.
    """
    bh, s, hd = x.shape
    ds = bmat.shape[-1]

    def body(h, xs):
        xt, bt, ct, dtt, dat = xs
        h = jnp.exp(dat)[:, None, None] * h + \
            dtt[:, None, None] * (xt[:, :, None] * bt[:, None, :])
        y = jnp.einsum("bds,bs->bd", h, ct)
        return h, y

    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
               for t in (x, bmat, cmat, dt, da))
    h0 = jnp.zeros((bh, hd, ds), jnp.float32)
    _, ys = jax.lax.scan(body, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)
