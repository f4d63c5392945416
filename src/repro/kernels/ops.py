"""Jit'd dispatch wrappers for the Pallas kernels.

On TPU the compiled kernels run natively.  On CPU ``interpret=True``
executes the kernel bodies in Python for correctness validation (the
model code itself uses the XLA twins in repro/models/attention.py).  Any
other backend is an error: a kernel never silently runs interpreted on
an accelerator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import gram as _gram
from repro.kernels import rmsnorm as _rn
from repro.kernels import ref


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels run natively on TPU or "
                           f"interpreted on CPU, not on {backend!r}")
    return backend == "cpu"


def gram(x: jnp.ndarray, use_pallas: bool = True) -> jnp.ndarray:
    """(M, d) stacked flat gradients -> (M, M) Gram matrix."""
    if not use_pallas:
        return ref.gram(x)
    return _gram.gram_pallas(x, interpret=_interpret())


def gram_from_pytrees(grads, use_pallas: bool = True) -> jnp.ndarray:
    """List of M gradient pytrees -> (M, M); flattens then calls gram."""
    rows = []
    for g in grads:
        leaves = [l.astype(jnp.float32).reshape(-1)
                  for l in jax.tree_util.tree_leaves(g)]
        rows.append(jnp.concatenate(leaves))
    return gram(jnp.stack(rows), use_pallas=use_pallas)


def flash_attention(q, k, v, *, causal=True, sliding_window=0,
                    use_pallas: bool = True, **kw):
    if not use_pallas:
        return ref.flash_attention(q, k, v, causal=causal,
                                   sliding_window=sliding_window)
    return _fa.flash_attention(q, k, v, causal=causal,
                               sliding_window=sliding_window,
                               interpret=_interpret(), **kw)


def rmsnorm(x, g, eps: float = 1e-5, use_pallas: bool = True):
    if not use_pallas:
        return ref.rmsnorm(x, g, eps)
    return _rn.rmsnorm(x, g, eps=eps, interpret=_interpret())


def ssd_scan(x, bmat, cmat, dt, da, *, chunk: int = 128,
             use_pallas: bool = True):
    """Chunked Mamba2 SSD scan: (BH,S,hd) x (BH,S,ds) etc -> (BH,S,hd)."""
    if not use_pallas:
        return ref.ssd_scan(x, bmat, cmat, dt, da)
    from repro.kernels import ssd as _ssd
    return _ssd.ssd_scan(x, bmat, cmat, dt, da, chunk=chunk,
                         interpret=_interpret())


# --------------------------------------------------- comms codec kernels
def quantize(x2, bits, qmax: int = 127, use_pallas: bool = True):
    """(R, B) f32 + uint32 rounding bits -> (int8 codes, (R, 1) scales)."""
    if not use_pallas:
        return ref.quantize(x2, bits, qmax)
    from repro.kernels import quantize as _q
    return _q.quantize(x2, bits, qmax=qmax, interpret=_interpret())


def dequantize(codes, scales, use_pallas: bool = True):
    if not use_pallas:
        return ref.dequantize(codes, scales)
    from repro.kernels import quantize as _q
    return _q.dequantize(codes, scales, interpret=_interpret())


def abs_threshold_count(x2, thresh, use_pallas: bool = True):
    if not use_pallas:
        return ref.abs_threshold_count(x2, thresh)
    from repro.kernels import quantize as _q
    return _q.abs_threshold_count(x2, jnp.asarray(thresh, jnp.float32),
                                  interpret=_interpret())


def abs_threshold_mask(x2, thresh, use_pallas: bool = True):
    if not use_pallas:
        return ref.abs_threshold_mask(x2, thresh)
    from repro.kernels import quantize as _q
    return _q.abs_threshold_mask(x2, jnp.asarray(thresh, jnp.float32),
                                 interpret=_interpret())


def topk_threshold(x2, k: int, iters: int = 32, use_pallas: bool = True):
    """Magnitude threshold bracket for top-k selection via bisection.

    The TPU-friendly top-k selection: ``iters`` streaming count passes
    (O(d) each, no sort).  Returns (lo, hi) with the invariant
    count(|x| >= lo) >= k > count(|x| >= hi) whenever such a bracket
    exists (count at hi may exceed k only if every entry ties at the
    max).  Entries with |x| >= hi are definite top-k members; entries in
    [lo, hi) are boundary ties that fill the remaining slots.
    """
    lo = jnp.float32(0.0)
    hi = jnp.nextafter(jnp.max(jnp.abs(x2.astype(jnp.float32))),
                       jnp.float32(jnp.inf))
    kf = jnp.float32(k)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        cnt = abs_threshold_count(x2, mid, use_pallas=use_pallas)
        lo, hi = jnp.where(cnt >= kf, mid, lo), jnp.where(cnt >= kf, hi, mid)
    return lo, hi
