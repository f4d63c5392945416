"""Low-rank sketch codec (PowerSGD-style randomized range finder).

The flat vector is reshaped to a near-square (a, b) matrix X and
approximated as Q @ B with Q = orth(X @ (X^T X)^p Ω) an (a, r) orthonormal
basis and B = Q^T X the (r, b) projection — wire cost r*(a+b) f32 words
instead of a*b, i.e. ~2r/sqrt(d) of identity.  Rank-r truncation is
biased, so "lowrank:r+ef" is the recommended spelling (exactly PowerSGD's
error-feedback construction).

The Gram/projection matmuls are the same streaming (tall, skinny)
contraction the gram Pallas kernel covers; at repro scale XLA's dot is
used directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.comms.codec import Codec


def _matrix_shape(d: int):
    a = 1
    while a * a < d:
        a *= 2
    b = -(-d // a)
    return a, b


class LowRankCodec(Codec):
    def __init__(self, rank: int = 4, power_iters: int = 1):
        if rank < 1:
            raise ValueError(f"lowrank rank must be >= 1, got {rank}")
        self.rank = rank
        self.power_iters = power_iters
        self.name = f"lowrank:{rank}"

    def encode_flat(self, flat, *, key=None):
        d = flat.size
        a, b = _matrix_shape(d)
        x = jnp.pad(flat, (0, a * b - d)).reshape(a, b)
        key = key if key is not None else jax.random.PRNGKey(0)
        omega = jax.random.normal(key, (b, self.rank), jnp.float32)
        p = x @ omega                              # (a, r) range sample
        for _ in range(self.power_iters):
            p = x @ (x.T @ p)
        q, _ = jnp.linalg.qr(p)                    # (a, r) orthonormal
        bmat = q.T @ x                             # (r, b)
        return ({"q": q.astype(jnp.float32), "b": bmat.astype(jnp.float32)},
                {"a": a, "b_cols": b})

    def decode_flat(self, payload):
        x = payload.arrays["q"] @ payload.arrays["b"]
        return x.reshape(-1)

    def bits_per_param(self, d: int) -> float:
        a, b = _matrix_shape(d)
        return 32.0 * self.rank * (a + b) / d

    def nbytes_static(self, d: int) -> int:
        a, b = _matrix_shape(d)
        return 4 * self.rank * (a + b)

    def meta_static(self, d: int):
        a, b = _matrix_shape(d)
        return {"a": a, "b_cols": b}
