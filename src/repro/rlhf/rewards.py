"""Synthetic reward models (DESIGN §5 — the HF reward models are a data
gate at repro band 2; we replace them with jittable proxies whose
*conflict structure* mirrors helpfulness-vs-harmlessness).

Token-band construction: helpfulness rewards response tokens inside a
"helpful" id band that OVERLAPS a "harmful" band, so pushing helpfulness
up drags harmlessness down — the same tension the paper's Fig. 2-4
navigate.  Conciseness linearly penalises length beyond a tolerance
(paper A.2.3).  All rewards are normalised to [0, 1] (paper §5).

A second parameterisation (`variant="alt"`) shifts the bands — used for
the heterogeneous-reward-model experiment (paper A.2.1), standing in for
the OpenAssistant/deberta RM.
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp


def _band(vocab: int, lo: float, hi: float):
    return int(vocab * lo), int(vocab * hi)


# ------------------------------------------------------------- banded cores
# The reward math lives in functions parameterized by the band edges so the
# vectorized round engine can vmap one scorer over a stacked client axis
# with per-client bands (heterogeneous RMs) instead of dispatching per-client
# Python closures.  ``make_reward_fns`` builds its closures on the same
# cores, so both engine paths share the exact arithmetic.

def _frac_in_band(tokens: jnp.ndarray, mask: jnp.ndarray,
                  band) -> jnp.ndarray:
    inb = ((tokens >= band[0]) & (tokens < band[1])).astype(jnp.float32)
    n = jnp.maximum(mask.sum(-1), 1.0)
    return (inb * mask).sum(-1) / n


def helpfulness_reward(tokens, mask, band):
    # concave in the helpful fraction: diminishing returns, in [0,1]
    f = _frac_in_band(tokens, mask, band)
    return jnp.sqrt(jnp.clip(f, 0.0, 1.0))


def harmlessness_reward(tokens, mask, band):
    f = _frac_in_band(tokens, mask, band)
    return jnp.clip(1.0 - 2.0 * f, 0.0, 1.0)


def conciseness_reward(tokens, mask, length_tolerance: int):
    # length penalty (paper A.2.3) + anti-redundancy: the simulation
    # generates fixed-length responses, so pure length is constant —
    # the distinct-token fraction gives the policy a live signal with
    # the same "don't pad/ramble" semantics.
    n = mask.sum(-1)
    over = jnp.maximum(n - length_tolerance, 0.0)
    length_term = jnp.clip(
        1.0 - over / jnp.maximum(length_tolerance, 1.0), 0.0, 1.0)
    tok = jnp.where(mask > 0, tokens, -1)
    same = (tok[:, :, None] == tok[:, None, :]) & \
        (tok[:, :, None] >= 0)
    repeats = same.sum(-1).astype(jnp.float32)            # (B, S)
    distinct = (mask / jnp.maximum(repeats, 1.0)).sum(-1) / \
        jnp.maximum(n, 1.0)
    return jnp.clip(0.5 * length_term + 0.5 * distinct, 0.0, 1.0)


def variant_bands(vocab: int, variant: str = "default"):
    """(helpful, harmful) band edges as (2,) int32 arrays — the traced
    per-client reward parameters of the vectorized scorer."""
    if variant == "alt":
        helpful = _band(vocab, 0.30, 0.55)
        harmful = _band(vocab, 0.42, 0.60)
    else:
        helpful = _band(vocab, 0.25, 0.50)
        harmful = _band(vocab, 0.45, 0.55)
    return (jnp.asarray(helpful, jnp.int32), jnp.asarray(harmful, jnp.int32))


def make_reward_fns(vocab: int, n_objectives: int = 2,
                    variant: str = "default",
                    length_tolerance: int = 24) -> Sequence[Callable]:
    """Returns M callables (tokens, mask) -> (B,) rewards in [0, 1].

    tokens: (B, S) response tokens; mask: (B, S) 1.0 on response positions.
    """
    helpful, harmful = variant_bands(vocab, variant)

    def helpfulness(tokens, mask):
        return helpfulness_reward(tokens, mask, helpful)

    def harmlessness(tokens, mask):
        return harmlessness_reward(tokens, mask, harmful)

    def conciseness(tokens, mask):
        return conciseness_reward(tokens, mask, length_tolerance)

    fns = [helpfulness, harmlessness, conciseness]
    if n_objectives > len(fns):
        raise ValueError(f"at most {len(fns)} synthetic objectives")
    return fns[:n_objectives]


def score_batch(reward_fns: Sequence[Callable], tokens: jnp.ndarray,
                mask: jnp.ndarray) -> jnp.ndarray:
    """(B, S) tokens/mask -> (B, M) rewards."""
    return jnp.stack([f(tokens, mask) for f in reward_fns], axis=-1)


def score_batch_banded(helpful: jnp.ndarray, harmful: jnp.ndarray,
                       tokens: jnp.ndarray, mask: jnp.ndarray,
                       n_objectives: int,
                       length_tolerance: int) -> jnp.ndarray:
    """Band-parameterized twin of ``score_batch``: (B, S) -> (B, M).

    ``helpful``/``harmful`` are (2,) int32 band edges (``variant_bands``);
    vmap over a leading client axis of (C, 2) bands scores every client's
    rollouts in one dispatch, including heterogeneous-RM sweeps.
    """
    with jax.named_scope("rewards"):
        cols = [helpfulness_reward(tokens, mask, helpful),
                harmlessness_reward(tokens, mask, harmful),
                conciseness_reward(tokens, mask, length_tolerance)]
        if n_objectives > len(cols):
            raise ValueError(f"at most {len(cols)} synthetic objectives")
        return jnp.stack(cols[:n_objectives], axis=-1)


# ---------------------------------------------------------------- learned RM
def init_learned_rm(key, vocab: int, d: int = 64):
    """A tiny fixed (frozen) scoring head: mean embedding -> scalar.

    Stands in for a learned reward model with an arbitrary preference
    direction; used in robustness experiments.
    """
    k1, k2 = jax.random.split(key)
    return {"embed": jax.random.normal(k1, (vocab, d)) * 0.05,
            "w": jax.random.normal(k2, (d,)) * 0.3}


def learned_rm_score(p, tokens, mask):
    e = p["embed"][tokens]                                   # (B, S, d)
    m = mask[..., None]
    pooled = (e * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    return jax.nn.sigmoid(pooled @ p["w"])                    # (B,) in [0,1]
