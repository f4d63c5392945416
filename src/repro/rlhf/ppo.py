"""Multi-objective PPO: M per-objective clipped-PPO gradients from ONE
shared forward pass (paper Alg. 1 lines 6-9).

The paper computes M separate PPO gradients; naively that is M full
forward+backward passes.  Beyond-paper optimisation (EXPERIMENTS §Perf):
the M losses share every forward intermediate, so we take a single
``jax.vjp`` of the stacked (M,) loss vector and pull M one-hot cotangents
through it — one forward + one linearization, M (cheap, shared-remat)
transposes.

Advantages follow TFIRM's TD/GAE construction: per-token shaped rewards
are  −kl_coef·KL(π‖π_ref)  at every response token plus the terminal
reward-model score r_j at the final response position (standard RLHF
shaping, TRL-compatible).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import FIRMConfig, ModelConfig
from repro.models import transformer
from repro.models.common import merge_trainable
from repro.rlhf import critic as critic_lib


class PPOBatch(NamedTuple):
    tokens: jnp.ndarray          # (B, S) int32 prompt+response
    response_mask: jnp.ndarray   # (B, S) f32: 1 on response positions
    old_logprobs: jnp.ndarray    # (B, S) f32 behaviour-policy logprobs
    ref_logprobs: jnp.ndarray    # (B, S) f32 frozen reference logprobs
    rewards: jnp.ndarray         # (B, M) f32 sequence-level RM scores


def token_logprobs(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """logprob of tokens[t] under logits[t-1]; position 0 gets 0.

    Returns (B, S) aligned with ``tokens``/masks.
    """
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    lp_tok = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.pad(lp_tok, ((0, 0), (1, 0)))


def shaped_rewards(kl: jnp.ndarray, mask: jnp.ndarray, rewards: jnp.ndarray,
                   kl_coef: jnp.ndarray) -> jnp.ndarray:
    """(B,S) kl, (B,S) mask, (B,M) terminal -> (B,S,M) per-token rewards."""
    # last response position per row
    idx = jnp.maximum(mask.sum(-1) - 1, 0).astype(jnp.int32)
    last = jax.nn.one_hot(
        (jnp.argmax(mask * jnp.arange(mask.shape[1])[None], axis=-1)),
        mask.shape[1], dtype=jnp.float32)                     # (B, S)
    del idx
    r = -kl_coef * kl[..., None] * mask[..., None]
    r = r + last[..., None] * rewards[:, None, :]
    return r


def gae(rewards_tok: jnp.ndarray, values: jnp.ndarray, mask: jnp.ndarray,
        gamma: float, lam: float):
    """(B,S,M) rewards, (B,S,M) values -> (advantages, returns)."""
    next_mask = jnp.concatenate([mask[:, 1:], jnp.zeros_like(mask[:, :1])],
                                axis=1)[..., None]
    v_next = jnp.concatenate([values[:, 1:], jnp.zeros_like(values[:, :1])],
                             axis=1)
    delta = rewards_tok + gamma * v_next * next_mask - values

    def body(carry, xs):
        d, nm = xs
        adv = d + gamma * lam * nm * carry
        return adv, adv

    ds = jnp.moveaxis(delta, 1, 0)[::-1]                     # (S, B, M)
    nms = jnp.moveaxis(next_mask, 1, 0)[::-1]
    _, advs = jax.lax.scan(body, jnp.zeros_like(ds[0]), (ds, nms))
    adv = jnp.moveaxis(advs[::-1], 0, 1)                     # (B, S, M)
    return adv, adv + values


def masked_mean(x, mask):
    return (x * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def router_trains(trainable) -> bool:
    """Does any router parameter of an expert layer train?  (Not under
    LoRA, whose adapters sit on attention only.)"""
    flat = jax.tree_util.tree_flatten_with_path(trainable)[0]
    return any(any(getattr(k, "key", None) == "router" for k in path)
               for path, _ in flat)


def multi_objective_losses(cfg: ModelConfig, fc: FIRMConfig, trainable,
                           frozen, critic, batch: PPOBatch, kl_coef,
                           aux: Optional[dict] = None):
    """Stacked (M,) PPO losses + auxiliary outputs (single forward)."""
    params = merge_trainable(trainable, frozen)
    out = transformer.forward_seq(cfg, params, batch.tokens, aux)
    lp = token_logprobs(out["logits"], batch.tokens)
    mask = batch.response_mask
    ratio = jnp.exp(jnp.clip(lp - batch.old_logprobs, -20.0, 20.0))
    kl = lp - batch.ref_logprobs

    feats = critic_lib.features(out["hidden"])
    vals = critic_lib.values(critic, feats)                  # (B, S, M)
    r_tok = shaped_rewards(jax.lax.stop_gradient(kl), mask, batch.rewards,
                           kl_coef)
    adv, rets = gae(jax.lax.stop_gradient(r_tok),
                    jax.lax.stop_gradient(vals), mask,
                    fc.gamma, fc.gae_lambda)
    # per-objective advantage whitening over response tokens
    mean = (adv * mask[..., None]).sum((0, 1)) / jnp.maximum(
        mask.sum(), 1.0)
    var = (((adv - mean) ** 2) * mask[..., None]).sum((0, 1)) / jnp.maximum(
        mask.sum(), 1.0)
    adv = (adv - mean) / jnp.sqrt(var + 1e-8)

    clipped = jnp.clip(ratio, 1.0 - fc.ppo_clip, 1.0 + fc.ppo_clip)
    pg = -jnp.minimum(ratio[..., None] * adv, clipped[..., None] * adv)
    losses = (pg * mask[..., None]).sum((0, 1)) / jnp.maximum(mask.sum(), 1.0)
    # the router's load-balance loss is a pretraining regulariser: it
    # enters the objectives only where the router itself trains
    if router_trains(trainable):
        losses = losses + out["aux_loss"]

    metrics = {
        "kl": masked_mean(kl, mask),
        "ratio_mean": masked_mean(ratio, mask),
        "entropy_proxy": -masked_mean(lp, mask),
        "aux_loss": out["aux_loss"],
    }
    if "moe_counts" in out:
        metrics["moe_counts"] = out["moe_counts"]
    return losses, (metrics, feats, r_tok, rets, mask)


def per_objective_grads(cfg: ModelConfig, fc: FIRMConfig, trainable, frozen,
                        critic, batch: PPOBatch, kl_coef,
                        aux: Optional[dict] = None):
    """M gradients of the M losses w.r.t. ``trainable`` — one forward.

    Returns (grads: list of M pytrees, losses (M,), extras).

    With ``cfg.batched_vjp`` the M cotangent pulls are vmapped: under
    remat the sequential pulls each re-run the rematerialised forward,
    while the vmapped transpose shares ONE recompute across objectives
    (EXPERIMENTS §Perf hillclimb — ~(M-1) forward-equivalents saved).
    """
    m = fc.n_objectives

    def fn(tr):
        return multi_objective_losses(cfg, fc, tr, frozen, critic, batch,
                                      kl_coef, aux)

    (losses, extras), vjp_fn = jax.vjp(fn, trainable, has_aux=False)
    # vjp over the tuple output: cotangent for extras must be zeros
    zeros_extras = jax.tree_util.tree_map(jnp.zeros_like, extras)
    if cfg.batched_vjp:
        stacked = jax.vmap(lambda e: vjp_fn((e, zeros_extras))[0])(
            jnp.eye(m, dtype=losses.dtype))
        grads = [jax.tree_util.tree_map(lambda l, j=j: l[j], stacked)
                 for j in range(m)]
    else:
        grads = []
        for j in range(m):
            ct = (jax.nn.one_hot(j, m, dtype=losses.dtype), zeros_extras)
            grads.append(vjp_fn(ct)[0])
    return grads, losses, extras
