"""Autoregressive response generation with the decode cache.

Used by the federated simulation engine and examples (toy scale, CPU).
The behaviour policy's per-token logprobs are recorded so PPO sees the
exact old_logprobs of the sampling distribution.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer


@partial(jax.jit, static_argnames=("cfg", "max_new", "temperature"))
def generate(cfg: ModelConfig, params, prompt: jnp.ndarray, key,
             max_new: int = 32, temperature: float = 1.0,
             aux: Optional[dict] = None):
    """prompt: (B, P) -> (tokens (B, P+max_new), logprobs (B, P+max_new)).

    logprobs are the sampling logprobs for generated positions, 0 elsewhere.
    """
    b, p = prompt.shape
    total = p + max_new
    with jax.named_scope("generate/prefill"):
        _, cache = transformer.prefill(cfg, params, prompt, aux,
                                       cache_len=total)
        last = prompt[:, -1:]

    def step(carry, k):
        cache, tok = carry
        logits, cache = transformer.decode_step(cfg, params, cache, tok)
        logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
        nxt = jax.random.categorical(k, logits, axis=-1)      # (B,)
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                 nxt[:, None], axis=-1)[:, 0]
        return (cache, nxt[:, None]), (nxt, lp)

    with jax.named_scope("generate/decode"):
        keys = jax.random.split(key, max_new)
        (_, _), (new_toks, new_lps) = jax.lax.scan(step, (cache, last),
                                                   keys)
        new_toks = jnp.moveaxis(new_toks, 0, 1)               # (B, max_new)
        new_lps = jnp.moveaxis(new_lps, 0, 1)
        tokens = jnp.concatenate([prompt, new_toks], axis=1)
        logprobs = jnp.concatenate(
            [jnp.zeros((b, p), jnp.float32), new_lps], axis=1)
        mask = jnp.concatenate([jnp.zeros((b, p), jnp.float32),
                                jnp.ones((b, max_new), jnp.float32)], axis=1)
    return tokens, logprobs, mask


def generate_stacked(cfg: ModelConfig, params, prompts: jnp.ndarray, keys,
                     max_new: int = 32, temperature: float = 1.0,
                     aux: Optional[dict] = None):
    """Multi-client batched generation: one dispatch for a (C, B, P) block.

    ``params`` is a stacked pytree with a leading client axis, ``keys`` is
    (C, 2) — one PRNG key per client so every client's rollout matches the
    per-client ``generate`` call with the same key.  Returns stacked
    (C, B, S) tokens / logprobs / mask.
    """

    def one(p, prompt, key):
        return generate(cfg, p, prompt, key, max_new=max_new,
                        temperature=temperature, aux=aux)

    return jax.vmap(one)(params, prompts, keys)
