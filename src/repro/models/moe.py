"""Mixture-of-Experts FFN: top-k softmax routing, dropless, einsum dispatch.

The router is ``softmax(x W_g)`` in float32; each token takes its top-k
experts and renormalises their gates over those k (Mixtral,
arXiv:2401.04088, eq. 1-2).  Every routed (token, expert) pair is
computed: there is no capacity and no token is dropped.

Tokens move with einsums only (GShard's dispatch / combine form), with
each row's capacity equal to its token count.  A token picks an expert
at most once, so its slot in an expert's buffer is its own position and
no pair can overflow:

  buf = einsum('bse,bsd->besd', dispatch, x)     # tokens -> expert rows
  y   = einsum('bse,besd->bsd', combine,  out)   # expert rows -> tokens

Why einsums: every op in both directions is a dot or elementwise, so
GSPMD partitions forward AND backward cleanly (batch on 'data',
expert/d_ff on 'model'); a sort + scatter form was measured at 40
TB/device/step of involuntary all-reduce on mixtral-8x22b train_4k,
because GSPMD cannot keep the batch dim sharded through batched
scatters.  The expert axis is a batch dim of each expert matmul, so
under the engine's client ``vmap`` (where the frozen experts are
unbatched) one matmul per expert matrix reads each expert once per step
for all clients.  (``jax.lax.ragged_dot`` has no vmap rule over an
unbatched right-hand side in JAX 0.9.0.)

The price is compute: every expert runs on every position of its row,
E/k times the routed pairs (4x at E=8, k=2); a grouped matmul over the
routed pairs, clients folded into the token axis, would remove it.

Scopes: ``moe/route`` (router matmul, top-k, gates, dispatch and
combine weights, the dispatch einsum) and ``moe/experts`` (the three
expert matmuls and the combine), read by ``repro.obs.jitwatch``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common


def init_moe(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    d, dff, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    scale = 1.0 / jnp.sqrt(d)
    return {
        "router": {"w": common._normal(ks[0], (d, e), scale, jnp.float32)},
        "experts": {
            "w_gate": common._normal(ks[1], (e, d, dff), scale, dtype),
            "w_up": common._normal(ks[2], (e, d, dff), scale, dtype),
            "w_down": common._normal(ks[3], (e, dff, d),
                                     1.0 / jnp.sqrt(dff), dtype),
        },
    }


def max_load(counts: jnp.ndarray) -> jnp.ndarray:
    """(L, E) routed pairs per layer and expert -> the largest expert's
    share of its layer's pairs over the uniform share, maximised over
    layers (1 when routing is even, E/k when every token of a layer
    picks the same k experts)."""
    e = counts.shape[-1]
    share = counts.max(-1) / jnp.maximum(counts.sum(-1), 1.0)
    return (share * e).max()


def moe_ffn(p, cfg: ModelConfig, x: jnp.ndarray):
    """x: (B, S, d) -> (y (B, S, d), router load-balance aux loss,
    routed pairs per expert (E,) f32)."""
    moe = cfg.moe
    e, k = moe.n_experts, moe.top_k
    b, s, d = x.shape

    with jax.named_scope("moe/route"):
        logits = x.astype(jnp.float32) @ p["router"]["w"]         # (B,S,E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_ids = jax.lax.top_k(probs, k)                 # (B,S,k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        onehot = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)  # (B,S,k,E)
        counts = onehot.sum(axis=(0, 1, 2))
        # load-balance aux (Switch): E * sum_e mean(route frac) * mean(prob)
        aux = moe.router_aux_weight * e * jnp.sum(
            counts / (b * s * k) * probs.mean(axis=(0, 1)))
        # the k choices of a token are distinct experts: 0/1 entries
        dispatch = onehot.sum(2).astype(x.dtype)                   # (B,S,E)
        combine = (onehot * gate[..., None]).sum(2)                # (B,S,E)
        buf = jnp.einsum("bse,bsd->besd", dispatch, x)

    with jax.named_scope("moe/experts"):
        w = p["experts"]
        g = jnp.einsum("besd,edf->besf", buf, w["w_gate"])
        u = jnp.einsum("besd,edf->besf", buf, w["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
        out = jnp.einsum("besf,efd->besd", h, w["w_down"])         # (B,E,S,d)
        y = jnp.einsum("bse,besd->bsd", combine.astype(out.dtype), out,
                       preferred_element_type=jnp.float32)
    return y.astype(x.dtype), aux, counts
