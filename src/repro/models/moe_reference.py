"""A plain float32 reference of the expert-layer decoder (Mixtral,
arXiv:2401.04088), of FIRM's per-objective PPO losses and of their LoRA
gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a Python loop over the
layers, full causal attention with the key/value heads repeated, and the
expert layer as the published sum over experts, walked one expert at a
time, of each expert's output weighted by its gate (zero for a token
not routed to it).  No scan, cache, vmap, remat or kernel, and nothing
of ``transformer``, ``moe``, ``attention`` or ``ppo``; ``forward`` and
``lora_grads`` are jitted whole.  It reads the program's parameter tree
(bfloat16 base stacked over layers, float32 adapters) and upcasts each
base matrix where it is used; the gradient of such a product keeps the
bfloat16 weight, not its float32 copy, so a gradient fits beside the
program at published widths.  The compile time grows with the tokens of
a call (at Mixtral's widths a forward over one 64-token row compiles for
a v5e in about 20 s, over eight rows in about 120 s): a caller at those
widths runs it row by row.

``routes`` fixes each token's experts (the program's own, so that the
two are compared under the same routing); the reference's own top-k on
its own router probabilities comes back beside them.

It follows the published model, with the program's two conventions:

* RoPE rotates interleaved pairs (2i, 2i+1) where the published code
  rotates halves: the same map up to a fixed permutation of each head's
  q and k columns, which random weights do not see;
* LoRA (A: din x r, B: r x dout, scale alpha / r) on the attention
  projections, the only trainable parameters.

``control`` (a traced flag) rounds each expert's output to
float8_e4m3fn: the lower-precision control of the on-chip comparison
(``bench/moe_check.py``), in the same compiled program as the
reference.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


class Batch(NamedTuple):
    tokens: jnp.ndarray          # (B, S) int32 prompt + response
    response_mask: jnp.ndarray   # (B, S) 1 on response positions
    old_logprobs: jnp.ndarray    # (B, S) behaviour policy's
    ref_logprobs: jnp.ndarray    # (B, S) frozen reference's
    rewards: jnp.ndarray         # (B, M) terminal scores


@jax.custom_vjp
def _mm(x, w, idx):
    """x @ w[idx] in float32, for a frozen (bfloat16) stack ``w``;
    ``idx`` is a tuple of (traced) integer indices."""
    return x @ jnp.asarray(w[idx], F32)


def _mm_fwd(x, w, idx):
    return _mm(x, w, idx), (w, idx)


def _mm_bwd(res, g):
    # the input's gradient from the stored bfloat16 stack; the frozen
    # weight and the indices get none
    w, idx = res
    return g @ jnp.asarray(w[idx], F32).T, None, None


_mm.defvjp(_mm_fwd, _mm_bwd)


def _round_e4m3(x):
    a = jnp.abs(x)
    exp = (jax.lax.bitcast_convert_type(a, jnp.int32) >> 23) - 127
    # the spacing of e4m3 values at |x|: 2**(exponent - 3), 2**-9 at least
    step = jax.lax.bitcast_convert_type(
        (jnp.maximum(exp - 3, -9) + 127) << 23, F32)
    return jnp.sign(x) * jnp.minimum(jnp.round(a / step) * step, 448.0)


@jax.custom_vjp
def round_e4m3(x):
    """float32 ``x`` rounded to the nearest float8_e4m3fn value (ties to
    even), saturating at +-448, its cotangent rounded the same way:
    ``x.astype(float8_e4m3fn).astype(float32)`` for |x| <= 448, values
    and gradients, in integer and float32 arithmetic.  (The v5e has no
    float8 unit; XLA's own conversion adds about a minute of compile
    time per layer at Mixtral's widths.)  Normal values keep 3 mantissa
    bits, those under 2**-6 the subnormal step 2**-9."""
    return _round_e4m3(x)


round_e4m3.defvjp(lambda x: (_round_e4m3(x), None),
                  lambda _, g: (_round_e4m3(g),))


def _rms_norm(g, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        jnp.asarray(g, F32)


def _rope(x, theta):
    """x: (B, S, H, Dh) at positions 0..S-1, interleaved pairs."""
    s, dh = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _proj(p, x, layer, scale):
    y = _mm(x, p["w"], (layer,))
    if "lora_A" in p:
        y = y + scale * (x @ p["lora_A"][layer]) @ p["lora_B"][layer]
    return y


def _attention(p, cfg, x, layer, scale):
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _rope(_proj(p["wq"], x, layer, scale).reshape(b, s, hq, dh),
              cfg.rope_theta)
    k = _rope(_proj(p["wk"], x, layer, scale).reshape(b, s, hkv, dh),
              cfg.rope_theta)
    v = _proj(p["wv"], x, layer, scale).reshape(b, s, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, hq * dh)
    return _proj(p["wo"], o, layer, scale)


def top_k_ids(probs, k: int):
    """The k most probable experts of each row, ties to the lower index."""
    return jnp.argsort(-probs, axis=-1, stable=True)[..., :k]


def moe_layer(p, cfg, x, layer, ids=None, control=False):
    """The expert layer on (B, S, d): router softmax in float32, top-k,
    gates renormalised over the k, y = sum_e gate_e * expert_e(x).
    ``ids`` (B*S, k) fixes the routes; returns (y, the reference's own
    top-k (B*S, k), router probabilities (B*S, E))."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    probs = jax.nn.softmax(xt @ p["router"]["w"][layer].astype(F32), -1)
    own = top_k_ids(probs, cfg.moe.top_k)
    ids = own if ids is None else jnp.asarray(ids)
    gates = jnp.take_along_axis(probs, ids, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True)
    w = p["experts"]
    y = jnp.zeros_like(xt)
    for e in range(cfg.moe.n_experts):
        gate = (gates * (ids == e)).sum(-1)                     # (B*S,)
        h = (jax.nn.silu(_mm(xt, w["w_gate"], (layer, e)))
             * _mm(xt, w["w_up"], (layer, e)))
        out = _mm(h, w["w_down"], (layer, e))
        out = jnp.where(control, round_e4m3(out), out)
        y = y + gate[:, None] * out
    return y.reshape(b, s, d), own, probs


@functools.partial(jax.jit, static_argnums=(0,))
def forward(cfg, params, tokens, routes=None, control=False):
    """tokens (B, S) -> {logits (B, S, V), hidden (B, S, d), routes (the
    reference's own top-k, (L, B*S, k)), probs (L, B*S, E)} of a stack
    of ``pattern ("moe",)`` blocks; ``routes`` (L, B*S, k) fixes the
    experts each token uses."""
    if tuple(cfg.pattern) != ("moe",) or cfg.sliding_window:
        raise ValueError("the reference covers full-attention expert "
                         "blocks (pattern ('moe',)) only")
    scale = cfg.lora.alpha / cfg.lora.rank
    st = params["slots"]["0"]
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"][jnp.asarray(tokens)], F32)
        ids_all, probs_all = [], []
        for layer in range(cfg.n_layers):
            h = _rms_norm(st["ln1"]["g"][layer], x, cfg.norm_eps)
            x = x + _attention(st["attn"], cfg, h, layer, scale)
            h = _rms_norm(st["ln2"]["g"][layer], x, cfg.norm_eps)
            y, ids, probs = moe_layer(
                st["moe"], cfg, h, layer,
                None if routes is None else routes[layer], control)
            x = x + y
            ids_all.append(ids)
            probs_all.append(probs)
        hidden = _rms_norm(params["final_norm"]["g"], x, cfg.norm_eps)
        logits = _mm(hidden, params["lm_head"]["w"], ())
    return {"logits": logits, "hidden": hidden,
            "routes": jnp.stack(ids_all), "probs": jnp.stack(probs_all)}


def token_logprobs(logits, tokens):
    """log p(tokens[t] | logits[t-1]); position 0 gets 0."""
    lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    got = jnp.take_along_axis(lp, jnp.asarray(tokens)[:, 1:, None],
                              axis=-1)[..., 0]
    return jnp.pad(got, ((0, 0), (1, 0)))


def _ppo_losses(cfg, fc, params, critic_w, batch: Batch, kl_coef,
                routes, control):
    """FIRM's M clipped-PPO losses (paper Alg. 1 lines 6-9) with TD/GAE
    advantages from the M linear critics, the KL to the reference as a
    per-token penalty and the terminal scores at the last response
    position; no router load-balance term (the router does not train)."""
    out = forward(cfg, params, batch.tokens, routes, control)
    mask = jnp.asarray(batch.response_mask, F32)
    lp = token_logprobs(out["logits"], batch.tokens)
    ratio = jnp.exp(jnp.clip(lp - batch.old_logprobs, -20.0, 20.0))
    kl = lp - batch.ref_logprobs
    h = jax.lax.stop_gradient(out["hidden"])
    feats = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1.0)
    values = feats @ jnp.asarray(critic_w, F32).T              # (B, S, M)
    s = mask.shape[1]
    last = jax.nn.one_hot(jnp.argmax(mask * jnp.arange(s), axis=-1), s)
    r_tok = (-kl_coef * jax.lax.stop_gradient(kl)[..., None] * mask[..., None]
             + last[..., None] * batch.rewards[:, None, :])
    adv = [None] * s
    nxt = jnp.zeros_like(values[:, 0])
    for t in reversed(range(s)):
        more = mask[:, t + 1, None] if t + 1 < s else 0.0
        v_next = values[:, t + 1] if t + 1 < s else 0.0
        delta = r_tok[:, t] + fc.gamma * v_next * more - values[:, t]
        nxt = delta + fc.gamma * fc.gae_lambda * more * nxt
        adv[t] = nxt
    adv = jax.lax.stop_gradient(jnp.stack(adv, axis=1))        # (B, S, M)
    n = jnp.maximum(mask.sum(), 1.0)
    mean = (adv * mask[..., None]).sum((0, 1)) / n
    var = (((adv - mean) ** 2) * mask[..., None]).sum((0, 1)) / n
    adv = (adv - mean) / jnp.sqrt(var + 1e-8)
    clipped = jnp.clip(ratio, 1.0 - fc.ppo_clip, 1.0 + fc.ppo_clip)
    pg = -jnp.minimum(ratio[..., None] * adv, clipped[..., None] * adv)
    return (pg * mask[..., None]).sum((0, 1)) / n


def _merge(trainable, frozen):
    return jax.tree_util.tree_map(
        lambda a, b: a if a is not None else b, trainable, frozen,
        is_leaf=lambda x: x is None)


@functools.partial(jax.jit, static_argnums=(0, 1))
def lora_grads(cfg, fc, trainable, frozen, critic_w, batch: Batch, kl_coef,
               routes=None, control=False):
    """(losses (M,), [M gradient trees shaped like ``trainable``]).
    Without ``routes`` the reference's own, held fixed: the top-k is
    piecewise constant, so that leaves the gradient as it is."""
    if routes is None:
        routes = forward(cfg, _merge(trainable, frozen), batch.tokens,
                         control=control)["routes"]

    def losses(tr):
        return _ppo_losses(cfg, fc, _merge(tr, frozen), critic_w, batch,
                           kl_coef, routes, control)

    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(losses, trainable)
        eye = jnp.eye(out.shape[0], dtype=F32)
        grads = [pull(eye[j])[0] for j in range(out.shape[0])]
    return out, grads
