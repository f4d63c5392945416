"""Model assembly: periodic layer patterns -> scan-over-periods stacks.

Parameters live in a nested dict:

  params['embed']            (V, d) token embedding
  params['slots'][str(i)]    pattern-slot i block params, stacked over
                             n_periods on the leading axis
  params['shared']           single param set for 'shared_attn' slots
  params['encoder']          whisper encoder {'slots': {...}, 'final_norm'}
  params['final_norm'], params['lm_head']

Three entry points:
  forward_seq(cfg, params, tokens, aux)            train / teacher-forced
  prefill(cfg, params, tokens, aux, cache_len)     build decode cache
  decode_step(cfg, params, cache, token)           one token w/ cache

``aux`` carries the modality stubs: {'vision': (B, Nv, d)} for VLMs,
{'frames': (B, Te, d)} for audio enc-dec (DESIGN §4 carve-out).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common, moe as moe_lib, ssm, xlstm
from repro.models.attention import chunked_attention, decode_attention

ATTN_KINDS = ("attn", "swa", "moe", "moe_swa", "enc_attn", "shared_attn",
              "cross")


# ================================================================== init
def _init_attn(key, cfg: ModelConfig, dtype, lora: bool):
    rank = cfg.lora.rank if (lora and cfg.lora) else 0
    dq = cfg.n_heads * cfg.head_dim
    dkv = cfg.n_kv_heads * cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": common.init_linear(ks[0], cfg.d_model, dq, lora_rank=rank,
                                 dtype=dtype),
        "wk": common.init_linear(ks[1], cfg.d_model, dkv, lora_rank=rank,
                                 dtype=dtype),
        "wv": common.init_linear(ks[2], cfg.d_model, dkv, lora_rank=rank,
                                 dtype=dtype),
        "wo": common.init_linear(ks[3], dq, cfg.d_model, lora_rank=rank,
                                 dtype=dtype),
    }


def init_block(key, kind: str, cfg: ModelConfig, dtype=jnp.bfloat16):
    d = cfg.d_model
    if kind == "mamba2":
        return ssm.init_mamba2(key, cfg, dtype)
    if kind == "mlstm":
        return xlstm.init_mlstm(key, cfg, dtype)
    if kind == "slstm":
        return xlstm.init_slstm(key, cfg, dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"ln1": common.init_norm(d, dtype),
         "attn": _init_attn(k1, cfg, dtype, lora=True),
         "ln2": common.init_norm(d, dtype)}
    if kind in ("moe", "moe_swa"):
        p["moe"] = moe_lib.init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = common.init_swiglu(k2, d, cfg.d_ff, dtype)
    if kind == "cross":
        p["lnx"] = common.init_norm(d, dtype)
        p["cross"] = _init_attn(k3, cfg, dtype, lora=True)
    return p


def _init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    keys = jax.random.split(key, 6)
    params = {
        "embed": common._normal(keys[0], (cfg.vocab, cfg.d_model),
                                0.02, dtype),
        "final_norm": common.init_norm(cfg.d_model, dtype),
        "lm_head": common.init_linear(keys[1], cfg.d_model, cfg.vocab,
                                      dtype=dtype),
        "slots": {},
    }
    slot_keys = jax.random.split(keys[2], len(cfg.pattern))
    for i, kind in enumerate(cfg.pattern):
        if kind == "shared_attn":
            continue
        per_keys = jax.random.split(slot_keys[i], cfg.n_periods)
        params["slots"][str(i)] = jax.vmap(
            lambda k: init_block(k, kind, cfg, dtype))(per_keys)
    if "shared_attn" in cfg.pattern:
        params["shared"] = init_block(keys[3], "shared_attn", cfg, dtype)
    if cfg.encoder_layers:
        enc_keys = jax.random.split(keys[4], cfg.encoder_layers)
        params["encoder"] = {
            "slots": {"0": jax.vmap(
                lambda k: init_block(k, "enc_attn", cfg, dtype))(enc_keys)},
            "final_norm": common.init_norm(cfg.d_model, dtype),
        }
    return params


_init_params_jit = jax.jit(_init_params, static_argnames=("cfg", "dtype"))


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    """Every parameter drawn from ``key`` by one jitted program.

    Each weight is drawn as float32 normals and rounded to ``dtype``;
    inside one program XLA fuses the draw into the rounding, so neither
    the float32 draw nor its random bits of a stacked weight are ever
    held (eager, a stacked expert matrix of mixtral-8x7b would take 7.5
    GB of float32 next to its 3.8 GB).  The values are those of the
    eager ``_init_params`` up to the rounding of fused arithmetic.
    """
    return _init_params_jit(cfg, key, dtype)


# ================================================================ seq mode
def _self_attention(p, cfg: ModelConfig, h, positions, kind):
    b, s, _ = h.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = common.linear(p["wq"], h).reshape(b, s, hq, dh)
    k = common.linear(p["wk"], h).reshape(b, s, hkv, dh)
    v = common.linear(p["wv"], h).reshape(b, s, hkv, dh)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    sw = cfg.sliding_window if kind in ("swa", "moe_swa") else 0
    o = chunked_attention(q, k, v, causal=(kind != "enc_attn"),
                          sliding_window=sw, block=cfg.attn_block,
                          q_positions=positions, kv_positions=positions)
    return common.linear(p["wo"], o.reshape(b, s, hq * dh)), (k, v)


def _cross_attention(p, cfg: ModelConfig, h, cross_states):
    b, s, _ = h.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n = cross_states.shape[1]
    q = common.linear(p["wq"], h).reshape(b, s, hq, dh)
    k = common.linear(p["wk"], cross_states).reshape(b, n, hkv, dh)
    v = common.linear(p["wv"], cross_states).reshape(b, n, hkv, dh)
    o = chunked_attention(q, k, v, causal=False)
    return common.linear(p["wo"], o.reshape(b, s, hq * dh)), (k, v)


def block_seq(kind: str, p, cfg: ModelConfig, x, positions, cross_states,
              collect_kv: bool):
    """Apply one block in sequence mode.  Returns (x, moe, kv_piece):
    ``moe`` is (router aux loss, routed pairs per expert) for an expert
    block, else None."""
    moe = None
    stateful = {"mamba2": ssm.mamba2_seq, "mlstm": xlstm.mlstm_seq,
                "slstm": xlstm.slstm_seq}
    if kind in stateful:
        if collect_kv:
            x2, st = stateful[kind](p, cfg, x, return_state=True)
            return x2, moe, st
        return stateful[kind](p, cfg, x), moe, None
    h = common.rms_norm(p["ln1"], x, cfg.norm_eps)
    attn_out, kv = _self_attention(p["attn"], cfg, h, positions, kind)
    x = x + attn_out
    ckv = None
    if kind == "cross":
        hx = common.rms_norm(p["lnx"], x, cfg.norm_eps)
        cross_out, ckv = _cross_attention(p["cross"], cfg, hx, cross_states)
        x = x + cross_out
    h2 = common.rms_norm(p["ln2"], x, cfg.norm_eps)
    if kind in ("moe", "moe_swa"):
        y, aux, counts = moe_lib.moe_ffn(p["moe"], cfg, h2)
        moe = (aux, counts)
    else:
        y = common.swiglu(p["mlp"], h2)
    x = x + y
    piece = None
    if collect_kv:
        piece = {"k": kv[0], "v": kv[1]}
        if ckv is not None:
            piece["ck"], piece["cv"] = ckv
    return x, moe, piece


def _encoder_forward(cfg: ModelConfig, params, frames):
    enc = params["encoder"]
    frames = frames.astype(params["embed"].dtype)
    positions = jnp.arange(frames.shape[1])
    stacked = enc["slots"]["0"]

    def body(x, p):
        x, _, _ = block_seq("enc_attn", p, cfg, x, positions, None, False)
        return x, None

    x, _ = jax.lax.scan(body, frames, stacked)
    return common.rms_norm(enc["final_norm"], x, cfg.norm_eps)


def _cross_source(cfg: ModelConfig, params, aux):
    if cfg.family == "vlm":
        return aux["vision"].astype(params["embed"].dtype)
    if cfg.is_encoder_decoder:
        return _encoder_forward(cfg, params, aux["frames"])
    return None


def forward_seq(cfg: ModelConfig, params, tokens, aux=None,
                collect_kv: bool = False, last_logit_only: bool = False):
    """tokens: (B, S) int32 -> dict(logits, hidden, aux_loss [, kv]
    [, moe_counts]).

    last_logit_only: compute logits for the final position only (prefill
    path — avoids materialising (B, S, V) at 32k x 200k scale).
    ``moe_counts`` (a model with expert blocks): (L_moe, E) routed pairs
    per expert block, in layer order, and expert.
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.arange(tokens.shape[1])
    cross_states = _cross_source(cfg, params, aux or {})
    shared = params.get("shared")

    def period_body(carry, slot_params):
        x, aux_sum = carry
        pieces, counts = {}, []
        for i, kind in enumerate(cfg.pattern):
            p = shared if kind == "shared_attn" else slot_params[str(i)]
            x, moe, piece = block_seq(kind, p, cfg, x, positions,
                                      cross_states, collect_kv)
            if moe is not None:
                aux_sum = aux_sum + moe[0]
                counts.append(moe[1])
            if collect_kv:
                pieces[str(i)] = piece
        return (x, aux_sum), (pieces if collect_kv else None,
                              jnp.stack(counts) if counts else None)

    xs = {i: v for i, v in params["slots"].items()}
    body = period_body
    if cfg.remat and not collect_kv:
        # activation checkpointing: store only the period-boundary x;
        # recompute block internals in the backward pass (drops train
        # temp memory from O(L * per-layer activations) to O(L * x)).
        # remat_policy='dots' additionally saves MXU outputs (less
        # recompute traffic, more residency — §Perf hillclimb #3).
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(period_body, policy=policy)
    (x, aux_loss), (kv, counts) = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), xs)
    x = common.rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = common.linear(params["lm_head"],
                           x[:, -1:] if last_logit_only else x)
    out = {"logits": logits, "hidden": x, "aux_loss": aux_loss}
    if counts is not None:
        out["moe_counts"] = counts.reshape(-1, counts.shape[-1])
    if collect_kv:
        out["kv"] = kv
        out["cross_states"] = cross_states
    return out


# ============================================================== decode mode
def _attn_cache_len(cfg: ModelConfig, kind: str, cache_len: int) -> int:
    if kind in ("swa", "moe_swa") and cfg.sliding_window:
        return min(cfg.sliding_window, cache_len)
    return cache_len


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16, n_cross: int = 0):
    """Pre-allocated decode cache (one entry per pattern slot)."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    slots = {}
    for i, kind in enumerate(cfg.pattern):
        if kind == "mamba2":
            piece = ssm.init_mamba2_cache(cfg, batch)
        elif kind == "mlstm":
            piece = xlstm.init_mlstm_cache(cfg, batch)
        elif kind == "slstm":
            piece = xlstm.init_slstm_cache(cfg, batch)
        else:
            c = _attn_cache_len(cfg, kind, cache_len)
            piece = {"k": jnp.zeros((batch, c, hkv, dh), dtype),
                     "v": jnp.zeros((batch, c, hkv, dh), dtype)}
            if kind == "cross":
                nc = n_cross or cfg.n_vision_tokens or 1
                piece["ck"] = jnp.zeros((batch, nc, hkv, dh), dtype)
                piece["cv"] = jnp.zeros((batch, nc, hkv, dh), dtype)
        # stack over periods
        slots[str(i)] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_periods,) + a.shape),
            piece)
    return {"slots": slots, "pos": jnp.zeros((), jnp.int32)}


def _ring_positions(pos, c, full_len_reached_len):
    """Absolute position held by each ring slot AFTER writing token `pos`."""
    j = jnp.arange(c)
    p = pos - ((pos - j) % c)
    return jnp.where(p >= 0, p, -1)


def block_decode(kind: str, p, cfg: ModelConfig, x, cache, pos):
    """One-token decode through one block.  Returns (x, new_cache)."""
    if kind == "mamba2":
        return ssm.mamba2_decode(p, cfg, x, cache)
    if kind == "mlstm":
        return xlstm.mlstm_decode(p, cfg, x, cache)
    if kind == "slstm":
        return xlstm.slstm_decode(p, cfg, x, cache)
    b = x.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = common.rms_norm(p["ln1"], x, cfg.norm_eps)
    q = common.linear(p["attn"]["wq"], h).reshape(b, 1, hq, dh)
    k = common.linear(p["attn"]["wk"], h).reshape(b, 1, hkv, dh)
    v = common.linear(p["attn"]["wv"], h).reshape(b, 1, hkv, dh)
    posv = pos[None] if pos.ndim == 0 else pos
    q = common.apply_rope(q, posv, cfg.rope_theta)
    k = common.apply_rope(k, posv, cfg.rope_theta)
    c = cache["k"].shape[1]
    idx = pos % c
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                           (0, idx, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                           (0, idx, 0, 0))
    sw = cfg.sliding_window if kind in ("swa", "moe_swa") else 0
    if sw and c < cfg.sliding_window + 1:
        cache_positions = _ring_positions(pos, c, c)
    else:
        cache_positions = jnp.arange(c)
    o = decode_attention(q, k_cache, v_cache, pos, sliding_window=sw,
                         cache_positions=cache_positions)
    x = x + common.linear(p["attn"]["wo"], o.reshape(b, 1, hq * dh))
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = k_cache, v_cache
    if kind == "cross":
        hx = common.rms_norm(p["lnx"], x, cfg.norm_eps)
        qx = common.linear(p["cross"]["wq"], hx).reshape(b, 1, hq, dh)
        n = cache["ck"].shape[1]
        o = decode_attention(qx, cache["ck"], cache["cv"],
                             jnp.asarray(n, jnp.int32))
        x = x + common.linear(p["cross"]["wo"], o.reshape(b, 1, hq * dh))
    h2 = common.rms_norm(p["ln2"], x, cfg.norm_eps)
    if kind in ("moe", "moe_swa"):
        y, _, _ = moe_lib.moe_ffn(p["moe"], cfg, h2)
    else:
        y = common.swiglu(p["mlp"], h2)
    return x + y, new_cache


def decode_step(cfg: ModelConfig, params, cache, token):
    """token: (B, 1) int32 -> (logits (B, V), new cache)."""
    x = jnp.take(params["embed"], token, axis=0)
    pos = cache["pos"]
    shared = params.get("shared")

    def period_body(x, xs):
        slot_params, slot_caches = xs
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            p = shared if kind == "shared_attn" else slot_params.get(str(i))
            x, new_caches[str(i)] = block_decode(kind, p, cfg, x,
                                                 slot_caches[str(i)], pos)
        return x, new_caches

    xs = (params["slots"], cache["slots"])
    x, new_slots = jax.lax.scan(period_body, x, xs)
    x = common.rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = common.linear(params["lm_head"], x)[:, 0]
    return logits, {"slots": new_slots, "pos": pos + 1}


# ================================================================== prefill
def prefill(cfg: ModelConfig, params, tokens, aux=None,
            cache_len: Optional[int] = None, cache_dtype=jnp.bfloat16):
    """Run the sequence forward AND build a decode cache.

    Returns (logits (B, S, V), cache).  cache_len defaults to S.
    """
    b, s = tokens.shape
    cache_len = cache_len or s
    out = forward_seq(cfg, params, tokens, aux, collect_kv=True)
    n_cross = 0
    if out.get("cross_states") is not None:
        n_cross = out["cross_states"].shape[1]
    cache = init_cache(cfg, b, cache_len, cache_dtype, n_cross=n_cross)

    new_slots = {}
    for i, kind in enumerate(cfg.pattern):
        piece = cache["slots"][str(i)]
        if kind not in ATTN_KINDS:
            # recurrent blocks: exact final states from the seq scan
            new_slots[str(i)] = jax.tree_util.tree_map(
                lambda harvested, init: harvested.astype(init.dtype),
                out["kv"][str(i)], piece)
            continue
        kv = out["kv"][str(i)]
        c = piece["k"].shape[2]
        take = min(s, c)
        ks, vs = kv["k"][:, :, -take:], kv["v"][:, :, -take:]
        if kind in ("swa", "moe_swa") and cfg.sliding_window and c <= s:
            # ring layout: absolute position p lives at slot p % c
            positions = jnp.arange(s - take, s)
            slots_idx = positions % c
            knew = jnp.zeros_like(piece["k"]).at[:, :, slots_idx].set(
                ks.astype(piece["k"].dtype))
            vnew = jnp.zeros_like(piece["v"]).at[:, :, slots_idx].set(
                vs.astype(piece["v"].dtype))
        else:
            knew = jax.lax.dynamic_update_slice(
                piece["k"], ks.astype(piece["k"].dtype), (0, 0, 0, 0, 0))
            vnew = jax.lax.dynamic_update_slice(
                piece["v"], vs.astype(piece["v"].dtype), (0, 0, 0, 0, 0))
        piece = dict(piece)
        piece["k"], piece["v"] = knew, vnew
        if kind == "cross":
            piece["ck"] = kv["ck"].astype(piece["ck"].dtype)
            piece["cv"] = kv["cv"].astype(piece["cv"].dtype)
        new_slots[str(i)] = piece
    cache = {"slots": new_slots, "pos": jnp.asarray(s, jnp.int32)}
    return out["logits"], cache
