"""Per-architecture smoke tests (deliverable f) + block-level numerics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import list_archs, get_config
from repro.models import ssm, transformer as T, xlstm
from repro.models.attention import chunked_attention, decode_attention
from repro.models.moe import moe_ffn, init_moe
from repro.kernels import ref

KEY = jax.random.PRNGKey(0)

# Architectures whose un-jitted smoke step dominates suite wall-time on
# CPU; they run in the full tier-1 but not in `pytest -m "not slow"`.
SLOW_ARCHS = {"zamba2-1.2b", "llama-3.2-vision-90b", "xlstm-125m",
              "whisper-large-v3", "moonshot-v1-16b-a3b",
              "phi4-mini-3.8b", "mixtral-8x7b"}


def _arch_params(archs):
    return [pytest.param(a, marks=pytest.mark.slow) if a in SLOW_ARCHS
            else a for a in archs]


def make_aux(cfg, b, s):
    aux = {}
    if cfg.family == "vlm":
        aux["vision"] = jnp.ones((b, cfg.n_vision_tokens, cfg.d_model),
                                 jnp.bfloat16)
    if cfg.is_encoder_decoder:
        aux["frames"] = jnp.ones((b, s, cfg.d_model), jnp.bfloat16)
    return aux


@pytest.mark.parametrize("arch", _arch_params(list_archs()))
def test_arch_smoke_forward_and_train_step(arch):
    """Reduced variant: one forward + one FIRM train step, shapes + no NaN."""
    cfg = get_config(arch).reduced(n_layers=2, d_model=128, vocab=256)
    params = T.init_params(cfg, KEY)
    b, s = 2, 32
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    aux = make_aux(cfg, b, s)
    out = T.forward_seq(cfg, params, tokens, aux)
    assert out["logits"].shape == (b, s, cfg.vocab)
    assert not np.isnan(np.asarray(out["logits"], np.float32)).any()

    # one full FIRM local step (PPO x2 -> MGDA -> Adam) on the same arch
    from repro.configs.base import FIRMConfig
    from repro.models.common import split_trainable
    from repro.rlhf import local as local_lib, ppo
    fc = FIRMConfig(batch_size=b)
    trainable, frozen = split_trainable(params)
    state = local_lib.init_client_state(trainable, 2, cfg.d_model)
    mask = jnp.concatenate([jnp.zeros((b, s // 2)), jnp.ones((b, s // 2))],
                           axis=1).astype(jnp.float32)
    lp = -jnp.ones((b, s), jnp.float32)
    batch = ppo.PPOBatch(tokens, mask, lp, lp,
                         jax.random.uniform(KEY, (b, 2)))
    new_state, metrics = local_lib.firm_local_step(cfg, fc, state, frozen,
                                                   batch, aux or None)
    assert metrics["lam"].shape == (2,)
    assert not np.isnan(float(metrics["losses"].sum()))
    assert abs(float(metrics["lam"].sum()) - 1.0) < 1e-4


@pytest.mark.parametrize("arch", _arch_params(
    ["llama-3.2-1b", "mixtral-8x7b", "zamba2-1.2b", "xlstm-125m",
     "whisper-large-v3", "llama-3.2-vision-90b"]))
def test_prefill_decode_consistency(arch):
    """decode logits after prefill(S) match the teacher-forced forward at
    position S (same params, same tokens)."""
    cfg = get_config(arch).reduced(n_layers=2, d_model=64, vocab=128)
    params = T.init_params(cfg, KEY, dtype=jnp.float32)
    b, s = 2, 16
    tokens = jax.random.randint(KEY, (b, s + 1), 0, cfg.vocab)
    aux = make_aux(cfg, b, s + 1)
    full = T.forward_seq(cfg, params, tokens, aux)
    _, cache = T.prefill(cfg, params, tokens[:, :s], aux,
                         cache_len=s + 4, cache_dtype=jnp.float32)
    logits, _ = T.decode_step(cfg, params, cache, tokens[:, s:s + 1])
    want = np.asarray(full["logits"][:, s], np.float32)
    got = np.asarray(logits, np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_chunked_attention_matches_oracle():
    b, s, hq, hkv, dh = 2, 96, 4, 2, 16
    q = jax.random.normal(KEY, (b, s, hq, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, dh))
    for block in (16, 32, 96, 200):
        got = chunked_attention(q, k, v, causal=True, block=block)
        want = ref.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_swa_ring_cache_decode():
    """Decode with a ring-buffer SWA cache == full-cache attention with a
    sliding-window mask."""
    b, hq, hkv, dh, w = 1, 2, 2, 8, 8
    total = 20
    k_full = jax.random.normal(KEY, (b, total, hkv, dh))
    v_full = jax.random.normal(jax.random.fold_in(KEY, 1),
                               (b, total, hkv, dh))
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (b, 1, hq, dh))
    pos = 15  # current position
    # ring cache of size w holding positions (pos-w, pos]
    ring_k = jnp.zeros((b, w, hkv, dh))
    ring_v = jnp.zeros((b, w, hkv, dh))
    for p in range(pos + 1):
        ring_k = ring_k.at[:, p % w].set(k_full[:, p])
        ring_v = ring_v.at[:, p % w].set(v_full[:, p])
    cache_positions = jnp.asarray([pos - ((pos - j) % w) for j in range(w)])
    got = decode_attention(q, ring_k, ring_v, jnp.asarray(pos),
                           sliding_window=w, cache_positions=cache_positions)
    want = decode_attention(q, k_full[:, :pos + 1], v_full[:, :pos + 1],
                            jnp.asarray(pos), sliding_window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_moe_topk1_matches_dense_expert():
    """With top_k=1, each token's output equals its selected expert's
    FFN output."""
    import dataclasses
    cfg = get_config("mixtral-8x7b").reduced(n_layers=2, d_model=32,
                                             vocab=64)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, top_k=1))
    p = init_moe(KEY, cfg, dtype=jnp.float32)
    x = jax.random.normal(KEY, (2, 8, cfg.d_model))
    y, aux, _ = moe_ffn(p, cfg, x)
    # manual: route each token and apply its expert
    xf = x.reshape(-1, cfg.d_model)
    logits = xf @ p["router"]["w"]
    eid = jnp.argmax(logits, -1)
    w = p["experts"]
    for t in range(xf.shape[0]):
        e = int(eid[t])
        g = jax.nn.silu(xf[t] @ w["w_gate"][e]) * (xf[t] @ w["w_up"][e])
        want = g @ w["w_down"][e]
        np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)[t]),
                                   np.asarray(want), rtol=1e-3, atol=1e-3)
    assert float(aux) >= 0.0


def test_moe_grad_flows_to_router_and_experts():
    cfg = get_config("mixtral-8x7b").reduced(n_layers=2, d_model=32,
                                             vocab=64)
    p = init_moe(KEY, cfg, dtype=jnp.float32)
    x = jax.random.normal(KEY, (1, 8, cfg.d_model))

    def loss(p):
        y, aux, _ = moe_ffn(p, cfg, x)
        return (y ** 2).sum() + aux

    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["router"]["w"]).sum()) > 0
    assert float(jnp.abs(g["experts"]["w_gate"]).sum()) > 0


@pytest.mark.slow
def test_mamba2_chunked_matches_stepwise():
    """The chunked SSD forward == exact per-token recurrence (decode)."""
    cfg = get_config("zamba2-1.2b").reduced(n_layers=2, d_model=64,
                                            vocab=64)
    p = ssm.init_mamba2(KEY, cfg, dtype=jnp.float32)
    b, s = 1, 40
    x = 0.5 * jax.random.normal(KEY, (b, s, cfg.d_model))
    y_seq, state = ssm.mamba2_seq(p, cfg, x, return_state=True)
    cache = ssm.init_mamba2_cache(cfg, b)
    ys = []
    for t in range(s):
        y_t, cache = ssm.mamba2_decode(p, cfg, x[:, t:t + 1], cache)
        ys.append(y_t)
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_step),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(state["state"]),
                               np.asarray(cache["state"]),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kind", ["mlstm", "slstm"])
def test_xlstm_seq_matches_stepwise(kind):
    cfg = get_config("xlstm-125m").reduced(n_layers=3, d_model=64, vocab=64)
    init = {"mlstm": xlstm.init_mlstm, "slstm": xlstm.init_slstm}[kind]
    seqf = {"mlstm": xlstm.mlstm_seq, "slstm": xlstm.slstm_seq}[kind]
    decf = {"mlstm": xlstm.mlstm_decode, "slstm": xlstm.slstm_decode}[kind]
    cachef = {"mlstm": xlstm.init_mlstm_cache,
              "slstm": xlstm.init_slstm_cache}[kind]
    p = init(KEY, cfg, dtype=jnp.float32)
    b, s = 1, 12
    x = 0.5 * jax.random.normal(KEY, (b, s, cfg.d_model))
    y_seq = seqf(p, cfg, x)
    cache = cachef(cfg, b)
    ys = []
    for t in range(s):
        y_t, cache = decf(p, cfg, x[:, t:t + 1], cache)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(y_seq),
                               np.asarray(jnp.concatenate(ys, 1)),
                               rtol=1e-4, atol=1e-4)


def test_lora_zero_init_is_identity():
    """Fresh LoRA adapters leave the forward unchanged (B=0 init)."""
    from repro.models.common import linear, init_linear
    p = init_linear(KEY, 16, 24, lora_rank=4, dtype=jnp.float32)
    x = jax.random.normal(KEY, (3, 16))
    np.testing.assert_allclose(np.asarray(linear(p, x)),
                               np.asarray(x @ p["w"]), rtol=1e-6)


def test_split_trainable_roundtrip():
    from repro.models.common import merge_trainable, split_trainable
    cfg = get_config("llama-3.2-1b").reduced()
    params = T.init_params(cfg, KEY)
    tr, fz = split_trainable(params)
    # only lora leaves trainable (stacked over periods -> 8 leaves)
    n_tr = len(jax.tree_util.tree_leaves(tr))
    assert n_tr == 2 * 4  # (A+B) x 4 projections, stacked over layers
    merged = merge_trainable(tr, fz)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape
    # xlstm has no adapters -> full-param mode
    cfg2 = get_config("xlstm-125m").reduced()
    p2 = T.init_params(cfg2, KEY)
    tr2, _ = split_trainable(p2)
    assert len(jax.tree_util.tree_leaves(tr2)) == \
        len(jax.tree_util.tree_leaves(p2))


def test_param_count_close_to_actual():
    for arch in ("llama-3.2-1b", "mixtral-8x7b", "zamba2-1.2b"):
        cfg = get_config(arch).reduced(n_layers=4, d_model=128, vocab=256)
        params = T.init_params(cfg, KEY)
        actual = sum(x.size for x in jax.tree_util.tree_leaves(params)
                     if x.dtype != jnp.float32)  # exclude lora/f32 extras
        est = cfg.param_count()
        assert 0.5 * actual < est < 2.0 * actual, (arch, est, actual)
