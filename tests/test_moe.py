"""The dropless expert layer on the program's path against the plain
float32 reference (``models/moe_reference.py``), at a small size on
seeded random weights: forward, prefill + decode through the cache,
per-objective LoRA gradients, skewed routing, the jitted init, the
router loss kept out of FIRM's objectives, and the round's load
counter."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import FIRMConfig, MoEConfig
from repro.models import moe as moe_lib
from repro.models import moe_reference as R
from repro.models import transformer as T
from repro.models.common import split_trainable
from repro.rlhf import local as local_lib
from repro.rlhf import ppo

KEY = jax.random.PRNGKey(7)


def _cfg(**kw):
    """Mixtral's block at a small size: 8 experts, top-2, GQA 4:2."""
    cfg = get_config("mixtral-8x7b").reduced(n_layers=2, d_model=64,
                                             vocab=128)
    kw.setdefault("moe", MoEConfig(n_experts=8, top_k=2))
    return dataclasses.replace(cfg, n_kv_heads=2, **kw)


def _params(cfg, key=KEY):
    """float32 weights with nonzero LoRA B factors, so the adapters act
    on the forward and every factor has a gradient."""
    params = T.init_params(cfg, key, dtype=jnp.float32)

    def perturb(path, x):
        if getattr(path[-1], "key", None) == "lora_B":
            k = jax.random.fold_in(key, x.size + len(jax.tree_util.keystr(
                path)))
            return 0.05 * jax.random.normal(k, x.shape, x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(perturb, params)


def _tokens(cfg, b=2, s=24, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0,
                              cfg.vocab)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


def test_forward_matches_the_reference():
    cfg = _cfg()
    params, tokens = _params(cfg), _tokens(cfg)
    prog = T.forward_seq(cfg, params, tokens)
    ref = R.forward(cfg, params, tokens)
    assert _rel(prog["logits"], ref["logits"]) < 1e-4
    assert _rel(prog["hidden"], ref["hidden"]) < 1e-4
    # the program counts the reference's routes, per layer and expert
    want = np.stack([np.bincount(ids.ravel(), minlength=8)
                     for ids in ref["routes"]])
    np.testing.assert_array_equal(np.asarray(prog["moe_counts"]), want)


def test_prefill_then_decode_matches_the_full_reference_forward():
    cfg = _cfg()
    params, tokens = _params(cfg), _tokens(cfg, s=20)
    s = 12
    ref = np.asarray(R.forward(cfg, params, tokens)["logits"])
    logits, cache = T.prefill(cfg, params, tokens[:, :s], cache_len=20,
                              cache_dtype=jnp.float32)
    assert _rel(logits[:, -1], ref[:, s - 1]) < 1e-4
    for t in range(s, 20):
        step, cache = T.decode_step(cfg, params, cache, tokens[:, t:t + 1])
        assert _rel(step, ref[:, t]) < 1e-4, t


def _batch(cfg, params, tokens, m=2, seed=3):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    b, s = tokens.shape
    mask = jnp.concatenate([jnp.zeros((b, s // 2)), jnp.ones((b, s // 2))],
                           axis=1).astype(jnp.float32)
    lp = R.token_logprobs(R.forward(cfg, params, tokens)["logits"], tokens)
    old = lp + 0.05 * jax.random.normal(k1, lp.shape)
    ref_lp = lp + 0.05 * jax.random.normal(k2, lp.shape)
    rewards = jax.random.uniform(k3, (b, m))
    return ppo.PPOBatch(tokens, mask, old, ref_lp, rewards)


def test_per_objective_lora_gradients_match_the_reference():
    cfg = _cfg()
    fc = FIRMConfig(n_objectives=2, batch_size=2)
    params, tokens = _params(cfg), _tokens(cfg)
    trainable, frozen = split_trainable(params)
    critic = {"w": 0.1 * jax.random.normal(KEY, (2, cfg.d_model))}
    batch = _batch(cfg, params, tokens)
    kl_coef = jnp.float32(0.1)
    grads, losses, _ = ppo.per_objective_grads(cfg, fc, trainable, frozen,
                                               critic, batch, kl_coef)
    ref_losses, ref_grads = R.lora_grads(cfg, fc, trainable, frozen,
                                         critic["w"], R.Batch(*batch),
                                         kl_coef)
    np.testing.assert_allclose(np.asarray(losses), np.asarray(ref_losses),
                               rtol=1e-4, atol=1e-6)
    for g, rg in zip(grads, ref_grads):
        got = jax.tree_util.tree_leaves(g)
        want = jax.tree_util.tree_leaves(rg)
        assert len(got) == len(want) == 8          # A and B of q/k/v/o
        flat = np.concatenate([np.ravel(x) for x in got])
        ref_flat = np.concatenate([np.ravel(x) for x in want])
        assert np.linalg.norm(flat - ref_flat) < 1e-4 * np.linalg.norm(
            ref_flat)


def test_skewed_routing_drops_no_token():
    """Every token picks the same two experts (a zero router ties all
    eight; the top-2 takes the lowest indices): a capacity of
    S*k/E*1.25 would drop most of them, the dropless layer computes
    every pair and matches the reference."""
    cfg = _cfg()
    params, tokens = _params(cfg), _tokens(cfg, s=32)
    router = params["slots"]["0"]["moe"]["router"]
    router["w"] = jnp.zeros_like(router["w"])
    prog = T.forward_seq(cfg, params, tokens)
    ref = R.forward(cfg, params, tokens)
    for ids in ref["routes"]:
        assert (np.sort(ids, -1) == [0, 1]).all()
    counts = np.asarray(prog["moe_counts"])
    assert (counts[:, :2] == tokens.size).all() and (counts[:, 2:] == 0).all()
    assert float(moe_lib.max_load(prog["moe_counts"])) == 8 / 2
    assert _rel(prog["logits"], ref["logits"]) < 1e-4


def test_jitted_init_matches_the_eager_draw():
    cfg = _cfg()
    jitted = T.init_params(cfg, KEY)
    eager = T._init_params(cfg, KEY)
    assert jax.tree_util.tree_structure(jitted) == \
        jax.tree_util.tree_structure(eager)
    for a, b in zip(jax.tree_util.tree_leaves(jitted),
                    jax.tree_util.tree_leaves(eager)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2 ** -7, atol=1e-6)


def test_a_configuration_file_may_give_moe_as_a_dict():
    cfg = dataclasses.replace(_cfg(), moe={"n_experts": 8, "top_k": 2})
    assert cfg.moe == MoEConfig(n_experts=8, top_k=2)
    hash(cfg)                                  # a static jit argument


def test_router_loss_stays_out_of_the_objectives_and_lambda():
    """Under LoRA the router is frozen: its load-balance loss is reported
    but enters neither the M losses nor MGDA's lambda."""
    fc = FIRMConfig(n_objectives=2, batch_size=2, beta=0.05)
    tokens = _tokens(_cfg(), s=16)
    out = {}
    for w in (0.0, 100.0):
        cfg = _cfg(moe=MoEConfig(n_experts=8, top_k=2, router_aux_weight=w))
        params = _params(cfg)
        trainable, frozen = split_trainable(params)
        assert not ppo.router_trains(trainable)
        state = local_lib.init_client_state(trainable, 2, cfg.d_model)
        state = state._replace(lam=jnp.asarray([0.3, 0.7]))
        _, m = local_lib.firm_local_step(cfg, fc, state, frozen,
                                         _batch(cfg, params, tokens))
        out[w] = m
    assert float(out[100.0]["aux_loss"]) > 0 == float(out[0.0]["aux_loss"])
    for k in ("losses", "lam", "lam_star"):
        np.testing.assert_array_equal(np.asarray(out[0.0][k]),
                                      np.asarray(out[100.0][k]))
    # with every parameter trainable the router trains, and its loss counts
    assert ppo.router_trains(_params(_cfg()))


def test_a_federated_round_reports_the_expert_load():
    from repro.fed.engine import EngineConfig, FederatedTrainer
    cfg = get_config("mixtral-8x7b").reduced(n_layers=2, d_model=64,
                                             vocab=256)
    fc = FIRMConfig(n_objectives=2, n_clients=2, local_steps=1,
                    batch_size=2)
    ec = EngineConfig(algorithm="firm", max_new=6, prompt_len=4, seed=0,
                      uplink_codec="int8+ef")
    tr = FederatedTrainer(cfg, fc, ec)
    summary = tr.run_round()
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    assert 1.0 <= summary["moe_max_load"] <= e / k
    assert tr.obs.values("round/moe_max_load") == [summary["moe_max_load"]]


def test_the_float8_control_rounds_as_a_float8_cast():
    """The reference's control rounds values and cotangents as
    ``astype(float8_e4m3fn)`` does, subnormals and ties included."""
    x = jnp.concatenate([
        jax.random.normal(KEY, (4096,)) * s for s in (1e-3, 0.1, 1, 30)]
        + [jnp.asarray([0.0, 448.0, -448.0, 2 ** -9, 3 * 2 ** -10, 1.0625,
                        240.0, 232.0])])

    def cast(v):
        return v.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    np.testing.assert_array_equal(np.asarray(R.round_e4m3(x)),
                                  np.asarray(cast(x)))
    w = jax.random.normal(jax.random.PRNGKey(1), x.shape) * 1e-2

    def loss(v, rnd):
        return jnp.sum(jnp.sin(1.7 * rnd(v)) * w)

    np.testing.assert_array_equal(
        np.asarray(jax.grad(loss)(x, R.round_e4m3)),
        np.asarray(jax.grad(loss)(x, cast)))
