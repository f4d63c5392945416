"""The dropless expert layer on the program's path against the plain
float32 reference (``models/moe_reference.py``), at a small size on
seeded random weights: forward, prefill + decode through the cache,
per-objective LoRA gradients, skewed routing, the jitted init, the
router loss kept out of FIRM's objectives, the round's load counters,
and the grouped layout against the einsum layout, batched and not."""
import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

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


# ------------------------------------------------- grouped vs einsum layout
def _layer_params(cfg, key=KEY):
    """One expert layer's float32 parameters and the same stacked as
    the reference reads them (one layer)."""
    p = moe_lib.init_moe(key, cfg, dtype=jnp.float32)
    return p, jax.tree_util.tree_map(lambda a: a[None], p)


def _force(monkeypatch, form, tile=8):
    """Steer the layout choice at a small size: the grouped layout on
    any shape (with a small row tile), or the einsum layout on any."""
    if form == "grouped":
        monkeypatch.setattr(moe_lib, "GROUPED_MIN_ROWS", 1)
        monkeypatch.setattr(moe_lib, "_row_tile", lambda n, e: tile)
    else:
        monkeypatch.setattr(moe_lib, "GROUPED_MIN_ROWS", 10 ** 9)


# tokens; a zero router ties every expert, so each token takes experts 0
# and 1; three tokens route 6 pairs, leaving at least two experts empty
# in the grouped layout; 37 tokens route 74 pairs, not a multiple of the
# row tile
LAYOUT_CASES = {"ragged_rows": (37, False), "empty_expert": (3, False),
                "same_two_experts": (24, True)}


@pytest.mark.parametrize("case", sorted(LAYOUT_CASES))
def test_grouped_layout_matches_einsum_layout_and_reference(case,
                                                            monkeypatch):
    """Forward output and input gradient (through the experts and the
    router's gates) of the grouped layout equal the einsum layout's and
    the float32 reference's, routed as the program routed."""
    cfg = _cfg()
    t, zero_router = LAYOUT_CASES[case]
    p, stacked = _layer_params(cfg)
    if zero_router:
        p["router"]["w"] = jnp.zeros_like(p["router"]["w"])
        stacked["router"]["w"] = jnp.zeros_like(stacked["router"]["w"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, t, cfg.d_model))
    r = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def program(form):
        _force(monkeypatch, form)

        def loss(x_):
            y, _, counts = moe_lib.moe_ffn(p, cfg, x_)
            return jnp.sum(y * r), (y, counts)
        (_, (y, counts)), dx = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(x)
        return y, dx, np.asarray(counts)

    y_g, dx_g, counts = program("grouped")
    y_e, dx_e, counts_e = program("einsum")
    np.testing.assert_array_equal(counts, counts_e)
    if case == "empty_expert":
        assert (counts == 0).sum() >= 2
    if case == "same_two_experts":
        assert (counts[:2] == t).all() and (counts[2:] == 0).all()
    assert _rel(y_g, y_e) < 1e-5 and _rel(dx_g, dx_e) < 1e-5

    probs = jax.nn.softmax(x[0] @ p["router"]["w"], -1)
    ids = jax.lax.top_k(probs, cfg.moe.top_k)[1]

    def ref_loss(x_):
        return jnp.sum(R.moe_layer(stacked, cfg, x_, 0, ids=ids)[0] * r)
    with jax.default_matmul_precision("highest"):
        y_r = R.moe_layer(stacked, cfg, x, 0, ids=ids)[0]
        dx_r = jax.grad(ref_loss)(x)
    assert _rel(y_g, y_r) < 1e-4 and _rel(dx_g, dx_r) < 1e-4


def _pallas_calls(closed):
    """(operand shapes, output shapes) of every Pallas call in a jaxpr,
    sub-jaxprs included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(([tuple(v.aval.shape) for v in eqn.invars],
                              [tuple(v.aval.shape) for v in eqn.outvars]))
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (list, tuple))
                            else [param]):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)
    walk(closed.jaxpr)
    return found


def _pairs_setup(c=3, t=20, m=2):
    cfg = _cfg()
    w = (moe_lib.init_moe(KEY, cfg, dtype=jnp.float32)["experts"], None)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k1, (c, t, cfg.d_model))
    ids = jnp.argsort(jax.random.normal(k2, (c, t, 8)), -1)[..., :2]
    cts = jax.random.normal(k3, (c, m, t, 2, cfg.d_model))
    return w, x, ids.astype(jnp.int32), cts


def _objective_grads(w):
    """Per client: the vjp of its pairs, pulled for M cotangents under
    an inner vmap (``ppo.per_objective_grads``' batched pulls)."""
    def one(x, ids, cts):
        _, vjp = jax.vjp(lambda x_: moe_lib.expert_pairs(x_, ids, w), x)
        return jax.vmap(lambda ct: vjp(ct)[0])(cts)
    return one


@pytest.mark.parametrize("batching", ["clients", "clients_objectives"])
def test_a_batched_call_equals_one_call_on_the_folded_rows(batching,
                                                           monkeypatch):
    """Under ``vmap`` over clients, and under nested ``vmap`` (clients,
    then the M objectives' cotangents), the grouped layout computes what
    one unbatched call computes on every client's (and objective's)
    rows folded into one row axis."""
    _force(monkeypatch, "grouped")
    w, x, ids, cts = _pairs_setup()
    c, t, d = x.shape
    m = cts.shape[1]
    if batching == "clients":
        got = jax.jit(jax.vmap(moe_lib.expert_pairs, (0, 0, None)))(
            x, ids, w)
        want = jax.jit(moe_lib.expert_pairs)(
            x.reshape(c * t, d), ids.reshape(c * t, 2), w)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(want).reshape(got.shape),
                                   rtol=1e-6, atol=1e-6)
        return
    got = jax.jit(jax.vmap(_objective_grads(w)))(x, ids, cts)
    rows = lambda a: jnp.broadcast_to(  # noqa: E731
        a[:, None], (c, m) + a.shape[1:]).reshape((c * m * t,) + a.shape[2:])
    _, vjp = jax.vjp(lambda x_: moe_lib.expert_pairs(x_, rows(ids), w),
                     rows(x))
    want = vjp(cts.reshape((c * m * t,) + cts.shape[3:]))[0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(got.shape),
                               rtol=1e-5, atol=1e-6)


def test_the_fold_reaches_the_kernels_and_the_backward_forms_no_weight_grad(
        monkeypatch):
    """Lowered under ``vmap`` over C clients (and M objectives inside):
    each grouped matmul takes all C*T*k pairs (C*M*T*k in the backward
    pass) as the rows of one 2-D operand, with no client axis, and the
    VJP of the forward pass holds the three forward and three input-
    gradient matmuls only: no grouped weight gradient (an (E, ., .)
    output) is formed."""
    _force(monkeypatch, "grouped")
    w, x, ids, cts = _pairs_setup()
    c, t, _ = x.shape
    m, e, tile = cts.shape[1], 8, 8
    padded = lambda n: -(-n // tile) * tile + e * tile  # noqa: E731

    fwd = _pallas_calls(jax.make_jaxpr(
        jax.vmap(moe_lib.expert_pairs, (0, 0, None)))(x, ids, w))
    assert [ins[-2][0] for ins, _ in fwd] == [padded(c * t * 2)] * 3
    grads = _pallas_calls(jax.make_jaxpr(jax.vmap(_objective_grads(w)))(
        x, ids, cts))
    assert len(grads) == 6
    assert [ins[-2][0] for ins, _ in grads] == (
        [padded(c * t * 2)] * 3 + [padded(c * m * t * 2)] * 3)
    for ins, outs in fwd + grads:
        assert len(ins[-2]) == 2                   # rows x features
        assert [len(o) for o in outs] == [2]       # no weight gradient


@pytest.mark.parametrize("form,want", [("einsum", 4.0), ("grouped", None)])
def test_a_round_reports_the_rows_per_routed_pair(form, want, monkeypatch):
    """``round/moe_rows_per_pair``: E/k = 4 where the einsum layout runs
    the policy forward; in the grouped layout each expert's rows rounded
    up to the row tile, at most 1 + E * (tile - 1) / pairs."""
    from repro.fed.engine import EngineConfig, FederatedTrainer
    _force(monkeypatch, form)
    cfg = dataclasses.replace(_cfg(), name=f"mixtral-rows-{form}")
    fc = FIRMConfig(n_objectives=2, n_clients=2, local_steps=1,
                    batch_size=2)
    ec = EngineConfig(algorithm="firm", max_new=6, prompt_len=4, seed=0,
                      uplink_codec="int8+ef")
    tr = FederatedTrainer(cfg, fc, ec)
    summary = tr.run_round()
    got = summary["moe_rows_per_pair"]
    assert tr.obs.values("round/moe_rows_per_pair") == [got]
    if want is not None:
        assert got == want
        return
    pairs = 2 * 2 * (4 + 6) * 2                    # C * B * S * k
    assert 1.0 <= got <= 1 + 8 * (8 - 1) / pairs
