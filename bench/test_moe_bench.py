"""The sparse-expert cell's files and counts on the CPU: its
configuration through the front door, the FLOP and byte counts by hand,
the numpy expert layer against the program's, and the four readers on a
small context, silent when they have nothing to read.

Nothing here is a device number.
"""
import json
import pathlib

import numpy as np
import pytest

import cell
import decode_bytes
import flops
import layer_time
import moe_bytes
import moe_flops
import moe_reference
import moe_time
import run
from repro.obs.jitwatch import ProgramMap

HERE = pathlib.Path(__file__).resolve().parent
READERS = ["moe_round_mfu", "moe_decode_hbm_share", "expert_ms_per_round",
           "router_ms_per_round"]


def _model():
    return json.loads((HERE / "configs" / "mixtral-8x7b-4L.json")
                      .read_text())["model"]


# ------------------------------------------------------- the configuration
def test_the_configuration_enters_through_the_front_door():
    import jax
    from repro.configs.base import MoEConfig
    from repro.fed import api
    from repro.models import transformer
    wl, conf, cfg = cell.load("mixtral-moe-rounds")
    assert cfg.moe == MoEConfig(n_experts=8, top_k=2, router_aux_weight=0.02)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab, cfg.n_layers) == (4096, 32, 8, 128, 14336,
                                                   32000, 4)
    assert cfg.pattern == ("moe",) and cfg.sliding_window == 0
    hash(cfg)
    spec = cell.run_spec(wl, cfg, seed=2 ** 31 + 3)
    assert api.plan(spec).executor == "vectorized"
    shapes = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # 4 layers of 41.9M attention + 1,409.3M experts, embedding and head
    assert 6_066e6 < n < 6_070e6


# --------------------------------------------------------------- counts
TINY = dict(flops.shapes_of({
    "n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
    "head_dim": 4, "d_ff": 16, "vocab": 32,
    "lora": {"rank": 2, "targets": ["wq", "wk", "wv", "wo"]}})._asdict())


def test_moe_flops_by_hand():
    s = flops.Shapes(**TINY)
    # attention 192, router 8 x 4, two experts of 3 x 8 x 16
    assert moe_flops.layer_matmul_params(s, 4, 2) == 192 + 32 + 768
    per_token = 2 * 2 * (992 + 112)            # two layers, LoRA 112
    head = 2 * 8 * 32
    attn = 4 * 2 * 4 * (1 + 2 + 3) * 2
    assert moe_flops.forward_flops(s, 4, 2, 1, 3) == (
        3 * per_token + 3 * head + attn)
    # two decode steps after 3 tokens attend to 4 and 5 positions
    assert moe_flops.decode_flops(s, 4, 2, 1, 3, 2) == (
        2 * (per_token + head) + 4 * 2 * 4 * (4 + 5) * 2)


def test_moe_flops_reduce_to_the_dense_count_plus_a_router():
    """One expert, always chosen, is the dense SwiGLU plus a d x 1
    router per token and layer."""
    s = flops.Shapes(**TINY)
    router = 2 * 2 * 8                          # 2 layers x 2 x d x 1
    assert moe_flops.forward_flops(s, 1, 1, 2, 5) == (
        flops.forward_flops(s, 2, 5) + 2 * 5 * router)
    assert moe_flops.backward_pull_flops(s, 1, 1, 2, 5) == (
        flops.backward_pull_flops(s, 2, 5) + 2 * 5 * router)
    wl = {"batch_size": 2, "prompt_len": 3, "max_new": 2,
          "n_objectives": 2, "n_clients": 3, "local_steps": 1}
    model = dict(TINY_MODEL, moe={"n_experts": 1, "top_k": 1})
    dense = flops.round_flops(TINY_MODEL, wl)
    assert moe_flops.round_flops(model, wl) > dense


TINY_MODEL = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
              "head_dim": 4, "d_ff": 16, "vocab": 32,
              "lora": {"rank": 2, "targets": ["wq", "wk", "wv", "wo"]}}


def test_moe_bytes_mixtral_by_hand():
    wl = cell.load_workload("mixtral-moe-rounds")
    got = moe_bytes.step_bytes(_model(), wl)
    # 4 layers of q, o 4096x4096, k, v 4096x1024, 8 experts of gate, up
    # 4096x14336 and down 14336x4096, two norms; final norm; 32000 x 4096
    layer = (2 * 4096 * 4096 + 2 * 4096 * 1024 + 8 * 3 * 4096 * 14336
             + 2 * 4096)
    weights = 2 * (4 * layer + 4096 + 32000 * 4096)
    assert got["weights"] == weights == 11_872_051_200     # 11.87 GB
    assert got["router"] == 4 * 4 * 4096 * 8
    # 8 clients x 4 layers x r 16 x (8192 + 5120 + 5120 + 8192), float32
    assert got["adapters"] == 4 * 8 * 4 * 16 * 26624 == 54_525_952
    # 8 rows x 4 layers x K, V x 64 positions x 8 heads x 128, bfloat16
    assert got["cache"] == 2 * 8 * 4 * 2 * 64 * 8 * 128 == 8_388_608
    assert got["embed_rows"] == 2 * 8 * 4096
    # the experts are 95% of the weights a step reads
    experts = 2 * 4 * 8 * 3 * 4096 * 14336
    assert 0.94 < experts / got["weights"] < 0.96


def test_moe_bytes_refuses_a_dense_stack():
    dense = json.loads((HERE / "configs" / "glm4-9b-8L.json").read_text())
    with pytest.raises(ValueError):
        moe_bytes.step_bytes(dense["model"],
                             cell.load_workload("glm4-short-rounds"))


# --------------------------------------------------- the numpy expert layer
@pytest.mark.parametrize("skewed", [False, True])
def test_numpy_layer_matches_the_programs(skewed):
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import MoEConfig
    from repro.models import moe
    cfg = dataclasses.replace(
        get_config("mixtral-8x7b").reduced(n_layers=2, d_model=32, vocab=64),
        moe=MoEConfig(n_experts=8, top_k=2))
    p = moe.init_moe(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    if skewed:                          # every token ties: experts 0 and 1
        p["router"]["w"] = jnp.zeros_like(p["router"]["w"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    y, _, counts = moe.moe_ffn(p, cfg, x)
    w = p["experts"]
    want, ids = moe_reference.moe_layer(
        np.asarray(x).reshape(-1, cfg.d_model), p["router"]["w"],
        w["w_gate"], w["w_up"], w["w_down"], 2)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model),
                               want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.bincount(ids.ravel(), minlength=8))


# --------------------------------------------------------------- readers
MAP = {
    "jit_vec_round_firm": ProgramMap(
        "vec_round[firm]", None,
        {"fusion.1": "moe/experts", "fusion.2": "moe/experts",
         "fusion.3": "moe/route", "fusion.4": "generate/decode",
         "fusion.5": "moe/experts", "copy.1": None},
        {"fusion.1": "generate/decode", "fusion.2": "local_step/grads",
         "fusion.3": "generate/decode", "fusion.4": "generate/decode",
         "fusion.5": "ref_forward", "copy.1": None}),
    "jit_flat_aggregate": ProgramMap("flat_aggregate", "aggregate",
                                     {"fusion.9": "aggregate"},
                                     {"fusion.9": "aggregate"}),
}


def _ctx(rounds=2):
    ops = {"fusion.1": 1.0, "fusion.2": 0.5, "fusion.3": 0.125,
           "fusion.4": 0.25, "fusion.5": 0.0625, "copy.1": 0.01,
           "fusion.9": 0.001}
    modules = {"jit_vec_round_firm": 2.0, "jit_flat_aggregate": 0.002}
    return {"trace": {"ops": ops, "modules": modules, "window_s": 3.0,
                      "busy_s": 2.5, "rounds": rounds},
            "rounds": rounds, "window_rounds": rounds,
            "device_kind": "TPU v5 lite",
            "workload": cell.load_workload("mixtral-moe-rounds"),
            "model": _model()}


@pytest.fixture
def mapped(monkeypatch):
    monkeypatch.setitem(layer_time._built, "map", MAP)


def test_time_by_phase_and_layer(mapped):
    t = moe_time.times(_ctx())
    assert t[("generate/decode", "moe/experts")] == 1.0
    assert t[("local_step/grads", "moe/experts")] == 0.5
    assert t[("generate/decode", "moe/route")] == 0.125
    assert t[("aggregate", "aggregate")] == 0.002
    assert t[(moe_time.UNATTRIBUTED, moe_time.UNATTRIBUTED)] == 0.01
    assert moe_time.seconds(_ctx(), phase="generate/decode") == 1.375


def test_readers_on_a_small_map(mapped):
    ctx = _ctx()
    read = {n: run.load_metric(n).read(ctx) for n in READERS}
    assert read["expert_ms_per_round"] == pytest.approx(1e3 * 1.5625 / 2)
    assert read["router_ms_per_round"] == pytest.approx(1e3 * 0.125 / 2)
    moved = 2 * 32 * sum(moe_bytes.step_bytes(
        ctx["model"], ctx["workload"]).values())
    assert read["moe_decode_hbm_share"] == pytest.approx(
        100 * moved / 1.375 / 819e9)
    assert read["moe_round_mfu"] == pytest.approx(
        100 * moe_flops.round_flops(ctx["model"], ctx["workload"]) * 2
        / 3.0 / 197e12)
    assert decode_bytes.steps_per_round(ctx["workload"]) == 32


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_nothing(monkeypatch, name):
    reader = run.load_metric(name)
    assert reader.read({}) is None
    monkeypatch.setitem(layer_time._built, "map", {})
    ctx = _ctx()
    if name == "moe_round_mfu":               # reads no map: a dense model
        ctx["model"] = {k: v for k, v in ctx["model"].items() if k != "moe"}
    assert reader.read(ctx) is None
    monkeypatch.setitem(layer_time._built, "map", None)
    assert reader.read(ctx) is None


def test_a_map_without_phases_reads_no_decode_share(monkeypatch):
    """A program from before the map kept phases: the experts' time is
    still read, the decode share is not."""
    old = {m: ProgramMap(pm.name, pm.layer, pm.ops) for m, pm in MAP.items()}
    monkeypatch.setitem(layer_time._built, "map", old)
    ctx = _ctx()
    assert run.load_metric("expert_ms_per_round").read(ctx) > 0
    assert run.load_metric("moe_decode_hbm_share").read(ctx) is None
