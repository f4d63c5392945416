"""The round's layers on the device trace's clock: named programs, host
spans in a profiler trace, and the map from compiled ops to layers
(``repro.obs.jitwatch``)."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import FIRMConfig
from repro.fed.engine import EngineConfig, FederatedTrainer
from repro.obs import jitwatch

ROUND_PROGRAMS = {"downlink_roundtrip", "stack_trees", "vec_round[firm]",
                  "unstack", "delta_flat", "ef_roundtrip_stacked",
                  "flat_aggregate", "summary_device"}
# the round's keys are drawn inside its programs, so no round/keys span
ROUND_PHASES = {"round", "round/downlink", "round/local_phase",
                "round/uplink", "round/aggregate", "round/summary"}
# the layers inside the per-client local phase
LOCAL_LAYERS = {"sample_prompts", "generate/prefill", "generate/decode",
                "rewards", "ref_forward", "local_step/grads",
                "local_step/mgda", "local_step/adam", "local_step/critic_kl"}
# the layers that are whole programs on the per-round path
PROGRAM_LAYERS = {"downlink_roundtrip": "downlink_codec",
                  "delta_flat": "delta",
                  "ef_roundtrip_stacked": "uplink_codec",
                  "flat_aggregate": "aggregate",
                  "summary_device": "summary"}


def _trainer(**kw):
    cfg = get_config("llama-3.2-1b").reduced(n_layers=2, d_model=64,
                                             vocab=256)
    fc = FIRMConfig(n_objectives=2, n_clients=2, local_steps=1,
                    batch_size=2, beta=0.05)
    ec = EngineConfig(algorithm="firm", max_new=6, prompt_len=4, seed=0,
                      uplink_codec="int8+ef", **kw)
    return FederatedTrainer(cfg, fc, ec)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One warm round of the per-round path under the JAX profiler:
    (trainer, host events [(name, start, end, stats)])."""
    from jax.profiler import ProfileData
    tr = _trainer()
    tr.run(1)
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        with jax.profiler.TraceAnnotation("bench_round"):
            tr.run(1)
    path, = glob.glob(str(out / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return tr, events


@pytest.fixture(scope="module")
def round_map(traced):
    return jitwatch.layer_map(ROUND_PROGRAMS)


def test_profiler_trace_holds_round_phases_and_programs(traced):
    tr, events = traced
    outer = [(s, e) for n, s, e, _ in events if n == "bench_round"]
    assert len(outer) == 1
    lo, hi = outer[0]
    named = {n: (s, e, st) for n, s, e, st in events
             if n in ROUND_PHASES | ROUND_PROGRAMS}
    assert set(named) == ROUND_PHASES | ROUND_PROGRAMS
    for n, (s, e, _) in named.items():
        assert lo <= s <= e <= hi, n
    # the round span carries the round's index
    assert named["round"][2]["round"] == tr._round_idx - 1
    # each program's span lies inside the host phase that dispatched it
    r0, r1, _ = named["round/local_phase"]
    assert r0 <= named["vec_round[firm]"][0] <= named["vec_round[firm]"][1] <= r1
    u0, u1, _ = named["round/uplink"]
    assert u0 <= named["ef_roundtrip_stacked"][0] <= u1


def test_one_rounds_programs_have_distinct_module_names(traced, round_map):
    _, events = traced
    ran = {n for n, *_ in events} & set(jitwatch._PROGRAMS)
    assert ran == ROUND_PROGRAMS
    modules = {m: pm.name for m, pm in round_map.items()}
    assert len(modules) == len(ran)
    assert sorted(modules) == sorted(
        "jit_" + re.sub(r"\W+", "_", n).strip("_") for n in ran)


def test_round_program_leaves_map_to_one_layer_each(round_map):
    pm, = [p for p in round_map.values() if p.name == "vec_round[firm]"]
    layers = set(pm.ops.values())
    # besides the local phase, the program draws the round's keys and
    # reduces the summary's means
    assert layers - {None} == LOCAL_LAYERS | {"keys", "summary"}
    attributed = sum(1 for v in pm.ops.values() if v is not None)
    # XLA's CPU backend adds bf16 converts without a name stack that no
    # layer uses alone; the chip's share is measured on the trace
    assert attributed >= 0.95 * len(pm.ops)
    prog = jitwatch._PROGRAMS["vec_round[firm]"]
    args, kwargs = prog.sig
    text = prog.jitted.lower(*args, **kwargs).compile().as_text()
    module, ops = jitwatch.hlo_ops(text)
    assert module == "jit_vec_round_firm"
    # every matrix product (the round's work) lies in exactly one layer
    dots = [line for line in text.splitlines()
            if re.search(r"= \S+ dot\(", line) and "op_name" in line]
    assert dots
    for line in dots:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        found = set(jitwatch._SCOPE.findall(op_name))
        assert len(found) == 1 and found <= LOCAL_LAYERS, op_name
    # the decode scan is one while, under generate/decode
    whiles = {lay for _, opc, lay, _ in ops if opc == "while"}
    assert "generate/decode" in whiles
    assert not any(opc == "while" for op, opc, _, _ in ops if op in pm.ops)
    # a dense model nests no layer: every op's phase is its layer
    assert pm.phases == pm.ops


def test_whole_program_layers(round_map):
    for pm in round_map.values():
        if pm.name in PROGRAM_LAYERS:
            assert pm.layer == PROGRAM_LAYERS[pm.name]
            assert set(pm.ops.values()) == {pm.layer}, pm.name


def test_fused_program_carries_the_same_layers():
    tr = _trainer(fused_rounds=2)
    with jitwatch.record():               # the map follows recorded calls
        tr.run(2)
    fused, = jitwatch.layer_map({"fused_rounds[firm]"}).values()
    assert set(fused.ops.values()) - {None} == LOCAL_LAYERS | {
        "delta", "uplink_codec", "aggregate", "summary"}


def test_scope_of_reads_the_innermost_layer_and_skips_jit_names():
    assert jitwatch.scope_of(
        "jit(vec_round[firm])/while/body/vmap(generate/decode)/while/dot"
    ) == "generate/decode"
    assert jitwatch.scope_of(
        "jit(f)/vmap(local_step/grads)/transpose(jvp())/mul"
    ) == "local_step/grads"
    assert jitwatch.scope_of("jit(summary_device)/add") is None
    assert jitwatch.scope_of("jit(f)/jit(rewards)/add") is None
    assert jitwatch.scope_of("jit(f)/rewards/jit(_where)/select") == "rewards"


def test_an_expert_layer_nests_inside_the_phase_that_runs_it():
    decode = ("jit(vec_round[firm])/while/body/vmap(generate/decode)/while/"
              "body/moe/experts/dot_general")
    assert jitwatch.scope_of(decode) == "moe/experts"
    assert jitwatch.phase_of(decode) == "generate/decode"
    grads = ("jit(f)/vmap(local_step/grads)/transpose(jvp(moe/route))/"
             "dot_general")
    assert jitwatch.scope_of(grads) == "moe/route"
    assert jitwatch.phase_of(grads) == "local_step/grads"
    assert jitwatch.phase_of("jit(f)/rewards/add") == "rewards"
    assert jitwatch.phase_of("jit(summary_device)/add") is None
    text = """HloModule jit_toy, entry_computation_layout={()->f32[]}

ENTRY %main (x: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %dot.1 = f32[] multiply(%x, %x), metadata={op_name="jit(toy)/ref_forward/moe/experts/dot_general"}
  %copy.2 = f32[] copy(%dot.1)
  ROOT %add.3 = f32[] add(%copy.2, %x), metadata={op_name="jit(toy)/ref_forward/moe/experts/add"}
}
"""
    _, ops = jitwatch.hlo_ops(text)
    assert sorted(ops) == [
        ("add.3", "add", "moe/experts", "ref_forward"),
        # an unnamed op takes its user's layer and phase
        ("copy.2", "copy", "moe/experts", "ref_forward"),
        ("dot.1", "multiply", "moe/experts", "ref_forward")]


def test_hlo_ops_skips_fused_computations_and_resolves_unnamed_ops():
    text = """HloModule jit_toy, entry_computation_layout={()->f32[]}

%fused (p: f32[]) -> f32[] {
  %p = f32[] parameter(0)
  ROOT %m = f32[] multiply(%p, %p), metadata={op_name="jit(toy)/rewards/mul"}
}

%body (t: (s32[], f32[])) -> (s32[], f32[]) {
  %t = (s32[], f32[]) parameter(0)
  %copy.3 = f32[] copy(%t)
  ROOT %tuple = (s32[], f32[]) tuple(%t, %copy.3)
}

%cond (t: (s32[], f32[])) -> pred[] {
  %t = (s32[], f32[]) parameter(0)
  ROOT %lt = pred[] compare(%t, %t), direction=LT
}

ENTRY %main (x: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %fusion.1 = f32[] fusion(%x), kind=kLoop, calls=%fused, metadata={op_name="jit(toy)/rewards/mul"}
  %copy.5 = f32[] copy(%x)
  %copy.6 = f32[] copy(%x)
  %while.2 = (s32[], f32[]) while(%copy.5), condition=%cond, body=%body, metadata={op_name="jit(toy)/vmap(generate/decode)/while"}
  %add.7 = f32[] add(%copy.6, %fusion.1), metadata={op_name="jit(toy)/aggregate/add"}
  ROOT %tuple.9 = (f32[], (s32[], f32[])) tuple(%add.7, %while.2, %copy.6)
}
"""
    module, ops = jitwatch.hlo_ops(text)
    assert module == "jit_toy"
    assert sorted(ops, key=str) == sorted([
        ("fusion.1", "fusion", "rewards", "rewards"),
        # an unnamed copy takes its one user's layer ...
        ("copy.5", "copy", "generate/decode", "generate/decode"),
        # ... and with users in different layers, none
        ("copy.6", "copy", None, None),
        ("add.7", "add", "aggregate", "aggregate"),
        ("while.2", "while", "generate/decode", "generate/decode"),
        # inside the loop, the loop's
        ("copy.3", "copy", "generate/decode", "generate/decode"),
        ("lt", "compare", "generate/decode", "generate/decode")], key=str)


def test_an_inactive_wrapper_keeps_only_the_first_signature(monkeypatch):
    f = jitwatch.wrap("test_inactive", lambda x: x * 2)
    seen = []
    real = jitwatch._abstract
    monkeypatch.setattr(jitwatch, "_abstract",
                        lambda t: seen.append(1) or real(t))
    f(jnp.zeros(3))
    f(jnp.zeros(3))
    f(jnp.zeros(5))
    assert len(seen) == 1
    assert jitwatch._PROGRAMS["test_inactive"].sig[0][0].shape == (3,)
    with jitwatch.record() as log:
        f(jnp.zeros(7))                   # compiles: the map follows it
    assert log.compile_count == 1
    assert jitwatch._PROGRAMS["test_inactive"].sig[0][0].shape == (7,)
    assert jitwatch.span("x") is jitwatch.span("y")   # no profiler: no-op
