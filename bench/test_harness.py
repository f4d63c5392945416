"""The harness on the CPU: its files, its refusals, a dry run of one cell
at a tiny size through the same code, the control and the planted faults.

Nothing here is a device number: the dry run reports no metric.
"""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

import cell
import control
import run

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

TINY_MODEL = {"name": "bench-tiny", "family": "dense", "n_layers": 2,
              "n_periods": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 256, "pattern": ["attn"],
              "rope_theta": 10000.0, "norm_eps": 1e-5,
              "tie_embeddings": False,
              "lora": {"rank": 4, "alpha": 32.0,
                       "targets": ["wq", "wk", "wv", "wo"]}}
TINY_SIZE = dict(n_clients=4, batch_size=2, prompt_len=8, max_new=8)


def tiny(name="phi4mini-short-rounds", model_name="bench-tiny"):
    wl = cell.load_workload(name)
    wl.update(TINY_SIZE)
    conf = {"model": dict(TINY_MODEL, name=model_name)}
    return wl, conf, cell.model_config(conf)


# ------------------------------------------------------------------ files
def test_every_cell_has_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        wl = cell.load_workload(w["name"])
        assert wl["config"] == w["config"] and w["config"] in configs
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        assert wl["why"] == w["why"]
        assert set(wl["limits"]) == {"adam_gap", "move_gap",
                                     "code_step", "scale_gap", "aggregate_gap", "wire_bytes_gap",
                                     "window_compiles"}


def test_every_per_layer_metric_has_its_reader():
    names = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        mod = run.load_metric(m["name"])
        assert (mod.LAYER, mod.MOVES, mod.UNIT, mod.SOURCE) == (
            m["layer"], m["moves"], m["unit"], m["source"])
        assert set(m.get("workloads", names)) <= names


def test_names_and_units_use_the_allowed_characters():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for item in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(item["name"]), item["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


def test_configurations_change_only_what_they_reduce():
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"]
        for ours, theirs in conf["maps"].items():
            same = conf["model"][ours] == conf["published"][theirs]
            assert same != (theirs in c["reduced"]), (ours, theirs)


# ---------------------------------------------------------------- refusals
def _bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "phi4mini-short-rounds", "--seed", "2147483905", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_refuses_a_backend_that_is_not_the_tpu():
    p = _bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ----------------------------------------------------------------- dry run
@pytest.fixture(scope="module")
def dry():
    wl, conf, cfg = tiny()
    lines = []
    out = run.run_cell(wl, conf, cfg, seed=2 ** 31 + 77, seconds=0.5,
                       trace=False, log=lambda *a, **k: lines.append(a[0]))
    return out, lines


def test_dry_run_prints_a_well_formed_line(dry):
    out, lines = dry
    line = json.loads(json.dumps(out))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
        assert any(ln.startswith(f"check {name} =") for ln in lines)


def test_dry_run_reports_no_device_metric(dry):
    out, _ = dry
    assert out["metrics"] == {}
    assert "busy_s" not in out["device"]


def test_same_seed_same_inputs():
    a = cell.run_spec(*tiny()[::2], seed=2 ** 31 + 5)
    b = cell.run_spec(*tiny()[::2], seed=2 ** 31 + 5)
    assert a == b and a.engine.seed < cell.SEED_MODULUS


# ------------------------------------------------- control, at test size
@pytest.fixture(scope="module")
def readings():
    wl, conf, cfg = tiny(model_name="bench-tiny-control")
    spec, st, tr, layout, snaps, _ = run.setup_cell(wl, cfg, 2 ** 31 + 91)
    return wl, control.readings(snaps, layout, wl, spec.firm.actor_lr,
                                int(snaps[0]["global"].size), 3)


def _fails(numbers, limits):
    return [k for k, v in numbers.items() if not v <= limits[k]]


def test_program_passes_and_control_fails(readings):
    wl, r = readings
    assert _fails(r["program"], wl["limits"]) == []
    assert _fails(r["control"], wl["limits"])


@pytest.mark.parametrize("fault", ["unchanged", "half_clients", "altered"])
def test_each_fault_fails_a_number(readings, fault):
    wl, r = readings
    assert _fails(r[fault], wl["limits"])


@pytest.mark.parametrize("value", [1e-5, -1e-5, 0.0])
def test_the_altered_upload_lies_two_steps_off_at_any_level(value):
    """The planted alteration moves a level three steps towards zero,
    so it reads two steps off or more even where every level of its
    block sits at the clip (a first Adam step moves all elements
    alike) or at zero (a factor that does not move)."""
    import numpy as np
    import flops
    import reference
    d = 2 * flops.BLOCK
    snaps = [{"global": np.zeros(d, np.float32)},
             {"theta": np.full((1, d), value, np.float32)}]
    rng = np.random.default_rng(0)
    for element in (0, 5, d - 1):
        chain = control.server_chain(snaps, 127, rng, alter=(1, 0, element))
        step = reference.codec_and_aggregate(chain, 127, flops.BLOCK, 8)[0]
        assert step >= 2.0


# ------------------------------------- faults planted in the program itself
def _plant(monkeypatch, fault):
    """Break the timed path underneath the harness (in this process)."""
    from repro.core import fedavg
    from repro.fed import engine
    from repro.train import optim
    if fault == "unchanged":
        def adam_update(grads, state, params, **kw):
            return params, state, optim.global_norm(grads)
        monkeypatch.setattr(optim, "adam_update", adam_update)
    elif fault == "half_clients":
        monkeypatch.setattr(fedavg, "fedavg_flat_weighted",
                            lambda flats, w: flats[:flats.shape[0] // 2]
                            .mean(0))
    elif fault == "altered":
        orig = fedavg.fedavg_flat_weighted
        monkeypatch.setattr(fedavg, "fedavg_flat_weighted",
                            lambda flats, w: orig(flats, w).at[7].add(1e-3))
    for fn in (engine._jit_vec_round, engine._jit_flat_aggregate):
        fn.cache_clear()


@pytest.mark.parametrize("fault", ["unchanged", "half_clients", "altered"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.fed import engine
    _plant(monkeypatch, fault)
    try:
        wl, conf, cfg = tiny(model_name=f"bench-tiny-{fault}")
        out = run.run_cell(wl, conf, cfg, seed=2 ** 31 + 13, seconds=0.2,
                           trace=False, log=lambda *a, **k: None)
    finally:
        monkeypatch.undo()
        for fn in (engine._jit_vec_round, engine._jit_flat_aggregate):
            fn.cache_clear()
    assert out["correct"] is False


# ---------------------------------------------------------- metric readers
def _ctx(ops=None, modules=None, rounds=4, window_s=2.0, busy_s=1.5):
    return {"trace": {"window_s": window_s, "busy_s": busy_s,
                      "rounds": rounds, "ops": ops or {},
                      "modules": modules or {}},
            "rounds": rounds, "window_rounds": rounds, "jit_calls": 24,
            "flops_per_round": 1e12, "device_kind": "TPU v5 lite",
            "workload": {"n_clients": 2}, "model": TINY_MODEL, "d": 2048}


def test_metric_readers_on_a_small_context():
    assert run.load_metric("round_mfu").read(_ctx()) == pytest.approx(
        100 * 1e12 * 4 / 2.0 / 197e12)
    assert run.load_metric("device_idle_share").read(_ctx()) == \
        pytest.approx(25.0)
    assert run.load_metric("dispatches_per_round").read(_ctx()) == 6


@pytest.mark.parametrize("name", ["round_mfu", "device_idle_share",
                                  "dispatches_per_round"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    ctx = _ctx(rounds=0, window_s=0.0, busy_s=0.0)
    ctx["window_rounds"] = 0
    assert run.load_metric(name).read(ctx) is None


# ------------------------------------------------------ the codec reference
def test_unpack_reads_the_programs_int4_layout():
    import numpy as np
    from repro.comms.quantize import pack_int4
    import reference
    levels = np.array([[-7, 7, 0, 3, -1, 5]], np.int8)
    packed = np.asarray(pack_int4(levels))
    assert reference.unpack_codes(packed, 4).tolist() == levels.tolist()


def test_codec_reference_on_a_hand_example():
    import numpy as np
    import reference
    # one client, two blocks of 4; the server started at zero
    delta = np.array([[1.0, -0.5, 0.25, 0.0, 2.0, 2.0, -2.0, 1.0]],
                     np.float32)
    scales = np.array([[1.0 / 127, 2.0 / 127]], np.float32)
    codes = np.array([[[127, -63, 32, 0], [127, 127, -127, 64]]], np.int8)
    dec = (codes * scales[..., None]).reshape(1, -1)
    snaps = [{"global": np.zeros(8, np.float32)},
             {"global": dec[0], "theta": delta, "codes": codes,
              "scales": scales, "participants": np.arange(1)}]
    step, gap, agg = reference.codec_and_aggregate(snaps, 127, 4, 8)
    assert step < 1 and gap == 0 and agg == 0
    codes[0, 1, 3] = 66                      # two levels off
    snaps[1]["global"] = (codes * scales[..., None]).reshape(-1)
    assert reference.codec_and_aggregate(snaps, 127, 4, 8)[0] > 1
