"""The per-layer readers on the CPU: the decode byte count against a
hand count, device time per layer from a small context and map, and
every new reader's silence when it has nothing to read.

Nothing here is a device number.
"""
import json
import pathlib

import pytest

import cell
import decode_bytes
import layer_time
import run
from repro.obs.jitwatch import ProgramMap

HERE = pathlib.Path(__file__).resolve().parent
READERS = ["generation_ms_per_round", "ref_forward_ms_per_round",
           "local_step_ms_per_round", "codec_ms_per_round",
           "aggregation_ms_per_round", "decode_hbm_share",
           "host_bound_idle_share"]


def _model(config):
    return json.loads((HERE / "configs" / f"{config}.json").read_text())[
        "model"]


# ------------------------------------------------------------ decode bytes
def test_decode_bytes_phi4_mini_by_hand():
    wl = cell.load_workload("phi4mini-short-rounds")
    got = decode_bytes.step_bytes(_model("phi4-mini-16L"), wl)
    # 16 layers of q, o 3072x3072, k, v 3072x1024, gate, up 3072x8192,
    # down 8192x3072 and two norms; the final norm; the 200064 x 3072 head
    layer = (2 * 3072 * 3072 + 2 * 3072 * 1024 + 3 * 3072 * 8192
             + 2 * 3072)
    weights = 2 * (16 * layer + 3072 + 200064 * 3072)
    assert got["weights"] == weights == 4_450_621_440       # 4.45 GB
    # 8 clients x 16 layers x r 16 x (6144 + 4096 + 4096 + 6144), float32
    assert got["adapters"] == 4 * 8 * 16 * 16 * 20480 == 167_772_160
    # 8 rows x 16 layers x K, V x 64 positions x 8 heads x 128, bfloat16
    assert got["cache"] == 2 * 8 * 16 * 2 * 64 * 8 * 128 == 33_554_432
    assert got["embed_rows"] == 2 * 8 * 3072
    assert decode_bytes.steps_per_round(wl) == 32


def test_decode_bytes_glm4_by_hand():
    wl = cell.load_workload("glm4-short-rounds")
    got = decode_bytes.step_bytes(_model("glm4-9b-8L"), wl)
    # 8 layers of q, o 4096x4096, k, v 4096x256, gate, up 4096x13696,
    # down 13696x4096 and two norms; the final norm; the 151552 x 4096 head
    layer = (2 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 13696
             + 2 * 4096)
    weights = 2 * (8 * layer + 4096 + 151552 * 4096)
    assert got["weights"] == weights == 4_504_821_760       # 4.50 GB
    # 8 clients x 8 layers x r 16 x (8192 + 4352 + 4352 + 8192), float32
    assert got["adapters"] == 4 * 8 * 8 * 16 * 25088 == 102_760_448
    # 8 rows x 8 layers x K, V x 64 positions x 2 heads x 128, bfloat16
    assert got["cache"] == 2 * 8 * 8 * 2 * 64 * 2 * 128 == 4_194_304
    assert got["embed_rows"] == 2 * 8 * 4096


def test_decode_bytes_refuses_what_it_does_not_count():
    m = dict(_model("phi4-mini-16L"), pattern=["attn", "mamba2"])
    with pytest.raises(ValueError):
        decode_bytes.step_bytes(m, cell.load_workload(
            "phi4mini-short-rounds"))


# ------------------------------------------------------------ layer times
MAP = {
    "jit_vec_round_firm": ProgramMap("vec_round[firm]", None, {
        "fusion.7": "generate/decode", "fusion.8": "generate/decode",
        "fusion.9": "generate/prefill", "fusion.10": "local_step/grads",
        "fusion.11": "local_step/adam", "fusion.12": "ref_forward",
        "copy.1": None, "fusion": "local_step/mgda"}),
    "jit_ef_roundtrip_stacked": ProgramMap(
        "ef_roundtrip_stacked", "uplink_codec",
        {"fusion": "uplink_codec", "custom-call": "uplink_codec"}),
    "jit_flat_aggregate": ProgramMap("flat_aggregate", "aggregate",
                                     {"fusion.1": "aggregate"}),
    "jit_never_ran": ProgramMap("never_ran", None, {"fusion.9": "rewards"}),
}


def _ctx(rounds=4):
    ops = {"while.3": 4.0, "fusion.7": 1.5, "fusion.8": 0.5,
           "fusion.9": 0.25, "fusion.10": 0.75, "fusion.11": 0.125,
           "fusion.12": 0.0625, "copy.1": 0.01, "fusion": 0.2,
           "custom-call": 0.02, "fusion.1": 0.001, "dynamic-slice": 0.3}
    modules = {"jit_vec_round_firm": 3.3, "jit_ef_roundtrip_stacked": 0.04,
               "jit_flat_aggregate": 0.002, "jit_dynamic_slice": 0.3}
    return {"trace": {"ops": ops, "modules": modules, "window_s": 4.0,
                      "busy_s": 3.0, "rounds": rounds},
            "rounds": rounds, "device_kind": "TPU v5 lite",
            "workload": cell.load_workload("phi4mini-short-rounds"),
            "model": _model("phi4-mini-16L")}


@pytest.fixture
def mapped(monkeypatch):
    monkeypatch.setitem(layer_time._built, "map", MAP)


def test_layer_times_from_a_small_map(mapped):
    t = layer_time.times(_ctx())
    assert t["generate/decode"] == 2.0
    assert t["generate/prefill"] == 0.25      # the program that never ran
    assert t["local_step/grads"] == 0.75      # does not make it ambiguous
    # whole-program layers read the modules line
    assert t["uplink_codec"] == 0.04 and t["aggregate"] == 0.002
    # "fusion" is a leaf of two programs that ran: unattributed, as is
    # the round program's copy outside every layer
    assert t[layer_time.UNATTRIBUTED] == pytest.approx(0.21)
    assert "local_step/mgda" not in t
    assert "rewards" not in t


def test_readers_on_a_small_map(mapped):
    ctx = _ctx()
    read = {n: run.load_metric(n).read(ctx) for n in READERS}
    assert read["generation_ms_per_round"] == pytest.approx(1e3 * 2.25 / 4)
    assert read["ref_forward_ms_per_round"] == pytest.approx(1e3 * 0.0625 / 4)
    assert read["local_step_ms_per_round"] == pytest.approx(
        1e3 * 0.875 / 4)
    assert read["codec_ms_per_round"] == pytest.approx(10.0)
    assert read["aggregation_ms_per_round"] == pytest.approx(0.5)
    moved = 4 * 32 * sum(decode_bytes.step_bytes(
        ctx["model"], ctx["workload"]).values())
    assert read["decode_hbm_share"] == pytest.approx(
        100 * moved / 2.0 / 819e9)
    assert read["host_bound_idle_share"] == pytest.approx(
        100 * (1 - 3.642 / 4.0))


@pytest.mark.parametrize("name", READERS)
def test_a_new_reader_with_nothing_to_read_returns_nothing(
        monkeypatch, name):
    reader = run.load_metric(name)
    assert reader.read({}) is None
    monkeypatch.setitem(layer_time._built, "map", {})
    ctx = _ctx()
    if name == "host_bound_idle_share":       # reads no map
        ctx["trace"]["modules"] = {}
    assert reader.read(ctx) is None
    monkeypatch.setitem(layer_time._built, "map", None)
    assert reader.read(ctx) is None


def test_a_reader_whose_layer_got_no_time_returns_nothing(mapped):
    ctx = _ctx()
    ctx["trace"]["ops"] = {"fusion.12": 0.1}
    ctx["trace"]["modules"] = {"jit_vec_round_firm": 0.1}
    assert run.load_metric("ref_forward_ms_per_round").read(ctx) > 0
    for name in READERS[:-1]:
        if name != "ref_forward_ms_per_round":
            assert run.load_metric(name).read(ctx) is None, name


def test_a_program_without_a_map_reads_nothing(monkeypatch):
    """A checkout whose ``jitwatch`` has no ``layer_map`` (before it was
    added) gives no map, and the readers stay silent."""
    from repro.obs import jitwatch
    monkeypatch.delattr(jitwatch, "layer_map")
    monkeypatch.setattr(layer_time, "_built", {})
    assert layer_time.program_map() is None
    assert run.load_metric("generation_ms_per_round").read(_ctx()) is None
