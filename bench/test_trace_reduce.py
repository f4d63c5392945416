"""The trace reduction on a small trace kept in ``testdata/``."""
import json
import pathlib
import types

import pytest

import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "testdata"


def _planes(raw):
    """ProfileData-shaped objects from the JSON file."""
    def ev(e):
        return types.SimpleNamespace(
            name=e["name"], start_ns=e["start_ns"],
            duration_ns=e["duration_ns"],
            stats=[tuple(s) for s in e["stats"]])
    return [types.SimpleNamespace(name=p["name"], lines=[
        types.SimpleNamespace(name=ln["name"],
                              events=[ev(e) for e in ln["events"]])
        for ln in p["lines"]]) for p in raw]


@pytest.fixture(scope="module")
def small():
    raw = json.loads((DATA / "small_trace.json").read_text())
    return raw["expect"], trace_reduce.reduce_events(
        trace_reduce.read_events(_planes(raw["planes"])))


def test_window_and_rounds(small):
    expect, red = small
    assert red["rounds"] == expect["rounds"]
    assert red["window_s"] == pytest.approx(expect["window_s"], rel=1e-12)


def test_busy_is_the_union_of_op_intervals_in_the_window(small):
    expect, red = small
    assert red["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-12)


def test_op_times_by_name_are_clipped_to_the_window(small):
    expect, red = small
    assert red["ops"] == pytest.approx(expect["ops"], rel=1e-12)
    assert red["top_ops"][0][0] == "fusion.1"


def test_program_times_are_clipped_and_named_without_fingerprint(small):
    expect, red = small
    assert red["modules"] == pytest.approx(expect["modules"], rel=1e-12)


def test_idle_gaps_carry_the_innermost_host_event(small):
    expect, red = small
    assert [g[0] for g in red["idle_gaps"]] == [
        g[0] for g in expect["idle_gaps"]]
    for (_, got), (_, want) in zip(red["idle_gaps"], expect["idle_gaps"]):
        assert got == pytest.approx(want, rel=1e-12)
    idle = sum(g[1] for g in red["idle_gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"],
                                                 rel=1e-12)


@pytest.mark.parametrize("text, name", [
    ("fusion.1", "fusion.1"),
    ("%while.137 = (s32[]{:T(128)}, bf16[8,16]{1,0}) while((s32[], "
     "bf16[8,16]) %tuple.9), condition=%cond.1, body=%body.2", "while.137"),
    ("%bitcast_add_fusion.28 = bf16[8,1,1,3072]{3,0,2,1} fusion(bf16[8]"
     " %p), kind=kLoop", "bitcast_add_fusion.28"),
])
def test_op_names_are_the_instructions_names(text, name):
    assert trace_reduce.op_name(text) == name


@pytest.mark.parametrize("intervals, merged", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(4, 5), (0, 1), (1, 2)], [(0, 2), (4, 5)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
])
def test_merge(intervals, merged):
    assert trace_reduce.merge(intervals) == merged


def test_a_trace_without_timed_rounds_is_refused():
    planes = _planes([{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            {"name": "x", "start_ns": 0, "duration_ns": 1, "stats": []}]}]}])
    with pytest.raises(ValueError, match="bench_round"):
        trace_reduce.reduce_events(trace_reduce.read_events(planes))
