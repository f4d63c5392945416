"""Round-engine throughput: per-client loop vs vectorized vs fused rounds.

Measures rounds/sec and engine-level jitted dispatch counts for the firm
algorithm at C ∈ {4, 8, 16} on all three paths, and emits a
machine-readable ``BENCH_round_throughput.json`` next to the CSV rows (CI
uploads it as an artifact on main) — the baseline for the bench
trajectory.

The loop path runs C × K × 3 jitted dispatches per round (generate, ref
logprobs, local step per client-step); the vectorized path fuses the
entire local phase into one scanned/vmapped jit but still pays Python
dispatch + a host transfer per round; the fused path
(``EngineConfig.fused_rounds``) wraps R whole rounds — participation,
codec roundtrips, aggregation included — in one round-level ``lax.scan``,
so a chunk of R rounds is O(1) dispatches and ONE host transfer.  At toy
model sizes rounds are dispatch-bound, which is exactly what the fused
path removes.
"""
from __future__ import annotations

import json
import time

from benchmarks.common import cell_sink_spec, make_trainer, row

CLIENT_COUNTS = (4, 8, 16)
# K=1, B=1: the communication-bound regime FIRM targets (a round IS
# cheap — one adapted-param upload), which is exactly where per-round
# driver overhead dominates and the fused scan pays off.  Heavier local
# phases (K=2, B=2) are compute-bound at toy scale and the three paths
# converge to kernel time.
LOCAL_STEPS = 1
BATCH = 1
TIMED_ROUNDS = 5
FUSED_R = 8          # rounds per fused chunk
FUSED_CHUNKS = 2     # timed chunks (R * CHUNKS rounds total)


def _measure(vectorized: bool, n_clients: int) -> dict:
    tr = make_trainer("firm", n_clients=n_clients, m=2,
                      local_steps=LOCAL_STEPS, batch=BATCH,
                      vectorized=vectorized)
    # the RunSpec front door resolved the executor this cell claims to
    # measure — a silent fallback would corrupt the benchmark
    want = "vectorized" if vectorized else "loop"
    assert tr.plan.executor == want, (tr.plan.executor, want)
    tr.run(1)                                   # compile/warmup round
    d0 = tr.jit_dispatches
    t0 = time.perf_counter()
    tr.run(TIMED_ROUNDS)
    dt = time.perf_counter() - t0
    return {
        "executor": tr.plan.executor,
        "rounds_per_sec": TIMED_ROUNDS / dt,
        "us_per_round": dt / TIMED_ROUNDS * 1e6,
        "dispatches_per_round": (tr.jit_dispatches - d0) / TIMED_ROUNDS,
    }


def _measure_fused(n_clients: int, r: int = FUSED_R) -> dict:
    name = f"round_throughput_fused_c{n_clients}"
    tr = make_trainer("firm", n_clients=n_clients, m=2,
                      local_steps=LOCAL_STEPS, batch=BATCH,
                      fused_rounds=r, metrics_sink=cell_sink_spec(name))
    assert tr.plan.executor == "fused", tr.plan.executor
    tr.run(r)                                   # compile/warmup chunk
    d0 = tr.jit_dispatches
    t0 = time.perf_counter()
    tr.run(r * FUSED_CHUNKS)
    dt = time.perf_counter() - t0
    tr.obs.close()
    rounds = r * FUSED_CHUNKS
    return {
        "executor": tr.plan.executor,
        "rounds": r,
        "rounds_per_sec": rounds / dt,
        "us_per_round": dt / rounds * 1e6,
        # O(1) per fused chunk: stack + fused program + unstack
        "dispatches_per_run": (tr.jit_dispatches - d0) / FUSED_CHUNKS,
    }


def bench_round_throughput():
    results = {"algorithm": "firm", "local_steps": LOCAL_STEPS,
               "batch_size": BATCH, "timed_rounds": TIMED_ROUNDS,
               "fused_rounds": FUSED_R, "clients": {}}
    rows = []
    for c in CLIENT_COUNTS:
        loop = _measure(False, c)
        vec = _measure(True, c)
        fused = _measure_fused(c)
        speedup = loop["us_per_round"] / vec["us_per_round"]
        fused_speedup = vec["us_per_round"] / fused["us_per_round"]
        results["clients"][str(c)] = {
            "loop": loop, "vectorized": vec, "fused": fused,
            "speedup": speedup, "fused_speedup_vs_vectorized": fused_speedup}
        rows.append(row(
            f"round_throughput_c{c}", vec["us_per_round"],
            {"speedup": speedup,
             "fused_speedup_vs_vec": fused_speedup,
             "loop_us": loop["us_per_round"],
             "vec_us": vec["us_per_round"],
             "fused_us": fused["us_per_round"],
             "loop_dispatches": loop["dispatches_per_round"],
             "vec_dispatches": vec["dispatches_per_round"],
             "fused_dispatches_per_run": fused["dispatches_per_run"]}))
    with open("BENCH_round_throughput.json", "w") as f:
        json.dump(results, f, indent=2)
    return rows


ALL = [bench_round_throughput]
