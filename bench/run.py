"""The benchmark: one cell of ``BENCHMARK.json``, one process, one chip.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

1. Refuses to run unless JAX's backend is the TPU with as many chips as
   the cell asks for; turns on the program's persistent compile cache.
2. Set-up: builds the cell through the program's front door
   (``api.plan(RunSpec(...)).build()``, weights drawn from the seed) and
   drives it through its first ``SETUP_ROUNDS`` federated rounds with the
   window's own call, ``trainer.run(1)``.  The first round compiles; the
   state after each round is read back for the correctness check.
3. Window: ``trainer.run(1)`` until ``--seconds`` have passed, ending in
   ``block_until_ready`` on the new global adapter.  Compiles inside the
   window are counted (there must be none).  With ``--trace 1`` the
   window runs under the JAX profiler and the per-layer metrics are
   read from its trace.
4. Reads the device's peak memory (reported under ``device``; it is
   set while the program initializes its weights, see PERF.md), frees
   the trainer, and compares what the first rounds produced with the
   plain references in ``reference.py``.  The numbers compared, each beside its limit (the
   cell's ``limits``), are the last lines of standard error and the last
   key of the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), then ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import cell  # noqa: E402  (puts the program's src/ on sys.path)
import flops  # noqa: E402
import reference  # noqa: E402

SETUP_ROUNDS = 3
TRACE_DIR = cell.ROOT / ".bench_trace"


def benchmark_entry():
    path = cell.ROOT / "BENCHMARK.json"
    return json.loads(path.read_text())


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"workload {name!r} is not in BENCHMARK.json")


def per_layer_for(bench: dict, name: str) -> list:
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name])]


def load_metric(name: str):
    path = cell.HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ program side
class CompileCounter:
    """Counts XLA compiles and persistent-cache loads in the process, so
    the window can show it compiled nothing."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


def flat_layout(tree):
    """Leaf slices of the flat adapter and the LoRA factor pairs (leaves
    that share a parent projection)."""
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    slices, parents, off = [], {}, 0
    for i, (path, leaf) in enumerate(leaves):
        slices.append((off, off + leaf.size))
        off += leaf.size
        parents.setdefault(jax.tree_util.keystr(path[:-1]), []).append(i)
    pairs = [tuple(v) for v in parents.values() if len(v) == 2]
    return slices, pairs


def snapshot(tr):
    """The adapter state after a round, read back to the host."""
    import jax
    import numpy as np

    def flat(tree):
        return np.concatenate([np.asarray(x, np.float32).ravel()
                               for x in jax.tree_util.tree_leaves(tree)])

    # the uploads of the last round, as they crossed the wire
    wire = [(p.arrays["codes"], p.arrays["scales"])
            for p in tr._last_up_payloads]
    g, states, wire = jax.device_get(
        (tr.global_trainable,
         [(cs.trainable, cs.opt.mu, cs.opt.nu) for cs in tr.client_states],
         wire))
    parts = tr.history[-1]["participants"] if tr.history else []
    out = {"global": flat(g),
           "theta": np.stack([flat(s[0]) for s in states]),
           "mu": np.stack([flat(s[1]) for s in states]),
           "nu": np.stack([flat(s[2]) for s in states]),
           "participants": np.asarray(parts, np.int64),
           "up_bytes": int(tr.ledger.up_bytes)}
    if wire:
        out["codes"] = np.stack([np.asarray(w[0]) for w in wire])
        out["scales"] = np.stack([np.asarray(w[1], np.float32)
                                  for w in wire])
    return out


def codec_bits(spec: str) -> int:
    m = re.match(r"int(\d+)", spec)
    if not m:
        raise ValueError(f"no quantizing uplink in {spec!r}")
    return int(m.group(1))


def compare(snaps, layout, wl, lr, d, recompiles):
    """Every number compared with its reference, by name."""
    slices, pairs = layout
    bits = codec_bits(wl["uplink_codec"])
    qmax = 2 ** (bits - 1) - 1
    per_round = wl["n_clients"] * flops.codec_wire_bytes(d, bits)
    step, scale_gap, agg = reference.codec_and_aggregate(
        snaps, qmax, flops.BLOCK, bits)
    return {
        "adam_gap": reference.adam_gap(snaps, slices, lr),
        "move_gap": reference.move_gap(snaps, slices, pairs, lr),
        "code_step": step,
        "scale_gap": scale_gap,
        "aggregate_gap": agg,
        "wire_bytes_gap": reference.wire_bytes_gap(snaps, per_round),
        "window_compiles": float(recompiles),
    }


def setup_cell(wl: dict, cfg, seed: int):
    """Build the cell's trainer from the seed and drive it through its
    first ``SETUP_ROUNDS`` rounds with the window's own call, reading the
    adapter state back after each: (spec, trainer, base trainer, leaf
    layout, snapshots, seconds spent reading snapshots)."""
    import jax
    from repro.fed import api

    if (wl["local_steps"] != 1 or wl["downlink_codec"] != "identity"
            or wl["fused_rounds"] != 1):
        raise SystemExit("the reference follows one Adam step per round "
                         "from the broadcast adapter, read after every "
                         "round: K=1, identity downlink, fused_rounds=1")
    spec = cell.run_spec(wl, cfg, seed)
    st = api.plan(spec).build()
    tr = getattr(st, "trainer", st)
    layout = flat_layout(tr.global_trainable)
    t = time.perf_counter()
    snaps = [snapshot(tr)]
    snap_s = time.perf_counter() - t
    for _ in range(SETUP_ROUNDS):
        with jax.profiler.TraceAnnotation("bench_setup_round"):
            st.run(wl["fused_rounds"])
        jax.block_until_ready(tr.global_trainable)
        t = time.perf_counter()
        snaps.append(snapshot(tr))
        snap_s += time.perf_counter() - t
    return spec, st, tr, layout, snaps, snap_s


def run_cell(wl: dict, conf: dict, cfg, seed: int, seconds: float,
             trace: bool, log=print, per_layer=()):
    """Set-up, window and check of one cell; returns the result dict
    (without the device check, which ``main`` makes)."""
    import jax
    from repro.obs import jitwatch

    counter = CompileCounter()
    spec, st, tr, layout, snaps, snap_s = setup_cell(wl, cfg, seed)

    compiles0 = counter.n
    tracing = contextlib.nullcontext()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracing = jax.profiler.trace(str(TRACE_DIR))
    rounds0 = len(tr.history)
    with tracing, jitwatch.record() as jlog:
        t0 = time.perf_counter()
        # the reference's read-backs are not set-up
        setup_s = t0 - T_START - snap_s
        while True:
            with jax.profiler.TraceAnnotation("bench_round"):
                st.run(wl["fused_rounds"])
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(tr.global_trainable)
        t1 = time.perf_counter()
    window_s = t1 - t0
    rounds = len(tr.history) - rounds0
    recompiles = counter.n - compiles0

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    d = int(snaps[0]["global"].size)
    lr = spec.firm.actor_lr
    del st, tr
    gc.collect()

    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        import trace_reduce
        red = trace_reduce.reduce_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["top_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        # device time by compiled program, for whoever adds a per-layer
        # metric from it
        for module, s in sorted(red["modules"].items(),
                                key=lambda kv: -kv[1])[:10]:
            log(f"module {module} {s!r} s", file=sys.stderr)
        ctx = {"trace": red, "rounds": red["rounds"] or rounds,
               "jit_calls": jlog.call_count, "window_rounds": rounds,
               "flops_per_round": flops.round_flops(conf["model"], wl),
               "device_kind": dev.device_kind, "workload": wl,
               "model": conf["model"], "d": d}
        for m in per_layer:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    elif dev.platform == "tpu":
        metrics = {
            "rounds_per_s": {"value": rounds / window_s,
                             "unit": "rounds/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }

    checks = {k: (v, wl["limits"][k]) for k, v in
              compare(snaps, layout, wl, lr, d, recompiles).items()}
    correct = all(math.isfinite(v) and v <= lim
                  for v, lim in checks.values())
    for k, (v, lim) in checks.items():
        log(f"check {k} = {v!r} (limit {lim!r})", file=sys.stderr)
    out = {"correct": correct, "attempted": rounds, "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (cell.ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    bench = benchmark_entry()
    entry = cell_entry(bench, args.workload)
    wl, conf, cfg = cell.load(args.workload)

    import jax
    if jax.default_backend() != "tpu":
        print(f"bench: needs a TPU; JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    if len(jax.devices()) < entry["chips"]:
        print(f"bench: {args.workload} needs {entry['chips']} chips, "
              f"found {len(jax.devices())}", file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    compile_cache.enable()
    # every program of the cell goes to the cache, the small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    out = run_cell(wl, conf, cfg, args.seed, args.seconds,
                   bool(args.trace),
                   per_layer=per_layer_for(bench, args.workload))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
