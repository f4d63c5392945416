# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point (deliverable d).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig2,thm45
  PYTHONPATH=src python -m benchmarks.run --only sched --trace-out traces/ \
      --metrics-sink jsonl:metrics.jsonl             # telemetry exports

Groups:
  paper_figures  — Figs. 1-8 / RQ1-RQ3 / App. A experiments (toy scale)
  theory_checks  — Thm 4.5 drift scaling, Lemma F.6, linear speedup
  kernels_micro  — kernel microbenches + Pallas oracle agreement
  codec_tradeoff — reward-vs-measured-bytes Pareto sweep (comms codecs)
  round_throughput — loop vs vectorized round engine (rounds/sec, dispatches)
  sched_wallclock — scheduler policy x codec x heterogeneity wall-clock sweep
  roofline       — per-(arch x shape x mesh) roofline from the dry-run
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated substrings of bench names")
    from benchmarks import common
    common.add_obs_flags(ap)
    args = ap.parse_args()
    common.parse_cli_options(args)

    from benchmarks import (codec_tradeoff, compression_error, kernels_micro,
                            paper_figures, roofline_report,
                            round_throughput, sched_wallclock, theory_checks)
    benches = (paper_figures.ALL + theory_checks.ALL + kernels_micro.ALL +
               compression_error.ALL + codec_tradeoff.ALL +
               round_throughput.ALL + sched_wallclock.ALL +
               roofline_report.ALL)
    filters = [f for f in args.only.split(",") if f]

    print("name,us_per_call,derived")
    failures = 0
    for fn in benches:
        name = fn.__name__
        if filters and not any(f in name for f in filters):
            continue
        try:
            out = fn()
            if isinstance(out, list):
                for line in out:
                    print(line, flush=True)
            else:
                print(out, flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0.0,{{\"error\": \"{type(e).__name__}: {e}\"}}",
                  flush=True)
            traceback.print_exc(file=sys.stderr)
    # per-pair roofline rows (compact, after the summary tables)
    if not filters or any("roofline" in f for f in filters):
        for line in roofline_report.bench_roofline_per_pair():
            print(line, flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
