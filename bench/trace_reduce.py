"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* window: from the start of the first ``bench_round`` host annotation to
  the end of the last (the benchmark wraps each timed ``trainer.run(1)``
  in one); ``rounds`` is how many there are.
* busy: the union of the intervals in which an operation ran on each
  TPU device plane ("XLA Ops" line), clipped to the window, averaged
  over the devices.
* ops: device time by operation name; modules: device time by compiled
  program ("XLA Modules" line, e.g. ``jit_round_fn``). The TPU plane's
  ops carry no name scope, so a layer inside one program is not told
  apart here.
* idle gaps: the stretches of the window in which no operation ran on
  the device, each labelled with the innermost host event under it.

Only ``jax.profiler.ProfileData`` is needed to read the file.
"""
from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ROUND = "bench_round"
LABELLED_GAPS = 500      # the longest gaps get a label each


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def gaps(busy, lo, hi):
    """Idle stretches of [lo, hi] between the merged busy intervals."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def op_name(text):
    """An XLA op's name from the text the TPU plane gives it: the whole
    HLO instruction ("%while.137 = (s32[], ...) while(...)") or its
    name alone."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def module_name(text):
    """A compiled program's name without the fingerprint the trace adds
    ("jit_round_fn(17667705987533127851)" -> "jit_round_fn")."""
    return text.split("(", 1)[0]


def read_events(planes):
    """Plain records from ProfileData planes (or any objects shaped like
    them): {"host": [(name, start, end)], "devices": {plane: {"ops":
    [(name, start, end)], "modules": [(name, start, end)]}}}, times in
    ns."""
    host, devices = [], {}
    for pl in planes:
        if DEVICE_PLANE.match(pl.name):
            lines = {OPS_LINE: ("ops", op_name),
                     MODULES_LINE: ("modules", module_name)}
            dev = {"ops": [], "modules": []}
            for ln in pl.lines:
                if ln.name not in lines:
                    continue
                key, name = lines[ln.name]
                for ev in ln.events:
                    dev[key].append((name(ev.name), ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
            devices[pl.name] = dev
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                for ev in ln.events:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
    return {"host": host, "devices": devices}


def reduce_events(events, top=10):
    rounds = [(s, e) for n, s, e in events["host"] if n == ROUND]
    if not rounds:
        raise ValueError(f"no {ROUND!r} host annotation in the trace")
    if not events["devices"]:
        raise ValueError("no TPU device plane with an 'XLA Ops' line")
    lo, hi = min(s for s, _ in rounds), max(e for _, e in rounds)
    window_ns = hi - lo

    def inside(evs):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                if e > lo and s < hi]

    busy_ns, by_name, by_module, idle = [], collections.Counter(), \
        collections.Counter(), []
    for dev in events["devices"].values():
        ops = inside(dev["ops"])
        busy = merge([(s, e) for _, s, e in ops])
        busy_ns.append(sum(e - s for s, e in busy))
        for n, s, e in ops:
            by_name[n] += e - s
        for n, s, e in inside(dev["modules"]):
            by_module[n] += e - s
        idle.extend(gaps(busy, lo, hi))

    host = [h for h in events["host"] if h[0] != ROUND]
    names = [n for n, _, _ in host]
    hs = np.array([h[1] for h in host], np.float64)
    he = np.array([h[2] for h in host], np.float64)
    labelled = collections.Counter()
    idle.sort(key=lambda g: g[0] - g[1])
    for i, (s, e) in enumerate(idle):
        if i >= LABELLED_GAPS:
            labelled["(shorter gaps)"] += e - s
            continue
        mid = (s + e) / 2
        under = np.flatnonzero((hs <= mid) & (mid < he))
        label = (names[under[np.argmin(he[under] - hs[under])]]
                 if under.size else "(no host event)")
        labelled[label] += e - s
    ns = 1e-9
    return {
        "window_s": window_ns * ns,
        "busy_s": sum(busy_ns) / len(busy_ns) * ns,
        "rounds": len(rounds),
        "ops": {k: v * ns for k, v in by_name.items()},
        "modules": {k: v * ns for k, v in by_module.items()},
        "top_ops": [[k, v * ns] for k, v in by_name.most_common(top)],
        "idle_gaps": [[k, v * ns] for k, v in labelled.most_common(top)],
    }


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir, top=10):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    return reduce_events(read_events(pd.planes), top)
