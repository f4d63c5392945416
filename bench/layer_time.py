"""Device time per layer of the federated round, from the reduced trace
and the program's own map from ops to layers.

The TPU's op events carry no name scope, so the program says which layer
each leaf instruction of its compiled programs belongs to
(``repro.obs.jitwatch.layer_map()``: the ``jax.named_scope`` layers of
the round, ``jitwatch.LAYERS``).  Here:

* a program that is one layer as a whole (the uplink codec, the
  aggregation, the delta, the summary) counts its time on the
  "XLA Modules" line;
* any other program counts the device time of its leaf ops under the
  layer the map gives each.  ``trace["ops"]`` is keyed by op name alone,
  so an op name that is a leaf of more than one program that ran, or a
  leaf outside every layer, counts as ``UNATTRIBUTED``, never as a guess.

``times(ctx)`` is {layer: seconds in the traced window}, or None where the
program has no map (a checkout whose ``jitwatch`` has no ``layer_map``)
or the trace holds none of its programs.  The map is built once per
process, after the window.
"""
from __future__ import annotations

import collections

UNATTRIBUTED = "(unattributed)"
_built = {}


def program_map():
    """The program's {module name: ProgramMap}, or None without one."""
    if "map" not in _built:
        try:
            from repro.obs import jitwatch
        except ImportError:
            jitwatch = None
        build = getattr(jitwatch, "layer_map", None)
        _built["map"] = build() if build is not None else None
    return _built["map"]


def times(ctx):
    tr = ctx.get("trace") or {}
    ops, modules = tr.get("ops"), tr.get("modules")
    if not ops or not modules:
        return None
    ran = {m: pm for m, pm in (program_map() or {}).items()
           if m in modules}
    if not ran:
        return None
    out = collections.Counter()
    owners = collections.defaultdict(list)
    for module, pm in ran.items():
        if pm.layer is not None:
            out[pm.layer] += modules[module]
        for op in pm.ops:
            owners[op].append(module)
    for op, s in ops.items():
        where = owners.get(op)
        if not where:
            continue                    # a holder, or another program's
        if len(where) > 1:
            out[UNATTRIBUTED] += s
            continue
        pm = ran[where[0]]
        if pm.layer is None:
            out[pm.ops[op] or UNATTRIBUTED] += s
    return dict(out)


def ms_per_round(ctx, *layers, prefix=False):
    """Milliseconds a round spends under ``layers`` (each a layer, or
    with ``prefix`` the start of one, e.g. ``generate/``), or None where
    nothing was attributed there."""
    t = times(ctx)
    if not t or not ctx.get("rounds"):
        return None
    s = sum(v for k, v in t.items()
            if any(k.startswith(x) if prefix else k == x for x in layers))
    return 1e3 * s / ctx["rounds"] if s > 0 else None
