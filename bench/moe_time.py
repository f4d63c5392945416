"""Device time by (phase, layer) of the federated round, from the reduced
trace and the program's map from ops to layers.

``layer_time.py`` gives each leaf op the innermost layer of its name
stack; an expert layer's ops (``moe/route``, ``moe/experts``) run inside
the round's phases (``generate/decode``, ``ref_forward``,
``local_step/grads``, ...).  The program's map also gives each op its
phase, the outermost layer (``ProgramMap.phases``), so the time of the
experts can be read per phase.  The ownership rules are
``layer_time.py``'s: a whole-program layer counts its time on the "XLA
Modules" line (its phase is itself), an op name that is a leaf of more
than one program that ran, or one outside every layer, counts as
``UNATTRIBUTED``.

``times(ctx)`` is {(phase, layer): seconds in the traced window}, or
None where the program has no map or the trace holds none of its
programs.  A program map without phases (a checkout from before they
were kept) puts every op under the phase ``UNATTRIBUTED``.
"""
from __future__ import annotations

import collections

import layer_time

UNATTRIBUTED = layer_time.UNATTRIBUTED


def times(ctx):
    tr = ctx.get("trace") or {}
    ops, modules = tr.get("ops"), tr.get("modules")
    if not ops or not modules:
        return None
    ran = {m: pm for m, pm in (layer_time.program_map() or {}).items()
           if m in modules}
    if not ran:
        return None
    out = collections.Counter()
    owners = collections.defaultdict(list)
    for module, pm in ran.items():
        if pm.layer is not None:
            out[(pm.layer, pm.layer)] += modules[module]
        for op in pm.ops:
            owners[op].append(module)
    for op, s in ops.items():
        where = owners.get(op)
        if not where:
            continue
        if len(where) > 1:
            out[(UNATTRIBUTED, UNATTRIBUTED)] += s
            continue
        pm = ran[where[0]]
        if pm.layer is None:
            phase = (getattr(pm, "phases", None) or {}).get(op)
            out[(phase or UNATTRIBUTED, pm.ops[op] or UNATTRIBUTED)] += s
    return dict(out)


def seconds(ctx, phase=None, layer=None):
    """Seconds under ``phase`` and ``layer`` (either None for any), or
    None where nothing was attributed there."""
    t = times(ctx)
    if not t:
        return None
    s = sum(v for (ph, lay), v in t.items()
            if (phase is None or ph == phase)
            and (layer is None or lay == layer))
    return s if s > 0 else None


def ms_per_round(ctx, phase=None, layer=None):
    s = seconds(ctx, phase, layer)
    if s is None or not ctx.get("rounds"):
        return None
    return 1e3 * s / ctx["rounds"]
