"""Jit-entry instrumentation of the engine's programs: dispatches,
compiles, host spans on the profiler's clock, and the map from each
program's device ops to the federated round's layers.

The engine and the ``+ef`` codec jit every program they own through
``wrap(name, fn)``, which also names the program: its compiled module is
``jit_<name>`` (XLA turns brackets into underscores), stable across
refactors and distinct per program.  With no recorder and no profiler
active the wrapper adds a constant-time check to the call.  Otherwise:

  * inside a ``record()`` context each counted call logs a ``JitSpan``
    (program name, entry wall-clock, duration, and whether THIS call
    compiled — the jit cache-size delta, ``fn._cache_size``); the plan
    auditor (``repro.obs.audit``) and the benchmark's dispatch count
    read these.  ``record()`` nests: every active recorder sees every
    span.
  * under a JAX profiler trace each dispatch runs inside
    ``jax.profiler.TraceAnnotation(name)``, and ``span()`` names the
    engine's host phases (``round/*``), so both land on the host plane
    of the device trace, on the device's clock.

The round's layers are ``jax.named_scope``s in the program (``LAYERS``).
TPU op events carry no name scope, so ``layer_map()`` rebuilds, after a
traced window, which layer each leaf instruction of every called
program belongs to, from the ``op_name`` metadata of the compiled
module.  It lowers each program from the abstract signature kept from
its first call (and from any later call that compiled under a
recorder); JAX's in-memory compile cache then hands back the executable
that ran, so the instruction names are the ones in the trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import re
import time
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

import jax

# The round's layers: one jax.named_scope each, placed around the code
# that does the work (sampling, engine, codec); the model's expert layer
# (``models/moe.py``) nests inside the phases that run the model.
LAYERS = (
    "participants", "keys", "downlink_codec", "sample_prompts",
    "generate/prefill", "generate/decode", "rewards", "ref_forward",
    "local_step/grads", "local_step/mgda", "local_step/adam",
    "local_step/critic_kl", "delta", "uplink_codec", "aggregate", "summary",
    "moe/route", "moe/experts",
)


@dataclasses.dataclass(frozen=True)
class JitSpan:
    name: str
    t0: float                 # perf_counter seconds at call entry
    dur: float                # seconds spent in the call (dispatch time)
    compiled: bool            # did this call grow the jit cache?


class JitLog:
    """Spans collected by one ``record()`` context."""

    def __init__(self) -> None:
        self.spans: List[JitSpan] = []

    @property
    def call_count(self) -> int:
        return len(self.spans)

    @property
    def compile_count(self) -> int:
        return sum(1 for s in self.spans if s.compiled)

    def calls_by_name(self) -> Dict[str, int]:
        return dict(Counter(s.name for s in self.spans))

    def compiles_by_name(self) -> Dict[str, int]:
        return dict(Counter(s.name for s in self.spans if s.compiled))


_STACK: List[JitLog] = []
_tracing = jax.profiler.TraceAnnotation.is_enabled
_NO_SPAN = contextlib.nullcontext()


@contextlib.contextmanager
def record(log: Optional[JitLog] = None):
    """Activate span recording for the dynamic extent of the block."""
    log = JitLog() if log is None else log
    _STACK.append(log)
    try:
        yield log
    finally:
        _STACK.remove(log)


def active() -> bool:
    return bool(_STACK)


def span(name: str, **metadata):
    """A host span named ``name`` on the profiler's clock while a JAX
    profiler trace is active (``metadata`` rides on the event); a shared
    no-op context otherwise."""
    if _tracing():
        return jax.profiler.TraceAnnotation(name, **metadata)
    return _NO_SPAN


@dataclasses.dataclass
class _Program:
    name: str
    jitted: Any
    sig: Any = None           # abstract (args, kwargs) to lower from


# per name, the program called last under a recorder or a profiler trace
# (or called for the first time); layer_map() reads it
_PROGRAMS: Dict[str, _Program] = {}


def _abstract(tree):
    def leaf(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        weak_type=x.weak_type)
        return x
    return jax.tree_util.tree_map(leaf, tree)


def wrap(name: str, fn, *, counted: bool = True, **jit_options):
    """``jax.jit(fn, **jit_options)`` as the program ``name``.

    ``counted=False`` keeps the program out of the recorders' spans —
    and so out of the dispatch counts — while it still gets its host
    span and its place in ``layer_map()``.  The wrapper keeps jax's call
    semantics (donation, static args).
    """
    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    jitted = jax.jit(program, **jit_options)
    prog = _Program(name, jitted)
    get_size = jitted._cache_size

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        if prog.sig is None:
            prog.sig = _abstract((args, kwargs))
            _PROGRAMS[name] = prog
        if not (_STACK or _tracing()):
            return jitted(*args, **kwargs)
        _PROGRAMS[name] = prog
        before = get_size()
        with span(name):
            t0 = time.perf_counter()
            out = jitted(*args, **kwargs)
            dur = time.perf_counter() - t0
        compiled = get_size() > before
        if compiled:
            # a new executable: lower the map from this call's shapes
            prog.sig = _abstract((args, kwargs))
        if counted:
            s = JitSpan(name, t0, dur, compiled)
            for log in _STACK:
                log.spans.append(s)
        return out

    wrapped._jitwatch_name = name
    wrapped._wrapped_jit = jitted
    return wrapped


# ------------------------------------------------------------ layer map
# Instructions that never run as a device op of their own, and those
# that only hold other ops (TPU op events nest: a while's event spans
# its body's).
_NO_OP = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
_HOLDERS = {"while", "conditional", "call"}
_SCOPE = re.compile(
    r"(?:^|/|(?<!jit)\()("
    + "|".join(re.escape(s) for s in sorted(LAYERS, key=len, reverse=True))
    + r")(?=/|\)|$)")
_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?<![\w\-])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost layer of ``LAYERS`` in an op's name stack."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def phase_of(op_name: str) -> Optional[str]:
    """The outermost layer of ``LAYERS`` in an op's name stack: the
    round's phase an op of a nested layer (``moe/experts``) runs in."""
    found = _SCOPE.findall(op_name)
    return found[0] if found else None


def _operands(rest: str, start: int) -> List[str]:
    """The instruction names between the opcode's parentheses."""
    depth, i = 1, start
    while depth and i < len(rest):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        i += 1
    return _OPERAND.findall(rest[start:i])


def hlo_ops(text: str) -> Tuple[
        str, List[Tuple[str, str, Optional[str], Optional[str]]]]:
    """(module name, [(instruction, opcode, layer, phase)]) of a compiled
    module's text: every instruction of the computations that run as op
    sequences (the entry, while bodies and conditions, conditional
    branches, called computations) that does work; fused computations
    are inside their fusion's op.

    An instruction takes the innermost layer of ``LAYERS`` in its name
    stack, and the outermost as its phase.  One outside every named
    scope (the layout copies, prefetches and loop-carried copies XLA
    adds have no name) takes the (layer, phase) of its users where they
    all have one and the same, else that of the while, conditional or
    call that holds it (None in the entry)."""
    module = re.match(r"HloModule\s+([^\s,]+)", text).group(1)
    comps: Dict[str, list] = {}
    entry, cur = None, None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h and not line.startswith((" ", "\t")):
            cur = comps.setdefault(h.group(2), [])
            if h.group(1):
                entry = h.group(2)
            continue
        m = _INSTR.match(line) if cur is not None else None
        if m is None:
            continue
        rest = m.group(2)
        op = _OPCODE.search(rest)
        if op is None:
            continue
        called = []
        for single, many in _CALLED.findall(rest):
            called += [single] if single else [
                c.strip().lstrip("%") for c in many.split(",")]
        if op.group(1) == "call":
            called += _TO_APPLY.findall(rest)
        name_stack = _OP_NAME.search(rest)
        stack = name_stack.group(1) if name_stack else ""
        cur.append((m.group(1), op.group(1),
                    (scope_of(stack), phase_of(stack)) if scope_of(stack)
                    else None,
                    called, _operands(rest, op.end())))
    out, todo, seen = [], [(entry, None)], set()
    while todo:
        comp, held = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        users: Dict[str, list] = {}
        for name, _, _, _, operands in comps[comp]:
            for o in operands:
                users.setdefault(o, []).append(name)
        own: Dict[str, Optional[Tuple[str, str]]] = {}
        for name, _, scope, _, _ in reversed(comps[comp]):
            theirs = {own.get(u) for u in users.get(name, ())}
            if scope is None and len(theirs) == 1:
                scope = theirs.pop()
            own[name] = scope or held
        for name, opcode, _, called, _ in comps[comp]:
            todo.extend((c, own[name]) for c in called)
            if opcode not in _NO_OP:
                out.append((name, opcode) + (own[name] or (None, None)))
    return module, out


@dataclasses.dataclass(frozen=True)
class ProgramMap:
    name: str                          # the jitwatch name
    layer: Optional[str]               # the whole program's layer, if one
    ops: Dict[str, Optional[str]]      # leaf instruction -> layer
    # leaf instruction -> phase (the outermost layer: ``generate/decode``
    # for an expert matmul of the decode scan)
    phases: Dict[str, Optional[str]] = dataclasses.field(
        default_factory=dict)


def layer_map(names=None) -> Dict[str, ProgramMap]:
    """{compiled module name: ProgramMap} of every wrapped program that
    has been called (or of those among ``names``), leaves only (no
    while, conditional or call), each with its layer and its phase.  A
    program whose named ops all lie in one layer is that layer as a
    whole (the codec's, the aggregation's): its unnamed ops count there
    too.  Call it after the traced window:
    it lowers and compiles each program from its kept signature, which
    JAX's compile cache answers."""
    out = {}
    for prog in list(_PROGRAMS.values()):
        if names is not None and prog.name not in names:
            continue
        args, kwargs = prog.sig
        text = prog.jitted.lower(*args, **kwargs).compile().as_text()
        module, ops = hlo_ops(text)
        leaves = {name: (lay, phase) for name, opcode, lay, phase in ops
                  if opcode not in _HOLDERS}
        named = {lay for lay, _ in leaves.values()} - {None}
        whole = named.pop() if len(named) == 1 else None
        out[module] = ProgramMap(
            prog.name, whole,
            {name: lay or whole for name, (lay, _) in leaves.items()},
            {name: phase or whole for name, (_, phase) in leaves.items()})
    return out
