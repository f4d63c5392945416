"""dispatches_per_round: jitted programs the round engine dispatches
per federated round.

Layer: round engine (``fed/engine.py``).  Moves: rounds_per_s.
Source: program_counter (``repro.obs.jitwatch`` calls in the window).
"""
LAYER = "round engine"
MOVES = "rounds_per_s"
UNIT = "dispatches"
SOURCE = "program_counter"


def read(ctx):
    if not ctx["window_rounds"]:
        return None
    return ctx["jit_calls"] / ctx["window_rounds"]
