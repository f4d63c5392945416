"""The expert layer in plain numpy, float64: imports nothing of the
program (like ``reference.py``).

Mixtral's sparse FFN (arXiv:2401.04088, eq. 1-2): the router's softmax
over E experts, each token's top-k experts (ties to the lower index),
their gates renormalised over the k, and the sum of the k experts'
SwiGLU outputs weighted by the gates.  Every routed pair is computed:
nothing is dropped.  The program's layer (``models/moe.py``) is held to
it at a small size on the CPU (``test_moe_bench.py``).
"""
from __future__ import annotations

import numpy as np


def _silu(x):
    return x / (1.0 + np.exp(-x))


def route(x, router_w, top_k):
    """x (T, d) -> (expert ids (T, k), renormalised gates (T, k),
    router probabilities (T, E))."""
    logits = np.asarray(x, np.float64) @ np.asarray(router_w, np.float64)
    logits -= logits.max(-1, keepdims=True)
    probs = np.exp(logits)
    probs /= probs.sum(-1, keepdims=True)
    ids = np.argsort(-probs, axis=-1, kind="stable")[:, :top_k]
    gates = np.take_along_axis(probs, ids, -1)
    return ids, gates / gates.sum(-1, keepdims=True), probs


def moe_layer(x, router_w, w_gate, w_up, w_down, top_k):
    """x (T, d); router_w (d, E); w_gate, w_up (E, d, f); w_down
    (E, f, d) -> (y (T, d), expert ids (T, k))."""
    x = np.asarray(x, np.float64)
    ids, gates, _ = route(x, router_w, top_k)
    y = np.zeros_like(x)
    for e in range(np.asarray(router_w).shape[1]):
        rows, slot = np.nonzero(ids == e)
        if rows.size == 0:
            continue
        xe = x[rows]
        h = (_silu(xe @ np.asarray(w_gate[e], np.float64))
             * (xe @ np.asarray(w_up[e], np.float64)))
        y[rows] += gates[rows, slot, None] * (
            h @ np.asarray(w_down[e], np.float64))
    return y, ids
