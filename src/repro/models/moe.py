"""Mixture-of-Experts FFN: top-k softmax routing, dropless, in one of two
layouts picked by the rows each expert sees.

The router is ``softmax(x W_g)`` in float32; each token takes its top-k
experts and renormalises their gates over those k (Mixtral,
arXiv:2401.04088, eq. 1-2).  Every routed (token, expert) pair is
computed: there is no capacity and no token is dropped.  The router's
work (``moe/route``) is the same in both layouts; ``expert_pairs`` then
gives each routed pair its expert's SwiGLU output, and the combine sums
a token's k outputs weighted by its gates, accumulating in float32.

``expert_pairs`` computes the pairs in one of two layouts:

  * einsum (few rows per expert, e.g. a decode step): every expert runs
    on every row, one batched matmul per expert matrix with the expert
    axis as its batch dim, and the k routed outputs of each row are
    gathered.  Where an expert sees a couple of rows the pass is bound
    by reading the expert weights, which this form reads once;
    ``bench/moe_bytes.py`` counts every expert as read each step.
  * grouped (at least ``GROUPED_MIN_ROWS`` routed rows per expert on
    average: prefill-size and longer sequence passes): the pairs are
    laid out expert by expert, each expert's group padded to the row
    tile, and three Pallas grouped matmuls (megablox ``gmm``) compute
    only those rows; the einsum form would compute E/k times as many.

The layout is chosen from the static shape the matmuls see, after
batching: the engine ``vmap``s each client's model and
``ppo.per_objective_grads`` ``vmap``s the M cotangent pulls, and
``expert_pairs`` folds every batch axis into its row axis (a
``custom_vmap`` rule), so one call computes all clients' (and
objectives') pairs and each expert's weights are read once per pass, not
once per client.  The layer scans hand an expert layer every layer's
matrices stacked and its own index (``transformer._split_experts``):
the kernels read the layer's matrices where they lie, where a slice the
scan took would be copied whole first.  Where the expert weights are
frozen (the adapters sit on attention) the backward pass computes the
input gradient only: no weight gradient is formed; where they train,
theirs comes from the einsum form.

This is the single-device path.  The expert-parallel layer, which
shards experts across chips, will choose its own form.

Scopes: ``moe/route`` (router matmul, top-k, gates, and the grouped
layout's rows and gather) and ``moe/experts`` (the expert matmuls, the
un-sort and the combine), read by ``repro.obs.jitwatch``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _megablox_gmm

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models import common

# Routed rows per expert (T*k/E over the folded rows) from which the
# grouped layout runs.  Far below it (a decode step's couple of rows) a
# pass is bound by reading the experts' weights, which the einsum form
# reads at full bandwidth; at 64 the grouped layout is already the faster
# on a v5e (one Mixtral-8x7B layer, 8 clients' 32-token prefill: 4.72 ms
# against 5.21 ms).
GROUPED_MIN_ROWS = 64


def init_moe(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    d, dff, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    scale = 1.0 / jnp.sqrt(d)
    return {
        "router": {"w": common._normal(ks[0], (d, e), scale, jnp.float32)},
        "experts": {
            "w_gate": common._normal(ks[1], (e, d, dff), scale, dtype),
            "w_up": common._normal(ks[2], (e, d, dff), scale, dtype),
            "w_down": common._normal(ks[3], (e, dff, d),
                                     1.0 / jnp.sqrt(dff), dtype),
        },
    }


def max_load(counts: jnp.ndarray) -> jnp.ndarray:
    """(L, E) routed pairs per layer and expert -> the largest expert's
    share of its layer's pairs over the uniform share, maximised over
    layers (1 when routing is even, E/k when every token of a layer
    picks the same k experts)."""
    e = counts.shape[-1]
    share = counts.max(-1) / jnp.maximum(counts.sum(-1), 1.0)
    return (share * e).max()


def grouped(n_pairs: int, n_experts: int) -> bool:
    """Does a pass of ``n_pairs`` routed pairs (after folding) take the
    grouped layout?"""
    return n_pairs >= GROUPED_MIN_ROWS * n_experts


def rows_per_pair(counts: jnp.ndarray, n_pairs: int,
                  top_k: int) -> jnp.ndarray:
    """Rows the expert matmuls compute over the routed pairs, for passes
    whose per-layer pairs per expert are ``counts`` (..., E), each pass
    folding ``n_pairs`` pairs: E/k in the einsum form, each group's rows
    rounded up to the row tile in the grouped form."""
    e = counts.shape[-1]
    if not grouped(n_pairs, e):
        return jnp.float32(e / top_k)
    rows = _round_up(counts.astype(jnp.int32), _row_tile(n_pairs, e)).sum()
    return rows.astype(jnp.float32) / (n_pairs * (counts.size // e))


def moe_ffn(p, cfg: ModelConfig, x: jnp.ndarray):
    """x: (B, S, d) -> (y (B, S, d), router load-balance aux loss,
    routed pairs per expert (E,) f32)."""
    moe = cfg.moe
    e, k = moe.n_experts, moe.top_k
    b, s, d = x.shape

    with jax.named_scope("moe/route"):
        logits = x.astype(jnp.float32) @ p["router"]["w"]         # (B,S,E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_ids = jax.lax.top_k(probs, k)                 # (B,S,k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        onehot = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)  # (B,S,k,E)
        counts = onehot.sum(axis=(0, 1, 2))
        # load-balance aux (Switch): E * sum_e mean(route frac) * mean(prob)
        aux = moe.router_aux_weight * e * jnp.sum(
            counts / (b * s * k) * probs.mean(axis=(0, 1)))

    out = expert_pairs(x.reshape(b * s, d), expert_ids.reshape(b * s, k),
                       (p["experts"], p.get("layer")))             # (T,k,d)
    with jax.named_scope("moe/experts"):
        y = jnp.einsum("tk,tkd->td", gate.reshape(b * s, k).astype(out.dtype),
                       out, preferred_element_type=jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), aux, counts


# ------------------------------------------------------------ expert pairs
def _round_up(n, m):
    return (n + m - 1) // m * m


def _row_tile(n_pairs: int, e: int) -> int:
    """Rows of a grouped matmul's tile, from the pairs of the pass.  A
    tile visit reads a (1024 x 2048) slice of one expert's matrix, so
    the fewer visits the fewer weight bytes, while a tile's rows beyond
    its group are computed for nothing.  128 rows where the mean group
    fits one (64 pairs an expert: 4.72 ms a prefill layer on a v5e, 5.31
    at 256 rows), else 256 (128 and 256 pairs an expert: 5.46 and 14.46
    ms a layer's forward and its M=2 backward, against 6.41 and 18.27 at
    128 rows and 9.64 and 22.33 at 512)."""
    return 128 if n_pairs <= 64 * e else 256


def _act(g, u):
    """SwiGLU's gate: silu in float32, product in the matmuls' dtype."""
    return jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u


# ``w`` below is (matrices, layer): the layer's expert matrices
# {w_gate, w_up (E, d, d_ff), w_down (E, d_ff, d)} and None, or the
# matrices of every layer stacked (L, E, ...) and the layer's index.
def _n_experts(w) -> int:
    return w[0]["w_gate"].shape[-3]


def _one_layer(w):
    mats, layer = w
    if layer is None:
        return mats
    return jax.tree_util.tree_map(lambda a: a[layer], mats)


def _grouped_operands(w, sizes):
    """The grouped matmuls' expert operands and group sizes: the layer's
    matrices, or the whole stack seen as L*E groups with the layer's
    sizes at its own groups and zeros elsewhere, so that the kernels
    read the layer's matrices where they lie."""
    mats, layer = w
    if layer is None:
        return mats, sizes
    n_layers, e = mats["w_gate"].shape[:2]
    flat = jax.tree_util.tree_map(
        lambda a: a.reshape((n_layers * e,) + a.shape[2:]), mats)
    return flat, jax.lax.dynamic_update_slice(
        jnp.zeros(n_layers * e, jnp.int32), sizes, (layer * e,))


def _dense_fwd(x, ids, w):
    """Einsum form: every expert on every row, then each row's k routed
    outputs.  x (T, d), ids (T, k) -> (pair outputs (T, k, d), g and u
    per pair (T, k, d_ff))."""
    w = _one_layer(w)
    e = w["w_gate"].shape[0]
    buf = jnp.broadcast_to(x, (e,) + x.shape)                      # (E,T,d)
    with jax.named_scope("moe/experts"):
        g = jnp.einsum("etd,edf->etf", buf, w["w_gate"])
        u = jnp.einsum("etd,edf->etf", buf, w["w_up"])
        out = jnp.einsum("etf,efd->etd", _act(g, u), w["w_down"])
        per_pair = lambda a: a[ids, jnp.arange(x.shape[0])[:, None]]  # noqa: E731
        return per_pair(out), (per_pair(g), per_pair(u))


def _dense(x, ids, w):
    return _dense_fwd(x, ids, w)[0]


def _layout(ids, e: int):
    """The grouped layout of (T, k) routed pairs: each pair's row in a
    buffer where expert e's pairs follow expert e-1's, every group
    padded to the row tile, so that no tile holds two experts' rows.
    Returns (row of each pair (T*k,), pair of each row (M,) with T*k on
    padding rows, padded group sizes (E,) int32, the row tile)."""
    flat = ids.reshape(-1)
    n = flat.shape[0]
    tile = _row_tile(n, e)
    m = _round_up(n, tile) + e * tile
    onehot = (flat[:, None] == jnp.arange(e)).astype(jnp.int32)    # (N, E)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0), flat[:, None],
                               axis=1)[:, 0] - 1
    sizes = _round_up(onehot.sum(0), tile)
    start = jnp.cumsum(sizes) - sizes
    row = start[flat] + rank
    pair = jnp.full((m,), n, jnp.int32).at[row].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    return row, pair, sizes.astype(jnp.int32), tile


def _gmm(lhs, rhs, sizes, tile, transpose_rhs=False):
    """One grouped matmul over the padded groups, in ``lhs``'s dtype
    (float32 accumulation).  Tiles of (tile, 1024, 2048): of (512, 1024)
    x (512, 2048) contraction x column slices the largest that fits the
    v5e's scoped vector memory was the fastest (``_row_tile``'s
    measurements; 1024 x 2048 at 512 rows does not fit)."""
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _megablox_gmm(lhs, rhs, sizes, lhs.dtype,
                         (tile, min(1024, k), min(2048, n)),
                        transpose_rhs=transpose_rhs,
                        interpret=kernel_ops._interpret())


def _gather_rows(a, idx):
    """Rows ``idx`` of ``a``; an index past the end gives a zero row."""
    return jnp.take(a, idx, axis=0, mode="fill", fill_value=0)


def _grouped_fwd(x, ids, w):
    """Grouped form: (pair outputs (T, k, d), g and u per pair
    (T, k, d_ff) for the backward pass)."""
    t, k = ids.shape
    with jax.named_scope("moe/route"):
        row, pair, sizes, tile = _layout(ids, _n_experts(w))
        xs = _gather_rows(x, pair // k)                            # (M, d)
        w, sizes = _grouped_operands(w, sizes)
    with jax.named_scope("moe/experts"):
        g = _gmm(xs, w["w_gate"], sizes, tile)
        u = _gmm(xs, w["w_up"], sizes, tile)
        out = _gmm(_act(g, u), w["w_down"], sizes, tile)
        per_pair = lambda a: a[row].reshape((t, k) + a.shape[1:])  # noqa: E731
        return per_pair(out), (per_pair(g), per_pair(u))


def _grouped_bwd(ids, g, u, dy, w):
    """Input gradient of the grouped form from the saved g and u: the
    cotangent through the transposed expert matrices, summed over each
    token's k pairs."""
    t, k = ids.shape
    with jax.named_scope("moe/route"):
        row, pair, sizes, tile = _layout(ids, _n_experts(w))
        lay = lambda a: _gather_rows(a.reshape((t * k,) + a.shape[2:]),  # noqa: E731
                                     pair)
        dys, gs, us = lay(dy), lay(g), lay(u)
        w, sizes = _grouped_operands(w, sizes)
    with jax.named_scope("moe/experts"):
        dh = _gmm(dys, w["w_down"], sizes, tile, transpose_rhs=True)
        _, act_vjp = jax.vjp(_act, gs, us)
        dg, du = act_vjp(dh)
        dxs = (_gmm(dg, w["w_gate"], sizes, tile, transpose_rhs=True)
               + _gmm(du, w["w_up"], sizes, tile, transpose_rhs=True))
        return dxs[row].reshape(t, k, -1).astype(jnp.float32).sum(1).astype(
            dxs.dtype)


# Each form decides from the rows it is given, so a pass and its backward
# (which folds the M objectives too) may differ; outputs and residuals
# have the same shapes in both forms, as a batching rule must keep them.
def _pairs_fwd_impl(x, ids, w):
    """(pair outputs, residuals: x, ids, and g and u per pair)."""
    fwd = _grouped_fwd if grouped(ids.size, _n_experts(w)) else _dense_fwd
    out, (g, u) = fwd(x, ids, w)
    return out, (x, ids, g, u)


def _pairs_bwd_impl(res, dy, w):
    x, ids, g, u = res
    if grouped(ids.size, _n_experts(w)):
        return _grouped_bwd(ids, g, u, dy, w)
    _, vjp = jax.vjp(lambda x_: _dense(x_, ids, w), x)
    return vjp(dy)[0]


def _fold_rows(fn):
    """``fn(*rows, w)`` with every leaf of ``rows`` leading with a row
    axis, batched by folding the batch axis into the row axis: an
    unbatched row argument is broadcast first, ``w`` (the experts) must
    be unbatched, and ``fn`` is called once on all the rows."""
    folded = jax.custom_batching.custom_vmap(fn)

    @folded.def_vmap
    def rule(axis_size, in_batched, *args):
        *rows, w = args
        if any(jax.tree_util.tree_leaves(in_batched[-1])):
            raise NotImplementedError("expert weights batched per row "
                                      "group: no folded form")

        def fold(a, batched):
            if not batched:
                a = jnp.broadcast_to(a, (axis_size,) + a.shape)
            return a.reshape((axis_size * a.shape[1],) + a.shape[2:])

        rows = jax.tree_util.tree_map(fold, rows, list(in_batched[:-1]))
        out = folded(*rows, w)
        out = jax.tree_util.tree_map(
            lambda a: a.reshape((axis_size, a.shape[0] // axis_size)
                                + a.shape[1:]), out)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return folded


_pairs_fwd = _fold_rows(_pairs_fwd_impl)
_pairs_bwd = _fold_rows(_pairs_bwd_impl)


@jax.custom_vjp
def expert_pairs(x, ids, w):
    """Each routed pair's expert output: x (T, d), ids (T, k) int32, w =
    (the expert matrices {w_gate, w_up (E, d, d_ff), w_down (E, d_ff,
    d)}, None), or (those of every layer stacked (L, E, ...), the
    layer's index) -> (T, k, d) in x's dtype."""
    return _pairs_fwd(x, ids, w)[0]          # unused residuals: dead code


def _expert_pairs_fwd(x, ids, w):
    w_trains = any(leaf.perturbed for leaf in jax.tree_util.tree_leaves(w))
    x, ids, w = jax.custom_derivatives.custom_vjp_primal_tree_values(
        (x, ids, w))
    out, res = _pairs_fwd(x, ids, w)
    # only where the experts train is their gradient formed (einsum form)
    return out, (res, w, w_trains or None)


def _expert_pairs_bwd(saved, dy):
    res, w, w_trains = saved
    if isinstance(dy, jax.custom_derivatives.SymbolicZero):
        return None, None, None
    dw = None
    if w_trains:
        x, ids = res[:2]
        mats, layer = w
        dw = (jax.vjp(lambda m: _dense(x, ids, (m, layer)), mats)[1](dy)[0],
              None)
    return _pairs_bwd(res, dy, w), None, dw


expert_pairs.defvjp(_expert_pairs_fwd, _expert_pairs_bwd,
                    symbolic_zeros=True)
