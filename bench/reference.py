"""Plain references for the numbers that decide ``correct``.

Independent of the program: numpy in float32 over flat float32 vectors
that the benchmark read back from the trainer after each of the first
rounds.  A snapshot ``s[t]`` (t = 0 before the first round) holds

  global  (d,)    the server's adapter after round t
  theta   (C, d)  each client's adapter after its local step of round t
  mu, nu  (C, d)  each client's Adam moments after round t
  up_bytes        uplink bytes the trainer's ledger counted so far

What each number compares:

* ``adam_gap``: the local update.  The gradient each client's optimizer
  took in round t is read from its first moment,
  g = (mu_t - b1 mu_{t-1}) / (1 - b1); the reference applies Adam
  (Kingma & Ba, with the configuration's learning rate) to it, from the
  broadcast adapter, and compares per leaf the norm of the adapter's
  change and of the second moment with the program's.  A leaf whose
  gradient is below a thousandth of the median leaf's is left out of
  the change.  Gap of norms over max(reference norm, median leaf norm),
  worst leaf, client and round.
* ``move_gap``: the first step moves.  At Adam's first step every
  element with a gradient moves by lr * |g| / (|g| + eps), almost
  exactly lr.  A LoRA factor's gradient is nonzero where its partner is
  nonzero, so in round 1 the reference moves the elements of every
  factor whose partner is nonzero (B, whose partner A is drawn at
  random; not A, whose partner B starts at zero), by a norm of
  lr * sqrt(n).  Worst client of |1 - program norm / reference norm|.
* ``code_step``, ``scale_gap``, ``aggregate_gap``: the uplink codec and
  the server's average, from what crossed the wire.  Each round's
  uploads (codes and one scale per block of each client's payload) are
  read back with the adapters.  The reference rebuilds what each
  participant (as the trainer's round summary names them) encoded: its
  update plus its error-feedback residual, the residual being what the
  reference's own decode of the previous upload left.  A blockwise
  symmetric quantizer with ``qmax`` levels and stochastic rounding
  sends the scale absmax * (1 / qmax) of each block (``scale_gap`` is
  the largest relative gap of the program's scale from that) and a
  level that is the floor or the ceiling of value / scale, within
  [-qmax, qmax] (``code_step`` is the largest |level - value / scale|:
  below 1, and a few float32 ulps over it at most).  The server's
  adapter then moves by the mean of the decoded uploads
  (``aggregate_gap``: the largest |move - mean| over what the average
  may lose to rounding when its products take one bfloat16 pass, the
  TPU's default precision for a float32 matrix product, plus four
  float32 ulps of the adapters: at most 1 for a sound average).
* ``wire_bytes_gap``: uplink bytes per round the ledger counted against
  C uploads of ``codec_wire_bytes``; exact.
"""
from __future__ import annotations

import math

import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
F32_ULP = 2.0 ** -23          # float32 unit in the last place, relative
# what one product of two operands rounded to bfloat16 may lose, relative:
# the chip's default precision for a float32 matrix product
BF16_PASS = 2.0 ** -8
DTYPE = np.float32            # the reference's arithmetic


def _leaf_norms(x, slices):
    """(C, d) -> (C, L) norms of each leaf's slice."""
    return np.stack([np.sqrt(np.sum(np.square(x[..., a:b]), axis=-1))
                     for a, b in slices], axis=-1)


def adam_reference(prev, cur, lr, t, rounding=None):
    """The reference Adam step of round t for every client: (gradient,
    change of the adapter, new second moment).  Arithmetic in ``DTYPE``
    with the hyperparameters as given; ``rounding`` (e.g. to bfloat16)
    is applied to every array the step makes, as an implementation that
    keeps its state in that type would."""
    rnd = (lambda a: a) if rounding is None else rounding
    b1, b2 = ADAM_B1, ADAM_B2
    mu0, nu0 = rnd(prev["mu"].astype(DTYPE)), rnd(prev["nu"].astype(DTYPE))
    mu1 = cur["mu"].astype(DTYPE)
    g = rnd((mu1 - b1 * mu0) / (1 - b1))
    v = rnd(b2 * nu0 + rnd((1 - b2) * rnd(g * g)))
    mhat = rnd(rnd(mu1) / (1 - b1 ** t))
    vhat = rnd(v / (1 - b2 ** t))
    step = rnd(mhat / rnd(np.sqrt(vhat) + ADAM_EPS))
    return g, rnd(-lr * step), v


def _worst(a, b):
    """max() that keeps a NaN: a reading that is not a number fails."""
    return float("nan") if math.isnan(a) or math.isnan(b) else max(a, b)


def _client(snap, c):
    return {k: snap[k][c] for k in ("mu", "nu", "theta")}


def adam_gap(snaps, slices, lr):
    worst = 0.0
    for t in range(1, len(snaps)):
        start = snaps[t - 1]["global"]
        for c in range(snaps[t]["theta"].shape[0]):
            prev, cur = _client(snaps[t - 1], c), _client(snaps[t], c)
            g, change_ref, v_ref = adam_reference(prev, cur, lr, t)
            gn = _leaf_norms(g, slices)
            live = gn >= 1e-3 * np.median(gn)
            pairs = [(cur["theta"] - start, change_ref, live),
                     (cur["nu"], v_ref,
                      np.ones_like(live))]
            for prog, ref, keep in pairs:
                pn, rn = _leaf_norms(prog, slices), _leaf_norms(ref, slices)
                scale = np.maximum(np.maximum(rn, np.median(rn)),
                                   np.finfo(DTYPE).tiny)
                gap = np.where(keep, np.abs(pn - rn) / scale, 0.0)
                worst = _worst(worst, float(gap.max()))
    return worst


def moving_leaves(global0, slices, pairs):
    """Indices of the leaves the reference moves in round 1: each LoRA
    factor whose partner factor is nonzero at the start."""
    moving = []
    for i, j in pairs:
        for a, b in ((i, j), (j, i)):
            lo, hi = slices[b]
            if np.any(global0[lo:hi] != 0):
                moving.append(a)
    return sorted(moving)


def move_gap(snaps, slices, pairs, lr):
    s0, s1 = snaps[0], snaps[1]
    idx = moving_leaves(s0["global"], slices, pairs)
    n = sum(slices[i][1] - slices[i][0] for i in idx)
    if n == 0:
        return float("inf")
    change = s1["theta"].astype(np.float64) - s0["global"][None]
    norms = np.sqrt(sum(np.sum(np.square(change[:, a:b]), axis=-1)
                        for a, b in (slices[i] for i in idx)))
    return float(np.max(np.abs(1.0 - norms / (lr * np.sqrt(n)))))


def _blocks(x, block):
    """(C, d) -> (C, rows, block), zero-padded."""
    c, d = x.shape
    rows = -(-d // block)
    pad = np.zeros((c, rows * block - d), x.dtype)
    return np.concatenate([x, pad], axis=1).reshape(c, rows, block)


def unpack_codes(codes, bits):
    """Wire codes -> (C, rows, block) integer levels (int4 packs two
    levels a byte, the high nibble first, each offset by 8)."""
    if bits == 8:
        return codes.astype(np.int16)
    hi = (codes.astype(np.int16) >> 4) - 8
    lo = (codes.astype(np.int16) & 0xF) - 8
    return np.stack([hi, lo], axis=-1).reshape(*codes.shape[:-1], -1)


def codec_and_aggregate(snaps, qmax, block, bits):
    """The uplink codec and the server's average, from what crossed the
    wire in every round: (code_step, scale_gap, aggregate_gap)."""
    c, d = snaps[1]["theta"].shape
    resid = np.zeros((c, d), DTYPE)
    inv = DTYPE(1.0) / DTYPE(qmax)
    step = scale_gap = agg = 0.0
    for t in range(1, len(snaps)):
        part = snaps[t].get("participants")
        part = np.arange(c) if part is None or not len(part) else part
        prev_g = snaps[t - 1]["global"].astype(DTYPE)
        x = (snaps[t]["theta"][part].astype(DTYPE) - prev_g[None]
             + resid[part])
        xb = _blocks(x, block)
        amax = np.abs(xb).max(-1)
        scale_ref = np.where(amax > 0, amax * inv, DTYPE(1.0))
        scales = snaps[t]["scales"].reshape(len(part), -1).astype(DTYPE)
        codes = unpack_codes(snaps[t]["codes"], bits).reshape(xb.shape)
        scale_gap = _worst(scale_gap, float(np.max(
            np.abs(scales.astype(np.float64) - scale_ref)
            / scale_ref.astype(np.float64))))
        level = xb / scales[..., None]
        level -= codes
        step = _worst(step, float(np.max(np.abs(level))))
        if np.any(np.abs(codes) > qmax):
            step = float("inf")
        dec = (codes.astype(DTYPE) * scales[..., None]).reshape(
            len(part), -1)[:, :d]
        resid[part] = x - dec
        moved = snaps[t]["global"].astype(np.float64) - prev_g
        mean = dec.astype(np.float64).mean(0)
        size = np.abs(dec).astype(np.float64).mean(0)
        room = (BF16_PASS * size + 4 * F32_ULP * (
            np.abs(snaps[t]["global"]) + np.abs(prev_g)).astype(np.float64))
        dev = np.abs(moved - mean)
        ratio = np.divide(dev, room, out=np.where(dev > 0, np.inf, 0.0),
                          where=room > 0)
        agg = _worst(agg, float(ratio.max()))
    return step, scale_gap, agg


def wire_bytes_gap(snaps, per_round):
    return float(max(abs((snaps[t]["up_bytes"] - snaps[t - 1]["up_bytes"])
                         - per_round) for t in range(1, len(snaps))))
