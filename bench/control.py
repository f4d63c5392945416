"""Readings that set the limits of ``correct``: the program, its control
and the planted faults, at a cell's own size, on many seeds.

  python3 bench/control.py --workload <name> --seeds 1,2,3 [--out FILE]

For each seed, in one process on the chip: builds the cell and drives it
through the same set-up rounds as ``run.py``, then reads every number
``run.py`` compares for

* ``program``: what the program produced (the lower reading);
* ``control``: the reference put in the program's place one precision
  below the configuration's: Adam in bfloat16 where the optimizer state
  is float32, and the uplink quantized to int4 where the configuration
  states int8 (the upper reading);
* the faults a training cell can have, planted in the reference put in
  the program's place: ``unchanged`` (every step returns its state),
  ``half_clients`` (the server averages half of the uploads) and
  ``altered`` (one element of one upload changed where it is produced).
  There is one chip, so there is no exchange between chips to leave out.

One JSON line per seed goes to standard output (and to ``--out``).  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import ml_dtypes
import numpy as np

import cell
import flops
import reference
import run


def _quantize(x, qmax, rng, block):
    """Blockwise symmetric stochastic quantization of (C, d) float32 rows:
    the reference's own codec.  Returns (codes, scales), as on the wire
    of an int8 codec."""
    xb = reference._blocks(x, block)
    amax = np.abs(xb).max(-1)
    scale = np.where(amax > 0, amax * (np.float32(1) / np.float32(qmax)),
                     np.float32(1)).astype(np.float32)
    u = rng.random(xb.shape).astype(np.float32)
    q = np.clip(np.floor(xb / scale[..., None] + u), -qmax, qmax)
    return q.astype(np.int8), scale


def server_chain(snaps, qmax, rng, keep=None, alter=None):
    """Snapshots whose server side is the reference's: clients' updates
    as the program made them, uploads through a ``qmax`` quantizer with
    error feedback, averaged over ``keep`` clients (all by default);
    ``alter`` = (round, client, element) moves one level of one upload
    three steps towards zero (up from zero) where it is produced, so it
    stays within [-qmax, qmax] and lies at least two steps from the
    value it encodes."""
    c, d = snaps[1]["theta"].shape
    keep = np.arange(c) if keep is None else keep
    g = snaps[0]["global"].astype(np.float32)
    resid = np.zeros((c, d), np.float32)
    out = [dict(snaps[0])]
    for t in range(1, len(snaps)):
        delta = (snaps[t]["theta"].astype(np.float32)
                 - snaps[t - 1]["global"][None])
        x = delta + resid
        codes, scales = _quantize(x, qmax, rng, flops.BLOCK)
        if alter is not None and alter[0] == t:
            flat = codes.reshape(c, -1)
            level = int(flat[alter[1], alter[2]])
            flat[alter[1], alter[2]] = level - 3 if level > 0 else level + 3
        dec = (codes.astype(np.float32) * scales[..., None]).reshape(
            c, -1)[:, :d]
        resid = x - dec
        new_g = (g.astype(np.float64)
                 + dec[keep].astype(np.float64).mean(0)).astype(np.float32)
        out.append(dict(snaps[t], theta=g[None] + delta,
                        codes=codes, scales=scales,
                        participants=np.arange(c),
                        **{"global": new_g}))
        g = new_g
    return out


def adam_control(snaps, lr, dtype):
    """Snapshots whose adapters and second moments are the reference's
    Adam with every array it makes rounded to ``dtype``."""
    def rounding(a):
        return a.astype(dtype).astype(reference.DTYPE)

    out = [dict(snaps[0])]
    for t in range(1, len(snaps)):
        _, change, v = reference.adam_reference(snaps[t - 1], snaps[t], lr,
                                                t, rounding)
        theta = rounding(rounding(snaps[t - 1]["global"])[None] + change)
        out.append(dict(snaps[t], theta=theta.astype(np.float32),
                        nu=v.astype(np.float32)))
    return out


def unchanged(snaps):
    out = [dict(snaps[0])]
    for t in range(1, len(snaps)):
        out.append(dict(snaps[t], theta=np.repeat(
            out[t - 1]["global"][None], snaps[t]["theta"].shape[0], 0),
            mu=out[t - 1]["mu"], nu=out[t - 1]["nu"],
            **{"global": out[t - 1]["global"]}))
    return out


def readings(snaps, layout, wl, lr, d, seed):
    def numbers(s):
        out = run.compare(s, layout, wl, lr, d, 0)
        del out["window_compiles"]
        return out

    c = wl["n_clients"]
    bits = run.codec_bits(wl["uplink_codec"])
    qmax = 2 ** (bits - 1) - 1
    rng = np.random.default_rng(seed)
    ctl = server_chain(adam_control(snaps, lr, ml_dtypes.bfloat16),
                       qmax=7 if bits == 8 else 1, rng=rng)
    element = int(rng.integers(d))
    return {
        "program": numbers(snaps),
        "control": numbers(ctl),
        "unchanged": numbers(server_chain(unchanged(snaps), qmax, rng)),
        "half_clients": numbers(server_chain(
            snaps, qmax, rng, keep=np.arange(max(1, c // 2)))),
        "altered": numbers(server_chain(
            snaps, qmax, rng, alter=(1, 0, element))),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    wl, _, cfg = cell.load(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        spec, st, tr, layout, snaps, _ = run.setup_cell(wl, cfg, seed)
        del st, tr
        gc.collect()
        line = json.dumps({"workload": args.workload, "seed": seed,
                           **readings(snaps, layout, wl,
                                      spec.firm.actor_lr,
                                      int(snaps[0]["global"].size),
                                      seed)})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
