"""Smoke run of the federated FIRM round on one TPU chip.

  python chip_smoke.py

Drives the system's main path once, in this one process, through its
front door (``RunSpec -> api.plan() -> build()``) at the full width of
unreduced llama-3.2-1b, the paper's own model (16 layers, d=2048, 32
heads, 8 KV heads, d_ff=8192, vocab 128256), with weights drawn from
the seed.  FIRM with M=2 objectives, the ``int8+ef`` uplink (the Pallas
quantize/dequantize kernels) and the ``sync`` scheduler policy:

  phase 1  per-round vectorized path: an audited window of rounds,
           then one more round whose uplink deltas feed the kernel check
  phase 2  fused path: audited ``fused_rounds=2`` chunks

It exits 1, printing what failed, unless:

  * every round's rewards, KL and lambda are finite and each client's
    lambda lies on the simplex;
  * ``obs.audit_run`` passes: planned dispatches and wire bytes equal
    what was observed, with 0 recompiles after warm-up;
  * the native quantize/dequantize kernels match the ``ref.py`` oracle
    exactly on the stacked uplink deltas of a real round.

It fails at once unless JAX's default backend is the TPU.  The timings
it prints are smoke-run timings (compilation and host overhead
included), not benchmark numbers.  The last line of its output is the
JSON object ``{"ok": true, "device": {...}}``.  The compile cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.comms import codec as codec_lib  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import FIRMConfig, SchedConfig  # noqa: E402
from repro.fed import api  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.quantize import BLOCK, _DET_BITS  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.obs import audit_run  # noqa: E402

# C=2 clients, K=1 local step, B=4 prompts, 32 prompt + 32 response
# tokens: each round program compiles for a v5e chip in under a minute
# (scripts/rehearse_v5e.py), so a cold run stays well inside a chip call
SIZE = dict(n_clients=2, local_steps=1, batch_size=4, prompt_len=32,
            max_new=32)
AUDIT_ROUNDS = 2


def make_spec(cfg, *, n_clients, local_steps, batch_size, prompt_len,
              max_new, fused_rounds, seed=0):
    fc = FIRMConfig(n_objectives=2, n_clients=n_clients,
                    local_steps=local_steps, batch_size=batch_size)
    ec = api.EngineConfig(algorithm="firm", prompt_len=prompt_len,
                          max_new=max_new, seed=seed,
                          uplink_codec="int8+ef", fused_rounds=fused_rounds)
    return api.RunSpec(model=cfg, firm=fc, engine=ec,
                       sched=SchedConfig(policy="sync"))


def check_rounds(history, failures):
    for i, s in enumerate(history):
        for k in ("rewards", "kl", "lam_mean", "per_client_lam"):
            if not np.all(np.isfinite(np.asarray(s[k]))):
                failures.append(f"round {i}: {k} not finite: {s[k]}")
        lam = np.asarray(s["per_client_lam"], np.float64)
        if lam.min() < -1e-6 or np.abs(lam.sum(-1) - 1).max() > 1e-5:
            failures.append(f"round {i}: lambda off the simplex: {lam}")


def check_kernels(flats, seed, failures):
    """Native codec kernels vs the jnp oracle, bit for bit, on (C, d)
    uplink deltas laid out as the stacked uplink encodes them."""
    c, d = flats.shape
    rows = -(-d // BLOCK)
    x2 = jnp.pad(flats, ((0, 0), (0, rows * BLOCK - d))
                 ).reshape(c * rows, BLOCK)
    bits = {"stochastic": jax.random.bits(jax.random.PRNGKey(seed),
                                          x2.shape, jnp.uint32),
            "nearest": jnp.full(x2.shape, _DET_BITS, jnp.uint32)}
    for name, b in bits.items():
        codes, scales = ops.quantize(x2, b)
        rcodes, rscales = ref.quantize(x2, b)
        codes, scales, rcodes, rscales = jax.device_get(
            (codes, scales, rcodes, rscales))
        if not (np.array_equal(codes, rcodes)
                and np.array_equal(scales, rscales)):
            failures.append(
                f"quantize[{name}] != ref on {x2.shape}: "
                f"{int((codes != rcodes).sum())} codes and "
                f"{int((scales != rscales).sum())} scales differ")
        out = np.asarray(ops.dequantize(codes, scales))
        if not np.array_equal(out, np.asarray(ref.dequantize(codes,
                                                             scales))):
            failures.append(f"dequantize[{name}] != ref on {x2.shape}")
    return x2.shape


def run_phase(label, spec, failures, log, *, kernel_check=False):
    t0 = time.perf_counter()
    p = api.plan(spec)
    st = p.build()
    base = st.trainer
    jax.block_until_ready(base.params)
    t1 = time.perf_counter()
    log(f"[{label}] plan: executor={p.executor} "
        f"dispatches/round={p.dispatches_per_round} "
        f"up_bytes/round={p.up_bytes_per_round} d={p.d_trainable}; "
        f"build {t1 - t0:.1f} s")

    report = audit_run(st, rounds=AUDIT_ROUNDS)
    jax.block_until_ready(base.global_trainable)
    t2 = time.perf_counter()
    log(f"[{label}] audit ok={report.ok} "
        + json.dumps([c.to_json() for c in report.checks]))
    if not report.ok:
        failures.append(f"{label}: plan drift {report.to_json()}")
    if p.executor != ("fused" if spec.engine.fused_rounds > 1
                      else "vectorized"):
        failures.append(f"{label}: planned executor {p.executor}")

    if kernel_check:
        anchor = codec_lib.tree_to_flat(base.global_trainable)[0]
        st.run(1)
        flats = jnp.stack([codec_lib.tree_to_flat(cs.trainable)[0] - anchor
                           for cs in base.client_states])
        shape = check_kernels(flats, spec.engine.seed, failures)
        log(f"[{label}] native quantize/dequantize vs ref.py on {shape}")

    n = len(base.history)
    check_rounds(base.history, failures)
    for s in base.history:
        log(f"[{label}] round: rewards={np.round(s['rewards'], 4).tolist()}"
            f" kl={float(s['kl']):.5f} "
            f"lam={np.round(s['lam_mean'], 4).tolist()} "
            f"up_bytes={s['up_nbytes']} dispatches={s['dispatches']}")
    warm = p.fused_chunks[0] if p.fused_chunks else 1
    log(f"[{label}] smoke-run timing, not a benchmark number: warm-up "
        f"(compile + {warm} round(s)) and {AUDIT_ROUNDS} audited rounds "
        f"took {t2 - t1:.1f} s; {n} rounds in all")
    return n


def smoke(cfg, size=SIZE, log=print):
    """Both phases; returns the list of failed checks (empty = pass)."""
    failures = []
    n1 = run_phase("per-round", make_spec(cfg, fused_rounds=1, **size),
                   failures, log, kernel_check=True)
    n2 = run_phase("fused", make_spec(cfg, fused_rounds=2, **size),
                   failures, log)
    if n1 < 2 or n2 < 2:
        failures.append(f"too few rounds: {n1} per-round, {n2} fused")
    return failures


def main():
    compile_cache.enable()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    t0 = time.perf_counter()
    failures = smoke(get_config("llama-3.2-1b"),
                     log=lambda m: print(m, flush=True))
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}; "
          f"smoke run {time.perf_counter() - t0:.1f} s", flush=True)
    if failures:
        print("chip_smoke FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
