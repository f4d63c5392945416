"""The sparse-expert cell against its float32 reference, on the chip, at
the cell's own sizes.

  python3 bench/moe_check.py --workload mixtral-moe-rounds --seeds 1,2 [--out FILE]

For each seed, in one process: builds the cell through ``cell.load`` and
the front door (``api.plan(RunSpec).build()``: the trainer's own weights
from the seed; device memory is read right after), samples every
client's first prompt block as the round does, and runs

* the program's generation: ``generate`` vmapped over the C clients,
  then the logits of its prefill and of each decode step through the
  cache (``transformer.prefill`` / ``decode_step``, fed as ``generate``
  feeds them);
* the program's teacher-forced ``forward_seq`` over every rollout, and
  the routes of its layers over the same tokens (the program's own
  blocks, scanned over the layers, with the router's top-k read beside
  each expert layer);
* one client's per-objective LoRA gradients (``ppo.per_objective_grads``)
  on its rollout, with the program's teacher-forced log-probabilities
  as its old and its reference ones (a first PPO step: ratio 1, KL 0)
  and seeded terminal scores in [0.5, 1.5];

and the same with ``repro.models.moe_reference`` (float32, highest
matmul precision, one row per call), and with that reference's
lower-precision control (every expert's output rounded to
float8_e4m3fn).  The reference and the control take the experts the
program's router chose: a top-2 set that flips on a near-tie sends a
token through another expert, and through attention every later
position, which no precision matches, so flips are counted apart.
Program and control are each compared with the reference:

* ``route_flip_share``: the share of (token, layer) whose top-k set
  differs from the reference's own top-k on its own router
  probabilities, and ``flip_margin_max``: the largest reference gate
  margin (k-th minus (k+1)-th router probability) of a flipped token;
* ``logit_gap``: the largest |logit - reference logit| over the
  reference logits' standard deviation (generation and teacher-forced
  logits both);
* ``grad_gap``: the largest, over objectives, of |g - g_ref| / |g_ref|
  over all LoRA factors of one client.

One JSON line per seed (the numbers beside their limits) goes to
standard output and to ``--out``.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from unittest import mock

import numpy as np

import cell

# Set from the chip at the cell's sizes (PERF.md, the Mixtral cell's
# on-chip comparison: the program and the float8 control over four
# seeds, three for the gradients), with more room above the program's
# largest reading than below the control's smallest, since fresh seeds
# read higher.
LIMITS = {
    # a top-2 set flips only where two experts nearly tie, so the share
    # counts near-ties (about 29 flips of 2,048 pairs at the program's
    # 1.42%): a lower precision widens it only a little (control 1.81%
    # to 2.44%), and the limit leaves the count's noise room
    "route_flip_share": 0.03,
    # how far a precision moves the router: a flip's reference margin
    # (program at most 0.0047, control 0.0082 to 0.0159)
    "flip_margin_max": 0.007,
    # bfloat16 weights and activations through 4 layers and the head,
    # under the same routes (program at most 0.080, control 0.166 up)
    "logit_gap": 0.12,
    # the same through the backward pass to the adapters (program at
    # most 0.025, control 0.817 up)
    "grad_gap": 0.15,
}


def _flat(tree):
    import jax
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree_util.tree_leaves(tree)])



def _spy(cfg, seen):
    """``moe.moe_ffn`` as it is, with its router's top-k recorded in
    ``seen`` (the same ops on the same input: XLA computes them once)."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe as moe_lib
    ffn = moe_lib.moe_ffn

    def spy(p_moe, c, h):
        probs = jax.nn.softmax(
            h.astype(jnp.float32) @ p_moe["router"]["w"], axis=-1)
        seen.append(jax.lax.top_k(probs, cfg.moe.top_k)[1])
        return ffn(p_moe, c, h)
    return spy


def program_routes(cfg, params, tokens):
    """(L, R, S, k) expert ids of the program's blocks over (R, S)
    tokens, as its sequence forward (``forward_seq``, ``prefill``) runs
    them: its ``block_seq`` scanned over the layers."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe as moe_lib
    from repro.models import transformer

    def body(x, p):
        seen = []
        with mock.patch.object(moe_lib, "moe_ffn", _spy(cfg, seen)):
            x, _, _ = transformer.block_seq(
                "moe", p, cfg, x, jnp.arange(tokens.shape[1]), None, False)
        return x, seen[0]

    x = jnp.take(params["embed"], tokens, axis=0)
    return jax.lax.scan(body, x, params["slots"]["0"])[1]


def program_decode_routes(cfg, params, cache, token):
    """(L, R, 1, k) expert ids of one decode step as ``decode_step``
    runs it (embedding, ``block_decode`` scanned over the layers), and
    that step's logits (R, V) and cache."""
    import jax
    from repro.models import common
    from repro.models import moe as moe_lib
    from repro.models import transformer
    pos = cache["pos"]

    def body(x, xs):
        p, c = xs
        seen = []
        with mock.patch.object(moe_lib, "moe_ffn", _spy(cfg, seen)):
            x, c = transformer.block_decode("moe", p, cfg, x, c, pos)
        return x, (c, seen[0])

    x = jax.numpy.take(params["embed"], token, axis=0)
    x, (slot, ids) = jax.lax.scan(
        body, x, (params["slots"]["0"], cache["slots"]["0"]))
    x = common.rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = common.linear(params["lm_head"], x)[:, 0]
    return logits, {"slots": {"0": slot}, "pos": pos + 1}, ids


def _flips(ids, ref_ids, ref_probs, k):
    """(share of (token, layer) whose top-k set differs from the
    reference's, the largest reference margin among them)."""
    a = np.sort(np.asarray(ids).reshape(-1, k), -1)
    b = np.sort(np.asarray(ref_ids).reshape(-1, k), -1)
    flip = (a != b).any(-1)
    p = np.sort(np.asarray(ref_probs, np.float64).reshape(
        flip.size, -1), -1)[:, ::-1]
    margin = (p[:, k - 1] - p[:, k])[flip]
    return float(flip.mean()), float(margin.max()) if margin.size else 0.0


def _reference_rows(cfg, params, tokens, ids, control):
    """``moe_reference.forward`` over (R, S) tokens one row at a time (its
    compile time grows with the tokens of a call), row r routed by
    ``ids[:, r]`` ((L, R, S, k) expert ids): {logits (R, S, V), routes
    and probs (L, R*S, ...)} on the host."""
    import jax
    from repro.models import moe_reference as R
    outs = [jax.device_get(R.forward(cfg, params, tokens[r:r + 1], ids[:, r],
                                     control=control))
            for r in range(tokens.shape[0])]
    return {"logits": np.concatenate([o["logits"] for o in outs]),
            "routes": np.concatenate([o["routes"] for o in outs], axis=1),
            "probs": np.concatenate([o["probs"] for o in outs], axis=1)}


def _logit_gap(got, want):
    """Largest |got - want| over the spread of ``want``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / want.std())


def _grad_gap(grads, ref_grads):
    return max(float(np.linalg.norm(_flat(a) - _flat(b))
                     / np.linalg.norm(_flat(b)))
               for a, b in zip(grads, ref_grads))


def check(wl, cfg, seed, log=print):
    import jax
    import jax.numpy as jnp
    from repro.data.partition import sample_prompt_block
    from repro.fed import api
    from repro.models import moe_reference as R
    from repro.models import transformer
    from repro.models.common import tree_bytes
    from repro.rlhf import ppo
    from repro.rlhf.sampling import generate

    start = time.time()
    spec = cell.run_spec(wl, cfg, seed)
    st = api.plan(spec).build()
    tr = getattr(st, "trainer", st)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    params = tr.params
    # the largest weight of one layer: a stack of E experts' matrices
    largest = max(x[0].size * x.dtype.itemsize for x in
                  jax.tree_util.tree_leaves(params["slots"]))
    memory = {"after_build_in_use": stats.get("bytes_in_use"),
              "after_build_peak": stats.get("peak_bytes_in_use"),
              "weights": tree_bytes(params),
              "largest_layer_weight": largest}
    log(f"memory after build: {memory}, {time.time() - start:.0f} s",
        file=sys.stderr)

    c, b, p, n = (wl["n_clients"], wl["batch_size"], wl["prompt_len"],
                  wl["max_new"])
    k, m, layers = cfg.moe.top_k, wl["n_objectives"], cfg.n_layers
    rows, s = c * b, p + n
    prompts = sample_prompt_block(tr._seeds_all, jnp.zeros(c, jnp.int32),
                                  tr._probs_all, b, p, cfg.vocab)
    keys = jax.random.split(jax.random.PRNGKey(cell.engine_seed(seed)), c)
    tokens, _, mask = jax.jit(jax.vmap(
        lambda w, pr, key: generate(cfg, w, pr, key, max_new=n),
        in_axes=(None, 0, 0)))(params, prompts, keys)      # (C, B, P+N)

    def gen(w, prompt, new):
        """The generation's logits (prefill's last, then every decode
        step's, fed as ``generate`` feeds them) and the routes of the
        sequence it saw; the replica's largest logit gap to
        ``decode_step``."""
        logits, cache = transformer.prefill(cfg, w, prompt, cache_len=s)
        feed = jnp.concatenate([prompt[:, -1:], new[:, :-1]], axis=1)

        def step(carry, tok):
            cache, twin = carry
            lg, cache = transformer.decode_step(cfg, w, cache, tok[:, None])
            lg2, twin, ids = program_decode_routes(cfg, w, twin,
                                                   tok[:, None])
            gap = jnp.abs(lg.astype(jnp.float32)
                          - lg2.astype(jnp.float32)).max()
            return (cache, twin), (lg, ids, gap)

        _, (steps, ids, gaps) = jax.lax.scan(step, (cache, cache), feed.T)
        out = jnp.concatenate([logits[:, -1:], jnp.moveaxis(steps, 0, 1)],
                              axis=1).astype(jnp.float32)
        routes = jnp.concatenate(
            [program_routes(cfg, w, prompt),
             jnp.moveaxis(ids[..., 0, :], 0, 2)], axis=2)  # (L, B, S, k)
        return out, routes, gaps.max()

    def per_rows(x):                      # (C, L, B, ...) -> (L, C*B, ...)
        return jnp.moveaxis(x, 0, 1).reshape(layers, rows, *x.shape[3:])

    new = tokens[..., p:]
    seen = jnp.concatenate([prompts, prompts[..., -1:], new[..., :-1]], -1)
    gen_logits, gen_ids, replica_gap = jax.jit(jax.vmap(
        gen, in_axes=(None, 0, 0)))(params, prompts, new)
    gen_logits = gen_logits.reshape(rows, n + 1, -1)
    gen_ids = per_rows(gen_ids)                              # (L, R, S, k)
    tf_logits, tf_ids = jax.jit(jax.vmap(
        lambda w, t: (transformer.forward_seq(cfg, w, t)["logits"].astype(
            jnp.float32), program_routes(cfg, w, t)),
        in_axes=(None, 0)))(params, tokens)
    log(f"program's generation and forward: {time.time() - start:.0f} s",
        file=sys.stderr)
    tf_logits = tf_logits.reshape(rows, s, -1)
    tf_ids = per_rows(tf_ids)

    # one client's gradients on its own rollout
    fc = spec.firm
    kl_coef = jnp.float32(fc.kl_coef_init)
    t0 = tokens[0]
    ref_lp = ppo.token_logprobs(tf_logits[:b], t0)
    # seeded terminal scores: a zero score (the synthetic reward models
    # give one often on 32 random tokens) leaves that objective's
    # whitened advantages as rounding noise, which no precision matches
    scores = jax.random.uniform(
        jax.random.fold_in(jax.random.PRNGKey(cell.engine_seed(seed)), 1),
        (b, m), minval=0.5, maxval=1.5)
    # old log-probabilities from the teacher-forced forward, not from the
    # generation: ``generate`` conditions its tokens on the last prompt
    # token twice (PERF.md, Open question 9), which leaves ratios on the
    # clip's edge, where a rounding difference switches a token's whole
    # gradient on or off; at ratio 1 every token is inside the clip
    batch = ppo.PPOBatch(t0, mask[0], ref_lp, ref_lp, scores)
    cs = tr.client_states[0]
    grads = jax.jit(lambda tr_, fz, cr, bt: ppo.per_objective_grads(
        cfg, fc, tr_, fz, cr, bt, kl_coef)[0])(
        cs.trainable, tr.frozen, cs.critic, batch)
    log(f"program's gradients: {time.time() - start:.0f} s", file=sys.stderr)

    # the reference and its control, both routed as the program routed
    readings = {}
    for name, control in (("reference", False), ("control", True)):
        tf = _reference_rows(cfg, params, tokens.reshape(rows, s), tf_ids,
                             control)
        gn = _reference_rows(cfg, params, seen.reshape(rows, s), gen_ids,
                             control)
        _, g = R.lora_grads(cfg, fc, cs.trainable, tr.frozen,
                            cs.critic["w"], R.Batch(*batch), kl_coef,
                            tf_ids[:, :b].reshape(layers, -1, k), control)
        readings[name] = (tf, gn, jax.device_get(g))
        log(f"{name}: {time.time() - start:.0f} s", file=sys.stderr)
    (ref_tf, ref_gen, ref_g) = readings["reference"]

    def compare(tf_routes, gen_routes, tf_lg, gen_lg, g):
        share_tf, margin_tf = _flips(tf_routes, ref_tf["routes"],
                                     ref_tf["probs"], k)
        share_gen, margin_gen = _flips(gen_routes, ref_gen["routes"],
                                       ref_gen["probs"], k)
        gap_tf = _logit_gap(tf_lg, ref_tf["logits"])
        gap_gen = _logit_gap(gen_lg, np.asarray(ref_gen["logits"])[:, p - 1:])
        return {"route_flip_share": max(share_tf, share_gen),
                "flip_margin_max": max(margin_tf, margin_gen),
                "logit_gap": max(gap_tf, gap_gen),
                "logit_gap_teacher_forced": gap_tf,
                "logit_gap_generation": gap_gen,
                "grad_gap": _grad_gap(g, ref_g)}

    ctl_tf, ctl_gen, ctl_g = readings["control"]
    out = {"seed": seed, "memory": memory,
           "decode_replica_gap": float(np.asarray(replica_gap).max()),
           "program": compare(tf_ids, gen_ids, tf_logits, gen_logits, grads),
           "control": compare(ctl_tf["routes"], ctl_gen["routes"],
                              ctl_tf["logits"],
                              np.asarray(ctl_gen["logits"])[:, p - 1:],
                              ctl_g)}
    out["limits"] = dict(LIMITS)
    out["program_fails"] = [x for x, lim in LIMITS.items()
                            if not out["program"][x] <= lim]
    out["control_fails"] = [x for x, lim in LIMITS.items()
                            if not out["control"][x] <= lim]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    wl, _, cfg = cell.load(args.workload)
    from repro.launch import compile_cache
    compile_cache.enable()
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = check(wl, cfg, seed)
        gc.collect()                       # the seed's trainer and weights
        line = json.dumps(out)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        ok = ok and not out["program_fails"] and bool(out["control_fails"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
