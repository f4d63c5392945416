"""Federated alignment simulation engine (paper §5 experimental loop).

Simulates the server + C clients protocol end-to-end at laptop scale:
generation with the current local policy, synthetic reward scoring, the
local update, FedAvg aggregation, and full metric / communication
accounting.  WHICH update runs is owned by the ``Algorithm`` objects in
``repro.fed.algorithms`` (paper Alg. 1, its β = 0 ablation, linear
scalarization, the server-centric MGDA baseline, and anything else the
registry holds) — this module contains no algorithm-name dispatch at
all: every path decision is a CAPABILITY query (``Algorithm.caps``)
resolved through ``repro.fed.api``, and the declarative front door
(``RunSpec -> plan() -> ExecutionPlan``) exposes the same decisions
for inspection before anything compiles.

All uplink/downlink traffic flows through the repro.comms codec layer
(EngineConfig.uplink_codec / downlink_codec registry specs): clients
upload encoded *deltas* against the decoded broadcast they trained from,
error-feedback residuals stay client-local, and the ledger records the
measured Payload bytes (int8 uplink ≈ 1/4 of raw f32).

Round execution (vectorized round engine)
-----------------------------------------
Two interchangeable local-phase paths:

* **vectorized** (default, ``EngineConfig.vectorized_clients``):
  participant ``ClientState``s are held as ONE pytree with a leading
  client axis; prompt sampling (``data.partition.sample_prompt_block``),
  rollout generation, reward scoring (banded, per-client parameters),
  reference logprobs and the local update are all ``jax.vmap``ed over
  that axis, and the K local steps run under one ``jax.lax.scan`` — the
  entire local phase is a single jitted dispatch with the stacked state
  donated.  Per-step metrics (stacked λ / KL / rewards) stay
  device-resident and transfer to host once per round.  The client→server
  delta and FedAvg are single batched tree ops over the stacked axis.
* **per-client loop**: the original Python loop (C × K dispatches), kept
  for equivalence testing and as the capability fallback.

vmap groups clients by IDENTICAL static config via a *cohort plan*
(repro.fed.sched.cohort): participants partition into groups with equal
static ``FIRMConfig`` (preference lifted to a traced (C, M) array when
``client_preferences`` is set), and each cohort runs as one vmapped
program — e.g. heterogeneous per-client ``client_local_steps``
(FedMOA-style rates) costs one dispatch per distinct K.  Generation
keys are drawn in the canonical loop order (step-major over all
participants) and sliced per cohort, so multi-cohort rounds stay
equivalent to the per-client loop.  Algorithms declaring
``single_cohort_required`` (a lock-step per-step server exchange) fall
back to the loop when configs diverge; algorithms whose server exchange
is host-driven (``traced_server_exchange=False``) route the vectorized
phase through their own ``exchange_phase_vectorized`` hook.  The uplink
codec runs at a *stacked* Payload boundary
(``Codec.roundtrip_stacked``): quantize codecs encode all C client
deltas in one batched kernel dispatch, byte-identical to per-client
encodes; for ``+ef`` codecs that boundary is one program that stacks
the per-client residuals and keys and returns each client's wire
buffers and new residual, so the host neither stacks nor slices.

On the per-round vectorized path every array operation of a round runs
inside a named program (``repro.obs.jitwatch``), with no eager op
between them: the downlink round trip is one program that also draws
the round's first key; ``vec_round`` draws the K x P generation keys
and then the P uplink keys from the main stream (the order sequential
``_next_key`` calls give, so the loop, cohort, fused and scheduler
paths consume the same stream), gathers the participants' inputs,
advances the prompt cursors it keeps on the device and reduces the
summary means.  The aggregation's zero staleness and exponent are made
once.

Participation sampling draws from a NAMED PRNG stream keyed on
(seed, round index), independent of how many keys generation / codecs
consumed — so the scheduler subsystem's deadline over-selection and
dropout (repro.fed.sched) reproduce the same client draws across
policies.

Fused multi-round execution
---------------------------
``EngineConfig.fused_rounds = R`` lifts the WHOLE round — participation
fold-in, downlink broadcast, the vectorized local phase, delta
extraction, the stacked uplink roundtrip, and the FedAvg aggregate —
into a round-level ``jax.lax.scan``: R rounds run as ONE jitted dispatch
with ONE host transfer at the end of the chunk (see ``FusedCarry`` for
the donated carry layout and ``_jit_fused_rounds`` for the body).  The
codecs run through their traced contract (``repro.comms``:
``roundtrip_traced*`` with explicit array state, ``nbytes_static`` byte
accounting), so the comms ledger and the scheduler's time models keep
exact bytes with zero per-round host syncs.  Results are bit-identical
to the per-round path: the body replicates ``run_round``'s PRNG split
sequence exactly, and the error-feedback residual is computed in the
same jitted composition on both paths (XLA contracts the dequantize
multiply into the residual subtract; doing it identically everywhere is
what keeps the trajectories exact).  ``run()`` chunks the horizon by R
when ``api.resolve_fused`` grants it — the algorithm declares
``fusable`` (which requires a traced server exchange), the population
forms one cohort, and both codecs support the traced contract — and
falls back to per-round execution otherwise; the ``sync`` scheduler
policy rides the fused path unchanged while the deadline/fedbuff
policies are host-driven between dispatches and stay per-round.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comms import make_codec
from repro.comms import codec as codec_lib
from repro.configs.base import FIRMConfig, ModelConfig
from repro.core import comms, drift, fedavg
from repro.data.partition import make_client_datasets, sample_prompt_block
from repro.fed import api as api_lib
from repro.fed.algorithms import client_configs, get_algorithm
from repro.fed.api import EngineConfig  # noqa: F401  (canonical home is api)
from repro.models import moe as moe_lib, transformer
from repro.models.common import merge_trainable, split_trainable, tree_size
from repro.obs import jitwatch
from repro.obs import records as obs_records
from repro.obs.metrics import MetricsPipeline
from repro.rlhf import local as local_lib
from repro.rlhf import ppo, rewards as rewards_lib
from repro.rlhf.sampling import generate


@functools.lru_cache(maxsize=None)
def _jit_ref_logprobs(cfg: ModelConfig):
    def ref_lp(ref_params, tokens):
        with jax.named_scope("ref_forward"):
            out = transformer.forward_seq(cfg, ref_params, tokens)
            return ppo.token_logprobs(out["logits"], tokens)
    return jitwatch.wrap("ref_logprobs", ref_lp)


def _make_round_fn(cfg: ModelConfig, cfc: FIRMConfig, kernel: str,
                   prompt_len: int, max_new: int, length_tol: int,
                   has_pref: bool):
    """One round's entire local phase as a pure function.

    vmap over the stacked client axis x lax.scan over the K local steps:
    sampling, generation, reward scoring, reference logprobs and the
    local update all fuse into one program.  ``kernel`` names the
    Algorithm whose ``traced_step`` runs inside the vmap (algorithms
    that lower to the same program share a kernel name and therefore a
    compile).  Jitted standalone by ``_jit_vec_round`` (the per-round
    path) and inlined into the round-level scan by
    ``_jit_fused_rounds``.
    """
    alg = get_algorithm(kernel)
    k_steps = cfc.local_steps
    m = cfc.n_objectives
    b = cfc.batch_size

    def round_fn(state, frozen, ref_trainable, seeds, counts0, probs,
                 band_h, band_x, gen_keys, pref, extra):
        # the frozen reference is the base with the initial adapters: the
        # base enters the program once, not a second time as the reference
        ref_params = merge_trainable(ref_trainable, frozen)

        def one_client(st, prompts, key, bh, bx, p):
            params = merge_trainable(st.trainable, frozen)
            tokens, old_lp, mask = generate(cfg, params, prompts, key,
                                            max_new=max_new)
            r = rewards_lib.score_batch_banded(bh, bx, tokens, mask, m,
                                               length_tol)
            with jax.named_scope("ref_forward"):
                ref_out = transformer.forward_seq(cfg, ref_params, tokens)
                ref_lp = ppo.token_logprobs(ref_out["logits"], tokens)
            batch = ppo.PPOBatch(tokens, mask, old_lp, ref_lp, r)
            return alg.traced_step(cfg, cfc, st, frozen, batch, p, extra)

        vstep = jax.vmap(one_client,
                         in_axes=(0, 0, 0, 0, 0, 0 if has_pref else None))

        def body(carry, xs):
            step_idx, keys_c = xs
            with jax.named_scope("sample_prompts"):
                prompts = sample_prompt_block(seeds, counts0 + step_idx,
                                              probs, b, prompt_len, cfg.vocab)
            new_state, metrics = vstep(carry, prompts, keys_c, band_h,
                                       band_x, pref)
            keep = {k: metrics[k] for k in ("lam", "rewards", "kl",
                                            "moe_counts") if k in metrics}
            return new_state, keep

        final, ms = jax.lax.scan(body, state,
                                 (jnp.arange(k_steps), gen_keys))
        return final, ms

    return round_fn


def _moe_stats(cfg: ModelConfig, ms, tokens: int):
    """The round's expert-layer statistics from the local phase's stacked
    metrics, the routed pairs of the policy forward per step, client,
    layer and expert: ``moe_max_load`` (``moe.max_load`` of the pairs
    summed over the K steps and the clients) and ``moe_rows_per_pair``
    (``moe.rows_per_pair`` of each step's forward, which folds every
    client's ``tokens`` tokens); {} for a model without expert blocks."""
    if "moe_counts" not in ms:
        return {}
    counts = ms["moe_counts"]                               # (K, C, L, E)
    k = cfg.moe.top_k
    return {"moe_max_load": moe_lib.max_load(counts.sum((0, 1))),
            "moe_rows_per_pair": moe_lib.rows_per_pair(
                counts.sum(1), counts.shape[1] * tokens * k, k)}


def _split_next(rng):
    """In-graph twin of ``FederatedTrainer._next_key``."""
    out = jax.random.split(rng)
    return out[0], out[1]


def _draw_keys(rng, rows: int, cols: int):
    """``rows x cols`` sequential ``_split_next`` draws, row-major:
    (advanced rng, (rows, cols, 2) keys)."""
    out = []
    for _ in range(rows):
        row = []
        for _ in range(cols):
            rng, k = _split_next(rng)
            row.append(k)
        out.append(jnp.stack(row))
    return rng, jnp.stack(out)


@functools.lru_cache(maxsize=None)
def _jit_vec_round(cfg: ModelConfig, cfc: FIRMConfig, kernel: str,
                   prompt_len: int, max_new: int, length_tol: int,
                   has_pref: bool):
    """The per-round dispatch of ``_make_round_fn`` (stacked state
    donated), with the round's array bookkeeping inside the program.

    ``keys`` is either the (K, P, 2) generation keys (the cohort path
    draws them across cohorts) or the main stream's key: then the
    program draws the K x P generation keys step-major and the P uplink
    keys after them, exactly as sequential ``_next_key`` calls would, and
    returns the uplink keys and the advanced stream key.  With ``idx``
    (the participants, when they are a strict subset) the per-client
    inputs are the whole population's and are gathered here; ``counts``
    comes back advanced by K for the participants.  The staged summary
    means are computed here too (one axis at a time, as in the fused
    round scan, so the two paths stay bit-identical).
    """
    round_fn = _make_round_fn(cfg, cfc, kernel, prompt_len, max_new,
                              length_tol, has_pref)
    k_steps = cfc.local_steps
    tokens = cfc.batch_size * (prompt_len + max_new)     # a client's

    def vec_round(state, frozen, ref_trainable, seeds, counts, probs,
                  band_h, band_x, keys, pref, extra, idx=None):
        counts0 = counts
        if idx is not None:
            seeds, counts0, probs = seeds[idx], counts[idx], probs[idx]
            band_h, band_x = band_h[idx], band_x[idx]
            pref = pref[idx] if has_pref else None
        rng = up_keys = None
        if keys.ndim == 1:
            with jax.named_scope("keys"):
                n_part = counts0.shape[0]
                rng, gen_keys = _draw_keys(keys, k_steps, n_part)
                rng, up_keys = _draw_keys(rng, 1, n_part)
                up_keys = up_keys[0]
        else:
            gen_keys = keys
        final, ms = round_fn(state, frozen, ref_trainable, seeds, counts0,
                             probs, band_h, band_x, gen_keys, pref, extra)
        with jax.named_scope("summary"):
            stats = (ms["lam"][-1],                            # (P, M)
                     ms["rewards"].mean(0).mean(0),
                     ms["kl"].mean(0).mean(0),
                     ms["rewards"].mean(0),                    # (P, M)
                     _moe_stats(cfg, ms, tokens))
        counts = (counts + k_steps if idx is None
                  else counts.at[idx].add(k_steps))
        return final, stats, up_keys, rng, counts

    return jitwatch.wrap(f"vec_round[{kernel}]", vec_round,
                         donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jit_downlink(downlink_spec: str):
    """The downlink broadcast as one program: the stream's next key, the
    codec's round trip of the flattened tree (with the state update of a
    stateful codec, host-format state in and out) and the unflatten."""
    codec = make_codec(downlink_spec)

    def downlink(rng, tree, state):
        with jax.named_scope("downlink_codec"):
            rng, key = _split_next(rng)
            flat, spec = codec_lib.tree_to_flat(tree)
            dec, state = codec.roundtrip_traced(
                flat, codec.init_state_traced(flat.size, state), key=key)
            return (rng, codec_lib.flat_to_tree(dec, spec),
                    codec.state_to_host(state))

    return jitwatch.wrap("downlink_roundtrip", downlink, counted=False)


@functools.lru_cache(maxsize=None)
def _jit_participants(n_clients: int, n: int):
    """The round's participants from the named stream, as one program."""
    def participants(base, round_idx):
        with jax.named_scope("participants"):
            return jax.random.choice(jax.random.fold_in(base, round_idx),
                                     n_clients, (n,), replace=False)
    return jitwatch.wrap("participants", participants, counted=False)


@functools.lru_cache(maxsize=None)
def _jit_unstack(n: int):
    return jitwatch.wrap(
        "unstack", lambda tree: tuple(fedavg.unstack_tree(tree, n)))


_stack_trees_jit = jitwatch.wrap(
    "stack_trees", lambda *trees: fedavg.stack_trees(trees))


def _delta_flat(stacked, anchor):
    """All C client deltas vs the broadcast anchor flattened in ONE
    batched tree op -> (C, d) f32; row c is bit-identical to
    tree_to_flat(delta_c)."""
    with jax.named_scope("delta"):
        return jnp.concatenate(
            [(a - b).astype(jnp.float32).reshape(a.shape[0], -1)
             for a, b in zip(jax.tree_util.tree_leaves(stacked),
                             jax.tree_util.tree_leaves(anchor))], axis=1)


_delta_flat_jit = jitwatch.wrap("delta_flat", _delta_flat)


@functools.lru_cache(maxsize=None)
def _jit_flat_aggregate(spec):
    """Staleness-weighted FedAvg of the decoded flat deltas over the
    stacked client axis + apply to the anchor, in one dispatch (one
    unflatten total instead of one per client).  Zero staleness gives
    exactly uniform 1/C weights, so the synchronous round and the async
    scheduler's zero-staleness barrier produce bit-identical aggregates.
    """

    def fn(anchor, flats, staleness, pow):
        with jax.named_scope("aggregate"):
            w = fedavg.staleness_weights(staleness, pow)
            agg = fedavg.fedavg_flat_weighted(flats, w)
            return jax.tree_util.tree_map(lambda b, d: b + d, anchor,
                                          codec_lib.flat_to_tree(agg, spec))

    return jitwatch.wrap("flat_aggregate", fn)


# operands the aggregation takes every round, made once: the synchronous
# round's zero staleness and the staleness exponent
@functools.lru_cache(maxsize=None)
def _zeros_f32(n: int):
    return jnp.zeros(n, jnp.float32)


@functools.lru_cache(maxsize=None)
def _f32(x: float):
    return jnp.float32(x)


def _summary_device_fn(lams, rewards_mean, kl_mean, stacked_trainable,
                       rewards_pc, moe=None):
    """All round-summary statistics computed device-side; the engine does
    ONE host transfer per round (jax.device_get of this dict)."""
    with jax.named_scope("summary"):
        out = {
            "rewards": rewards_mean,
            "lam_mean": lams.mean(0),
            "lam_disagreement":
                drift.lambda_disagreement(lams)["pairwise_mean"],
            "param_drift": drift.param_drift_stacked(stacked_trainable),
            "kl": kl_mean,
            "per_client_lam": lams,
            "rewards_per_client": rewards_pc,
        }
        out.update(moe or {})
        return out


_summary_device = jitwatch.wrap("summary_device", _summary_device_fn)


class LocalPhaseResult(NamedTuple):
    """What every local-phase path (loop / vec / cohorts) hands back."""
    lams: jnp.ndarray                # (P, M) final per-client λ
    rewards_mean: jnp.ndarray        # (M,) mean over all client-steps
    kl_mean: jnp.ndarray             # scalar
    stacked_trainable: object        # pytree with leading (P,) client axis
    rewards_pc: jnp.ndarray          # (P, M) per-client mean over steps
    # (P, 2) uplink keys, when the phase drew them in-graph after the
    # generation keys; None when the caller draws them with _next_key
    up_keys: Optional[jnp.ndarray] = None
    # the policy forward's expert-layer statistics (``_moe_stats``),
    # where the phase's program measured them
    moe: Optional[dict] = None


class FusedCarry(NamedTuple):
    """Donated carry of the round-level ``lax.scan`` (fused_rounds path).

    Everything a round mutates rides the scan carry as arrays, so R
    rounds are ONE dispatch with zero host round-trips in between:

      states    stacked ClientState for ALL C clients (leading (C,) axis;
                critic/opt/λ/KL/step persist across rounds, trainable is
                overwritten by each round's decoded broadcast)
      ul_state  stacked traced uplink-codec state — e.g. the (C, d) error
                feedback residuals; () for stateless codecs
      dl_state  traced downlink-codec state — e.g. the DeltaCodec
                (reference reconstruction, inner state) pair
      counts    (C,) per-client prompt-stream cursors
      rng       the MAIN PRNG stream key; the body replicates run_round's
                exact split sequence (downlink key -> K x P generation
                keys step-major -> P uplink keys) for bit parity with the
                per-round path

    The server parameters are carried too but enter the jit as a
    NON-donated argument: at trainer init they alias ``ref_trainable``
    leaves, which must survive the call.
    """
    states: object
    ul_state: object
    dl_state: object
    counts: jnp.ndarray
    rng: jnp.ndarray


@functools.lru_cache(maxsize=None)
def _jit_fused_rounds(cfg: ModelConfig, cfc: FIRMConfig, kernel: str,
                      prompt_len: int, max_new: int, length_tol: int,
                      has_pref: bool, uplink_spec: str, downlink_spec: str,
                      spec, n_clients: int, n_part: int):
    """R federated rounds as ONE jitted program (round-level lax.scan).

    The scan body is a faithful in-graph transcription of ``run_round``
    on the vectorized path: participation fold-in from the named stream,
    downlink roundtrip (traced codec contract), the cohort local phase
    (``_make_round_fn``), batched delta extraction, stacked uplink
    roundtrip with carried codec state, and the weighted FedAvg
    aggregate.  Per-round summary statistics accumulate as stacked scan
    outputs — the caller does ONE host transfer per R rounds.  R itself
    stays out of this builder's cache key (jit specializes on the length
    of ``round_idxs``), so trailing partial chunks reuse the builder.
    """
    round_fn = _make_round_fn(cfg, cfc, kernel, prompt_len, max_new,
                              length_tol, has_pref)
    ul = make_codec(uplink_spec)
    dl = make_codec(downlink_spec)
    k_steps = cfc.local_steps
    tokens = cfc.batch_size * (prompt_len + max_new)     # a client's
    full = n_part >= n_clients

    def fused(carry, global_tr, round_idxs, part_base, frozen, ref_trainable,
              seeds_all, probs_all, band_h_all, band_x_all, pref_all,
              extra):

        def body(c, round_idx):
            (states, g_tree, ul_state, dl_state, counts, rng) = c
            rng, dl_key = _split_next(rng)
            flat_g = jnp.concatenate(
                [l.astype(jnp.float32).reshape(-1)
                 for l in jax.tree_util.tree_leaves(g_tree)])
            bcast_flat, dl_state = dl.roundtrip_traced(flat_g, dl_state,
                                                       key=dl_key)
            broadcast = codec_lib.flat_to_tree(bcast_flat, spec)

            if full:
                idx = jnp.arange(n_clients, dtype=jnp.int32)
                seeds, probs = seeds_all, probs_all
                band_h, band_x = band_h_all, band_x_all
                pref = pref_all if has_pref else None
                counts0 = counts
                part_states = states
                ul_part = ul_state
            else:
                pk = jax.random.fold_in(part_base, round_idx)
                idx = jnp.sort(jax.random.choice(
                    pk, n_clients, (n_part,), replace=False)
                ).astype(jnp.int32)
                seeds, probs = seeds_all[idx], probs_all[idx]
                band_h, band_x = band_h_all[idx], band_x_all[idx]
                pref = pref_all[idx] if has_pref else None
                counts0 = counts[idx]
                part_states = jax.tree_util.tree_map(
                    lambda x: x[idx], states)
                ul_part = jax.tree_util.tree_map(
                    lambda x: x[idx], ul_state)

            # every participant adopts the decoded broadcast
            part_states = part_states._replace(
                trainable=jax.tree_util.tree_map(
                    lambda b: jnp.broadcast_to(b, (n_part,) + b.shape),
                    broadcast))

            # generation keys in the canonical loop order (step-major)
            gks = []
            for _k in range(k_steps):
                row = []
                for _p in range(n_part):
                    rng, kk = _split_next(rng)
                    row.append(kk)
                gks.append(jnp.stack(row))
            gen_keys = jnp.stack(gks)

            new_part, ms = round_fn(part_states, frozen, ref_trainable,
                                    seeds, counts0, probs, band_h,
                                    band_x, gen_keys, pref, extra)

            flat_deltas = _delta_flat(new_part.trainable, broadcast)
            up_keys = []
            for _p in range(n_part):
                rng, kk = _split_next(rng)
                up_keys.append(kk)
            decoded, ul_part = ul.roundtrip_traced_stacked(
                flat_deltas, ul_part, keys=jnp.stack(up_keys))

            with jax.named_scope("aggregate"):
                w = fedavg.staleness_weights(
                    jnp.zeros(n_part, jnp.float32), jnp.float32(0.5))
                agg = fedavg.fedavg_flat_weighted(decoded, w)
                g_tree = jax.tree_util.tree_map(
                    lambda b, d: b + d, broadcast,
                    codec_lib.flat_to_tree(agg, spec))

            if full:
                states = new_part
                ul_state = ul_part
                counts = counts + k_steps
            else:
                states = jax.tree_util.tree_map(
                    lambda f, u: f.at[idx].set(u), states, new_part)
                ul_state = jax.tree_util.tree_map(
                    lambda f, u: f.at[idx].set(u), ul_state, ul_part)
                counts = counts.at[idx].add(k_steps)

            lams = ms["lam"][-1]                              # (P, M)
            with jax.named_scope("summary"):
                ys = {
                    # staged means match _local_phase_vectorized
                    # bit-for-bit (see the comment there)
                    "rewards": ms["rewards"].mean(0).mean(0),
                    "lam_mean": lams.mean(0),
                    "lam_disagreement":
                        drift.lambda_disagreement(lams)["pairwise_mean"],
                    "param_drift":
                        drift.param_drift_stacked(new_part.trainable),
                    "kl": ms["kl"].mean(0).mean(0),
                    "per_client_lam": lams,
                    "rewards_per_client": ms["rewards"].mean(0),
                    "participants": idx,
                }
                ys.update(_moe_stats(cfg, ms, tokens))
            return (states, g_tree, ul_state, dl_state, counts, rng), ys

        init = (carry.states, global_tr, carry.ul_state, carry.dl_state,
                carry.counts, carry.rng)
        (states, g_tree, ul_state, dl_state, counts, rng), ys = \
            jax.lax.scan(body, init, round_idxs)
        return (FusedCarry(states, ul_state, dl_state, counts, rng),
                g_tree, ys)

    return jitwatch.wrap(f"fused_rounds[{kernel}]", fused,
                         donate_argnums=(0,))


class FederatedTrainer:
    def __init__(self, cfg: ModelConfig, fc: FIRMConfig,
                 ec: Optional[EngineConfig] = None,
                 plan: Optional["api_lib.ExecutionPlan"] = None):
        # default must be constructed per instance: a shared EngineConfig
        # default would leak mutations across trainers
        ec = EngineConfig() if ec is None else ec
        self.cfg, self.fc, self.ec = cfg, fc, ec
        # the Algorithm object owns the local-step machinery and the
        # capability declaration every path decision queries; validate
        # (fc, ec) against it before any expensive initialization
        self.algorithm = get_algorithm(ec.algorithm)
        self.algorithm.validate(fc, ec)
        key = jax.random.PRNGKey(ec.seed)
        self.params = transformer.init_params(cfg, key)
        trainable, frozen = split_trainable(self.params)
        self.frozen = frozen
        self.ref_params = self.params                     # frozen reference
        # ... and its adapters, which the round programs merge with the
        # frozen base they already take
        self.ref_trainable = trainable
        self.global_trainable = trainable
        self.client_states = [
            local_lib.init_client_state(trainable, fc.n_objectives,
                                        cfg.d_model, fc.kl_coef_init)
            for _ in range(fc.n_clients)]
        self.datasets = make_client_datasets(
            fc.n_clients, cfg.vocab, ec.prompt_len,
            alpha=ec.dirichlet_alpha, seed=ec.seed)
        # static per-client sampler inputs, cached for the vmapped block
        # sampler (only the per-client counts change between rounds)
        self._seeds_all = jnp.asarray([ds.seed for ds in self.datasets],
                                      jnp.int32)
        self._probs_all = jnp.stack([ds.topic_probs
                                     for ds in self.datasets])
        # shared TreeSpec of the per-client delta (the uplink's flat
        # Payload boundary)
        leaves, treedef = jax.tree_util.tree_flatten(trainable)
        self._delta_spec = codec_lib.TreeSpec(
            treedef, tuple(l.shape for l in leaves),
            tuple(l.dtype for l in leaves))
        self._length_tol = max(4, ec.max_new // 2)
        self.reward_fns = []
        bands = []
        for c in range(fc.n_clients):
            variant = ("alt" if ec.heterogeneous_rms and
                       c >= fc.n_clients // 2 else "default")
            self.reward_fns.append(rewards_lib.make_reward_fns(
                cfg.vocab, fc.n_objectives, variant=variant,
                length_tolerance=self._length_tol))
            bands.append(rewards_lib.variant_bands(cfg.vocab, variant))
        # per-client reward-band parameters, stacked for the vmapped scorer
        self._bands_h = jnp.stack([bh for bh, _ in bands])
        self._bands_x = jnp.stack([bx for _, bx in bands])
        self.ledger = comms.CommsLedger()
        # comms codecs: one stateless codec per link; per-client error
        # feedback residuals stay in client-indexed slots here
        self.uplink_codec = make_codec(ec.uplink_codec)
        self.downlink_codec = make_codec(ec.downlink_codec)
        self._uplink_state = [None] * fc.n_clients
        self._downlink_state = None
        self.d_trainable = tree_size(trainable)
        self.history: List[dict] = []
        self._rng = jax.random.PRNGKey(ec.seed + 1)
        # named PRNG stream for participation sampling: keyed on
        # (seed, round index) only, never on how many keys the main
        # stream consumed — deadline over-selection and dropout in the
        # scheduler reproduce the same client draws across policies
        self._part_rng_base = jax.random.fold_in(
            jax.random.PRNGKey(ec.seed + 1), 0x5ced)
        self._round_idx = 0
        # per-client configs expanded through the algorithm (pluralistic
        # preferences, FedMOA-style heterogeneous local-step rates)
        self._client_fcs = client_configs(self.algorithm, fc)
        self._jit_steps = [self.algorithm.local_step_fn(cfg, cfc)
                           for cfc in self._client_fcs]
        self._jit_ref_lp = partial(_jit_ref_logprobs(cfg), self.ref_params)
        self._stacked_pref = (
            jnp.asarray(fc.client_preferences, jnp.float32)
            if fc.client_preferences is not None else None)
        # engine-level jitted dispatch counter (round_throughput benchmark)
        self.jit_dispatches = 0
        # engine-owned device->host summary transfers: ONE per round on
        # the per-round paths, ONE per chunk on the fused path (the plan
        # auditor and the obs overhead test read this)
        self.host_transfers = 0
        # telemetry write path: every round summary fans out through
        # this pipeline (EngineConfig.metrics_sink names extra sinks;
        # an in-memory sink is always attached)
        self.obs = MetricsPipeline.from_spec(ec.metrics_sink)
        # last round's uplink payloads (per-round path only; offline
        # payload analysis, e.g. entropy estimates in codec_tradeoff)
        self._last_up_payloads: List = []
        # (host prompt cursors, their device copy), reused while the two
        # agree instead of being sent again each round
        self._counts = (None, None)
        # the declarative mirror of this trainer's path decisions; built
        # through the same capability resolution the methods below use
        self.plan = plan if plan is not None else api_lib.plan(
            api_lib.RunSpec(model=cfg, firm=fc, engine=ec),
            d_trainable=self.d_trainable)

    # ------------------------------------------------------------------
    def _fc_for_algorithm(self) -> FIRMConfig:
        return self.algorithm.resolve_config(self.fc)

    def _next_key(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _make_batch(self, c: int) -> ppo.PPOBatch:
        prompts = self.datasets[c].next_batch(self.fc.batch_size)
        params = merge_trainable(self.client_states[c].trainable,
                                 self.frozen)
        tokens, old_lp, mask = generate(self.cfg, params, prompts,
                                        self._next_key(),
                                        max_new=self.ec.max_new)
        self.jit_dispatches += 1
        r = rewards_lib.score_batch(self.reward_fns[c], tokens, mask)
        ref_lp = self._jit_ref_lp(tokens)
        self.jit_dispatches += 1
        return ppo.PPOBatch(tokens, mask, old_lp, ref_lp, r)

    # ------------------------------------------------------------------
    def _sample_participants(self, n: Optional[int] = None,
                             round_idx: Optional[int] = None) -> List[int]:
        """Draw this round's participants from the named stream.

        ``n`` overrides the participation-derived count (the deadline
        policy over-selects); same (seed, round) -> same draw no matter
        what else consumed PRNG keys in between.
        """
        fc = self.fc
        if n is None:
            n = max(1, int(round(fc.participation * fc.n_clients)))
        if n >= fc.n_clients:
            return list(range(fc.n_clients))
        r = self._round_idx if round_idx is None else round_idx
        idx = _jit_participants(fc.n_clients, n)(self._part_rng_base, r)
        return sorted(np.asarray(idx).tolist())

    def _local_phase_mode(self, participants: List[int]):
        """Pick the round's local-phase path: ("vec"|"cohort"|"loop", plan).

        Pure capability resolution — see ``api.resolve_local_mode`` for
        the rules (shared with the plan-time front door).
        """
        mode, plan, _ = api_lib.resolve_local_mode(
            self.algorithm, self._client_fcs, participants,
            vectorized_clients=self.ec.vectorized_clients,
            lift_preference=self._stacked_pref is not None)
        return mode, plan

    def _use_vectorized(self) -> bool:
        """Back-compat probe: does any vmapped path serve a full round?"""
        mode, _ = self._local_phase_mode(list(range(self.fc.n_clients)))
        return mode != "loop"

    def _fused_mode(self):
        """(eligible, cohort cfc) for the fused multi-round program —
        ``api.resolve_fused`` over the full population's local mode."""
        mode, plan = self._local_phase_mode(list(range(self.fc.n_clients)))
        ok, _ = api_lib.resolve_fused(self.algorithm, mode,
                                      self.uplink_codec,
                                      self.downlink_codec)
        if not ok:
            return False, None
        return True, plan[0].cfc

    # ------------------------------------------------------------------
    def _aggregate_flat(self, anchor, flats, staleness,
                        staleness_pow: float = 0.5):
        """(anchor tree, (C, d) decoded deltas, (C,) staleness) -> new
        params; the single server-side aggregation dispatch.  The async
        scheduler calls this directly with nonzero staleness."""
        out = _jit_flat_aggregate(self._delta_spec)(
            anchor, flats, jnp.asarray(staleness, jnp.float32),
            _f32(staleness_pow))
        self.jit_dispatches += 1
        return out

    def _prompt_counts(self):
        """Every client's prompt-stream cursor on the device, (C,) int32.

        The vectorized round program returns the advanced cursors, so
        while the host cursors match the ones it returned last, they are
        not sent again; any other path that moves a cursor (the loop, a
        fused chunk, a host-exchange algorithm) makes them differ."""
        host = tuple(ds._count for ds in self.datasets)
        if self._counts[0] != host:
            self._counts = (host, jnp.asarray(np.asarray(host, np.int32)))
        return self._counts[1]

    def run_round(self, participants: Optional[List[int]] = None) -> dict:
        # the host phases are spans on the profiler's clock while a JAX
        # profiler trace runs (``jitwatch.span``), so an idle gap on the
        # device reads as the phase the host was in
        with jitwatch.span("round", round=self._round_idx):
            return self._run_round(participants)

    def _run_round(self, participants: Optional[List[int]]) -> dict:
        fc = self._fc_for_algorithm()
        if participants is None:
            participants = self._sample_participants()
        round_idx = self._round_idx
        dispatch0 = self.jit_dispatches
        # broadcast θ_t through the downlink codec; every client receives
        # (and trains from) the same decoded broadcast
        with jitwatch.span("round/downlink"):
            broadcast, down_nbytes = self._downlink()
            self.ledger.down_bytes += len(participants) * down_nbytes

        with jitwatch.span("round/local_phase"):
            mode, plan = self._local_phase_mode(participants)
            if mode == "vec":
                # the cohort's shared config, not the base fc: a UNIFORM
                # client_local_steps override still forms one cohort but
                # its K differs from fc.local_steps
                res = self._local_phase_vectorized(plan[0].cfc,
                                                   participants, broadcast)
            elif mode == "cohort":
                res = self._local_phase_cohorts(plan, participants,
                                                broadcast)
            else:
                res = self._local_phase_loop(fc, participants, broadcast)

        # participating clients transmit adapted-param deltas through the
        # uplink codec (residuals stay client-local); the delta against
        # the broadcast anchor flattens in one batched tree op over the
        # stacked axis, the codec encodes all clients at the stacked
        # (flat) Payload boundary — one program for the error-feedback
        # codecs — and the server aggregates the decoded (C, d) matrix in
        # one matvec + single unflatten
        with jitwatch.span("round/uplink"):
            flat_deltas = _delta_flat_jit(res.stacked_trainable, broadcast)
            self.jit_dispatches += 1
            up_keys = res.up_keys
            if up_keys is None:
                with jitwatch.span("round/keys"):
                    up_keys = [self._next_key() for _ in participants]
            payloads, new_states, decoded = \
                self.uplink_codec.roundtrip_stacked(
                    flat_deltas, self._delta_spec,
                    [self._uplink_state[c] for c in participants],
                    keys=up_keys)
            for ci, c in enumerate(participants):
                self._uplink_state[c] = new_states[ci]
                self.ledger.send_up(payloads[ci])
        # kept for offline payload analysis (entropy-coded size estimates
        # in benchmarks/codec_tradeoff.py) — references only, no copies
        self._last_up_payloads = payloads
        with jitwatch.span("round/aggregate"):
            self.global_trainable = self._aggregate_flat(
                broadcast, decoded, _zeros_f32(len(participants)))
        self.ledger.next_round()
        self._round_idx += 1

        # metrics were accumulated on device; ONE host transfer per round
        with jitwatch.span("round/summary"):
            stats = _summary_device(res.lams, res.rewards_mean,
                                    res.kl_mean, res.stacked_trainable,
                                    res.rewards_pc, res.moe)
            self.jit_dispatches += 1
            host = jax.device_get(stats)
        self.host_transfers += 1
        summary = obs_records.round_summary(
            stats=host,
            comm_bytes=self.ledger.total,
            up_bytes=self.ledger.up_bytes,
            down_bytes=self.ledger.down_bytes,
            participants=participants,
            dispatches=self.jit_dispatches - dispatch0,
            # per-client wire/work facts the scheduler's time model reads
            up_nbytes=[int(p.nbytes) for p in payloads],
            down_nbytes=down_nbytes,
            local_steps=[self._client_fcs[c].local_steps
                         for c in participants],
            cohorts=len(plan) if plan is not None else 0,
        )
        self.history.append(summary)
        self.obs.emit_round(summary, round=round_idx)
        return summary

    def _downlink(self):
        """θ_t through the downlink codec: (decoded broadcast, wire bytes
        per receiver).  A codec whose traced round trip matches its host
        round trip runs as one program that also draws the stream's next
        key; any other codec draws the key and round-trips on the host."""
        codec = self.downlink_codec
        if codec.traced_matches_host:
            self._rng, broadcast, self._downlink_state = _jit_downlink(
                self.ec.downlink_codec)(self._rng, self.global_trainable,
                                        self._downlink_state)
            return broadcast, codec.nbytes_static(self.d_trainable)
        with jitwatch.span("round/keys"):
            key = self._next_key()
        payload, self._downlink_state, broadcast = codec.roundtrip(
            self.global_trainable, self._downlink_state, key=key)
        return broadcast, comms.measured_bytes(payload)

    # ------------------------------------------------- fused rounds path
    def run_rounds_fused(self, rounds: int) -> List[dict]:
        """R rounds as ONE jitted dispatch + ONE host transfer.

        See ``FusedCarry`` for the scan-carry layout and
        ``_jit_fused_rounds`` for the round body.  Byte accounting uses
        the codecs' exact ``nbytes_static`` sizes (no payloads are
        materialized), and the per-round summaries match ``run_round``'s
        except that ``dispatches`` is the chunk total amortized per round
        and a ``fused`` key records the chunk length.
        """
        ok, cfc = self._fused_mode()
        if not ok:
            raise ValueError(
                "fused_rounds requires a fusable algorithm (traced server "
                "exchange, vmap-safe local step), one full-population "
                "static-config cohort, and codecs supporting the traced "
                "contract; use run()/run_round() instead")
        fc = self.fc
        c_all = fc.n_clients
        n_part = min(c_all, max(1, int(round(fc.participation * c_all))))
        has_pref = self._stacked_pref is not None
        cfc_t = (dataclasses.replace(cfc, preference=None)
                 if has_pref else cfc)
        extra = self.algorithm.traced_extra(cfc, self.ec)
        d = self.d_trainable
        dispatch0 = self.jit_dispatches

        # stacking copies every per-client buffer, so the donated carry
        # never aliases live host state (client_states / ref_params)
        stacked_states = _stack_trees_jit(*self.client_states)
        self.jit_dispatches += 1
        carry = FusedCarry(
            states=stacked_states,
            ul_state=self.uplink_codec.init_states_traced(
                d, self._uplink_state),
            dl_state=self.downlink_codec.init_state_traced(
                d, self._downlink_state),
            counts=jnp.asarray([ds._count for ds in self.datasets],
                               jnp.int32),
            rng=self._rng)
        round_idxs = jnp.arange(self._round_idx, self._round_idx + rounds,
                                dtype=jnp.int32)
        fn = _jit_fused_rounds(self.cfg, cfc_t, self.algorithm.kernel,
                               self.ec.prompt_len, self.ec.max_new,
                               self._length_tol, has_pref,
                               self.ec.uplink_codec, self.ec.downlink_codec,
                               self._delta_spec, c_all, n_part)
        carry, new_global, ys = fn(
            carry, self.global_trainable, round_idxs, self._part_rng_base,
            self.frozen, self.ref_trainable, self._seeds_all,
            self._probs_all,
            self._bands_h, self._bands_x, self._stacked_pref, extra)
        self.jit_dispatches += 1

        # ONE host transfer for the whole chunk's metrics
        host = jax.device_get({"ys": ys, "counts": carry.counts})
        self.host_transfers += 1
        self.client_states = list(_jit_unstack(c_all)(carry.states))
        self.jit_dispatches += 1
        self.global_trainable = new_global
        self._uplink_state = self.uplink_codec.states_to_host(
            carry.ul_state, c_all)
        self._downlink_state = self.downlink_codec.state_to_host(
            carry.dl_state)
        self._rng = carry.rng
        for ci, ds in enumerate(self.datasets):
            ds._count = int(host["counts"][ci])
        self._round_idx += rounds

        up_static = self.uplink_codec.nbytes_static(d)
        down_static = self.downlink_codec.nbytes_static(d)
        per_round_dispatches = (self.jit_dispatches - dispatch0) / rounds
        ys_h = host["ys"]
        round0 = self._round_idx - rounds
        out = []
        for r in range(rounds):
            parts = [int(x) for x in ys_h["participants"][r]]
            p = len(parts)
            self.ledger.down_bytes += p * down_static
            self.ledger.up_bytes += p * up_static
            self.ledger.next_round()
            # per-round records derive from the chunk's stacked scan
            # outputs + static plan bytes: zero additional host syncs
            summary = obs_records.round_summary(
                stats={k: ys_h[k][r] for k in
                       ("rewards", "lam_mean", "lam_disagreement",
                        "param_drift", "kl", "per_client_lam",
                        "rewards_per_client", "moe_max_load",
                        "moe_rows_per_pair")
                       if k in ys_h},
                comm_bytes=self.ledger.total,
                up_bytes=self.ledger.up_bytes,
                down_bytes=self.ledger.down_bytes,
                participants=parts,
                dispatches=per_round_dispatches,
                up_nbytes=[up_static] * p,
                down_nbytes=down_static,
                local_steps=[cfc.local_steps] * p,
                cohorts=1,
                fused=rounds,
            )
            out.append(summary)
            self.history.append(summary)
            self.obs.emit_round(summary, round=round0 + r)
        return out

    # ------------------------------------------------- per-client loop path
    def _local_phase_loop(self, fc: FIRMConfig, participants: List[int],
                          broadcast):
        # the jitted local step donates its state argument, so every
        # participant must OWN its trainable buffers: adopt the broadcast
        # by copy, never by alias (the anchor must survive for the delta,
        # and clients must not share donated buffers)
        for c in participants:
            self.client_states[c] = self.client_states[c]._replace(
                trainable=jax.tree_util.tree_map(jnp.copy, broadcast))
        # the algorithm owns the loop body (step order, exchanges, the
        # per-entry metric dicts); the engine owns the common accounting
        round_metrics = self.algorithm.loop_phase(self, fc, participants)

        # metrics stay device-resident: stack on device, convert to host
        # once per round in run_round's summary
        last_lam = {m["client"]: m["lam"] for m in round_metrics
                    if "lam" in m}
        lams = jnp.stack([last_lam[c] for c in participants])
        rewards_mean = jnp.stack([m["rewards"]
                                  for m in round_metrics]).mean(0)
        kl_mean = jnp.stack([m["kl"] for m in round_metrics]).mean()
        rewards_pc = jnp.stack([
            jnp.stack([m["rewards"] for m in round_metrics
                       if m["client"] == c]).mean(0) for c in participants])
        stacked_tr = _stack_trees_jit(
            *[self.client_states[c].trainable for c in participants])
        self.jit_dispatches += 1
        return LocalPhaseResult(lams, rewards_mean, kl_mean, stacked_tr,
                                rewards_pc)

    # ------------------------------------------------- vectorized path
    def _local_phase_vectorized(self, fc: FIRMConfig,
                                participants: List[int], broadcast,
                                gen_keys=None) -> "LocalPhaseResult":
        """One cohort's local phase as a single scanned/vmapped dispatch.

        Every participant starts from the shared ``broadcast`` (each
        dispatch in the async scheduler uses one version, too).
        ``gen_keys`` optionally supplies pre-drawn (K, C, 2) generation
        keys — the multi-cohort dispatch draws them in the canonical
        loop order across ALL participants and slices per cohort.
        Without them the round program draws the generation keys and
        then the participants' uplink keys (``LocalPhaseResult.up_keys``)
        from the main stream.
        """
        p_count = len(participants)
        k_steps = fc.local_steps
        has_pref = self._stacked_pref is not None
        cfc = dataclasses.replace(fc, preference=None) if has_pref else fc
        full = p_count == self.fc.n_clients
        counts = self._prompt_counts()
        # advance the per-client prompt streams exactly as the loop would
        for c in participants:
            self.datasets[c]._count += k_steps

        # stacking copies the broadcast into a fresh (C, ...) buffer, so
        # the stacked state is safe to donate and the anchor survives
        states = [self.client_states[c]._replace(trainable=broadcast)
                  for c in participants]
        stacked = _stack_trees_jit(*states)
        self.jit_dispatches += 1

        up_keys = moe = None
        if not self.algorithm.caps.traced_server_exchange:
            # host-driven server exchange: the algorithm owns the phase
            # (jitted client phases around its host exchange)
            if full:
                seeds, probs = self._seeds_all, self._probs_all
                band_h, band_x = self._bands_h, self._bands_x
            else:
                idx = jnp.asarray(participants, jnp.int32)
                seeds, probs = self._seeds_all[idx], self._probs_all[idx]
                band_h, band_x = self._bands_h[idx], self._bands_x[idx]
                counts = counts[idx]
            lams, rewards_mean, kl_mean, rewards_pc, stacked = \
                self.algorithm.exchange_phase_vectorized(
                    self, cfc, participants, stacked, seeds, counts,
                    probs, band_h, band_x)
        else:
            # without pre-drawn keys the program draws the generation
            # keys, then the uplink keys, from the main stream in the
            # loop path's order (step-major, then participant order)
            extra = self.algorithm.traced_extra(cfc, self.ec)
            fn = _jit_vec_round(self.cfg, cfc, self.algorithm.kernel,
                                self.ec.prompt_len, self.ec.max_new,
                                self._length_tol, has_pref)
            stacked, stats, up_keys, rng, counts = fn(
                stacked, self.frozen, self.ref_trainable, self._seeds_all,
                counts, self._probs_all, self._bands_h, self._bands_x,
                self._rng if gen_keys is None else gen_keys,
                self._stacked_pref, extra,
                None if full else np.asarray(participants, np.int32))
            self.jit_dispatches += 1
            lams, rewards_mean, kl_mean, rewards_pc, moe = stats
            if rng is not None:
                self._rng = rng
            self._counts = (tuple(ds._count for ds in self.datasets),
                            counts)

        new_states = _jit_unstack(p_count)(stacked)
        self.jit_dispatches += 1
        for ci, c in enumerate(participants):
            self.client_states[c] = new_states[ci]
        return LocalPhaseResult(lams, rewards_mean, kl_mean,
                                stacked.trainable, rewards_pc, up_keys,
                                moe)

    # ------------------------------------------------- cohort dispatch
    def _local_phase_cohorts(self, plan, participants: List[int],
                             broadcast) -> "LocalPhaseResult":
        """Group-by-config dispatch: one vmapped program per cohort.

        Generation keys are drawn ONCE in the canonical loop order —
        step-major over all participants, skipping clients whose K is
        exhausted — then sliced per cohort, so a multi-cohort round
        consumes the PRNG stream exactly like the per-client loop and
        stays equivalent to it.  Per-cohort results reassemble into
        participant order; scalar metrics merge weighted by each
        cohort's client-step count (n_g * K_g), matching the loop's
        mean-over-entries semantics.
        """
        steps = {c: self._client_fcs[c].local_steps for c in participants}
        keys = {}
        for k in range(max(steps.values())):
            for c in participants:
                if k < steps[c]:
                    keys[(c, k)] = self._next_key()

        pos = {c: i for i, c in enumerate(participants)}
        lam_rows = [None] * len(participants)
        rpc_rows = [None] * len(participants)
        stacked_parts, order, moes = [], [], []
        rew_acc, kl_acc, w_tot = 0.0, 0.0, 0
        for co in plan:
            members = list(co.members)
            gk = jnp.stack(
                [jnp.stack([keys[(c, k)] for c in members])
                 for k in range(co.cfc.local_steps)])
            res = self._local_phase_vectorized(co.cfc, members, broadcast,
                                               gen_keys=gk)
            for i, c in enumerate(members):
                lam_rows[pos[c]] = res.lams[i]
                rpc_rows[pos[c]] = res.rewards_pc[i]
            w = len(members) * co.cfc.local_steps
            rew_acc = rew_acc + w * res.rewards_mean
            kl_acc = kl_acc + w * res.kl_mean
            w_tot += w
            stacked_parts.append(res.stacked_trainable)
            order.extend(members)
            if res.moe:
                moes.append(res.moe)

        inv = jnp.asarray([order.index(c) for c in participants], jnp.int32)
        stacked_tr = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0)[inv], *stacked_parts)
        self.jit_dispatches += 1
        # the most uneven cohort's load, the most padded cohort's rows
        return LocalPhaseResult(
            jnp.stack(lam_rows), rew_acc / w_tot, kl_acc / w_tot,
            stacked_tr, jnp.stack(rpc_rows),
            moe=({k: jnp.max(jnp.stack([m[k] for m in moes]))
                  for k in moes[0]} if moes else None))

    def run(self, rounds: Optional[int] = None) -> List[dict]:
        total = rounds or self.fc.rounds
        chunk = max(1, int(self.ec.fused_rounds))
        if chunk > 1 and self._fused_mode()[0]:
            left = total
            while left > 0:
                r = min(chunk, left)
                if r == 1:
                    self.run_round()
                else:
                    self.run_rounds_fused(r)
                left -= r
        else:
            for _ in range(total):
                self.run_round()
        return self.history
