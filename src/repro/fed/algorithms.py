"""Algorithm protocol + registry: capability-driven federated algorithms.

Every federated algorithm the engine can run — the paper's FIRM, its
β = 0 ablation, linear scalarization, and the server-centric FedCMOO
baseline — is a first-class ``Algorithm`` object owning three things:

* its **local-step machinery**: the jitted per-client loop step
  (``local_step_fn``), the traced step the vectorized/fused round body
  vmaps (``traced_step``), and — for algorithms with a host-driven
  server exchange — the whole exchange phase
  (``loop_phase`` / ``exchange_phase_vectorized``);
* its **config resolution**: ``resolve_config`` (e.g. firm_unreg pins
  β = 0 so it shares firm's trace), ``validate`` (e.g. fedcmoo rejects
  heterogeneous per-client local-step counts), and the per-client
  config expansion (``client_configs``);
* its declared **capabilities** (``Capabilities``) — the ONLY thing the
  engine and the ``repro.fed.api`` planner dispatch on.  The engine
  never branches on algorithm-name strings; adding an algorithm is one
  ``register_algorithm`` call, after which every executor decision
  (loop / cohort-vectorized / fused) falls out of the capability
  queries.

Capability semantics
--------------------
``vmap_safe``
    The per-client local step can ride ``jax.vmap`` over a stacked
    client axis (one program per cohort).  False forces the per-client
    Python loop.
``traced_server_exchange``
    Any server interaction the algorithm performs DURING the local
    phase stays inside the traced program.  Client-local algorithms
    (firm/linear — no mid-phase exchange at all) are trivially True;
    fedcmoo's per-step λ solve runs on the host between two jitted
    phases, so it is False.  False also routes the vectorized local
    phase through ``exchange_phase_vectorized`` instead of the shared
    scanned round body.
``single_cohort_required``
    Every participant must advance in lock-step through one dispatch
    group (fedcmoo's λ is global per local step).  With several static
    cohorts such an algorithm falls back to the loop, and the async
    scheduler policies reject it.
``fusable``
    Eligible for the round-level ``lax.scan`` (``fused_rounds``).
    Requires ``traced_server_exchange`` and ``vmap_safe`` —
    ``register_algorithm`` rejects a declaration that violates either
    (the scan body cannot leave the graph).
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.comms import ErrorFeedback
from repro.configs.base import FIRMConfig
from repro.core import fedcmoo
from repro.data.partition import sample_prompt_block
from repro.models import transformer
from repro.models.common import merge_trainable
from repro.rlhf import local as local_lib
from repro.rlhf import ppo, rewards as rewards_lib
from repro.rlhf.sampling import generate


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What an algorithm's execution paths can do (see module docstring)."""
    vmap_safe: bool = True
    traced_server_exchange: bool = True
    single_cohort_required: bool = False
    fusable: bool = True


def validate_capabilities(caps: Capabilities, name: str) -> None:
    """Reject internally inconsistent capability declarations."""
    if caps.fusable and not caps.traced_server_exchange:
        raise ValueError(
            f"algorithm {name!r} declares fusable=True but "
            "traced_server_exchange=False: the round-level lax.scan "
            "cannot pause for a host-driven server exchange")
    if caps.fusable and not caps.vmap_safe:
        raise ValueError(
            f"algorithm {name!r} declares fusable=True but "
            "vmap_safe=False: the fused round body vmaps the local step "
            "over the stacked client axis")


# Jitted callables are memoized on the (hashable, frozen) configs so every
# trainer with the same architecture + FIRM hyperparameters shares one
# trace/compile per process.
@functools.lru_cache(maxsize=None)
def _jit_local_step(cfg, cfc: FIRMConfig):
    # the client-state argument is donated: its buffers are reused for the
    # updated state in place.  Callers must pass states whose buffers are
    # not aliased elsewhere (the engine adopts the broadcast by copy).
    return jax.jit(partial(local_lib.firm_local_step, cfg, cfc),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jit_sample_block(batch_size: int, prompt_len: int, vocab: int):
    return jax.jit(lambda seeds, counts, probs: sample_prompt_block(
        seeds, counts, probs, batch_size, prompt_len, vocab))


class Algorithm:
    """Base protocol; subclasses fill in the hooks their capabilities
    promise.

    ``traced_server_exchange=True`` algorithms implement ``traced_step``
    (used by the shared vectorized/fused round body) and ``loop_phase``;
    ``traced_server_exchange=False`` algorithms implement ``loop_phase``
    and ``exchange_phase_vectorized`` instead.  ``kernel`` is the
    trace-cache key for the shared round body: algorithms that lower to
    the same traced step (firm / firm_unreg after ``resolve_config``)
    share one compile by sharing a kernel name.
    """

    name: str = "algorithm"
    kernel: str = "algorithm"
    caps: Capabilities = Capabilities()
    # plan-time dispatch-cost model: engine-counted jit dispatches per
    # client-step on the per-client loop path
    loop_dispatches_per_client_step: int = 3

    # ---- config resolution -------------------------------------------
    def validate(self, fc: FIRMConfig, ec) -> None:
        """Raise if (fc, ec) cannot run under this algorithm."""

    def resolve_config(self, fc: FIRMConfig) -> FIRMConfig:
        """The FIRMConfig the local step actually traces against."""
        return fc

    # ---- local-step machinery ----------------------------------------
    def local_step_fn(self, cfg, cfc: FIRMConfig):
        """Jitted per-client loop step, or None if the loop phase builds
        its own dispatches."""
        return None

    def traced_step(self, cfg, cfc: FIRMConfig, st, frozen, batch, pref,
                    extra):
        """One client's local update inside the traced round body."""
        raise NotImplementedError(self.name)

    def traced_extra(self, cfc: FIRMConfig, ec):
        """Static-per-run operand threaded to ``traced_step`` (e.g. the
        linear scalarization weights); None when unused."""
        return None

    def loop_phase(self, tr, fc: FIRMConfig, participants: List[int]
                   ) -> List[dict]:
        """Per-client-loop local phase; returns per-entry metric dicts
        (each with 'client', 'rewards', 'kl' and, when the algorithm
        produces one, 'lam')."""
        raise NotImplementedError(self.name)

    def exchange_phase_vectorized(self, tr, cfc: FIRMConfig,
                                  participants: List[int], stacked, seeds,
                                  counts0, probs, band_h, band_x):
        """Vectorized local phase for host-exchange algorithms; returns
        (lams, rewards_mean, kl_mean, rewards_pc, stacked)."""
        raise NotImplementedError(self.name)

    # ---- plan-time cost model ----------------------------------------
    def vec_phase_dispatches(self, k_steps: int) -> int:
        """Engine-counted dispatches inside one cohort's vectorized
        local phase (excluding the stack/unstack pair)."""
        return 1

    def uplink_bytes_per_participant(self, fc: FIRMConfig, ul_codec,
                                     d: int) -> int:
        """Exact wire bytes one participant uploads per round."""
        return ul_codec.nbytes_static(d)

    # ------------------------------------------------------------------
    def __repr__(self) -> str:
        return f"<Algorithm {self.name} caps={self.caps}>"


def _step_major(tr, participants: List[int]):
    """The canonical loop order: step-major over participants with
    per-client K (heterogeneous ``client_local_steps`` finish early and
    skip) — the order the cohort path's pre-drawn generation keys
    replicate."""
    steps = {c: tr._client_fcs[c].local_steps for c in participants}
    for k in range(max(steps.values())):
        for c in participants:
            if k < steps[c]:
                yield c


class FIRMAlgorithm(Algorithm):
    """Paper Alg. 1: in-client regularized MGDA (client-local)."""

    name = "firm"
    kernel = "firm"
    caps = Capabilities()
    loop_dispatches_per_client_step = 3     # generate, ref logprobs, step

    def local_step_fn(self, cfg, cfc: FIRMConfig):
        return _jit_local_step(cfg, cfc)

    def traced_step(self, cfg, cfc, st, frozen, batch, pref, extra):
        return local_lib.firm_local_step(cfg, cfc, st, frozen, batch,
                                         preference=pref)

    def loop_phase(self, tr, fc, participants):
        metrics = []
        for c in _step_major(tr, participants):
            batch = tr._make_batch(c)
            tr.client_states[c], m = tr._jit_steps[c](
                tr.client_states[c], tr.frozen, batch)
            tr.jit_dispatches += 1
            m["client"] = c
            metrics.append(m)
        return metrics


class FIRMUnregAlgorithm(FIRMAlgorithm):
    """β = 0 ablation (RQ2): identical machinery, regularizer off.

    ``kernel`` stays "firm" — after ``resolve_config`` pins β = 0 the
    traced step is the same program, so firm and firm_unreg share every
    trace cache.
    """

    name = "firm_unreg"

    def resolve_config(self, fc):
        return dataclasses.replace(fc, beta=0.0)


class LinearAlgorithm(Algorithm):
    """Fixed-weight linear scalarization (implicit baseline)."""

    name = "linear"
    kernel = "linear"
    caps = Capabilities()
    loop_dispatches_per_client_step = 2     # generate, ref logprobs

    def traced_step(self, cfg, cfc, st, frozen, batch, pref, extra):
        return local_lib.linear_local_step(cfg, cfc, st, frozen, batch,
                                           extra)

    def traced_extra(self, cfc, ec):
        return jnp.asarray(
            ec.linear_weights
            or [1.0 / cfc.n_objectives] * cfc.n_objectives, jnp.float32)

    def loop_phase(self, tr, fc, participants):
        w = self.traced_extra(fc, tr.ec)
        metrics = []
        for c in _step_major(tr, participants):
            batch = tr._make_batch(c)
            grads, losses, extras = local_lib.fedcmoo_local_grads(
                tr.cfg, fc, tr.client_states[c], tr.frozen, batch)
            tr.client_states[c], m = local_lib.fedcmoo_local_apply(
                fc, tr.client_states[c], grads, w, extras)
            m["client"] = c
            m["rewards"] = batch.rewards.mean(0)
            metrics.append(m)
        return metrics


@functools.lru_cache(maxsize=None)
def _jit_vec_fedcmoo_grads(cfg, cfc: FIRMConfig, max_new: int,
                           length_tol: int):
    """FedCMOO client phase 1, vmapped: rollouts + M gradients for every
    participant in one dispatch.  Gradients return stacked so the server
    exchange (per-client codec Payloads + one λ solve) stays at the host
    boundary between the two jitted phases."""
    m = cfc.n_objectives

    def fn(state, frozen, ref_trainable, prompts, keys, band_h, band_x):
        ref_params = merge_trainable(ref_trainable, frozen)

        def one(st, pr, key, bh, bx):
            params = merge_trainable(st.trainable, frozen)
            tokens, old_lp, mask = generate(cfg, params, pr, key,
                                            max_new=max_new)
            r = rewards_lib.score_batch_banded(bh, bx, tokens, mask, m,
                                               length_tol)
            ref_out = transformer.forward_seq(cfg, ref_params, tokens)
            ref_lp = ppo.token_logprobs(ref_out["logits"], tokens)
            batch = ppo.PPOBatch(tokens, mask, old_lp, ref_lp, r)
            grads, losses, extras = local_lib.fedcmoo_local_grads(
                cfg, cfc, st, frozen, batch)
            return grads, extras, batch.rewards.mean(0)

        return jax.vmap(one)(state, prompts, keys, band_h, band_x)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _jit_vec_fedcmoo_apply(cfc: FIRMConfig):
    """FedCMOO client phase 2, vmapped, with the stacked state donated."""

    def fn(state, grads, lam, extras):
        def one(st, g, e):
            return local_lib.fedcmoo_local_apply(cfc, st, g, lam, e)

        return jax.vmap(one)(state, grads, extras)

    return jax.jit(fn, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jit_grads_flat(m: int):
    return jax.jit(partial(fedcmoo.stack_grads_flat, m=m))


class FedCMOOAlgorithm(Algorithm):
    """Server-centric MGDA baseline (RQ1, Askin et al. 2024).

    Gradients go up every local step and the server broadcasts one
    global λ back — a HOST-driven exchange between two jitted phases,
    hence ``traced_server_exchange=False`` (never fused) and
    ``single_cohort_required=True`` (λ is global per step, so every
    participant must advance in lock-step).
    """

    name = "fedcmoo"
    kernel = "fedcmoo"
    caps = Capabilities(vmap_safe=True, traced_server_exchange=False,
                        single_cohort_required=True, fusable=False)
    loop_dispatches_per_client_step = 2     # generate, ref logprobs

    def validate(self, fc, ec):
        if fc.client_local_steps is not None:
            raise ValueError("fedcmoo needs homogeneous local_steps: its "
                             "server λ exchange is global per local step")

    def vec_phase_dispatches(self, k_steps: int) -> int:
        # per step: sampler, vmapped grads, batched flatten, vmapped apply
        return 4 * k_steps

    def uplink_bytes_per_participant(self, fc, ul_codec, d):
        # M per-step gradient uploads ride the EF-stripped inner codec on
        # top of the end-of-round adapted-param delta
        grad = self._grad_codec(ul_codec)
        return (ul_codec.nbytes_static(d)
                + fc.n_objectives * fc.local_steps * grad.nbytes_static(d))

    @staticmethod
    def _grad_codec(ul_codec):
        """Codec for per-step gradient uploads: error feedback is defined
        per client *stream*, not per objective, so the M parallel
        gradient trees use the EF-stripped inner codec."""
        return ul_codec.inner if isinstance(ul_codec, ErrorFeedback) \
            else ul_codec

    def loop_phase(self, tr, fc, participants):
        grad_codec = self._grad_codec(tr.uplink_codec)
        metrics = []
        for k in range(fc.local_steps):
            per_client = []
            server_grads = []
            for c in participants:
                batch = tr._make_batch(c)
                grads, losses, extras = local_lib.fedcmoo_local_grads(
                    tr.cfg, fc, tr.client_states[c], tr.frozen, batch)
                per_client.append((grads, extras, batch.rewards.mean(0)))
                # gradients go up every local step: the O(CMd) cost; the
                # server solves λ from what it actually receives (codec
                # error feeds the q-term, Askin et al. Rmk 4.6)
                received = []
                for g in grads:
                    gp, _, dec = grad_codec.roundtrip(g, key=tr._next_key())
                    tr.ledger.send_up(gp)
                    received.append(dec)
                server_grads.append(received)
            lam = fedcmoo.fedcmoo_round_lambda(
                server_grads, compress_rank=tr.ec.fedcmoo_compress_rank,
                key=tr._next_key())
            for ci, c in enumerate(participants):
                grads, extras, rmean = per_client[ci]
                tr.client_states[c], m = local_lib.fedcmoo_local_apply(
                    fc, tr.client_states[c], grads, lam, extras)
                m["client"] = c
                m["rewards"] = rmean
                metrics.append(m)
        return metrics

    def exchange_phase_vectorized(self, tr, cfc, participants, stacked,
                                  seeds, counts0, probs, band_h, band_x):
        """Two jitted dispatches per step (vmapped grads, vmapped apply)
        around the batched server exchange: all C×M gradient trees
        flatten in one batched tree op, the codec encodes them at the
        stacked Payload boundary (one kernel dispatch for quantize
        codecs), and the stacked decode feeds the λ solve directly — no
        per-client host loop."""
        m = cfc.n_objectives
        p_count = len(participants)
        grad_codec = self._grad_codec(tr.uplink_codec)
        grads_fn = _jit_vec_fedcmoo_grads(tr.cfg, cfc, tr.ec.max_new,
                                          tr._length_tol)
        apply_fn = _jit_vec_fedcmoo_apply(cfc)
        sampler = _jit_sample_block(cfc.batch_size, tr.ec.prompt_len,
                                    tr.cfg.vocab)
        lam_last, rew_hist, kl_hist = None, [], []
        for k in range(cfc.local_steps):
            # key parity with the loop path: per client, one batch key
            # then M gradient-codec keys, interleaved in participant order
            kb, kg = [], []
            for _ in participants:
                kb.append(tr._next_key())
                kg.extend(tr._next_key() for _ in range(m))
            prompts = sampler(seeds, counts0 + k, probs)
            tr.jit_dispatches += 1
            grads, extras, rmean = grads_fn(
                stacked, tr.frozen, tr.ref_trainable, prompts,
                jnp.stack(kb), band_h, band_x)
            tr.jit_dispatches += 1
            # (C, M, d) client-major rows match the loop path's upload
            # order, so payload keys and ledger bytes are identical
            gmat = _jit_grads_flat(m)(grads)
            tr.jit_dispatches += 1
            gpayloads, _, gdec = grad_codec.roundtrip_stacked(
                gmat.reshape(p_count * m, -1), tr._delta_spec, keys=kg)
            for gp in gpayloads:
                tr.ledger.send_up(gp)
            lam = fedcmoo.fedcmoo_round_lambda_stacked(
                gdec.reshape(p_count, m, -1),
                compress_rank=tr.ec.fedcmoo_compress_rank,
                key=tr._next_key())
            stacked, metrics = apply_fn(stacked, grads, lam, extras)
            tr.jit_dispatches += 1
            lam_last = metrics["lam"]
            rew_hist.append(rmean)
            kl_hist.append(metrics["kl"])
        rewards_mean = jnp.stack(rew_hist).reshape(-1, m).mean(0)
        kl_mean = jnp.stack(kl_hist).mean()
        rewards_pc = jnp.stack(rew_hist).mean(0)              # (C, M)
        return lam_last, rewards_mean, kl_mean, rewards_pc, stacked


# ---------------------------------------------------------------- registry
_REGISTRY: Dict[str, Algorithm] = {}


def register_algorithm(algorithm: Algorithm) -> Algorithm:
    """Validate the capability declaration and add the algorithm to the
    registry (name collisions overwrite — latest wins, like codecs)."""
    validate_capabilities(algorithm.caps, algorithm.name)
    _REGISTRY[algorithm.name] = algorithm
    return algorithm


def get_algorithm(name: str) -> Algorithm:
    if name not in _REGISTRY:
        raise ValueError(f"unknown algorithm {name!r}; "
                         f"available: {available_algorithms()}")
    return _REGISTRY[name]


def available_algorithms() -> tuple:
    return tuple(sorted(_REGISTRY))


register_algorithm(FIRMAlgorithm())
register_algorithm(FIRMUnregAlgorithm())
register_algorithm(LinearAlgorithm())
register_algorithm(FedCMOOAlgorithm())


def client_configs(algorithm: Algorithm, fc: FIRMConfig
                   ) -> List[FIRMConfig]:
    """Per-client FIRM configs (pluralistic preferences §6 future work,
    FedMOA-style heterogeneous local-step rates), expanded from the
    algorithm-resolved base config.  Single source of truth for the
    trainer AND the plan-time cohort structure."""
    base = algorithm.resolve_config(fc)
    out = []
    for c in range(fc.n_clients):
        cfc = base
        if fc.client_preferences is not None:
            cfc = dataclasses.replace(
                cfc, preference=fc.client_preferences[c])
        if fc.client_local_steps is not None:
            cfc = dataclasses.replace(
                cfc, local_steps=int(fc.client_local_steps[c]))
        out.append(cfc)
    return out
