"""The FIRM client-local update step (Alg. 1, inner loop body).

``firm_local_step`` is the jittable unit of work the framework runs
everywhere: the federated simulation engine executes it per client on CPU,
and the multi-pod dry-run lowers it at full scale under the production
mesh (each pod = one client; see launch/steps.py).

Pipeline: multi-objective PPO grads (one forward, M pulls) -> in-client
regularized MGDA resolve (Eq. 1) -> Adam on the adapters -> TD update of
the M linear critics -> adaptive-KL bookkeeping.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FIRMConfig, ModelConfig
from repro.core import fedcmoo, firm
from repro.rlhf import critic as critic_lib
from repro.rlhf import kl as kl_lib
from repro.rlhf import ppo
from repro.train import optim


class ClientState(NamedTuple):
    trainable: object            # LoRA adapters (or full params)
    critic: dict                 # M linear value heads
    opt: optim.AdamState
    lam: jnp.ndarray             # smoothed MGDA weights (M,)
    kl_coef: jnp.ndarray
    step: jnp.ndarray            # local+global step counter (for η_t)


def init_client_state(trainable, m: int, d_model: int,
                      kl_coef: float = 0.1) -> ClientState:
    return ClientState(
        trainable=trainable,
        critic=critic_lib.init_critic(m, d_model),
        opt=optim.adam_init(trainable),
        lam=jnp.full((m,), 1.0 / m, jnp.float32),
        kl_coef=jnp.asarray(kl_coef, jnp.float32),
        step=jnp.zeros((), jnp.int32),
    )


def _adam(fc: FIRMConfig, direction, state: ClientState):
    with jax.named_scope("local_step/adam"):
        return optim.adam_update(direction, state.opt, state.trainable,
                                 lr=fc.actor_lr, max_grad_norm=1.0)


def _critic_kl(fc: FIRMConfig, state: ClientState, feats, r_tok, mask,
               metrics):
    """TD update of the M critics and the adaptive-KL coefficient:
    (new critic, TD error, new KL coefficient)."""
    with jax.named_scope("local_step/critic_kl"):
        r_w = critic_lib.r_w_bound(r_max=1.0)
        new_critic, td_err = critic_lib.td_update(
            state.critic, feats, r_tok, mask, fc.gamma, fc.critic_lr, r_w)
        new_kl = kl_lib.adaptive_kl_update(state.kl_coef, metrics["kl"],
                                           fc.kl_target)
    return new_critic, td_err, new_kl


def firm_local_step(cfg: ModelConfig, fc: FIRMConfig, state: ClientState,
                    frozen, batch: ppo.PPOBatch,
                    aux: Optional[dict] = None, gram_fn=None,
                    preference=None):
    """One local FIRM update.  Returns (new_state, metrics).

    ``preference`` optionally overrides ``fc.preference`` with a traced
    (M,) array — the vmap-safe signature the vectorized engine uses to run
    heterogeneous per-client preferences through a single trace.
    """
    with jax.named_scope("local_step/grads"):
        grads, losses, (metrics, feats, r_tok, rets, mask) = \
            ppo.per_objective_grads(cfg, fc, state.trainable, frozen,
                                    state.critic, batch, state.kl_coef, aux)
    with jax.named_scope("local_step/mgda"):
        eta = (firm.eta_schedule(state.step + 1) if fc.lambda_smoothing
               else None)
        res = firm.resolve(grads, fc, prev_lam=state.lam, eta=eta,
                           gram_fn=gram_fn, preference=preference)
    new_trainable, new_opt, gnorm = _adam(fc, res.direction, state)
    new_critic, td_err, new_kl = _critic_kl(fc, state, feats, r_tok, mask,
                                            metrics)
    new_state = ClientState(new_trainable, new_critic, new_opt, res.lam,
                            new_kl, state.step + 1)
    metrics = dict(metrics, losses=losses, lam=res.lam,
                   lam_star=res.lam_star, gram=res.gram, grad_norm=gnorm,
                   td_err=td_err, rewards=batch.rewards.mean(0))
    return new_state, metrics


def fedcmoo_local_grads(cfg: ModelConfig, fc: FIRMConfig,
                        state: ClientState, frozen, batch: ppo.PPOBatch,
                        aux: Optional[dict] = None):
    """FedCMOO client phase 1: compute and 'transmit' the M gradients."""
    with jax.named_scope("local_step/grads"):
        grads, losses, (metrics, feats, r_tok, rets, mask) = \
            ppo.per_objective_grads(cfg, fc, state.trainable, frozen,
                                    state.critic, batch, state.kl_coef, aux)
    return grads, losses, (metrics, feats, r_tok, mask)


def fedcmoo_local_apply(fc: FIRMConfig, state: ClientState, grads,
                        lam: jnp.ndarray, extras):
    """FedCMOO client phase 2: apply the server-broadcast λ."""
    metrics, feats, r_tok, mask = extras
    with jax.named_scope("local_step/mgda"):
        direction = firm.mgda.combine(grads, lam)
    new_trainable, new_opt, gnorm = _adam(fc, direction, state)
    new_critic, td_err, new_kl = _critic_kl(fc, state, feats, r_tok, mask,
                                            metrics)
    new_state = ClientState(new_trainable, new_critic, new_opt, lam,
                            new_kl, state.step + 1)
    return new_state, dict(metrics, lam=lam, grad_norm=gnorm, td_err=td_err)


def linear_local_step(cfg: ModelConfig, fc: FIRMConfig, state: ClientState,
                      frozen, batch: ppo.PPOBatch, weights: jnp.ndarray,
                      aux: Optional[dict] = None):
    """Fixed-weight linear scalarization step (the implicit RQ1 baseline).

    Fuses ``fedcmoo_local_grads`` + ``fedcmoo_local_apply`` with a constant
    λ = ``weights`` so the vectorized engine can scan it as one jittable
    unit; the math is exactly the loop path's two-phase call sequence.
    """
    grads, losses, extras = fedcmoo_local_grads(cfg, fc, state, frozen,
                                                batch, aux)
    new_state, metrics = fedcmoo_local_apply(fc, state, grads, weights,
                                             extras)
    return new_state, dict(metrics, losses=losses,
                           rewards=batch.rewards.mean(0))
