"""Aggregation policies behind one Scheduler protocol.

``ScheduledTrainer`` layers an event-driven simulated clock over the
vectorized round engine: client system profiles (profiles.py) turn the
engine's *measured* payload bytes and per-client step counts into
simulated seconds (repro.core.comms time models), and a policy decides
when the server aggregates:

  sync      today's behavior — every selected client must report before
            the round closes.  The exact-equivalence anchor: it runs
            ``FederatedTrainer.run_round`` unchanged and only adds
            timing, so rewards/λ/bytes are bit-identical to the bare
            engine.  Round time = slowest client.
  deadline  over-select participants (SchedConfig.overselect), predict
            each client's round time from analytic codec bytes + its
            profile, drop those past the deadline, FedAvg the survivors.
            Round time = the deadline when anyone was dropped.
  fedbuff   buffered async: clients run continuously from the broadcast
            version they last received; the server aggregates every B
            arrivals with staleness weights w ∝ (1+s)^-pow
            (core.fedavg.staleness_weights) and redispatches the idle
            clients from the new version.  FIRM's in-client regularizer
            β scales with each client's observed staleness
            (core.firm.staleness_beta) — the paper's drift-mitigation
            knob doubles as the staleness control.  With buffer B = C
            and homogeneous profiles every arrival has staleness 0 and
            the policy degenerates to sync FedAvg bit-for-bit.

All policies compute client work *eagerly* at dispatch time (results
depend only on the anchor params and RNG stream, never on the clock) and
only simulated durations flow through the event queue, so runs are
deterministic under a fixed seed.  Dispatches group in-flight clients by
identical static config (cohort.build_cohorts) — e.g. per-bucket
staleness-scaled β — and run each cohort as one vmapped program; nothing
falls back to the per-client Python loop.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.configs.base import SchedConfig
from repro.core import comms, fedavg, firm
from repro.fed.sched.clock import EventQueue, SimClock
from repro.fed.sched.cohort import build_cohorts
from repro.fed.sched.profiles import sample_profiles
from repro.obs import records as obs_records
from repro.obs.trace import TraceBuilder


def client_round_seconds(profile, down_nbytes: float, up_nbytes: float,
                         local_steps: int, batch_size: int,
                         seq_len: int) -> float:
    """download + local compute + upload, from bytes/tokens and rates.

    The sum of ``core.comms.client_round_segments`` — one definition for
    the policies' timing and the trace emitter's spans, so per-client
    spans always add up to the reported round time."""
    return sum(d for _, d in comms.client_round_segments(
        profile, down_nbytes, up_nbytes, local_steps, batch_size,
        seq_len))


class SyncPolicy:
    """Synchronous barrier: the bare engine round + a max-over-clients
    clock advance.  Bit-identical results to ``FederatedTrainer``.

    When the trainer is configured with ``EngineConfig.fused_rounds > 1``
    (and the fused program applies), the whole horizon runs through
    ``FederatedTrainer.run`` — R rounds per dispatch — and the clock
    annotations are applied per summary afterwards.  The fused path's
    static codec bytes equal the measured payload bytes, so simulated
    durations (and everything derived from them) are unchanged.  The
    deadline/fedbuff policies stay on the per-round engine: their
    control flow consults the clock between dispatches.
    """

    name = "sync"

    def run(self, st: "ScheduledTrainer", rounds: int) -> List[dict]:
        tr = st.trainer
        if tr.ec.fused_rounds > 1 and tr._fused_mode()[0]:
            start = len(tr.history)
            tr.run(rounds)
            return [self._annotate(st, s, round_idx=start + i)
                    for i, s in enumerate(tr.history[start:])]
        return [self.step(st) for _ in range(rounds)]

    def step(self, st: "ScheduledTrainer") -> dict:
        s = st.trainer.run_round()
        return self._annotate(st, s,
                              round_idx=len(st.trainer.history) - 1)

    def _annotate(self, st: "ScheduledTrainer", s: dict,
                  round_idx: Optional[int] = None) -> dict:
        t0 = st.clock.now
        segs = [st.client_segments(c, s["down_nbytes"], s["up_nbytes"][i],
                                   s["local_steps"][i])
                for i, c in enumerate(s["participants"])]
        durs = [sum(d for _, d in seg) for seg in segs]
        dur = max(durs)
        for c, seg in zip(s["participants"], segs):
            st.trace.client_span(c, t0, seg, round_idx=round_idx)
        st.trace.server_span("round", t0, dur,
                             {"policy": self.name, "round": round_idx,
                              "participants": len(durs)})
        st.clock.advance_by(dur)
        st.trace.instant("aggregate", st.clock.now,
                         args={"round": round_idx})
        obs_records.annotate_schedule(
            s, policy=self.name, sim_time=st.clock.now,
            round_duration=dur, dropped=[], client_seconds=durs)
        st.obs.emit_schedule(s, round=round_idx)
        return s


class DeadlinePolicy:
    """Over-select, predict, drop stragglers, FedAvg the survivors.

    Predictions use the *analytic* codec byte model (what a real
    scheduler knows before the round); measured bytes time the survivors
    after the fact.  overselect=1 with an infinite deadline selects and
    keeps exactly the sync participants — the equivalence anchor the
    tests pin.
    """

    name = "deadline"

    def run(self, st: "ScheduledTrainer", rounds: int) -> List[dict]:
        return [self.step(st) for _ in range(rounds)]

    def step(self, st: "ScheduledTrainer") -> dict:
        tr, sc = st.trainer, st.sc
        fc = tr.fc
        target = max(1, int(round(fc.participation * fc.n_clients)))
        n_sel = min(fc.n_clients,
                    max(target, int(round(sc.overselect * target))))
        selected = tr._sample_participants(n=n_sel)
        d = tr.d_trainable
        up_pred = comms.codec_bytes_per_param(tr.ec.uplink_codec, d) * d
        down_pred = comms.codec_bytes_per_param(tr.ec.downlink_codec, d) * d
        pred = {c: st.client_seconds(c, down_pred, up_pred,
                                     tr._client_fcs[c].local_steps)
                for c in selected}
        deadline = sc.deadline_s
        if sc.deadline_quantile is not None:
            deadline = float(np.quantile(list(pred.values()),
                                         sc.deadline_quantile))
        survivors = [c for c in selected if pred[c] <= deadline]
        if not survivors:                 # never stall: keep the fastest
            survivors = [min(selected, key=lambda c: pred[c])]
        dropped = [c for c in selected if c not in survivors]

        t0 = st.clock.now
        s = tr.run_round(participants=survivors)
        round_idx = len(tr.history) - 1
        if dropped:
            # dropped clients were still dispatched and received the
            # broadcast before missing the deadline — their downlink
            # bytes are spent, only their uploads never land
            tr.ledger.down_bytes += len(dropped) * s["down_nbytes"]
            s["down_bytes"] = tr.ledger.down_bytes
            s["comm_bytes"] = tr.ledger.total
        segs = [st.client_segments(c, s["down_nbytes"], s["up_nbytes"][i],
                                   s["local_steps"][i])
                for i, c in enumerate(survivors)]
        durs = [sum(d for _, d in seg) for seg in segs]
        # the server holds the barrier open until the deadline whenever
        # anyone was dropped (it cannot know they won't make it)
        dur = max(durs) if not dropped else max(max(durs), deadline)
        for c, seg in zip(survivors, segs):
            st.trace.client_span(c, t0, seg, round_idx=round_idx)
        for c in dropped:
            # spans from the scheduler's own prediction (analytic bytes):
            # the work was dispatched, the upload never landed
            st.trace.client_span(
                c, t0,
                st.client_segments(c, down_pred, up_pred,
                                   tr._client_fcs[c].local_steps),
                round_idx=round_idx, extra={"dropped": True})
            st.trace.instant("deadline missed", t0 + deadline, client=c,
                             args={"predicted_seconds": round(pred[c], 6)})
        st.trace.server_span("round (deadline)", t0, dur,
                             {"policy": self.name, "round": round_idx,
                              "deadline": deadline,
                              "dropped": len(dropped)})
        st.clock.advance_by(dur)
        st.trace.instant("aggregate", st.clock.now,
                         args={"round": round_idx})
        obs_records.annotate_schedule(
            s, policy=self.name, sim_time=st.clock.now,
            round_duration=dur, dropped=dropped, client_seconds=durs,
            selected=selected, deadline=deadline)
        st.obs.emit_schedule(s, round=round_idx)
        return s


@dataclasses.dataclass
class _Arrival:
    """One client upload in flight: what the server will see land."""
    client: int
    version: int                     # server version it trained from
    decoded: jnp.ndarray             # (d,) delta as the server decodes it
    rewards: jnp.ndarray             # (M,) client mean rewards this phase
    up_nbytes: int
    flow_id: int = 0                 # trace flow arrow: upload -> aggregate


class FedBuffPolicy:
    """Buffered asynchronous aggregation with staleness-weighted deltas
    and staleness-scaled in-client regularization."""

    name = "fedbuff"

    def __init__(self) -> None:
        self._last_cohorts = 0
        # decoded broadcast of the current server version: the anchor
        # aggregation applies deltas to (exactly the engine round's
        # choice, so lossy downlinks keep fedbuff(B=C) == sync)
        self._anchor = None

    def run(self, st: "ScheduledTrainer", rounds: int) -> List[dict]:
        tr, sc = st.trainer, st.sc
        if tr.algorithm.caps.single_cohort_required:
            raise ValueError(
                "fedbuff needs a client-local algorithm; "
                f"{tr.algorithm.name} requires lock-step participants "
                "(per-step server exchange is inherently synchronous)")
        n = tr.fc.n_clients
        buf_size = sc.buffer_size or n
        if not 1 <= buf_size <= n:
            raise ValueError(f"buffer_size {buf_size} outside [1, {n}]")

        def tap(op, t, depth):
            # queue depth = uploads in flight; sampled at dispatch time
            # for pushes, at the arrival's own time for pops
            st.trace.counter("uploads in flight",
                             st.clock.now if op == "push" else t,
                             {"in_flight": depth})

        queue = EventQueue(tap=tap)
        version = 0
        last_staleness: Dict[int, int] = {c: 0 for c in range(n)}
        self._dispatch(st, list(range(n)), version, last_staleness, queue)
        buffer: List[_Arrival] = []
        history: List[dict] = []
        last_agg = st.clock.now
        while len(history) < rounds and queue:
            ev = queue.pop()
            st.clock.advance_to(ev.time)
            buffer.append(ev.item)
            if len(buffer) < buf_size:
                continue
            staleness = [version - a.version for a in buffer]
            flats = jnp.stack([a.decoded for a in buffer])
            tr.global_trainable = tr._aggregate_flat(
                self._anchor, flats, staleness, sc.staleness_pow)
            version += 1
            tr.ledger.next_round()
            for a, s_c in zip(buffer, staleness):
                last_staleness[a.client] = s_c
            # report the same weights the aggregate applied (one formula)
            w = np.asarray(fedavg.staleness_weights(staleness,
                                                    sc.staleness_pow))
            rewards_pc = np.asarray(jnp.stack([a.rewards for a in buffer]))
            summary = obs_records.fedbuff_summary(
                version=version,
                sim_time=st.clock.now,
                round_duration=st.clock.now - last_agg,
                participants=[a.client for a in buffer],
                staleness=staleness,
                staleness_weights=w,
                rewards=rewards_pc.mean(0),
                rewards_per_client=rewards_pc,
                comm_bytes=tr.ledger.total,
                up_bytes=tr.ledger.up_bytes,
                down_bytes=tr.ledger.down_bytes,
            )
            st.trace.server_span(f"buffer v{version}", last_agg,
                                 st.clock.now - last_agg,
                                 {"policy": self.name,
                                  "arrivals": len(buffer)})
            st.trace.instant(f"aggregate v{version}", st.clock.now,
                             args={"staleness": staleness})
            for a, s_c in zip(buffer, staleness):
                st.trace.flow_end("upload", st.clock.now, a.flow_id,
                                  args={"client": a.client,
                                        "staleness": s_c})
            st.obs.emit_round(summary, round=version - 1)
            last_agg = st.clock.now
            idle = [a.client for a in buffer]
            buffer = []
            history.append(summary)
            if len(history) < rounds:
                # idle clients restart from the new version; skipped
                # after the last aggregation so no discarded work runs
                self._dispatch(st, idle, version, last_staleness, queue)
                summary["cohorts"] = self._last_cohorts
            else:
                summary["cohorts"] = 0
        return history

    def _dispatch(self, st: "ScheduledTrainer", clients: List[int],
                  version: int, last_staleness: Dict[int, int],
                  queue: EventQueue) -> None:
        """Broadcast the current version to ``clients``, run their local
        phases eagerly (cohort-vectorized), encode their uplinks, and
        schedule the arrival events."""
        tr, sc = st.trainer, st.sc
        from repro.fed import engine as engine_lib
        dl_payload, tr._downlink_state, broadcast = \
            tr.downlink_codec.roundtrip(tr.global_trainable,
                                        tr._downlink_state,
                                        key=tr._next_key())
        self._anchor = broadcast
        down_nbytes = comms.measured_bytes(dl_payload)
        for _ in clients:
            tr.ledger.send_down(dl_payload)
        # per-client config with staleness-scaled β, bucketed so a handful
        # of static configs (and vmapped cohorts / compiles) cover every
        # staleness level
        pairs = []
        for c in clients:
            base = tr._client_fcs[c]
            bucket = min(int(last_staleness[c]), sc.staleness_bucket_max)
            beta = firm.staleness_beta(base.beta, bucket,
                                       sc.staleness_beta_gain,
                                       sc.staleness_beta_cap)
            pairs.append((c, dataclasses.replace(base, beta=beta)))
        plan = build_cohorts(pairs,
                             lift_preference=tr._stacked_pref is not None)
        self._last_cohorts = len(plan)
        for co in plan:
            members = list(co.members)
            res = tr._local_phase_vectorized(co.cfc, members, broadcast)
            flats = engine_lib._delta_flat_jit(res.stacked_trainable,
                                               broadcast)
            tr.jit_dispatches += 1
            for i, c in enumerate(members):
                # the vectorized phase drew the members' uplink keys
                # after their generation keys, as _next_key would
                payload, tr._uplink_state[c], dec = \
                    tr.uplink_codec.roundtrip_flat(
                        flats[i], tr._delta_spec, tr._uplink_state[c],
                        key=(tr._next_key() if res.up_keys is None
                             else res.up_keys[i]))
                tr.ledger.send_up(payload)
                segs = st.client_segments(c, down_nbytes, payload.nbytes,
                                          co.cfc.local_steps)
                dur = sum(d for _, d in segs)
                t_end = st.trace.client_span(c, st.clock.now, segs,
                                             extra={"version": version})
                fid = st.trace.flow_start("upload", t_end, client=c,
                                          args={"version": version})
                queue.push(st.clock.now + dur,
                           _Arrival(c, version, dec, res.rewards_pc[i],
                                    int(payload.nbytes), fid))


_POLICIES = {"sync": SyncPolicy, "deadline": DeadlinePolicy,
             "fedbuff": FedBuffPolicy}


def make_policy(name: str):
    if name not in _POLICIES:
        raise ValueError(f"unknown scheduler policy {name!r}; "
                         f"available: {tuple(sorted(_POLICIES))}")
    return _POLICIES[name]()


class ScheduledTrainer:
    """Simulated-time federation: a FederatedTrainer + client profiles +
    an aggregation policy on an event-driven clock.

        tr = FederatedTrainer(cfg, fc, ec)
        st = ScheduledTrainer(tr, SchedConfig(policy="deadline",
                                              profile="bimodal",
                                              deadline_quantile=0.7))
        history = st.run(rounds)     # entries carry sim_time etc.

    One history entry per server aggregation.  The underlying trainer is
    shared mutable state — don't reuse it across ScheduledTrainers.
    """

    def __init__(self, trainer, sc: Optional[SchedConfig] = None):
        self.trainer = trainer
        self.sc = SchedConfig() if sc is None else sc
        self.profiles = sample_profiles(trainer.fc.n_clients,
                                        self.sc.profile,
                                        self.sc.profile_seed)
        self.clock = SimClock()
        self.policy = make_policy(self.sc.policy)
        self.history: List[dict] = []
        # telemetry: round records ride the engine's pipeline; the
        # policies additionally feed the simulated-time trace (client
        # phase spans, aggregation instants, drop/staleness annotations)
        self.obs = trainer.obs
        self.trace = TraceBuilder()
        # a legacy-constructed trainer planned itself without this
        # SchedConfig; re-resolve so trainer.plan reflects the policy it
        # will actually run under (e.g. deadline/fedbuff force per-round
        # execution even when the bare engine would fuse).  An
        # algorithm x policy combination plan() rejects is left to raise
        # from run() (the legacy contract: construction succeeds).
        if trainer.plan.spec.sched is not self.sc:
            from repro.fed import api
            try:
                trainer.plan = api.plan(
                    api.RunSpec(model=trainer.cfg, firm=trainer.fc,
                                engine=trainer.ec, sched=self.sc),
                    d_trainable=trainer.d_trainable)
            except ValueError:
                pass

    def client_seconds(self, c: int, down_nbytes: float, up_nbytes: float,
                       local_steps: int) -> float:
        seq = self.trainer.ec.prompt_len + self.trainer.ec.max_new
        return client_round_seconds(self.profiles[c], down_nbytes,
                                    up_nbytes, local_steps,
                                    self.trainer.fc.batch_size, seq)

    def client_segments(self, c: int, down_nbytes: float,
                        up_nbytes: float, local_steps: int):
        """(phase, seconds) decomposition of ``client_seconds`` — what
        the trace emitter renders as consecutive spans."""
        seq = self.trainer.ec.prompt_len + self.trainer.ec.max_new
        return comms.client_round_segments(self.profiles[c], down_nbytes,
                                           up_nbytes, local_steps,
                                           self.trainer.fc.batch_size, seq)

    def run(self, rounds: Optional[int] = None) -> List[dict]:
        out = self.policy.run(self, rounds or self.trainer.fc.rounds)
        self.history.extend(out)
        return self.history

    def export_trace(self, path: str) -> dict:
        """Write the accumulated schedule as Chrome/Perfetto trace-event
        JSON (open at https://ui.perfetto.dev).  Validates before writing;
        returns the trace dict."""
        return self.trace.write(path)
