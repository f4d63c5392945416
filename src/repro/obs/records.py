"""Versioned metric records and the single round-summary constructor.

Every telemetry datum in the repo is one of three typed records:

  counter  a cumulative, monotonically accumulated quantity (wire bytes,
           jit dispatches) — sinks may diff consecutive values
  gauge    an instantaneous scalar (λ disagreement, param drift, KL,
           simulated round duration)
  series   a small vector sampled once per round (per-objective rewards,
           mean λ, per-client upload bytes)

Records carry ``schema=SCHEMA_VERSION`` so downstream consumers (the CI
bench report, offline notebooks) can reject files written under a
different layout instead of misparsing them.  Bump the version whenever
a record field or a round-summary key changes meaning.

This module is also the ONE place a federated round summary dict is
built: ``round_summary`` is shared by ``FederatedTrainer.run_round`` and
``run_rounds_fused`` (they used to hand-build near-identical dicts), and
``annotate_schedule`` / ``fedbuff_summary`` own the scheduler policies'
additions — so the summary schema cannot drift between producers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

SCHEMA_VERSION = 1

KINDS = ("counter", "gauge", "series")


def _plain(value):
    """Numpy/JAX scalars and arrays -> JSON-able python values."""
    if isinstance(value, (list, tuple)):
        return [_plain(v) for v in value]
    arr = np.asarray(value)
    if arr.ndim == 0:
        return arr.item()
    return arr.tolist()


@dataclasses.dataclass(frozen=True)
class MetricRecord:
    """One typed telemetry datum."""
    kind: str                               # counter | gauge | series
    name: str                               # e.g. "round/rewards"
    value: Any                              # scalar or (for series) list
    round: Optional[int] = None             # server round / version index
    labels: Tuple[Tuple[str, str], ...] = ()
    schema: int = SCHEMA_VERSION

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown record kind {self.kind!r}; "
                             f"expected one of {KINDS}")

    def to_json(self) -> dict:
        d = {"schema": self.schema, "kind": self.kind, "name": self.name,
             "value": _plain(self.value)}
        if self.round is not None:
            d["round"] = int(self.round)
        if self.labels:
            d["labels"] = dict(self.labels)
        return d


def counter(name: str, value, round: Optional[int] = None,
            **labels) -> MetricRecord:
    return MetricRecord("counter", name, _plain(value), round,
                        tuple(sorted((k, str(v)) for k, v in labels.items())))


def gauge(name: str, value, round: Optional[int] = None,
          **labels) -> MetricRecord:
    return MetricRecord("gauge", name, _plain(value), round,
                        tuple(sorted((k, str(v)) for k, v in labels.items())))


def series(name: str, value, round: Optional[int] = None,
           **labels) -> MetricRecord:
    return MetricRecord("series", name, _plain(value), round,
                        tuple(sorted((k, str(v)) for k, v in labels.items())))


# ------------------------------------------------- round-summary builders
def round_summary(*, stats: Dict[str, Any], comm_bytes: int, up_bytes: int,
                  down_bytes: int, participants: Sequence[int],
                  dispatches: float, up_nbytes: Sequence[int],
                  down_nbytes: int, local_steps: Sequence[int],
                  cohorts: int, fused: Optional[int] = None) -> dict:
    """The engine's per-round summary dict — the ONLY constructor.

    ``stats`` holds the device-computed statistics after the round's one
    host transfer (keys: rewards, lam_mean, lam_disagreement,
    param_drift, kl, per_client_lam, rewards_per_client, and for a model
    with expert blocks moe_max_load and moe_rows_per_pair).  Both the
    per-round and the fused executors call this with their own slices;
    ``tests/test_obs.py`` pins the output bit-identical to the legacy
    hand-built dicts.
    """
    summary = {
        "rewards": stats["rewards"],
        "lam_mean": stats["lam_mean"],
        "lam_disagreement": float(stats["lam_disagreement"]),
        "param_drift": float(stats["param_drift"]),
        "kl": float(stats["kl"]),
        "comm_bytes": comm_bytes,
        "up_bytes": up_bytes,
        "down_bytes": down_bytes,
        "participants": list(participants),
        "per_client_lam": stats["per_client_lam"],
        "rewards_per_client": stats["rewards_per_client"],
        "dispatches": dispatches,
        "up_nbytes": list(up_nbytes),
        "down_nbytes": down_nbytes,
        "local_steps": list(local_steps),
        "cohorts": cohorts,
    }
    for key in ("moe_max_load", "moe_rows_per_pair"):
        if key in stats:
            summary[key] = float(stats[key])
    if fused is not None:
        summary["fused"] = fused
    return summary


def annotate_schedule(summary: dict, *, policy: str, sim_time: float,
                      round_duration: float, dropped: Sequence[int],
                      client_seconds: Sequence[float], **extra) -> dict:
    """The sync/deadline policies' timing additions to an engine summary."""
    summary.update(policy=policy, sim_time=sim_time,
                   round_duration=round_duration, dropped=list(dropped),
                   client_seconds=[round(d, 6) for d in client_seconds],
                   **extra)
    return summary


def fedbuff_summary(*, version: int, sim_time: float, round_duration: float,
                    participants: Sequence[int], staleness: Sequence[int],
                    staleness_weights: Sequence[float], rewards,
                    rewards_per_client, comm_bytes: int, up_bytes: int,
                    down_bytes: int) -> dict:
    """One buffered-async aggregation's summary (fedbuff policy)."""
    return {
        "policy": "fedbuff",
        "version": version,
        "sim_time": sim_time,
        "round_duration": round_duration,
        "participants": list(participants),
        "staleness": list(staleness),
        "staleness_weights": [float(x) for x in staleness_weights],
        "rewards": rewards,
        "rewards_per_client": rewards_per_client,
        "comm_bytes": comm_bytes,
        "up_bytes": up_bytes,
        "down_bytes": down_bytes,
    }


# ------------------------------------------------- summary -> records
def records_from_round(summary: dict, *, round: Optional[int] = None,
                       policy: Optional[str] = None) -> List[MetricRecord]:
    """Fan one round-summary dict out into typed records.

    Emits a stable set of names under the ``round/`` (engine),
    ``comm/`` (ledger) and ``sched/`` (policy timing) prefixes; keys
    absent from the summary (e.g. ``sim_time`` on a bare engine run) are
    simply skipped.
    """
    labels = {"policy": policy} if policy else {}
    if "policy" in summary and not policy:
        labels = {"policy": summary["policy"]}
    out: List[MetricRecord] = []

    def g(name, key):
        if key in summary:
            out.append(gauge(name, summary[key], round, **labels))

    def s(name, key):
        if key in summary:
            out.append(series(name, summary[key], round, **labels))

    def c(name, key):
        if key in summary:
            out.append(counter(name, summary[key], round, **labels))

    s("round/rewards", "rewards")
    s("round/lam_mean", "lam_mean")
    g("round/lam_disagreement", "lam_disagreement")
    g("round/param_drift", "param_drift")
    g("round/kl", "kl")
    g("round/dispatches", "dispatches")
    g("round/cohorts", "cohorts")
    g("round/moe_max_load", "moe_max_load")
    g("round/moe_rows_per_pair", "moe_rows_per_pair")
    s("round/local_steps", "local_steps")
    c("comm/total_bytes", "comm_bytes")
    c("comm/up_bytes", "up_bytes")
    c("comm/down_bytes", "down_bytes")
    s("comm/up_nbytes", "up_nbytes")
    g("comm/down_nbytes", "down_nbytes")
    g("sched/sim_time", "sim_time")
    g("sched/round_duration", "round_duration")
    s("sched/client_seconds", "client_seconds")
    if "dropped" in summary:
        out.append(gauge("sched/dropped", len(summary["dropped"]), round,
                         **labels))
    if "staleness" in summary:
        st = summary["staleness"]
        out.append(gauge("sched/staleness_max",
                         max(st) if len(st) else 0, round, **labels))
        out.append(series("sched/staleness", st, round, **labels))
    return out
