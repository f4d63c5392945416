"""Fused multi-round engine: R rounds as one jitted program.

Pins the fused round scan (``EngineConfig.fused_rounds``) against the
per-round vectorized engine: bit-identical rewards and aggregates for
identity and int8+ef uplinks, codec-state parity (EF residuals, delta
reconstructions), in-graph participation fold-in equivalence with the
host-side named stream, static byte accounting, and the ScheduledTrainer
``sync`` policy riding the fused path unchanged.

The R=2/C=2 smoke test is the fast-lane compile canary — a fused-program
trace/compile regression fails PRs here instead of on main.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comms import make_codec, tree_to_flat
from repro.configs.base import FIRMConfig, SchedConfig
from repro.fed.engine import EngineConfig, FederatedTrainer
from repro.fed.sched.policies import ScheduledTrainer

from tests.test_fed_vectorized import _cfg


def _trainer(algorithm="firm", *, n_clients=2, local_steps=2, m=2, seed=0,
             fused_rounds=1, **kw):
    fc_kw = {k: kw.pop(k) for k in ("client_preferences", "participation",
                                    "client_local_steps") if k in kw}
    fc = FIRMConfig(n_objectives=m, n_clients=n_clients,
                    local_steps=local_steps, batch_size=2, beta=0.05,
                    **fc_kw)
    ec = EngineConfig(algorithm=algorithm, max_new=6, prompt_len=4,
                      seed=seed, fused_rounds=fused_rounds, **kw)
    return FederatedTrainer(_cfg(), fc, ec)


def _assert_bit_identical(h0, h1, trees=()):
    for a, b in zip(h0, h1):
        np.testing.assert_array_equal(np.asarray(a["rewards"]),
                                      np.asarray(b["rewards"]))
        np.testing.assert_array_equal(
            np.asarray(a["rewards_per_client"]),
            np.asarray(b["rewards_per_client"]))
        np.testing.assert_array_equal(np.asarray(a["per_client_lam"]),
                                      np.asarray(b["per_client_lam"]))
        assert a["participants"] == b["participants"]
        assert a["comm_bytes"] == b["comm_bytes"]
        assert a["up_bytes"] == b["up_bytes"]
        assert a["down_bytes"] == b["down_bytes"]
    for t0, t1 in zip(*trees) if trees else ():
        np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))


# ---------------------------------------------------------- fast-lane smoke
def test_fused_smoke_compiles_r2_c2():
    """Fast-lane canary: the fused program jits and runs at R=2, C=2 with
    O(1) dispatches per chunk and sane summaries."""
    tr = _trainer(fused_rounds=2)
    hist = tr.run(2)
    assert len(hist) == 2
    assert all(np.isfinite(np.asarray(s["rewards"])).all() for s in hist)
    assert all(s["fused"] == 2 for s in hist)
    assert all(s["cohorts"] == 1 for s in hist)
    # stack + fused scan + unstack across the whole chunk
    assert sum(s["dispatches"] for s in hist) <= 4


def test_fused_equivalence_identity_fast():
    """R=2 fused vs per-round: rewards and aggregates bit-identical."""
    h0 = _trainer().run(2)
    tr1 = _trainer(fused_rounds=2)
    h1 = tr1.run(2)
    tr0 = _trainer()
    tr0.run(2)
    _assert_bit_identical(h0, h1)
    for l0, l1 in zip(jax.tree_util.tree_leaves(tr0.global_trainable),
                      jax.tree_util.tree_leaves(tr1.global_trainable)):
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


# ------------------------------------------------- fused-R vs per-round
@pytest.mark.slow
@pytest.mark.parametrize("alg,uplink", [
    ("firm", "identity"), ("firm", "int8+ef"),
    ("linear", "identity"), ("linear", "int8+ef")])
def test_fused_vs_round_loop_equivalent(alg, uplink):
    """R=3 fused chunk vs three per-round dispatches: rewards are
    bit-identical and the EF residual buffers match exactly after R
    rounds (the host EF path computes its residual in the same jitted
    composition as the fused scan, so even the fms-contracted bits
    agree)."""
    rounds = 3
    tr0 = _trainer(alg, uplink_codec=uplink)
    h0 = tr0.run(rounds)
    tr1 = _trainer(alg, uplink_codec=uplink, fused_rounds=rounds)
    h1 = tr1.run(rounds)
    _assert_bit_identical(h0, h1)
    for l0, l1 in zip(jax.tree_util.tree_leaves(tr0.global_trainable),
                      jax.tree_util.tree_leaves(tr1.global_trainable)):
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    for s0, s1 in zip(tr0._uplink_state, tr1._uplink_state):
        assert (s0 is None) == (s1 is None)
        if s0 is not None:
            np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


@pytest.mark.slow
def test_fused_delta_downlink_reconstruction_matches():
    """delta+int8 downlink under the fused scan: the reference
    reconstruction chain matches the per-round path to ≤ 1e-6 (the
    reconstruction add is fma-contracted in-graph) and rewards stay
    bit-identical."""
    rounds = 3
    kw = dict(uplink_codec="int8+ef", downlink_codec="delta+int8")
    tr0 = _trainer(**kw)
    h0 = tr0.run(rounds)
    tr1 = _trainer(fused_rounds=rounds, **kw)
    h1 = tr1.run(rounds)
    _assert_bit_identical(h0, h1)
    ref0, _ = tr0._downlink_state
    ref1, _ = tr1._downlink_state
    np.testing.assert_allclose(np.asarray(ref0), np.asarray(ref1),
                               atol=1e-6)
    for s0, s1 in zip(tr0._uplink_state, tr1._uplink_state):
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                                   atol=1e-6)


def test_fused_partial_participation_matches_named_stream():
    """In-graph participation fold-in ≡ host-side keying on
    (seed, round): the fused chunk draws the same participants as
    ``_sample_participants`` and matches the per-round run."""
    rounds = 3
    tr0 = _trainer(n_clients=4, participation=0.5)
    h0 = tr0.run(rounds)
    tr1 = _trainer(n_clients=4, participation=0.5, fused_rounds=rounds)
    h1 = tr1.run(rounds)
    _assert_bit_identical(h0, h1)
    # a fresh twin reproduces each round's draw from the named stream
    probe = _trainer(n_clients=4, participation=0.5)
    for r, s in enumerate(h1):
        assert s["participants"] == probe._sample_participants(round_idx=r)
        assert len(s["participants"]) == 2


def test_fused_byte_accounting_matches_measured():
    """nbytes_static drives the fused ledger: totals equal the per-round
    path's measured Payload accounting for coded links."""
    rounds = 2
    kw = dict(uplink_codec="int4+ef", downlink_codec="int8")
    h0 = _trainer(**kw).run(rounds)
    h1 = _trainer(fused_rounds=rounds, **kw).run(rounds)
    for a, b in zip(h0, h1):
        assert a["up_bytes"] == b["up_bytes"]
        assert a["down_bytes"] == b["down_bytes"]
        assert a["up_nbytes"] == b["up_nbytes"]
        assert a["down_nbytes"] == b["down_nbytes"]


def test_fused_mode_gating():
    """fedcmoo, the per-client loop, and heterogeneous static configs all
    fall back to per-round execution; run_rounds_fused refuses them."""
    assert _trainer()._fused_mode()[0]
    assert not _trainer("fedcmoo")._fused_mode()[0]
    assert not _trainer(vectorized_clients=False)._fused_mode()[0]
    het = _trainer(n_clients=2, client_local_steps=(1, 2))
    assert not het._fused_mode()[0]
    with pytest.raises(ValueError, match="fused_rounds"):
        het.run_rounds_fused(2)
    # run() falls back silently and still completes the horizon
    tr = _trainer("fedcmoo", fused_rounds=4)
    assert len(tr.run(2)) == 2


def test_fused_uniform_local_steps_override():
    """A uniform client_local_steps override forms one cohort whose K
    differs from fc.local_steps; the fused chunk must honor it."""
    kw = dict(n_clients=2, local_steps=1, client_local_steps=(2, 2))
    h0 = _trainer(**kw).run(2)
    h1 = _trainer(fused_rounds=2, **kw).run(2)
    assert h1[0]["local_steps"] == [2, 2]
    _assert_bit_identical(h0, h1)


def test_fused_chunking_partial_tail():
    """A horizon that is not a multiple of fused_rounds runs the tail as
    a smaller chunk (or single round) and matches the per-round run."""
    h0 = _trainer().run(3)
    h1 = _trainer(fused_rounds=2).run(3)       # chunk of 2 + chunk of 1
    _assert_bit_identical(h0, h1)


# ------------------------------------------------- scheduler integration
def test_sync_policy_rides_fused_rounds():
    """ScheduledTrainer(sync) over a fused trainer: results AND simulated
    timing are unchanged vs the per-round sync policy."""
    rounds = 2
    s0 = ScheduledTrainer(_trainer(uplink_codec="int8+ef"),
                          SchedConfig(policy="sync", profile="bimodal"))
    h0 = s0.run(rounds)
    s1 = ScheduledTrainer(
        _trainer(uplink_codec="int8+ef", fused_rounds=rounds),
        SchedConfig(policy="sync", profile="bimodal"))
    h1 = s1.run(rounds)
    for a, b in zip(h0, h1):
        np.testing.assert_array_equal(np.asarray(a["rewards"]),
                                      np.asarray(b["rewards"]))
        assert a["participants"] == b["participants"]
        assert a["round_duration"] == b["round_duration"]
        assert a["sim_time"] == b["sim_time"]
        assert a["client_seconds"] == b["client_seconds"]
        assert b["policy"] == "sync"


# ------------------------------------------------- traced codec contract
@pytest.mark.parametrize("spec", ["identity", "int8", "int4", "topk:0.05",
                                  "lowrank:4", "int8+ef", "int4+ef",
                                  "topk:0.05+ef", "delta+int8",
                                  "delta+int8+ef"])
def test_nbytes_static_matches_measured(spec):
    """Every codec's static byte model equals the measured Payload bytes
    (the fused engine accounts bytes without materializing payloads)."""
    key = jax.random.PRNGKey(0)
    for d in (1000, 4096, 50000):
        flat = jax.random.normal(key, (d,)) * 0.01
        tspec = tree_to_flat({"w": flat})[1]
        codec = make_codec(spec)
        payload, _, _ = codec.roundtrip_flat(flat, tspec, None, key=key)
        assert codec.nbytes_static(d) == payload.nbytes


@pytest.mark.parametrize("spec", ["identity", "int8", "topk:0.05",
                                  "lowrank:4", "int8+ef", "delta+int8"])
def test_roundtrip_traced_matches_host(spec):
    """The in-graph roundtrip (jitted) decodes bit-identically to the
    host-boundary roundtrip_flat, with codec state threaded as arrays.
    The delta chain's host reconstruction add stays eager (the in-graph
    one is fma-contracted), so it matches to 1e-6 instead of exactly."""
    key = jax.random.PRNGKey(1)
    d = 5000
    flat = jax.random.normal(key, (d,)) * 0.01
    tspec = tree_to_flat({"w": flat})[1]
    c_host, c_traced = make_codec(spec), make_codec(spec)
    host_state, traced_state = None, c_traced.init_state_traced(d, None)
    fn = jax.jit(lambda f, s, k: c_traced.roundtrip_traced(f, s, key=k))
    x = flat
    for t in range(3):
        x = x + 0.005 * jax.random.normal(jax.random.fold_in(key, t),
                                          (d,))
        kq = jax.random.fold_in(key, 100 + t)
        _, host_state, dec_h = c_host.roundtrip_flat(x, tspec, host_state,
                                                     key=kq)
        dec_t, traced_state = fn(x, traced_state, kq)
        if spec.startswith("delta+"):
            np.testing.assert_allclose(np.asarray(dec_h),
                                       np.asarray(dec_t), atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(dec_h),
                                          np.asarray(dec_t))


@pytest.mark.parametrize("spec", ["identity", "int8", "int4", "topk:0.05",
                                  "lowrank:4", "int8+ef", "topk:0.05+ef",
                                  "delta+int8"])
def test_traced_stacked_matches_host_stacked(spec):
    """roundtrip_traced_stacked (the fused uplink boundary) matches the
    host stacked Payload boundary bit for bit over two rounds: decodes
    and the codec states threaded between them."""
    key = jax.random.PRNGKey(2)
    c, d = 3, 5000
    tspec = tree_to_flat({"w": jnp.zeros((d,))})[1]
    ch, ct = make_codec(spec), make_codec(spec)
    host, traced = [None] * c, ct.init_states_traced(d, [None] * c)
    fn = jax.jit(lambda f, s, k: ct.roundtrip_traced_stacked(f, s, keys=k))
    for t in range(2):
        flats = jax.random.normal(jax.random.fold_in(key, t), (c, d)) * 0.01
        keys = jnp.stack([jax.random.fold_in(key, 10 * t + i)
                          for i in range(c)])
        _, host, dec_h = ch.roundtrip_stacked(flats, tspec, host,
                                              keys=list(keys))
        dec_t, traced = fn(flats, traced, keys)
        np.testing.assert_array_equal(np.asarray(dec_h), np.asarray(dec_t))
        for h, r in zip(jax.tree_util.tree_leaves(host),
                        jax.tree_util.tree_leaves(ct.states_to_host(traced,
                                                                    c)),
                        strict=True):
            np.testing.assert_array_equal(np.asarray(h), np.asarray(r))


def test_payload_entropy_estimate():
    """nbytes_entropy: discrete-code payloads compress below their fixed
    layout; f32-only payloads report nbytes unchanged."""
    key = jax.random.PRNGKey(3)
    d = 50000
    # training-delta-like: heavy mass near zero -> skewed code histogram
    flat = jax.random.normal(key, (d,)) * 0.01 * (
        jax.random.uniform(jax.random.fold_in(key, 1), (d,)) < 0.2)
    tspec = tree_to_flat({"w": flat})[1]
    for spec in ("int8", "int4", "topk:0.05"):
        p, _, _ = make_codec(spec).roundtrip_flat(flat, tspec, None,
                                                  key=key)
        assert 0 < p.nbytes_entropy < p.nbytes
    p_id, _, _ = make_codec("identity").roundtrip_flat(flat, tspec, None)
    assert p_id.nbytes_entropy == p_id.nbytes
