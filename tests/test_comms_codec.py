"""Comms codec subsystem: Pallas kernels vs jnp oracles, codec roundtrips,
measured byte accounting, error feedback, registry, engine integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comms import (ErrorFeedback, IdentityCodec, LowRankCodec,
                         QuantizeCodec, TopKCodec, flat_to_tree, make_codec,
                         tree_to_flat)
from repro.core import comms
from repro.kernels import ops, ref
from repro.kernels.quantize import _DET_BITS

KEY = jax.random.PRNGKey(0)


def _tree(seed=0, scale=1.0):
    k = jax.random.PRNGKey(seed)
    return {"a": scale * jax.random.normal(k, (300, 16)),
            "b": {"c": scale * jax.random.normal(
                jax.random.fold_in(k, 1), (16, 64))}}


# ----------------------------------------------------- kernels vs oracles
@pytest.mark.parametrize("qmax", [127, 7])
@pytest.mark.parametrize("stochastic", [True, False])
@pytest.mark.parametrize("rows", [1, 5, 37])
def test_quantize_pallas_matches_ref(qmax, stochastic, rows):
    x = jax.random.normal(jax.random.fold_in(KEY, rows), (rows, 1024))
    if stochastic:
        bits = jax.random.bits(jax.random.fold_in(KEY, 1), x.shape,
                               jnp.uint32)
    else:
        bits = jnp.full(x.shape, _DET_BITS, jnp.uint32)
    cp, sp = ops.quantize(x, bits, qmax)                 # Pallas interpret
    cr, sr = ref.quantize(x, bits, qmax)                 # jnp oracle
    np.testing.assert_array_equal(np.asarray(cp), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(sr))
    dq_p = ops.dequantize(cp, sp)
    dq_r = ref.dequantize(cr, sr)
    np.testing.assert_array_equal(np.asarray(dq_p), np.asarray(dq_r))
    # per-element error bound: one quantization step
    err = np.abs(np.asarray(dq_p) - np.asarray(x))
    assert (err <= np.asarray(sp) + 1e-6).all()


def test_quantize_deterministic_rounds_to_nearest():
    # 0.25 and -0.25 sit exactly on a half step: the offset from
    # _DET_BITS is exactly 0.5, so they round half up
    x = jnp.asarray([[0.0, 0.24, 0.26, -0.26, 1.0, 0.25, -0.25]
                     + [0.0] * 1017])
    bits = jnp.full(x.shape, _DET_BITS, jnp.uint32)
    codes, scales = ref.quantize(x, bits, qmax=2)        # scale = 0.5
    got = np.asarray(codes[0, :7])
    np.testing.assert_array_equal(got, [0, 0, 1, -1, 2, 1, 0])
    kcodes, kscales = ops.quantize(x, bits, qmax=2)
    np.testing.assert_array_equal(np.asarray(kcodes), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(kscales), np.asarray(scales))


def test_stochastic_rounding_unbiased():
    """Mean of many stochastic quantizations converges to the input."""
    x = jnp.full((1, 1024), 0.35)
    x = x.at[0, 0].set(1.0)                              # pins scale
    acc = np.zeros((1, 1024))
    n = 200
    for s in range(n):
        bits = jax.random.bits(jax.random.fold_in(KEY, s), x.shape,
                               jnp.uint32)
        c, sc = ref.quantize(x, bits, qmax=7)
        acc += np.asarray(ref.dequantize(c, sc))
    np.testing.assert_allclose(acc[0, 1:] / n, 0.35, atol=0.02)


@pytest.mark.parametrize("thresh", [0.0, 0.5, 1.5])
def test_threshold_ops_pallas_match_ref(thresh):
    x = jax.random.normal(KEY, (37, 1024))
    np.testing.assert_allclose(
        float(ops.abs_threshold_count(x, jnp.float32(thresh))),
        float(ref.abs_threshold_count(x, thresh)))
    np.testing.assert_allclose(
        np.asarray(ops.abs_threshold_mask(x, jnp.float32(thresh))),
        np.asarray(ref.abs_threshold_mask(x, thresh)))


def test_topk_threshold_bisection_brackets_k():
    x = jax.random.normal(KEY, (11, 1024))
    for k in (1, 64, 2000):
        lo, hi = ops.topk_threshold(x, k)
        cnt_lo = float(ref.abs_threshold_count(x, lo))
        cnt_hi = float(ref.abs_threshold_count(x, hi))
        assert cnt_hi < k <= cnt_lo, (k, cnt_lo, cnt_hi)


def test_topk_support_pallas_matches_lax_topk():
    from repro.comms.sparsify import topk_support
    flat = jax.random.normal(KEY, (5000,))
    for k in (1, 250):
        ip, vp = topk_support(flat, k, use_pallas=True)
        ir, vr = topk_support(flat, k, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
        np.testing.assert_allclose(np.asarray(vp), np.asarray(vr))


def test_topk_support_ties_keep_largest():
    """Boundary ties must never evict a strictly larger entry: k-1 tied
    0.5s at low indices + one 5.0 at the end — the 5.0 must survive."""
    from repro.comms.sparsify import topk_support
    k = 8
    flat = jnp.zeros((4096,)).at[:k - 1].set(0.5).at[20:40].set(0.5)
    flat = flat.at[-1].set(5.0)
    idx, vals = topk_support(flat, k, use_pallas=True)
    assert 4095 in np.asarray(idx)
    assert float(vals[np.asarray(idx) == 4095][0]) == 5.0
    # every selected value is a 0.5-tie or the 5.0, never a zero
    assert (np.abs(np.asarray(vals)) >= 0.5).all()


def test_topk_support_fewer_nonzeros_than_k():
    """With m < k nonzeros the decoded vector must keep all of them
    (the old first-k-by-index path returned all zeros here)."""
    from repro.comms.sparsify import topk_support
    flat = jnp.zeros((4096,)).at[4092:].set(
        jnp.asarray([1.0, 2.0, 3.0, 4.0]))
    idx, vals = topk_support(flat, 16, use_pallas=True)
    dense = np.zeros(4096)
    dense[np.asarray(idx)] = np.asarray(vals)
    np.testing.assert_array_equal(dense[4092:], [1.0, 2.0, 3.0, 4.0])
    assert np.abs(dense[:4092]).sum() == 0.0


# -------------------------------------------------------- codec roundtrip
def test_flatten_roundtrip_preserves_tree():
    tree = _tree()
    flat, spec = tree_to_flat(tree)
    back = flat_to_tree(flat, spec)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_identity_codec_exact_and_f32_bytes():
    tree = _tree()
    flat, _ = tree_to_flat(tree)
    codec = IdentityCodec()
    payload, _ = codec.encode(tree)
    assert payload.nbytes == 4 * flat.size
    back = codec.decode(payload)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("spec,max_ratio,max_rel_err", [
    ("int8", 0.30, 0.03),
    ("int4", 0.16, 0.30),
    ("topk:0.05", 0.11, 1.0),
    ("lowrank:4", 0.15, 1.0),
])
def test_lossy_codecs_bytes_and_error(spec, max_ratio, max_rel_err):
    tree = _tree()
    flat, _ = tree_to_flat(tree)
    identity_bytes = 4 * flat.size
    codec = make_codec(spec)
    payload, _ = codec.encode(tree, key=KEY)
    assert payload.nbytes <= max_ratio * identity_bytes, spec
    dec, _ = tree_to_flat(codec.decode(payload))
    rel = float(jnp.linalg.norm(dec - flat) / jnp.linalg.norm(flat))
    assert rel <= max_rel_err, (spec, rel)
    # analytic model agrees with the measured bytes to within padding
    analytic = codec.bits_per_param(flat.size) / 8.0 * flat.size
    assert payload.nbytes <= analytic * 1.25 + 64


def test_int4_pack_unpack_roundtrip():
    from repro.comms.quantize import pack_int4, unpack_int4
    codes = jnp.asarray(
        np.random.RandomState(0).randint(-7, 8, (3, 64)), jnp.int8)
    np.testing.assert_array_equal(np.asarray(unpack_int4(pack_int4(codes))),
                                  np.asarray(codes))


def test_lowrank_codec_recovers_lowrank_signal():
    """A genuinely rank-1 flat vector is reconstructed near-exactly."""
    a, b = 64, 64
    u = jax.random.normal(KEY, (a, 1))
    v = jax.random.normal(jax.random.fold_in(KEY, 1), (1, b))
    tree = {"w": (u @ v).reshape(-1)}
    codec = LowRankCodec(rank=2)
    payload, _ = codec.encode(tree, key=KEY)
    dec = codec.decode(payload)["w"]
    flat = tree["w"]
    rel = float(jnp.linalg.norm(dec - flat) / jnp.linalg.norm(flat))
    assert rel < 1e-4


@pytest.mark.parametrize("spec", ["identity", "int8", "topk:0.1",
                                  "int8+ef", "topk:0.1+ef"])
def test_roundtrip_flat_matches_tree_roundtrip(spec):
    """The pre-flattened Payload boundary (used by the vectorized engine's
    batched delta uplink) is payload- and state-equivalent to the
    tree-based roundtrip."""
    tree = _tree()
    flat, tspec = tree_to_flat(tree)
    key = jax.random.fold_in(KEY, 7)
    c1, c2 = make_codec(spec), make_codec(spec)
    p1, s1, dec_tree = c1.roundtrip(tree, None, key=key)
    p2, s2, dec_flat = c2.roundtrip_flat(flat, tspec, None, key=key)
    assert p1.nbytes == p2.nbytes
    for k in p1.arrays:
        np.testing.assert_array_equal(np.asarray(p1.arrays[k]),
                                      np.asarray(p2.arrays[k]))
    np.testing.assert_allclose(np.asarray(tree_to_flat(dec_tree)[0]),
                               np.asarray(dec_flat), rtol=1e-6)
    if s1 is None:
        assert s2 is None
    else:                                 # error-feedback residuals agree
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-6)


# ------------------------------------------------- stacked-client encode
@pytest.mark.parametrize("spec", ["identity", "int8", "int4", "topk:0.1",
                                  "int8+ef", "int4+ef"])
def test_roundtrip_stacked_byte_identical_to_per_client(spec):
    """The stacked-axis encode (ONE batched kernel dispatch for the
    quantize codecs — the cohort dispatch path) must produce payloads,
    decodes and EF states bit-identical to C per-client
    ``roundtrip_flat`` calls with the same keys."""
    tree = _tree()
    flat, tspec = tree_to_flat(tree)
    flats = jnp.stack([flat, 2.0 * flat, -0.5 * flat])
    keys = [jax.random.fold_in(KEY, 10 + i) for i in range(3)]
    states = [None, jnp.zeros_like(flat), 0.1 * flat] \
        if spec.endswith("+ef") else [None] * 3

    c_stacked, c_per = make_codec(spec), make_codec(spec)
    ps, ns, dec = c_stacked.roundtrip_stacked(flats, tspec, states,
                                              keys=keys)
    assert dec.shape == flats.shape
    for i in range(3):
        p1, s1, d1 = c_per.roundtrip_flat(flats[i], tspec, states[i],
                                          key=keys[i])
        assert ps[i].nbytes == p1.nbytes
        for k in p1.arrays:
            np.testing.assert_array_equal(np.asarray(ps[i].arrays[k]),
                                          np.asarray(p1.arrays[k]))
        np.testing.assert_array_equal(np.asarray(dec[i]), np.asarray(d1))
        if s1 is None:
            assert ns[i] is None
        else:
            np.testing.assert_array_equal(np.asarray(ns[i]),
                                          np.asarray(s1))


def test_encode_stacked_matches_roundtrip_stacked():
    tree = _tree()
    flat, tspec = tree_to_flat(tree)
    flats = jnp.stack([flat, 3.0 * flat])
    keys = [jax.random.fold_in(KEY, 20 + i) for i in range(2)]
    codec = make_codec("int4")
    ps, _ = codec.encode_stacked(flats, tspec, keys=keys)
    ps2, _, _ = codec.roundtrip_stacked(flats, tspec, keys=keys)
    for a, b in zip(ps, ps2):
        for k in a.arrays:
            np.testing.assert_array_equal(np.asarray(a.arrays[k]),
                                          np.asarray(b.arrays[k]))


# ------------------------------------------------------- delta downlink
def test_delta_codec_first_round_full_then_deltas():
    """delta+identity is lossless and the reference chain tracks the
    reconstruction exactly."""
    flat, spec = tree_to_flat(_tree())
    codec = make_codec("delta")
    st = None
    x = flat
    for _ in range(3):
        x = x + 0.01
        p, st, dec = codec.roundtrip_flat(x, spec, st, key=KEY)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(x),
                                   rtol=1e-5, atol=1e-6)
    # from round 2 the wire carries the (tiny) delta, not the weights
    assert float(jnp.abs(p.arrays["values"]).max()) <= 0.011


def test_delta_int8_lower_distortion_same_bytes():
    """Same bits/param as int8, but the quantizer scale tracks the small
    round-to-round delta: distortion collapses from round 2 on."""
    key = jax.random.PRNGKey(3)
    flat, spec = tree_to_flat(_tree(seed=3))
    plain, delta = make_codec("int8"), make_codec("delta+int8")
    assert delta.bits_per_param(flat.size) == plain.bits_per_param(
        flat.size)
    sp, sd = None, None
    x = flat
    errs_p, errs_d = [], []
    for t in range(1, 5):
        x = x + 0.005 * jax.random.normal(jax.random.fold_in(key, t),
                                          x.shape)
        kq = jax.random.fold_in(key, 100 + t)
        pp, sp, dp = plain.roundtrip_flat(x, spec, sp, key=kq)
        pd, sd, dd = delta.roundtrip_flat(x, spec, sd, key=kq)
        assert pp.nbytes == pd.nbytes
        errs_p.append(float(jnp.linalg.norm(dp - x)))
        errs_d.append(float(jnp.linalg.norm(dd - x)))
    # round 1 transmits the full params either way; afterwards the delta
    # codec is at least 10x more accurate at identical wire bytes
    assert all(d < p / 10 for p, d in zip(errs_p[1:], errs_d[1:]))


def test_delta_codec_tree_roundtrip_and_registry():
    from repro.comms import DeltaCodec
    codec = make_codec("delta+int8+ef")
    assert isinstance(codec, DeltaCodec)
    assert isinstance(codec.inner, ErrorFeedback)
    assert codec.name == "delta+int8+ef"
    tree = _tree(scale=0.1)
    p, st, dec = codec.roundtrip(tree, None, key=KEY)
    assert p.nbytes < 0.3 * 4 * tree_to_flat(tree)[0].size
    rel = float(jnp.linalg.norm(tree_to_flat(dec)[0]
                                - tree_to_flat(tree)[0])
                / jnp.linalg.norm(tree_to_flat(tree)[0]))
    assert rel < 0.05
    with pytest.raises(NotImplementedError):
        codec.decode(p)                   # needs the receiver reference


@pytest.mark.slow
def test_engine_delta_downlink_trains():
    tr = _tiny_trainer(downlink_codec="delta+int8")
    h = tr.run(2)
    assert np.isfinite(h[-1]["rewards"]).all()
    d = tr.d_trainable
    # two rounds x C=2 recipients of ~1 byte/param broadcasts
    assert h[-1]["down_bytes"] <= 0.30 * 2 * 2 * 4 * d


# --------------------------------------------------------- error feedback
def test_error_feedback_residual_reinjected():
    """EF conservation: at every step, sum(decoded so far) + residual
    == sum(inputs so far) *exactly* — compression error is deferred,
    never lost — and the relative deferred mass shrinks over time."""
    tree = _tree(scale=0.1)
    flat, _ = tree_to_flat(tree)
    codec = make_codec("topk:0.1+ef")
    state, total = None, jnp.zeros_like(flat)
    rels = {}
    for t in range(1, 31):
        payload, state = codec.encode(tree, state,
                                      key=jax.random.fold_in(KEY, t))
        dec, _ = tree_to_flat(codec.decode(payload))
        total = total + dec
        # conservation identity: total + e_t == t * x (up to f32 roundoff)
        np.testing.assert_allclose(np.asarray(total + state),
                                   np.asarray(t * flat),
                                   rtol=1e-4, atol=1e-5)
        rels[t] = float(jnp.linalg.norm(total - t * flat)
                        / jnp.linalg.norm(t * flat))
    # deferred fraction decays as the residual re-injects (EF property)
    assert rels[30] < rels[5]
    assert rels[30] < 0.5


def test_error_feedback_beats_plain_topk():
    tree = _tree(scale=0.1)
    flat, _ = tree_to_flat(tree)

    def accumulate(spec):
        codec = make_codec(spec)
        state, total = None, jnp.zeros_like(flat)
        for t in range(15):
            p, state = codec.encode(tree, state,
                                    key=jax.random.fold_in(KEY, t))
            dec, _ = tree_to_flat(codec.decode(p))
            total = total + dec
        return float(jnp.linalg.norm(total - 15.0 * flat))

    assert accumulate("topk:0.02+ef") < accumulate("topk:0.02")


# ---------------------------------------------------------------- registry
def test_registry_specs_parse():
    assert make_codec("identity").name == "identity"
    assert make_codec("int8").name == "int8"
    assert make_codec("topk:0.1").frac == 0.1
    assert make_codec("lowrank:8").rank == 8
    ef = make_codec("int4+ef")
    assert isinstance(ef, ErrorFeedback) and ef.inner.bits == 4


def test_registry_rejects_bad_specs():
    with pytest.raises(ValueError):
        make_codec("gzip")
    with pytest.raises(ValueError):
        make_codec("identity+ef")
    with pytest.raises(ValueError):
        make_codec("topk:1.5")


# ------------------------------------------------------- byte accounting
def test_ledger_accepts_payloads_and_trees():
    tree = _tree()
    flat, _ = tree_to_flat(tree)
    ledger = comms.CommsLedger()
    ledger.send_up(tree)                                 # raw pytree
    assert ledger.up_bytes == 4 * flat.size
    payload, _ = make_codec("int8").encode(tree, key=KEY)
    ledger.send_up(payload)                              # encoded payload
    assert ledger.up_bytes == 4 * flat.size + payload.nbytes
    bf16 = {"x": jnp.ones((10,), jnp.bfloat16)}
    ledger.send_down(bf16)                               # itemsize-aware
    assert ledger.down_bytes == 20


# ---------------------------------------------------- engine integration
def _tiny_trainer(**kw):
    from repro.configs import get_config
    from repro.configs.base import FIRMConfig
    from repro.fed.engine import EngineConfig, FederatedTrainer
    cfg = get_config("llama-3.2-1b").reduced(n_layers=2, d_model=64,
                                             vocab=256)
    fc = FIRMConfig(n_objectives=2, n_clients=2, local_steps=1,
                    batch_size=2, beta=0.05)
    ec = EngineConfig(algorithm=kw.pop("algorithm", "firm"), max_new=6,
                      prompt_len=4, **kw)
    return FederatedTrainer(cfg, fc, ec)


def test_engine_config_default_not_shared():
    """The EngineConfig default must be constructed per trainer, not one
    dataclass instance shared by every FederatedTrainer (mutating one
    trainer's ec must not leak into the next)."""
    import inspect
    from repro.fed.engine import EngineConfig, FederatedTrainer
    sig = inspect.signature(FederatedTrainer.__init__)
    assert sig.parameters["ec"].default is None
    tr = _tiny_trainer()
    assert isinstance(tr.ec, EngineConfig)
    tr.ec.algorithm = "mutated"
    assert EngineConfig().algorithm == "firm"


@pytest.mark.slow
def test_engine_int8_uplink_byte_ratio():
    """Acceptance: measured int8 uplink <= ~30% of the identity codec,
    training still healthy."""
    base = _tiny_trainer()
    s0 = base.run(1)[-1]
    tr = _tiny_trainer(uplink_codec="int8+ef")
    s1 = tr.run(1)[-1]
    assert s1["up_bytes"] <= 0.30 * s0["up_bytes"]
    assert s1["down_bytes"] == s0["down_bytes"]          # downlink raw
    assert np.isfinite(s1["rewards"]).all()
    # EF residual allocated per client, client-local
    assert len(tr._uplink_state) == 2
    assert tr._uplink_state[0] is not None


@pytest.mark.slow
def test_engine_coded_downlink_and_fedcmoo_grads():
    tr = _tiny_trainer(uplink_codec="topk:0.1+ef", downlink_codec="int8")
    s = tr.run(1)[-1]
    d = tr.d_trainable
    assert np.isfinite(s["rewards"]).all()
    assert s["down_bytes"] <= 0.30 * 2 * 4 * d       # int8 down, C=2
    assert s["up_bytes"] <= 0.25 * 2 * 4 * d         # topk:0.1 ~ 20% of f32
    fed = _tiny_trainer(algorithm="fedcmoo", uplink_codec="int8")
    sf = fed.run(1)[-1]
    assert np.isfinite(sf["rewards"]).all()
    # raw up would be C*(M*K+1)*4d = 24d: M=2 grad payloads + delta, int8
    assert sf["up_bytes"] <= 0.30 * 24 * d


def test_analytic_codec_round_bytes():
    d, c = 100_000, 8
    raw = comms.firm_round_bytes(d, c)
    coded = comms.firm_round_bytes_codec(d, c, uplink_codec="int8")
    assert coded["down"] == raw["down"]
    assert coded["up"] < 0.3 * raw["up"]
    both = comms.firm_round_bytes_codec(d, c, uplink_codec="int4+ef",
                                        downlink_codec="int8")
    assert both["total"] < 0.3 * raw["total"]
    fed = comms.fedcmoo_round_bytes_codec(d, c, n_objectives=3,
                                          local_steps=2,
                                          uplink_codec="int8")
    fed_raw = comms.fedcmoo_round_bytes(d, c, 3, 2)
    assert fed["up"] < 0.3 * fed_raw["up"]
