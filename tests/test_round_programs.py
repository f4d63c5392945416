"""The per-round vectorized engine's work between programs: a steady-state
round runs only named programs, and the work moved inside them (key
draws, the downlink round trip, the uplink's stacking and per-client
slicing, the summary means) matches the per-row eager computation bit
for bit."""
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FIRMConfig
from repro.fed import engine
from repro.fed.engine import EngineConfig, FederatedTrainer
from repro.obs import jitwatch

from tests.test_fed_vectorized import _cfg

ROUND_PROGRAMS = {"downlink_roundtrip", "stack_trees", "vec_round[firm]",
                  "unstack", "delta_flat", "ef_roundtrip_stacked",
                  "flat_aggregate", "summary_device"}


def _trainer(*, n_clients, local_steps=1, participation=1.0,
             uplink="int8+ef", downlink="identity"):
    fc = FIRMConfig(n_objectives=2, n_clients=n_clients,
                    local_steps=local_steps, batch_size=2, beta=0.05,
                    participation=participation)
    ec = EngineConfig(algorithm="firm", max_new=6, prompt_len=4, seed=0,
                      uplink_codec=uplink, downlink_codec=downlink)
    return FederatedTrainer(_cfg(), fc, ec)


class _Compiles(logging.Handler):
    def __init__(self):
        super().__init__()
        self.names = []

    def emit(self, record):
        m = re.match(r"Compiling jit\((.*?)\) with", record.getMessage())
        if m:
            self.names.append(m.group(1))


@pytest.mark.parametrize("participation,extra", [
    (1.0, set()), (0.5, {"participants"})])
def test_steady_state_round_runs_only_named_programs(participation, extra):
    """After two rounds, with every compiled program dropped, a third
    round compiles exactly the engine's named programs: no eager op
    (key split, slice, stack, convert, mean) runs between them."""
    tr = _trainer(n_clients=4, participation=participation)
    tr.run(2)
    jax.clear_caches()
    seen = _Compiles()
    logger = logging.getLogger("jax")
    logger.addHandler(seen)
    jax.config.update("jax_log_compiles", True)
    try:
        tr.run(1)
    finally:
        jax.config.update("jax_log_compiles", False)
        logger.removeHandler(seen)
    assert sorted(seen.names) == sorted(ROUND_PROGRAMS | extra)
    assert set(seen.names) <= {p.name for p in jitwatch._PROGRAMS.values()}
    assert tr.history[-1]["dispatches"] == 6


def _eager_round(tr):
    """One full-participation round as the engine ran it with the work
    between programs done eagerly: sequential ``_next_key`` draws, the
    downlink codec's host round trip and a per-row ``roundtrip_flat``
    per client.  Returns (rng after the downlink key, generation keys,
    uplink keys, payloads)."""
    parts = list(range(tr.fc.n_clients))
    _, tr._downlink_state, broadcast = tr.downlink_codec.roundtrip(
        tr.global_trainable, tr._downlink_state, key=tr._next_key())
    rng_mid = tr._rng
    gen_keys = jnp.stack([jnp.stack([tr._next_key() for _ in parts])
                          for _ in range(tr.fc.local_steps)])
    res = tr._local_phase_vectorized(tr.fc, parts, broadcast,
                                     gen_keys=gen_keys)
    flats = engine._delta_flat_jit(res.stacked_trainable, broadcast)
    up_keys = [tr._next_key() for _ in parts]
    payloads, decoded = [], []
    for i, c in enumerate(parts):
        p, tr._uplink_state[c], dec = tr.uplink_codec.roundtrip_flat(
            flats[i], tr._delta_spec, tr._uplink_state[c], key=up_keys[i])
        payloads.append(p)
        decoded.append(dec)
    tr.global_trainable = tr._aggregate_flat(
        broadcast, jnp.stack(decoded), np.zeros(len(parts), np.float32))
    return rng_mid, gen_keys, jnp.stack(up_keys), payloads


def _equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("downlink", ["identity", "int8"])
@pytest.mark.parametrize("uplink", ["int8+ef", "int4+ef", "identity"])
def test_moved_work_matches_per_row_eager_round(uplink, downlink):
    """Three rounds of the engine against three eager rounds: the same
    keys, uplink residuals, wire buffers, Adam moments and global
    adapter, bit for bit."""
    c, k = 3, 2
    tr = _trainer(n_clients=c, local_steps=k, uplink=uplink,
                  downlink=downlink)
    ref = _trainer(n_clients=c, local_steps=k, uplink=uplink,
                   downlink=downlink)
    seen_keys = []
    stacked_rt = tr.uplink_codec.roundtrip_stacked

    def spy(flats, spec, states=None, *, keys=None):
        seen_keys.append(keys)
        return stacked_rt(flats, spec, states, keys=keys)

    tr.uplink_codec.roundtrip_stacked = spy
    draw = jax.jit(engine._draw_keys, static_argnums=(1, 2))
    for _ in range(3):
        tr.run_round()
        rng_mid, gen_keys, up_keys, payloads = _eager_round(ref)
        # the program's key draws continue the stream like _next_key
        rng, drawn = draw(rng_mid, k, c)
        _equal(drawn, gen_keys)
        _equal(draw(rng, 1, c)[1][0], up_keys)
        _equal(jnp.asarray(seen_keys[-1]), up_keys)
        _equal(tr._rng, ref._rng)
        for p, q in zip(tr._last_up_payloads, payloads, strict=True):
            assert sorted(p.arrays) == sorted(q.arrays)
            _equal(p.arrays, q.arrays)
            assert p.nbytes == q.nbytes
    for s, t in zip(tr._uplink_state, ref._uplink_state, strict=True):
        assert (s is None) == (t is None)
        if s is not None:
            _equal(s, t)
    for a, b in zip(tr.client_states, ref.client_states, strict=True):
        _equal((a.opt.mu, a.opt.nu, a.trainable),
               (b.opt.mu, b.opt.nu, b.trainable))
    _equal(tr.global_trainable, ref.global_trainable)
    assert [d._count for d in tr.datasets] == [d._count
                                               for d in ref.datasets]
