"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes.  (Hypothesis property sweeps live in
test_properties_hypothesis.py so this module collects without it.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gram import gram_pallas
from repro.kernels.rmsnorm import rmsnorm as rmsnorm_pallas


# ------------------------------------------------------------------ gram
@pytest.mark.parametrize("m", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("d", [100, 8192, 10000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_sweep(m, d, dtype):
    key = jax.random.PRNGKey(m * 1000 + d)
    x = (jax.random.normal(key, (m, d)) * 0.3).astype(dtype)
    got = gram_pallas(x, interpret=True)
    want = ref.gram(x)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("seed", [0, 7, 42])
def test_gram_invariants(seed):
    """Deterministic twin of the hypothesis sweep: symmetry + PSD."""
    rng = np.random.RandomState(seed)
    m, d = int(rng.randint(1, 9)), int(rng.randint(1, 3000))
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, d))
    got = np.asarray(gram_pallas(x, interpret=True))
    np.testing.assert_allclose(got, np.asarray(ref.gram(x)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, got.T, atol=1e-5)
    assert np.linalg.eigvalsh(got).min() > -1e-3


# -------------------------------------------------------------- attention
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(hq, hkv, causal, dtype):
    key = jax.random.PRNGKey(0)
    b, s, dh = 2, 128, 64
    q = jax.random.normal(key, (b, s, hq, dh)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (b, s, hkv, dh)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (b, s, hkv, dh)).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [16, 64, 128])
def test_flash_attention_sliding_window(window):
    key = jax.random.PRNGKey(3)
    b, s, h, dh = 1, 256, 2, 32
    q = jax.random.normal(key, (b, s, h, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, dh))
    got = flash_attention(q, k, v, causal=True, sliding_window=window,
                          block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_matches_model_xla_twin():
    """The XLA chunked_attention used by the models == the Pallas kernel."""
    from repro.models.attention import chunked_attention
    key = jax.random.PRNGKey(9)
    b, s, hq, hkv, dh = 2, 128, 4, 2, 32
    q = jax.random.normal(key, (b, s, hq, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, dh))
    a = chunked_attention(q, k, v, causal=True, block=64)
    p = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(p),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("shape", [(7, 64), (2, 33, 256), (1, 1, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, shape).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(key, 1), shape[-1:]).astype(dtype)
    got = rmsnorm_pallas(x, g, interpret=True, block_rows=4)
    want = ref.rmsnorm(x, g)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_rmsnorm_matches_model_impl():
    from repro.models.common import rms_norm
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (4, 128))
    g = jnp.ones((128,))
    np.testing.assert_allclose(
        np.asarray(rmsnorm_pallas(x, g, interpret=True)),
        np.asarray(rms_norm({"g": g}, x)), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- dispatch
def test_ops_dispatch_gram_pytrees():
    key = jax.random.PRNGKey(2)
    grads = [{"a": jax.random.normal(jax.random.fold_in(key, j), (40,))}
             for j in range(2)]
    got = ops.gram_from_pytrees(grads)
    from repro.core.mgda import gram_matrix
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(gram_matrix(grads)),
                               rtol=1e-4, atol=1e-5)


# -------------------------------------------------------------------- ssd
@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("shape", [(2, 64, 16, 8), (1, 128, 64, 64),
                                   (4, 32, 8, 16)])
def test_ssd_scan_sweep(chunk, shape):
    from repro.kernels.ssd import ssd_scan
    bh, s, hd, ds = shape
    key = jax.random.PRNGKey(bh * 100 + s)
    x = 0.5 * jax.random.normal(key, (bh, s, hd))
    b = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (bh, s, ds))
    c = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (bh, s, ds))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3),
                                           (bh, s)))
    da = -0.1 * jax.nn.softplus(jax.random.normal(
        jax.random.fold_in(key, 4), (bh, s)))
    got = ssd_scan(x, b, c, dt, da, chunk=chunk, interpret=True)
    want = ref.ssd_scan(x, b, c, dt, da)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


def test_ssd_kernel_matches_model_ssm_block():
    """The Pallas SSD and the model's chunked SSD agree with the exact
    per-token recurrence (transitively with each other)."""
    from repro.configs import get_config
    from repro.models import ssm
    cfg = get_config("zamba2-1.2b").reduced(n_layers=2, d_model=64,
                                            vocab=64)
    p = ssm.init_mamba2(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model))
    y_seq = ssm.mamba2_seq(p, cfg, x)
    cache = ssm.init_mamba2_cache(cfg, 1)
    ys = []
    for t in range(32):
        y_t, cache = ssm.mamba2_decode(p, cfg, x[:, t:t + 1], cache)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(y_seq),
                               np.asarray(jnp.concatenate(ys, 1)),
                               rtol=3e-3, atol=3e-3)


def test_interpret_mode_only_on_cpu(monkeypatch):
    assert ops._interpret() is True                # tests run on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()
