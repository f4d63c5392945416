"""The loop-aware HLO cost walker: scan scaling, dot flops, collectives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_matches_unrolled():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)

    def scanned(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    def unrolled(w, x):
        for _ in range(10):
            x = jnp.tanh(x @ w)
        return x

    t1 = hlo_cost.analyze(_compile(scanned, w, x).as_text())
    t2 = hlo_cost.analyze(_compile(unrolled, w, x).as_text())
    assert t1["flops"] == pytest.approx(t2["flops"], rel=0.1)
    # XLA's own counter misses the 10x
    xla = _compile(scanned, w, x).cost_analysis()["flops"]
    assert t1["flops"] > 5 * xla


def test_dot_flops_exact():
    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    t = hlo_cost.analyze(_compile(lambda a, b: a @ b, a, b).as_text())
    want = 2 * 64 * 256 * 32
    assert t["flops"] == pytest.approx(want, rel=0.05)


def test_unrolled_bytes_match_xla():
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)

    def f(a):
        return jnp.tanh(a @ a) @ a

    c = _compile(f, a)
    t = hlo_cost.analyze(c.as_text())
    xla = c.cost_analysis()["bytes accessed"]
    assert t["bytes"] == pytest.approx(xla, rel=0.5)


def test_nested_scan_multiplies():
    x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def nested(w, x):
        def outer(c, _):
            def inner(ci, _):
                return jnp.tanh(ci @ w), None
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=6)
        return out

    t = hlo_cost.analyze(_compile(nested, w, x).as_text())
    want = 30 * 2 * 16 * 64 * 64     # 6*5 matmuls
    assert t["flops"] == pytest.approx(want, rel=0.3)


def test_dus_counted_in_place():
    buf = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MB
    upd = jax.ShapeDtypeStruct((1, 1024), jnp.float32)     # 4 KB

    def f(buf, upd):
        def body(b, i):
            return jax.lax.dynamic_update_slice(b, upd, (i, 0)), None
        out, _ = jax.lax.scan(body, buf, jnp.arange(100))
        return out

    t = hlo_cost.analyze(_compile(f, buf, upd).as_text())
    # in-place: ~100 * 2 * 4KB, NOT 100 * 8MB
    assert t["bytes"] < 100e6


def test_collective_parse():
    import os
    # (mesh-based collectives need >1 device; parse a synthetic module)
    hlo = """
HloModule m

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(%p), replica_groups={}, to_apply=%add
}
"""
    t = hlo_cost.analyze(hlo)
    assert t["collectives"]["all-reduce"]["count"] == 1
    assert t["collectives"]["all-reduce"]["bytes"] == 4096
