"""The uplink codec kernels, and the expert layer's grouped matmuls,
compile for a described TPU v5e chip.

Interpret mode on CPU cannot see what the chip's compiler refuses (the
quantize kernel once cast uint32 to f32, which Mosaic rejects).  These
tests compile each kernel natively for one chip of a described
``v5e:2x2`` topology at the llama-3.2-1b adapter size (3328 rows of
1024), at the two-client stacked size ``chip_smoke.py`` encodes, and at
a small row count, and assert the kernel is in the compiled HLO; the
expert layer at Mixtral-8x7B's widths in its grouped layout, whose
kernels the layer map must find under ``moe/experts``.  Nothing runs.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import quantize as q

ADAPTER_ROWS = 3328          # llama-3.2-1b LoRA delta: 3,407,872 / 1024
ROWS = [3, ADAPTER_ROWS, 2 * ADAPTER_ROWS]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without the chip; keep the cache off here."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(kernel, rows, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    x = s((rows, q.BLOCK), jnp.float32)
    return {
        "quantize": (x, s((rows, q.BLOCK), jnp.uint32)),
        "dequantize": (s((rows, q.BLOCK), jnp.int8),
                       s((rows, 1), jnp.float32)),
        "abs_threshold_count": (x, s((), jnp.float32)),
        "abs_threshold_mask": (x, s((), jnp.float32)),
    }[kernel]


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("kernel", ["quantize", "dequantize",
                                    "abs_threshold_count",
                                    "abs_threshold_mask"])
def test_codec_kernel_compiles_for_v5e(kernel, rows, one_chip,
                                       no_persistent_cache):
    fn = getattr(q, kernel)
    compiled = fn.lower(*_args(kernel, rows, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tokens", [512, 1024])
def test_grouped_expert_layer_compiles_for_v5e_and_maps_to_its_layer(
        tokens, one_chip, no_persistent_cache, monkeypatch):
    """One Mixtral-8x7B expert layer (8 experts of 4096 x 14336, top-2)
    over a reference-forward-sized pass of 8 clients in the grouped
    layout: its three grouped matmuls are Mosaic kernels, which the
    layer map gives layer ``moe/experts`` and the enclosing pass as
    phase, and the layout's gather sits under ``moe/route``."""
    import dataclasses
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import moe
    from repro.obs import jitwatch
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("mixtral-8x7b"), n_layers=1,
                              n_periods=1)
    assert moe.grouped(tokens * cfg.moe.top_k, cfg.moe.n_experts)

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)
    p = shaped(jax.eval_shape(
        lambda: moe.init_moe(jax.random.PRNGKey(0), cfg)))
    x = shaped(jax.ShapeDtypeStruct((8, tokens // 8, cfg.d_model),
                                    jnp.bfloat16))

    def layer(p, x):
        with jax.named_scope("ref_forward"):
            return jax.vmap(lambda xi: moe.moe_ffn(p, cfg, xi[None])[0])(x)
    text = jax.jit(layer).lower(p, x).compile().as_text()
    kernels = {line.split("=")[0].strip().lstrip("%")
               for line in text.splitlines()
               if "tpu_custom_call" in line and " = " in line}
    assert len(kernels) == 3
    _, ops_ = jitwatch.hlo_ops(text)
    where = {name: (lay, phase) for name, _, lay, phase in ops_}
    assert {where[k] for k in kernels} == {("moe/experts", "ref_forward")}
    assert ("moe/route", "ref_forward") in set(where.values())
