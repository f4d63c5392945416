"""Partition-spec rules + input_specs shapes (no devices needed —
AbstractMesh carries only axis sizes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.configs.base import FIRMConfig
from repro.launch import sharding as sh
from repro.launch import specs as specs_lib

MESH = AbstractMesh((16, 16), ("data", "model"))


class _FakePath:
    def __init__(self, *names):
        self.names = names


def _path(*names):
    return tuple(type("K", (), {"key": n})() for n in names)


def _leaf(shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def test_embed_vocab_sharded():
    spec = sh.param_spec(_path("embed"), _leaf((128256, 8192)), MESH)
    assert spec == P("model", None)


def test_column_and_row_parallel():
    spec = sh.param_spec(_path("slots", "0", "attn", "wq", "w"),
                         _leaf((16, 4096, 4096)), MESH)
    assert spec == P(None, None, "model")
    spec = sh.param_spec(_path("slots", "0", "attn", "wo", "w"),
                         _leaf((16, 4096, 4096)), MESH)
    assert spec == P(None, "model", None)


def test_lora_replicated():
    spec = sh.param_spec(_path("slots", "0", "attn", "wq", "lora_A"),
                         _leaf((16, 4096, 16)), MESH)
    assert spec == P(None, None, None)


def test_expert_parallel_when_divisible():
    spec = sh.param_spec(_path("slots", "0", "moe", "experts", "w_gate"),
                         _leaf((48, 64, 2048, 1408)), MESH)
    assert spec == P(None, "model", None, None)
    # 8 experts don't divide 16 -> fall back to d_ff tensor parallel
    spec = sh.param_spec(_path("slots", "0", "moe", "experts", "w_gate"),
                         _leaf((32, 8, 4096, 14336)), MESH)
    assert spec == P(None, None, None, "model")
    spec = sh.param_spec(_path("slots", "0", "moe", "experts", "w_down"),
                         _leaf((32, 8, 14336, 4096)), MESH)
    assert spec == P(None, None, "model", None)


def test_divisibility_guard_replicates():
    # 24 heads * 128 = 3072 out dim divides 16; but 100 doesn't
    spec = sh.param_spec(_path("slots", "0", "attn", "wq", "w"),
                         _leaf((4, 512, 100)), MESH)
    assert spec == P(None, None, None)


def test_batch_spec_data_axes():
    assert sh.batch_spec((256, 4096), MESH) == P("data", None)
    assert sh.batch_spec((1, 4096), MESH) == P(None, None)
    multi = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert sh.batch_spec((64, 128), multi,
                         data_axes=("pod", "data")) == \
        P(("pod", "data"), None)


@pytest.mark.parametrize("arch", list(ASSIGNED_ARCHS))
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_input_specs_build_for_every_pair(arch, shape_name):
    """eval_shape-only construction of every (arch x shape) input pytree."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.subquadratic:
        pytest.skip("full-attention arch skips long_500k (DESIGN §4)")
    spec = specs_lib.input_specs(cfg, shape, FIRMConfig())
    leaves = jax.tree_util.tree_leaves(
        spec, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    assert leaves, (arch, shape_name)
    if spec["kind"] == "train":
        assert spec["batch"].tokens.shape[0] == shape.global_batch
    elif spec["kind"] == "decode":
        assert spec["token"].shape == (shape.global_batch, 1)
        # cache exists for every pattern slot
        assert len(spec["cache"]["slots"]) == len(cfg.pattern)


def test_cache_shardings_rules():
    cfg = get_config("mistral-large-123b")
    cache = specs_lib.cache_specs(cfg, INPUT_SHAPES["decode_32k"])
    shd = sh.cache_shardings(cfg, cache, MESH, batch=128)
    k_sh = shd["slots"]["0"]["k"]
    assert k_sh.spec == P(None, "data", "model", None, None)
    # B=1 long context -> seq sharded over both axes
    cfg2 = get_config("zamba2-1.2b")
    cache2 = specs_lib.cache_specs(cfg2, INPUT_SHAPES["long_500k"])
    shd2 = sh.cache_shardings(cfg2, cache2, MESH, batch=1)
    # find the shared-attn slot kv
    for i, kind in enumerate(cfg2.pattern):
        if kind == "shared_attn":
            assert shd2["slots"][str(i)]["k"].spec == \
                P(None, None, ("data", "model"), None, None)
            break


def test_param_shardings_cover_full_tree():
    cfg = get_config("mixtral-8x7b").reduced()
    params = specs_lib.param_specs(cfg)
    shd = sh.param_shardings(params, MESH)
    n1 = len(jax.tree_util.tree_leaves(params))
    n2 = len(jax.tree_util.tree_leaves(shd))   # NamedSharding is a leaf
    assert n1 == n2
