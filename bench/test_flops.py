"""``flops.py`` against a hand count, against the FLOPs XLA counts in the
program's own forward and backward, and the codec's byte counts."""
import json
import pathlib

import pytest

import flops

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 4, "d_ff": 16, "vocab": 32,
        "lora": {"rank": 2, "targets": ["wq", "wk", "wv", "wo"]}}


def test_forward_by_hand():
    s = flops.shapes_of(TINY)
    # per layer: q 8x8 + k 8x4 + v 8x4 + o 8x8 = 192, MLP 3*8*16 = 384
    assert flops.layer_matmul_params(s) == 576
    # LoRA r=2: q 2*(8+8) + k 2*(8+4) + v 2*(8+4) + o 2*(8+8) = 112
    assert flops.lora_params(s) == 112
    per_token = 2 * 2 * (576 + 112)            # two layers
    head = 2 * 8 * 32                          # per position
    attn = 4 * 2 * 4 * (1 + 2 + 3) * 2        # 3 causal positions, 2 layers
    assert flops.forward_flops(s, 1, 3) == 3 * per_token + 3 * head + attn
    assert flops.forward_flops(s, 1, 3, head_positions=1) == (
        3 * per_token + head + attn)


def test_decode_by_hand():
    s = flops.shapes_of(TINY)
    per_token = 2 * 2 * (576 + 112) + 2 * 8 * 32
    # 2 steps after a 3-token prefix attend to 4 and 5 positions
    attn = 4 * 2 * 4 * (4 + 5) * 2
    assert flops.decode_flops(s, 1, 3, 2) == 2 * per_token + attn


def test_round_is_clients_times_steps():
    wl = {"batch_size": 2, "prompt_len": 3, "max_new": 2,
          "n_objectives": 2, "n_clients": 3, "local_steps": 1}
    s = flops.shapes_of(TINY)
    one = sum(flops.local_step_flops(s, 2, 3, 2, 2).values())
    assert flops.round_flops(TINY, wl) == 3 * one


def _xla_flops(fn, *args):
    """FLOPs of the compiled program by the repo's loop-aware HLO walker
    (XLA's own cost analysis counts a loop body once)."""
    import jax
    from repro.launch import hlo_cost
    text = jax.jit(fn).lower(*args).compile().as_text()
    return float(hlo_cost.analyze(text)["flops"])


@pytest.fixture(scope="module")
def program():
    import jax
    import jax.numpy as jnp
    import cell
    from repro.models import transformer
    from repro.models.common import merge_trainable, split_trainable
    model = dict(TINY, name="flops-tiny", family="dense", n_periods=2,
                 pattern=["attn"], rope_theta=10000.0, norm_eps=1e-5,
                 tie_embeddings=False,
                 lora={"rank": 2, "alpha": 32.0,
                       "targets": ["wq", "wk", "wv", "wo"]},
                 d_model=256, head_dim=64, n_heads=4, n_kv_heads=2,
                 d_ff=512, vocab=512)
    tokens = jnp.zeros((2, 64), jnp.int32)

    def costs(remat):
        import dataclasses
        cfg = dataclasses.replace(cell.model_config({"model": model}),
                                  remat=remat, attn_block=64)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        trainable, frozen = split_trainable(params)

        def fwd(tr):
            out = transformer.forward_seq(cfg, merge_trainable(tr, frozen),
                                          tokens)
            return out["logits"].astype(jnp.float32).mean()
        return (_xla_flops(fwd, trainable),
                _xla_flops(jax.grad(fwd), trainable))

    return model, costs(False), costs(True)


def test_forward_matches_xla(program):
    model, (fwd, _), _ = program
    ours = flops.forward_flops(flops.shapes_of(model), 2, 64)
    # XLA also counts elementwise work and the masked half of attention
    assert 0.8 * fwd <= ours <= fwd


def test_backward_counts_no_rematerialised_forward(program):
    model, (fwd, grad), (_, grad_remat) = program
    s = flops.shapes_of(model)
    ours = flops.forward_flops(s, 2, 64) + flops.backward_pull_flops(
        s, 2, 64)
    assert 0.75 * grad <= ours <= grad
    # with remat the program runs most of the forward again inside the
    # backward; the count leaves that out
    recompute = grad_remat - grad
    assert recompute >= 0.5 * flops.forward_flops(s, 2, 64)
    assert ours <= grad_remat - recompute


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("d", [1, 1024, 3000, 5242880])
def test_codec_wire_bytes_match_the_codec(bits, d):
    from repro.comms import make_codec
    assert flops.codec_wire_bytes(d, bits) == make_codec(
        f"int{bits}").nbytes_static(d)


def test_trainable_size_matches_the_configurations():
    here = pathlib.Path(__file__).resolve().parent / "configs"
    for path in here.glob("*.json"):
        model = json.loads(path.read_text())["model"]
        import cell
        from repro.fed.api import trainable_size
        assert flops.trainable_size(flops.shapes_of(model)) == \
            trainable_size(cell.model_config({"model": model}))
