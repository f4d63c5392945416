"""Operations and bytes that a federated round needs, from shapes alone.

Model FLOPs count what the algorithm requires, once: a matrix product
(m, k) @ (k, n) is 2mkn, causal attention over S positions is
4 * heads * head_dim * S(S+1)/2 per row and layer (scores and values).
Work the program repeats to save memory (rematerialised forwards) or
does beyond the need (logits of every prompt position in prefill,
attention over the padded decode cache) is not counted, so a share of
the peak built on these counts cannot pass 100 % by counting too much.
Elementwise work (norms, softmax, Adam, MGDA on M x M Gram matrices) is
left out: it is a small share and not matrix work.

A client's local step is:

* prefill of the B x P prompt block (the head only at the last position);
* ``max_new`` decode steps of B rows, each attending to its prefix;
* the frozen reference's forward over the B x S rollouts, S = P + max_new;
* the policy's forward over the same rollouts for the PPO loss;
* M backward pulls: the input gradient through every frozen matrix and
  attention (dX only, no base weight gradient) plus the LoRA factors'
  weight gradients.

A round is C clients x K local steps of that.
"""
from __future__ import annotations

from typing import NamedTuple

BLOCK = 1024          # elements per quantization group of the int codecs


class Shapes(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    lora_rank: int
    lora_targets: tuple


def shapes_of(model: dict) -> Shapes:
    """From a configuration file's ``model`` block."""
    lora = model.get("lora") or {"rank": 0, "targets": []}
    return Shapes(model["n_layers"], model["d_model"], model["n_heads"],
                  model["n_kv_heads"], model["head_dim"], model["d_ff"],
                  model["vocab"], lora["rank"], tuple(lora["targets"]))


def _proj_dims(s: Shapes) -> dict:
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return {"wq": (s.d, q), "wk": (s.d, kv), "wv": (s.d, kv), "wo": (q, s.d)}


def layer_matmul_params(s: Shapes) -> int:
    """Weights multiplied per token in one layer (attention + SwiGLU)."""
    return sum(a * b for a, b in _proj_dims(s).values()) + 3 * s.d * s.d_ff


def lora_params(s: Shapes) -> int:
    """LoRA factor weights per layer (A: din x r, B: r x dout)."""
    dims = _proj_dims(s)
    return sum(s.lora_rank * (dims[t][0] + dims[t][1])
               for t in s.lora_targets)


def trainable_size(s: Shapes) -> int:
    return s.layers * lora_params(s)


def _attn(s: Shapes, pairs: int) -> int:
    """Forward attention FLOPs over ``pairs`` (query, key) pairs, all
    layers: QK^T and PV, each 2 * heads * head_dim per pair."""
    return 4 * s.heads * s.head_dim * pairs * s.layers


def _causal_pairs(n: int) -> int:
    return n * (n + 1) // 2


def forward_flops(s: Shapes, rows: int, seq: int, head_positions=None
                  ) -> int:
    """Forward over ``rows`` x ``seq`` tokens from position 0, the head at
    ``head_positions`` per row (default: every position)."""
    hp = seq if head_positions is None else head_positions
    per_tok = 2 * s.layers * (layer_matmul_params(s) + lora_params(s))
    return (rows * seq * per_tok + 2 * rows * hp * s.d * s.vocab
            + rows * _attn(s, _causal_pairs(seq)))


def decode_flops(s: Shapes, rows: int, prompt: int, new: int) -> int:
    """``new`` one-token steps after a ``prompt``-token prefix; step j
    attends to prompt + j + 1 positions."""
    per_tok = (2 * s.layers * (layer_matmul_params(s) + lora_params(s))
               + 2 * s.d * s.vocab)
    pairs = sum(prompt + j + 1 for j in range(new))
    return rows * (new * per_tok + _attn(s, pairs))


def backward_pull_flops(s: Shapes, rows: int, seq: int) -> int:
    """One cotangent pull to the LoRA factors: dX through every matrix
    (head, base and LoRA paths) except into the first layer's input,
    where nothing upstream trains, twice the forward attention (dQ, dK,
    dV), and the LoRA factors' weight gradients (2 products per
    factor)."""
    per_tok = 2 * s.layers * (layer_matmul_params(s) + lora_params(s))
    head = 2 * s.d * s.vocab
    lora_dw = 2 * s.layers * lora_params(s)
    dims = _proj_dims(s)
    first_in = 2 * sum(dims[t][0] * dims[t][1]
                       + (s.lora_rank * dims[t][0]
                          if t in s.lora_targets else 0)
                       for t in ("wq", "wk", "wv"))
    return (rows * seq * (per_tok + head + lora_dw - first_in)
            + 2 * rows * _attn(s, _causal_pairs(seq)))


def local_step_flops(s: Shapes, batch: int, prompt: int, new: int,
                     objectives: int) -> dict:
    seq = prompt + new
    return {
        "prefill": forward_flops(s, batch, prompt, head_positions=1),
        "decode": decode_flops(s, batch, prompt, new),
        "ref_forward": forward_flops(s, batch, seq),
        "loss_forward": forward_flops(s, batch, seq),
        "backward": objectives * backward_pull_flops(s, batch, seq),
    }


def round_flops(model: dict, wl: dict) -> int:
    """Model FLOPs of one federated round of a cell."""
    parts = local_step_flops(shapes_of(model), wl["batch_size"],
                             wl["prompt_len"], wl["max_new"],
                             wl["n_objectives"])
    return wl["n_clients"] * wl["local_steps"] * sum(parts.values())


# ------------------------------------------------------------- codec bytes
def quantized_rows(d: int) -> int:
    return -(-d // BLOCK)


def codec_wire_bytes(d: int, bits: int) -> int:
    """Bytes of one client's upload: codes of the padded (rows, BLOCK)
    groups (int4 packs two per byte) and one f32 scale per group."""
    rows = quantized_rows(d)
    return rows * BLOCK * bits // 8 + rows * 4
