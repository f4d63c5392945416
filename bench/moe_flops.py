"""Model FLOPs of a federated round of a sparse-expert model, from shapes.

As ``flops.py`` counts a dense round (its docstring gives the rules:
2mkn a matrix product, causal attention once, no rematerialised or
padded work, no elementwise work), with each layer's SwiGLU replaced by
what a token needs of an expert layer: its router (d x E) and the
``top_k`` experts it is routed to, three d x d_ff matrices each.  The
experts a token is not routed to are not counted, nor any capacity
padding: the program's dropless einsum layer runs every expert on every
position of a row (E / k times the routed work), and a share of the peak
built on this count shows that work as lost.  Attention and LoRA are
counted as ``flops.py`` counts them.
"""
from __future__ import annotations

import flops


def layer_matmul_params(s: flops.Shapes, experts: int, top_k: int) -> int:
    """Weights a token is multiplied by in one layer: attention, the
    router and its ``top_k`` experts."""
    attn = sum(a * b for a, b in flops._proj_dims(s).values())
    return attn + s.d * experts + top_k * 3 * s.d * s.d_ff


def _per_token(s, experts, top_k):
    return 2 * s.layers * (layer_matmul_params(s, experts, top_k)
                           + flops.lora_params(s))


def forward_flops(s, experts, top_k, rows, seq, head_positions=None):
    hp = seq if head_positions is None else head_positions
    return (rows * seq * _per_token(s, experts, top_k)
            + 2 * rows * hp * s.d * s.vocab
            + rows * flops._attn(s, flops._causal_pairs(seq)))


def decode_flops(s, experts, top_k, rows, prompt, new):
    per_tok = _per_token(s, experts, top_k) + 2 * s.d * s.vocab
    pairs = sum(prompt + j + 1 for j in range(new))
    return rows * (new * per_tok + flops._attn(s, pairs))


def backward_pull_flops(s, experts, top_k, rows, seq):
    """``flops.backward_pull_flops`` with the expert layer's matrices."""
    dense = flops.backward_pull_flops(s, rows, seq)
    swap = (layer_matmul_params(s, experts, top_k)
            - flops.layer_matmul_params(s))
    return dense + 2 * rows * seq * s.layers * swap


def local_step_flops(s, experts, top_k, batch, prompt, new, objectives):
    seq = prompt + new
    return {
        "prefill": forward_flops(s, experts, top_k, batch, prompt,
                                 head_positions=1),
        "decode": decode_flops(s, experts, top_k, batch, prompt, new),
        "ref_forward": forward_flops(s, experts, top_k, batch, seq),
        "loss_forward": forward_flops(s, experts, top_k, batch, seq),
        "backward": objectives * backward_pull_flops(s, experts, top_k,
                                                     batch, seq),
    }


def round_flops(model: dict, wl: dict) -> int:
    """Model FLOPs of one federated round of a cell whose ``model`` has a
    ``moe`` block (``n_experts``, ``top_k``)."""
    moe = model["moe"]
    parts = local_step_flops(flops.shapes_of(model), moe["n_experts"],
                             moe["top_k"], wl["batch_size"],
                             wl["prompt_len"], wl["max_new"],
                             wl["n_objectives"])
    return wl["n_clients"] * wl["local_steps"] * sum(parts.values())
