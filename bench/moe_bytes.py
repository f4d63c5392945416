"""The bytes one decode step of a sparse-expert round must read from HBM.

As ``decode_bytes.py`` counts a dense step (the frozen weights once for
all clients, the embedding rows of the step's tokens, every client's
float32 adapters, every client's whole bfloat16 KV cache), with each
layer's SwiGLU replaced by its expert layer: all E experts' three
d x d_ff matrices in bfloat16, and the router's d x E matrix in float32
(the program holds it so).  Every held expert is counted because the
program's dropless einsum layer reads all of them each step, whichever
experts the step's C x B tokens are routed to; that is also the most a
step can need, since 8 rows route 16 pairs over 8 experts.  A change
that skips the experts no token is routed to must bring its own count
of the bytes it reads, or the share built on this one overstates it.
"""
from __future__ import annotations

import decode_bytes

ROUTER_BYTES = 4      # float32 router


def step_bytes(model: dict, wl: dict) -> dict:
    """{"weights", "router", "embed_rows", "adapters", "cache"}: bytes
    per decode step of a stack of expert blocks (``pattern ["moe"]``)."""
    if set(model["pattern"]) != {"moe"} or "moe" not in model:
        raise ValueError(f"{model['name']}: only expert-block stacks")
    d, layers = model["d_model"], model["n_layers"]
    experts = model["moe"]["n_experts"]
    proj = decode_bytes._proj_shapes(model)
    per_layer = (sum(a * b for a, b in proj.values())
                 + experts * 3 * d * model["d_ff"] + 2 * d)
    weights = layers * per_layer + d + model["vocab"] * d
    rows = wl["n_clients"] * wl["batch_size"]
    lora = model["lora"]
    adapter = layers * sum(lora["rank"] * (proj[t][0] + proj[t][1])
                           for t in lora["targets"])
    cache_len = wl["prompt_len"] + wl["max_new"]
    cache = (rows * layers * 2 * cache_len * model["n_kv_heads"]
             * model["head_dim"])
    return {"weights": decode_bytes.WEIGHT_BYTES * weights,
            "router": ROUTER_BYTES * layers * d * experts,
            "embed_rows": decode_bytes.WEIGHT_BYTES * rows * d,
            "adapters": decode_bytes.ADAPTER_BYTES * wl["n_clients"]
            * adapter,
            "cache": decode_bytes.CACHE_BYTES * cache}
