"""The bytes one decode step of a federated round must read from HBM.

One step of the round's decode scan advances every client's B rows by
one token.  The clients share the frozen base, so the step reads:

* the frozen weights once: every layer's attention projections
  (q, k, v, o), its SwiGLU matrices (gate, up, down) and its two norms,
  the final norm and the output head, in bfloat16;
* the embedding rows of the C x B tokens it feeds in (a gather, not the
  table);
* every client's LoRA adapters (A and B factors on each target
  projection of every layer), in float32, as the program holds them
  (``common.init_linear``: the configurations' ``assumed`` say so too);
* every client's KV cache: K and V of every layer over the whole cache
  of P + max_new positions, in bfloat16 (``transformer.init_cache``);
  the step attends over all of it, masked.

Nothing is counted twice and nothing beyond the need (logits, sampling
noise), so a share of the bandwidth built on it is a lower bound of the
traffic and cannot pass 100 % by counting too much.
"""
from __future__ import annotations

WEIGHT_BYTES = 2      # bfloat16 base weights
ADAPTER_BYTES = 4     # float32 LoRA factors
CACHE_BYTES = 2       # bfloat16 KV cache


def _proj_shapes(m: dict) -> dict:
    d, dq = m["d_model"], m["n_heads"] * m["head_dim"]
    dkv = m["n_kv_heads"] * m["head_dim"]
    return {"wq": (d, dq), "wk": (d, dkv), "wv": (d, dkv), "wo": (dq, d)}


def step_bytes(model: dict, wl: dict) -> dict:
    """{"weights", "embed_rows", "adapters", "cache"}: bytes per decode
    step of a dense attention stack (``pattern`` all "attn")."""
    if model.get("family") != "dense" or set(model["pattern"]) != {"attn"}:
        raise ValueError(f"{model['name']}: only dense attention stacks")
    d, layers = model["d_model"], model["n_layers"]
    proj = _proj_shapes(model)
    per_layer = (sum(a * b for a, b in proj.values())
                 + 3 * d * model["d_ff"] + 2 * d)
    weights = layers * per_layer + d + model["vocab"] * d
    rows = wl["n_clients"] * wl["batch_size"]
    lora = model["lora"]
    adapter = layers * sum(lora["rank"] * (proj[t][0] + proj[t][1])
                           for t in lora["targets"])
    cache_len = wl["prompt_len"] + wl["max_new"]
    cache = (rows * layers * 2 * cache_len * model["n_kv_heads"]
             * model["head_dim"])
    return {"weights": WEIGHT_BYTES * weights,
            "embed_rows": WEIGHT_BYTES * rows * d,
            "adapters": ADAPTER_BYTES * wl["n_clients"] * adapter,
            "cache": CACHE_BYTES * cache}


def steps_per_round(wl: dict) -> int:
    return wl["local_steps"] * wl["max_new"]
