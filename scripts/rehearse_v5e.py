"""Compile the chip smoke run's programs for a described TPU v5e chip.

  JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/rehearse_v5e.py \
      [--clients 2 --batch 4 --prompt-len 32 --max-new 32]

Nothing runs and no parameter is allocated: every argument is a shape
(``jax.eval_shape``) placed on one chip of a described ``v5e:2x2``
topology, and the TPU compiler, installed here, compiles for it.  It
compiles the four codec kernels at the adapter size, the per-round local
phase (``_jit_vec_round``) and a two-round fused chunk
(``_jit_fused_rounds``) of unreduced llama-3.2-1b with the ``int8+ef``
uplink, and prints each program's compile time and
``memory_analysis()``.  A compile that passes is not a chip run: it
proves the chip's compiler accepts the programs and that they fit in
one chip's HBM, nothing about results or times.
"""
import argparse
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.comms import make_codec  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import FIRMConfig  # noqa: E402
from repro.core import fedavg  # noqa: E402
from repro.fed import engine  # noqa: E402
from repro.fed.algorithms import get_algorithm  # noqa: E402
from repro.fed.api import EngineConfig  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels import quantize as q  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.common import split_trainable, tree_size  # noqa: E402
from repro.rlhf import local as local_lib  # noqa: E402

GB = 1e9


def _report(name, lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    kernel = "tpu_custom_call" in compiled.as_text()
    print(f"{name}: compiled in {dt:.1f} s (rehearsal host, not a chip "
          f"time); args {ma.argument_size_in_bytes / GB:.2f} GB, "
          f"temps {ma.temp_size_in_bytes / GB:.2f} GB, "
          f"outputs {ma.output_size_in_bytes / GB:.2f} GB, "
          f"aliased {ma.alias_size_in_bytes / GB:.2f} GB, "
          f"pallas kernel in HLO: {kernel}", flush=True)
    return compiled


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--arch", default="llama-3.2-1b")
    args = ap.parse_args()

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    # default_backend() is the CPU here; compile the native kernels
    ops._interpret = lambda: False

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    cfg = get_config(args.arch)
    fc = FIRMConfig(n_objectives=2, n_clients=args.clients,
                    local_steps=args.local_steps, batch_size=args.batch)
    ec = EngineConfig(algorithm="firm", prompt_len=args.prompt_len,
                      max_new=args.max_new, uplink_codec="int8+ef",
                      fused_rounds=2)
    alg = get_algorithm(ec.algorithm)
    cfc = alg.resolve_config(fc)
    c = fc.n_clients

    params = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0)))
    trainable, frozen = split_trainable(params)
    d = tree_size(trainable)
    rows = -(-d // q.BLOCK)
    print(f"{cfg.name}: {tree_size(params) / 1e9:.3f} B params, "
          f"d_trainable={d} ({rows} rows of {q.BLOCK}); C={c} "
          f"K={fc.local_steps} B={fc.batch_size} "
          f"P={ec.prompt_len} new={ec.max_new}", flush=True)

    for n in (rows, c * rows):
        f32 = jax.ShapeDtypeStruct((n, q.BLOCK), jnp.float32, sharding=one)
        u32 = jax.ShapeDtypeStruct((n, q.BLOCK), jnp.uint32, sharding=one)
        i8 = jax.ShapeDtypeStruct((n, q.BLOCK), jnp.int8, sharding=one)
        sc = jax.ShapeDtypeStruct((n, 1), jnp.float32, sharding=one)
        t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
        _report(f"quantize[{n}]", q.quantize.lower(f32, u32))
        _report(f"dequantize[{n}]", q.dequantize.lower(i8, sc))
        _report(f"abs_threshold_count[{n}]",
                q.abs_threshold_count.lower(f32, t))
        _report(f"abs_threshold_mask[{n}]",
                q.abs_threshold_mask.lower(f32, t))

    state = jax.eval_shape(lambda tr: fedavg.stack_trees(
        [local_lib.init_client_state(tr, fc.n_objectives, cfg.d_model,
                                     fc.kl_coef_init)] * c), trainable)
    seeds = jax.ShapeDtypeStruct((c,), jnp.int32)
    counts = jax.ShapeDtypeStruct((c,), jnp.int32)
    # topic mixtures: one (C, T) row per client, as make_client_datasets
    from repro.data.partition import make_client_datasets
    ds = make_client_datasets(c, cfg.vocab, ec.prompt_len, seed=0)
    probs = jnp.stack([x.topic_probs for x in ds])
    bands = jax.ShapeDtypeStruct((c, 2), jnp.int32)
    gen_keys = jax.ShapeDtypeStruct((fc.local_steps, c, 2), jnp.uint32)
    extra = alg.traced_extra(cfc, ec)
    length_tol = max(4, ec.max_new // 2)

    vec = engine._jit_vec_round(cfg, cfc, alg.kernel, ec.prompt_len,
                                ec.max_new, length_tol, False)
    _report("vec_round", vec._wrapped_jit.lower(
        *on_chip((state, frozen, params, seeds, counts, probs, bands,
                  bands, gen_keys)), None, on_chip(extra)))

    ul = make_codec(ec.uplink_codec)
    dl = make_codec(ec.downlink_codec)
    leaves, treedef = jax.tree_util.tree_flatten(trainable)
    spec = engine.codec_lib.TreeSpec(treedef, tuple(x.shape for x in leaves),
                                     tuple(x.dtype for x in leaves))
    carry = engine.FusedCarry(
        states=state,
        ul_state=jax.eval_shape(lambda: ul.init_states_traced(d, [None] * c)),
        dl_state=jax.eval_shape(lambda: dl.init_state_traced(d, None)),
        counts=counts, rng=jax.ShapeDtypeStruct((2,), jnp.uint32))
    fused = engine._jit_fused_rounds(cfg, cfc, alg.kernel, ec.prompt_len,
                                     ec.max_new, length_tol, False,
                                     ec.uplink_codec, ec.downlink_codec,
                                     spec, c, c)
    _report("fused_rounds[2]", fused._wrapped_jit.lower(
        *on_chip((carry, trainable,
                  jax.ShapeDtypeStruct((2,), jnp.int32),
                  jax.ShapeDtypeStruct((2,), jnp.uint32), frozen, params,
                  seeds, probs, bands, bands)), None, on_chip(extra)))


if __name__ == "__main__":
    main()
