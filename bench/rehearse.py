"""Compile one cell's round program for a described TPU v5e chip.

  JAX_PLATFORMS=cpu python bench/rehearse.py --workload <name>

Nothing runs and no parameter is allocated: every argument is a shape
(``jax.eval_shape``) placed on one chip of a described ``v5e:2x2``
topology, and the TPU compiler compiles for it.  It compiles the cell's
per-round local phase (the program the window dispatches once a round)
and the codec's quantize kernel at the stacked uplink's size, and prints
each compile time and ``memory_analysis()``, with the bf16 base counted
once (the base and the frozen reference are one set of buffers on the
device, passed as two arguments).  A compile that passes says the chip's
compiler accepts the program and that it fits; nothing about times.
"""
from __future__ import annotations

import argparse
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import cell  # noqa: E402  (puts the program on sys.path)

GB = 1e9


def _compile(name, lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    print(f"{name}: compiled in {dt:.1f} s on the rehearsal host (not a "
          f"chip time); args {ma.argument_size_in_bytes / GB:.2f} GB, "
          f"temps {ma.temp_size_in_bytes / GB:.2f} GB, outputs "
          f"{ma.output_size_in_bytes / GB:.2f} GB, aliased "
          f"{ma.alias_size_in_bytes / GB:.2f} GB", flush=True)
    return ma, dt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=INT", help="try the cell at another size, "
                    "e.g. --set max_new=64")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.core import fedavg
    from repro.data.partition import make_client_datasets
    from repro.fed import engine
    from repro.fed.algorithms import get_algorithm
    from repro.kernels import ops
    from repro.kernels import quantize as q
    from repro.models import transformer
    from repro.models.common import split_trainable, tree_size
    from repro.rlhf import local as local_lib

    wl, _, cfg = cell.load(args.workload)
    for kv in args.set:
        key, value = kv.split("=")
        wl[key] = int(value)
    spec = cell.run_spec(wl, cfg, seed=0)
    fc, ec = spec.firm, spec.engine
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    ops._interpret = lambda: False      # the backend here is the CPU

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    alg = get_algorithm(ec.algorithm)
    cfc = alg.resolve_config(fc)
    c = fc.n_clients
    params = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0)))
    trainable, frozen = split_trainable(params)
    d = tree_size(trainable)
    rows = -(-d // q.BLOCK)
    base_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(params))
    print(f"{args.workload}: {cfg.name} {tree_size(params) / 1e9:.3f} B "
          f"params ({base_bytes / GB:.2f} GB), d_trainable={d}; C={c} "
          f"K={fc.local_steps} B={fc.batch_size} P={ec.prompt_len} "
          f"new={ec.max_new}", flush=True)

    f32 = jax.ShapeDtypeStruct((c * rows, q.BLOCK), jnp.float32,
                               sharding=one)
    u32 = jax.ShapeDtypeStruct((c * rows, q.BLOCK), jnp.uint32,
                               sharding=one)
    _compile(f"quantize[{c * rows}]", q.quantize.lower(f32, u32))

    state = jax.eval_shape(lambda tr: fedavg.stack_trees(
        [local_lib.init_client_state(tr, fc.n_objectives, cfg.d_model,
                                     fc.kl_coef_init)] * c), trainable)
    ds = make_client_datasets(c, cfg.vocab, ec.prompt_len, seed=0)
    probs = jnp.stack([x.topic_probs for x in ds])
    i32 = jax.ShapeDtypeStruct((c,), jnp.int32)
    bands = jax.ShapeDtypeStruct((c, 2), jnp.int32)
    gen_keys = jax.ShapeDtypeStruct((fc.local_steps, c, 2), jnp.uint32)
    extra = alg.traced_extra(cfc, ec)
    vec = engine._jit_vec_round(cfg, cfc, alg.kernel, ec.prompt_len,
                                ec.max_new, max(4, ec.max_new // 2), False)
    ma, _ = _compile("vec_round", vec._wrapped_jit.lower(
        *on_chip((state, frozen, params, i32, i32, probs, bands, bands,
                  gen_keys)), None, on_chip(extra)))
    frozen_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(frozen))
    once = (ma.argument_size_in_bytes - frozen_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    print(f"vec_round with the base counted once: "
          f"{once / GB:.2f} GB of 16", flush=True)


if __name__ == "__main__":
    main()
