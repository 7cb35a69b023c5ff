"""Compile a configuration's decode and prefill programs for a DESCRIBED
TPU v5e at the real shapes, without a chip, and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python benchmarks/aot_check.py --config mixtral-8x7b-d8 \
        --prefill 1x4096 2x4096 --hit 8x2048x128 --decode 16

What the chip's compiler refuses here (a program that does not fit, a kernel
it cannot lower) costs no chip time.  A compile that passes is not a chip run
and gives no time.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--prefill", nargs="*", default=[],
                    help="ROWSxBUCKET prefix-miss prefill programs")
    ap.add_argument("--hit", nargs="*", default=[],
                    help="ROWSxBUCKETxTABLE prefix-hit (chunk) prefill "
                         "programs: suffix bucket, prefix-table pages")
    ap.add_argument("--decode", nargs="*", type=int, default=[],
                    help="decode programs by steps (1 = stepwise)")
    args = ap.parse_args(argv)

    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import build
    from k8s_llm_rca_tpu.engine import paged
    from k8s_llm_rca_tpu.engine.sampling import SamplingParams
    from k8s_llm_rca_tpu.models.quant import quantize_params

    conf = build.load_json(os.path.join(build.BENCH_DIR, "configs",
                                        args.config + ".json"))
    cfg, ecfg = build.model_config(conf, args.config), \
        build.engine_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the kernels' interpret=None and the engine's use_kernel=None ask
    # jax.default_backend(); here it would answer "cpu"
    jax.default_backend = lambda: "tpu"

    def described(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    bits = conf.get("weight_quant_bits")
    init_params = build.init_params_fn(conf)
    params = described(jax.eval_shape(
        lambda: quantize_params(
            init_params(cfg, jax.random.PRNGKey(0)), bits=bits)
        if bits else init_params(cfg, jax.random.PRNGKey(0))))
    pool = described(jax.eval_shape(
        lambda: paged.init_paged_cache(cfg, ecfg.num_pages, ecfg.page_size,
                                       ecfg.kv_cache_dtype)))
    leaves = jax.tree.leaves(params) + jax.tree.leaves(pool)
    arg_gb = sum(x.size * x.dtype.itemsize for x in leaves) / 1e9
    print(f"{args.config}: weights + pool {arg_gb:.2f} GB", flush=True)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    def report(label, lowered):
        t0 = time.perf_counter()
        try:
            mem = lowered.compile().memory_analysis()
        except Exception as e:  # noqa: BLE001 — the refusal is the result
            print(f"{label}: REFUSED {type(e).__name__}: "
                  f"{str(e)[:400]}", flush=True)
            return
        print(f"{label}: compiled in {time.perf_counter() - t0:.0f} s; "
              f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"output {mem.output_size_in_bytes / 1e9:.2f} GB "
              f"(aliased {mem.alias_size_in_bytes / 1e9:.2f})", flush=True)

    page, b = ecfg.page_size, ecfg.max_batch
    pps = -(-ecfg.max_seq_len // page)
    for spec in args.prefill:
        n, s = (int(x) for x in spec.split("x"))
        fn = jax.jit(functools.partial(paged.paged_prefill_batch,
                                       use_flash=True),
                     static_argnums=0, donate_argnums=(2,))
        report(f"prefill {n}x{s}",
               fn.lower(cfg, params, pool, i32(n, s), i32(n),
                        i32(n, s // page)))
    for spec in args.hit:
        n, s, t = (int(x) for x in spec.split("x"))
        fn = jax.jit(paged.paged_prefill_chunk_batch, static_argnums=0,
                     donate_argnums=(2,))
        report(f"hit prefill {n}x{s}, table {t}",
               fn.lower(cfg, params, pool, i32(n, s), i32(n), i32(n),
                        i32(n, t), i32(n, s // page)))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    for steps in args.decode:
        if steps == 1:
            fn = jax.jit(paged.paged_decode_step, static_argnums=(0,),
                         donate_argnums=(2,), static_argnames=("use_kernel",))
            lowered = fn.lower(cfg, params, pool, i32(b), i32(b),
                               i32(b, pps), use_kernel=True)
        else:
            fn = jax.jit(paged.paged_decode_scan,
                         static_argnums=(0, 7, 8, 9), donate_argnums=(2,),
                         static_argnames=("use_kernel",))
            lowered = fn.lower(cfg, params, pool, i32(b), i32(b),
                               i32(b, pps), key, steps, SamplingParams(),
                               258, use_kernel=True)
        report(f"decode x{steps} ({b} slots)", lowered)
    return 0


if __name__ == "__main__":
    sys.exit(main())
