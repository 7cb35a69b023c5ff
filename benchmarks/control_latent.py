"""``benchmarks/control.py`` for a model with latent attention: the control is
the engine with its latent rows kept ONE PRECISION DOWN, int8 with one scale a
row (the step that would tempt a later PR: half the bytes of the walk that is
most of a decode step), under a file that leaves the cache in the
activations' type.

The engine refuses a quantized latent pool by name (``engine/paged.py``), so
no configuration builds one.  The control is made here and only here: for its
side the two functions through which every row reaches the pool
(``paged._write_pool_pages``, the prefill's, and ``paged._write_pool_rows``,
the decode step's) are wrapped so that a latent row is rounded to its own
int8 grid (127 steps up to its largest element) before it is stored, in the
pool's type.  The pool then holds, value for value, what an int8 pool with
one scale a row would hand the walk, the engine's own programs read it, and
nothing else differs.

Everything else is ``control.py``'s: the same check against the reference of
the file AS IT STANDS, the same summary and the same exit code (0 only where
the check passed the sound engine and refused the control on every seed);
kept as ``chiprun_out/control_latent_<cell>.json``.  The seed's weights are
made once and both sides built over them, as ``control_window.py`` does.

    chiprun -- python benchmarks/control_latent.py \
        --workload kanana2-d12.deepdump-reason --seeds 11 12
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def on_int8_grid(rows):
    """Rows [..., width] rounded to their own int8 grid, in their type."""
    import jax.numpy as jnp

    f = rows.astype(jnp.float32)
    top = jnp.max(jnp.abs(f), axis=-1, keepdims=True)
    step = jnp.where(top > 0, top / 127.0, 1.0)
    return (jnp.clip(jnp.round(f / step), -127, 127) * step).astype(
        rows.dtype)


@contextlib.contextmanager
def int8_rows():
    """While open, every latent row an engine's programs TRACE a write of
    is rounded to its int8 grid first (a program traced inside keeps the
    rounding for its life)."""
    from k8s_llm_rca_tpu.engine import paged

    pages, rows = paged._write_pool_pages, paged._write_pool_rows

    def write_pages(cfg, pool, new_k, new_v, *rest):
        if pool.v is None:
            new_k = on_int8_grid(new_k)
        return pages(cfg, pool, new_k, new_v, *rest)

    def write_rows(cfg, pool, li, page_ids, offsets, k_rows, v_rows):
        if pool.v is None:
            k_rows = on_int8_grid(k_rows)
        return rows(cfg, pool, li, page_ids, offsets, k_rows, v_rows)

    paged._write_pool_pages, paged._write_pool_rows = write_pages, write_rows
    try:
        yield
    finally:
        paged._write_pool_pages, paged._write_pool_rows = pages, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--prompt-tokens", type=int, nargs="+")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import logging

    import jax

    from benchmarks import control
    from benchmarks import run as harness
    from benchmarks.lib import build, correct
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.utils import get_tokenizer

    logging.disable(logging.INFO)
    _, cell, conf, traffic = harness.load_cell(args.benchmark, args.workload)
    device = build.describe_device(cell["chips"], args.allow_cpu)
    build.enable_compile_cache()
    mcfg = build.model_config(conf, cell["config"])
    if not mcfg.latent_row:
        raise SystemExit("control: the cell's model has no latent attention")
    group = ({"prompt_tokens": args.prompt_tokens} if args.prompt_tokens
             else traffic["check"])
    rows = []
    for seed in args.seeds:
        params = build.init_params_fn(conf)(mcfg, jax.random.PRNGKey(seed))
        for side, rounding in (("sound", contextlib.nullcontext),
                               ("control", int8_rows)):
            with rounding():
                engine = make_engine(
                    mcfg, build.engine_config(conf), params,
                    get_tokenizer(vocab_size=mcfg.vocab_size))
                check = correct.check(engine, conf, seed=seed, **group)
            rows.append({"cell": cell["name"], "seed": seed, "side": side,
                         "check": check})
            print(json.dumps(rows[-1]), flush=True)
            del engine
            gc.collect()
        del params
    out = {"cell": cell["name"], "device": device, "rows": rows,
           "summary": control.summary(rows)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_latent_{cell['name']}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["summary"]), flush=True)
    s = out["summary"]
    return 0 if all(s["sound_ok"]) and not any(s["control_ok"]) else 1


if __name__ == "__main__":
    sys.exit(main())
