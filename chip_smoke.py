#!/usr/bin/env python3
"""Does the RCA serving path still start on the chip?  One process, one run.

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # only the TP=4 path and its one-chip twin

With no arguments it drives the main path once at the published widths of
``llama3-8b`` (32 layers, hidden 4096, 32/8 heads, vocab 128256; seeded
random weights, int4 weights + int4 KV, paged engine, prefix cache on,
attention kernels on) through the entry points a user calls:
``sweeps.common.build_service`` -> ``EngineBackend`` -> ``AssistantService``
-> ``RCAPipeline`` under ``sweeps.run_file.main``.  It prints one JSON
object per phase (device, kernels, engine, rca) and exits non-zero as soon
as a phase fails.  The last line is the verdict the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything else it prints is an observation of a smoke run, not a benchmark
result.  On a machine where JAX finds no TPU it exits before any model
phase and prints no verdict.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import re
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
MODEL = "llama3-8b"
# the nearest set the sweep CLI offers to the flagship serving config: it has
# no page-size / pool-size / bucket flags, so those are EngineConfig's
# defaults (page 16, 1024 pages, buckets 64..1024 then the full 4096)
ENGINE_ARGV = ["--backend", "engine", "--model", MODEL, "--int4",
               "--kv-dtype", "int4", "--fresh-threads", "--concurrency", "2",
               "--max-seq-len", "4096", "--max-batch", "16"]
SEED = 0
# kernel against XLA reference, both on bf16 inputs with f32 accumulation:
# what is left is bf16 rounding of outputs and of the softmax weights
# (.claude/skills/verify/SKILL.md, precision note).  Relative to the largest
# reference magnitude (at least 1).
KERNEL_TOL = 2e-2
# first-step logits of the whole 32-layer stack, kernel against XLA decode
LOGITS_TOL = 5e-2


def emit(phase: str, fields: dict) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileMeter:
    """Seconds and count of backend compilations, and persistent-cache
    hits, from jax.monitoring: compile time is reported apart from run
    time."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.seconds, self.count, self.cache_hits)

    def since(self, mark) -> dict:
        return {"compile_s": round(self.seconds - mark[0], 2),
                "compiles": self.count - mark[1],
                "compile_cache_hits": self.cache_hits - mark[2]}


def hbm(device) -> dict:
    from k8s_llm_rca_tpu.runtime import profiling

    return profiling.device_memory_stats(device)


def rel_err(got, ref):
    """(max abs error, largest reference magnitude), both as floats."""
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    return (float(jnp.max(jnp.abs(got - ref))),
            float(jnp.max(jnp.abs(ref))))


# --------------------------------------------------------------- device


def phase_device(min_chips: int) -> dict:
    import jax

    from k8s_llm_rca_tpu.runtime import profiling

    devices = jax.devices()
    d0 = devices[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}
    if d0.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {devices}); this "
              f"script measures nothing on a {d0.platform}",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < min_chips:
        print(f"chip_smoke: --chips {min_chips} on a machine with "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    peaks = profiling.chip_peaks(d0)        # raises on an unknown TPU kind
    emit("device", {**info, "bytes_limit": hbm(d0).get("bytes_limit"),
                    "peaks": peaks._asdict(), "jax": jax.__version__})
    return info


# -------------------------------------------------------------- kernels


def phase_kernels(cfg, page_size: int, num_pages: int, max_seq_len: int,
                  interpret: bool = False):
    """Every main-path kernel, compiled (``interpret=False``: a refusal by
    the chip's compiler raises) at ``cfg``'s widths, against its XLA
    reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_rca_tpu.engine.paged import _gather_dequant_pages
    from k8s_llm_rca_tpu.models.llama import _quantize_kv
    from k8s_llm_rca_tpu.models.quant import dq, quantize
    from k8s_llm_rca_tpu.ops import attention
    from k8s_llm_rca_tpu.ops.flash_attention import flash_attention
    from k8s_llm_rca_tpu.ops.paged_attention import (
        paged_attention, paged_attention_quant, paged_attention_xla,
    )
    from k8s_llm_rca_tpu.ops.quant_matmul import (
        quant_matmul, quant_matmul_head,
    )

    dtype = jnp.dtype(cfg.dtype)
    n_heads, n_kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    rng = np.random.default_rng(SEED)
    rows = []

    def normal(shape, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    def check(name, got, ref):
        err, mag = rel_err(got, ref)
        tol = KERNEL_TOL * max(1.0, mag)
        ok = bool(np.isfinite(err)) and err <= tol
        rows.append({"kernel": name, "max_abs_err": err, "ref_max_abs": mag,
                     "tol": tol, "ok": ok})

    # inputs and references are built under jit: op by op, the float32
    # temporaries of a [vocab, hidden] table would fill the chip

    # decode attention over a paged pool: 4 sequences of very different
    # lengths, block tables over random pages
    b, pps = 4, max_seq_len // page_size
    q = normal((b, n_heads, d))
    kp = normal((num_pages, page_size, n_kv * d))
    vp = normal((num_pages, page_size, n_kv * d))
    lengths = jnp.asarray([max_seq_len, max_seq_len // 2 + 3, page_size + 1,
                           1], jnp.int32)
    tables = jnp.asarray(rng.integers(1, num_pages, (b, pps)), jnp.int32)
    check("paged_attention",
          paged_attention(q, kp, vp, lengths, tables, interpret=interpret),
          jax.jit(paged_attention_xla)(q, kp, vp, lengths, tables))

    @functools.partial(jax.jit, static_argnums=2)
    def quantized(kp, vp, packed):
        kq, ks = _quantize_kv(kp, packed)
        vq, vs = _quantize_kv(vp, packed)
        return kq, vq, ks.astype(jnp.float32), vs.astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=4)
    def xla_quant_attention(kq, vq, ks, vs, packed):
        k_all = _gather_dequant_pages(kq, ks, tables, n_kv, d, dtype, packed)
        v_all = _gather_dequant_pages(vq, vs, tables, n_kv, d, dtype, packed)
        return attention.decode_attention(q[:, None], k_all, v_all,
                                          lengths)[:, 0]

    for name, packed in (("paged_attention_quant_int8", False),
                         ("paged_attention_quant_int4", True)):
        kq, vq, ks, vs = quantized(kp, vp, packed)
        check(name,
              paged_attention_quant(q, kq, vq, ks, vs, lengths, tables,
                                    packed=packed, interpret=interpret),
              xla_quant_attention(kq, vq, ks, vs, packed))

    # prefill attention
    s = min(2048, max_seq_len)
    fq, fk, fv = (normal((1, s, heads, d)) for heads in (n_heads, n_kv, n_kv))
    seq_lens = jnp.asarray([s - 37], jnp.int32)
    check(f"flash_attention_s{s}",
          flash_attention(fq, fk, fv, seq_lens, interpret=interpret),
          jax.jit(attention.causal_attention)(fq, fk, fv, seq_lens))

    # fused weight-dequant matmuls (off by default, ROADMAP A5)
    h, inter = cfg.hidden_size, cfg.intermediate_size
    x = normal((144, h))
    make_weight = jax.jit(
        lambda key, shape, axis, bits: quantize(
            (jax.random.normal(key, shape) / np.sqrt(h)).astype(dtype),
            axis=axis, bits=bits),
        static_argnums=(1, 2, 3))
    w_key = next(keys)
    for bits in (8, 4):
        wq = make_weight(w_key, (h, inter), -1, bits)
        check(f"quant_matmul_int{bits}",
              quant_matmul(x, wq, interpret=interpret),
              jax.jit(lambda x, w: x @ dq(w))(x, wq))
    hq = make_weight(next(keys), (cfg.vocab_size, h), 0, 4)
    check("quant_matmul_head_int4",
          quant_matmul_head(x, hq, interpret=interpret),
          jax.jit(lambda x, w: jnp.einsum("mh,vh->mv", x, dq(w)))(x, hq))
    return ({"kernels": rows}, [r["kernel"] for r in rows if not r["ok"]],
            None)


# --------------------------------------------------------------- engine


def parse_engine_args(argv):
    from k8s_llm_rca_tpu.sweeps.common import add_common_args

    parser = argparse.ArgumentParser()
    add_common_args(parser)
    return parser.parse_known_args(argv)[0]


def random_prompts(vocab: int, lengths) -> list:
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [[int(t) for t in rng.integers(1, vocab - 1, n)] for n in lengths]


def lowered_has_kernel(jitted, *args, **kw) -> bool:
    """Whether the program a jit would compile for these shapes calls a
    Pallas TPU kernel (and not the XLA gather path)."""
    return "tpu_custom_call" in jitted.lower(*args, **kw).as_text()


def phase_engine(engine_argv, device, prompt_lens=(1900, 1200),
                 max_new: int = 8):
    """Build the engine through ``build_service``; a few ``generate``
    requests with the decode kernel on, and on an XLA twin over the same
    weights; first-step logits of one decode step, kernel against XLA."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.engine.paged import TRASH_PAGE, PagePool
    from k8s_llm_rca_tpu.models.quant import QuantTensor4
    from k8s_llm_rca_tpu.sweeps.common import build_service

    fails = []
    t0 = time.perf_counter()
    service = build_service(parse_engine_args(engine_argv))
    eng = service.backend.engine
    jax.block_until_ready(eng.params)
    build_s = time.perf_counter() - t0
    cfg, ecfg = eng.model_cfg, eng.engine_cfg
    out = {"model": cfg.name, "n_layers": cfg.n_layers,
           "hidden": cfg.hidden_size,
           "heads": [cfg.n_heads, cfg.n_kv_heads], "vocab": cfg.vocab_size,
           "weights": type(eng.params["layers"][0]["wq"]).__name__,
           "kv_cache": ecfg.kv_cache_dtype, "page_size": ecfg.page_size,
           "num_pages": ecfg.num_pages, "max_batch": ecfg.max_batch,
           "max_seq_len": ecfg.max_seq_len,
           "build_s": round(build_s, 2),
           "hbm_in_use_after_build": hbm(device).get("bytes_in_use")}
    if not isinstance(eng.params["layers"][0]["wq"], QuantTensor4):
        fails.append("weights are not int4")

    prompts = random_prompts(cfg.vocab_size, prompt_lens)
    pool_before = eng.pool.k
    t0 = time.perf_counter()
    got = eng.generate(prompts, max_new_tokens=max_new)
    out["generate_s"] = round(time.perf_counter() - t0, 2)
    out["pool_donated"] = pool_before.is_deleted()

    twin = make_engine(cfg, ecfg, eng.params, eng.tokenizer,
                       use_kernel=False)
    ref = twin.generate(prompts, max_new_tokens=max_new)
    del twin
    n_tok = sum(len(r.token_ids) for r in ref)
    out["generated_tokens"] = n_tok
    out["greedy_tokens_equal_to_xla_engine"] = sum(
        a == b for r, g in zip(ref, got)
        for a, b in zip(r.token_ids, g.token_ids))
    if any(len(g.token_ids) != max_new for g in got):
        fails.append("a generate request came back short")

    # one decode step over a random pool, the engine's own stepwise program
    # (what an interpreted-grammar tick runs), kernel against XLA
    b, pps = ecfg.max_batch, eng.pages_per_seq
    rng = np.random.default_rng(SEED)
    cur = jnp.asarray(rng.integers(1, cfg.vocab_size - 1, b), jnp.int32)
    lens = jnp.asarray(rng.integers(1, ecfg.max_seq_len - 1, b), jnp.int32)
    tables = jnp.asarray(rng.integers(TRASH_PAGE + 1, ecfg.num_pages,
                                      (b, pps)), jnp.int32)

    def random_pool():
        ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
        def int8(key, shape):
            return jax.lax.bitcast_convert_type(
                jax.random.bits(key, shape, jnp.uint8), jnp.int8)

        return PagePool(
            k=int8(ks[0], eng.pool.k.shape), v=int8(ks[1], eng.pool.v.shape),
            k_scale=jax.random.uniform(ks[2], eng.pool.k_scale.shape,
                                       jnp.float32, 0.01, 0.1),
            v_scale=jax.random.uniform(ks[3], eng.pool.v_scale.shape,
                                       jnp.float32, 0.01, 0.1))

    _, logits_k = eng._decode(cfg, eng.params, random_pool(), cur, lens,
                              tables, use_kernel=eng.use_kernel)
    _, logits_x = eng._decode(cfg, eng.params, random_pool(), cur, lens,
                              tables, use_kernel=False)
    err, mag = rel_err(logits_k, logits_x)
    tol = LOGITS_TOL * max(1.0, mag)
    out["decode_logits"] = {
        "max_abs_err": err, "ref_max_abs": mag, "tol": tol,
        "finite": bool(jnp.all(jnp.isfinite(logits_k))),
        "argmax_equal": int(jnp.sum(jnp.argmax(logits_k, -1)
                                    == jnp.argmax(logits_x, -1))),
        "rows": b}
    if not (out["decode_logits"]["finite"] and err <= tol):
        fails.append("decode logits: kernel and XLA disagree")

    # are the kernels in the programs the engine compiles?
    shape = jax.ShapeDtypeStruct
    n, s_pad = 2, eng._bucket(max(prompt_lens))
    out["kernel_in_decode_program"] = lowered_has_kernel(
        eng._decode, cfg, eng.params, eng.pool, cur, lens, tables,
        use_kernel=eng.use_kernel)
    out["kernel_in_prefill_program"] = lowered_has_kernel(
        eng._prefill_batch, cfg, eng.params, eng.pool,
        shape((n, s_pad), jnp.int32), shape((n,), jnp.int32),
        shape((n, s_pad // ecfg.page_size), jnp.int32))
    for key in ("pool_donated", "kernel_in_decode_program",
                "kernel_in_prefill_program"):
        if not out[key]:
            fails.append(f"{key} is false")
    gc.collect()
    return out, fails, service


# ------------------------------------------------------------------ rca


REFERENCE_KEYS = {"error_message", "locator_attempts", "analysis",
                  "time_cost", "token_usage"}
ANALYSIS_KEYS = {"extend_metapath", "cypher_query", "cypher_attempts",
                 "statepath"}
STATE_KEYS = {"report", "clue"}


def on_schema(record: dict) -> bool:
    """The reference's record schema (sweeps/run_file.py docstring)."""
    return (REFERENCE_KEYS <= set(record) and "error" not in record
            and all(ANALYSIS_KEYS <= set(a)
                    and all(STATE_KEYS <= set(s) for s in a["statepath"])
                    for a in record["analysis"]))


def pool_is_finite(eng) -> bool:
    """One more decode step whose 16 rows between them attend over EVERY
    page of the engine's live pool: a NaN or inf written anywhere during
    the sweep comes out in the logits.  The step's own KV write goes to the
    trash page."""
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_rca_tpu.engine.paged import TRASH_PAGE

    ecfg = eng.engine_cfg
    b, pps = ecfg.max_batch, eng.pages_per_seq
    pages = 1 + np.arange(b * pps) % (ecfg.num_pages - 1)
    tables = pages.reshape(b, pps).astype(np.int32)
    tables[:, -1] = TRASH_PAGE
    lens = np.full((b,), pps * ecfg.page_size - 1, np.int32)
    eng.pool, logits = eng._decode(
        eng.model_cfg, eng.params, eng.pool, jnp.ones((b,), jnp.int32),
        jnp.asarray(lens), jnp.asarray(tables), use_kernel=eng.use_kernel)
    return bool(jnp.all(jnp.isfinite(logits)))


def phase_rca(service, engine_argv, out_path: str):
    """The built-in incidents through ``sweeps.run_file.main`` on the
    engine the caller built."""
    from k8s_llm_rca_tpu import native
    from k8s_llm_rca_tpu.sweeps import run_file
    from k8s_llm_rca_tpu.utils.logging import METRICS

    fails = []
    eng = service.backend.engine
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    if os.path.exists(out_path):
        os.remove(out_path)                # main() appends
    before = METRICS.snapshot()
    summary = run_file.main(
        engine_argv + ["--input", os.path.join(REPO, "data",
                                               "incidents.csv"),
                       "--output", out_path], service=service)
    after = summary["metrics"]

    def delta(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    records = run_file.load_records(out_path)
    bad = [r.get("error_message", "?")[:60] for r in records
           if not on_schema(r)]
    grammar = {}
    for name in after:
        if name.startswith("serve.grammar."):
            _, _, mode, stage = name.split(".", 3)
            grammar.setdefault(stage, {})[mode] = int(delta(name))
    out = {"incidents": summary["incidents"],
           "failed_incidents": summary["failures"],
           "records": len(records),
           "records_off_schema": bad,
           "sweep_wall_s": round(summary["wall_s"], 2),
           "s_per_incident": round(
               summary["wall_s"] / max(1, summary["incidents"]), 2),
           "prefill_tokens": int(delta("engine.prefill_tokens")),
           "decode_tokens": int(delta("engine.decode_tokens")),
           "decode_dispatches": int(delta("engine.decode_step.count")),
           "prefix_hit_pages": int(delta("engine.prefix_hits_l0")),
           "prefix_hit_tokens": int(delta("engine.prefix_hit_tokens")),
           "grammar_runs_by_stage": grammar,
           "compiled_shapes": {
               name: getattr(eng, name)._cache_size()
               for name in ("_prefill", "_prefill_batch", "_prefill_chunk",
                            "_prefill_chunk_batch", "_decode",
                            "_decode_scan", "_decode_scan_dfa")},
           "host_components": {
               "allocator": type(eng.allocator).__name__,
               "native_library": (native.lib_path()
                                  if native.available() else None),
               "native_build_error": native.build_error()},
           "pool_finite": pool_is_finite(eng)}
    if out["incidents"] < 4:
        fails.append("fewer than 4 incidents")
    if (out["failed_incidents"] or out["records"] != out["incidents"]
            or bad):
        fails.append("failed or off-schema incidents")
    if not (out["prefill_tokens"] > 0 and out["decode_tokens"] > 0):
        fails.append("token counters did not move")
    if not out["pool_finite"]:
        fails.append("non-finite values in the KV pool")
    return out, fails, None


# ------------------------------------------------------- four chips: TP


def device_bytes(tree) -> dict:
    """Bytes of ``tree`` held by each device, from its shards."""
    import jax

    held = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = (held.get(shard.device.id, 0)
                                     + shard.data.nbytes)
    return held


def phase_tp(cfg, ecfg, n_chips: int):
    """A TP=``n_chips`` paged engine (int8 weights, int8 KV, per-head-shard
    kernels) answers the same requests as a one-chip engine on the same
    weights; greedy tokens compared.

    Seeded random weights give nearly flat logits, so in bf16 the rounding
    of each chip's partial sums before the all-reduce flips near-tied
    argmaxes and token equality would say nothing.  The pair therefore runs
    with float32 activations (``cfg.dtype``) and full-precision matmuls,
    where equal tokens mean the sharded program computes the one-chip
    function."""
    import jax

    with jax.default_matmul_precision("highest"):
        return tp_pair(cfg, ecfg, n_chips)


def tp_pair(cfg, ecfg, n_chips: int, prompt_lens=(900, 600),
            max_new: int = 8):
    import jax
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.config import MeshConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.models.quant import quantizing_transform
    from k8s_llm_rca_tpu.runtime.mesh import build_mesh
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils import get_tokenizer

    fails = []
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    params = llama.init_params(cfg, jax.random.PRNGKey(SEED),
                               tensor_transform=quantizing_transform(bits=8))
    prompts = random_prompts(cfg.vocab_size, prompt_lens)

    t0 = time.perf_counter()
    one = make_engine(cfg, ecfg, params, tok, use_kernel=True)
    ref = one.generate(prompts, max_new_tokens=max_new)
    one_s = time.perf_counter() - t0
    del one

    mesh = build_mesh(MeshConfig(model=n_chips),
                      devices=jax.devices()[:n_chips])
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    jax.block_until_ready(sharded)
    del params                             # the one-chip copy leaves chip 0
    gc.collect()

    t0 = time.perf_counter()
    eng = make_engine(cfg, ecfg, sharded, tok, tp_mesh=mesh, use_kernel=True)
    got = eng.generate(prompts, max_new_tokens=max_new)
    tp_s = time.perf_counter() - t0

    equal = sum(a == b for r, g in zip(ref, got)
                for a, b in zip(r.token_ids, g.token_ids))
    total = sum(len(r.token_ids) for r in ref)
    param_bytes, pool_bytes = device_bytes(eng.params), device_bytes(eng.pool)

    # the decode program the engine just ran, compiled again for its text
    # (a persistent-cache hit when the cache is on)
    b = ecfg.max_batch
    vec = jax.ShapeDtypeStruct((b,), jnp.int32)
    bt = jax.ShapeDtypeStruct((b, eng.pages_per_seq), jnp.int32)
    text = eng._decode_scan.lower(
        cfg, eng.params, eng.pool, vec, vec, bt, eng._key,
        ecfg.decode_chunk, eng.sampling, tok.eos_id,
        use_kernel=eng.use_kernel).compile().as_text()
    collectives = {
        op: len(re.findall(rf" {op}(-start)?\(", text)) for op in
        ("all-reduce", "all-gather", "reduce-scatter",
         "collective-permute", "all-to-all")}
    out = {"model": cfg.name, "n_layers": cfg.n_layers, "tp": n_chips,
           "weights": "int8", "kv_cache": ecfg.kv_cache_dtype,
           "activations": cfg.dtype,
           "one_chip_s": round(one_s, 2), "tp_s": round(tp_s, 2),
           "greedy_tokens": total, "greedy_tokens_equal": equal,
           "param_bytes_per_device": param_bytes,
           "pool_bytes_per_device": pool_bytes,
           "sharded_kernel": eng._kernel_mesh is mesh,
           "kernel_in_decode_program": "tpu_custom_call" in text,
           "collectives_in_decode_program": collectives}
    if equal != total:
        fails.append(f"TP={n_chips} greedy tokens differ from one chip")
    for name, held in (("params", param_bytes), ("pool", pool_bytes)):
        if (len(held) != n_chips
                or max(held.values()) > 1.05 * min(held.values())):
            fails.append(f"{name} are not spread evenly over the chips")
    if not (out["sharded_kernel"] and out["kernel_in_decode_program"]):
        fails.append("paged_attention_sharded is not in the decode program")
    if not collectives["all-reduce"]:
        fails.append("no all-reduce in a tensor-parallel decode step")
    return out, fails, None


# ----------------------------------------------------------------- main


def run_phase(meter: CompileMeter, device, phase: str, fn, *args):
    """Run one phase and print its line: ``fn`` returns (fields to print,
    failures, a value for the next phase).  A failed phase ends the
    script."""
    mark = meter.mark()
    t0 = time.perf_counter()
    try:
        fields, fails, value = fn(*args)
    except Exception as e:
        traceback.print_exc()
        fields, fails, value = {}, [f"{type(e).__name__}: {e}"[:500]], None
    phase_s = time.perf_counter() - t0
    compiled = meter.since(mark)
    emit(phase, {**fields, "ok": not fails, "failures": fails,
                 "phase_s": round(phase_s, 2), **compiled,
                 "run_s": round(phase_s - compiled["compile_s"], 2),
                 "hbm": hbm(device)})
    if fails:
        print(json.dumps({"ok": False, "failed_phase": phase}), flush=True)
        sys.exit(1)
    return value


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the TP=4 engine and the one-chip "
                             "engine it is compared with")
    args = parser.parse_args(argv)

    # the program itself, before JAX touches the chip: alone in a directory
    # this script has nothing to prove
    from k8s_llm_rca_tpu.config import MODEL_REGISTRY, EngineConfig
    from k8s_llm_rca_tpu.runtime.compile_cache import enable_compile_cache

    import jax

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    t_start = time.perf_counter()
    info = phase_device(args.chips)
    device = jax.devices()[0]
    cfg = MODEL_REGISTRY[MODEL]

    def run(phase, fn, *a):
        return run_phase(meter, device, phase, fn, *a)

    if args.chips == 4:
        ecfg = EngineConfig(max_batch=16, max_seq_len=4096,
                            kv_cache_dtype="int8")
        run("tp4", phase_tp,
            cfg.replace(max_seq_len=4096, dtype="float32"), ecfg, 4)
    else:
        defaults = EngineConfig()
        run("kernels", phase_kernels, cfg, defaults.page_size,
            defaults.num_pages, 4096, False)
        service = run("engine", phase_engine, ENGINE_ARGV, device)
        run("rca", phase_rca, service, ENGINE_ARGV,
            os.path.join(REPO, "chiprun_out", "smoke", "rca-results.json"))

    emit("total", {"wall_s": round(time.perf_counter() - t_start, 2),
                   **meter.since((0.0, 0, 0)),
                   "compile_cache_dir": cache_dir})
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0

if __name__ == "__main__":
    sys.exit(main())
