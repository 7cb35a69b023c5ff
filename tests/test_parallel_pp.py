"""Parallelism-module tests on the virtual 8-device CPU mesh, continued from
tests/test_parallel.py: PIPELINE-parallel serving (the pipelined paged
programs, ``pp_mesh=`` on the engine, and its compositions with TP, EP, the
prefix cache, grammars and speculation); every sharded path must match its
single-device reference exactly."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_rca_tpu.config import TINY, TINY_MOE, MeshConfig
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.ops.attention import causal_attention
from k8s_llm_rca_tpu.parallel import (
    expert_parallel_moe, pipeline_apply, ring_attention, ulysses_attention,
)
from k8s_llm_rca_tpu.runtime.mesh import build_mesh


def _pp_pool_case(cfg, b=4, s_pad=16, page=8, n_pages=40):
    """Seeded prompts with one private run of pages a row: page maps for
    the prefill, block tables (two pages of headroom) for the decode."""
    from k8s_llm_rca_tpu.engine.paged import TRASH_PAGE, init_paged_cache

    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s_pad), 0,
                                cfg.vocab_size)
    lengths = jnp.asarray([16, 13, 9, 16], jnp.int32)[:b]
    per_seq = s_pad // page + 2
    tables = np.full((b, cfg.max_seq_len // page), TRASH_PAGE, np.int32)
    tables[:, :per_seq] = 1 + np.arange(b * per_seq).reshape(b, per_seq)
    return (init_paged_cache(cfg, n_pages, page), tokens, lengths,
            jnp.asarray(tables[:, :s_pad // page]), jnp.asarray(tables))


def test_paged_pp_prefill_decode_matches_plain(cpu_devices):
    """PP SERVING, the functions the engine jits (round-1 review item 9):
    the pipelined paged prefill scatters each stage's layers' KV into the
    pool and the pipelined decode step — slot-group microbatches flowing
    GPipe-style — gives the plain paged path's logits, greedy tokens and
    pool over several steps."""
    from k8s_llm_rca_tpu.engine.paged import (
        paged_decode_step, paged_prefill_batch,
    )
    from k8s_llm_rca_tpu.parallel import (
        paged_pp_decode_step, paged_pp_prefill, stack_llama_stages,
    )

    cfg = TINY.replace(max_seq_len=64, n_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_stages, m, steps = 2, 2, 5
    mesh = build_mesh(MeshConfig(stage=n_stages),
                      devices=cpu_devices[:n_stages])
    pool, tokens, lengths, page_maps, tables = _pp_pool_case(cfg)

    ref_pool, ref_logits = paged_prefill_batch(cfg, params, pool, tokens,
                                               lengths, page_maps)
    stacked = stack_llama_stages(params, n_stages)
    pp_pool, pp_logits = paged_pp_prefill(
        cfg, params, pool, tokens, lengths, page_maps, mesh,
        microbatches=m, stacked_layers=stacked)
    np.testing.assert_allclose(np.asarray(pp_logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
    ref_tok = jnp.argmax(ref_logits, -1).astype(jnp.int32)
    pp_tok = jnp.argmax(pp_logits, -1).astype(jnp.int32)
    lens = lengths
    for _ in range(steps - 1):
        np.testing.assert_array_equal(np.asarray(pp_tok),
                                      np.asarray(ref_tok))
        ref_pool, lg = paged_decode_step(cfg, params, ref_pool, ref_tok,
                                         lens, tables, use_kernel=False)
        pp_pool, pp_lg = paged_pp_decode_step(
            cfg, params, pp_pool, pp_tok, lens, tables, mesh,
            microbatches=m, stacked_layers=stacked)
        lens = lens + 1
        ref_tok = jnp.argmax(lg, -1).astype(jnp.int32)
        pp_tok = jnp.argmax(pp_lg, -1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(pp_tok), np.asarray(ref_tok))
    # the pools agree on every page a sequence owns (the trash page takes
    # the padding rows' writes in whatever order)
    np.testing.assert_allclose(np.asarray(pp_pool.k[:, 1:]),
                               np.asarray(ref_pool.k[:, 1:]),
                               rtol=1e-4, atol=1e-4)


def test_paged_pp_decode_under_jit_with_sharded_pool(cpu_devices):
    """The PP decode step compiles under jit with the pool PLACED sharded
    (layer axis over "stage") and leaves it so: each stage device holds
    1/P of the KV bytes."""
    from jax.sharding import NamedSharding
    from k8s_llm_rca_tpu.parallel import (
        kv_cache_stage_specs, paged_pp_decode_step, paged_pp_prefill,
        stack_llama_stages,
    )

    cfg = TINY.replace(max_seq_len=64, n_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
    pool, tokens, lengths, page_maps, tables = _pp_pool_case(cfg)
    spec = NamedSharding(mesh, kv_cache_stage_specs())
    pool = type(pool)(jax.device_put(pool.k, spec),
                      jax.device_put(pool.v, spec))
    stacked = stack_llama_stages(params, 2)     # hoisted off the hot path
    pool, logits = paged_pp_prefill(cfg, params, pool, tokens, lengths,
                                    page_maps, mesh, stacked_layers=stacked)

    step = jax.jit(lambda pl, t, ln: paged_pp_decode_step(
        cfg, params, pl, t, ln, tables, mesh, stacked_layers=stacked))
    pool, logits = step(pool, jnp.argmax(logits, -1).astype(jnp.int32),
                        lengths)
    assert bool(jnp.isfinite(logits).all())
    shard_shape = pool.k.sharding.shard_shape(pool.k.shape)
    assert shard_shape[0] == cfg.n_layers // 2      # layers over stages


# ---------------------------------------------------------------------------
# PP ENGINE integration (round-2 review item 1): pp_mesh=
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype,page_size", [
    (None, 16), ("int8", 16), ("int4", 16), (None, 8), ("int8", 8)])
def test_pp_engine_matches_plain(cpu_devices, kv_dtype, page_size):
    """Serving PP: the continuous-batching engine with ``pp_mesh=`` — layer
    axis of weights AND page pool sharded over "stage", admissions through
    the batched pipelined prefill (pages scattered per stage), decode
    GPipe-microbatched over the gathered local page view — must emit the
    plain engine's exact greedy tokens, incl. quantized KV (the
    optimization that carries the big single-chip configs) and
    continuous-batching admission/retirement churn."""
    from k8s_llm_rca_tpu.config import EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64, n_layers=4)
    mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch=4, max_seq_len=64,
                        prefill_buckets=(16, 32), max_new_tokens=6,
                        temperature=0.0, kv_cache_dtype=kv_dtype,
                        page_size=page_size, num_pages=512 // page_size,
                        prefix_cache=False, decode_chunk=1)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod pending unschedulable", add_bos=True),
               tok.encode("pvc not bound", add_bos=True),
               tok.encode("oom killed container", add_bos=True),
               tok.encode("node disk pressure taint", add_bos=True),
               tok.encode("dns resolution failing", add_bos=True)]

    with jax.default_matmul_precision("float32"):
        ref = PagedInferenceEngine(cfg, ecfg, params, tok).generate(
            prompts, max_new_tokens=6)
        eng = PagedInferenceEngine(cfg, ecfg, params, tok, pp_mesh=mesh)
        got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids, kv_dtype
    # the pool is genuinely stage-sharded: 1/P of the layer axis per device
    shard = eng.pool.k.sharding.shard_shape(eng.pool.k.shape)
    assert shard[0] == cfg.n_layers // 2
    eng.allocator.check()                      # no pages leaked under PP


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_pp_paged_prefix_cache_reuse(cpu_devices, kv_dtype):
    """Prefix caching composes with (stage-only) PP: a repeated prompt's
    second admission routes through the PIPELINED chunked prefix prefill
    — each stage reuses its own layers' cached prefix pages from its
    local pool slice — with greedy output identical to the plain paged
    prefix engine and real page-level KV reuse (prefix_hit_tokens),
    including the quantized pool (scale gather + scale scatter in the
    pipelined chunk body)."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.logging import METRICS
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64, n_layers=4)
    mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, page_size=8,
                        num_pages=64, prefill_buckets=(16, 32),
                        max_new_tokens=6, temperature=0.0,
                        prefix_cache=True, decode_chunk=1,
                        kv_cache_dtype=kv_dtype)
    prompt = tok.encode("incident pod crashloop in namespace prod",
                        add_bos=True)
    assert len(prompt) > 16            # spans >2 pages -> cacheable prefix

    with jax.default_matmul_precision("float32"):
        plain = PagedInferenceEngine(cfg, ecfg, params, tok,
                                     use_kernel=False)
        p1 = plain.generate([list(prompt)], max_new_tokens=6)[0]
        eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                   use_kernel=False, pp_mesh=mesh)
        r1 = eng.generate([list(prompt)], max_new_tokens=6)[0]
        before = METRICS.count("engine.prefix_hit_tokens")
        r2 = eng.generate([list(prompt)], max_new_tokens=6)[0]
    assert r1.token_ids == p1.token_ids
    assert r2.token_ids == r1.token_ids
    # the second admission actually REUSED cached prefix KV through the
    # pipelined chunk path
    assert METRICS.count("engine.prefix_hit_tokens") > before, kv_dtype
    eng.allocator.check()


@pytest.mark.parametrize("kv_dtype,weight_bits", [
    (None, None), ("int8", None), ("int4", None),
    # int4 weights (re-packed per shard) under the int4 pool: the reuse
    # through the manual-TP chunk prefill at the quantization the smoke
    # serves (moved here from the dryrun at PR 50)
    ("int4", 4)])
def test_pp_tp_paged_prefix_cache_reuse(cpu_devices, kv_dtype, weight_bits):
    """Prefix caching composes with PP×TP (round-4 review item 9 — the
    production mesh of the agent workload the cache was built for): a
    repeated prompt's second admission routes through the pipelined
    chunked prefix prefill whose stage bodies run the MANUAL-TP chunk
    layer (paged._chunk_layer(tp_axis=): per-shard prefix gather incl. the per-shard
    int4 layout, psum combines, pmax full-row scales) — greedy output
    identical to the plain paged prefix engine, with real page-level KV
    reuse."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.logging import METRICS
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64, n_layers=4)
    mesh = build_mesh(MeshConfig(stage=2, model=2),
                      devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if weight_bits:
        from k8s_llm_rca_tpu.models.quant import quantize_params

        params = quantize_params(params, compute_dtype=jnp.float32,
                                 bits=weight_bits)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, page_size=8,
                        num_pages=64, prefill_buckets=(16, 32),
                        max_new_tokens=6, temperature=0.0,
                        prefix_cache=True, decode_chunk=1,
                        kv_cache_dtype=kv_dtype)
    prompt = tok.encode("incident pod crashloop in namespace prod",
                        add_bos=True)
    assert len(prompt) > 16            # spans >2 pages -> cacheable prefix

    with jax.default_matmul_precision("float32"):
        plain = PagedInferenceEngine(cfg, ecfg, params, tok,
                                     use_kernel=False)
        p1 = plain.generate([list(prompt)], max_new_tokens=6)[0]
        eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                   use_kernel=False, pp_mesh=mesh,
                                   tp_mesh=mesh)
        r1 = eng.generate([list(prompt)], max_new_tokens=6)[0]
        before = METRICS.count("engine.prefix_hit_tokens")
        r2 = eng.generate([list(prompt)], max_new_tokens=6)[0]
    assert r1.token_ids == p1.token_ids, kv_dtype
    assert r2.token_ids == r1.token_ids, kv_dtype
    # the second admission actually REUSED cached prefix KV through the
    # pipelined manual-TP chunk path
    assert METRICS.count("engine.prefix_hit_tokens") > before, kv_dtype
    eng.allocator.check()


def test_pp_engine_dfa_scan_parity(cpu_devices):
    """Grammar-constrained decode stays on the fast path under PP: the
    DFA rides inside the chunked scan whose body is the PIPELINED decode
    step, emitting the same tokens as the stepwise host path."""
    import json as jsonlib

    from k8s_llm_rca_tpu.config import EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.engine.constrain import make_grammar
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=128, n_layers=4)
    mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    schema = {"type": "object", "properties": [
        ("kind", {"enum": ["Pod", "Service", "Node"]}),
        ("ok", {"type": "boolean"})]}
    prompt = tok.encode("diagnose:", add_bos=True)

    outs = {}
    with jax.default_matmul_precision("float32"):
        for chunk in (1, 8):
            ecfg = EngineConfig(max_batch=4, max_seq_len=128,
                                prefill_buckets=(16, 32), max_new_tokens=40,
                                decode_chunk=chunk)
            eng = make_engine(cfg, ecfg, params, tok, pp_mesh=mesh)
            rid = eng.submit(prompt, max_new_tokens=40,
                             grammar=make_grammar(schema, tok))
            res = {r.seq_id: r for r in eng.run_to_completion()}
            outs[chunk] = res[rid].text
    assert outs[1] == outs[8], outs
    jsonlib.loads(outs[1])


@pytest.mark.parametrize("page_size", [16, 8])
@pytest.mark.parametrize("kv_dtype", [None, "int8", "int4"])
def test_pp_tp_composed_engine_matches_plain(cpu_devices, kv_dtype,
                                             page_size):
    """PP×TP in ONE mesh — the realistic multi-host pod serving shape
    (paged KV + continuous batching, stages over DCN, heads/hidden over
    ICI): weights shard (stage, model), the pool shards layer-over-stage
    × kv-over-model, stage bodies run manual-TP qkv/attention with psum
    combines.  Quantized pools (int8 + packed int4) compose via the pmax
    full-row scale, so greedy parity with the plain engine is exact —
    through admission churn, page growth and the chunked scan."""
    from k8s_llm_rca_tpu.config import EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64, n_layers=4)
    mesh = build_mesh(MeshConfig(stage=2, model=2),
                      devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod pending unschedulable", add_bos=True),
               tok.encode("pvc not bound", add_bos=True),
               tok.encode("oom killed container", add_bos=True),
               tok.encode("node disk pressure taint", add_bos=True),
               tok.encode("dns resolution failing", add_bos=True)]
    for chunk in (1, 4):
        ecfg = EngineConfig(max_batch=4, max_seq_len=64,
                            prefill_buckets=(16, 32), max_new_tokens=6,
                            temperature=0.0, kv_cache_dtype=kv_dtype,
                            page_size=page_size,
                            num_pages=512 // page_size,
                            prefix_cache=False, decode_chunk=chunk)
        with jax.default_matmul_precision("float32"):
            ref = PagedInferenceEngine(cfg, ecfg, params, tok).generate(
                prompts, max_new_tokens=6)
            eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                       pp_mesh=mesh, tp_mesh=mesh)
            got = eng.generate(prompts, max_new_tokens=6)
        for r, g in zip(ref, got):
            assert r.token_ids == g.token_ids, (kv_dtype, chunk)
        eng.allocator.check()                  # no pages leaked
    # the pool is genuinely sharded on BOTH axes
    shard = eng.pool.k.sharding.shard_shape(eng.pool.k.shape)
    assert shard[0] == cfg.n_layers // 2           # layers over 'stage'
    assert shard[3] == eng.pool.k.shape[3] // 2    # kv over 'model'
    if kv_dtype is not None:
        # scale pools shard layer-over-stage, replicate across model
        sc = eng.pool.k_scale.sharding.shard_shape(eng.pool.k_scale.shape)
        assert sc[0] == cfg.n_layers // 2


@pytest.mark.parametrize("page_size", [16, 8])
def test_pp_ep_composed_engine_matches_dense(cpu_devices, page_size):
    """PP×EP in ONE mesh (Mixtral across pods: stages over DCN, expert
    dispatch over ICI within each stage): stacked expert weights shard
    (stage, expert), stage bodies run dense attention on the replicated
    stream and route each expert peer's token slice through the shared
    all-to-all dispatch — exact greedy parity with the dense
    single-device engine."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(n_layers=4, n_experts=4, max_seq_len=64)
    mesh = build_mesh(MeshConfig(stage=2, expert=2),
                      devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod pending unschedulable", add_bos=True),
               tok.encode("pvc not bound", add_bos=True),
               tok.encode("oom killed container", add_bos=True)]
    for chunk in (1, 4):
        ecfg = EngineConfig(max_batch=4, max_seq_len=64,
                            prefill_buckets=(16, 32), max_new_tokens=6,
                            temperature=0.0, decode_chunk=chunk,
                            page_size=page_size,
                            num_pages=512 // page_size,
                            prefix_cache=False)
        with jax.default_matmul_precision("float32"):
            ref = make_engine(cfg, ecfg, params, tok).generate(
                prompts, max_new_tokens=6)
            eng = make_engine(cfg, ecfg, params, tok, pp_mesh=mesh,
                              ep_mesh=mesh, use_kernel=False)
            got = eng.generate(prompts, max_new_tokens=6)
        for r, g in zip(ref, got):
            assert r.token_ids == g.token_ids, chunk
    # expert weights genuinely sharded on BOTH axes: stage × expert
    _, stacked = eng.params
    shard = stacked["w_gate"].sharding.shard_shape(stacked["w_gate"].shape)
    assert shard[0] == 1                            # stages split
    assert shard[2] == cfg.n_experts // 2           # experts split
    eng.allocator.check()


@pytest.mark.parametrize("page_size", [16, 8])
@pytest.mark.parametrize("draft", ["ngram", "model", "ngram-int8"])
def test_pp_speculative_matches_plain(cpu_devices, page_size, draft):
    """Speculation composes with PP: the verify step runs the PIPELINED
    multi-token decode (paged_pp_decode_multi) over the stage-sharded
    pool, with exact greedy parity against the non-speculative non-PP
    engine — for n-gram drafts, a draft MODEL, and an int8-quantized
    pool (the pipelined verify's quantized scale-write path)."""
    import dataclasses

    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(n_layers=4, max_seq_len=64)
    mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    extra = dict(page_size=page_size, num_pages=512 // page_size,
                 prefix_cache=False)
    if draft == "ngram-int8":
        extra["kv_cache_dtype"] = "int8"
    dm = dict(draft_model=(cfg, params)) if draft == "model" else {}
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, prefill_buckets=(16,),
                        max_new_tokens=10, temperature=0.0, **extra)
    prompts = [tok.encode("the pod the pod", add_bos=True),
               tok.encode("pvc bound pvc", add_bos=True)]
    with jax.default_matmul_precision("float32"):
        ref = make_engine(cfg, ecfg, params, tok,
                          use_kernel=False).generate(
            [list(p) for p in prompts], max_new_tokens=10)
        spec = make_engine(cfg, dataclasses.replace(ecfg, speculative_k=3),
                           params, tok, pp_mesh=mesh, use_kernel=False,
                           **dm)
        got = spec.generate([list(p) for p in prompts], max_new_tokens=10)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids, draft
    spec.allocator.check()


def test_pp_composed_speculative_matches_plain(cpu_devices):
    """Speculation through the COMPOSED pipelined verify: PP×TP (the
    pod serving shape) and PP×EP (MoE) both match their
    non-speculative plain engines exactly."""
    import dataclasses

    from k8s_llm_rca_tpu.config import TINY, TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    prompts_txt = ["the pod the pod", "pvc bound pvc"]
    with jax.default_matmul_precision("float32"):
        # PP×TP × spec
        cfg = TINY.replace(n_layers=4, max_seq_len=64)
        mesh = build_mesh(MeshConfig(stage=2, model=2),
                          devices=cpu_devices[:4])
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        prompts = [tok.encode(t, add_bos=True) for t in prompts_txt]
        ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                            prefill_buckets=(16,), max_new_tokens=8,
                            temperature=0.0, page_size=16,
                            num_pages=32, prefix_cache=False)
        ref = make_engine(cfg, ecfg, params, tok,
                          use_kernel=False).generate(
            [list(p) for p in prompts], max_new_tokens=8)
        spec = make_engine(cfg, dataclasses.replace(ecfg, speculative_k=3),
                           params, tok, pp_mesh=mesh, tp_mesh=mesh,
                           use_kernel=False)
        got = spec.generate([list(p) for p in prompts], max_new_tokens=8)
        for r, g in zip(ref, got):
            assert r.token_ids == g.token_ids
        spec.allocator.check()

        # PP×EP × spec
        mcfg = TINY_MOE.replace(n_layers=4, n_experts=4, max_seq_len=64)
        emesh = build_mesh(MeshConfig(stage=2, expert=2),
                           devices=cpu_devices[:4])
        mparams = llama.init_params(mcfg, jax.random.PRNGKey(1))
        mtok = get_tokenizer(vocab_size=mcfg.vocab_size)
        mp = [mtok.encode(t, add_bos=True) for t in prompts_txt]
        mecfg = EngineConfig(max_batch=4, max_seq_len=64,
                             prefill_buckets=(16,), max_new_tokens=8,
                             temperature=0.0, prefix_cache=False)
        mref = make_engine(mcfg, mecfg, mparams, mtok).generate(
            [list(p) for p in mp], max_new_tokens=8)
        mspec = make_engine(mcfg,
                            dataclasses.replace(mecfg, speculative_k=3),
                            mparams, mtok, pp_mesh=emesh, ep_mesh=emesh)
        mgot = mspec.generate([list(p) for p in mp], max_new_tokens=8)
        for r, g in zip(mref, mgot):
            assert r.token_ids == g.token_ids


@pytest.mark.parametrize("bits,page_size,spec_k", [
    (8, 16, 0), (8, 8, 0), (4, 16, 0), (4, 8, 0),
    # int8 weights + int8 pool + the pipelined multi-token verify, against
    # the plain engine that does not speculate (moved here from the dryrun
    # at PR 50)
    (8, 16, 2)])
def test_pp_tp_quantized_weights_matches_plain(cpu_devices, bits, page_size,
                                               spec_k):
    """Quantized WEIGHTS compose with PP×TP (the quantized-flagship pod
    serving shape): stacked QuantTensor leaves shard their payload on
    the weight spec and their per-channel scales with reduced dims
    replicated; int4 leaves are additionally RE-PACKED per shard at the
    sharding boundary ("shard first, pack second") so the manual-TP
    stage bodies' shard-local dequant is exact — greedy parity with the
    plain engine on the same quantized params.  bits=4 runs the bench's
    own flagship quant config (int4 weights + int4 KV)."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models.quant import quantize_params
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(n_layers=4, max_seq_len=64)
    mesh = build_mesh(MeshConfig(stage=2, model=2),
                      devices=cpu_devices[:4])
    params = quantize_params(
        llama.init_params(cfg, jax.random.PRNGKey(0)),
        compute_dtype=jnp.float32, bits=bits)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32), max_new_tokens=6,
                        temperature=0.0,
                        kv_cache_dtype="int8" if bits == 8 else "int4",
                        page_size=page_size, num_pages=512 // page_size,
                        prefix_cache=False)
    prompts = [tok.encode("pod crashloop kube-system", add_bos=True),
               tok.encode("node disk pressure taint", add_bos=True)]
    with jax.default_matmul_precision("float32"):
        ref = make_engine(cfg, ecfg, params, tok,
                          use_kernel=False).generate(
            prompts, max_new_tokens=6)
        eng = make_engine(
            cfg, dataclasses.replace(ecfg, speculative_k=spec_k), params,
            tok, pp_mesh=mesh, tp_mesh=mesh, use_kernel=False)
        got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    # the int8 payloads are genuinely sharded on BOTH axes
    _, stacked = eng.params
    shard = stacked["wq"].q.sharding.shard_shape(stacked["wq"].q.shape)
    assert shard[0] == 1                          # stages split
    assert shard[3] == stacked["wq"].q.shape[3] // 2   # columns over model
    eng.allocator.check()


def test_pp_tp_exclusions(cpu_devices):
    """PP×TP rejects loudly: distinct meshes, int4 weights whose channel
    dims don't divide 2*n_tp (per-shard split-half packing needs even
    per-shard pairs; divisible int4 composes — see the parity tests
    above), MoE models, and Megatron SP."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models.quant import quantize_params
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(n_layers=4, max_seq_len=64)
    mesh = build_mesh(MeshConfig(stage=2, model=2),
                      devices=cpu_devices[:4])
    mesh_b = build_mesh(MeshConfig(stage=2, model=2),
                        devices=cpu_devices[4:8])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, prefill_buckets=(16,))
    with pytest.raises(ValueError, match="SAME composed mesh"):
        make_engine(cfg, ecfg, params, tok, pp_mesh=mesh, tp_mesh=mesh_b)
    # intermediate_size=250 is even (packable) but 250 % (2*n_tp)=4 != 0:
    # the per-shard repack cannot split its column pairs evenly
    odd_cfg = cfg.replace(intermediate_size=250)
    odd_params = quantize_params(
        llama.init_params(odd_cfg, jax.random.PRNGKey(2)), bits=4)
    with pytest.raises(ValueError, match="per-shard split-half"):
        make_engine(odd_cfg, ecfg, odd_params, tok,
                    pp_mesh=mesh, tp_mesh=mesh)
    with pytest.raises(ValueError, match="MoE"):
        moe_cfg = TINY_MOE.replace(n_layers=4, n_experts=4, max_seq_len=64)
        make_engine(moe_cfg, ecfg,
                    llama.init_params(moe_cfg, jax.random.PRNGKey(1)),
                    tok, pp_mesh=mesh, tp_mesh=mesh)
    with pytest.raises(ValueError, match="unsupported on the PP paths"):
        make_engine(cfg, ecfg, params, tok, pp_mesh=mesh, tp_mesh=mesh,
                    sp=True)


def test_pp_mesh_validation(cpu_devices):
    """PP preconditions fail loudly at construction, not mid-serve."""
    from k8s_llm_rca_tpu.config import EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64, n_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    pp = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
    tp = build_mesh(MeshConfig(data=2, model=2), devices=cpu_devices[:4])
    base = dict(max_batch=4, max_seq_len=64, prefill_buckets=(16, 32),
                max_new_tokens=4)

    with pytest.raises(ValueError, match="SAME composed mesh"):
        # PP×TP composes only on ONE mesh; two distinct meshes reject
        make_engine(cfg, EngineConfig(**base), params, tok,
                    pp_mesh=pp, tp_mesh=tp)
    from jax.sharding import Mesh as _Mesh

    no_stage = _Mesh(np.array(cpu_devices[:2]), ("x",))
    with pytest.raises(ValueError, match="stage"):
        make_engine(cfg, EngineConfig(**base), params, tok, pp_mesh=no_stage)
    with pytest.raises(ValueError, match="n_layers"):
        make_engine(cfg.replace(n_layers=3), EngineConfig(**base),
                    llama.init_params(cfg.replace(n_layers=3),
                                      jax.random.PRNGKey(0)),
                    tok, pp_mesh=pp)
    with pytest.raises(ValueError, match="microbatches"):
        make_engine(cfg, EngineConfig(**base), params, tok, pp_mesh=pp,
                    pp_microbatches=3)
    with pytest.raises(ValueError, match="prefix_cache"):
        # prefix caching composes with stage-only PP and PP×TP (see
        # test_pp_paged_prefix_cache_reuse / test_pp_tp_paged_prefix_
        # cache_reuse) but not with PP×EP — the chunk layer has no
        # expert dispatch
        moe_cfg4 = TINY_MOE.replace(n_layers=4, n_experts=4,
                                    max_seq_len=64)
        ppep = build_mesh(MeshConfig(stage=2, expert=2),
                          devices=cpu_devices[:4])
        PagedInferenceEngine(
            moe_cfg4, EngineConfig(page_size=16, num_pages=32,
                                   prefix_cache=True, **base),
            llama.init_params(moe_cfg4, jax.random.PRNGKey(3)), tok,
            pp_mesh=ppep, ep_mesh=ppep, use_kernel=False)
    with pytest.raises(ValueError, match="use_kernel"):
        PagedInferenceEngine(
            cfg, EngineConfig(page_size=16, num_pages=32,
                              prefix_cache=False, **base),
            params, tok, pp_mesh=pp, use_kernel=True)
