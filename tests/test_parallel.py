"""Parallelism-module tests on the virtual 8-device CPU mesh: every sharded
path must match its single-device reference implementation exactly
(tolerance = fp32 accumulation noise).  One mesh axis at a time: ring and
Ulysses attention, the pipeline schedule, expert-parallel dispatch, and the
engines under TP, CP and EP.  The composed meshes and sequence parallelism
are in tests/test_parallel_composed.py, pipeline-parallel serving in
tests/test_parallel_pp.py (split by mode at PR 46 so that ``--dist
loadfile`` spreads them; no test changed)."""

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


@pytest.fixture(scope="module")
def seq_mesh(cpu_devices):
    return build_mesh(MeshConfig(seq=4), devices=cpu_devices[:4])


def _qkv(key, b=2, s=32, n_heads=4, n_kv=2, d=16):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, n_heads, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, n_kv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, n_kv, d), jnp.float32)
    return q, k, v


def test_ring_attention_matches_reference(seq_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = causal_attention(q, k, v, jnp.full((2,), 32, jnp.int32))
    out = ring_attention(q, k, v, seq_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_jit(seq_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(1))
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, seq_mesh))(q, k, v)
    ref = causal_attention(q, k, v, jnp.full((2,), 32, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_matches_reference(seq_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(2))
    ref = causal_attention(q, k, v, jnp.full((2,), 32, jnp.int32))
    out = ulysses_attention(q, k, v, seq_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_rejects_indivisible_heads(seq_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(3), n_heads=6, n_kv=6)
    with pytest.raises(ValueError):
        ulysses_attention(q, k, v, seq_mesh)


def test_pipeline_matches_sequential(cpu_devices):
    mesh = build_mesh(MeshConfig(stage=4), devices=cpu_devices[:4])
    n_stages, m, b, h = 4, 6, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(4), n_stages)
    stacked = {
        "w": jnp.stack([jax.random.normal(k, (h, h)) * 0.3 for k in keys]),
        "b": jnp.stack([jax.random.normal(k, (h,)) * 0.1 for k in keys]),
    }
    x_mb = jax.random.normal(jax.random.PRNGKey(5), (m, b, h))

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    out = pipeline_apply(stage_fn, stacked, x_mb, mesh)

    ref = x_mb
    for i in range(n_stages):
        ref = stage_fn(jax.tree.map(lambda a, i=i: a[i], stacked), ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_llama_pipeline_forward_matches_sequential(cpu_devices):
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.parallel import llama_pipeline_forward

    cfg = TINY.replace(n_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(6))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (8, 16), 0,
                                cfg.vocab_size)
    ref = llama.forward(cfg, params, tokens)
    for n_stages, microbatches in ((4, 4), (2, 2)):
        mesh = build_mesh(MeshConfig(stage=n_stages),
                          devices=cpu_devices[:n_stages])
        out = llama_pipeline_forward(cfg, params, tokens, mesh,
                                     microbatches=microbatches)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_llama_pipeline_prestacked_layers_match(cpu_devices):
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.parallel import (
        llama_pipeline_forward, stack_llama_stages,
    )

    cfg = TINY.replace(n_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (4, 8), 0,
                                cfg.vocab_size)
    mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
    stacked = stack_llama_stages(params, 2)    # hoisted once by the caller
    out = llama_pipeline_forward(cfg, params, tokens, mesh, microbatches=2,
                                 stacked_layers=stacked)
    ref = llama_pipeline_forward(cfg, params, tokens, mesh, microbatches=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_llama_pipeline_forward_quantized(cpu_devices):
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.models.quant import quantize_params
    from k8s_llm_rca_tpu.parallel import llama_pipeline_forward

    cfg = TINY.replace(n_layers=4)
    params = quantize_params(llama.init_params(cfg, jax.random.PRNGKey(8)),
                             compute_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (4, 12), 0,
                                cfg.vocab_size)
    mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
    out = llama_pipeline_forward(cfg, params, tokens, mesh, microbatches=2)
    ref = llama.forward(cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_llama_pipeline_rejects_indivisible_layers(cpu_devices):
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.parallel import llama_pipeline_forward

    cfg = TINY.replace(n_layers=3)
    params = llama.init_params(cfg, jax.random.PRNGKey(10))
    tokens = jnp.zeros((2, 8), jnp.int32)
    mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
    with pytest.raises(AssertionError, match="stages"):
        llama_pipeline_forward(cfg, params, tokens, mesh, microbatches=2)


def test_expert_parallel_moe_matches_dense(cpu_devices):
    """Hard EP dispatch == dense soft-dispatch when capacity is ample."""
    mesh = build_mesh(MeshConfig(data=2, expert=4),
                      devices=cpu_devices[:8])
    cfg = TINY_MOE.replace(n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(6))
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, cfg.hidden_size),
                          jnp.float32)

    dense = llama._moe_mlp(cfg, layer, x)
    ep = expert_parallel_moe(x, layer, mesh, top_k=cfg.n_experts_per_tok,
                             capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_expert_parallel_moe_drops_under_pressure(cpu_devices):
    """With capacity ~0 the output collapses toward zero (tokens dropped),
    proving the capacity accounting actually binds."""
    mesh = build_mesh(MeshConfig(data=2, expert=4), devices=cpu_devices[:8])
    cfg = TINY_MOE.replace(n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(8))
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, cfg.hidden_size),
                          jnp.float32)
    tight = expert_parallel_moe(x, layer, mesh, top_k=2,
                                capacity_factor=0.01)
    dense = llama._moe_mlp(cfg, layer, x)
    assert float(jnp.abs(tight).sum()) < float(jnp.abs(dense).sum())


def test_tp_sharded_engine_matches_unsharded(cpu_devices):
    """Serving TP: the continuous-batching engine fed TP-sharded params
    must emit the same greedy tokens as the unsharded engine."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2), devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod pending unschedulable", add_bos=True),
               tok.encode("pvc not bound", add_bos=True)]

    ref = make_engine(cfg, ecfg, params, tok).generate(
        prompts, max_new_tokens=6)
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    got = make_engine(cfg, ecfg, sharded, tok).generate(
        prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids


def test_tp_sharded_engine_quantized_params(cpu_devices):
    """TP x quantization: sharding int8/int4 params must work (the int
    payload takes the weight spec, per-channel scales replicate their
    reduced dims) and the sharded engine must emit the unsharded engine's
    greedy tokens."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models.quant import quantize_params
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2), devices=cpu_devices[:4])
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod pending unschedulable", add_bos=True)]

    for bits in (8, 4):
        qp = quantize_params(llama.init_params(cfg, jax.random.PRNGKey(0)),
                             compute_dtype=jnp.float32, bits=bits)
        ref = make_engine(cfg, ecfg, qp, tok).generate(
            prompts, max_new_tokens=6)
        sharded = shard_pytree(qp, llama_param_specs(cfg), mesh)
        got = make_engine(cfg, ecfg, sharded, tok).generate(
            prompts, max_new_tokens=6)
        assert ref[0].token_ids == got[0].token_ids, bits


def test_cp_prefill_matches_single_device(seq_mesh):
    """Ring-attention (context-parallel) prefill must produce the same KV
    and last-token logits as the plain single-device prefill."""
    from k8s_llm_rca_tpu.config import TINY

    cfg = TINY
    mesh = seq_mesh
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 32), jnp.int32).at[0, :27].set(
        jax.random.randint(jax.random.PRNGKey(1), (27,), 0, cfg.vocab_size))
    length = jnp.int32(27)

    ref_k, ref_v, ref_logits = llama.prefill_kv(cfg, params, tokens, length)
    cp_k, cp_v, cp_logits = llama.prefill_kv_cp(cfg, params, tokens, length,
                                                mesh)
    np.testing.assert_allclose(np.asarray(cp_logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
    # only positions < length matter (padded KV is never attended to)
    np.testing.assert_allclose(np.asarray(cp_k[:, :27]),
                               np.asarray(ref_k[:, :27]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(cp_v[:, :27]),
                               np.asarray(ref_v[:, :27]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cp_mode,page_size", [
    ("ring", 8), ("ulysses", 8), ("ring", 16)])
def test_engine_cp_prefill_matches_plain_engine(seq_mesh, cp_mode,
                                                page_size):
    """The engine in context-parallel prefill mode (ring, and Ulysses,
    the second CP mode) emits the same greedy tokens as the plain
    engine."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, page_size=page_size,
                        num_pages=256 // page_size,
                        prefill_buckets=(16, 32, 64),
                        max_new_tokens=6, temperature=0.0,
                        prefix_cache=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod sandbox changed restarting", add_bos=True),
               tok.encode("oom killed container", add_bos=True)]

    ref = PagedInferenceEngine(cfg, ecfg, params, tok,
                               use_kernel=False).generate(
        prompts, max_new_tokens=6)
    eng = PagedInferenceEngine(cfg, ecfg, params, tok, use_kernel=False,
                               cp_mesh=seq_mesh, cp_mode=cp_mode)
    got = eng.generate([list(p) for p in prompts], max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    eng.allocator.check()


@pytest.mark.parametrize("knobs,refusal", [
    (dict(page_size=8, prefix_cache=True), "prefix_cache"),
    (dict(page_size=6, prefill_buckets=(18,), prefix_cache=False),
     "must divide")])
def test_engine_cp_rejects_bad_configs(seq_mesh, knobs, refusal):
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    with pytest.raises(ValueError, match=refusal):
        PagedInferenceEngine(
            cfg, EngineConfig(max_batch=1, max_seq_len=64, num_pages=32,
                              **knobs),
            llama.init_params(cfg, jax.random.PRNGKey(0)),
            get_tokenizer(vocab_size=cfg.vocab_size), cp_mesh=seq_mesh)


def test_ep_sharded_engine_matches_unsharded(cpu_devices):
    """EP serving: MoE engine fed expert-sharded params must emit the same
    greedy tokens as the unsharded engine (GSPMD partitions the dense
    soft-dispatch einsums over the expert axis)."""
    from k8s_llm_rca_tpu.config import EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(n_experts=4, max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, expert=4), devices=cpu_devices[:8])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("node notready kubelet down", add_bos=True)]

    ref = make_engine(cfg, ecfg, params, tok).generate(
        prompts, max_new_tokens=6)
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    got = make_engine(cfg, ecfg, sharded, tok).generate(
        [list(prompts[0])], max_new_tokens=6)
    assert ref[0].token_ids == got[0].token_ids


def test_ep_engine_matches_dense(cpu_devices):
    """Serving EP (round-1 review item 4): an engine built with an expert-axis
    mesh — every MoE MLP dispatching through the all-to-all path, prefill
    AND decode — must emit the same greedy tokens as the dense
    soft-dispatch engine (lossless capacity)."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models import mixtral
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(max_seq_len=64, n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch=4, max_seq_len=64,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod pending unschedulable", add_bos=True),
               tok.encode("pvc not bound", add_bos=True),
               tok.encode("secret missing for mount", add_bos=True)]

    ref = make_engine(cfg, ecfg, params, tok).generate(
        prompts, max_new_tokens=6)
    ep_engine = mixtral.make_ep_engine(
        cfg, ecfg, params, tok, n_expert_shards=4, n_data=1,
        devices=cpu_devices[:4])
    got = ep_engine.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
        assert r.finish_reason == g.finish_reason


def test_ep_paged_engine_matches_dense(cpu_devices):
    """EP x paged: the paged engine under an expert mesh (page-scatter
    writes + all-to-all MoE) matches the dense paged engine."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models import mixtral
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(max_seq_len=64, n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch=4, max_seq_len=64,
                        page_size=8, num_pages=48,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("node notready kubelet stopped", add_bos=True),
               tok.encode("image pull backoff", add_bos=True)]

    ref = make_engine(cfg, ecfg, params, tok, use_kernel=False).generate(
        prompts, max_new_tokens=6)
    ep_engine = mixtral.make_ep_engine(
        cfg, ecfg, params, tok, n_expert_shards=4, n_data=1,
        devices=cpu_devices[:4], use_kernel=False)
    got = ep_engine.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    ep_engine.allocator.check()


def test_ep_mesh_validation():
    """Misconfigured EP serving fails loudly at construction."""
    from k8s_llm_rca_tpu.config import TINY, TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine.engine import validate_ep_mesh
    from k8s_llm_rca_tpu.models import mixtral

    mesh = build_mesh(MeshConfig(data=1, expert=4),
                      devices=jax.devices("cpu")[:4])
    ecfg = EngineConfig(max_batch=4, max_seq_len=64, prefill_buckets=(16,))
    with pytest.raises(ValueError, match="MoE model"):
        validate_ep_mesh(mesh, TINY, ecfg, None)
    with pytest.raises(ValueError, match="not divisible"):
        validate_ep_mesh(mesh, TINY_MOE.replace(n_experts=4),
                         EngineConfig(max_batch=3, max_seq_len=64,
                                      prefill_buckets=(16,)), None)
    with pytest.raises(ValueError, match="n_experts"):
        validate_ep_mesh(mesh, TINY_MOE.replace(n_experts=3), ecfg, None)
    with pytest.raises(ValueError, match="not an MoE"):
        mixtral.make_ep_engine(TINY, ecfg, {}, None, n_expert_shards=4)


def test_paged_tp_engine_matches_unsharded(cpu_devices):
    """Paged serving TP (round-1 review item 5): the paged engine with
    TP-sharded params AND the page pool sharded on the merged kv axis must
    emit the unsharded paged engine's greedy tokens."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2), devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        page_size=8, num_pages=32,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod pending unschedulable", add_bos=True),
               tok.encode("pvc not bound", add_bos=True)]

    ref = make_engine(cfg, ecfg, params, tok, use_kernel=False).generate(
        prompts, max_new_tokens=6)
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    eng = make_engine(cfg, ecfg, sharded, tok, tp_mesh=mesh)
    got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
        assert r.finish_reason == g.finish_reason
    eng.allocator.check()
    # the pool really is distributed: each device holds 1/model of kv bytes
    shard_shape = eng.pool.k.sharding.shard_shape(eng.pool.k.shape)
    assert shard_shape[-1] == cfg.kv_dim // 2


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_paged_tp_engine_quantized_pool(cpu_devices, kv_dtype):
    """Paged TP x quantized pool: int8/int4 pages shard on the merged kv
    axis (int4's nibble-packed halved axis included), per-token scale
    pools replicate, greedy tokens match the unsharded quantized engine."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2), devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        page_size=8, num_pages=32,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0, kv_cache_dtype=kv_dtype)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("node notready kubelet stopped", add_bos=True),
               tok.encode("image pull backoff", add_bos=True)]

    ref = make_engine(cfg, ecfg, params, tok, use_kernel=False).generate(
        prompts, max_new_tokens=6)
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    eng = make_engine(cfg, ecfg, sharded, tok, tp_mesh=mesh)
    got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    eng.allocator.check()


@pytest.mark.parametrize("use_kernel", [True, False])
def test_paged_tp_kernel_matches_unsharded(cpu_devices, use_kernel):
    """The paged-attention KERNEL under TP (round-4 review item 3): decode
    runs ops.paged_attention_sharded — the Pallas kernel per head shard
    inside shard_map — and emits exactly the plain paged engine's greedy
    tokens.  Parametrized against the XLA path so a silent fallback
    cannot fake the parity."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2), devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        page_size=8, num_pages=32,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0, decode_chunk=4)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod pending unschedulable", add_bos=True),
               tok.encode("pvc not bound", add_bos=True)]

    ref = make_engine(cfg, ecfg, params, tok, use_kernel=False).generate(
        prompts, max_new_tokens=6)
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    eng = make_engine(cfg, ecfg, sharded, tok, tp_mesh=mesh,
                      use_kernel=use_kernel)
    assert (eng._kernel_mesh is mesh) == use_kernel
    got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
        assert r.finish_reason == g.finish_reason
    eng.allocator.check()


def test_paged_tp_kernel_engine_under_tick_faults_and_the_tracer(cpu_devices):
    """The fault plan and the flight recorder on a multi-chip engine (both
    moved here from the dryrun at PR 50; tests/test_faults.py and
    tests/test_obs.py hold them on one device): an oom page steal, a forced
    preemption and a host stall fire against the paged TP engine that
    decodes through the kernel per head shard and leave greedy output and
    page accounting untouched; a VirtualClock-bound tracer over the same
    engine records tick spans and pool gauges, perturbs nothing, and its
    Chrome export validates."""
    from k8s_llm_rca_tpu.config import EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.faults import Fault, FaultPlan, inject
    from k8s_llm_rca_tpu.faults.plan import VirtualClock
    from k8s_llm_rca_tpu.obs import (
        Tracer, chrome_trace, trace, validate_chrome_trace,
    )
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=4, model=2), devices=cpu_devices)
    params = llama.init_params(cfg, jax.random.PRNGKey(12))
    ecfg = EngineConfig(max_batch=4, max_seq_len=64, page_size=8,
                        num_pages=32, prefill_buckets=(16, 32),
                        max_new_tokens=4, decode_chunk=4)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod crashloop kube-system", add_bos=True)]
    with jax.default_matmul_precision("float32"):
        ref = make_engine(cfg, ecfg, params, tok, use_kernel=False).generate(
            prompts, max_new_tokens=4)[0].token_ids
        eng = make_engine(
            cfg, ecfg, shard_pytree(params, llama_param_specs(cfg), mesh),
            tok, tp_mesh=mesh, use_kernel=True)
        assert eng._kernel_mesh is mesh

        plan = FaultPlan([Fault(inject.SITE_ENGINE_TICK, 1, "oom"),
                          Fault(inject.SITE_ENGINE_TICK, 2, "preempt"),
                          Fault(inject.SITE_ENGINE_TICK, 3, "stall",
                                delay_s=0.01)])
        with inject.armed(plan):
            # a longer budget so the schedule spans several ticks; the
            # greedy prefix must match the unfaulted 4-token run
            chaos = eng.generate(prompts, max_new_tokens=12)
        assert chaos[0].token_ids[:len(ref)] == ref
        assert plan.fired, "no scheduled tick fault fired"
        eng.allocator.check()

        tracer = Tracer(clock=VirtualClock())
        with trace.tracing(tracer):
            traced = eng.generate(prompts, max_new_tokens=4)
    assert traced[0].token_ids == ref
    assert tracer.timeline.total > 0, "no engine tick sampled"
    assert {"engine.tick", "engine.prefill",
            "engine.decode_step"} <= tracer.emitted_names()
    assert tracer.timeline.samples()[-1].free_pages == eng.allocator.n_free
    assert validate_chrome_trace(chrome_trace(tracer)) > 0


def test_paged_tp_kernel_int8_pool_matches_unsharded(cpu_devices):
    """TP x int8 pool x kernel: paged_attention_quant_sharded (per-shard
    quantized kernel, replicated full-row scales) matches the unsharded
    quantized engine's greedy tokens."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2), devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        page_size=8, num_pages=32,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0, kv_cache_dtype="int8",
                        decode_chunk=4)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("node notready kubelet stopped", add_bos=True),
               tok.encode("image pull backoff", add_bos=True)]

    ref = make_engine(cfg, ecfg, params, tok, use_kernel=False).generate(
        prompts, max_new_tokens=6)
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    eng = make_engine(cfg, ecfg, sharded, tok, tp_mesh=mesh,
                      use_kernel=True)
    assert eng._kernel_mesh is mesh
    got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    eng.allocator.check()


def test_paged_tp_rejects_kernel_unsupported_configs(cpu_devices):
    """The sharded kernel's remaining exclusions stay loud: packed-int4
    pools (split-half packing vs head shard), indivisible kv heads, and
    CP seq-sharded pools all reject use_kernel=True."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2), devices=cpu_devices[:4])
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        page_size=8, num_pages=32, prefill_buckets=(16,),
                        kv_cache_dtype="int4")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="int4"):
        PagedInferenceEngine(cfg, ecfg, params, get_tokenizer(),
                             use_kernel=True, tp_mesh=mesh)
    # indivisible kv heads: 2 kv heads cannot split over model=4
    mesh4 = build_mesh(MeshConfig(data=2, model=4),
                       devices=cpu_devices[:8])
    ecfg8 = EngineConfig(max_batch=2, max_seq_len=64,
                         page_size=8, num_pages=32, prefill_buckets=(16,))
    with pytest.raises(ValueError, match="divisible"):
        PagedInferenceEngine(cfg, ecfg8, params, get_tokenizer(),
                             use_kernel=True, tp_mesh=mesh4)
    # CP seq-sharded pool: pages are distributed across the seq axis,
    # which the per-head-shard kernel cannot express — even with
    # unsharded (host) params the mesh alone must reject the kernel
    seq_mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
    ecfg_cp = EngineConfig(max_batch=2, max_seq_len=64,
                           page_size=8, num_pages=32,
                           prefill_buckets=(16,), prefix_cache=False)
    with pytest.raises(ValueError, match="cp_mesh"):
        PagedInferenceEngine(cfg, ecfg_cp, params, get_tokenizer(),
                             use_kernel=True, cp_mesh=seq_mesh)
