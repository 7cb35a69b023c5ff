"""Parallelism-module tests on the virtual 8-device CPU mesh: every sharded
path must match its single-device reference implementation exactly
(tolerance = fp32 accumulation noise)."""

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


def test_cp_tp_requires_one_composed_mesh(cpu_devices):
    """CP×TP composes only on ONE mesh carrying both axes: two distinct
    mesh objects (which would each claim the cache layout) are rejected,
    as is a composed mesh whose head counts don't split over 'model'."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh_a = build_mesh(MeshConfig(data=1, model=2, seq=2),
                        devices=cpu_devices[:4])
    mesh_b = build_mesh(MeshConfig(data=1, model=2, seq=2),
                        devices=cpu_devices[4:8])
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, prefill_buckets=(16,))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="SAME composed mesh"):
        PagedInferenceEngine(cfg, ecfg, params, get_tokenizer(),
                             cp_mesh=mesh_a, tp_mesh=mesh_b)
    with pytest.raises(ValueError, match="not divisible by model"):
        # n_kv_heads=2 cannot split over model=4
        mesh4 = build_mesh(MeshConfig(data=1, model=4, seq=2),
                           devices=cpu_devices[:8])
        PagedInferenceEngine(cfg, ecfg, params, get_tokenizer(),
                             cp_mesh=mesh4, tp_mesh=mesh4)


@pytest.mark.parametrize("cp_mode,kv_dtype", [
    ("ring", None), ("ulysses", None), ("ring", "int8"), ("ulysses", "int8")])
def test_cp_tp_composed_engine_matches_plain(cpu_devices, cp_mode,
                                             kv_dtype):
    """CP×TP in ONE mesh (SURVEY §7 hard part 6 — the long-context 8B
    shape: TP heads within a node, sequence ring across): the TP-aware
    ring/Ulysses prefill runs per head shard and scatters into the
    seq×model sharded page pool (page axis over 'seq', merged kv over
    'model'; an int8 pool shards its per-token scales the same way);
    decode composes via GSPMD — exact greedy parity with the plain
    engine."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2, seq=2),
                      devices=cpu_devices[:8])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32), max_new_tokens=6,
                        page_size=16, num_pages=32, prefix_cache=False,
                        kv_cache_dtype=kv_dtype, decode_chunk=1)
    prompts = [tok.encode("pod crashloop kube-system", add_bos=True),
               tok.encode("node disk pressure taint", add_bos=True)]

    with jax.default_matmul_precision("float32"):
        ref = PagedInferenceEngine(cfg, ecfg, params, tok).generate(
            prompts, max_new_tokens=6)
        eng = PagedInferenceEngine(cfg, ecfg, sharded, tok, cp_mesh=mesh,
                                   tp_mesh=mesh, cp_mode=cp_mode)
        got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    eng.allocator.check()
    # the pool is sharded on BOTH axes: pages over 'seq', kv over 'model'
    shard = eng.pool.k.sharding.shard_shape(eng.pool.k.shape)
    assert shard[1] == ecfg.num_pages // 2
    assert shard[3] == cfg.kv_dim // 2


def test_cp_paged_seq_sharded_pool(cpu_devices):
    """CP seq-sharded paged pool (page-aligned CP splits): each CP device
    owns the page RANGE covering its sequence shard, so the paged engine
    stores 1/P of a long context's KV per device.  Greedy parity with the
    plain engine through decode that GROWS across the partition boundary, plus
    pool-bytes-per-device and allocator-partition assertions."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import (
        PagedInferenceEngine, PartitionedPageAllocator, TRASH_PAGE,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=32)
    mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    # pages_per_seq = 4, partition boundary at page idx 2 (position 16):
    # a 12-token prompt + 12 new tokens crosses into partition 1 mid-decode
    ecfg = EngineConfig(max_batch=2, max_seq_len=32, page_size=8,
                        num_pages=16, prefill_buckets=(16,),
                        max_new_tokens=12, temperature=0.0,
                        prefix_cache=False, decode_chunk=1)
    prompts = [tok.encode("0123456789a", add_bos=True),   # 12 tokens
               tok.encode("pvc not bnd", add_bos=True)]
    assert all(len(p) == 12 for p in prompts)

    with jax.default_matmul_precision("float32"):
        ref = PagedInferenceEngine(cfg, ecfg, params, tok).generate(
            prompts, max_new_tokens=12)
        eng = PagedInferenceEngine(cfg, ecfg, params, tok, cp_mesh=mesh)
        # partition-aware allocation is active
        assert isinstance(eng.allocator, PartitionedPageAllocator)
        got = eng.generate(prompts, max_new_tokens=12)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
        # every sequence decoded past position 16 (the partition boundary)
        assert r.prompt_tokens + r.completion_tokens > 16
    eng.allocator.check()
    assert eng.allocator.n_free == 15              # nothing leaked

    # 1/P pool bytes per device: page axis sharded over 'seq'
    shard = eng.pool.k.sharding.shard_shape(eng.pool.k.shape)
    assert shard[1] == ecfg.num_pages // 2

    # partition alignment invariant: after a fresh admission, the page
    # covering positions [16, 24) must come from partition 1's id range
    seq = eng.submit(tok.encode("0123456789a", add_bos=True),
                     max_new_tokens=12)
    for _ in range(40):
        if not eng.has_work:
            break
        eng.step()
        for slot, st in eng._active.items():
            table = eng.block_tables[slot]
            for j in range(eng.pages_per_seq):
                if table[j] != TRASH_PAGE:
                    assert eng.allocator.part_of(int(table[j])) == \
                        eng._page_part(j), (j, int(table[j]))
    eng.allocator.check()


@pytest.mark.parametrize("page_size", [8, 16])
def test_cp_speculative_matches_plain(cpu_devices, page_size):
    """Speculation composes with CP: the multi-token verify step runs
    over the seq-sharded page pool through GSPMD, with exact greedy
    parity against the non-speculative non-CP engine."""
    import dataclasses

    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=32)
    mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=32, prefill_buckets=(16,),
                        max_new_tokens=10, temperature=0.0,
                        page_size=page_size, num_pages=128 // page_size,
                        prefix_cache=False)
    prompts = [tok.encode("the pod the pod", add_bos=True),
               tok.encode("pvc bound pvc", add_bos=True)]
    with jax.default_matmul_precision("float32"):
        ref = make_engine(cfg, ecfg, params, tok,
                          use_kernel=False).generate(
            [list(p) for p in prompts], max_new_tokens=10)
        spec = make_engine(cfg, dataclasses.replace(ecfg, speculative_k=3),
                           params, tok, cp_mesh=mesh, use_kernel=False)
        got = spec.generate([list(p) for p in prompts], max_new_tokens=10)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    spec.allocator.check()


def test_cp_paged_partition_exhaustion_preempts_not_crashes(cpu_devices):
    """CP seq-sharded pool under PARTITION pressure: when the partition a
    growing slot needs is exhausted, evicting the youngest slot may free
    pages only in OTHER partitions — step() must keep evicting (and
    finally preempt the growing slot itself) instead of crashing on the
    unsatisfied retry (regression: the single-retry grow assumed any
    freed page could satisfy alloc, true only for the unpartitioned
    pool)."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.logging import METRICS
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=32)
    mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=32, page_size=8,
                        num_pages=16, prefill_buckets=(16,),
                        max_new_tokens=12, temperature=0.0,
                        prefix_cache=False, decode_chunk=1)
    eng = PagedInferenceEngine(cfg, ecfg, params, tok, cp_mesh=mesh)
    # exhaust partition 1 (pages 8..15) so crossing position 16 cannot grow
    stolen = eng.allocator.alloc(8, owner=999, part=1)
    prompts = [tok.encode("0123456789a", add_bos=True) for _ in range(2)]
    assert all(len(p) == 12 for p in prompts)
    for p in prompts:
        eng.submit(p, max_new_tokens=12)
    before = METRICS.count("engine.preemptions")
    for _ in range(12):                      # churns, must not raise
        if eng.has_work:
            eng.step()
    assert METRICS.count("engine.preemptions") > before
    eng.allocator.check()
    # free the hostage partition: the sweep completes normally
    eng.allocator.free(stolen, owner=999)
    results = eng.run_to_completion()
    assert len(results) == 2
    eng.allocator.check()
    assert eng.allocator.n_free == 15


def test_ep_tp_dp_composed_engine_matches_dense(cpu_devices):
    """EP x TP x DP in ONE mesh (the v5e-16 Mixtral shape: experts across
    nodes, tensor-parallel heads within, batch replicas on top): the
    stacked expert weights shard over 'expert' AND their hidden dims over
    'model' (llama_param_specs composes both in one spec), the MoE MLPs
    dispatch all-to-all, and greedy output matches the dense single-device
    engine exactly."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(max_seq_len=64, n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=4, max_seq_len=64,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    prompts = [tok.encode("pod pending", add_bos=True),
               tok.encode("pvc not bound", add_bos=True),
               tok.encode("secret missing", add_bos=True)]
    ref = make_engine(cfg, ecfg, params, tok).generate(
        prompts, max_new_tokens=6)

    mesh = build_mesh(MeshConfig(data=2, expert=2, model=2),
                      devices=cpu_devices[:8])
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    eng = make_engine(cfg, ecfg, sharded, tok, ep_mesh=mesh)
    got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids


def test_sp_forward_matches_and_shards_sequence(cpu_devices):
    """Megatron-style SP (SURVEY §2.2 SP row): under TP, constraining the
    residual stream's sequence dim over 'model' must not change the
    function, and the lowered module must actually carry the sequence
    sharding constraints (XLA then chooses reduce-scatter/all-gather or
    all-reduce+slice per its cost model — on TPU the former)."""
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=1, model=4),
                      devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("float32"):
        ref = llama.forward(cfg, params, tokens)
        fn = jax.jit(lambda p, t: llama.forward(cfg, p, t, sp_mesh=mesh))
        got = fn(sharded, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)
        lowered = fn.lower(sharded, tokens).as_text()
    # two constraints per layer on the [B, S, H] residual stream: the
    # seq (middle) dim sharded over the model axis (shardy dialect:
    # `sdy.sharding_constraint ... [{}, {"model"}, {}]`; pre-shardy:
    # `custom_call @Sharding`)
    n_sp = (lowered.count('sdy.sharding_constraint')
            + lowered.count('custom_call @Sharding'))
    assert n_sp >= 2 * cfg.n_layers, \
        f"expected >= {2 * cfg.n_layers} SP sharding constraints, " \
        f"found {n_sp}"
    assert ('[{}, {"model"}, {}]' in lowered
            or "Sharding" in lowered), \
        "no seq-over-model sharding annotation in the lowered module"


def test_sp_engine_matches_unsharded(cpu_devices):
    """sp=True: TP prefill with sequence-parallel activations emits the
    plain engine's greedy tokens."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2),
                      devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod crashloop kube-system", add_bos=True),
               tok.encode("node disk pressure taint", add_bos=True)]
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32), max_new_tokens=6,
                        temperature=0.0, page_size=16, num_pages=32,
                        prefix_cache=False, decode_chunk=1)
    with jax.default_matmul_precision("float32"):
        ref = make_engine(cfg, ecfg, params, tok,
                          use_kernel=False).generate(
            prompts, max_new_tokens=6)
        got = make_engine(cfg, ecfg, sharded, tok, tp_mesh=mesh,
                          sp=True, use_kernel=False).generate(
            prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids


def test_sp_requires_tp(cpu_devices):
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    with pytest.raises(ValueError, match="requires tp_mesh"):
        PagedInferenceEngine(
            cfg, EngineConfig(max_batch=2, max_seq_len=64,
                              prefill_buckets=(16,)),
            llama.init_params(cfg, jax.random.PRNGKey(0)),
            get_tokenizer(vocab_size=cfg.vocab_size), sp=True)


@pytest.mark.parametrize("cp_mode,page_size", [
    ("ring", 8), ("ulysses", 8), ("ring", 16)])
def test_cp_ep_composed_engine_matches_dense(cpu_devices, cp_mode,
                                             page_size):
    """CP×EP in ONE mesh (long-context MoE serving: experts across the
    expert axis, sequence ring over 'seq'): CP prefill shards MoE tokens
    over (seq, expert) — the sequence never moves, dispatch rides the
    expert all-to-all — and writes through the page-scatter path; decode
    tokens shard over (data, expert) against the seq-sharded pool.
    Exact greedy parity vs the dense engine."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.models import mixtral
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(max_seq_len=64, n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, page_size=page_size,
                        num_pages=256 // page_size,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0, prefix_cache=False,
                        decode_chunk=1)
    prompts = [tok.encode("pod pending unschedulable node", add_bos=True),
               tok.encode("pvc not bound storageclass", add_bos=True)]

    mesh = mixtral.build_ep_mesh(2, n_data=1, n_seq=2,
                                 devices=cpu_devices[:4])
    sharded = mixtral.shard_params_ep(cfg, params, mesh)
    with jax.default_matmul_precision("float32"):
        ref = PagedInferenceEngine(cfg, ecfg, params, tok,
                                   use_kernel=False).generate(
            prompts, max_new_tokens=6)
        eng = PagedInferenceEngine(cfg, ecfg, sharded, tok, cp_mesh=mesh,
                                   ep_mesh=mesh, cp_mode=cp_mode,
                                   use_kernel=False)
        got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    eng.allocator.check()
    # the pool is genuinely page-sharded across the composed mesh
    shard = eng.pool.k.sharding.shard_shape(eng.pool.k.shape)
    assert shard[1] == ecfg.num_pages // 2


def test_cp_ep_requires_one_composed_mesh(cpu_devices):
    """CP×EP composes only on ONE mesh; distinct mesh objects are
    rejected, and prefill buckets must split over seq*expert."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.models import mixtral
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(max_seq_len=64, n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh_a = mixtral.build_ep_mesh(2, n_seq=2, devices=cpu_devices[:4])
    mesh_b = mixtral.build_ep_mesh(2, n_seq=2, devices=cpu_devices[4:8])
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, prefill_buckets=(16,))
    with pytest.raises(ValueError, match="SAME composed mesh"):
        PagedInferenceEngine(cfg, ecfg, params, get_tokenizer(),
                             cp_mesh=mesh_a, ep_mesh=mesh_b)
    with pytest.raises(ValueError, match="prefill token sharding"):
        # 18 splits over seq=2 but not over seq*expert=4
        PagedInferenceEngine(
            cfg, EngineConfig(max_batch=2, max_seq_len=64,
                              prefill_buckets=(18, 64)),
            params, get_tokenizer(), cp_mesh=mesh_a, ep_mesh=mesh_a)


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


@pytest.mark.parametrize("kv_dtype", [None, "int8", "int4"])
def test_pp_tp_paged_prefix_cache_reuse(cpu_devices, kv_dtype):
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


@pytest.mark.parametrize("page_size", [16, 8])
@pytest.mark.parametrize("bits", [8, 4])
def test_pp_tp_quantized_weights_matches_plain(cpu_devices, page_size,
                                               bits):
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
        eng = make_engine(cfg, ecfg, params, tok, pp_mesh=mesh,
                          tp_mesh=mesh, use_kernel=False)
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
