"""Weight-only int8 quantization (models/quant.py): numerics, pytree
mechanics, and end-to-end engine compatibility."""

import jax
import jax.numpy as jnp
import numpy as np

from k8s_llm_rca_tpu.config import TINY, TINY_MOE, EngineConfig
from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.models.quant import (
    QuantTensor, dq, gather_rows, quantize, quantize_params,
)
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer


def test_quantize_roundtrip_error_bound():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    qt = quantize(w, axis=-1, compute_dtype=jnp.float32)
    assert qt.q.dtype == jnp.int8 and qt.scale.shape == (1, 128)
    err = jnp.max(jnp.abs(dq(qt) - w))
    # per-channel symmetric: max error is half a quantization step
    step = jnp.max(jnp.abs(w), axis=0) / 127.0
    assert float(err) <= float(jnp.max(step)) * 0.5 + 1e-6


def test_row_quantized_gather_matches_dense():
    w = jax.random.normal(jax.random.PRNGKey(1), (50, 16), jnp.float32)
    qt = quantize(w, axis=0, compute_dtype=jnp.float32)
    idx = jnp.asarray([[3, 7], [49, 0]])
    np.testing.assert_allclose(np.asarray(gather_rows(qt, idx)),
                               np.asarray(dq(qt)[idx]), rtol=1e-6, atol=1e-6)


def test_dq_passthrough_for_plain_arrays():
    w = jnp.ones((4, 4))
    assert dq(w) is w
    assert gather_rows(w, jnp.asarray([1])).shape == (1, 4)


def test_quantize_params_skips_1d_and_quantizes_weights():
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    qp = quantize_params(params)
    assert isinstance(qp["layers"][0]["wq"], QuantTensor)
    assert isinstance(qp["embedding"], QuantTensor)
    # per-row scales on the embedding (usable as gather AND lm head)
    assert qp["embedding"].scale.shape == (TINY.vocab_size, 1)
    # norm gains stay full precision
    assert not isinstance(qp["layers"][0]["attn_norm"], QuantTensor)
    assert not isinstance(qp["final_norm"], QuantTensor)


def _top1_agreement(a, b):
    return float(jnp.mean((jnp.argmax(a, -1) == jnp.argmax(b, -1))))


def test_forward_close_to_fp_and_top1_mostly_agrees():
    cfg = TINY
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(params, compute_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0,
                                cfg.vocab_size)
    ref = llama.forward(cfg, params, tokens)
    got = llama.forward(cfg, qp, tokens)
    assert np.isfinite(np.asarray(got)).all()
    # int8 noise is real but small; logits correlate and top-1 mostly agrees
    corr = np.corrcoef(np.asarray(ref).ravel(), np.asarray(got).ravel())[0, 1]
    assert corr > 0.99, corr
    assert _top1_agreement(ref, got) > 0.8


def test_moe_forward_quantized_runs():
    cfg = TINY_MOE
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(params, compute_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0,
                                cfg.vocab_size)
    out = llama.forward(cfg, qp, tokens)
    assert np.isfinite(np.asarray(out)).all()


def test_engine_runs_with_quantized_params():
    cfg = TINY.replace(max_seq_len=64)
    params = quantize_params(llama.init_params(cfg, jax.random.PRNGKey(0)))
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = PagedInferenceEngine(cfg, ecfg, params, tok)
    res = eng.generate([tok.encode("pod oom", add_bos=True)],
                       max_new_tokens=6)
    assert res[0].completion_tokens == 6


def test_quantize_params_idempotent():
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    qp = quantize_params(params)
    qp2 = quantize_params(qp)
    w = qp2["layers"][0]["wq"]
    assert isinstance(w, QuantTensor) and not isinstance(w.scale, QuantTensor)
    assert dq(w).shape == (TINY.hidden_size, TINY.q_dim)


def test_gather_rows_rejects_column_scales():
    import pytest

    w = jax.random.normal(jax.random.PRNGKey(4), (10, 8))
    qt = quantize(w, axis=-1)                      # per-column: wrong for gather
    with pytest.raises(AssertionError, match="per-row"):
        gather_rows(qt, jnp.asarray([1, 2]))


def test_paged_engine_runs_with_quantized_params():

    cfg = TINY.replace(max_seq_len=64)
    params = quantize_params(llama.init_params(cfg, jax.random.PRNGKey(0)))
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, page_size=8,
                        num_pages=32, prefill_buckets=(16, 32, 64),
                        max_new_tokens=6, temperature=0.0)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = PagedInferenceEngine(cfg, ecfg, params, tok, use_kernel=False)
    prompt = tok.encode("kubelet failed to mount volume for pod",
                        add_bos=True)
    r1 = eng.generate([prompt], max_new_tokens=6)[0]
    assert r1.completion_tokens == 6
    # second submit exercises the chunked prefill path with quantized params
    r2 = eng.generate([list(prompt)], max_new_tokens=6)[0]
    assert r2.token_ids == r1.token_ids
    eng.allocator.check()


def test_expert_parallel_moe_quantized(monkeypatch):
    # EP dispatch must accept quantized expert weights (dq at the boundary)
    import os
    if jax.default_backend() != "cpu":
        import pytest
        pytest.skip("mesh test runs on the CPU backend")
    from k8s_llm_rca_tpu.config import MeshConfig
    from k8s_llm_rca_tpu.parallel import expert_parallel_moe
    from k8s_llm_rca_tpu.runtime.mesh import build_mesh

    cfg = TINY_MOE
    mesh = build_mesh(MeshConfig(data=2, expert=4), devices=jax.devices()[:8])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    layer = params["layers"][0]
    qlayer = quantize_params(layer, compute_dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.hidden_size))
    out_q = expert_parallel_moe(x, qlayer, mesh, top_k=cfg.n_experts_per_tok,
                                capacity_factor=8.0)
    ref = expert_parallel_moe(x, layer, mesh, top_k=cfg.n_experts_per_tok,
                              capacity_factor=8.0)
    assert np.isfinite(np.asarray(out_q)).all()
    corr = np.corrcoef(np.asarray(out_q).ravel(), np.asarray(ref).ravel())[0, 1]
    assert corr > 0.99


def test_int4_pack_unpack_roundtrip():
    from k8s_llm_rca_tpu.models.quant import _pack_nibbles, _unpack_nibbles

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-8, 8, (6, 32)), jnp.int8)
    packed = _pack_nibbles(q)
    assert packed.shape == (6, 16) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(_unpack_nibbles(packed)),
                                  np.asarray(q))


def test_int4_quantize_roundtrip_error_bound():
    from k8s_llm_rca_tpu.models.quant import QuantTensor4

    w = jax.random.normal(jax.random.PRNGKey(6), (64, 128), jnp.float32)
    qt = quantize(w, axis=-1, compute_dtype=jnp.float32, bits=4)
    assert isinstance(qt, QuantTensor4)
    assert qt.q.shape == (64, 64) and qt.shape == (64, 128)
    assert qt.scale.shape == (1, 128)
    err = jnp.max(jnp.abs(dq(qt) - w))
    # per-channel symmetric at 4 bits: max error is half a step of amax/7
    step = jnp.max(jnp.abs(w), axis=0) / 7.0
    assert float(err) <= float(jnp.max(step)) * 0.5 + 1e-6


def test_int4_rejects_odd_last_dim():
    import pytest

    with pytest.raises(AssertionError, match="even last dim"):
        quantize(jnp.ones((4, 7)), bits=4)


def test_int4_row_quantized_gather_matches_dense():
    w = jax.random.normal(jax.random.PRNGKey(7), (50, 16), jnp.float32)
    qt = quantize(w, axis=0, compute_dtype=jnp.float32, bits=4)
    idx = jnp.asarray([[3, 7], [49, 0]])
    np.testing.assert_allclose(np.asarray(gather_rows(qt, idx)),
                               np.asarray(dq(qt)[idx]), rtol=1e-6, atol=1e-6)


def test_int4_forward_correlates_with_fp():
    cfg = TINY
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(params, compute_dtype=jnp.float32, bits=4)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 24), 0,
                                cfg.vocab_size)
    ref = llama.forward(cfg, params, tokens)
    got = llama.forward(cfg, qp, tokens)
    assert np.isfinite(np.asarray(got)).all()
    corr = np.corrcoef(np.asarray(ref).ravel(), np.asarray(got).ravel())[0, 1]
    # 4-bit noise is substantially larger than 8-bit but structure must hold
    assert corr > 0.9, corr


def test_int4_quantize_params_idempotent_and_moe_scales():
    from k8s_llm_rca_tpu.models.quant import QuantTensor4

    params = llama.init_params(TINY_MOE, jax.random.PRNGKey(0))
    qp = quantize_params(params, bits=4)
    qp2 = quantize_params(qp, bits=4)
    gate = qp2["layers"][0]["w_gate"]
    assert isinstance(gate, QuantTensor4)
    assert gate.scale.shape[0] == TINY_MOE.n_experts   # per-expert scales
    assert gate.q.shape[-1] == TINY_MOE.intermediate_size // 2
    assert not isinstance(qp2["layers"][0]["attn_norm"], QuantTensor4)


def test_quantize_params_rejects_width_change():
    import pytest

    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    qp8 = quantize_params(params, bits=8)
    with pytest.raises(AssertionError, match="already int8"):
        quantize_params(qp8, bits=4)


def test_int4_engine_generates():
    cfg = TINY.replace(max_seq_len=64)
    params = quantize_params(llama.init_params(cfg, jax.random.PRNGKey(0)),
                             bits=4)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = PagedInferenceEngine(cfg, ecfg, params, tok)
    res = eng.generate([tok.encode("pod oom", add_bos=True)],
                       max_new_tokens=6)
    assert res[0].completion_tokens == 6


def test_int4_quantizing_transform_streaming_init():
    from k8s_llm_rca_tpu.models.quant import QuantTensor4, quantizing_transform

    cfg = TINY
    params = llama.init_params(cfg, jax.random.PRNGKey(0),
                               tensor_transform=quantizing_transform(bits=4))
    assert isinstance(params["layers"][0]["wq"], QuantTensor4)
    assert isinstance(params["embedding"], QuantTensor4)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, 8), 0,
                                cfg.vocab_size)
    out = llama.forward(cfg, params, tokens)
    assert np.isfinite(np.asarray(out)).all()


def test_moe_experts_get_per_expert_scales():
    # [E, H, I] expert stacks must not share one scale across experts
    w = jnp.stack([jnp.ones((8, 16)) * 0.01,
                   jnp.ones((8, 16)) * 10.0])      # outlier expert
    qt = quantize(w, axis=(0, -1), compute_dtype=jnp.float32)
    assert qt.scale.shape == (2, 1, 16)
    np.testing.assert_allclose(np.asarray(dq(qt)), np.asarray(w),
                               rtol=1e-2, atol=1e-4)
    # and quantize_params picks that layout for 3-D weights
    params = llama.init_params(TINY_MOE, jax.random.PRNGKey(0))
    qp = quantize_params(params, compute_dtype=jnp.float32)
    gate = qp["layers"][0]["w_gate"]
    assert gate.scale.shape[0] == TINY_MOE.n_experts


def test_repack_nibbles_grouped_shard_local_unpack():
    """The "shard first, pack second" property that makes int4 compose
    with PP×TP: after repacking into G groups, each contiguous 1/G block
    of the PACKED axis is a self-contained split-half buffer whose local
    unpack yields exactly that shard's logical columns (with the
    matching contiguous scale block) — for every group count dividing
    the column pairs."""
    from k8s_llm_rca_tpu.models.quant import (
        _unpack_nibbles, quantize, repack_nibbles_grouped,
    )

    w = jax.random.normal(jax.random.PRNGKey(3), (16, 24), jnp.float32)
    qt = quantize(w, axis=-1, compute_dtype=jnp.float32, bits=4)
    full = np.asarray(dq(qt))                       # global dequant [16, 24]
    for groups in (1, 2, 3, 4, 6):
        rp = repack_nibbles_grouped(qt, groups)
        assert rp.q.shape == qt.q.shape
        packed_w = qt.q.shape[-1] // groups         # packed cols per shard
        logical_w = 24 // groups
        for g in range(groups):
            q_shard = rp.q[:, g * packed_w:(g + 1) * packed_w]
            s_shard = np.asarray(
                qt.scale[:, g * logical_w:(g + 1) * logical_w])
            local = np.asarray(_unpack_nibbles(q_shard)).astype(np.float32)
            np.testing.assert_array_equal(
                local * s_shard,
                full[:, g * logical_w:(g + 1) * logical_w])


def test_repack_nibbles_grouped_rejects_odd_pairs():
    import pytest

    from k8s_llm_rca_tpu.models.quant import quantize, repack_nibbles_grouped

    w = jax.random.normal(jax.random.PRNGKey(4), (4, 10), jnp.float32)
    qt = quantize(w, axis=-1, bits=4)
    with pytest.raises(ValueError, match="divisible"):
        repack_nibbles_grouped(qt, 3)               # 10 % (2*3) != 0
