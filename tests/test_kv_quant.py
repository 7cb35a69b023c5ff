"""int8/int4 KV cache: numerics and engine mechanics."""

import jax
import jax.numpy as jnp
import numpy as np

from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer


def _decode_chain(cfg, params, cache, prompt, n_steps):
    toks = jnp.asarray([prompt], jnp.int32)
    cache, logits = llama.prefill(cfg, params, cache, toks,
                                  jnp.int32(len(prompt)), jnp.int32(0))
    all_logits = [np.asarray(logits[0])]
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    cur = jnp.asarray([int(np.argmax(all_logits[-1]))], jnp.int32)
    for _ in range(n_steps):
        cache, lg = llama.decode_step(cfg, params, cache, cur, lengths)
        all_logits.append(np.asarray(lg[0]))
        lengths = lengths + 1
        cur = jnp.asarray([int(np.argmax(all_logits[-1]))], jnp.int32)
    return np.stack(all_logits)


def test_int8_cache_close_to_full_precision():
    cfg = TINY.replace(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = list(range(5, 25))
    full = _decode_chain(cfg, params,
                         llama.init_cache(cfg, 1, 64), prompt, 6)
    q = _decode_chain(cfg, params,
                      llama.init_cache(cfg, 1, 64, kv_dtype=jnp.int8),
                      prompt, 6)
    assert np.isfinite(q).all()
    corr = np.corrcoef(full.ravel(), q.ravel())[0, 1]
    assert corr > 0.99, corr


def test_int8_cache_shapes_and_flag():
    cfg = TINY
    c = llama.init_cache(cfg, 2, 32, kv_dtype=jnp.int8)
    assert c.quantized and c.k.dtype == jnp.int8
    assert c.k_scale.shape == (cfg.n_layers, 2, 32)
    assert not llama.init_cache(cfg, 2, 32).quantized


def test_engine_with_int8_kv_cache():
    cfg = TINY.replace(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = PagedInferenceEngine(
        cfg, EngineConfig(max_batch=2, max_seq_len=64,
                          prefill_buckets=(16, 32, 64), max_new_tokens=6,
                          temperature=0.0, kv_cache_dtype="int8"),
        params, tok)
    res = eng.generate([tok.encode("pod oom killed", add_bos=True),
                        tok.encode("pvc pending", add_bos=True)],
                       max_new_tokens=6)
    assert all(r.completion_tokens == 6 for r in res)
    assert eng.pool.quantized


def test_int4_cache_correlates_with_full_precision():
    cfg = TINY.replace(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = list(range(5, 25))
    full = _decode_chain(cfg, params,
                         llama.init_cache(cfg, 1, 64), prompt, 6)
    q = _decode_chain(cfg, params,
                      llama.init_cache(cfg, 1, 64, kv_dtype="int4"),
                      prompt, 6)
    assert np.isfinite(q).all()
    # 4-bit KV with per-token scalar scales: noisier than int8 but the
    # logit structure must survive
    corr = np.corrcoef(full.ravel(), q.ravel())[0, 1]
    assert corr > 0.95, corr


def test_int4_cache_shapes_and_flag():
    cfg = TINY
    c = llama.init_cache(cfg, 2, 32, kv_dtype="int4")
    assert c.quantized and c.k.dtype == jnp.int8
    assert c.k.shape == (cfg.n_layers, 2, 32, cfg.kv_dim // 2)  # packed
    assert c.k_scale.shape == (cfg.n_layers, 2, 32)
    assert llama._kv_packed(cfg, c)
    assert not llama._kv_packed(cfg, llama.init_cache(cfg, 2, 32,
                                                      kv_dtype=jnp.int8))


def test_engine_with_int4_kv_cache():
    cfg = TINY.replace(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = PagedInferenceEngine(
        cfg, EngineConfig(max_batch=2, max_seq_len=64,
                          prefill_buckets=(16, 32, 64), max_new_tokens=6,
                          temperature=0.0, kv_cache_dtype="int4"),
        params, tok)
    res = eng.generate([tok.encode("pod oom killed", add_bos=True),
                        tok.encode("pvc pending", add_bos=True)],
                       max_new_tokens=6)
    assert all(r.completion_tokens == 6 for r in res)
    assert eng.pool.quantized and eng.pool.k.shape[-1] == cfg.kv_dim // 2


def test_int4_cache_speculative_tick_runs():
    cfg = TINY.replace(max_seq_len=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = PagedInferenceEngine(
        cfg, EngineConfig(max_batch=1, max_seq_len=128,
                          prefill_buckets=(32, 64, 128), max_new_tokens=12,
                          temperature=0.0, kv_cache_dtype="int4",
                          speculative_k=4),
        params, tok)
    r = eng.generate([tok.encode("aaaa bbbb aaaa bbbb", add_bos=True)],
                     max_new_tokens=12)[0]
    assert r.completion_tokens == 12


def test_int8_cache_speculative_tick_runs():
    # decode_multi path with a quantized cache
    cfg = TINY.replace(max_seq_len=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = PagedInferenceEngine(
        cfg, EngineConfig(max_batch=1, max_seq_len=128,
                          prefill_buckets=(32, 64, 128), max_new_tokens=12,
                          temperature=0.0, kv_cache_dtype="int8",
                          speculative_k=4),
        params, tok)
    r = eng.generate([tok.encode("aaaa bbbb aaaa bbbb", add_bos=True)],
                     max_new_tokens=12)[0]
    assert r.completion_tokens == 12
