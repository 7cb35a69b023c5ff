"""The token-grouped expert matmul of ``llama._moe_mlp`` (PR 30).

A large call (prefill, training) sorts its (token, expert) pairs by expert
and runs gate, up and down as grouped matmuls (``jax.lax.ragged_dot``) on
the routed rows only; a small call (decode) keeps the dense soft dispatch
that runs every expert on every token.  Both are one function: held here
to each other for plain, int8 and int4 expert weights under the routings
that break a grouped matmul first (empty groups, a row count no tile
divides, tied logits), for the gradient ``engine/train.py`` takes, and for
the one place that chooses (``llama.moe_grouped``).  On the CPU: a count
or a correctness check, never a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_rca_tpu.config import (
    MIXTRAL_8X7B, TINY, TINY_MOE, EngineConfig,
)
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.models.quant import quantize_params
from k8s_llm_rca_tpu.utils import get_tokenizer

E = 8
CFG = TINY_MOE.replace(n_experts=E, n_experts_per_tok=2, dtype="bfloat16")


def _layer(cfg, bits):
    """One MoE layer whose router reads the logits off the first E
    features of a row, so a test states each token's routing in ``x``."""
    layer = llama.init_params(cfg, jax.random.PRNGKey(7))["layers"][0]
    layer = dict(layer, router=jnp.eye(cfg.hidden_size, cfg.n_experts,
                                       dtype=jnp.dtype(cfg.dtype)))
    if bits:
        layer = quantize_params(layer, compute_dtype=jnp.dtype(cfg.dtype),
                                bits=bits)
    return layer


def _tokens(cfg, n_tokens, routing):
    """``[1, n_tokens, H]`` rows whose first E features are the router's
    logits under ``routing``."""
    x = jax.random.normal(jax.random.PRNGKey(n_tokens),
                          (n_tokens, cfg.hidden_size), jnp.float32)
    t = np.arange(n_tokens)
    logits = np.zeros((n_tokens, cfg.n_experts), np.float32)
    if routing == "one_expert":        # k = 1: seven groups are empty
        logits[:, 5] = 8.0
    elif routing == "even":            # token t -> experts t, t + 1 (mod E)
        logits[t, t % cfg.n_experts] = 8.0
        logits[t, (t + 1) % cfg.n_experts] = 7.0
    elif routing == "tied":            # every logit equal: top_k's own order
        pass
    else:
        assert routing == "random"
        logits = np.asarray(jax.random.normal(
            jax.random.PRNGKey(3), logits.shape)) * 4.0
    x = x.at[:, :cfg.n_experts].set(jnp.asarray(logits))
    return x.astype(jnp.dtype(cfg.dtype))[None]


def _both_forms(monkeypatch, cfg, layer, x):
    """``_moe_mlp`` with the threshold below and above the call's rows."""
    out = {}
    for name, rows in (("grouped", 0), ("dense", 1 << 30)):
        monkeypatch.setattr(llama, "MOE_GROUPED_MIN_ROWS_PER_EXPERT", rows)
        assert llama.moe_grouped(cfg, x.shape[0] * x.shape[1]) == (
            name == "grouped")
        out[name] = jax.jit(llama._moe_mlp, static_argnums=0)(cfg, layer, x)
    return out["grouped"].astype(jnp.float32), out["dense"].astype(
        jnp.float32)


ROUTINGS = [pytest.param("one_expert", 64, id="one-expert-seven-empty"),
            pytest.param("even", 64, id="even-spread"),
            pytest.param("random", 131, id="rows-no-tile-divides"),
            pytest.param("tied", 48, id="tied-logits")]


@pytest.mark.parametrize("bits", [0, 8, 4], ids=["bf16", "int8", "int4"])
@pytest.mark.parametrize("routing,n_tokens", ROUTINGS)
def test_grouped_equals_dense_within_bf16_rounding(monkeypatch, routing,
                                                   n_tokens, bits):
    cfg = CFG.replace(n_experts_per_tok=1) if routing == "one_expert" else CFG
    layer = _layer(cfg, bits)
    grouped, dense = _both_forms(monkeypatch, cfg, layer,
                                 _tokens(cfg, n_tokens, routing))
    assert float(jnp.max(jnp.abs(dense))) > 0
    # the two forms round the same bf16 products in another order
    np.testing.assert_allclose(grouped, dense, rtol=2e-2,
                               atol=2e-2 * float(jnp.max(jnp.abs(dense))))


@pytest.mark.parametrize("routing,n_tokens", ROUTINGS)
def test_grouped_equals_dense_in_float32(monkeypatch, routing, n_tokens):
    """The same mathematics, not a near one: in float32 the two forms
    agree to accumulation order."""
    cfg = CFG.replace(dtype="float32")
    if routing == "one_expert":
        cfg = cfg.replace(n_experts_per_tok=1)
    grouped, dense = _both_forms(monkeypatch, cfg, _layer(cfg, 0),
                                 _tokens(cfg, n_tokens, routing))
    np.testing.assert_allclose(grouped, dense, rtol=1e-4, atol=1e-5)


def test_every_pair_is_computed_whatever_the_spread(monkeypatch):
    """Lossless: no capacity, no dropped pair.  With every token on one
    expert the dense form's answer is that expert's MLP of every row."""
    cfg = CFG.replace(dtype="float32", n_experts_per_tok=1)
    layer = _layer(cfg, 0)
    x = _tokens(cfg, 96, "one_expert")
    grouped, _ = _both_forms(monkeypatch, cfg, layer, x)
    gate = jax.nn.silu(x[0] @ layer["w_gate"][5])
    alone = (gate * (x[0] @ layer["w_up"][5])) @ layer["w_down"][5]
    np.testing.assert_allclose(grouped[0], alone, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("routing", ["even", "random", "tied"])
def test_gradient_matches_the_dense_forms(monkeypatch, routing):
    """``engine/train.py`` differentiates the same forward: the gradient
    through the grouped path, to the rows and to every expert's weights,
    is the dense path's."""
    cfg = CFG.replace(dtype="float32")
    layer = _layer(cfg, 0)
    x = _tokens(cfg, 72, routing)

    def loss(layer, x):
        return jnp.sum(jnp.square(llama._moe_mlp(cfg, layer, x)))

    grads = {}
    for name, rows in (("grouped", 0), ("dense", 1 << 30)):
        monkeypatch.setattr(llama, "MOE_GROUPED_MIN_ROWS_PER_EXPERT", rows)
        grads[name] = jax.jit(jax.grad(loss, argnums=(0, 1)))(layer, x)
    flat_g, tree_g = jax.tree.flatten(grads["grouped"])
    flat_d, tree_d = jax.tree.flatten(grads["dense"])
    assert tree_g == tree_d
    for g, d in zip(flat_g, flat_d):
        np.testing.assert_allclose(g, d, rtol=1e-3,
                                   atol=1e-4 * float(jnp.max(jnp.abs(d))))
    # an expert no token chose gets no gradient, from either form
    if routing == "tied":
        assert not np.any(np.asarray(grads["grouped"][0]["w_down"][2:]))


@pytest.mark.parametrize("n_tokens,grouped", [
    pytest.param(32, False, id="decode-call-32-slots"),
    pytest.param(512, False, id="one-row-of-512"),
    pytest.param(1024, False, id="1024-dense-measured-faster"),
    pytest.param(1536, True, id="1536-grouped-measured-faster"),
    pytest.param(2048, True, id="prefill-bucket-2048"),
    pytest.param(4 * 4096, True, id="prefill-4x4096"),
])
def test_moe_grouped_is_chosen_from_rows_per_expert(n_tokens, grouped):
    """The chip's verdict (the table beside the constant): a top-2-of-8
    router's dense form is faster up to 256 rows an expert, the grouped
    form from 384."""
    assert llama.moe_grouped(MIXTRAL_8X7B, n_tokens) is grouped


def test_moe_grouped_follows_the_router_not_the_model():
    """Rows an expert, not tokens: the same 2048 positions spread over 64
    experts, 2 a token, are 64 rows each."""
    assert llama.moe_grouped(MIXTRAL_8X7B.replace(n_experts=64), 2048) is False
    assert llama.moe_grouped(
        MIXTRAL_8X7B.replace(n_experts=64, n_experts_per_tok=16), 2048) is True


@pytest.mark.parametrize("cfg", [
    pytest.param(TINY, id="dense-model"),
    pytest.param(MIXTRAL_8X7B.replace(fused_quant_matmul=True),
                 id="fused-quant-matmul-keeps-its-kernels"),
])
def test_moe_grouped_never_for(cfg):
    assert not llama.moe_grouped(cfg, 4 * 4096)


def test_dense_models_mlp_is_untouched(monkeypatch):
    """``n_experts == 0`` never enters the expert code, at any size."""
    def refuse(*a, **k):
        raise AssertionError("a dense model entered the expert MLP")

    monkeypatch.setattr(llama, "_moe_mlp", refuse)
    monkeypatch.setattr(llama, "_moe_experts_grouped", refuse)
    cfg = TINY
    layer = llama.init_params(cfg, jax.random.PRNGKey(0))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 512, cfg.hidden_size))
    want = (jax.nn.silu(x @ layer["w_gate"]) * (x @ layer["w_up"])
            ) @ layer["w_down"]
    np.testing.assert_allclose(llama._mlp(cfg, layer, x), want,
                               rtol=1e-5, atol=1e-6)


def test_decode_program_keeps_the_dense_form():
    """Below the threshold the traced program is today's: no sort and no
    ragged dot reach a decode call's jaxpr; a prefill bucket's has both."""
    cfg = MIXTRAL_8X7B.replace(hidden_size=64, intermediate_size=128,
                               dtype="float32")
    layer = _layer(cfg, 0)

    def traced(n_tokens):
        x = jnp.zeros((1, n_tokens, cfg.hidden_size))
        return str(jax.make_jaxpr(
            lambda layer, x: llama._moe_mlp(cfg, layer, x))(layer, x))

    decode, prefill = traced(32), traced(2048)
    assert "sort[" not in decode and "ragged_dot_general" not in decode
    assert "sort[" in prefill and prefill.count("ragged_dot_general") == 3


class TestEngineCountsGroupedPrefill:
    """``engine.moe_grouped_tokens`` beside
    ``engine.prefill_padded_tokens``: the share of the prefill that was
    routed."""

    def _run(self, monkeypatch, cfg, bucket):
        # 64 rows an expert, so that a tiny bucket sits on either side
        monkeypatch.setattr(llama, "MOE_GROUPED_MIN_ROWS_PER_EXPERT", 64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        eng = make_engine(
            cfg, EngineConfig(max_batch=2, max_seq_len=bucket + 16,
                              prefill_buckets=(bucket,), max_new_tokens=2,
                              page_size=16, num_pages=2 * (bucket // 16 + 2),
                              temperature=0.0, prefix_cache=False),
            params, tok, use_kernel=False)
        eng.generate([tok.encode("pod oom killed", add_bos=True)],
                     max_new_tokens=2)
        return eng._counts

    @pytest.mark.parametrize("bucket,share", [(128, 1.0), (64, 0.0)],
                             ids=["bucket-128-grouped", "bucket-64-dense"])
    def test_moe_model(self, monkeypatch, bucket, share):
        # 4 experts, 2 a token: 128 positions are 64 rows an expert
        counts = self._run(monkeypatch, TINY_MOE.replace(max_seq_len=256),
                           bucket)
        assert counts["engine.prefill_padded_tokens"] == bucket
        assert counts.get("engine.moe_grouped_tokens", 0.0) == share * bucket

    def test_dense_model_counts_nothing(self, monkeypatch):
        counts = self._run(monkeypatch, TINY.replace(max_seq_len=256), 128)
        assert counts["engine.prefill_padded_tokens"] == 128
        assert "engine.moe_grouped_tokens" not in counts
