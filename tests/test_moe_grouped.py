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
from k8s_llm_rca_tpu.models.quant import dq, quantize_params
from k8s_llm_rca_tpu.ops.quant_matmul import quant_swiglu_experts
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


@pytest.mark.parametrize("cfg,grouped", [
    pytest.param(TINY, False, id="dense-model"),
    pytest.param(MIXTRAL_8X7B.replace(fused_quant_matmul=True), True,
                 id="fused-quant-matmul-selects-nothing-here"),
])
def test_moe_grouped_at_a_large_call(cfg, grouped):
    """A dense model never; ``fused_quant_matmul`` has no say in the expert
    layer any more (PR 35: the call's shape chooses all three forms)."""
    assert llama.moe_grouped(cfg, 4 * 4096) is grouped


# ---------------------------------------------------------------------------
# the third form (PR 35): small calls over stacked int4 SwiGLU experts read
# them packed
# ---------------------------------------------------------------------------

NEMOTRON_LIKE = TINY_MOE.replace(
    n_experts=E, n_experts_per_tok=2, mlp_act="relu2", dtype="bfloat16")


# 64 experts, 2 a token: grouped from 12,288 positions, so the fused form's
# own threshold is the one that ends it
MANY_EXPERTS = MIXTRAL_8X7B.replace(n_experts=64)


def _fused_cases():
    top = llama.MOE_FUSED_MAX_POSITIONS
    return [
        pytest.param(MIXTRAL_8X7B, 4, 32, True, id="int4-decode-call-32"),
        pytest.param(MIXTRAL_8X7B, 4, 1024, True,
                     id="int4-the-check-prefill-1024"),
        pytest.param(MIXTRAL_8X7B, 4, 1535, True,
                     id="int4-last-call-under-the-grouped-form"),
        pytest.param(MIXTRAL_8X7B, 4, 1536, False,
                     id="int4-grouped-call-1536"),
        pytest.param(MIXTRAL_8X7B, 4, 4 * 4096, False,
                     id="int4-grouped-call-4x4096"),
        pytest.param(MANY_EXPERTS, 4, top, True, id="int4-at-the-threshold"),
        pytest.param(MANY_EXPERTS, 4, top + 1, False,
                     id="int4-one-position-over"),
        pytest.param(MIXTRAL_8X7B.replace(fused_quant_matmul=True), 4, 32,
                     True, id="int4-whatever-the-flag"),
        pytest.param(MIXTRAL_8X7B, 8, 32, False, id="int8-experts"),
        pytest.param(MIXTRAL_8X7B, 0, 32, False, id="bf16-experts"),
        pytest.param(NEMOTRON_LIKE, 4, 32, False, id="relu2-experts"),
        pytest.param(MIXTRAL_8X7B.replace(moe_latent_size=64), 4, 32, False,
                     id="latent-experts"),
        pytest.param(TINY, 4, 32, False, id="dense-model"),
    ]


@pytest.mark.parametrize("cfg,bits,n_tokens,fused", _fused_cases())
def test_moe_fused_is_chosen_from_storage_and_positions(cfg, bits, n_tokens,
                                                        fused):
    """The rule sees the weights' storage type, the MLP's kind and the
    call's positions, nothing else (no flag, no model's name): widths play
    no part, so toy weights stand under the published configuration."""
    small = (CFG if cfg.n_experts else TINY).replace(mlp_act=cfg.mlp_act)
    layer = llama.init_params(small, jax.random.PRNGKey(7))["layers"][0]
    if bits:
        layer = quantize_params(layer, compute_dtype=jnp.bfloat16, bits=bits)
    assert llama.moe_fused(cfg, layer, n_tokens) is fused


def _interpreted(monkeypatch):
    """Off the TPU the shim keeps the XLA expression; a test that wants the
    kernels runs them in interpret mode, and counts the calls."""
    calls = []

    def shim(x, w_gate, w_up, w_down):
        calls.append(x.shape)
        return quant_swiglu_experts(x, w_gate, w_up, w_down, interpret=True)

    monkeypatch.setattr(llama, "qmm_swiglu_experts", shim)
    return calls


@pytest.mark.parametrize("routing,n_tokens", [
    pytest.param("even", 32, id="even-spread"),
    pytest.param("one_expert", 32, id="one-expert-seven-idle"),
    pytest.param("random", 24, id="random"),
    pytest.param("tied", 16, id="tied-logits"),
])
def test_fused_equals_dense_and_hard_routing_inside_a_scan(monkeypatch,
                                                           routing,
                                                           n_tokens):
    """``_experts`` through the packed kernels (interpret mode, toy widths,
    int4), as the decode scan runs it: every step of a ``lax.scan`` equals
    the XLA dense form within bf16 rounding, and equals hard routing (each
    token through its chosen experts alone, from the dequantized weights)."""
    cfg = CFG.replace(n_experts_per_tok=1) if routing == "one_expert" else CFG
    layer = _layer(cfg, 4)
    x = _tokens(cfg, n_tokens, routing)
    steps = jnp.stack([x, x * 0.5, -x])             # [3, 1, T, H]
    calls = _interpreted(monkeypatch)

    def scanned(expert_kernel):
        def body(carry, x):
            return carry, llama._moe_mlp(cfg, layer, x, expert_kernel)
        return jax.jit(lambda steps: jax.lax.scan(body, 0, steps)[1])(
            steps).astype(jnp.float32)

    fused, dense = scanned(True), scanned(False)
    assert calls == [x.shape]                       # traced once, in the scan
    assert float(jnp.max(jnp.abs(dense))) > 0
    tol = 2e-2 * float(jnp.max(jnp.abs(dense)))
    np.testing.assert_allclose(fused, dense, rtol=2e-2, atol=tol)

    topi, weights = llama._route(cfg, layer, x)
    w = {k: dq(layer[k]).astype(jnp.float32)
         for k in ("w_gate", "w_up", "w_down")}
    rows = x[0].astype(jnp.float32)
    hard = np.zeros((n_tokens, cfg.hidden_size), np.float32)
    for t in range(n_tokens):
        for j in range(cfg.n_experts_per_tok):
            e = int(topi[0, t, j])
            hid = jax.nn.silu(rows[t] @ w["w_gate"][e]) * (
                rows[t] @ w["w_up"][e])
            hard[t] += float(weights[0, t, j]) * np.asarray(
                hid @ w["w_down"][e])
    np.testing.assert_allclose(fused[0, 0], hard, rtol=3e-2,
                               atol=3e-2 * float(np.max(np.abs(hard))))


def test_expert_kernel_off_keeps_the_dense_program(monkeypatch):
    """Training, the reference loops and every sharded path leave
    ``expert_kernel`` False: nothing of the fused form is traced, and the
    program is the dense form's to the jaxpr."""
    cfg, layer = CFG, _layer(CFG, 4)
    x = _tokens(cfg, 32, "even")
    calls = _interpreted(monkeypatch)

    def jaxpr(*a):
        return str(jax.make_jaxpr(
            lambda layer, x: llama._moe_mlp(cfg, layer, x, *a))(layer, x))

    assert jaxpr() == jaxpr(False) and not calls
    assert "pallas_call" not in jaxpr()
    assert "pallas_call" in jaxpr(True) and calls
    # a differentiated call cannot reach the kernels: the loss goes through
    # the default
    jax.grad(lambda x: jnp.sum(llama._moe_mlp(cfg, _layer(cfg, 0), x)
                               .astype(jnp.float32)))(x)
    assert len(calls) == 1


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


def _int4_engine(cfg, bits=4, decode_chunk=4, **mesh_kw):
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if bits:
        params = quantize_params(params, compute_dtype=jnp.float32,
                                 bits=bits)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = make_engine(
        cfg, EngineConfig(max_batch=2, max_seq_len=64, page_size=8,
                          num_pages=32, prefill_buckets=(16, 32),
                          max_new_tokens=6, temperature=0.0,
                          prefix_cache=False, decode_chunk=decode_chunk),
        params, tok, use_kernel=False, **mesh_kw)
    return eng, tok


class TestEngineCountsFusedSteps:
    """``engine.moe_fused_steps`` beside ``engine.decode_steps``: the model
    steps whose expert MLPs read their int4 experts packed, counted on the
    host from the model's own predicate and the engine's word on meshes.
    (On the CPU the shim computes the same form in XLA; the count says
    which form the program asked for.)"""

    def _counts(self, cfg, **kw):
        eng, tok = _int4_engine(cfg.replace(max_seq_len=64), **kw)
        eng.generate([tok.encode("pod oom killed", add_bos=True)],
                     max_new_tokens=6)
        assert eng._counts["engine.decode_steps"] >= 5
        return eng._counts

    @pytest.mark.parametrize("decode_chunk", [1, 4],
                             ids=["stepwise", "scan"])
    def test_int4_sparse_engine_counts_every_step(self, decode_chunk):
        counts = self._counts(TINY_MOE, decode_chunk=decode_chunk)
        assert (counts["engine.moe_fused_steps"]
                == counts["engine.decode_steps"])

    @pytest.mark.parametrize("cfg,bits", [
        pytest.param(TINY, 4, id="dense-int4"),
        pytest.param(TINY_MOE, 8, id="sparse-int8"),
        pytest.param(TINY_MOE, 0, id="sparse-plain"),
    ])
    def test_absent_for(self, cfg, bits):
        assert "engine.moe_fused_steps" not in self._counts(cfg, bits=bits)

    def test_over_the_threshold_counts_nothing(self, monkeypatch):
        monkeypatch.setattr(llama, "MOE_FUSED_MAX_POSITIONS", 1)
        assert "engine.moe_fused_steps" not in self._counts(TINY_MOE)


class TestNoExpertKernelUnderAMesh:
    """``pallas_call`` has no partitioning rule: an engine with any mesh,
    or with weights spread over devices, keeps the kernels out of every
    program it binds, whatever the weights' type."""

    def test_one_device_lets_them_in(self):
        eng, _ = _int4_engine(TINY_MOE.replace(max_seq_len=64))
        assert eng._expert_kernel is True
        assert eng._decode_scan.__wrapped__.keywords["expert_kernel"] is True

    @pytest.mark.parametrize("kind", ["tp", "ep", "cp", "pp", "fsdp",
                                      "sharded-weights-no-mesh"])
    def test_never_with(self, cpu_devices, kind):
        from k8s_llm_rca_tpu.config import MeshConfig
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh
        from k8s_llm_rca_tpu.runtime.sharding import (
            llama_param_specs, shard_pytree,
        )

        cfg = TINY_MOE.replace(max_seq_len=64)
        params = quantize_params(
            llama.init_params(cfg, jax.random.PRNGKey(0)),
            compute_dtype=jnp.float32, bits=4)
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        ecfg = EngineConfig(max_batch=4, max_seq_len=64, page_size=8,
                            num_pages=32, prefill_buckets=(16, 32, 64),
                            max_new_tokens=6, temperature=0.0,
                            prefix_cache=False)
        kw = {}
        if kind in ("tp", "fsdp", "sharded-weights-no-mesh"):
            mesh = build_mesh(MeshConfig(data=2, model=2),
                              devices=cpu_devices[:4])
            params = shard_pytree(params, llama_param_specs(cfg), mesh)
            if kind != "sharded-weights-no-mesh":
                kw = {f"{kind}_mesh": mesh}
        elif kind == "ep":
            mesh = build_mesh(MeshConfig(data=2, expert=2, model=2),
                              devices=cpu_devices[:8])
            params = shard_pytree(params, llama_param_specs(cfg), mesh)
            kw = {"ep_mesh": mesh}
        elif kind == "cp":
            kw = {"cp_mesh": build_mesh(MeshConfig(seq=2),
                                        devices=cpu_devices[:2])}
        else:
            kw = {"pp_mesh": build_mesh(MeshConfig(stage=2),
                                        devices=cpu_devices[:2])}
        eng = make_engine(cfg, ecfg, params, tok, use_kernel=False, **kw)
        assert eng._expert_kernel is False
        assert eng._decode_scan.__wrapped__.keywords["expert_kernel"] is False
        assert "engine.moe_fused_steps" not in (eng._counts or {})


# ---------------------------------------------------------------------------
# a share of the router's experts held (PR 38): the grouped form compacts to
# the local pairs, up to a capacity that never drops
# ---------------------------------------------------------------------------

HELD_POSITIONS = 512        # 2,048 pairs; a quarter of the experts held
HELD_CAP = 1024             # twice the 512 a uniform router sends here


def _held_share(act, dtype="float32", **kw):
    """(cfg, its weights) of a preset made to hold 8 of its router's 32
    experts from the 4th: SwiGLU experts at the model's width
    (``TINY_EXAONE_MOE``) or squared-ReLU experts in a latent space
    (``TINY_NEMOTRON_H``); layer 1 of either is an expert layer."""
    from k8s_llm_rca_tpu.config import TINY_EXAONE_MOE, TINY_NEMOTRON_H
    from k8s_llm_rca_tpu.models import nemotron_h

    preset, init = ((TINY_EXAONE_MOE, llama.init_params) if act == "swiglu"
                    else (TINY_NEMOTRON_H, nemotron_h.init_params))
    cfg = preset.replace(dtype=dtype, router_width=32, **kw)
    return cfg, init(cfg, jax.random.PRNGKey(3))


def _routed(cfg, n_local):
    """Rows for the experts and a routing, in the router's numbering, of
    which exactly ``n_local`` (position, expert) pairs name an expert held
    here; a position's picks are distinct."""
    t, k = HELD_POSITIONS, cfg.n_experts_per_tok
    rng = np.random.default_rng(n_local)
    held = np.arange(cfg.expert_first, cfg.expert_first + cfg.n_experts)
    far = np.setdiff1d(np.arange(cfg.n_router), held)
    local = np.zeros(t * k, bool)
    local[rng.permutation(t * k)[:n_local]] = True
    topi = np.where(local.reshape(t, k),
                    np.stack([rng.permutation(held)[:k] for _ in range(t)]),
                    np.stack([rng.permutation(far)[:k] for _ in range(t)]))
    weights = rng.uniform(0.5, 1.5, (t, k)).astype(np.float32)
    x = jax.random.normal(
        jax.random.PRNGKey(n_local),
        (1, t, cfg.moe_latent_size or cfg.hidden_size), jnp.float32)
    return (x.astype(jnp.dtype(cfg.dtype)), jnp.asarray(topi[None], jnp.int32),
            jnp.asarray(weights[None]))


def _held_forms(monkeypatch, cfg, layer, x, topi, weights):
    """``_experts`` in its dense form, in the whole grouped form alone (a
    slack that leaves nothing to compact: the parent's program) and with
    the compact form before it; and the device's word on whether the last
    ran over (``n_compact_overflows``)."""
    def run(min_rows, slack):
        for name in ("MOE_GROUPED_MIN_ROWS_PER_EXPERT_FINE",
                     "MOE_GROUPED_MIN_ROWS_PER_EXPERT_LATENT"):
            monkeypatch.setattr(llama, name, min_rows)
        monkeypatch.setattr(llama, "MOE_COMPACT_SLACK", slack)

        def fn(layer, x, topi, weights):
            out = llama._experts(cfg, layer, x, topi, weights)
            return out.astype(jnp.float32), llama.n_compact_overflows(
                cfg, x.shape[1], [llama.n_local_pairs(cfg, topi)])

        return jax.jit(fn)(layer, x, topi, weights)

    slack = llama.MOE_COMPACT_SLACK
    dense, none = run(1 << 30, slack)
    assert int(none) == 0               # not grouped: one form
    whole, none = run(0, 1e9)
    assert int(none) == 0               # nothing to compact: one form
    compact, over = run(0, slack)
    return dense, whole, compact, int(over)


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("n_local,overflows", [
    pytest.param(300, 0, id="well-under-the-capacity"),
    pytest.param(HELD_CAP, 0, id="exactly-at-it"),
    pytest.param(HELD_CAP + 1, 1, id="one-over-it"),
    pytest.param(4 * HELD_POSITIONS, 1, id="every-pair-local"),
    pytest.param(0, 0, id="none-local"),
])
def test_compact_equals_dense_and_whole(monkeypatch, act, n_local,
                                        overflows):
    """The same sum over the same local pairs whichever form the device
    takes: under the capacity the compact rows, over it the whole form,
    which is then the parent's to the bit."""
    cfg, params = _held_share(act)
    pairs = HELD_POSITIONS * cfg.n_experts_per_tok
    assert llama.moe_compact_rows(cfg, HELD_POSITIONS) == HELD_CAP < pairs
    dense, whole, compact, over = _held_forms(
        monkeypatch, cfg, params["layers"][1], *_routed(cfg, n_local))
    assert over == overflows
    assert (float(jnp.max(jnp.abs(dense))) > 0) == (n_local > 0)
    np.testing.assert_allclose(compact, dense, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(compact, whole, rtol=1e-4, atol=1e-5)
    if overflows:
        np.testing.assert_array_equal(compact, whole)


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("n_local", [300, HELD_CAP + 1],
                         ids=["under", "over"])
def test_compact_equals_dense_within_bf16_rounding(monkeypatch, act,
                                                   n_local):
    cfg, params = _held_share(act, "bfloat16")
    dense, whole, compact, _ = _held_forms(
        monkeypatch, cfg, params["layers"][1], *_routed(cfg, n_local))
    tol = 2e-2 * float(jnp.max(jnp.abs(dense)))
    np.testing.assert_allclose(compact, dense, rtol=2e-2, atol=tol)
    np.testing.assert_allclose(compact, whole, rtol=2e-2, atol=tol)


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("n_local", [300, HELD_CAP + 1],
                         ids=["under", "over"])
def test_gradient_through_the_conditional(monkeypatch, act, n_local):
    """``engine/train.py`` with a held share: the gradient through the
    conditional, to the rows, the router's weights and every expert's
    weights, is the dense form's on either side of the capacity."""
    cfg, params = _held_share(act)
    layer = params["layers"][1]
    x, topi, weights = _routed(cfg, n_local)

    def loss(layer, x, weights):
        return jnp.sum(jnp.square(llama._experts(cfg, layer, x, topi,
                                                 weights)))

    grads = {}
    for name, rows in (("grouped", 0), ("dense", 1 << 30)):
        for const in ("MOE_GROUPED_MIN_ROWS_PER_EXPERT_FINE",
                      "MOE_GROUPED_MIN_ROWS_PER_EXPERT_LATENT"):
            monkeypatch.setattr(llama, const, rows)
        grads[name] = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
            layer, x, weights)
        if name == "grouped":
            text = str(jax.make_jaxpr(loss)(layer, x, weights))
            assert "cond[" in text and "ragged_dot" in text
    flat_g, tree_g = jax.tree.flatten(grads["grouped"])
    flat_d, tree_d = jax.tree.flatten(grads["dense"])
    assert tree_g == tree_d
    for g, d in zip(flat_g, flat_d):
        np.testing.assert_allclose(g, d, rtol=1e-3,
                                   atol=1e-4 * float(jnp.max(jnp.abs(d))))


@pytest.mark.parametrize("name,positions,rows", [
    # the third cell's buckets (22 picks, 128 of 512 held)
    pytest.param("nemotron", 1024, 11264, id="nemotron-1024"),
    pytest.param("nemotron", 2048, 22528, id="nemotron-2048"),
    pytest.param("nemotron", 3072, 33792, id="nemotron-3072"),
    pytest.param("nemotron", 4096, 45056, id="nemotron-4096"),
    # the fourth cell's (8 picks, 16 of 128 held)
    pytest.param("exaone", 2048, 4096, id="exaone-2048"),
    pytest.param("exaone", 3072, 6144, id="exaone-3072"),
    pytest.param("exaone", 4096, 8192, id="exaone-4096"),
    pytest.param("exaone", 6144, 12288, id="exaone-6144"),
    pytest.param("exaone", 8192, 16384, id="exaone-8192"),
])
def test_compact_rows_from_the_calls_shape(name, positions, rows):
    """The capacity is the share of the experts held, times the slack, up
    to a whole row tile of the grouped kernel; never above the pairs."""
    from k8s_llm_rca_tpu.config import TINY_EXAONE_MOE, TINY_NEMOTRON_H

    cfg = (TINY_NEMOTRON_H.replace(n_experts=128, router_width=512,
                                   expert_first=0, n_experts_per_tok=22)
           if name == "nemotron" else
           TINY_EXAONE_MOE.replace(n_experts=16, router_width=128,
                                   expert_first=0, n_experts_per_tok=8))
    pairs = positions * cfg.n_experts_per_tok
    assert llama.moe_compact_rows(cfg, positions) == rows < pairs
    assert rows % llama._GROUPED_MATMUL_TILES[0] == 0
    assert rows >= pairs * cfg.n_experts / cfg.n_router * \
        llama.MOE_COMPACT_SLACK


@pytest.mark.parametrize("cfg,router_width,positions,min_rows", [
    pytest.param(MIXTRAL_8X7B, 0, 4096, None, id="every-expert-held"),
    pytest.param(TINY_MOE, 0, 512, 0, id="tiny-every-expert-held"),
    pytest.param(None, 32, 64, None, id="a-call-that-is-not-grouped"),
    pytest.param(None, 16, 512, None, id="twice-half-the-pairs-is-all"),
    pytest.param(None, 32, 128, 0, id="one-row-tile-covers-the-call"),
])
def test_a_call_with_nothing_to_save_has_the_whole_form_alone(
        monkeypatch, cfg, router_width, positions, min_rows):
    from k8s_llm_rca_tpu.config import TINY_EXAONE_MOE

    if min_rows is not None:
        for name in ("MOE_GROUPED_MIN_ROWS_PER_EXPERT",
                     "MOE_GROUPED_MIN_ROWS_PER_EXPERT_FINE"):
            monkeypatch.setattr(llama, name, min_rows)
    cfg = cfg or TINY_EXAONE_MOE.replace(router_width=router_width)
    assert llama.moe_compact_rows(cfg, positions) is None


class TestCompactCounters:
    """``engine.moe_compact_calls`` (from shapes, on the host) and
    ``engine.moe_compact_overflows`` (counted on the device, fetched with
    the tick's tokens) of an engine whose model holds a share of its
    router's experts: two prompts in a 512 bucket, so every expert layer
    of every row holds both forms."""

    @staticmethod
    def _engine(act, bias):
        cfg, params = _held_share(act, max_seq_len=1024)
        params = dict(params, layers=[
            dict(layer, router_bias=jnp.asarray(bias, jnp.float32))
            if "router_bias" in layer else layer
            for layer in params["layers"]])
        ecfg = EngineConfig(max_batch=2, max_seq_len=1024, page_size=16,
                            num_pages=96, prefill_buckets=(512, 1024),
                            max_new_tokens=4, decode_chunk=2,
                            temperature=0.0, prefix_cache=False)
        return cfg, make_engine(cfg, ecfg, params,
                                get_tokenizer(vocab_size=cfg.vocab_size))

    @staticmethod
    def _run(engine, cfg):
        rng = np.random.default_rng(1)
        for n in (300, 410):
            engine.submit([int(t) for t in rng.integers(
                3, cfg.vocab_size - 1, n)], max_new_tokens=4)
        assert len(engine.run_to_completion()) == 2
        return engine._counts

    @pytest.mark.parametrize("act", ["swiglu", "relu2"])
    @pytest.mark.parametrize("router", ["uniform", "every-pick-held"])
    def test_calls_from_shapes_and_overflows_from_the_device(self, act,
                                                             router):
        # no selection bias: the scores alone choose, evenly enough; or
        # one that lifts experts 4..11 of the router's 32 over the rest
        bias = np.zeros(32)
        if router == "every-pick-held":
            bias[4:12] = 10.0
        cfg, engine = self._engine(act, bias)
        assert engine.pool.moe_compact_overflows is not None
        counts = self._run(engine, cfg)
        expert_layers = (cfg.layer_pattern.count("E") if cfg.layer_pattern
                         else cfg.n_layers - cfg.n_dense_layers)
        rows = counts["engine.prefill_padded_tokens"] // 512
        assert rows >= 2
        assert counts["engine.moe_compact_calls"] == rows * expert_layers
        assert counts["engine.moe_compact_overflows"] == (
            0 if router == "uniform" else rows * expert_layers)
        share = (counts["engine.moe_local_pairs"]
                 / counts["engine.moe_routed_pairs"])
        assert (share == 1.0) if bias.any() else (0.1 < share < 0.4)

    def test_absent_where_every_expert_is_held(self):
        eng, _ = _int4_engine(TINY_MOE.replace(max_seq_len=64))
        eng.submit(list(range(3, 30)), max_new_tokens=4)
        eng.run_to_completion()
        assert eng.pool.moe_compact_overflows is None
        assert not {"engine.moe_compact_calls",
                    "engine.moe_compact_overflows"} & set(eng._counts)

    def test_absent_under_the_compact_forms_size(self):
        """A 64-position bucket is not grouped (and one tile would cover
        it): nothing is counted as a call, and the device's count, which
        the pool keeps all the same, reads 0."""
        from k8s_llm_rca_tpu.config import TINY_EXAONE_MOE

        cfg = TINY_EXAONE_MOE
        ecfg = EngineConfig(max_batch=2, max_seq_len=128, page_size=16,
                            num_pages=32, prefill_buckets=(64, 128),
                            max_new_tokens=4, temperature=0.0,
                            prefix_cache=False)
        eng = make_engine(cfg, ecfg,
                          llama.init_params(cfg, jax.random.PRNGKey(0)),
                          get_tokenizer(vocab_size=cfg.vocab_size))
        eng.submit(list(range(3, 40)), max_new_tokens=4)
        eng.run_to_completion()
        assert "engine.moe_compact_calls" not in eng._counts
        assert eng._counts["engine.moe_compact_overflows"] == 0


@pytest.mark.parametrize("dims,tiles", [
    pytest.param((2 * 4096, 4096, 14336), (512, 1024, 1024),
                 id="mixtral-up-the-measured-tiles"),
    pytest.param((2 * 4096, 14336, 4096), (512, 1024, 1024),
                 id="mixtral-down"),
    pytest.param((12288, 6144, 2048), (512, 1024, 1024), id="exaone-up"),
    pytest.param((2 * 1552, 4096, 14336), None,
                 id="rows-512-does-not-divide-xlas-own"),
    pytest.param((33792, 1024, 2688), (256, 1024, 2688), id="nemotron-up"),
    pytest.param((33792, 2688, 1024), (256, 2688, 1024),
                 id="nemotron-down"),
    pytest.param((22 * 3072, 1024, 2688), (256, 1024, 2688),
                 id="nemotron-up-the-whole-form"),
    pytest.param((33792, 5376, 1024), (256, 2688, 1024),
                 id="a-side-no-longer-than-measured"),
    pytest.param((33792, 2688, 2688), (256, 2688, 896),
                 id="a-weight-tile-no-larger-than-measured"),
    pytest.param((300, 1024, 2688), None, id="rows-no-128-divides"),
    pytest.param((2048, 128, 96), None, id="toy-widths"),
])
def test_grouped_matmul_tiles_divide_or_are_left_to_xla(dims, tiles):
    """The measured tiles where they divide (the older cells' programs keep
    theirs), the largest multiples of 128 within the measured bounds for
    weights they do not divide, XLA's own choice otherwise."""
    assert llama._grouped_matmul_tiles(*dims) == tiles
    if tiles:
        assert not any(d % t for d, t in zip(dims, tiles))
