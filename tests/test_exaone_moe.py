"""The Llama block with per-layer kinds (``exaone_moe``, preset
``TINY_EXAONE_MOE``): window layers beside full ones, a leading dense layer,
query/key norms, rotary embedding by kind, a share of a sigmoid router's
experts beside a shared expert.  The programs against the plain reference
(``benchmarks/reference/exaone_moe.py``, none of the program's model code)
through both kinds of cache, the two window kernels against a masked
``causal_attention``, the shares against the uncut layer, each refusal by the
mechanism's name, and the programs of the three older families as they
were."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import exaone_moe as reference  # noqa: E402
from k8s_llm_rca_tpu.config import (  # noqa: E402
    TINY, TINY_EXAONE_MOE, TINY_NEMOTRON_H, EngineConfig, ModelConfig,
)
from k8s_llm_rca_tpu.engine import make_engine, paged  # noqa: E402
from k8s_llm_rca_tpu.engine.sampling import SamplingParams  # noqa: E402
from k8s_llm_rca_tpu.models import llama, nemotron_h  # noqa: E402
from k8s_llm_rca_tpu.ops.attention import causal_attention  # noqa: E402
from k8s_llm_rca_tpu.ops.flash_attention import flash_attention  # noqa: E402
from k8s_llm_rca_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention, paged_attention_quant, paged_attention_xla,
)
from k8s_llm_rca_tpu.utils import get_tokenizer  # noqa: E402

CFG = TINY_EXAONE_MOE          # window 8, page 4 below: a ring of 3 pages
PAGE, SLOTS, PPS = 4, 4, 16


def conf_of(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, for a program config."""
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "layer_types": cfg.attn_layer_types,
        "sliding_window": cfg.attn_window,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "rope_parameters": {"rope_theta": cfg.rope_theta},
        "num_experts_per_tok": cfg.n_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling,
        "first_routed_expert": cfg.expert_first, "kv_cache_dtype": None}


@pytest.fixture(scope="module")
def weights():
    return llama.init_params(CFG, jax.random.PRNGKey(3))


def reference_logits(cfg, params, seq):
    out, _ = reference.forward(conf_of(cfg), params, np.asarray(seq),
                               np.arange(len(seq)))
    return np.asarray(out)


# ------------------------------------------------------------------ the config


def test_the_config_says_each_layers_kind():
    assert CFG.attn_windows == (8, 8, 8, 0, 8)
    assert (CFG.n_window_layers, CFG.n_kv_layers) == (4, 1)
    assert CFG.ring_pages(PAGE) == 3 and CFG.ring_pages(16) == 2
    assert [CFG.layer_cfg(i).n_experts for i in range(5)] == [0, 8, 8, 8, 8]
    assert [CFG.layer_cfg(i).use_rope for i in range(5)] == [
        True, True, True, False, True]
    assert TINY.layer_cfg(1) is TINY and TINY.attn_windows == (0, 0)


@pytest.mark.parametrize("changes, message", [
    (dict(attn_layer_types=("full_attention",) * 4), "4 entries for n_layers=5"),
    (dict(attn_layer_types=("chunked_attention",) * 5), "unknown attention kind"),
    (dict(attn_window=0), "attn_window=0"),
    (dict(n_dense_layers=6), "n_dense_layers=6"),
])
def test_a_table_that_does_not_fit_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        CFG.replace(**changes)


def test_init_params_makes_the_new_leaves(weights):
    dense, sparse = weights["layers"][0], weights["layers"][1]
    assert "router" not in dense and dense["w_gate"].shape == (128, 256)
    assert sparse["router"].shape == (128, 16)            # the router's 16
    assert sparse["router_bias"].shape == (16,)
    assert sparse["w_gate"].shape == (8, 128, 96)         # the 8 held
    assert sparse["w_shared_gate"].shape == (128, 96)
    assert sparse["w_shared_down"].shape == (96, 128)
    assert dense["q_norm"].shape == dense["k_norm"].shape == (32,)


# -------------------------------------------- the programs against the reference


def _prefill_then_decode(cfg, params, lens, steps, dtype_tol, use_kernel):
    """Prompts of ``lens`` tokens batch-prefilled (one padding row) into
    slots 0.., then ``steps`` decode steps of seeded tokens; every step's
    logits against the reference's full forward pass."""
    rng = np.random.default_rng(0)
    s_pad = -(-max(lens) // PAGE) * PAGE
    n = len(lens)
    pool = paged.init_paged_cache(cfg, 1 + SLOTS * PPS, PAGE, n_slots=SLOTS)
    toks = np.zeros((n + 1, s_pad), np.int32)
    seqs = []
    for i, ln in enumerate(lens):
        seqs.append(list(rng.integers(3, cfg.vocab_size - 1, ln)))
        toks[i, :ln] = seqs[i]
    toks[n] = toks[n - 1]
    own = [1 + i * PPS + np.arange(PPS) for i in range(n)]
    maps = np.stack([o[:s_pad // PAGE] for o in own] + [own[-1][:s_pad // PAGE]])
    pool, logits = jax.jit(paged.paged_prefill_batch, static_argnums=0)(
        cfg, params, pool, jnp.asarray(toks),
        jnp.asarray(list(lens) + [lens[-1]], jnp.int32), jnp.asarray(maps),
        slots=jnp.asarray(list(range(n)) + [n - 1], jnp.int32))
    worst = 0.0
    for i in range(n):
        want = reference_logits(cfg, params, seqs[i])[-1]
        worst = max(worst, np.abs(np.asarray(logits[i]) - want).max()
                    / np.abs(want).max())
    tables = np.full((SLOTS, PPS), paged.TRASH_PAGE, np.int32)
    for i in range(n):
        tables[i] = own[i]
    step = jax.jit(paged.paged_decode_step, static_argnums=0,
                   static_argnames="use_kernel")
    for _ in range(steps):
        cur, pos = np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), np.int32)
        for i in range(n):
            seqs[i].append(int(rng.integers(3, cfg.vocab_size - 1)))
            cur[i], pos[i] = seqs[i][-1], len(seqs[i]) - 1
        pool, logits = step(cfg, params, pool, jnp.asarray(cur),
                            jnp.asarray(pos), jnp.asarray(tables),
                            use_kernel=use_kernel)
    for i in range(n):          # after the last step: everything before it
        want = reference_logits(cfg, params, seqs[i])[-1]    # was read back
        worst = max(worst, np.abs(np.asarray(logits[i]) - want).max()
                    / np.abs(want).max())
    assert worst < dtype_tol, worst
    return pool, seqs


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_prefill_and_decode_through_both_caches_agree_with_the_reference(
        weights, use_kernel):
    """Prompts shorter than the window (3), equal to it (8) and several
    times longer (29), unequal rows of one batched prefill; 26 decode steps
    wrap the ring of 12 positions twice.  ``kernel``: the Pallas decode
    kernels, interpreted."""
    _prefill_then_decode(CFG, weights, (3, 8, 29), 26, 2e-4, use_kernel)


def test_bfloat16_agrees_within_its_rounding():
    cfg = CFG.replace(dtype="bfloat16")
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    _prefill_then_decode(cfg, params, (5, 24), 13, 6e-2, False)


def test_the_cache_holds_what_the_reference_says_it_must(weights):
    """Full layers: every token in the pages.  Window layers: the last 8
    tokens in the slot's ring, each where the write's arithmetic put it."""
    pool, seqs = _prefill_then_decode(CFG, weights, (3, 8, 29), 26, 2e-4,
                                      False)
    r = CFG.ring_pages(PAGE)
    for i, seq in enumerate(seqs):
        n = len(seq)
        _, held = reference.forward(conf_of(CFG), weights, np.asarray(seq),
                                    np.arange(n))
        pages = 1 + i * PPS + np.arange(-(-n // PAGE))
        got = np.asarray(pool.k[0][pages]).reshape(-1, CFG.kv_dim)[:n]
        np.testing.assert_allclose(got, held["k"][3], atol=2e-5)
        last = np.arange(n - 8, n)
        at = (last // PAGE) % r * PAGE + last % PAGE
        ring = np.asarray(pool.ring.v[:, i * r:(i + 1) * r]).reshape(
            4, r * PAGE, CFG.kv_dim)
        for wi, li in enumerate((0, 1, 2, 4)):
            np.testing.assert_allclose(ring[wi][at], held["v"][li][-8:],
                                       atol=2e-5)


def test_the_engine_serves_it(weights):
    """submit -> tick -> result through ``make_engine``: greedy tokens equal
    the reference's argmax, a prompt longer than the window and enough
    decode for the ring to wrap; 24 local pairs of 64 routed a position
    counted in the program."""
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, page_size=PAGE,
                        num_pages=40, prefill_buckets=(16, 32, 64),
                        max_new_tokens=20, decode_chunk=4, prefix_cache=False,
                        temperature=0.0)
    engine = make_engine(CFG, ecfg, weights,
                         get_tokenizer(vocab_size=CFG.vocab_size))
    prompt = [int(t) for t in np.random.default_rng(1).integers(3, 500, 19)]
    engine.submit(prompt, max_new_tokens=20)
    (result,) = engine.run_to_completion()
    seq = list(prompt)
    for tok in result.token_ids:
        assert int(np.argmax(reference_logits(CFG, weights, seq)[-1])) == tok
        seq.append(tok)
    assert engine.pool.moe_local_pairs is not None


# ------------------------------------------------------------ the two kernels


@pytest.mark.parametrize("s", [1, 127, 128, 129, 1000])
def test_banded_flash_attention_equals_the_masked_form(s):
    key = jax.random.PRNGKey(s)
    q = jax.random.normal(key, (2, s, 4, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, s, 2, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, s, 2, 32))
    lens = jnp.asarray([s, max(1, s - 5)])
    got = flash_attention(q, k, v, lens, window=128, interpret=True)
    want = causal_attention(q, k, v, lens, window=128)
    true = (np.arange(s)[None] < np.asarray(lens)[:, None])[..., None, None]
    np.testing.assert_allclose(np.asarray(got) * true,
                               np.asarray(want) * true, atol=2e-5)
    if s > 128:             # and the band is no full causal mask
        assert np.abs(np.asarray(causal_attention(q, k, v, lens))
                      - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("s,n,block_q,block_k,window", [
    (300, 257, 64, 64, 0), (300, 300, 64, 128, 0), (512, 100, 128, 64, 0),
    (600, 333, 256, 256, 0), (129, 129, 32, 32, 0), (1000, 777, 128, 128, 128),
])
def test_lean_flash_attention_equals_the_masked_form(s, n, block_q, block_k,
                                                     window, dtype, atol):
    """The lean call (operands as they come, key blocks no query sees and
    q blocks of padding skipped) against the masked XLA form, in blocks
    larger and smaller than a row's true length."""
    key = jax.random.PRNGKey(s + n)
    q = jax.random.normal(key, (2, s, 8, 64), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, s, 2, 64), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, s, 2, 64), dtype)
    lens = jnp.asarray([n, max(1, n // 3)])
    got = flash_attention(q, k, v, lens, block_q=block_q, block_k=block_k,
                          window=window, lean=True, interpret=True)
    want = causal_attention(q, k, v, lens, window=window)
    true = (np.arange(s)[None] < np.asarray(lens)[:, None])[..., None, None]
    np.testing.assert_allclose(np.asarray(got, np.float32) * true,
                               np.asarray(want, np.float32) * true, atol=atol)


def test_lean_flash_attention_over_a_chunk_of_queries():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 256, 4, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 64))
    lens = jnp.asarray([200])
    got = flash_attention(q[:, 128:], k, v, lens, jnp.asarray([128]),
                          block_q=32, block_k=64, lean=True, interpret=True)
    want = causal_attention(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got[0, :72]),
                               np.asarray(want[0, 128:200]), atol=2e-5)


def test_only_the_full_layers_prefill_takes_the_lean_call(monkeypatch):
    """A model with window layers sends its full layers' prefill through
    the lean call in ``FLASH_LEAN_BLOCK`` blocks and its bands through the
    call as it was; the uniform models' ``_flash_attention_fn`` asks for
    neither, so their programs hold the kernel they held."""
    from k8s_llm_rca_tpu.ops import flash_attention as fa

    seen = []
    monkeypatch.setattr(fa, "flash_attention",
                        lambda *a, **kw: seen.append(kw))
    lens = jnp.asarray([2048])
    for li in range(CFG.n_layers):
        llama._layer_attention_fn(CFG, li, lens, True, 2048)(None, None, None)
    llama._flash_attention_fn(lens, None)(None, None, None)
    lean = dict(interpret=False, lean=True,
                block_q=llama.FLASH_LEAN_BLOCK, block_k=llama.FLASH_LEAN_BLOCK)
    assert seen == [
        lean if kind == "full_attention"
        else dict(interpret=False, window=CFG.attn_window)
        for kind in CFG.attn_layer_types] + [dict(interpret=False)]
    assert llama._layer_attention_fn(CFG, 3, lens, True, 512) is None


def test_a_band_is_a_whole_fresh_sequence():
    q = jnp.zeros((1, 256, 4, 32))
    with pytest.raises(ValueError, match="whole fresh sequence"):
        flash_attention(q, q[:, :, :2], q[:, :, :2], jnp.asarray([256]),
                        jnp.asarray([4]), window=128, interpret=True)


@pytest.mark.parametrize("length", [1, 127, 128, 129, 1000])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_the_window_decode_kernel_equals_the_masked_form(length, quant):
    """One decode query at position ``length - 1`` over the ring as the
    step lays it out (``_ring_view``), against ``causal_attention`` with a
    window over the whole sequence: interpreter and XLA form."""
    cfg = TINY.replace(n_layers=1, attn_layer_types=("sliding_attention",),
                       attn_window=128)
    page, r = 16, cfg.ring_pages(16)
    key = jax.random.PRNGKey(length)
    q = jax.random.normal(key, (1, length, cfg.n_heads, cfg.head_dim))
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, length, cfg.n_kv_heads, cfg.head_dim))
    v = jax.random.normal(jax.random.fold_in(key, 2), k.shape)
    want = causal_attention(q, k, v, jnp.asarray([length]), window=128)[0, -1]
    # slot 1 of 2: every position where the ring's arithmetic puts it
    ring = paged.init_paged_cache(cfg, 8, page, n_slots=2,
                                  kv_dtype="int8" if quant else None).ring
    pos = np.arange(max(0, length - r * page), length)
    pages, offs = r + (pos // page) % r, pos % page
    ring = paged._write_pool_rows(
        cfg, ring, 0, jnp.asarray(pages), jnp.asarray(offs),
        k[0, pos].reshape(-1, cfg.kv_dim), v[0, pos].reshape(-1, cfg.kv_dim))
    lengths = jnp.asarray([0, length - 1], jnp.int32)
    _, tables, rel, starts = paged._ring_view(cfg, lengths, page)
    rel = rel.at[0].set(0)                         # slot 0 holds no sequence
    qs = jnp.stack([q[0, -1], q[0, -1]])
    if quant:
        got = paged_attention_quant(qs, ring.k, ring.v, ring.k_scale,
                                    ring.v_scale, rel, tables, layer=0,
                                    starts=starts, interpret=True)[1]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=0.05)
        return
    got = paged_attention(qs, ring.k, ring.v, rel, tables, layer=0,
                          starts=starts, interpret=True)[1]
    xla = paged_attention_xla(qs, ring.k[0], ring.v[0], rel, tables,
                              starts)[1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(want), atol=2e-5)


def test_the_two_decode_calls_carry_different_names():
    """The trace tells a window layer's call from a full layer's, and a
    banded prefill call from a full one, by the ``pallas_call``'s name."""
    q = jnp.zeros((2, 4, 32))
    pool = jnp.zeros((1, 8, 16, 64))
    args = (q, pool, pool, jnp.asarray([3, 3]), jnp.zeros((2, 4), jnp.int32))
    text = lambda **kw: str(jax.make_jaxpr(lambda *a: paged_attention(
        *a, layer=0, interpret=False, **kw))(*args))
    assert "window_paged_attention" in text(starts=jnp.asarray([1, 1]))
    assert "window_paged_attention" not in text()
    assert "name=paged_attention" in text()
    banded = lambda **kw: str(jax.make_jaxpr(lambda q, k: flash_attention(
        q, k, k, jnp.asarray([256]), interpret=False, **kw))(
        jnp.zeros((1, 256, 4, 32)), jnp.zeros((1, 256, 2, 32))))
    assert "flash_attention_window" in banded(window=128)
    assert "flash_attention_window" not in banded()


# ------------------------------------------------------------ the shares add up


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """One sparse MLP over a router of 16: the uncut layer (all 16 held)
    equals the sum over eight chips' shares of 2 (each computed as
    ``_moe_mlp`` computes its own part) with the shared expert, which every
    share carries, counted once; so does the reference's."""
    uncut = CFG.replace(n_experts=16, router_width=0, expert_first=0)
    whole = llama.init_params(uncut, jax.random.PRNGKey(9))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 7, 128))
    lcfg = uncut.layer_cfg(1)
    want = llama._moe_mlp(lcfg, whole, x)
    shared = llama._w_mm(lcfg, llama._shared_hidden(lcfg, whole, x),
                         whole["w_shared_down"])
    total, pairs = shared, []
    for chip in range(8):
        share = CFG.replace(n_experts=2, router_width=16, expert_first=2 * chip)
        part = dict(whole, **{n: whole[n][2 * chip:2 * chip + 2]
                              for n in ("w_gate", "w_up", "w_down")})
        total = total + llama._moe_mlp(share.layer_cfg(1), part, x,
                                       local_pairs=pairs) - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    assert int(sum(pairs)) == 2 * 7 * 4          # every pair is local once
    ref = reference.mlp(x[0], dict(whole, mlp_norm=jnp.ones((128,))),
                        top_k=4, scaling=2.5, first=0, eps=1e-5) - x[0]
    normed = x[0] * jax.lax.rsqrt(jnp.mean(x[0] ** 2, -1, keepdims=True)
                                  + 1e-5)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(llama._moe_mlp(lcfg, whole, normed[None])[0]),
        atol=2e-5)


def test_grouped_and_dense_experts_agree_for_a_share(weights, monkeypatch):
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, 128))
    lcfg, layer = CFG.layer_cfg(2), weights["layers"][2]
    outs = []
    for rows in (1 << 30, 0):
        monkeypatch.setattr(llama, "MOE_GROUPED_MIN_ROWS_PER_EXPERT_FINE",
                            rows)
        assert llama.moe_grouped(lcfg, 64) == (rows == 0)
        outs.append(np.asarray(llama._moe_mlp(lcfg, layer, x)))
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-5)


# ------------------------------------------------------------------ the refusals


def _engine(weights, **kw):
    base = dict(max_batch=2, max_seq_len=64, page_size=PAGE, num_pages=40,
                prefill_buckets=(16, 32, 64), prefix_cache=False)
    base.update(kw.pop("ecfg", {}))
    return make_engine(CFG, EngineConfig(**base), weights,
                       get_tokenizer(vocab_size=CFG.vocab_size), **kw)


@pytest.mark.parametrize("kw, name", [
    (dict(ecfg=dict(prefix_cache=True)), "the prefix cache"),
    (dict(ecfg=dict(max_spilled_pages=8)), "KV spill to the host"),
    (dict(ecfg=dict(prefill_chunk_budget=16)), "chunked prefill"),
    (dict(ecfg=dict(speculative_k=2)), "speculative decoding"),
], ids=["prefix", "spill", "chunked", "speculative"])
def test_what_is_not_built_for_a_ring_is_refused_by_name(weights, kw, name):
    with pytest.raises(ValueError) as err:
        _engine(weights, **kw)
    assert name in str(err.value) and "ring of pages per slot" in str(err.value)


def test_a_mesh_is_refused_by_name(weights):
    from k8s_llm_rca_tpu.runtime import cpu_mesh_for_tests

    with pytest.raises(ValueError, match="a TP, EP, CP, PP or FSDP mesh"):
        _engine(weights, tp_mesh=cpu_mesh_for_tests(2))


def test_the_sharding_rules_refuse_by_name():
    from k8s_llm_rca_tpu.runtime import rules

    for fn in (rules.llama_rules, rules.llama_param_template):
        with pytest.raises(ValueError, match="a mesh over the window "
                           "layers' ring is not built"):
            fn(CFG)
        assert fn(TINY)                       # the uniform block: as it was


def test_export_and_adoption_are_refused_by_name(weights):
    engine = _engine(weights)
    with pytest.raises(ValueError, match=r"export of a run \(export_run\)"):
        engine.export_run(0)
    with pytest.raises(ValueError, match="adoption of a run's cache"):
        engine.adopt_run({"prompt_ids": [1], "generated": []}, kv={})


def test_the_programs_without_a_ring_form_refuse_by_name(weights):
    pool = paged.init_paged_cache(CFG, 8, PAGE, n_slots=2)
    i32 = jnp.int32
    with pytest.raises(ValueError, match="chunked prefix prefill"):
        paged.paged_prefill_chunk_batch(
            CFG, weights, pool, jnp.zeros((1, 8), i32), jnp.ones((1,), i32),
            jnp.zeros((1,), i32), jnp.zeros((1, 2), i32),
            jnp.zeros((1, 2), i32))
    with pytest.raises(ValueError, match="multi-token decode"):
        paged.paged_decode_multi(CFG, weights, pool, jnp.zeros((2, 2), i32),
                                 jnp.zeros((2,), i32), jnp.zeros((2, 4), i32))
    with pytest.raises(ValueError, match="needs n_slots"):
        paged.init_paged_cache(CFG, 8, PAGE)
    with pytest.raises(ValueError, match=r"slots=\) whose ring"):
        paged.paged_prefill_batch(CFG, weights, pool, jnp.zeros((1, 8), i32),
                                  jnp.ones((1,), i32), jnp.zeros((1, 2), i32))
    for fn, args in ((llama.decode_step, (None, None, None)),
                     (llama.decode_multi, (None, None, None)),
                     (llama.prefill_kv, (jnp.zeros((1, 8), i32), 1)),
                     (llama._prefill_batch_kv, (jnp.zeros((1, 8), i32),
                                                jnp.ones((1,), i32)))):
        with pytest.raises(ValueError, match="4 sliding-window layers keep "
                           "the last 8 positions in a ring"):
            fn(CFG, weights, *args)


# ------------------------------------- what needs a slot is the window alone

# the same block and the same weights with every layer a full one: still a
# leading dense MLP, the query/key norm, no rotary embedding on a full layer,
# a shared expert and a share of a sigmoid router, and nothing per slot
NO_WINDOW = CFG.replace(attn_layer_types=("full_attention",) * 5)


def _rel(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def test_a_block_without_window_layers_takes_the_uniform_programs(weights):
    """Layers that differ in kind without a window are read layer by layer
    (``layer_cfg``) by the programs every Llama-family model runs: the
    batched prefill and the decode step over pages alone, the chunk over a
    cached prefix, the multi-token step, and the contiguous-cache loops;
    each against the reference's full forward pass."""
    assert NO_WINDOW.mixed_layers and not NO_WINDOW.n_window_layers
    pool, seqs = _prefill_then_decode(NO_WINDOW, weights, (3, 8, 29), 5,
                                      2e-4, False)
    assert pool.ring is None and pool.k.shape[0] == 5
    assert pool.moe_local_pairs is None
    seq = np.asarray(seqs[2][:31], np.int32)
    want = reference_logits(NO_WINDOW, weights, seq)
    i32 = jnp.int32
    pages = jnp.arange(1, 9, dtype=i32)

    def prefilled(n):
        """``seq[:n]`` (16 at most) in pages 1.. of a fresh pool."""
        toks = np.zeros((1, 16), np.int32)
        toks[0, :n] = seq[:n]
        return paged.paged_prefill(
            NO_WINDOW, weights, paged.init_paged_cache(NO_WINDOW, 12, PAGE),
            jnp.asarray(toks), i32(n), pages[:4])

    # the 16 tokens behind a cached prefix of 12
    pool, first = prefilled(12)
    assert _rel(first[0], want[11]) < 2e-4
    chunk = np.zeros((1, 16), np.int32)
    chunk[0] = seq[12:28]
    pool, logits = paged.paged_prefill_chunk(
        NO_WINDOW, weights, pool, jnp.asarray(chunk), i32(16), i32(12),
        pages, pages[3:7])
    assert _rel(logits[0], want[27]) < 2e-4
    # three tokens in one multi-token step, all in the page of position 16
    pool, _ = prefilled(16)
    pool, _, logits = paged.paged_decode_multi(
        NO_WINDOW, weights, pool, jnp.asarray(seq[None, 16:19]),
        jnp.asarray([16], i32), pages[None])
    assert _rel(logits[0], want[16:19]) < 2e-4
    # the contiguous cache: prefill, one step, two tokens at once
    toks = np.zeros((1, 32), np.int32)
    toks[0, :29] = seq[:29]
    cache, logits = llama.prefill(NO_WINDOW, weights,
                                  llama.init_cache(NO_WINDOW, 1, 64),
                                  jnp.asarray(toks), i32(29), i32(0))
    assert _rel(logits[0], want[28]) < 2e-4
    cache, logits = llama.decode_step(NO_WINDOW, weights, cache,
                                      jnp.asarray(seq[29:30]),
                                      jnp.asarray([29], i32))
    assert _rel(logits[0], want[29]) < 2e-4
    _, logits = llama.decode_multi(NO_WINDOW, weights, cache,
                                   jnp.asarray(seq[None, 29:31]),
                                   jnp.asarray([29], i32))
    assert _rel(logits[0], want[29:31]) < 2e-4


def test_the_engine_refuses_it_a_mesh_and_nothing_else(weights):
    """Prefix reuse, spill, chunked prefill and export hold pages alone, so
    a block whose layers differ without a window has them; a mesh it has
    not, by the sharding rules' own refusal."""
    from k8s_llm_rca_tpu.runtime import cpu_mesh_for_tests

    tok = get_tokenizer(vocab_size=CFG.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, page_size=PAGE,
                        num_pages=40, prefill_buckets=(16, 32, 64),
                        max_new_tokens=6, decode_chunk=4, prefix_cache=True,
                        prefill_chunk_budget=16, max_spilled_pages=8,
                        temperature=0.0)
    engine = make_engine(NO_WINDOW, ecfg, weights, tok)
    prompt = [int(t) for t in np.random.default_rng(2).integers(3, 500, 27)]
    for _ in range(2):                  # the second is a prefix hit
        engine.submit(prompt, max_new_tokens=6)
        (result,) = engine.run_to_completion()
        seq = list(prompt)
        for t in result.token_ids:
            assert t == int(reference_logits(NO_WINDOW, weights,
                                             seq)[-1].argmax())
            seq.append(t)
    with pytest.raises(ValueError, match=r"a TP, EP, CP, PP or FSDP mesh "
                       r"\(runtime/rules.py\) is not built"):
        make_engine(NO_WINDOW, ecfg, weights, tok,
                    tp_mesh=cpu_mesh_for_tests(2))


# ------------------------------------------------- the older families, as they were

# sha256 (first 16 hex digits) of the StableHLO the layer-table preset's
# programs lower to, taken by this very function at PR 36's parent commit
# (d80f647) and re-taken at PR 38, whose pool gained a leaf for that preset
# (``PagePool.moe_compact_overflows``: a ``tensor<1xi32>`` more that the
# decode programs take and hand back untouched, and one more count out of
# the prefill's map over rows; the programs were diffed against the
# parent's and differ in nothing else): tests/test_nemotron_h.py keeps the
# two Llama-family presets', unedited
NEMOTRON_PARENT_HLO = {
    "decode_step": "55cbeba26a40fbb0",
    "decode_scan": "e4722ffbc08ed7df",
    "prefill_batch": "c728d75524189320",
}


@pytest.mark.parametrize("program", sorted(NEMOTRON_PARENT_HLO))
def test_the_layer_table_programs_keep_their_hlo(program):
    cfg = TINY_NEMOTRON_H
    i32, sd = jnp.int32, jax.ShapeDtypeStruct
    weights = jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0)))
    b, pps = 4, 8
    pool = jax.eval_shape(
        lambda: paged.init_paged_cache(cfg, 32, 16, n_slots=b))
    if program == "decode_step":
        text = jax.jit(paged.paged_decode_step, static_argnums=0,
                       static_argnames="use_kernel").lower(
            cfg, weights, pool, sd((b,), i32), sd((b,), i32),
            sd((b, pps), i32), use_kernel=False).as_text()
    elif program == "decode_scan":
        text = jax.jit(paged.paged_decode_scan, static_argnums=(0, 7, 8, 9),
                       static_argnames="use_kernel").lower(
            cfg, weights, pool, sd((b,), i32), sd((b,), i32),
            sd((b, pps), i32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)), 4,
            SamplingParams(), 2, use_kernel=False).as_text()
    else:
        text = jax.jit(paged.paged_prefill_batch, static_argnums=0).lower(
            cfg, weights, pool, sd((2, 64), i32), sd((2,), i32),
            sd((2, 4), i32), slots=sd((2,), i32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        NEMOTRON_PARENT_HLO[program]
