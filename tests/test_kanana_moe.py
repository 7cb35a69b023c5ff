"""Latent attention on the serving path (``deepseek_v3`` as kanana-2
publishes it, preset ``TINY_KANANA_MOE``: a latent of 32 and one rotated key
of 16 a token where keys and values per head would be 224, a leading dense
layer, a share of a sigmoid router's experts beside a shared expert).  The
programs against the plain reference (``benchmarks/reference/deepseek_v3.py``,
un-absorbed, none of the program's model code) through the latent pool, what
the pool holds, the engine through ``AssistantService``, the shares against
the uncut layer, each refusal by the mechanism's name, and the window
family's programs as they were."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import deepseek_v3 as reference  # noqa: E402
from k8s_llm_rca_tpu.config import (  # noqa: E402
    TINY, TINY_EXAONE_MOE, TINY_KANANA_MOE, EngineConfig, ModelConfig,
)
from k8s_llm_rca_tpu.engine import make_engine, paged  # noqa: E402
from k8s_llm_rca_tpu.engine.sampling import SamplingParams  # noqa: E402
from k8s_llm_rca_tpu.models import llama  # noqa: E402
from k8s_llm_rca_tpu.utils import get_tokenizer  # noqa: E402
from k8s_llm_rca_tpu.utils.logging import METRICS  # noqa: E402

CFG = TINY_KANANA_MOE
PAGE, SLOTS, PPS = 4, 4, 16
TOL = 2e-5          # float32 programs against the float32 reference


def conf_of(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, for a program config."""
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "qk_head_dim": cfg.qk_head_dim, "v_head_dim": cfg.v_head_dim,
        "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.n_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling,
        "first_routed_expert": cfg.expert_first, "kv_cache_dtype": None}


@pytest.fixture(scope="module")
def weights():
    return llama.init_params(CFG, jax.random.PRNGKey(3))


def reference_forward(cfg, params, seq):
    out, held = reference.forward(conf_of(cfg), params, np.asarray(seq),
                                  np.arange(len(seq)))
    return np.asarray(out), held


# ------------------------------------------------------------------ the config


def test_the_config_says_what_a_token_caches(weights):
    assert (CFG.latent_row, CFG.kv_dim, CFG.qk_head_dim) == (48, 48, 40)
    assert CFG.q_dim == 4 * 16                    # what wo takes: heads x v
    assert CFG.latent_row < CFG.n_heads * CFG.qk_head_dim
    assert CFG.mixed_layers and CFG.n_kv_layers == 3
    assert [CFG.layer_cfg(i).n_experts for i in range(3)] == [0, 8, 8]
    layer = weights["layers"][1]
    assert layer["wq"].shape == (128, 4 * 40)
    assert layer["w_kva"].shape == (128, 48)
    assert layer["kv_norm"].shape == (32,)
    assert layer["w_kvb"].shape == (32, 4 * (24 + 16))
    assert layer["wo"].shape == (64, 128)
    assert "wk" not in layer and "wv" not in layer
    assert TINY.kv_dim == 64 and TINY.q_dim == 128        # as they were


@pytest.mark.parametrize("changes, message", [
    (dict(v_head_dim=0), "needs its three head widths"),
    (dict(head_dim=32), "head_dim=32 is the rotary table's width"),
    (dict(n_dense_layers=0, layer_pattern="***"), "in a layer table"),
    (dict(attn_layer_types=("full_attention", "sliding_attention",
                            "full_attention"), attn_window=8),
     "beside sliding-window layers"),
    (dict(qk_norm=True), "its own norm"),
    (dict(attn_scale=0.25), "its own softmax scale"),
    (dict(kv_lora_rank=0), "need kv_lora_rank > 0"),
], ids=["widths", "rotary-width", "layer-table", "window", "qk-norm",
        "scale", "no-latent"])
def test_what_latent_attention_is_not_built_beside_is_refused(changes,
                                                              message):
    with pytest.raises(ValueError, match=message):
        CFG.replace(**changes)


# -------------------------------------------- the programs against the reference


def _prefill_then_decode(cfg, params, lens, steps, tol, use_kernel):
    """Prompts of ``lens`` tokens batch-prefilled (one padding row), then
    ``steps`` decode steps of seeded tokens; the prefill's logits and the
    last step's, which read everything before it back through the latent
    pool, against the reference's full un-absorbed forward pass."""
    rng = np.random.default_rng(0)
    s_pad = -(-max(lens) // PAGE) * PAGE
    n = len(lens)
    pool = paged.init_paged_cache(cfg, 1 + SLOTS * PPS, PAGE)
    toks = np.zeros((n + 1, s_pad), np.int32)
    seqs = []
    for i, ln in enumerate(lens):
        seqs.append(list(rng.integers(3, cfg.vocab_size - 1, ln)))
        toks[i, :ln] = seqs[i]
    toks[n] = toks[n - 1]
    own = [1 + i * PPS + np.arange(PPS) for i in range(n)]
    maps = np.stack([o[:s_pad // PAGE] for o in own + own[-1:]])
    pool, logits = jax.jit(paged.paged_prefill_batch, static_argnums=0)(
        cfg, params, pool, jnp.asarray(toks),
        jnp.asarray(list(lens) + [lens[-1]], jnp.int32), jnp.asarray(maps))

    def worst_of(logits):
        return max(np.abs(np.asarray(logits[i], np.float32) - want).max()
                   / np.abs(want).max()
                   for i in range(n)
                   for want in [reference_forward(cfg, params, seqs[i])[0][-1]])

    worst = worst_of(logits)
    tables = np.full((SLOTS, PPS), paged.TRASH_PAGE, np.int32)
    tables[:n] = own
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
    worst = max(worst, worst_of(logits))
    assert worst < tol, worst
    return pool, seqs, worst


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_prefill_then_decode_through_the_latent_pool_agrees_with_the_reference(
        weights, use_kernel):
    """Float32 weights and activations, a latent pool in float32: unequal
    rows of one batched prefill (3, a page boundary at 8, 29), 11 decode
    steps across page boundaries.  ``TOL`` is 2e-5 of the largest logit: two
    float32 paths of three layers in different orders of summation
    (absorbed against un-absorbed) read 6e-7; a latent stored in bfloat16
    or a walk computed in it reads 4e-4
    (``test_one_precision_down_is_refused``).  ``kernel``: the Pallas decode
    kernel, interpreted."""
    pool, _, _ = _prefill_then_decode(CFG, weights, (3, 8, 29), 11, TOL,
                                      use_kernel)
    assert pool.v is None and pool.k.shape == (3, 1 + SLOTS * PPS, PAGE, 128)


@pytest.mark.parametrize("what", ["stored", "walked"])
def test_one_precision_down_is_refused(weights, what, monkeypatch):
    """The same float32 model with the latent STORED in bfloat16 (rounded
    on its way into the pool) or the walk COMPUTED in it (its operands
    rounded) is off by 3.9e-4 and 4.7e-4 of the largest logit: twenty times
    the tolerance the sound programs meet."""
    if what == "stored":
        down = lambda rows: rows.astype(jnp.bfloat16).astype(rows.dtype)
        pages, write = paged._write_pool_pages, paged._write_pool_rows
        monkeypatch.setattr(
            paged, "_write_pool_pages",
            lambda cfg, pool, rows, v, *a: pages(cfg, pool, down(rows), v,
                                                 *a))
        monkeypatch.setattr(
            paged, "_write_pool_rows",
            lambda cfg, pool, li, p, o, rows, v: write(cfg, pool, li, p, o,
                                                       down(rows), v))
    else:
        walk = paged.mla_paged_attention_xla
        monkeypatch.setattr(
            paged, "mla_paged_attention_xla",
            lambda q, pages, *a, **kw: walk(
                q.astype(jnp.bfloat16).astype(q.dtype),
                pages.astype(jnp.bfloat16).astype(pages.dtype), *a, **kw))
    # jit caches a trace by the function: the programs are traced anew with
    # the rounding in them, and dropped again for the tests that follow
    jax.clear_caches()
    try:
        _, _, worst = _prefill_then_decode(CFG, weights, (3, 8, 29), 11, 1.0,
                                           False)
    finally:
        jax.clear_caches()
    assert worst > 10 * TOL, worst


def test_bfloat16_agrees_within_its_rounding():
    """bfloat16 weights, activations and pool against the float32 reference
    over the same stored weights: 6e-2 of the largest logit is the
    benchmark's own tolerance for bf16 through every layer
    (``benchmarks/lib/correct.py``); three toy layers read 1-2e-2."""
    cfg = CFG.replace(dtype="bfloat16")
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    pool, _, worst = _prefill_then_decode(cfg, params, (5, 24), 9, 6e-2,
                                          True)
    assert pool.k.dtype == jnp.bfloat16 and worst > 1e-4


def test_the_pool_holds_what_the_reference_says_it_must(weights):
    """Every token's row in its pages, prefill's and decode's alike: the
    normed latent, then the rotated key in the de-interleaved order; the
    lanes behind the row zero; nothing else anywhere."""
    pool, seqs, _ = _prefill_then_decode(CFG, weights, (3, 8, 29), 11, TOL,
                                         False)
    for i, seq in enumerate(seqs):
        n = len(seq)
        _, held = reference_forward(CFG, weights, seq)
        assert held["latent"].shape == (3, n, 48)
        pages = 1 + i * PPS + np.arange(-(-n // PAGE))
        got = np.asarray(pool.k[:, pages]).reshape(3, -1, 128)[:, :n]
        np.testing.assert_allclose(got[..., :48], held["latent"], atol=2e-5)
        assert not got[..., 48:].any()
    assert pool.moe_local_pairs is not None and int(pool.moe_local_pairs[0])


def test_forward_scores_it_in_the_published_form(weights):
    seq = np.random.default_rng(2).integers(3, 500, 21)
    got = llama.forward(CFG, weights, jnp.asarray(seq)[None])[0]
    want, _ = reference_forward(CFG, weights, seq)
    assert np.abs(np.asarray(got) - want).max() / np.abs(want).max() < TOL


# ------------------------------------------------------------------ the engine


def _engine(weights, cfg=CFG, **kw):
    base = dict(max_batch=2, max_seq_len=64, page_size=PAGE, num_pages=40,
                prefill_buckets=(16, 32, 64), max_new_tokens=12,
                decode_chunk=4, prefix_cache=False, temperature=0.0)
    base.update(kw.pop("ecfg", {}))
    return make_engine(cfg, EngineConfig(**base), weights,
                       get_tokenizer(vocab_size=cfg.vocab_size), **kw)


@pytest.mark.parametrize("use_kernel", [None, True], ids=["xla", "kernel"])
def test_the_engine_serves_it(weights, use_kernel):
    """submit -> tick -> result through ``make_engine``: greedy tokens equal
    the reference's argmax over a prefill, the 4-step scan and page growth;
    the walk's rows, the prefill's pairs and the pool's bytes a token are
    counted."""
    before = METRICS.snapshot()
    engine = _engine(weights, use_kernel=use_kernel)
    prompt = [int(t) for t in np.random.default_rng(1).integers(3, 500, 19)]
    engine.submit(prompt, max_new_tokens=12)
    (result,) = engine.run_to_completion()
    seq = list(prompt)
    for tok in result.token_ids:
        assert int(np.argmax(reference_forward(CFG, weights, seq)[0][-1])) \
            == tok
        seq.append(tok)
    after = METRICS.snapshot()
    count = lambda name: after.get(name, 0) - before.get(name, 0)
    # 11 tokens behind the prefill's in three scans of 4 steps (the last
    # step's token is dropped, its walk ran): each step reads the prompt,
    # the tokens since and its own, in 3 layers
    assert count("engine.mla_decode_row_reads") == 3 * sum(
        19 + j + 1 for j in range(12))
    assert count("engine.mla_prefill_pairs") == 3 * 19 * 20 // 2
    # a row of 48 float32 values kept at 128 lanes, in 3 layers
    assert after["engine.latent_cache_bytes_per_token"] == 3 * 128 * 4
    assert count("engine.moe_routed_pairs") > count("engine.moe_local_pairs") \
        > 0


@pytest.mark.parametrize("layout", ["runs", "scattered"])
def test_the_walks_copies_are_counted_by_the_tables_runs(weights, layout):
    """A prompt whose bucket is the whole table (16 pages, one block): out
    of a fresh pool they are 1..16 and each group of eight is one copy, an
    eighth of the pages the walk visits; out of a pool whose even pages
    are held they are odd ids, no group is a run, and it is a copy a
    page."""
    engine = _engine(weights)
    if layout == "scattered":
        held = engine.allocator.alloc(engine.allocator.n_free, owner=-99)
        engine.allocator.free([p for p in held if p % 2], owner=-99)
    before = METRICS.snapshot()
    prompt = [int(t) for t in np.random.default_rng(3).integers(3, 500, 40)]
    engine.submit(prompt, max_new_tokens=5)
    (result,) = engine.run_to_completion()
    assert len(result.token_ids) == 5
    after = METRICS.snapshot()
    count = lambda name: after.get(name, 0) - before.get(name, 0)
    grid = count("engine.attn_pages_grid")
    assert grid and grid % 16 == 0
    assert count("engine.mla_decode_page_copies") == (
        grid // 8 if layout == "runs" else grid)


def test_assistant_service_serves_it(weights):
    """The serving path a cell takes: ``AssistantService`` over
    ``EngineBackend`` over the one paged engine, no flag anywhere."""
    from k8s_llm_rca_tpu.serve.api import AssistantService
    from k8s_llm_rca_tpu.serve.backend import EngineBackend, GenOptions

    engine = _engine(weights, ecfg=dict(max_seq_len=128, num_pages=80,
                                        prefill_buckets=(32, 64, 128)))
    service = AssistantService(EngineBackend(engine))
    assistant = service.create_assistant("audit", "kanana")
    thread = service.create_thread()
    service.add_message(thread.id, "pods crashloop in namespace a")
    run = service.create_run(thread.id, assistant.id,
                             gen=GenOptions(max_new_tokens=8))
    run = service.wait_run(run.id, timeout_s=120)
    assert run.status == "completed", run.status
    assert engine.pool.v is None


# ------------------------------------------------------------ the shares add up


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """One sparse MLP over a router of 16 at kanana-2's numbers (top-4 here,
    scaling 2.448, a shared expert of twice the experts' width): the uncut
    layer equals the sum over eight chips' shares of 2 with the shared
    expert, which every share carries, counted once."""
    uncut = CFG.replace(n_experts=16, router_width=0, expert_first=0)
    whole = llama.init_params(uncut, jax.random.PRNGKey(9))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 7, 128))
    lcfg = uncut.layer_cfg(1)
    want = llama._moe_mlp(lcfg, whole, x)
    shared = llama._w_mm(lcfg, llama._shared_hidden(lcfg, whole, x),
                         whole["w_shared_down"])
    total, pairs = shared, []
    for chip in range(8):
        share = CFG.replace(n_experts=2, router_width=16,
                            expert_first=2 * chip)
        part = dict(whole, **{n: whole[n][2 * chip:2 * chip + 2]
                              for n in ("w_gate", "w_up", "w_down")})
        total = total + llama._moe_mlp(share.layer_cfg(1), part, x,
                                       local_pairs=pairs) - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    assert int(sum(pairs)) == 2 * 7 * 4          # every pair is local once


# ------------------------------------------------------------------ the refusals


@pytest.mark.parametrize("kw, name", [
    (dict(ecfg=dict(prefix_cache=True)), "the prefix cache"),
    (dict(ecfg=dict(max_spilled_pages=8)), "KV spill to the host"),
    (dict(ecfg=dict(prefill_chunk_budget=16)), "chunked prefill"),
    (dict(ecfg=dict(speculative_k=2)), "speculative decoding"),
    (dict(mesh=True), "a TP, EP, CP, PP or FSDP mesh"),
], ids=["prefix", "spill", "chunked", "speculative", "mesh"])
def test_what_is_not_built_for_a_latent_pool_is_refused_by_name(weights, kw,
                                                                name):
    if kw.pop("mesh", False):
        from k8s_llm_rca_tpu.runtime import cpu_mesh_for_tests

        kw["tp_mesh"] = cpu_mesh_for_tests(2)
    with pytest.raises(ValueError) as err:
        _engine(weights, **kw)
    assert name in str(err.value)
    assert "one row of 48 values a token" in str(err.value)


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_a_quantized_latent_cache_is_refused_by_name(weights, kv_dtype):
    with pytest.raises(ValueError, match="a quantized latent cache"):
        _engine(weights, ecfg=dict(kv_cache_dtype=kv_dtype))


def test_export_and_adoption_are_refused_by_name(weights):
    engine = _engine(weights)
    with pytest.raises(ValueError, match=r"export of a run \(export_run\)"):
        engine.export_run(0)
    with pytest.raises(ValueError, match="adoption of a run's cache"):
        engine.adopt_run({"prompt_ids": [1], "generated": []}, kv={})


def test_the_programs_without_a_latent_form_refuse_by_name(weights):
    pool = paged.init_paged_cache(CFG, 8, PAGE)
    i32 = jnp.int32
    with pytest.raises(ValueError, match="chunked prefix prefill"):
        paged.paged_prefill_chunk_batch(
            CFG, weights, pool, jnp.zeros((1, 8), i32), jnp.ones((1,), i32),
            jnp.zeros((1,), i32), jnp.zeros((1, 2), i32),
            jnp.zeros((1, 2), i32))
    with pytest.raises(ValueError, match="multi-token decode"):
        paged.paged_decode_multi(CFG, weights, pool, jnp.zeros((2, 2), i32),
                                 jnp.zeros((2,), i32), jnp.zeros((2, 4), i32))
    for fn, args in ((llama.decode_step, (None, None, None)),
                     (llama.decode_multi, (None, None, None)),
                     (llama.prefill_kv, (jnp.zeros((1, 8), i32), 1)),
                     (llama.prefill_kv_cp, (jnp.zeros((1, 8), i32), 1, None)),
                     (llama._prefill_batch_kv, (jnp.zeros((1, 8), i32),
                                                jnp.ones((1,), i32)))):
        with pytest.raises(ValueError, match="caches one row of 48 values a "
                           "token, and this loop keeps keys and values per "
                           "head"):
            fn(CFG, weights, *args)


# ------------------------------------------------- the window family, as it was

# sha256 (first 16 hex digits) of the StableHLO ``TINY_EXAONE_MOE``'s programs
# lower to, taken by this very function at PR 46's parent commit (2c41bc6):
# the cell that shares the block walk, the router, the experts and the flash
# call with this model keeps its programs (tests/test_exaone_moe.py and
# tests/test_nemotron_h.py keep the other families', unedited)
EXAONE_PARENT_HLO = {
    "decode_step": "2a1e5b32bcdefb53",
    "decode_scan": "9db96b0b14d4d84d",
    "prefill_batch": "d2da2b73826d2606",
}


@pytest.mark.parametrize("program", sorted(EXAONE_PARENT_HLO))
def test_the_window_familys_programs_keep_their_hlo(program):
    cfg = TINY_EXAONE_MOE
    i32, sd = jnp.int32, jax.ShapeDtypeStruct
    weights = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
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
        EXAONE_PARENT_HLO[program]
