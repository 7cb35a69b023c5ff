"""nemotron_h on the normal serving path (models/nemotron_h.py, the layer
table in engine/paged.py): the program against the plain reference on seeded
weights, prefill in a padded bucket and decode through the engine's cache
against the reference's full forward (logits, pages, both states), the
router's rules, the chip's share of the experts, the two forms of the expert
layer, a state per slot that starts from zero, survives preemption by
recompute and a snapshot, every mechanism that is not built refused by name,
a grammar-constrained run through the service, and the Llama family's
programs as they were.  On the CPU at a toy size: a correctness check, never
a time."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import build  # noqa: E402
from benchmarks.reference import nemotron_h as reference  # noqa: E402
from conftest import scan_kernel_in_the_engine  # noqa: E402
from k8s_llm_rca_tpu.config import (  # noqa: E402
    TINY, TINY_MOE, TINY_NEMOTRON_H, EngineConfig, ModelConfig,
)
from k8s_llm_rca_tpu.engine import make_engine, paged  # noqa: E402
from k8s_llm_rca_tpu.engine.sampling import SamplingParams  # noqa: E402
from k8s_llm_rca_tpu.models import llama, nemotron_h  # noqa: E402
from k8s_llm_rca_tpu.utils import get_tokenizer  # noqa: E402
from k8s_llm_rca_tpu.utils.logging import METRICS  # noqa: E402

CFG = TINY_NEMOTRON_H
SEED = 3


def conf_of(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, for a program config."""
    return {"hybrid_override_pattern": cfg.layer_pattern,
            "layer_norm_epsilon": cfg.rms_norm_eps,
            "mamba_num_heads": cfg.ssm_heads,
            "mamba_head_dim": cfg.ssm_head_dim, "n_groups": cfg.ssm_groups,
            "ssm_state_size": cfg.ssm_state_size,
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling,
            "first_routed_expert": cfg.expert_first,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "ssm_state_dtype": cfg.ssm_state_dtype, "kv_cache_dtype": None}


@pytest.fixture(scope="module")
def params():
    return nemotron_h.init_params(CFG, jax.random.PRNGKey(SEED))


def engine_of(params, use_kernel=None, **kw):
    ecfg = EngineConfig(**{**dict(
        max_batch=4, max_seq_len=256, prefill_buckets=(64, 128, 256),
        page_size=16, num_pages=64, prefix_cache=False, decode_chunk=4), **kw})
    return make_engine(CFG, ecfg, params,
                       get_tokenizer(vocab_size=CFG.vocab_size),
                       use_kernel=use_kernel)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(3, CFG.vocab_size - 1, n)]
            for n in lengths]


def tokens_of(engine, prompts, n_new):
    ids = [engine.submit(p, max_new_tokens=n_new) for p in prompts]
    got = {r.seq_id: r.token_ids for r in engine.run_to_completion()}
    return [got[i] for i in ids]


# ----------------------------------------------------- model and reference


def test_forward_equals_the_reference(params):
    tokens = prompts_of([70])[0]
    want = reference.logits(conf_of(CFG), params, np.asarray(tokens),
                            np.arange(70))
    with jax.default_matmul_precision("highest"):
        got = nemotron_h.forward(CFG, params, jnp.asarray([tokens]))[0]
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_a_padded_row_reads_as_the_true_one(params):
    """Pad positions of a bucket change no logit and no state."""
    tokens = prompts_of([45])[0]
    short = nemotron_h.prefill_rows(
        CFG, params, jnp.asarray([tokens + [0] * 3]), jnp.asarray([45]))
    padded = nemotron_h.prefill_rows(
        CFG, params, jnp.asarray([tokens + [7] * 19]), jnp.asarray([45]))
    for a, b in zip(short[2:5], padded[2:5]):      # states and logits
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_rows_of_a_group_read_as_each_row_alone(params):
    """A prefill runs its rows one after another: a row of a group gives
    what it gives alone, and a padding row (the engine pads a group to a
    power of two by repeating its last row) gives that row's again."""
    tokens = np.zeros((4, 64), np.int32)
    lengths = np.asarray([40, 64, 33, 33], np.int32)
    for row, prompt in enumerate(prompts_of([40, 64, 33])):
        tokens[row, :len(prompt)] = prompt
    tokens[3] = tokens[2]
    four = nemotron_h.prefill_rows(CFG, params, jnp.asarray(tokens),
                                   jnp.asarray(lengths))
    pairs = 0
    for row in range(3):
        alone = nemotron_h.prefill_rows(
            CFG, params, jnp.asarray(tokens[row:row + 1]),
            jnp.asarray(lengths[row:row + 1]))
        for a, b in zip(alone[:4], four[:4]):       # [layers, rows, ...]
            np.testing.assert_allclose(a[:, 0], b[:, row], rtol=1e-5,
                                       atol=1e-5)
        np.testing.assert_allclose(alone[4][0], four[4][row], rtol=1e-5,
                                   atol=1e-5)
        pairs += int(alone[5])
    for b in four[:4]:
        np.testing.assert_array_equal(b[:, 3], b[:, 2])
    np.testing.assert_array_equal(four[4][3], four[4][2])
    assert int(four[5]) == pairs + int(alone[5]) > 0


@pytest.mark.parametrize("lengths", [[100], [60, 100, 97]],
                         ids=["one-row", "batched-rows"])
def test_prefill_then_decode_through_the_cache_equals_the_reference(
        params, lengths):
    """The engine's own prefill program in a padded bucket, then its own
    decode program step by step, through pages and the slots' state: the
    logits at every step, the attention layer's keys and values by token
    and both states after the last token, against the reference's full
    forward over the same tokens; what state and pages keep below the next
    precision down is the reference's own to within a rounding that falls
    the other way in an element or two."""
    driver = build.check_driver({"model_type": "nemotron_h"})
    engine = engine_of(params)
    prompts = prompts_of(lengths, seed=len(lengths))
    steps = 6
    seqs, got = driver.run(engine, prompts, steps)
    held = driver.cached(engine, prompts, steps)
    for seq, have, cache, prompt in zip(seqs, got, held, prompts):
        first = len(prompt) - 1
        want, want_held = reference.forward(
            conf_of(CFG), params, np.asarray(seq),
            np.arange(first, first + steps + 1))
        np.testing.assert_allclose(have, want, rtol=2e-3, atol=2e-3)
        assert set(cache) == set(want_held) == {
            "k", "v", "ssm_state", "conv_state", "ssm_grain", "k_grain",
            "v_grain"}
        assert want_held["ssm_state"].shape[0] == 1         # the first
        assert want_held["ssm_grain"].shape[0] == CFG.n_ssm_layers
        for name, there in want_held.items():
            assert cache[name].shape == there.shape, name
            np.testing.assert_allclose(
                cache[name], there, atol=2e-3,
                rtol=2e-2 if name.endswith("_grain") else 2e-3, err_msg=name)


def test_engine_tokens_equal_the_plain_forward(params):
    """Greedy tokens through admission, the decode scan and retirement
    equal the plain forward's, one padded program for every length."""
    prompts = prompts_of([40, 70, 33])
    got = tokens_of(engine_of(params), prompts, 10)

    @jax.jit
    def next_token(tokens, n):
        logits = nemotron_h.forward(CFG, params, tokens, n[None])
        return jnp.argmax(logits[0, n - 1])

    for prompt, tokens in zip(prompts, got):
        seq = list(prompt)
        for _ in range(10):
            padded = jnp.asarray([seq + [0] * (96 - len(seq))])
            seq.append(int(next_token(padded, jnp.int32(len(seq)))))
        assert tokens == seq[len(prompt):]


# ------------------------------------------------------------- the router


def _expert_layer(params):
    return next(layer for kind, layer in zip(CFG.layer_pattern,
                                             params["layers"])
                if kind == "E")


def test_router_bias_moves_the_choice_and_not_the_weight(params):
    layer = _expert_layer(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, CFG.hidden_size))
    topi, w = llama._route(CFG, layer, x)
    scores = jax.nn.sigmoid(x @ layer["router"])
    # a large bias on an expert nobody chose: everyone now chooses it ...
    unchosen = int(np.setdiff1d(np.arange(CFG.n_router),
                                np.asarray(topi[0, 0]))[0])
    pushed = dict(layer, router_bias=layer["router_bias"].at[unchosen]
                  .add(10.0))
    topi2, w2 = llama._route(CFG, pushed, x)
    assert bool(jnp.all(jnp.any(topi2 == unchosen, axis=-1)))
    # ... under its own score, not score + bias
    at = jnp.argmax(topi2 == unchosen, axis=-1)
    picked = jnp.take_along_axis(scores, topi2, axis=-1)
    want = CFG.routed_scaling * picked / picked.sum(-1, keepdims=True)
    np.testing.assert_allclose(w2, want, rtol=1e-5)
    np.testing.assert_allclose(
        jnp.take_along_axis(w2, at[..., None], -1)[..., 0],
        CFG.routed_scaling * scores[..., unchosen] / picked.sum(-1),
        rtol=1e-5)


def test_router_weights_are_normalised_over_every_pick_and_scaled(params):
    """The weights of the k chosen sum to ``routed_scaling``, over those
    held here AND those held elsewhere."""
    layer = _expert_layer(params)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, CFG.hidden_size))
    topi, w = llama._route(CFG, layer, x)
    assert topi.shape == w.shape == (2, 9, CFG.n_experts_per_tok)
    np.testing.assert_allclose(w.sum(-1), CFG.routed_scaling, rtol=1e-5)
    local, held = llama._held(CFG, topi)
    assert bool(jnp.any(held)) and not bool(jnp.all(held))
    assert bool(jnp.all(jnp.where(held, local == topi - CFG.expert_first,
                                  local == CFG.n_experts)))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips each hold a quarter of the experts; the routed parts
    that the four compute, with the shared expert (which every chip
    computes alike) counted once, add up to the plain reference's layer
    over all the experts."""
    whole = CFG.replace(n_experts=16, router_width=16, expert_first=0)
    layer = _expert_layer(nemotron_h.init_params(whole,
                                                 jax.random.PRNGKey(5)))
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 24, CFG.hidden_size))
    want = reference.expert_layer(
        x[0], layer, top_k=CFG.n_experts_per_tok,
        scaling=CFG.routed_scaling, first=0, eps=CFG.rms_norm_eps) - x[0]
    no_shared = dict(layer, w_shared_down=jnp.zeros_like(
        layer["w_shared_down"]))
    total, pairs = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for share in range(4):
            cfg = CFG.replace(n_experts=4, router_width=16,
                              expert_first=4 * share)
            mine = {k: (v[4 * share:4 * share + 4]
                        if k in ("w_up", "w_down") else v)
                    for k, v in (layer if share == 0 else no_shared).items()}
            out, n_local = nemotron_h.expert_layer(cfg, mine, x)
            total = total + (out - x)[0]
            pairs += int(n_local)
    assert pairs == 24 * CFG.n_experts_per_tok       # every pair, once
    np.testing.assert_allclose(total, want, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("tokens", [5, 64, 200])
def test_dense_and_grouped_forms_agree(params, monkeypatch, tokens):
    layer = _expert_layer(params)
    x = jax.random.normal(jax.random.PRNGKey(tokens),
                          (1, tokens, CFG.hidden_size))
    outs = {}
    for form, least in (("dense", 1 << 30), ("grouped", 0)):
        monkeypatch.setattr(llama, "MOE_GROUPED_MIN_ROWS_PER_EXPERT_LATENT",
                            least)
        assert llama.moe_grouped(CFG, tokens) == (form == "grouped")
        outs[form], n_local = nemotron_h.expert_layer(CFG, layer, x)
    np.testing.assert_allclose(outs["dense"], outs["grouped"], rtol=1e-4,
                               atol=1e-5)
    topi, _ = llama._route(CFG, layer, nemotron_h.rms_norm(
        x, layer["norm"], CFG.rms_norm_eps))
    assert int(n_local) == int(llama._held(CFG, topi)[1].sum())


# --------------------------------------------------- the state in the engine


def test_a_reused_slot_starts_from_a_zero_state(params):
    """One slot, three sequences after one another: each reads as it does
    alone in a fresh engine, so nothing of a slot's last tenant is left."""
    prompts = prompts_of([50, 30, 61], seed=9)
    engine = engine_of(params, max_batch=1)
    assert engine.pool.ssm_state.shape[1] == 1
    got = tokens_of(engine, prompts, 8)
    for prompt, tokens in zip(prompts, got):
        assert tokens == tokens_of(engine_of(params, max_batch=1),
                                   [prompt], 8)[0]


def test_preemption_by_recompute_gives_the_same_tokens(params):
    prompts = prompts_of([40, 44, 36], seed=11)
    want = tokens_of(engine_of(params), prompts, 40)
    with METRICS.scoped():
        # 12 usable pages hold the three admissions (a bucket of 4 pages
        # each) and none of the pages they grow into
        tight = engine_of(params, num_pages=13, max_batch=3,
                          max_seq_len=128, prefill_buckets=(64, 128))
        got = tokens_of(tight, prompts, 40)
        assert METRICS.count("engine.preemptions") > 0
    assert got == want
    tight.allocator.check()


def test_snapshot_and_restore_give_the_same_tokens(params):
    prompts = prompts_of([40, 70], seed=13)
    want = tokens_of(engine_of(params), prompts, 20)
    engine = engine_of(params)
    ids = [engine.submit(p, max_new_tokens=20) for p in prompts]
    done = []
    for _ in range(3):
        done.extend(engine.step())
    snap = engine.snapshot_sequences()
    assert any(s["generated"] for s in snap["sequences"])
    fresh = engine_of(params)               # the state died with the process
    assert fresh.restore_sequences(snap) == sorted(ids)
    done.extend(fresh.run_to_completion())
    got = {r.seq_id: r.token_ids for r in done}
    assert [got[i] for i in ids] == want


def test_cancel_frees_the_slot_for_a_clean_sequence(params):
    prompts = prompts_of([40, 52], seed=17)
    engine = engine_of(params, max_batch=1)
    first = engine.submit(prompts[0], max_new_tokens=30)
    engine.step(), engine.step()
    assert engine.cancel_seq(first)
    assert tokens_of(engine, [prompts[1]], 8) == tokens_of(
        engine_of(params, max_batch=1), [prompts[1]], 8)


def test_counters_of_the_new_layers(params):
    with METRICS.scoped():
        engine = engine_of(params)
        tokens_of(engine, prompts_of([40, 70, 33]), 12)
        count = METRICS.count
        n_m, n_e = CFG.n_ssm_layers, CFG.layer_pattern.count("E")
        assert count("engine.ssm_prefill_tokens") == n_m * count(
            "engine.prefill_padded_tokens")
        assert count("engine.ssm_decode_slot_steps") == (
            n_m * 4 * count("engine.decode_steps"))
        routed = count("engine.moe_routed_pairs")
        assert routed == CFG.n_experts_per_tok * n_e * (
            count("engine.prefill_padded_tokens")
            + 4 * count("engine.decode_steps"))
        # half of the router's experts are held here
        assert 0.35 < count("engine.moe_local_pairs") / routed < 0.65
    with METRICS.scoped():
        # three prompts of one bucket are admitted as four rows, the last
        # a repeat of the third: four are counted, and the tokens are
        # those each prompt gives alone
        prompts = prompts_of([40, 50, 33], seed=5)
        got = tokens_of(engine_of(params), prompts, 6)
        assert METRICS.count("engine.batched_admissions") == 3
        assert METRICS.count("engine.prefill_padded_tokens") == 4 * 64
        assert METRICS.count("engine.ssm_prefill_tokens") == n_m * 4 * 64
    assert got == [tokens_of(engine_of(params), [p], 6)[0] for p in prompts]


# ------------------------------ the prefill's scan kernel (interpret mode here)


@pytest.mark.parametrize("lengths", [[40], [40, 70, 33]],
                         ids=["one-row", "three-rows-two-buckets"])
def test_the_scan_kernel_gives_the_same_tokens_and_counts_what_it_ran(
        params, monkeypatch, lengths):
    """The prefill's chunked scan is its Pallas kernel exactly where the
    prefill's other kernel may stand, at every bucket; off (a CPU) the
    engine is today's (``conftest.scan_kernel_in_the_engine``)."""
    prompts = prompts_of(lengths)
    scan_kernel_in_the_engine(
        monkeypatch, CFG.n_ssm_layers,
        lambda: tokens_of(engine_of(params), prompts, 8))


# ------------------------------------------------------------- the refusals


@pytest.mark.parametrize("kw, named", [
    (dict(prefix_cache=True), "the prefix cache (EngineConfig.prefix_cache)"),
    (dict(max_spilled_pages=8), "KV spill to the host"),
    (dict(prefill_chunk_budget=32), "chunked prefill"),
    (dict(speculative_k=2), "speculative decoding"),
])
def test_what_is_not_built_is_refused_at_construction(params, kw, named):
    with pytest.raises(ValueError) as refused:
        engine_of(params, **kw)
    assert named in str(refused.value)
    assert "Mamba-2 layers" in str(refused.value)
    assert CFG.name in str(refused.value)


def test_a_mesh_over_the_new_layers_is_refused(params, cpu_devices):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(cpu_devices[:2]).reshape(1, 2), ("data", "model"))
    with pytest.raises(ValueError, match="TP, EP, CP, PP or FSDP mesh"):
        make_engine(CFG, EngineConfig(prefix_cache=False), params,
                    get_tokenizer(vocab_size=CFG.vocab_size), tp_mesh=mesh)


def test_export_and_adoption_are_refused_at_the_call(params):
    engine = engine_of(params)
    sid = engine.submit(prompts_of([40])[0], max_new_tokens=8)
    engine.step()
    with pytest.raises(ValueError, match=r"export of a run \(export_run\)"):
        engine.export_run(sid)
    entry = {"seq_id": 99, "prompt_ids": [3, 4, 5], "generated": [],
             "remaining_new_tokens": 4, "stop_strings": [],
             "grammar": False, "priority": 1, "deadline": None}
    with pytest.raises(ValueError, match="adoption of a run's cache"):
        engine.adopt_run(entry, kv={"n_pages": 1})
    engine.run_to_completion()


@pytest.mark.parametrize("program, named", [
    ("paged_decode_multi", "multi-token decode"),
    ("paged_prefill_chunk_batch", "chunked prefix prefill"),
])
def test_programs_that_are_not_built_are_refused_at_the_call(
        params, program, named):
    pool = paged.init_paged_cache(CFG, 8, 16, n_slots=2)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    args = {"paged_decode_multi": (i32(2, 2), i32(2), i32(2, 4)),
            "paged_prefill_chunk_batch": (i32(1, 16), i32(1), i32(1),
                                          i32(1, 1), i32(1, 1))}[program]
    with pytest.raises(ValueError, match=named):
        getattr(paged, program)(CFG, params, pool, *args)


def test_a_prefill_without_slots_and_a_pool_without_them_are_refused(params):
    with pytest.raises(ValueError, match="needs n_slots"):
        paged.init_paged_cache(CFG, 8, 16)
    pool = paged.init_paged_cache(CFG, 8, 16, n_slots=2)
    with pytest.raises(ValueError, match=r"slots="):
        paged.paged_prefill_batch(CFG, params, pool,
                                  jnp.zeros((1, 16), jnp.int32),
                                  jnp.ones((1,), jnp.int32),
                                  jnp.ones((1, 1), jnp.int32))


def test_a_pattern_that_does_not_fit_is_refused():
    with pytest.raises(ValueError, match="3 letters for n_layers=5"):
        CFG.replace(layer_pattern="ME*")
    with pytest.raises(ValueError, match="unknown layer kind '-'"):
        CFG.replace(layer_pattern="ME*M-")
    with pytest.raises(ValueError, match="held of a router over 16"):
        CFG.replace(expert_first=12)


# ------------------------------------------------------------ the service


def test_a_grammar_constrained_run_settles_and_validates(params):
    """Through ``AssistantService``: a schema-constrained run on the tiny
    preset completes and its text parses to the schema's shape."""
    import json

    from k8s_llm_rca_tpu.serve.api import AssistantService
    from k8s_llm_rca_tpu.serve.backend import EngineBackend, GenOptions

    schema = {"type": "object",
              "properties": {"cause": {"type": "string"},
                             "ok": {"type": "boolean"}},
              "required": ["cause", "ok"]}
    service = AssistantService(EngineBackend(engine_of(
        params, max_seq_len=512, prefill_buckets=(128, 256, 512),
        num_pages=96)))
    assistant = service.create_assistant(
        "audit", "auditor", gen=GenOptions(max_new_tokens=160,
                                           grammar=schema))
    thread = service.create_thread()
    service.add_message(thread.id, "pod crashloops after the rollout")
    run = service.wait_run(service.create_run(thread.id, assistant.id).id)
    assert run.status == "completed"
    doc = json.loads(service.list_messages(thread.id).data[0].raw_content)
    assert set(doc) == {"cause", "ok"} and isinstance(doc["ok"], bool)


# ------------------------------------------------- the Llama family, as it was

# sha256 (first 16 hex digits) of the StableHLO each program lowers to for
# the two Llama-family presets, taken at the parent commit (4a00464) by this
# very function: the layer table walks the same blocks in the same order
PARENT_HLO = {
    "tiny.decode_step": "caa26fecc838ceb3",
    "tiny.decode_scan": "70bcedb24b876ded",
    "tiny.prefill_batch": "fe8ceccea50bfcb6",
    "tiny_moe.decode_step": "fb739e61df58535a",
    "tiny_moe.decode_scan": "0d73b62c6fa7f04c",
    "tiny_moe.prefill_batch": "2aa0b3c53e8075c0",
}


def _lowered(cfg, program):
    i32, sd = jnp.int32, jax.ShapeDtypeStruct
    weights = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    pool = jax.eval_shape(lambda: paged.init_paged_cache(cfg, 32, 16))
    b, pps = 4, 8
    if program == "decode_step":
        return jax.jit(paged.paged_decode_step, static_argnums=0,
                       static_argnames="use_kernel").lower(
            cfg, weights, pool, sd((b,), i32), sd((b,), i32),
            sd((b, pps), i32), use_kernel=False).as_text()
    if program == "decode_scan":
        return jax.jit(paged.paged_decode_scan, static_argnums=(0, 7, 8, 9),
                       static_argnames="use_kernel").lower(
            cfg, weights, pool, sd((b,), i32), sd((b,), i32),
            sd((b, pps), i32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)), 4,
            SamplingParams(), 2, use_kernel=False).as_text()
    return jax.jit(paged.paged_prefill_batch, static_argnums=0).lower(
        cfg, weights, pool, sd((2, 64), i32), sd((2,), i32),
        sd((2, 4), i32)).as_text()


@pytest.mark.parametrize("name", sorted(PARENT_HLO))
def test_llama_family_programs_keep_their_hlo(name):
    preset, program = name.split(".")
    cfg = {"tiny": TINY, "tiny_moe": TINY_MOE}[preset]
    text = _lowered(cfg, program)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_HLO[name]


# ------------------------------------ the state kernel (interpret mode here)


@pytest.mark.parametrize("n_prompts", [1, 3, 4],
                         ids=["one-live", "one-dead", "all-live"])
def test_the_state_kernel_gives_the_same_tokens_and_counts_what_it_ran(
        params, n_prompts):
    """``use_kernel=True`` (off the TPU the kernels are interpreted): the
    decode step's state update is the Pallas call on the pool, told the
    live slots by the table rows.  The tokens are the XLA form's; the
    updates the step ran are the live slots' (live x steps x Mamba
    layers), the other slots' are counted as skipped, and the two add up
    to what the XLA form runs."""
    n_m = CFG.n_ssm_layers
    prompts = prompts_of([40, 33, 50, 45][:n_prompts])
    want = tokens_of(engine_of(params), prompts, 12)
    with METRICS.scoped():
        engine = engine_of(params, use_kernel=True)
        assert tokens_of(engine, prompts, 12) == want
        count = METRICS.count
        steps = count("engine.decode_steps")
        assert steps > 0
        assert count("engine.ssm_decode_slot_steps") == (
            n_prompts * steps * n_m)
        assert count("engine.ssm_decode_skipped_slot_steps") == (
            (4 - n_prompts) * steps * n_m)
        assert count("engine.ssm_decode_live_slot_steps") == count(
            "engine.ssm_decode_slot_steps")
    with METRICS.scoped():
        # without the kernels every slot's update is run, none skipped
        tokens_of(engine_of(params, use_kernel=False), prompts, 12)
        assert METRICS.count("engine.ssm_decode_slot_steps") == (
            4 * METRICS.count("engine.decode_steps") * n_m)
        assert METRICS.count("engine.ssm_decode_skipped_slot_steps") == 0


def test_a_slot_the_kernel_passed_over_is_clean_for_its_next_tenant(params):
    """Two slots, three sequences one after another, the kernel on: a slot
    is freed, skipped while it is empty (its last tenant's state stays in
    it untouched), and taken again; each sequence reads as it does alone
    on a fresh engine without the kernel."""
    prompts = prompts_of([50, 30, 61], seed=9)
    engine = engine_of(params, max_batch=2, use_kernel=True)
    for prompt in prompts:
        assert tokens_of(engine, [prompt], 8) == tokens_of(
            engine_of(params, max_batch=2), [prompt], 8)
