"""granitemoehybrid on the normal serving path (the layer table of
models/nemotron_h.py and engine/paged.py with a gated MLP behind every
mixer, four scale factors and a tied head; preset ``TINY_GRANITE_HYBRID``):
the program against the plain reference on seeded weights, prefill in a
padded bucket and decode through the engine's state and pages against the
reference's full forward, each factor and each sublayer shown to move the
logits, a state per slot that starts from zero and survives preemption by
recompute, the counters of the state updates, every mechanism that is not
built refused by name, and a grammar-constrained run through the service.
On the CPU at a toy size: a correctness check, never a time."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import build  # noqa: E402
from benchmarks.reference import granite_hybrid as reference  # noqa: E402
from conftest import scan_kernel_in_the_engine  # noqa: E402
from k8s_llm_rca_tpu import models  # noqa: E402
from k8s_llm_rca_tpu.config import (  # noqa: E402
    TINY_GRANITE_HYBRID, EngineConfig, ModelConfig,
)
from k8s_llm_rca_tpu.engine import make_engine  # noqa: E402
from k8s_llm_rca_tpu.models import nemotron_h  # noqa: E402
from k8s_llm_rca_tpu.utils import get_tokenizer  # noqa: E402
from k8s_llm_rca_tpu.utils.logging import METRICS  # noqa: E402

CFG = TINY_GRANITE_HYBRID
SEED = 3


def conf_of(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, for a program config."""
    return {"layer_types": list(cfg.mixer_types),
            "rms_norm_eps": cfg.rms_norm_eps,
            "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
            "mamba_n_groups": cfg.ssm_groups,
            "mamba_d_state": cfg.ssm_state_size,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attn_scale,
            "logits_scaling": cfg.logits_scaling,
            "tie_word_embeddings": cfg.tie_embeddings,
            "ssm_state_dtype": cfg.ssm_state_dtype, "kv_cache_dtype": None}


@pytest.fixture(scope="module")
def params():
    return models.init_params(CFG, jax.random.PRNGKey(SEED))


def engine_of(params, cfg=CFG, use_kernel=None, **kw):
    ecfg = EngineConfig(**{**dict(
        max_batch=4, max_seq_len=256, prefill_buckets=(64, 128, 256),
        page_size=16, num_pages=64, prefix_cache=False, decode_chunk=4), **kw})
    return make_engine(cfg, ecfg, params,
                       get_tokenizer(vocab_size=cfg.vocab_size),
                       use_kernel=use_kernel)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(3, CFG.vocab_size - 1, n)]
            for n in lengths]


def tokens_of(engine, prompts, n_new):
    ids = [engine.submit(p, max_new_tokens=n_new) for p in prompts]
    got = {r.seq_id: r.token_ids for r in engine.run_to_completion()}
    return [got[i] for i in ids]


def forward_of(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(nemotron_h.forward(cfg, params,
                                             jnp.asarray([tokens]))[0])


# ----------------------------------------------------- model and reference


def test_the_preset_is_the_family_at_toy_widths(params):
    """Every kind of layer by its published name, two sublayers a layer,
    one group, no factor at 1, a softmax scale that is not 1 / sqrt(d), a
    tied head (no ``lm_head`` is made)."""
    assert set(CFG.mixer_types) == {"mamba", "attention"}
    assert CFG.layer_table == "M*MM" and CFG.layer_pattern == ""
    assert (CFG.n_ssm_layers, CFG.n_kv_layers, CFG.ssm_groups) == (3, 1, 1)
    assert 1.0 not in (CFG.embedding_multiplier, CFG.residual_multiplier,
                       CFG.logits_scaling)
    assert CFG.attn_scale * CFG.head_dim ** 0.5 == CFG.q_fold == 0.25
    assert CFG.tie_embeddings and "lm_head" not in params
    for layer in params["layers"]:
        assert {"mlp_norm", "w_gate", "w_up", "w_down"} <= set(layer)
        assert layer["w_gate"].shape == (CFG.hidden_size, CFG.block_mlp_size)


def test_forward_equals_the_reference(params):
    tokens = prompts_of([70])[0]
    want = reference.logits(conf_of(CFG), params, np.asarray(tokens),
                            np.arange(70))
    np.testing.assert_allclose(forward_of(CFG, params, tokens), want,
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("change", [
    dict(embedding_multiplier=1.0), dict(residual_multiplier=1.0),
    dict(logits_scaling=1.0), dict(attn_scale=0.0), dict(attn_scale=0.125),
    dict(block_mlp_size=0),
], ids=["embedding_multiplier", "residual_multiplier", "logits_scaling",
        "softmax-scale-dropped", "softmax-scale-wrong", "mlp-sublayer"])
def test_each_factor_and_the_mlp_sublayer_move_the_logits(params, change):
    """A program that drops one factor, takes another softmax scale or
    leaves the MLP sublayer out is not the reference's model: each alone
    moves some logit by over ten times what the comparison above allows
    it (the softmax scale moves least: one layer of four attends, and the
    tied head's logit of the token itself is the largest)."""
    tokens = prompts_of([70])[0]
    want = np.asarray(reference.logits(conf_of(CFG), params,
                                       np.asarray(tokens), np.arange(70)))
    got = forward_of(CFG.replace(**change), params, tokens)
    assert np.max(np.abs(got - want) / (2e-4 + 2e-3 * np.abs(want))) > 10


def test_a_padded_row_reads_as_the_true_one_across_a_chunk_boundary(params):
    """Pad positions of a bucket change no logit and no state; 45 true
    positions end inside the third chunk of 16."""
    tokens = prompts_of([45])[0]
    short = nemotron_h.prefill_rows(
        CFG, params, jnp.asarray([tokens + [0] * 3]), jnp.asarray([45]))
    padded = nemotron_h.prefill_rows(
        CFG, params, jnp.asarray([tokens + [7] * 19]), jnp.asarray([45]))
    for a, b in zip(short[2:5], padded[2:5]):      # states and logits
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("lengths", [[100], [60, 100, 97]],
                         ids=["one-row", "batched-rows"])
def test_prefill_then_decode_through_the_cache_equals_the_reference(
        params, lengths):
    """The engine's own prefill program in a padded bucket, then its own
    decode program step by step, through pages and the slots' state: the
    logits at every step, the attention layer's keys and values by token
    and both states after the last token, against the reference's full
    forward over the same tokens."""
    driver = build.check_driver({"model_type": "granitemoehybrid"})
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
        np.testing.assert_allclose(have, want, rtol=2e-3, atol=2e-4)
        assert set(cache) == set(want_held) == {
            "k", "v", "ssm_state", "conv_state", "ssm_grain", "k_grain",
            "v_grain"}
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


# --------------------------------------------------- the state in the engine


def test_a_reused_slot_starts_from_a_zero_state(params):
    """One slot, three sequences after one another: each reads as it does
    alone in a fresh engine, so nothing of a slot's last tenant is left."""
    prompts = prompts_of([50, 30, 61], seed=9)
    engine = engine_of(params, max_batch=1)
    assert engine.pool.ssm_state.shape[:2] == (CFG.n_ssm_layers, 1)
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


@pytest.mark.parametrize("n_prompts", [2, 4], ids=["half-empty", "all-full"])
def test_the_state_updates_are_counted_over_all_slots_and_the_live(
        params, n_prompts):
    """Every decode dispatch moves all four slots' states on;
    ``engine.ssm_decode_live_slot_steps`` counts the slots that hold a
    sequence (live x steps x Mamba layers), the whole count when none is
    empty.  The older counters of a layer table fire as for any."""
    n_m = CFG.n_ssm_layers
    lengths = [40, 33, 50, 45][:n_prompts]
    with METRICS.scoped():
        engine = engine_of(params)
        tokens_of(engine, prompts_of(lengths), 12)
        count = METRICS.count
        steps = count("engine.decode_steps")
        assert steps > 0
        assert count("engine.ssm_decode_slot_steps") == 4 * steps * n_m
        assert count("engine.ssm_decode_live_slot_steps") == (
            n_prompts * steps * n_m)
        assert count("engine.ssm_prefill_tokens") == n_m * count(
            "engine.prefill_padded_tokens") > 0
        # no expert layer, so nothing is routed
        assert count("engine.moe_routed_pairs") == 0


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
    said = str(refused.value)
    assert named in said and CFG.name in said
    assert "3 Mamba-2 layers keep a recurrent state per slot" in said
    assert "1 attention layers" in said


def test_a_mesh_is_refused(params, cpu_devices):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(cpu_devices[:2]).reshape(1, 2), ("data", "model"))
    with pytest.raises(ValueError, match="TP, EP, CP, PP or FSDP mesh"):
        make_engine(CFG, EngineConfig(prefix_cache=False), params,
                    get_tokenizer(vocab_size=CFG.vocab_size), tp_mesh=mesh)


def test_export_is_refused_at_the_call(params):
    engine = engine_of(params)
    sid = engine.submit(prompts_of([40])[0], max_new_tokens=8)
    engine.step()
    with pytest.raises(ValueError, match=r"export of a run \(export_run\)"):
        engine.export_run(sid)
    engine.run_to_completion()


@pytest.mark.parametrize("change, said", [
    (dict(mixer_types=("mamba", "attention", "mamba")),
     "mixer_types has 3 entries for n_layers=4"),
    (dict(mixer_types=("mamba", "attention", "mamba", "sliding_attention")),
     "unknown layer kind 'sliding_attention'"),
    (dict(layer_pattern="M*MM"),
     "layer_pattern and mixer_types both state the layer table"),
    (dict(attn_scale=0.1), "attn_scale=0.1 with head_dim=16"),
    (dict(mixer_types=(), block_mlp_size=192),
     "block_mlp_size=192 is a dense gated MLP behind every mixer"),
    (dict(n_experts=4), "block_mlp_size=192 is a dense gated MLP"),
    (dict(mixer_types=(), block_mlp_size=0),
     "embedding_multiplier=12.0 and residual_multiplier=0.22 are applied "
     "by the layer table's programs"),
])
def test_a_table_or_a_scale_that_does_not_fit_is_refused(change, said):
    with pytest.raises(ValueError, match=said):
        CFG.replace(**change)


def test_a_softmax_scale_that_folds_exactly_is_taken():
    """A power of two times 1 / sqrt(head_dim), whatever the head: the
    fold is exact in every dtype, so it is taken; 1 / sqrt(head_dim)
    itself folds to 1."""
    assert CFG.replace(head_dim=64, attn_scale=0.015625).q_fold == 0.125
    assert CFG.replace(attn_scale=0.25).q_fold == 1.0
    assert CFG.replace(attn_scale=0.0).q_fold == 1.0


# ------------------------------------------------------------ the service


def test_a_grammar_constrained_run_settles_and_validates(params):
    """Through ``AssistantService``: a schema-constrained run completes and
    its text parses to the schema's shape (the logits are divided by
    ``logits_scaling`` before the mask, which they must not break)."""
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
