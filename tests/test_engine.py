"""Engine tests: continuous batching must be invisible to each sequence.

The load-bearing invariant (SURVEY §5 race-detection note): a sequence
decoded in a shared batch — admitted/evicted alongside others — must produce
exactly the tokens it would produce alone.  This is the KV-slot-isolation
equivalent of the reference's "no double-free/alias of pages" requirement.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reference_greedy
from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine import make_engine as build_engine
from k8s_llm_rca_tpu.engine.sampling import SamplingParams, sample_tokens
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.utils import get_tokenizer


@pytest.fixture(scope="module")
def setup():
    cfg = TINY
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer()
    return cfg, params, tok


def make_engine(cfg, params, tok, **over):
    defaults = dict(max_batch=4, max_seq_len=128,
                    prefill_buckets=(16, 32, 64), max_new_tokens=16,
                    page_size=16, num_pages=40)
    defaults.update(over)
    return build_engine(cfg, EngineConfig(**defaults), params, tok,
                        use_kernel=False)


def ref_greedy(cfg, params, prompt_ids, n_new):
    """Direct model loop: the ground truth the engine must reproduce."""
    return reference_greedy(cfg, params, prompt_ids, n_new, max_seq_len=128)


def test_engine_matches_direct_decode(setup):
    cfg, params, tok = setup
    engine = make_engine(cfg, params, tok)
    prompt = tok.encode("exceeded quota: pods=50", add_bos=True)
    [res] = engine.generate([prompt], max_new_tokens=8)
    assert res.token_ids == ref_greedy(cfg, params, prompt, 8)
    assert res.finish_reason in ("length", "eos")
    assert res.prompt_tokens == len(prompt)


def test_batched_equals_solo(setup):
    """3 sequences through one shared batch == each alone (greedy)."""
    cfg, params, tok = setup
    prompts = [tok.encode(s, add_bos=True) for s in
               ("secret not found", "configmap missing from pod spec",
                "stale NFS file handle on mount")]
    solo = []
    for p in prompts:
        engine = make_engine(cfg, params, tok)
        solo.append(engine.generate([p], max_new_tokens=8)[0].token_ids)
    engine = make_engine(cfg, params, tok)
    batched = engine.generate(prompts, max_new_tokens=8)
    for got, want in zip(batched, solo):
        assert got.token_ids == want


def test_queue_overflow_is_continuous(setup):
    """6 prompts through 4 slots: later admissions reuse freed slots."""
    cfg, params, tok = setup
    prompts = [tok.encode(f"incident number {i}", add_bos=True) for i in range(6)]
    engine = make_engine(cfg, params, tok)
    results = engine.generate(prompts, max_new_tokens=6)
    assert len(results) == 6
    for p, r in zip(prompts, results):
        assert r.token_ids == ref_greedy(cfg, params, p, 6)


def test_stop_string(setup):
    cfg, params, tok = setup
    engine = make_engine(cfg, params, tok)
    prompt = tok.encode("hello", add_bos=True)
    # pick the stop string from what the model actually generates
    free = engine.generate([prompt], max_new_tokens=12)[0]
    stop = free.text[2:5]
    engine2 = make_engine(cfg, params, tok)
    [res] = engine2.generate([prompt], max_new_tokens=12, stop_strings=(stop,))
    assert res.finish_reason == "stop"
    assert stop not in res.text
    assert free.text.startswith(res.text)


@pytest.mark.parametrize("decode_chunk", [1, 8])
def test_decode_scan_matches_step_loop(setup, decode_chunk):
    """The on-device scan (8 steps a dispatch) and the stepwise tick
    both give the model's own greedy tokens."""
    cfg, params, tok = setup
    prompt = tok.encode("MountVolume.SetUp failed", add_bos=True)
    engine = make_engine(cfg, params, tok, decode_chunk=decode_chunk)
    [res] = engine.generate([prompt], max_new_tokens=9)
    assert res.token_ids == ref_greedy(cfg, params, prompt, 9)


def test_sampling_modes():
    logits = jnp.array([[0.0, 5.0, 1.0, -2.0]], jnp.float32)
    key = jax.random.PRNGKey(0)
    assert int(sample_tokens(logits, key, SamplingParams())[0]) == 1
    # top_k=1 must always pick the argmax regardless of temperature
    for seed in range(5):
        t = sample_tokens(logits, jax.random.PRNGKey(seed),
                          SamplingParams(temperature=5.0, top_k=1))
        assert int(t[0]) == 1
    # top_p tiny keeps only the top token
    for seed in range(5):
        t = sample_tokens(logits, jax.random.PRNGKey(seed),
                          SamplingParams(temperature=5.0, top_p=0.01))
        assert int(t[0]) == 1
    # high temperature with no truncation eventually samples others
    seen = {int(sample_tokens(logits, jax.random.PRNGKey(s),
                              SamplingParams(temperature=50.0))[0])
            for s in range(64)}
    assert len(seen) > 1


def test_prompt_truncation_keeps_tail(setup):
    cfg, params, tok = setup
    engine = make_engine(cfg, params, tok)
    long_prompt = tok.encode("x" * 500, add_bos=True)   # >> max_seq_len 128
    seq = engine.submit(long_prompt, max_new_tokens=4)
    results = engine.run_to_completion()
    assert results and results[0].seq_id == seq
    assert results[0].prompt_tokens <= 128 - 4 - 1


def test_max_new_exceeding_cache_is_clamped(setup):
    """Regression: max_new >= max_seq_len used to drive the prompt budget
    negative (truncation to -1 tokens) and long prompts crashed _admit."""
    cfg, params, tok = setup
    engine = make_engine(cfg, params, tok)        # max_seq_len=128
    prompt = tok.encode("y" * 300, add_bos=True)  # longer than any bucket
    engine.submit(prompt, max_new_tokens=500)     # max_new >> cache
    [res] = engine.run_to_completion()
    assert res.finish_reason == "length"
    # reserved generation room: cap//4 = 32 tokens of prompt budget headroom
    assert res.prompt_tokens <= 128 - 32 - 1
    assert res.completion_tokens >= 32


def test_engine_runs_moe_model():
    """Continuous batching over a Mixtral-style MoE model (dense
    soft-dispatch MLP in decode): greedy generate works end-to-end."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig

    cfg = TINY_MOE.replace(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = build_engine(
        cfg, EngineConfig(max_batch=2, max_seq_len=64,
                          prefill_buckets=(16, 32, 64), max_new_tokens=6,
                          temperature=0.0), params, tok)
    res = eng.generate([tok.encode("pod oom", add_bos=True),
                        tok.encode("pvc pending", add_bos=True)],
                       max_new_tokens=6)
    assert all(r.completion_tokens == 6 for r in res)


def test_batched_admission_matches_serial(setup):
    """Same-bucket pending prompts prefill in one dispatch; output must be
    bit-identical to one-at-a-time admission."""
    from k8s_llm_rca_tpu.utils.logging import METRICS

    cfg, params, tok = setup
    prompts = [tok.encode(t, add_bos=True) for t in
               ["pod oomkilled restarting", "pvc pending unbound",
                "node pressure evicting", "image pull backoff"]]

    def run(batch_admission):
        ecfg = EngineConfig(max_batch=4, max_seq_len=128,
                            prefill_buckets=(32, 64, 128),
                            max_new_tokens=8, temperature=0.0)
        eng = build_engine(cfg, ecfg, params, tok)
        eng._batch_admission = batch_admission
        out = eng.generate([list(p) for p in prompts], max_new_tokens=8)
        return [(r.token_ids, r.finish_reason) for r in out]

    before = METRICS.counters.get("engine.batched_admissions", 0)
    batched = run(True)
    assert METRICS.counters.get("engine.batched_admissions", 0) > before
    assert batched == run(False)


def test_batched_admission_with_grammar_and_quantized_cache(setup):
    """Batch admission composes with grammar first-token constraints and
    the int8 KV cache."""
    import json as jsonlib

    from k8s_llm_rca_tpu.engine.constrain import make_grammar

    cfg, params, tok = setup
    ecfg = EngineConfig(max_batch=4, max_seq_len=128,
                        prefill_buckets=(32, 64, 128), max_new_tokens=16,
                        temperature=0.0, kv_cache_dtype="int8")
    eng = build_engine(cfg, ecfg, params, tok)
    ids = []
    for _ in range(3):
        g = make_grammar("json", tok, prefer_native=False)
        ids.append(eng.submit(tok.encode("emit json", add_bos=True),
                              max_new_tokens=16, grammar=g))
    res = {r.seq_id: r for r in eng.run_to_completion()}
    for i in ids:
        jsonlib.loads(res[i].text)


def test_prompt_admission_forces_stepwise_while_queued(setup):
    """prompt_admission=True: while requests are queued the engine ticks
    stepwise (chunk == 1), so a freed slot is noticed within ONE decode
    step instead of up to decode_chunk-1; default (False) keeps the full
    scan chunk (tuned for dispatch-latency-dominated hosts)."""
    cfg, params, tok = setup
    prompts = [tok.encode("pod crashloop", add_bos=True),
               tok.encode("pvc pending", add_bos=True)]

    def build(prompt_admission):
        ecfg = EngineConfig(max_batch=1, max_seq_len=128,
                            prefill_buckets=(32,), max_new_tokens=12,
                            temperature=0.0, decode_chunk=8,
                            page_size=32,   # one page holds prompt + 2 scans
                            prompt_admission=prompt_admission)
        eng = build_engine(cfg, ecfg, params, tok)
        for p in prompts:
            # budget 12 > decode_chunk 8, so one chunked scan cannot
            # retire the active sequence mid-assert
            eng.submit(list(p), max_new_tokens=12)
        eng.step()                         # admits the first; second queues
        assert eng._pending and eng._active
        return eng

    eng = build(True)
    assert eng._scan_chunk() == 1          # stepwise while the queue waits
    res = eng.run_to_completion()
    assert len(res) == 2                   # both complete, greedy unchanged

    eng2 = build(False)
    assert eng2._scan_chunk() == 8         # default amortizes dispatches
    res2 = eng2.run_to_completion()
    for a, b in zip(res, res2):
        assert a.token_ids == b.token_ids  # knob changes latency, not output


@pytest.mark.parametrize("door", ["make_engine", "export", "flag"])
def test_the_engine_switch_is_gone(setup, door, capsys):
    """One engine: ``paged=False`` is refused by name of what was removed,
    the package exports no second engine, and the sweeps take no
    ``--paged``."""
    import k8s_llm_rca_tpu.engine as engine_pkg

    cfg, params, tok = setup
    if door == "make_engine":
        with pytest.raises(ValueError, match="contiguous-slot engine was "
                                             "removed"):
            build_engine(cfg, EngineConfig(paged=False), params, tok)
    elif door == "export":
        import k8s_llm_rca_tpu.engine.engine as engine_mod

        for mod in (engine_pkg, engine_mod):
            assert not hasattr(mod, "InferenceEngine")
        for gone in ("decode_scan", "decode_scan_dfa", "overlap_step"):
            assert not hasattr(engine_mod, gone)
    else:
        from k8s_llm_rca_tpu.sweeps import run_file

        with pytest.raises(SystemExit) as e:
            run_file.main(["--backend", "engine", "--model", "tiny",
                           "--paged"])
        assert e.value.code == 2
        assert "unrecognized arguments: --paged" in capsys.readouterr().err
