"""Serve-layer tests: run-state machine, message shapes, token windows —
the contracts stage code depends on (reference:
common/openai_generic_assistant.py:92-135)."""

import time

import jax
import pytest

from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.serve import (
    AssistantService, EchoBackend, EngineBackend, GenericAssistant, RunStatus,
)
from k8s_llm_rca_tpu.serve.backend import GenOptions
from k8s_llm_rca_tpu.utils import get_tokenizer


@pytest.fixture()
def echo_service():
    tok = get_tokenizer()
    return AssistantService(EchoBackend(tok, reply="the answer"))


def make_client(service, name="helper"):
    c = GenericAssistant(service)
    c.create_assistant("you are a test assistant", name)
    c.create_thread()
    return c


def test_run_lifecycle_completed(echo_service):
    c = make_client(echo_service)
    c.add_message("question?")
    c.run_assistant()
    assert c.run.status in (RunStatus.QUEUED, RunStatus.IN_PROGRESS)
    msgs = c.wait_get_last_k_message(1)
    assert msgs is not None
    # newest-first, OpenAI content shape
    assert msgs.data[0].content[0].text.value == "the answer"
    run = c.get_run_status()
    assert run.status == RunStatus.COMPLETED
    assert run.usage["prompt_tokens"] > 0
    assert run.usage["total_tokens"] == (
        run.usage["prompt_tokens"] + run.usage["completion_tokens"])
    # thread history: system-less, user then assistant, oldest first
    roles = [m.role for m in c.thread.messages]
    assert roles == ["user", "assistant"]


def test_run_failure_returns_none():
    tok = get_tokenizer()
    service = AssistantService(EchoBackend(tok, fail=True))
    c = make_client(service)
    c.add_message("q")
    c.run_assistant()
    assert c.wait_get_last_k_message(1) is None
    assert c.get_run_status().status == RunStatus.FAILED


def test_run_expiry():
    tok = get_tokenizer()
    service = AssistantService(EchoBackend(tok, delay_pumps=10 ** 9),
                               run_timeout_s=0.05)
    c = make_client(service)
    c.add_message("q")
    c.run_assistant()
    time.sleep(0.06)
    assert c.wait_get_last_k_message(1) is None
    assert c.get_run_status().status == RunStatus.EXPIRED


def test_wait_run_timeout_releases_backend_slot():
    """wait_run(timeout_s=) must cancel the backend run and drop it from
    the in-flight map (mirroring the deadline path), so the slot frees
    and a later pump cannot flip the observed EXPIRED run to COMPLETED."""
    tok = get_tokenizer()
    backend = EchoBackend(tok, delay_pumps=2)
    service = AssistantService(backend)
    c = make_client(service)
    c.add_message("q")
    c.run_assistant()
    run = service.wait_run(c.run.id, timeout_s=0.0)
    assert run.status == RunStatus.EXPIRED
    assert run.backend_handle not in service._inflight
    for _ in range(5):                  # enough pumps to pass the delay
        service._pump()
    assert service.runs[c.run.id].status == RunStatus.EXPIRED


def test_cancel_run(echo_service):
    c = make_client(echo_service)
    c.add_message("q")
    c.run_assistant()
    c.service.cancel_run(c.run.id)
    assert c.get_run_status().status == RunStatus.CANCELLED
    assert c.wait_get_last_k_message(1) is None


def test_token_usage_window(echo_service):
    """Window semantics of reference :117-135: created_at AND completed_at
    in [tmin, tmax)."""
    c = make_client(echo_service)
    t0 = int(time.time())
    c.add_message("q1")
    c.run_assistant()
    c.wait_get_last_k_message(1)
    t1 = int(time.time()) + 1
    usage = c.get_token_usage(t0, t1)
    assert usage["total_tokens"] > 0
    # empty window before the run
    assert c.get_token_usage(t0 - 100, t0 - 50)["total_tokens"] == 0
    # half-open: window ending at created_at excludes the run
    run = c.get_run_status()
    assert c.get_token_usage(t0 - 100, run.created_at)["total_tokens"] == 0


def test_forced_prefix_and_suffix(echo_service):
    c = GenericAssistant(echo_service)
    c.create_assistant("a", "fenced",
                       gen=GenOptions(forced_prefix="```json\n", suffix="\n```"))
    c.create_thread()
    c.add_message("emit")
    c.run_assistant()
    text = c.wait_get_last_k_message(1).data[0].content[0].text.value
    assert text.startswith("```json\n") and text.endswith("\n```")


def test_engine_backend_end_to_end():
    """Two clients share one service + engine; both runs complete through
    the continuous batch."""
    cfg = TINY
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer()
    engine = PagedInferenceEngine(
        cfg, EngineConfig(max_batch=4, max_seq_len=256,
                          prefill_buckets=(64, 128), max_new_tokens=8),
        params, tok)
    service = AssistantService(EngineBackend(engine))
    c1, c2 = make_client(service, "a"), make_client(service, "b")
    c1.add_message("first incident")
    c2.add_message("second incident")
    c1.run_assistant()
    c2.run_assistant()
    m1 = c1.wait_get_last_k_message(1)
    m2 = c2.wait_get_last_k_message(1)
    assert m1 is not None and m2 is not None
    assert c1.get_run_status().status == RunStatus.COMPLETED
    assert c2.get_run_status().status == RunStatus.COMPLETED
    u = c1.get_token_usage(0, int(time.time()) + 10)
    assert u["completion_tokens"] > 0


def test_service_state_roundtrip(tmp_path, echo_service):
    """Session checkpoint/resume: the whole assistant/thread/run store
    round-trips through JSON; resumed threads answer retrieve-by-id and
    token-usage windows exactly as before the restart."""
    from k8s_llm_rca_tpu.serve.api import (
        load_service_state, save_service_state,
    )
    from k8s_llm_rca_tpu.serve.backend import EchoBackend
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    service = echo_service
    a = service.create_assistant("be terse", "helper")
    t = service.create_thread()
    service.add_message(t.id, "first question")
    run = service.create_run(t.id, a.id)
    service.wait_run(run.id)
    service.add_message(t.id, "second question")
    run2 = service.create_run(t.id, a.id)
    service.wait_run(run2.id)

    path = str(tmp_path / "serve_state.json")
    save_service_state(service, path)
    restored = load_service_state(path, EchoBackend(get_tokenizer()))

    rt = restored.retrieve_thread(t.id)
    assert [m.raw_content for m in rt.messages] == \
        [m.raw_content for m in service.threads[t.id].messages]
    assert restored.retrieve_assistant(a.id).instructions == "be terse"
    # token-usage windows over the restored runs match the live service
    from k8s_llm_rca_tpu.serve.api import GenericAssistant

    lo = min(r.created_at for r in service.runs.values())
    hi = max(r.completed_at for r in service.runs.values()) + 1

    def usage_of(svc):
        ga = GenericAssistant(svc)
        ga.retrieve_assistant(a.id)
        ga.retrieve_thread(t.id)
        return ga.get_token_usage(lo, hi)

    assert usage_of(restored) == usage_of(service)
    assert usage_of(restored)["total_tokens"] > 0
    assert [r.id for r in restored.list_runs(t.id)] == \
        [r.id for r in service.list_runs(t.id)]
    # the restored service keeps allocating non-colliding ids
    t2 = restored.create_thread()
    assert t2.id not in {t.id}
    # and a new run on the restored thread still works end-to-end
    restored.add_message(t.id, "third question")
    r3 = restored.create_run(t.id, a.id)
    assert restored.wait_run(r3.id).status == "completed"


def test_service_state_preserves_gen_options(tmp_path):
    """Restored assistants must keep their GenOptions — the RCA stage
    assistants rely on grammar/fence/stop settings for parse guarantees."""
    from k8s_llm_rca_tpu.serve.api import (
        AssistantService, load_service_state, save_service_state,
    )
    from k8s_llm_rca_tpu.serve.backend import EchoBackend, GenOptions
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    tok = get_tokenizer()
    service = AssistantService(EchoBackend(tok))
    gen = GenOptions(max_new_tokens=512, stop=("```",),
                     forced_prefix="```json\n", suffix="\n```",
                     grammar="json")
    a = service.create_assistant("plan", "locator", gen=gen)
    path = str(tmp_path / "state.json")
    save_service_state(service, path)
    # saving must not mutate the live service (snapshot idempotence)
    save_service_state(service, path)
    restored = load_service_state(path, EchoBackend(tok))
    got = restored.retrieve_assistant(a.id).gen
    assert got == gen


def test_scan_tick_matches_stepwise_near_cache_cap():
    """decode_chunk must not change WHERE a cache-capacity 'length' fires
    (regression: the scan tick once passed an off-by-one device length)."""
    import jax

    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompt = list(range(5, 25))           # 20 tokens; cap at 32

    def run(chunk):
        eng = PagedInferenceEngine(
            cfg, EngineConfig(max_batch=1, max_seq_len=32,
                              prefill_buckets=(32,), max_new_tokens=30,
                              temperature=0.0, decode_chunk=chunk),
            params, tok)
        r = eng.generate([list(prompt)], max_new_tokens=30)[0]
        return r.token_ids, r.finish_reason, r.completion_tokens

    assert run(1) == run(8)
