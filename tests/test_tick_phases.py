"""A tick's wall time as a sum of named phases, the stall a live sequence
suffers from other callers' prefills, and the benchmark readers that rest on
both, held on a program that lacks them (ISSUE 40).

CPU, TINY.  Every time asserted here is a VirtualClock's, so it is exact;
nothing here is a device metric.
"""

import importlib
import os
import sys
import types

import jax
import pytest

from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.engine.constrain import JsonGrammar
from k8s_llm_rca_tpu.engine.engine import SequenceTiming
from k8s_llm_rca_tpu.faults.plan import VirtualClock
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.obs import SITES, Tracer
from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.serve.api import AssistantService
from k8s_llm_rca_tpu.serve.backend import EngineBackend, GenOptions
from k8s_llm_rca_tpu.utils.logging import METRICS
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TINY.replace(max_seq_len=64)
PHASES = ("engine.tick.reap", "engine.tick.prefill_chunk",
          "engine.tick.admission", "engine.tick.first_tokens",
          "engine.tick.eviction", "engine.tick.decode")
UNDER = ("engine.admission.stage", "engine.admission.activate",
         "engine.scan_setup")
# the four decode programs, by the configuration that selects each
DECODE_BRANCHES = {"scan": dict(decode_chunk=4),
                   "plain": dict(decode_chunk=1),
                   "overlapped": dict(decode_chunk=1, host_overlap=True),
                   "speculative": dict(decode_chunk=1, speculative_k=2)}


@pytest.fixture(scope="module")
def model():
    return (llama.init_params(CFG, jax.random.PRNGKey(0)),
            get_tokenizer(vocab_size=CFG.vocab_size))


def build(model, **over):
    params, tok = model
    kw = dict(max_batch=4, max_seq_len=64, prefill_buckets=(16, 32),
              temperature=0.0, decode_chunk=4, prefix_cache=False,
              page_size=8, num_pages=40)
    kw.update(over)
    eng = make_engine(CFG, EngineConfig(**kw), params, tok,
                      use_kernel=False)
    eng.clock = VirtualClock()
    return eng


@pytest.fixture(scope="module")
def engine(model):
    return build(model)


@pytest.fixture
def counters():
    with METRICS.scoped() as m:
        yield m


def slow_phase(eng, monkeypatch, name, clock, seconds):
    """The engine's clock moves ``seconds`` on inside every call of the
    tick's ``name``: a prefill that takes that long."""
    orig = getattr(eng, name)

    def slowed(*args, **kwargs):
        clock.sleep(seconds)
        return orig(*args, **kwargs)

    monkeypatch.setattr(eng, name, slowed)


def names_of(tr):
    return [s.name for s in tr.spans]


# -------------------------------------------------------------- the stall


class TestPrefillStall:
    def test_a_live_sequence_stands_behind_the_others_prefill(
            self, engine, counters, monkeypatch):
        eng = engine
        clock = eng.clock = VirtualClock()
        slow_phase(eng, monkeypatch, "_tick_admission", clock, 0.25)
        first = eng.submit([1, 2, 3, 4, 5], max_new_tokens=13)
        eng.step()              # admitted alone at 0.25: nobody waits
        assert counters.count("engine.prefill_stall_seq_s") == 0.0
        assert "engine.prefill_stall_seq_s" in counters.snapshot()
        second = eng.submit([1, 2, 3], max_new_tokens=5)
        out = eng.step()        # one live sequence behind 0.25 s of prefill
        assert counters.count("engine.prefill_stall_seq_s") == 0.25
        while eng.has_work:     # decode-only ticks add nothing
            out.extend(eng.step())
        assert counters.count("engine.prefill_stall_seq_s") == 0.25
        timing = {r.seq_id: r.timing for r in out}
        assert timing[first].stall_s == 0.25
        assert timing[second].stall_s == 0.0
        assert timing[first].t_first == 0.25
        assert timing[second].t_first == 0.5

    def test_the_stall_counts_every_live_sequence(self, engine, counters,
                                                  monkeypatch):
        eng = engine
        clock = eng.clock = VirtualClock()
        slow_phase(eng, monkeypatch, "_tick_admission", clock, 0.5)
        a = eng.submit([1, 2, 3, 4, 5], max_new_tokens=13)
        b = eng.submit([5, 4, 3, 2, 1], max_new_tokens=13)
        eng.step()              # one group of two: nobody was live
        c = eng.submit([1, 2, 3], max_new_tokens=5)
        out = eng.step()        # two live sequences x 0.5 s
        assert counters.count("engine.prefill_stall_seq_s") == 1.0
        while eng.has_work:
            out.extend(eng.step())
        stalls = {r.seq_id: r.timing.stall_s for r in out}
        assert stalls == {a: 0.5, b: 0.5, c: 0.0}

    def test_the_first_tokens_wait_is_part_of_the_stall(self, engine,
                                                        counters,
                                                        monkeypatch):
        eng = engine
        clock = eng.clock = VirtualClock()
        slow_phase(eng, monkeypatch, "_tick_admission", clock, 0.25)
        # the fetch that waits for the tick's prefills, ahead of the commit
        slow_phase(eng, monkeypatch, "_fetch", clock, 0.125)
        a = eng.submit([1, 2, 3, 4, 5], max_new_tokens=9)
        eng.step()
        b = eng.submit([1, 2, 3], max_new_tokens=2)
        out = eng.step()
        # admission 0.25 + the first tokens' fetch 0.125; the decode's own
        # fetch is no stall
        assert counters.count("engine.prefill_stall_seq_s") == 0.375
        while eng.has_work:
            out.extend(eng.step())
        stalls = {r.seq_id: r.timing.stall_s for r in out}
        assert stalls == {a: 0.375, b: 0.0}

    def test_stall_reaches_the_request_span_and_the_run(self, engine,
                                                        counters,
                                                        monkeypatch):
        eng = engine
        clock = eng.clock = VirtualClock()
        slow_phase(eng, monkeypatch, "_tick_admission", clock, 0.5)
        service = AssistantService(EngineBackend(eng), clock=clock)
        a = service.create_assistant("inst", "stall", gen=GenOptions(
            max_new_tokens=9))
        runs = []
        tr = Tracer(clock=clock)
        with obs_trace.tracing(tr):
            for _ in range(2):
                t = service.create_thread()
                service.add_message(t.id, "node notready")
                runs.append(service.create_run(t.id, a.id))
                service.pump_once()
            while eng.has_work:
                service.pump_once()
            service.pump_once()
        first, second = (service.runs[r.id] for r in runs)
        assert first.timing["stall_s"] == 0.5
        assert second.timing["stall_s"] == 0.0
        by_seq = {s.args["seq"]: s.args["stall_s"] for s in tr.spans
                  if s.name == "engine.request"}
        assert by_seq == {first.timing["seq"]: 0.5,
                          second.timing["seq"]: 0.0}


# ------------------------------------------------------------- the phases


class TestTickPhases:
    def test_an_admitting_tick_then_a_decode_only_tick(self, engine,
                                                       counters):
        eng = engine
        tr = Tracer(clock=VirtualClock())
        with obs_trace.tracing(tr):
            eng.submit([1, 2, 3, 4, 5], max_new_tokens=9)
            eng.submit([5, 4, 3, 2, 1], max_new_tokens=9)   # one group
            eng.step()
            admitting = names_of(tr)
            eng.step()
            decode_only = names_of(tr)[len(admitting):]
        for name in ("engine.tick", "engine.tick.admission",
                     "engine.tick.first_tokens", "engine.tick.eviction",
                     "engine.tick.decode", "engine.admission.stage",
                     "engine.prefill", "engine.admission.activate",
                     "engine.scan_setup", "engine.decode_step"):
            assert admitting.count(name) == 1, name
        # the phases in the tick's order, each before the spans under it
        order = [n for n in admitting if n in PHASES]
        assert order == ["engine.tick.admission",
                         "engine.tick.first_tokens",
                         "engine.tick.eviction", "engine.tick.decode"]
        assert set(decode_only) == {
            "engine.tick", "engine.tick.eviction", "engine.tick.decode",
            "engine.scan_setup", "engine.decode_step", "engine.fetch",
            "engine.commit", "engine.request"}
        assert decode_only.count("engine.tick.decode") == 1
        assert "engine.tick.reap" not in admitting + decode_only

    def test_phases_are_siblings_under_the_tick(self, engine, counters):
        eng = engine
        tr = Tracer(clock=VirtualClock())
        with obs_trace.tracing(tr):
            eng.submit([1, 2, 3, 4, 5], max_new_tokens=9, deadline_s=1e9)
            while eng.has_work:
                eng.step()
        by_id = {s.span_id: s for s in tr.spans}
        phases = [s for s in tr.spans if s.name in PHASES]
        assert {s.name for s in phases} == set(PHASES) - {
            "engine.tick.prefill_chunk"}
        for s in phases:
            assert by_id[s.parent_id].name == "engine.tick", s.name
        for s in tr.spans:
            if s.name in ("engine.admission.stage",
                          "engine.admission.activate"):
                assert by_id[s.parent_id].name == "engine.tick.admission"
            if s.name in ("engine.scan_setup", "engine.decode_step"):
                assert by_id[s.parent_id].name == "engine.tick.decode"
        # the timers are the spans: the six phases fit inside the tick
        snap = counters.snapshot()
        named = sum(snap.get(p + ".total_s", 0.0) for p in PHASES)
        assert 0.0 <= snap["engine.tick.total_s"] - named
        assert (snap["engine.tick.total_s"] - named
                < 0.05 * snap["engine.tick.total_s"])

    def test_one_stage_and_one_activate_a_group(self, engine, counters):
        eng = engine
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=2)       # bucket 16
        eng.submit([5, 4, 3, 2, 1], max_new_tokens=2)       # the same group
        eng.submit(list(range(1, 21)), max_new_tokens=2)    # bucket 32
        eng.step()
        snap = counters.snapshot()
        assert snap["engine.prefill.count"] == 2.0
        assert snap["engine.admission.stage.count"] == 2.0
        assert snap["engine.admission.activate.count"] == 2.0
        assert snap["engine.tick.admission.count"] == 1.0
        assert snap["engine.tick.first_tokens.count"] == 1.0
        while eng.has_work:
            eng.step()

    @pytest.mark.parametrize("branch", DECODE_BRANCHES)
    def test_every_decode_branch_sits_in_the_decode_phase(self, model,
                                                          counters, branch):
        eng = build(model, **DECODE_BRANCHES[branch])
        taken = []
        for name in ("_speculative_tick", "_scan_tick", "_overlap_step_tick",
                     "_step_tick"):
            def spy(*args, _orig=getattr(eng, name), _name=name, **kwargs):
                taken.append(_name)
                return _orig(*args, **kwargs)
            setattr(eng, name, spy)
        # a repetitive prompt, so the n-gram draft has something to offer
        eng.generate([[1, 2, 3, 1, 2, 3, 1, 2]], max_new_tokens=6)
        want = {"scan": "_scan_tick", "plain": "_step_tick",
                "overlapped": "_overlap_step_tick",
                "speculative": "_speculative_tick"}[branch]
        assert want in taken
        snap = counters.snapshot()
        # one decode phase a tick that decodes, whatever ran inside it
        # (the overlapped path's last flush, with nothing live, is one too)
        assert snap["engine.tick.decode.count"] >= len(taken)
        assert snap["engine.tick.decode.count"] <= snap["engine.tick.count"]
        assert snap["engine.decode_step.count"] == len(taken)
        if branch != "speculative":
            assert snap["engine.scan_setup.count"] == len(taken)

    def test_a_grammar_first_token_is_committed_inside_admission(
            self, model, counters):
        eng = build(model, decode_chunk=1)
        eng.submit([1, 2, 3], max_new_tokens=4,
                   grammar=JsonGrammar(eng.tokenizer))
        eng.step()
        snap = counters.snapshot()
        # synchronous activation: no deferred first token to fetch
        assert snap["engine.tick.admission.count"] == 1.0
        assert "engine.tick.first_tokens.count" not in snap
        assert snap["engine.admission.activate.count"] == 1.0
        while eng.has_work:
            eng.step()

    def test_chunked_prefill_has_a_phase_and_its_dispatches_are_prefills(
            self, model, counters):
        eng = build(model, prefill_chunk_budget=8)
        eng.submit(list(range(1, 21)), max_new_tokens=2)    # 3 chunks of 8
        while eng.has_work:
            eng.step()
        snap = counters.snapshot()
        assert snap["engine.prefill_chunks"] == 3.0
        assert snap["engine.prefill.count"] == 3.0
        # the first chunk rides the admission, the other two their own phase
        assert snap["engine.tick.admission.count"] == 1.0
        assert snap["engine.tick.prefill_chunk.count"] == 2.0
        # the pages once, then each chunk's rows; the last chunk activates
        assert snap["engine.admission.stage.count"] == 4.0
        assert snap["engine.admission.activate.count"] == 1.0

    def test_every_new_name_is_a_registered_site(self):
        assert set(PHASES) | set(UNDER) <= SITES


# ---------------------------------------------- expired while still queued


def test_a_sequence_expired_in_the_queue_returns_its_timing(engine,
                                                            counters):
    eng = engine
    clock = eng.clock = VirtualClock(start=2.0)
    for _ in range(eng.engine_cfg.max_batch):       # fill every slot
        eng.submit([1, 2, 3], max_new_tokens=20)
    eng.step()
    sid = eng.submit([1, 2, 3, 4], max_new_tokens=4, deadline_s=0.5)
    clock.sleep(1.0)
    (res,) = [r for r in eng.step() if r.seq_id == sid]
    assert res.finish_reason == "expired"
    assert res.timing == SequenceTiming(seq_id=sid, t_arrival=2.0)
    assert (res.timing.queue_wait_s, res.timing.ttft_s,
            res.timing.stall_s) == (None, None, None)
    assert counters.count("engine.deadline_expirations") == 1
    while eng.has_work:
        eng.step()


# ----------------------------- the readers, on a program that lacks the names

READERS = ("tick_prefill_phase_ms", "tick_decode_phase_ms",
           "tick_unnamed_ms", "tick_admission_stage_ms",
           "tick_admission_activate_ms", "tick_scan_setup_ms",
           "prefill_stall_ms_per_token")
# what the tree this PR starts from (4ef9f41) records: two requests through
# this file's engine there, METRICS.snapshot() less the medians
PARENT = {
    "engine.attn_pages_grid": 128.0, "engine.attn_pages_live": 20.0,
    "engine.commit.count": 5.0, "engine.commit.total_s": 0.000462,
    "engine.d2h_syncs": 5.0, "engine.decode_step.count": 3.0,
    "engine.decode_step.total_s": 0.386962, "engine.decode_steps": 12.0,
    "engine.decode_tokens": 16.0, "engine.dispatches": 5.0,
    "engine.fetch.count": 5.0, "engine.fetch.total_s": 0.023004,
    "engine.h2d_uploads": 9.0, "engine.prefill.count": 2.0,
    "engine.prefill.total_s": 0.466754,
    "engine.prefill_padded_tokens": 32.0, "engine.prefill_tokens": 8.0,
    "engine.queue_wait.count": 2.0, "engine.queue_wait.total_s": 0.467291,
    "engine.scan_limit.full": 3.0, "engine.tick.admission.count": 2.0,
    "engine.tick.admission.total_s": 0.489837, "engine.tick.count": 3.0,
    "engine.tick.eviction.count": 3.0,
    "engine.tick.eviction.total_s": 5.2e-05,
    "engine.tick.total_s": 0.914338, "engine.tpot.count": 2.0,
    "engine.tpot.total_s": 0.05366, "engine.ttft.count": 2.0,
    "engine.ttft.total_s": 0.490308}
# every name this PR's program adds, made up
NEW = {"engine.tick.reap.count": 1.0, "engine.tick.reap.total_s": 0.001,
       "engine.tick.prefill_chunk.count": 1.0,
       "engine.tick.prefill_chunk.total_s": 0.1,
       "engine.tick.first_tokens.count": 2.0,
       "engine.tick.first_tokens.total_s": 0.02,
       "engine.tick.decode.count": 3.0, "engine.tick.decode.total_s": 0.3,
       "engine.admission.stage.count": 2.0,
       "engine.admission.stage.total_s": 0.002,
       "engine.admission.activate.count": 2.0,
       "engine.admission.activate.total_s": 0.001,
       "engine.scan_setup.count": 3.0, "engine.scan_setup.total_s": 0.003,
       "engine.prefill_stall_seq_s": 0.25}


def read(name, counters):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    reader = importlib.import_module("benchmarks.layer_metrics." + name)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("name", READERS)
class TestReadersStandOnTheParent:
    def test_no_counters(self, name):
        assert read(name, {}) is None

    def test_the_parents_counters_read_nothing(self, name):
        assert read(name, dict(PARENT)) is None

    def test_any_one_name_missing_never_raises(self, name):
        full = dict(PARENT, **NEW)
        assert isinstance(read(name, dict(full)), float)
        for gone in full:
            value = read(name, {k: v for k, v in full.items() if k != gone})
            assert value is None or isinstance(value, float), gone

    def test_the_file_is_this_prs_and_needs_nothing_of_the_program(
            self, name):
        path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                            name + ".py")
        with open(path) as f:
            source = f.read()
        assert "import" not in source.split('"""', 2)[2]


def test_the_engine_feeds_every_reader(engine, counters, monkeypatch):
    """This tree's own counters, through the seven readers: the phases
    make up the tick, and the stall is the one the clock was given."""
    eng = engine
    clock = eng.clock = VirtualClock()
    slow_phase(eng, monkeypatch, "_tick_admission", clock, 0.25)
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=13)
    eng.step()
    eng.submit([1, 2, 3], max_new_tokens=5)
    while eng.has_work:
        eng.step()
    snap = {k: v for k, v in counters.snapshot().items()
            if not k.endswith(".p50_s")}
    assert not set(PARENT) - set(snap)
    values = {name: read(name, snap) for name in READERS}
    assert all(isinstance(v, float) for v in values.values()), values
    tick_ms = 1e3 * snap["engine.tick.total_s"] / snap["engine.tick.count"]
    parts = (values["tick_prefill_phase_ms"] + values["tick_decode_phase_ms"]
             + 1e3 * snap["engine.tick.eviction.total_s"]
             / snap["engine.tick.count"] + values["tick_unnamed_ms"])
    assert parts == pytest.approx(tick_ms)
    assert 0.0 <= values["tick_unnamed_ms"] < 0.05 * tick_ms
    assert values["prefill_stall_ms_per_token"] == pytest.approx(
        1e3 * 0.25 / snap["engine.decode_tokens"])
