"""The engine's own record of what it did: request lifecycle stamps, tick
phase spans, work counters, named device programs (ISSUE 23).

CPU, TINY.  Every time here is a VirtualClock's, so it is exact; a count
repeats for a seed.  Nothing here is a device metric.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.engine.constrain import JsonGrammar, make_grammar
from k8s_llm_rca_tpu.engine.engine import SequenceTiming
from k8s_llm_rca_tpu.faults.plan import VirtualClock
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.obs import (
    SITES, Tracer, coverage_missing, critical_path,
)
from k8s_llm_rca_tpu.ops.paged_attention import block_pages
from k8s_llm_rca_tpu.runtime import profiling
from k8s_llm_rca_tpu.utils.logging import METRICS, Metrics
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

CFG = TINY.replace(max_seq_len=64)
# the same engine at two page sizes: nothing a request's stamps or the
# work counters say may depend on how the pool is cut
ENGINES = {"page8": dict(page_size=8, num_pages=40),
           "page16": dict(page_size=16, num_pages=20)}
NEW_SPANS = {"engine.tick", "engine.fetch", "engine.grammar_mask",
             "engine.commit", "engine.request"}


@pytest.fixture(scope="module")
def model():
    return (llama.init_params(CFG, jax.random.PRNGKey(0)),
            get_tokenizer(vocab_size=CFG.vocab_size))


def build(model, kind="page8", **over):
    params, tok = model
    kw = dict(max_batch=4, max_seq_len=64, prefill_buckets=(16, 32),
              temperature=0.0, decode_chunk=4, prefix_cache=False,
              **ENGINES[kind])
    kw.update(over)
    eng = make_engine(CFG, EngineConfig(**kw), params, tok,
                      use_kernel=False)
    eng.clock = VirtualClock()
    return eng


@pytest.fixture(scope="module")
def engines(model):
    """One engine of each kind for the tests that need no config of their
    own (each test drains it and reads METRICS inside ``scoped()``)."""
    return {kind: build(model, kind) for kind in ENGINES}


@pytest.fixture
def counters():
    with METRICS.scoped() as m:
        yield m


def spy_chunks(eng, monkeypatch):
    chunks = []
    orig = eng._scan_chunk

    def spy():
        chunks.append(orig())
        return chunks[-1]

    monkeypatch.setattr(eng, "_scan_chunk", spy)
    return chunks


def scan_limits(snap):
    return {k.rsplit(".", 1)[1]: v for k, v in snap.items()
            if k.startswith("engine.scan_limit.")}


# ---------------------------------------------------------------- lifecycle


class TestLifecycle:
    @pytest.mark.parametrize("kind", ENGINES)
    def test_stamps_and_durations_exact(self, engines, counters, kind):
        eng = engines[kind]
        clock = eng.clock = VirtualClock(start=1.0)
        sid = eng.submit([1, 2, 3, 4, 5], max_new_tokens=9)
        clock.sleep(0.25)
        assert eng.step() == []       # admitted, first token + a scan of 4
        clock.sleep(0.5)
        (res,) = eng.step()           # the second scan of 4 ends it
        assert res.seq_id == sid and res.completion_tokens == 9
        assert res.timing == SequenceTiming(
            seq_id=sid, t_arrival=1.0, t_admitted=1.25, t_first=1.25,
            t_last=1.75, preemptions=0, stall_s=0.0)
        assert (res.timing.queue_wait_s, res.timing.ttft_s,
                res.timing.decode_s) == (0.25, 0.25, 0.5)
        snap = counters.snapshot()
        for name, total in (("engine.queue_wait", 0.25),
                            ("engine.ttft", 0.25),
                            ("engine.tpot", 0.5 / 8)):
            assert snap[f"{name}.total_s"] == total
            assert snap[f"{name}.count"] == 1.0

    def test_single_token_request_observes_no_tpot(self, engines, counters):
        eng = engines["page8"]
        eng.clock = VirtualClock()
        (res,) = eng.generate([[1, 2, 3]], max_new_tokens=1)
        assert res.timing.t_first == res.timing.t_last
        snap = counters.snapshot()
        assert snap["engine.ttft.count"] == 1.0
        assert "engine.tpot.count" not in snap

    def test_chunked_admission_stamps_the_slot_grant(self, model, counters):
        eng = build(model, prefill_chunk_budget=8)
        clock = eng.clock
        eng.submit(list(range(1, 21)), max_new_tokens=2)    # 3 chunks of 8
        stamps = []
        while eng.has_work:
            clock.sleep(1.0)
            stamps.extend(r.timing for r in eng.step())
        (timing,) = stamps
        assert timing.t_admitted == 1.0      # slot granted with chunk one
        assert timing.t_first == 3.0         # first token after chunk three
        assert counters.count("engine.prefill_chunks") == 3

    def test_preempted_sequence_keeps_its_record(self, engines, counters):
        eng = engines["page8"]
        clock = eng.clock = VirtualClock()
        sid = eng.submit([1, 2, 3, 4, 5], max_new_tokens=13)
        clock.sleep(1.0)
        eng.step()                           # 5 tokens at t=1
        assert eng._preempt_victim()
        (req,) = eng._pending
        assert req.life.preemptions == 1 and req.life.t_first == 1.0
        out = []
        while eng.has_work:
            clock.sleep(1.0)
            out.extend(eng.step())
        (res,) = out
        assert res.seq_id == sid and res.completion_tokens == 13
        assert res.timing.t_arrival == 0.0
        assert res.timing.t_admitted == 1.0  # the FIRST slot grant
        assert res.timing.t_first == 1.0     # not the resume's re-prefill
        assert res.timing.t_last == clock.time()
        assert res.timing.preemptions == 1
        assert counters.count("engine.preemptions") == 1

    def test_request_span_under_a_tracer(self, engines, counters):
        eng = engines["page16"]
        clock = eng.clock = VirtualClock()
        tr = Tracer(clock=clock)
        with obs_trace.tracing(tr):
            sid = eng.submit([1, 2, 3], max_new_tokens=9)
            clock.sleep(0.5)
            eng.step()
            clock.sleep(0.5)
            eng.step()
        (sp,) = [s for s in tr.spans if s.name == "engine.request"]
        assert (sp.t0, sp.t1) == (0.0, 1.0)
        assert sp.args == {"seq": sid, "queue_wait_s": 0.5,
                           "prefill_s": 0.0, "decode_s": 0.5,
                           "stall_s": 0.0,
                           "tokens": 9, "preemptions": 0}


# ----------------------------------------------------------------- counters


class TestWorkCounters:
    @pytest.mark.parametrize("kind", ENGINES)
    def test_decode_steps_are_the_dispatched_chunks(self, engines, counters,
                                                    monkeypatch, kind):
        eng = engines[kind]
        chunks = spy_chunks(eng, monkeypatch)
        eng.generate([[1, 2, 3, 4, 5], [1, 2, 3]], max_new_tokens=11)
        snap = counters.snapshot()
        assert chunks and snap["engine.decode_steps"] == sum(chunks)
        assert snap["engine.decode_step.count"] == len(chunks)
        assert (snap["engine.decode_tokens"]
                <= snap["engine.decode_steps"] * eng.engine_cfg.max_batch)
        # exactly one bound is named per decode tick
        assert sum(scan_limits(snap).values()) == len(chunks)

    def test_stepwise_and_speculative_steps(self, model, counters):
        eng = build(model, decode_chunk=1)
        eng.generate([[1, 2, 3]], max_new_tokens=5)
        assert counters.count("engine.decode_steps") == 4
        assert scan_limits(counters.snapshot()) == {"full": 4.0}
        counters.reset()
        eng = build(model, decode_chunk=1, speculative_k=2)
        eng.generate([[1, 2, 3, 1, 2, 3, 1, 2]], max_new_tokens=6)
        snap = counters.snapshot()
        # a verify dispatch scores k + 1 positions
        assert (snap["engine.decode_steps"]
                == 3 * snap["engine.decode_step.count"])
        assert snap["engine.decode_tokens"] <= snap["engine.decode_steps"]

    @pytest.mark.parametrize("reason", ["full", "pages", "headroom",
                                        "grammar", "admission"])
    def test_each_scan_limit_reason_is_reachable(self, model, counters,
                                                 monkeypatch, reason):
        prompts = [[1, 2, 3, 4, 5]]
        new, grammar = 12, None
        if reason == "full":
            eng = build(model)
        elif reason == "pages":
            # three sequences in a 9-page pool: the lookahead pages of a
            # 16-step scan are not to be had
            eng = build(model, max_batch=3, num_pages=9,
                        decode_chunk=16)
            prompts = [list(range(1, 14)) + [t] for t in (20, 21, 22)]
        elif reason == "headroom":
            # 40 + 17 + 1 of 64 positions taken: 6 are left for a scan of 8
            eng = build(model, decode_chunk=8,
                        prefill_buckets=(16, 64))
            prompts, new = [list(range(1, 41))], 23
        elif reason == "grammar":
            eng = build(model)
            grammar = JsonGrammar(eng.tokenizer)       # interpreted FSM
        else:
            eng = build(model, max_batch=1,
                        prompt_admission=True)
            prompts = [[1, 2, 3], [4, 5, 6]]
        chunks = spy_chunks(eng, monkeypatch)
        for p in prompts:
            eng.submit(p, max_new_tokens=new, grammar=grammar)
        eng.run_to_completion()
        limits = scan_limits(counters.snapshot())
        assert limits.get(reason, 0) >= 1, limits
        assert sum(limits.values()) == len(chunks)

    def test_attention_pages_hand_count(self, engines, counters):
        eng = engines["page8"]
        eng.submit([1] * 5, max_new_tokens=4)
        eng.submit([1] * 17, max_new_tokens=4)
        eng.step()      # both admitted; one scan of 4 steps at lengths 5, 17
        # ceil(5 / 8) + ceil(17 / 8) = 1 + 3 pages hold context
        assert counters.count("engine.attn_pages_live") == 4 * (1 + 3)
        # the kernel visits each live slot's pages rounded up to its block
        # (here the whole table of 64 // 8 pages: 256 tokens are more),
        # and no page of a slot that holds no sequence
        block = block_pages(8, 64 // 8)
        assert block == 8 and eng.engine_cfg.max_batch > 2
        assert counters.count("engine.attn_pages_grid") == 4 * (block + block)
        eng.run_to_completion()
        snap = counters.snapshot()
        assert (snap["engine.attn_pages_live"]
                <= snap["engine.attn_pages_grid"])

    @pytest.mark.parametrize("kind", ENGINES)
    def test_prefill_padded_tokens_are_rows_times_bucket(self, engines,
                                                         counters, kind):
        eng = engines[kind]
        prompts = [[1, 2, 3], [1, 2, 3, 4, 5], [1] * 9]   # one 16 bucket
        for p in prompts:
            eng.submit(p, max_new_tokens=1)
        eng.step()
        # three rows pad to four, each to the bucket
        assert counters.count("engine.prefill_padded_tokens") == 4 * 16
        assert counters.count("engine.prefill_tokens") == 3 + 5 + 9
        eng.run_to_completion()


# -------------------------------------------------------------------- spans


class TestSeam:
    def test_fetch_of_host_arrays_opens_no_span(self, engines, counters):
        eng = engines["page8"]
        (host,) = eng._fetch(np.arange(3))
        assert host.tolist() == [0, 1, 2]
        snap = counters.snapshot()
        assert "engine.fetch.count" not in snap
        assert "engine.d2h_syncs" not in snap
        dev, host = eng._fetch(jnp.arange(3), np.arange(2))
        assert dev.tolist() == [0, 1, 2] and host.tolist() == [0, 1]
        snap = counters.snapshot()
        assert snap["engine.fetch.count"] == 1.0
        assert snap["engine.d2h_syncs"] == 1.0

    def test_annotate_feeds_three_sinks_under_one_name(self, counters,
                                                       tmp_path):
        tr = Tracer(clock=VirtualClock())
        with profiling.trace(str(tmp_path)), obs_trace.tracing(tr):
            with profiling.annotate("test.seam", replica=3):
                jnp.ones((8, 8)).block_until_ready()
        # the always-on timer
        snap = counters.snapshot()
        assert snap["test.seam.count"] == 1.0
        assert snap["test.seam.total_s"] > 0.0
        # the obs span, which alone carries the args
        (sp,) = tr.spans
        assert (sp.name, sp.args) == ("test.seam", {"replica": 3})
        # the profiler's annotation, under the bare name
        (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        names = {ev.name for plane in data.planes for line in plane.lines
                 for ev in line.events}
        assert "test.seam" in names

    def test_observe_appends_to_the_timer_reservoir(self):
        m = Metrics()
        with m.timer("t"):
            pass
        m.observe("t", 2.0)
        m.observe("u", 0.5)
        snap = m.snapshot()
        assert snap["t.count"] == 2.0 and snap["t.total_s"] >= 2.0
        assert (snap["u.count"], snap["u.total_s"]) == (1.0, 0.5)

    def test_new_names_are_registered_and_emitted(self, model, counters):
        eng = build(model, decode_chunk=1)
        tr = Tracer(clock=eng.clock)
        with obs_trace.tracing(tr):
            eng.submit([1, 2, 3], max_new_tokens=6,
                       grammar=JsonGrammar(eng.tokenizer))
            eng.run_to_completion()
        assert NEW_SPANS <= SITES
        assert not NEW_SPANS & set(coverage_missing(tr))
        # the critical-path vocabulary is derived, never emitted
        assert not [n for n in SITES if n.startswith("cp.")]
        snap = counters.snapshot()
        for name in NEW_SPANS - {"engine.request"}:
            assert snap[f"{name}.count"] >= 1.0, name

    def test_sweep_decode_rate_is_over_tick_seconds(self, counters):
        from k8s_llm_rca_tpu.sweeps.run_file import chip_metrics

        counters.inc("engine.decode_tokens", 10)
        counters.observe("engine.tick", 2.0)
        counters.observe("engine.decode_step", 0.001)   # the dispatch only
        assert chip_metrics(4.0)["decode_tokens_per_sec"] == 5.0


# ------------------------------------------------------------ program names

# attribute -> the function whose name its program carries
PROGRAMS = {
    "_prefill": "paged_prefill", "_prefill_batch": "paged_prefill_batch",
    "_prefill_chunk": "paged_prefill_chunk",
    "_prefill_chunk_batch": "paged_prefill_chunk_batch",
    "_decode": "paged_decode_step",
    "_overlap_decode": "paged_overlap_step",
    "_decode_scan": "paged_decode_scan",
    "_decode_scan_dfa": "paged_decode_scan_dfa",
    "_decode_multi": "paged_decode_multi", "_sample": "sample_tokens",
    "_sample_masked": "sample_tokens_masked",
    "_spec_dfa_greedy": "dfa_greedy_multi"}


class TestProgramNames:
    @pytest.mark.parametrize("kind", ENGINES)
    def test_lowered_programs_carry_their_function_name(self, model,
                                                        monkeypatch, kind):
        eng = build(model, kind, prefix_cache=True)
        heads = {}

        def recording(attr, jitted):
            def call(*args, **kw):
                if attr not in heads:
                    heads[attr] = jitted.lower(*args, **kw).as_text(
                        dialect="hlo").split(",", 1)[0]
                return jitted(*args, **kw)
            return call

        for attr, fn_name in PROGRAMS.items():
            jitted = getattr(eng, attr)
            assert jitted.__name__ == fn_name, attr
            monkeypatch.setattr(eng, attr, recording(attr, jitted))
        shared = list(range(1, 18))
        eng.generate([shared + [30], [1, 2, 3], [4, 5]], max_new_tokens=6)
        eng.generate([shared + [31]], max_new_tokens=2)      # a prefix hit
        eng.submit([1, 2], max_new_tokens=3,
                   grammar=JsonGrammar(eng.tokenizer))
        eng.run_to_completion()        # interpreted: the stepwise program
        eng.submit([1, 2], max_new_tokens=6,
                   grammar=make_grammar("json", eng.tokenizer))
        eng.run_to_completion()        # compiled tables ride the scan
        want = {"_prefill", "_prefill_batch", "_prefill_chunk", "_decode",
                "_decode_scan", "_decode_scan_dfa", "_sample"}
        assert want <= set(heads), sorted(heads)
        for attr, head in heads.items():
            assert head == f"HloModule jit_{PROGRAMS[attr]}", attr

    def test_named_partial_keeps_name_and_binds_keywords(self):
        def scaled(x, scale=1.0):
            return x * scale

        part = profiling.named_partial(scaled, scale=3.0)
        assert part.__name__ == "scaled" and part(2.0) == 6.0
        text = jax.jit(part).lower(jnp.ones(2)).as_text(dialect="hlo")
        assert text.startswith("HloModule jit_scaled")


# ---------------------------------------------------------------- the serve


class TestServePassThrough:
    def _service(self, eng, clock):
        from k8s_llm_rca_tpu.serve.api import AssistantService
        from k8s_llm_rca_tpu.serve.backend import EngineBackend, GenOptions

        service = AssistantService(EngineBackend(eng), clock=clock)
        a = service.create_assistant("inst", "timing",
                                     gen=GenOptions(max_new_tokens=9))
        return service, a

    def _run(self, service, a, clock, text="node notready"):
        from k8s_llm_rca_tpu.serve.api import RunStatus

        t = service.create_thread()
        service.add_message(t.id, text)
        run = service.create_run(t.id, a.id)
        while run.status not in RunStatus.TERMINAL:
            clock.sleep(0.5)
            service.pump_once()
        return run

    def test_timing_reaches_the_run_and_its_span(self, engines, counters):
        eng = engines["page8"]
        clock = eng.clock = VirtualClock()
        service, a = self._service(eng, clock)
        tr = Tracer(clock=clock)
        with obs_trace.tracing(tr):
            run = self._run(service, a, clock)
        # first pump at 0.5 (admit, first token, 4 more), second at 1.0
        assert run.timing == {"seq": run.timing["seq"], "t_arrival": 0.0,
                              "queue_wait_s": 0.5, "ttft_s": 0.5,
                              "decode_s": 0.5, "stall_s": 0.0}
        (sp,) = [s for s in tr.spans if s.name == "serve.run"]
        (req,) = [s for s in tr.spans if s.name == "engine.request"]
        assert sp.args["seq"] == req.args["seq"] == run.timing["seq"]
        assert sp.args["seq_t0"] == req.t0

    def test_critical_path_reads_the_requests_own_stamps(self, engines,
                                                         counters):
        eng = engines["page8"]
        clock = eng.clock = VirtualClock()
        service, a = self._service(eng, clock)
        tr = Tracer(clock=clock)
        with obs_trace.tracing(tr):
            # a batch-mate's prefill dispatch, 0.25 s of it, inside the
            # run's window: the dispatch spans carry no request id
            first = self._run(service, a, clock)
            t = service.create_thread()
            service.add_message(t.id, "pod crashloop")
            run = service.create_run(t.id, a.id)
            with tr.span("engine.prefill", cat="xprof"):
                clock.sleep(0.25)
            from k8s_llm_rca_tpu.serve.api import RunStatus
            while run.status not in RunStatus.TERMINAL:
                clock.sleep(0.5)
                service.pump_once()
        rows = critical_path(tr)
        segs = rows[run.id]["segments_us"]
        # arrival .. slot grant is queue wait, 0.25 + 0.5 s of it; the
        # mate's 0.25 s of prefill dispatch is not this run's prefill
        assert segs["cp.prefill"] == 0
        assert segs["cp.decode"] == 500_000
        assert segs["cp.queue_wait"] == 750_000
        assert sum(segs.values()) == rows[run.id]["total_us"] == 1_250_000
        assert (sum(rows[first.id]["segments_us"].values())
                == rows[first.id]["total_us"])
        # a run without a record keeps the overlay of dispatch spans
        tr.add_span("serve.run", 0.0, clock.time(), cat="serve",
                    args={"run": "no-record", "status": "completed"})
        assert (critical_path(tr)["no-record"]["segments_us"]["cp.prefill"]
                == 250_000)


# ------------------------------------------------------- no token is changed

# what commit 1f62705 (the parent of PR 23) generates for these prompts
PROMPTS = [[1, 17, 33, 49, 65], [1, 9, 8, 7], [1, 200, 100, 50, 25, 12, 6]]
PARENT_GREEDY = [[65] * 12, [7] * 12, [6] * 12]
PARENT_SAMPLED = [
    [212, 209, 17, 179, 250, 250, 417, 505, 99, 11, 352, 375],
    [320, 120, 15, 180, 295, 121, 411, 331, 31, 200, 3, 56],
    [454, 343, 77, 287, 287, 340, 474, 204, 42, 254, 282, 266]]


class TestTokensUnchanged:
    @pytest.mark.parametrize("host_overlap", [False, True])
    @pytest.mark.parametrize("temperature,want", [
        pytest.param(0.0, PARENT_GREEDY, id="greedy"),
        pytest.param(30.0, PARENT_SAMPLED, id="sampled")])
    def test_seeded_run_matches_the_parent(self, model, host_overlap,
                                           temperature, want):
        eng = build(model, temperature=temperature, seed=3, num_pages=24,
                    host_overlap=host_overlap)
        out = eng.generate(PROMPTS, max_new_tokens=12)
        assert [r.token_ids for r in out] == want
