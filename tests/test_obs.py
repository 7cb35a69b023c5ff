"""Observability / flight recorder (k8s_llm_rca_tpu/obs/).

Covers the ISSUE-2 acceptance bars:

- deterministic traces: two seeded chaos soaks with a VirtualClock export
  byte-identical Chrome trace-event JSON, and the document validates
  (sorted ts, complete X events);
- the Prometheus renderer escapes HELP text, types counters/summaries/
  gauges correctly and never duplicates a HELP line; the serve API
  surfaces the rendering with live engine gauges;
- the SITES registry self-check: every name the tracer registry declares
  is emitted by at least one instrumented call site (instrumentation
  cannot silently rot);
- Metrics.timings growth is bounded (reservoir) with exact total/count,
  and reset()/scoped() isolate tests from the global METRICS.
"""

import jax
import pytest

from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.faults.plan import VirtualClock
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.obs import (
    SITES, Tracer, chrome_trace, chrome_trace_bytes, coverage_missing,
    prometheus_text, validate_chrome_trace,
)
from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.utils.logging import METRICS, Metrics, TIMING_RESERVOIR
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Never leak an active tracer into other tests."""
    yield
    if obs_trace.active() is not None:
        obs_trace.deactivate()


@pytest.fixture(scope="module")
def small_engine():
    """One TINY paged engine shared by the obs tests (greedy decode:
    outputs depend only on weights/prompts, same rationale as
    test_faults.shared_engine)."""
    cfg = TINY.replace(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = make_engine(
        cfg, EngineConfig(max_batch=4, max_seq_len=64,
                          page_size=8, num_pages=24,
                          prefill_buckets=(16, 32), max_new_tokens=8,
                          temperature=0.0, decode_chunk=1,
                          prefix_cache=False),
        params, tok, use_kernel=False)
    return eng, tok


# ---------------------------------------------------------------------------
# bounded metrics (satellite 1)
# ---------------------------------------------------------------------------


class TestBoundedMetrics:
    def test_reservoir_bounds_growth_keeps_exact_totals(self):
        m = Metrics()
        n = TIMING_RESERVOIR + 300
        for _ in range(n):
            with m.timer("t"):
                pass
        r = m.timings["t"]
        assert len(r) == TIMING_RESERVOIR          # bounded retention
        assert r.count == n                        # exact count
        assert r.total == pytest.approx(sum([r.total]))  # finite
        snap = m.snapshot()
        assert snap["t.count"] == float(n)         # snapshot uses EXACT count
        assert snap["t.total_s"] == pytest.approx(r.total)

    def test_p50_over_retained_window(self):
        m = Metrics()
        # bypass the timer to control sample values
        with m._lock:
            res = m.timings["t"]
        for v in range(TIMING_RESERVOIR + 100):
            res.append(float(v))
        # the retained window is the NEWEST TIMING_RESERVOIR samples
        window = res.window()
        assert len(window) == TIMING_RESERVOIR
        assert min(window) == 100.0
        import statistics
        assert m.p50("t") == statistics.median(window)

    def test_reset_and_scoped_isolation(self):
        m = Metrics()
        m.inc("a", 2)
        with m.timer("t"):
            pass
        with m.scoped():
            assert m.count("a") == 0               # fresh inside
            m.inc("a", 99)
            m.inc("only_inside")
        assert m.count("a") == 2                   # restored
        assert m.count("only_inside") == 0
        assert len(m.timings["t"]) == 1
        m.reset()
        assert m.count("a") == 0
        assert m.total("t") == 0.0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def _record_fixed(tracer: Tracer) -> None:
    clock = tracer.clock
    with tracer.span("outer", cat="test", k="v"):
        clock.sleep(0.5)
        with tracer.span("inner"):
            clock.sleep(0.25)
        tracer.event("blip", x=1)
    tracer.add_span("detached", t0=0.1, t1=0.3, args={"run": "run_0"})


class TestTracer:
    def test_deterministic_ids_and_parentage(self):
        t1, t2 = Tracer(clock=VirtualClock()), Tracer(clock=VirtualClock())
        _record_fixed(t1)
        _record_fixed(t2)
        assert [(s.span_id, s.parent_id, s.name, s.t0, s.t1)
                for s in t1.spans] == \
               [(s.span_id, s.parent_id, s.name, s.t0, s.t1)
                for s in t2.spans]
        outer, inner, detached = t1.spans
        assert inner.parent_id == outer.span_id
        assert detached.parent_id is None          # stack empty at add time
        assert t1.events[0].parent_id == outer.span_id
        assert outer.t1 - outer.t0 == pytest.approx(0.75)   # virtual time

    def test_bounded_store_counts_drops(self):
        tr = Tracer(clock=VirtualClock(), max_spans=3)
        for i in range(6):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.spans) == 3
        assert tr.dropped == 3
        doc = chrome_trace(tr)
        assert doc["metadata"]["dropped"] == 3

    def test_inactive_helpers_are_noops(self):
        assert obs_trace.active() is None
        with obs_trace.span("nope"):
            obs_trace.event("nope.event")
        # nothing recorded anywhere, nothing raised
        tr = Tracer()
        with obs_trace.tracing(tr):
            assert obs_trace.active() is tr
            with pytest.raises(RuntimeError, match="already active"):
                obs_trace.activate(Tracer())
        assert obs_trace.active() is None

    def test_flight_summary_since_mark(self):
        tr = Tracer(clock=VirtualClock())
        with tr.span("before"):
            pass
        mark = tr.mark()
        with tr.span("after"):
            tr.event("after.event")
        s = tr.flight_summary(since=mark)
        assert s["spans"] == 1 and s["events"] == 1
        assert s["by_name"] == {"after": 1}


# ---------------------------------------------------------------------------
# Chrome trace exporter
# ---------------------------------------------------------------------------


class TestChromeExport:
    def test_validates_and_is_byte_stable(self):
        t1, t2 = Tracer(clock=VirtualClock()), Tracer(clock=VirtualClock())
        _record_fixed(t1)
        _record_fixed(t2)
        d1, d2 = chrome_trace(t1), chrome_trace(t2)
        assert validate_chrome_trace(d1) == len(d1["traceEvents"]) == 4
        assert chrome_trace_bytes(d1) == chrome_trace_bytes(d2)
        ts = [e["ts"] for e in d1["traceEvents"]]
        assert ts == sorted(ts)
        for ev in d1["traceEvents"]:
            if ev["ph"] == "X":
                assert ev["dur"] >= 0

    def test_subtree_export_per_incident(self):
        tr = Tracer(clock=VirtualClock())
        with tr.span("rca.incident") as root:
            with tr.span("rca.stage.locate"):
                pass
        with tr.span("other"):
            pass
        doc = chrome_trace(tr, root=root.span_id)
        names = {e["name"] for e in doc["traceEvents"]}
        assert names == {"rca.incident", "rca.stage.locate"}
        validate_chrome_trace(doc)

    def test_validator_rejects_unsorted_and_unmatched(self):
        good = {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 2, "dur": 1, "pid": 1, "tid": 1},
            {"name": "b", "ph": "i", "ts": 1, "pid": 1, "tid": 1},
        ]}
        with pytest.raises(ValueError, match="unsorted"):
            validate_chrome_trace(good)
        with pytest.raises(ValueError, match="without matching B"):
            validate_chrome_trace({"traceEvents": [
                {"name": "a", "ph": "E", "ts": 1, "pid": 1, "tid": 1}]})
        with pytest.raises(ValueError, match="unmatched B"):
            validate_chrome_trace({"traceEvents": [
                {"name": "a", "ph": "B", "ts": 1, "pid": 1, "tid": 1}]})


# ---------------------------------------------------------------------------
# Prometheus renderer
# ---------------------------------------------------------------------------


class _StubEngine:
    """Engine-shaped stub for gauge rendering (no device work)."""

    def __init__(self):
        self._active = {0: object(), 1: object()}
        self._pending = [object()]
        self._counts = {"engine.prefix_hit_tokens": 7.0,
                        "engine.prefix_hits_l1": 5.0,
                        "engine.prefix_demotions": 9.0}
        self.allocator = type("A", (), {"n_free": 11})()
        self.prefix_cache = type("P", (), {"n_evictable": 3})()


class TestPrometheus:
    def test_counter_and_summary_families(self):
        m = Metrics()
        m.inc("engine.decode_tokens", 5)
        with m.timer("rca.incident"):
            pass
        text = prometheus_text(m)
        assert "# TYPE k8s_llm_rca_engine_decode_tokens_total counter" \
            in text
        assert "k8s_llm_rca_engine_decode_tokens_total 5" in text
        assert "# TYPE k8s_llm_rca_rca_incident_seconds summary" in text
        assert 'k8s_llm_rca_rca_incident_seconds{quantile="0.5"}' in text
        assert "k8s_llm_rca_rca_incident_seconds_count 1" in text

    def test_help_escaping_and_no_duplicate_help(self):
        m = Metrics()
        m.inc("weird\nname\\x", 1)
        m.inc("weird name x", 1)      # sanitizes to the SAME family
        text = prometheus_text(m)
        help_lines = [ln for ln in text.splitlines()
                      if ln.startswith("# HELP")]
        assert len(help_lines) == len(set(help_lines))
        # one family name appears in exactly one HELP line
        fam = "k8s_llm_rca_weird_name_x_total"
        assert sum(ln.split()[2] == fam for ln in help_lines) == 1
        # newline/backslash escaped per the exposition format
        assert "\\n" in text.split(fam)[1].splitlines()[0] \
            or any("\\n" in ln or "\\\\" in ln for ln in help_lines)
        for ln in text.splitlines():
            assert "\n" not in ln     # trivially true; no raw newlines leak

    def test_engine_gauges(self):
        text = prometheus_text(Metrics(), engine=_StubEngine())
        assert "k8s_llm_rca_engine_running_seqs 2" in text
        assert "k8s_llm_rca_engine_queued_seqs 1" in text
        assert "k8s_llm_rca_engine_free_pages 11" in text
        assert "k8s_llm_rca_engine_evictable_pages 3" in text
        assert "k8s_llm_rca_engine_prefix_hit_tokens 7" in text
        assert "k8s_llm_rca_engine_prefix_hits_l1 5" in text
        assert "k8s_llm_rca_engine_prefix_hits_l0 0" in text
        assert "k8s_llm_rca_engine_prefix_demotions 9" in text
        assert "# TYPE k8s_llm_rca_engine_free_pages gauge" in text

    def test_serve_api_surfaces_rendering(self, small_engine):
        from k8s_llm_rca_tpu.serve.api import AssistantService
        from k8s_llm_rca_tpu.serve.backend import EngineBackend

        engine, tok = small_engine
        service = AssistantService(EngineBackend(engine))
        text = service.prometheus_metrics()
        assert "k8s_llm_rca_engine_running_seqs" in text
        assert "k8s_llm_rca_engine_free_pages" in text

    def test_cluster_router_gauges(self):
        """Router-aware exposition: per-replica queue depth / occupancy
        as labelled gauges plus the alive-replica count (satellite 2 of
        the cluster subsystem)."""
        from k8s_llm_rca_tpu.cluster import ClusterRouter, Replica
        from k8s_llm_rca_tpu.serve.backend import EchoBackend, GenOptions
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        tok = get_tokenizer()
        router = ClusterRouter([
            Replica(0, EchoBackend(tok, delay_pumps=10 ** 9)),
            Replica(1, EchoBackend(tok, delay_pumps=10 ** 9))])
        router.start("p", GenOptions())
        text = prometheus_text(Metrics(), router=router)
        assert "k8s_llm_rca_cluster_replicas_alive 2" in text
        assert ('k8s_llm_rca_cluster_replica_queue_depth'
                '{replica="0"} 1') in text
        assert ('k8s_llm_rca_cluster_replica_queue_depth'
                '{replica="1"} 0') in text
        assert 'k8s_llm_rca_cluster_replica_occupancy{replica="0"}' in text
        assert "# TYPE k8s_llm_rca_cluster_replicas_alive gauge" in text
        router.fail_replica(0)
        text = prometheus_text(Metrics(), router=router)
        assert "k8s_llm_rca_cluster_replicas_alive 1" in text
        assert '{replica="0"}' not in text    # dead replicas drop out

    def test_serve_api_cluster_router_rendering(self):
        """AssistantService.prometheus_metrics detects a router backend
        and renders the cluster families."""
        from k8s_llm_rca_tpu.cluster import ClusterRouter, Replica
        from k8s_llm_rca_tpu.serve.api import AssistantService
        from k8s_llm_rca_tpu.serve.backend import EchoBackend
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        tok = get_tokenizer()
        service = AssistantService(ClusterRouter(
            [Replica(0, EchoBackend(tok)), Replica(1, EchoBackend(tok))]))
        text = service.prometheus_metrics()
        assert "k8s_llm_rca_cluster_replicas_alive 2" in text

    def test_autoscaler_fleet_gauges_two_way(self):
        """Elastic-fleet exposition (cluster/autoscale.py): the
        cluster_fleet_size{tier=} gauge and the
        cluster_scale_events_total{kind=} counter render from the
        router's autoscaler backref once actions fired — and stay
        absent on a router without one (two-way coverage)."""
        from k8s_llm_rca_tpu.cluster import (
            Autoscaler, ClusterRouter, HealthPolicy, HealthWatchdog,
            Replica, ReplicaSupervisor,
        )
        from k8s_llm_rca_tpu.serve.backend import EchoBackend
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        tok = get_tokenizer()
        mk = lambda i: Replica(i, EchoBackend(tok),         # noqa: E731
                               rebuild=lambda: EchoBackend(tok))
        router = ClusterRouter([mk(0), mk(1)])
        # no autoscaler attached: the elastic families stay absent
        text = prometheus_text(Metrics(), router=router)
        assert "cluster_fleet_size" not in text
        assert "cluster_scale_events_total" not in text
        router.attach_health(
            HealthWatchdog(HealthPolicy(miss_budget=1,
                                        hung_tick_threshold=2),
                           clock=VirtualClock()),
            ReplicaSupervisor())
        scaler = Autoscaler(router, reserve=[mk(2)])
        text = prometheus_text(Metrics(), router=router)
        assert 'k8s_llm_rca_cluster_fleet_size{tier="all"} 2' in text
        assert "# TYPE k8s_llm_rca_cluster_fleet_size gauge" in text
        assert "cluster_scale_events_total" not in text  # no actions yet
        scaler.scale_up()
        scaler.scale_down()
        text = prometheus_text(Metrics(), router=router)
        assert 'k8s_llm_rca_cluster_fleet_size{tier="all"} 2' in text
        assert ('k8s_llm_rca_cluster_scale_events_total'
                '{kind="up"} 1') in text
        assert ('k8s_llm_rca_cluster_scale_events_total'
                '{kind="down"} 1') in text
        assert '{kind="rebalance"}' not in text   # never fired: no row
        assert ("# TYPE k8s_llm_rca_cluster_scale_events_total "
                "counter") in text

    def test_autoscaler_tier_labels(self):
        """On a TierRouter the fleet-size gauge splits per tier."""
        from k8s_llm_rca_tpu.cluster import (
            Autoscaler, HealthPolicy, HealthWatchdog, Replica,
            ReplicaSupervisor, TierRouter,
        )
        from k8s_llm_rca_tpu.serve.backend import EchoBackend
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        tok = get_tokenizer()
        mk = lambda i: Replica(i, EchoBackend(tok),         # noqa: E731
                               rebuild=lambda: EchoBackend(tok))
        router = TierRouter([mk(0)], [mk(1), mk(2)])
        router.attach_health(
            HealthWatchdog(HealthPolicy(miss_budget=1,
                                        hung_tick_threshold=2),
                           clock=VirtualClock()),
            ReplicaSupervisor())
        Autoscaler(router)
        text = prometheus_text(Metrics(), router=router)
        assert ('k8s_llm_rca_cluster_fleet_size'
                '{tier="prefill"} 1') in text
        assert ('k8s_llm_rca_cluster_fleet_size'
                '{tier="decode"} 2') in text

    def test_store_fabric_families_two_way(self):
        """Cache-fabric exposition (cluster/store.py): with a live store
        handle, hits render as the labeled
        cluster_store_hits_total{tier=} counter plus op/residency
        gauges; without one — or with a DEAD store, whose stats()
        degrades to {} by the fabric's cold-miss contract — the
        families stay absent and the scrape never errors (two-way
        coverage)."""

        class _StubStore:
            def stats(self):
                return {"puts": 3.0, "gets": 5.0, "hits_l1": 2.0,
                        "hits_l2": 1.0, "misses": 2.0, "rejected": 0.0,
                        "n_host": 2, "n_disk": 1}

        class _DeadStore:
            def stats(self):
                return {}

        text = prometheus_text(Metrics())
        assert "cluster_store_" not in text
        text = prometheus_text(Metrics(), store=_StubStore())
        assert ('k8s_llm_rca_cluster_store_hits_total'
                '{tier="l1"} 2') in text
        assert ('k8s_llm_rca_cluster_store_hits_total'
                '{tier="l2"} 1') in text
        assert ("# TYPE k8s_llm_rca_cluster_store_hits_total "
                "counter") in text
        assert "k8s_llm_rca_cluster_store_puts 3" in text
        assert "k8s_llm_rca_cluster_store_misses 2" in text
        assert "k8s_llm_rca_cluster_store_n_host 2" in text
        assert "# TYPE k8s_llm_rca_cluster_store_n_disk gauge" in text
        text = prometheus_text(Metrics(), store=_DeadStore())
        assert "cluster_store_" not in text


# ---------------------------------------------------------------------------
# golden byte-identity: traced seeded chaos soak (acceptance bar)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestTracedSoak:
    def test_traced_soak_chrome_json_byte_identical(self):
        """Two runs of the seeded chaos soak with a VirtualClock-bound
        tracer must export byte-identical, Perfetto-valid Chrome trace
        JSON — the flight recorder's golden acceptance bar."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        t1, t2 = Tracer(), Tracer()
        r1 = run_chaos_soak(seed=0, n_incidents=2, backend="oracle",
                            tracer=t1)
        r2 = run_chaos_soak(seed=0, n_incidents=2, backend="oracle",
                            tracer=t2)
        d1, d2 = chrome_trace(t1), chrome_trace(t2)
        assert validate_chrome_trace(d1) > 0
        assert chrome_trace_bytes(d1) == chrome_trace_bytes(d2)
        # the traced report (incl. per-incident flight digests) is still
        # byte-identical, and tracing didn't change the soak outcome
        assert report_bytes(r1) == report_bytes(r2)
        assert r1["flight"]["spans"] > 0
        untr = run_chaos_soak(seed=0, n_incidents=2, backend="oracle")
        for row, row_t in zip(untr["incidents"], r1["incidents"]):
            assert row["status"] == row_t["status"]
            assert "flight" in row_t and row_t["flight"]["spans"] > 0

    def test_engine_tick_timeline_gauges(self, small_engine):
        """Traced paged-engine run: the tick timeline samples pool
        gauges, and tracing does not perturb greedy output."""
        engine, tok = small_engine
        prompts = [tok.encode("pod oom killed", add_bos=True),
                   tok.encode("pvc unbound", add_bos=True)]
        ref = engine.generate(prompts, max_new_tokens=6)
        tr = Tracer(clock=VirtualClock())
        with obs_trace.tracing(tr):
            got = engine.generate(prompts, max_new_tokens=6)
        assert [r.token_ids for r in ref] == [r.token_ids for r in got]
        assert tr.timeline.total > 0
        samples = tr.timeline.samples()
        last = samples[-1]
        assert last.free_pages == engine.allocator.n_free
        assert last.decode_tokens > 0 and last.prefill_tokens > 0
        assert any(s.running > 0 for s in samples)
        doc = chrome_trace(tr)
        validate_chrome_trace(doc)
        counter_names = {e["name"] for e in doc["traceEvents"]
                         if e["ph"] == "C"}
        assert {"engine.seqs", "engine.pages", "engine.tokens",
                "engine.sched", "engine.prefix"} <= counter_names

    def test_cluster_counter_tracks_separate_by_replica(self):
        """TickSamples stamped with engine_id render onto per-replica
        Chrome counter tracks (tid = replica id) and the engine.host
        track carries the router's queue-depth/occupancy gauges
        (satellite 2 of the cluster subsystem)."""
        from k8s_llm_rca_tpu.obs.timeline import TickSample

        tr = Tracer(clock=VirtualClock())
        tr.timeline.record(TickSample(
            tick=0, ts=0.001, running=1, queued=0, engine_id=0,
            cluster_queue_depth=2.0, cluster_occupancy=0.5))
        tr.timeline.record(TickSample(
            tick=0, ts=0.002, running=1, queued=1, engine_id=1,
            cluster_queue_depth=1.0, cluster_occupancy=0.25))
        doc = chrome_trace(tr)
        validate_chrome_trace(doc)
        host = sorted((e for e in doc["traceEvents"]
                       if e["name"] == "engine.host"),
                      key=lambda e: e["ts"])
        assert [e["tid"] for e in host] == [0, 1]   # separate tracks
        assert host[0]["args"]["cluster_queue_depth"] == 2.0
        assert host[1]["args"]["cluster_occupancy"] == 0.25
        # every counter event of one sample rides that sample's track
        assert {e["tid"] for e in doc["traceEvents"]
                if e["ph"] == "C" and e["ts"] == host[1]["ts"]} == {1}

    def test_scale_events_counter_track(self):
        """Autoscaler actions render as a running per-kind Chrome
        counter track (cluster.scale_events) plus fleet-size samples
        (cluster.fleet_size), mirroring the Prometheus families."""
        from k8s_llm_rca_tpu.cluster import (
            Autoscaler, ClusterRouter, HealthPolicy, HealthWatchdog,
            Replica, ReplicaSupervisor,
        )
        from k8s_llm_rca_tpu.serve.backend import EchoBackend
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        tok = get_tokenizer()
        mk = lambda i: Replica(i, EchoBackend(tok),         # noqa: E731
                               rebuild=lambda: EchoBackend(tok))
        clock = VirtualClock()
        tr = Tracer(clock=clock)
        with obs_trace.tracing(tr):
            router = ClusterRouter([mk(0), mk(1)])
            router.attach_health(
                HealthWatchdog(HealthPolicy(miss_budget=1,
                                            hung_tick_threshold=2),
                               clock=clock),
                ReplicaSupervisor())
            scaler = Autoscaler(router, reserve=[mk(2)], clock=clock)
            scaler.scale_up()
            clock.sleep(0.001)
            scaler.scale_down()
        doc = chrome_trace(tr)
        validate_chrome_trace(doc)
        tracks = [e for e in doc["traceEvents"]
                  if e["ph"] == "C" and e["name"] == "cluster.scale_events"]
        # running counts per kind, one sample per action
        assert [t["args"] for t in tracks] == [{"up": 1},
                                               {"down": 1, "up": 1}]
        fleet = [e for e in doc["traceEvents"]
                 if e["ph"] == "C" and e["name"] == "cluster.fleet_size"]
        assert [f["args"]["alive"] for f in fleet] == [3, 2]


# ---------------------------------------------------------------------------
# site registry self-check (satellite 5): instrumentation cannot rot
# ---------------------------------------------------------------------------


class TestSiteCoverage:
    def test_every_registered_site_is_emitted(self, small_engine, tmp_path):
        """Drive each instrumented layer under a tracer and assert the
        SITES registry is fully covered — a renamed or deleted call site
        fails HERE, not silently on a dashboard."""
        from k8s_llm_rca_tpu.faults.policy import (
            CircuitOpen, ResiliencePolicy, RetriesExhausted, RetryPolicy,
        )
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak
        from k8s_llm_rca_tpu.serve.api import AssistantService, RunStatus
        from k8s_llm_rca_tpu.serve.backend import EngineBackend, GenOptions

        engine, tok = small_engine
        tracers = []

        # (1) serve + backend + engine + durability sites: one journaled
        # run through the assistants API on the real engine backend, then
        # a journal replay (serve/recover.py)
        from k8s_llm_rca_tpu.serve.journal import RunJournal
        from k8s_llm_rca_tpu.serve.recover import recover_service

        wal = str(tmp_path / "serve.wal")
        tr_engine = Tracer(clock=VirtualClock())
        tracers.append(tr_engine)
        with obs_trace.tracing(tr_engine):
            service = AssistantService(EngineBackend(engine),
                                       journal=RunJournal(wal))
            a = service.create_assistant("inst", "cover", gen=GenOptions(
                max_new_tokens=4))
            t = service.create_thread()
            service.add_message(t.id, "node notready")
            run = service.create_run(t.id, a.id)
            assert service.wait_run(run.id).status == RunStatus.COMPLETED
            # a grammar-constrained run: the per-tick FSM masks are built
            # inside the engine.grammar_mask span
            masked = service.create_run(t.id, a.id, gen=GenOptions(
                max_new_tokens=8, grammar="json"))
            assert (service.wait_run(masked.id).status
                    == RunStatus.COMPLETED)
            service._journal.close()
            recovered, _ = recover_service(wal, EngineBackend(engine))
            assert recovered.runs[run.id].status == RunStatus.COMPLETED

        # (2) rca + graph sites: one clean oracle soak incident
        tr_soak = Tracer()
        tracers.append(tr_soak)
        run_chaos_soak(seed=0, n_incidents=1, backend="oracle",
                       plan_spec={}, tracer=tr_soak)

        # (3) resilience sites: retry -> breaker open -> probe close ->
        # ladder rung drop, on a virtual clock
        clock = VirtualClock()
        policy = ResiliencePolicy(
            retry=RetryPolicy(max_attempts=2, base_delay_s=0.01,
                              clock=clock),
            failure_threshold=1, reset_timeout_s=0.05)
        tr_pol = Tracer(clock=clock)
        tracers.append(tr_pol)
        with obs_trace.tracing(tr_pol):
            with pytest.raises((RetriesExhausted, CircuitOpen)):
                policy.call("dep", lambda: (_ for _ in ()).throw(
                    RuntimeError("boom")))
            clock.sleep(0.1)
            assert policy.call("dep", lambda: "ok") == "ok"
            assert policy.ladder("stage", [
                ("full", lambda: (_ for _ in ()).throw(RuntimeError("no"))),
                ("fallback", lambda: 42),
            ]) == 42

        # (4) cluster sites: route one run through a 2-replica echo
        # cluster, then fail a replica over (cluster/router.py)
        from k8s_llm_rca_tpu.cluster import ClusterRouter, Replica
        from k8s_llm_rca_tpu.serve.backend import EchoBackend

        tr_cluster = Tracer(clock=VirtualClock())
        tracers.append(tr_cluster)
        with obs_trace.tracing(tr_cluster):
            router = ClusterRouter([
                Replica(0, EchoBackend(tok, delay_pumps=10 ** 9)),
                Replica(1, EchoBackend(tok))])
            h = router.start("node notready", GenOptions(session="t"))
            router.fail_replica(router._handle_map[h][0])
            assert h in router.pump()

        # (5) overload sites: preempt a victim on a spill-enabled engine
        # so engine.spill (d2h) and engine.restore (h2d) both fire
        tr_spill = Tracer(clock=VirtualClock())
        tracers.append(tr_spill)
        spill_eng = make_engine(
            TINY.replace(max_seq_len=64),
            EngineConfig(max_batch=2, max_seq_len=64,
                         page_size=8, num_pages=24,
                         prefill_buckets=(16, 32), max_new_tokens=8,
                         temperature=0.0, decode_chunk=1,
                         prefix_cache=False, max_spilled_pages=24),
            engine.params, tok, use_kernel=False)
        with obs_trace.tracing(tr_spill):
            spill_eng.submit(tok.encode("node notready"))
            spill_eng.step()
            spill_eng.step()
            assert spill_eng._preempt_victim()
            while spill_eng.has_work:
                spill_eng.step()
        assert {"engine.spill", "engine.restore"} \
            <= tr_spill.emitted_names()

        # (5b) the tick phases a plain run does not open: a prompt over
        # the chunk budget prefills one chunk a tick
        # (engine.tick.prefill_chunk), and a sequence with a deadline
        # gives every tick something to reap (engine.tick.reap)
        tr_chunk = Tracer(clock=VirtualClock())
        tracers.append(tr_chunk)
        chunk_eng = make_engine(
            TINY.replace(max_seq_len=64),
            EngineConfig(max_batch=2, max_seq_len=64,
                         page_size=8, num_pages=24,
                         prefill_buckets=(16, 32), max_new_tokens=2,
                         temperature=0.0, decode_chunk=1,
                         prefix_cache=False, prefill_chunk_budget=8),
            engine.params, tok, use_kernel=False)
        with obs_trace.tracing(tr_chunk):
            chunk_eng.submit(list(range(1, 21)), deadline_s=1e9)
            while chunk_eng.has_work:
                chunk_eng.step()
        assert {"engine.tick.prefill_chunk", "engine.tick.reap"} \
            <= tr_chunk.emitted_names()

        # (6) self-heal sites: wedge a replica on a watchdog-armed echo
        # cluster — SUSPECT/DEAD verdicts, poison-run quarantine (K=1),
        # supervisor restart and the MTTD/MTTR spans all fire
        # (cluster/health.py)
        from k8s_llm_rca_tpu.cluster import (
            HealthPolicy, HealthWatchdog, ReplicaSupervisor,
        )

        tr_heal = Tracer(clock=VirtualClock())
        tracers.append(tr_heal)
        with obs_trace.tracing(tr_heal):
            heal_router = ClusterRouter(
                [Replica(0, EchoBackend(tok, delay_pumps=10 ** 9),
                         rebuild=lambda: EchoBackend(tok)),
                 Replica(1, EchoBackend(tok, delay_pumps=10 ** 9),
                         rebuild=lambda: EchoBackend(tok))],
                quarantine_after=1)
            heal_router.attach_health(
                HealthWatchdog(HealthPolicy(miss_budget=1,
                                            hung_tick_threshold=2),
                               clock=VirtualClock()),
                ReplicaSupervisor())
            h_heal = heal_router.start("node notready",
                                       GenOptions(session="s"))
            heal_router.replicas[heal_router._handle_map[h_heal][0]].wedge()
            heal_res = {}
            for _ in range(6):
                heal_res.update(heal_router.pump())
            assert "quarantined" in heal_res[h_heal].error
        assert {"cluster.health", "cluster.restart", "cluster.quarantine",
                "cluster.mttd", "cluster.mttr"} <= tr_heal.emitted_names()

        # (7) tiered prefix-cache sites: run a prefix-hitting prompt,
        # demote every resident page into the host store (engine
        # .prefix_demote, d2h), then re-run so tier-aware match promotes
        # them back (engine.prefix_promote, h2d)
        tr_tier = Tracer(clock=VirtualClock())
        tracers.append(tr_tier)
        tier_eng = make_engine(
            TINY.replace(max_seq_len=64),
            EngineConfig(max_batch=2, max_seq_len=64,
                         page_size=8, num_pages=24,
                         prefill_buckets=(16, 32), max_new_tokens=4,
                         temperature=0.0, prefix_cache=True,
                         prefix_host_pages=24),
            engine.params, tok, use_kernel=False)
        with obs_trace.tracing(tr_tier):
            tier_eng.submit(tok.encode("node notready on node-3"))
            while tier_eng.has_work:
                tier_eng.step()
            assert tier_eng.prefix_cache.evict(10 ** 6) > 0
            tier_eng.submit(tok.encode("node notready on node-3"))
            while tier_eng.has_work:
                tier_eng.step()
        assert {"engine.prefix_demote", "engine.prefix_promote"} \
            <= tr_tier.emitted_names()
        tier_c = tier_eng._counts or {}
        assert tier_c.get("engine.prefix_demotions", 0) > 0
        assert tier_c.get("engine.prefix_hits_l1", 0) > 0

        # (8) pipelined-sweep sites: a 2-in-flight oracle sweep parks
        # machines on the shared pump (rca.stage.queue_wait spans from
        # rca/scheduler.py), and a pump with a live-but-orphaned handle
        # on a drained engine counts an idle tick (serve/backend.py)
        from k8s_llm_rca_tpu.faults.soak import run_pipelined_sweep

        tr_sweep = Tracer()
        tracers.append(tr_sweep)
        run_pipelined_sweep(n_incidents=2, backend="oracle",
                            concurrency=2, tracer=tr_sweep)
        assert "rca.stage.queue_wait" in tr_sweep.emitted_names()

        tr_idle = Tracer(clock=VirtualClock())
        tracers.append(tr_idle)
        with obs_trace.tracing(tr_idle):
            idle_backend = EngineBackend(engine)
            idle_backend.start("node notready", GenOptions(max_new_tokens=2))
            while engine.has_work:     # drain around the backend: the
                engine.step()          # handle stays live, nothing decodable
            idle_backend.pump()
        assert "engine.idle_ticks" in tr_idle.emitted_names()
        assert (engine._counts or {}).get("engine.idle_ticks", 0) > 0

        # (9) out-of-process sites: spawn ONE real oracle worker (own
        # interpreter, ~0.5 s), run a start/pump round-trip over the
        # framed pipe, and close it — spawn span, rpc spans and the exit
        # event all fire (cluster/proc.py)
        from k8s_llm_rca_tpu.cluster.proc import build_proc_replicas

        tr_proc = Tracer(clock=VirtualClock())
        tracers.append(tr_proc)
        with obs_trace.tracing(tr_proc):
            (proc_replica,) = build_proc_replicas(1, kind="oracle")
            try:
                hp = proc_replica.backend.start("node notready",
                                                GenOptions())
                for _ in range(20):
                    if hp in proc_replica.backend.pump():
                        break
                assert not proc_replica.backend.busy(hp)
            finally:
                proc_replica.close()
        assert {"cluster.proc.spawn", "cluster.proc.rpc",
                "cluster.proc.exit"} <= tr_proc.emitted_names()

        # (10) cross-host link sites: sever ONE socket worker's link
        # (process stays alive) and relink it under a fresh nonce — the
        # link-evidence event and the relink span both fire
        # (cluster/proc.py: link death =/= process death)
        from k8s_llm_rca_tpu.cluster.wire import WireError

        tr_net = Tracer(clock=VirtualClock())
        tracers.append(tr_net)
        with obs_trace.tracing(tr_net):
            (net_replica,) = build_proc_replicas(1, kind="oracle",
                                                 transport="socket")
            try:
                net_replica.partition_link()
                with pytest.raises(WireError):
                    net_replica.backend._rpc("ping", probe=0)
                assert net_replica.backend.relink()
            finally:
                net_replica.close()
        assert {"cluster.net.partition", "cluster.net.relink"} \
            <= tr_net.emitted_names()

        # (11) disaggregated-tier sites: one run through an in-process
        # echo TierRouter — admitted on the prefill tier, moved to the
        # decode tier by the EXPORT -> ADOPT -> RELEASE handoff
        # (cluster/disagg.py), which emits the cluster.handoff event
        from k8s_llm_rca_tpu.cluster import TierRouter

        tr_disagg = Tracer(clock=VirtualClock())
        tracers.append(tr_disagg)
        with obs_trace.tracing(tr_disagg):
            disagg_router = TierRouter(
                [Replica(0, EchoBackend(tok, delay_pumps=2))],
                [Replica(1, EchoBackend(tok, delay_pumps=2))])
            h_d = disagg_router.start("node notready", GenOptions())
            disagg_out = {}
            for _ in range(8):
                disagg_out.update(disagg_router.pump())
            assert disagg_out[h_d].error is None
            assert disagg_router.handoffs == 1
        assert "cluster.handoff" in tr_disagg.emitted_names()

        # (12) elastic-fleet sites: a scale-up spawn through the
        # supervisor rebuild-recipe path and a drain-down retirement
        # both emit the cluster.scale event (cluster/autoscale.py)
        from k8s_llm_rca_tpu.cluster import Autoscaler

        tr_scale = Tracer(clock=VirtualClock())
        tracers.append(tr_scale)
        with obs_trace.tracing(tr_scale):
            scale_router = ClusterRouter(
                [Replica(0, EchoBackend(tok),
                         rebuild=lambda: EchoBackend(tok)),
                 Replica(1, EchoBackend(tok),
                         rebuild=lambda: EchoBackend(tok))])
            scale_router.attach_health(
                HealthWatchdog(HealthPolicy(miss_budget=1,
                                            hung_tick_threshold=2),
                               clock=VirtualClock()),
                ReplicaSupervisor())
            scaler = Autoscaler(
                scale_router,
                reserve=[Replica(2, EchoBackend(tok),
                                 rebuild=lambda: EchoBackend(tok))])
            up = scaler.scale_up()
            down = scaler.scale_down()
            assert up["kind"] == "up" and down["kind"] == "down"
        assert "cluster.scale" in tr_scale.emitted_names()

        # (13) fleet-telemetry + critical-path sites: ONE worker spawned
        # with the flight recorder on — its cluster.proc.serve spans
        # ship back piggybacked on reply frames (cluster.telemetry.ship)
        # and close() flushes the ring (cluster.telemetry.drain); the
        # handoff PHASE spans (cluster.handoff.export/adopt/release,
        # disagg._attempt_handoff) already fired in segment (11).  Then
        # the critical-path pass decomposes the recorded serve.run span
        # (obs/critical_path.py; it reads the tracer and emits nothing)
        from k8s_llm_rca_tpu.obs import critical_path

        tr_fleet = Tracer(clock=VirtualClock())
        tracers.append(tr_fleet)
        with obs_trace.tracing(tr_fleet):
            (tel_replica,) = build_proc_replicas(1, kind="oracle",
                                                 trace=True)
            try:
                ht = tel_replica.backend.start("node notready",
                                               GenOptions())
                for _ in range(20):
                    if ht in tel_replica.backend.pump():
                        break
            finally:
                tel_replica.close()
            tr_fleet.add_span("serve.run", 0.0, tr_fleet.now(),
                              cat="serve", args={"run": "cover-cp",
                                                 "status": "completed"})
            assert critical_path(tr_fleet)
        assert {"cluster.proc.serve", "cluster.telemetry.ship",
                "cluster.telemetry.drain"} <= tr_fleet.emitted_names()

        # (14) cache-fabric sites: spawn ONE real store server (own
        # interpreter, ~0.5 s), round-trip a page record through the
        # RemoteStore client — the serve (spawn) event and the
        # put/get success events all fire (cluster/store.py; failed
        # ops emit nothing by the cold-miss contract)
        import numpy as np

        from k8s_llm_rca_tpu.cluster.store import RemoteStore, StoreServer

        tr_store = Tracer(clock=VirtualClock())
        tracers.append(tr_store)
        with obs_trace.tracing(tr_store):
            store_server = StoreServer(host_pages=4, transport="pipe")
            try:
                remote_store = RemoteStore(server=store_server)
                rec = {"n_pages": 1,
                       "k": np.zeros((1, 1, 2, 4), np.float32),
                       "v": np.zeros((1, 1, 2, 4), np.float32)}
                remote_store.put(b"\x01" * 20, rec)
                assert remote_store.get(b"\x01" * 20) is not None
            finally:
                store_server.close()
        assert {"cluster.store.serve", "cluster.store.put",
                "cluster.store.get"} <= tr_store.emitted_names()

        missing = coverage_missing(*tracers)
        assert not missing, f"registered sites never emitted: {missing}"
        # and the registry is the full emitted vocabulary for our names:
        # anything we emit under a known prefix must be registered
        prefixes = ("engine.", "serve.", "backend.", "graph.", "rca.",
                    "resilience.", "cluster.", "cp.")
        emitted = set()
        for tr in tracers:
            emitted |= tr.emitted_names()
        unregistered = {n for n in emitted
                        if n.startswith(prefixes) and n not in SITES}
        assert not unregistered, \
            f"emitted sites missing from the registry: {unregistered}"
