"""Fault-injection + resilience subsystem (k8s_llm_rca_tpu/faults/).

Everything here is seeded and deterministic: fault schedules are pure
functions of (seed, spec), backoff jitter is seeded, slow/stall time runs
on the virtual clock, and the chaos soak asserts byte-identical reports
across two runs of the same seed.  The soak is sized to stay inside the
tier-1 time budget (``chaos`` marker, registered in pyproject.toml).

Greedy decode ignores the sampling PRNG (temperature 0), so one shared
module engine serves every non-soak test: outputs depend only on weights
and prompts, and each test leaves the engine drained (asserted).
"""

import jax
import jax.numpy as jnp
import pytest

from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.faults.plan import Fault, FaultPlan, VirtualClock
from k8s_llm_rca_tpu.faults.policy import (
    CircuitBreaker, CircuitOpen, ResiliencePolicy, ResilientExecutor,
    RetriesExhausted, RetryPolicy,
)
from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
from k8s_llm_rca_tpu.graph.fixtures import build_stategraph
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.serve.api import AssistantService, RunStatus
from k8s_llm_rca_tpu.serve.backend import BudgetError, EngineBackend, GenOptions
from k8s_llm_rca_tpu.utils.logging import METRICS
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer


@pytest.fixture(autouse=True)
def _disarmed():
    """Never leak an armed plan into other tests."""
    yield
    if inject.active() is not None:
        inject.disarm()


@pytest.fixture(scope="module")
def shared_engine():
    """One TINY paged engine for every non-soak test (see module
    docstring); decode_chunk=1 so tick-indexed fault schedules see one
    poll per decode step."""
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
# plan: determinism
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_same_seed_same_schedule(self):
        spec = {"site.a": {"rate": 0.5, "horizon": 40,
                           "kinds": ("error", "timeout")},
                "site.b": {"indices": {3: "empty"}}}
        p1 = FaultPlan.from_spec(7, spec)
        p2 = FaultPlan.from_spec(7, spec)
        assert p1._by_site == p2._by_site
        p3 = FaultPlan.from_spec(8, spec)
        assert p1._by_site != p3._by_site   # overwhelmingly at rate 0.5/40

    def test_poll_fires_at_scheduled_index_only(self):
        plan = FaultPlan([Fault("s", 2, "error")])
        assert plan.poll("s") is None
        assert plan.poll("s") is None
        f = plan.poll("s")
        assert f is not None and f.kind == "error"
        assert plan.poll("s") is None
        snap = plan.snapshot()
        assert snap["polls"] == {"s": 4}
        assert snap["fired"] == [["s", 2, "error"]]

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultPlan([Fault("s", 0, "kaboom")])

    def test_double_arm_rejected(self):
        with inject.armed(FaultPlan()):
            with pytest.raises(RuntimeError, match="already armed"):
                inject.arm(FaultPlan())


# ---------------------------------------------------------------------------
# policy: retry / breaker / resilient executor
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def test_succeeds_after_transient_failures_on_virtual_clock(self):
        clock = VirtualClock()
        policy = RetryPolicy(max_attempts=3, base_delay_s=0.1,
                             max_delay_s=1.0, jitter=0.5, seed=11,
                             clock=clock)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise inject.InjectedFault("boom")
            return "ok"

        assert policy.call(flaky) == "ok"
        assert len(calls) == 3
        # backoff advanced the VIRTUAL clock by the seeded deterministic sum
        expected = sum(RetryPolicy(max_attempts=3, base_delay_s=0.1,
                                   max_delay_s=1.0, jitter=0.5,
                                   seed=11).delays())
        assert clock.time() == pytest.approx(expected)

    def test_deadline_budget_stops_retries_early(self):
        clock = VirtualClock()
        policy = RetryPolicy(max_attempts=10, base_delay_s=10.0,
                             max_delay_s=10.0, jitter=0.0, deadline_s=5.0,
                             clock=clock)
        calls = []

        def always_fails():
            calls.append(1)
            raise inject.InjectedFault("down")

        with pytest.raises(RetriesExhausted):
            policy.call(always_fails)
        # the first backoff (10s) would blow the 5s budget: exactly one call
        assert len(calls) == 1 and clock.time() == 0.0

    def test_breaker_opens_and_half_opens(self):
        clock = VirtualClock()
        br = CircuitBreaker("dep", failure_threshold=2, reset_timeout_s=1.0,
                            clock=clock)
        assert br.allow()
        br.record_failure()
        br.record_failure()
        assert br.state == "open" and not br.allow()
        clock.sleep(1.5)
        assert br.allow() and br.state == "half_open"
        br.record_failure()                 # probe fails -> re-open
        assert br.state == "open"
        clock.sleep(1.5)
        assert br.allow()
        br.record_success()
        assert br.state == "closed" and br.opens == 2

    def test_open_breaker_short_circuits_retry(self):
        clock = VirtualClock()
        policy = RetryPolicy(max_attempts=3, clock=clock)
        br = CircuitBreaker("dep", failure_threshold=1,
                            reset_timeout_s=100.0, clock=clock)
        br.record_failure()
        with pytest.raises(CircuitOpen):
            policy.call(lambda: "never", breaker=br)

    def test_resilient_executor_degrades_to_empty_rows(self):
        clock = VirtualClock()
        policy = ResiliencePolicy(
            retry=RetryPolicy(max_attempts=2, base_delay_s=0.01,
                              clock=clock))

        class Down:
            def run_query(self, q, p=None):
                raise inject.InjectedFault("neo4j down")

            def close(self):
                pass

        ex = ResilientExecutor(Down(), policy, dep="graph.state")
        assert ex.run_query("MATCH (n) RETURN n") == []
        assert policy.counters["retries"] == 1
        assert policy.counters["degraded_queries:graph.state"] == 1


# ---------------------------------------------------------------------------
# injection sites
# ---------------------------------------------------------------------------


class TestGraphInjection:
    Q = """
        MATCH (n1:Event)-[s1:HasEvent]->(N1:EVENT)
        WHERE N1.message CONTAINS $message
        RETURN n1.kind
        """
    P = {"message": "secret"}

    def _ex(self):
        return InMemoryGraphExecutor(build_stategraph())

    @staticmethod
    def _vals(rows):
        return [r["n1.kind"] for r in rows]

    def test_inert_when_disarmed(self):
        ex = self._ex()
        assert inject.active() is None
        rows = ex.run_query(self.Q, self.P)
        assert rows and self._vals(rows) == self._vals(
            ex.run_query(self.Q, self.P))

    def test_fault_kinds(self):
        ex = self._ex()
        want = self._vals(ex.run_query(self.Q, self.P))
        plan = FaultPlan([Fault(inject.SITE_GRAPH, 0, "error"),
                          Fault(inject.SITE_GRAPH, 1, "timeout"),
                          Fault(inject.SITE_GRAPH, 2, "empty"),
                          Fault(inject.SITE_GRAPH, 3, "slow", delay_s=0.5),
                          Fault(inject.SITE_GRAPH, 4, "poison")])
        with inject.armed(plan):
            with pytest.raises(inject.InjectedFault):
                ex.run_query(self.Q, self.P)
            with pytest.raises(inject.InjectedTimeout):
                ex.run_query(self.Q, self.P)
            assert ex.run_query(self.Q, self.P) == []
            t0 = plan.clock.time()
            # slow but correct
            assert self._vals(ex.run_query(self.Q, self.P)) == want
            assert plan.clock.time() == pytest.approx(t0 + 0.5)
            poisoned = ex.run_query(self.Q, self.P)
            assert len(poisoned) == max(1, len(want))
            with pytest.raises(KeyError, match="poisoned"):
                poisoned[0]["n1.kind"]
            # past the schedule
            assert self._vals(ex.run_query(self.Q, self.P)) == want
        # disarmed again
        assert self._vals(ex.run_query(self.Q, self.P)) == want


class TestEngineInjection:
    def test_tick_faults_preserve_greedy_output(self, shared_engine):
        """oom + preemption-wave + stall tick faults churn the pool but
        must not change greedy output (preemption resumes via re-prefill),
        and the allocator must stay leak-free."""
        eng, tok = shared_engine
        ids = [tok.encode(p, add_bos=True)
               for p in ("pod crashloop kube-system", "node disk pressure")]
        want = eng.generate([list(i) for i in ids], max_new_tokens=8)

        plan = FaultPlan([Fault(inject.SITE_ENGINE_TICK, 1, "oom"),
                          Fault(inject.SITE_ENGINE_TICK, 3, "preempt",
                                wave=2),
                          Fault(inject.SITE_ENGINE_TICK, 5, "stall",
                                delay_s=0.2)])
        pre = METRICS.count("engine.preemptions")
        with inject.armed(plan):
            got = eng.generate([list(i) for i in ids], max_new_tokens=8)
        assert [r.token_ids for r in got] == [r.token_ids for r in want]
        assert len(plan.fired) == 3
        assert METRICS.count("engine.preemptions") > pre
        assert plan.clock.time() >= 0.2              # the stall ran
        eng.allocator.check()
        assert not eng._fault_pages                  # cleanup ran
        assert eng.allocator.n_free == eng.engine_cfg.num_pages - 1

    def test_empty_plan_is_inert_for_greedy_output(self, shared_engine):
        eng, tok = shared_engine
        ids = [tok.encode("pvc not bound storageclass", add_bos=True)]
        want = eng.generate([list(i) for i in ids], max_new_tokens=8)
        with inject.armed(FaultPlan()):              # armed but empty
            got = eng.generate([list(i) for i in ids], max_new_tokens=8)
        assert [r.token_ids for r in got] == [r.token_ids for r in want]


class TestBackendInjection:
    def _service(self, shared_engine, clock=None, run_timeout_s=600.0):
        eng, _ = shared_engine
        return AssistantService(EngineBackend(eng),
                                run_timeout_s=run_timeout_s,
                                clock=clock), eng

    def _run(self, service, text="q", max_new=8):
        a = service.create_assistant("test", "t")
        th = service.create_thread()
        service.add_message(th.id, text)
        return service.create_run(th.id, a.id,
                                  gen=GenOptions(max_new_tokens=max_new))

    def test_error_fault_fails_run(self, shared_engine):
        service, _ = self._service(shared_engine)
        with inject.armed(FaultPlan([Fault(inject.SITE_BACKEND, 0,
                                           "error")])):
            run = self._run(service)
            run = service.wait_run(run.id)
        assert run.status == RunStatus.FAILED
        assert "injected" in run.error

    def test_budget_fault_raises_budget_error(self, shared_engine):
        service, _ = self._service(shared_engine)
        with inject.armed(FaultPlan([Fault(inject.SITE_BACKEND, 0,
                                           "budget")])):
            with pytest.raises(BudgetError, match="injected"):
                self._run(service)

    def test_stalled_run_expires_on_virtual_deadline(self, shared_engine):
        clock = VirtualClock()
        plan = FaultPlan([Fault(inject.SITE_BACKEND, 0, "stall")],
                         clock=clock)
        service, eng = self._service(shared_engine, clock=clock,
                                     run_timeout_s=0.5)
        with inject.armed(plan):
            run = self._run(service)
            run = service.wait_run(run.id)        # no wall-clock timeout
        assert run.status == RunStatus.EXPIRED
        assert not eng.has_work                   # nothing leaked in-engine

    def test_expired_run_frees_engine_pages(self, shared_engine):
        """Satellite: a run reaped by the serve deadline/cancel paths must
        free its engine pages — no leaked allocator blocks — and wait_run
        must surface the expired status."""
        service, eng = self._service(shared_engine)
        run = self._run(service, text="pod oom " * 8, max_new=40)
        got = service.wait_run(run.id, timeout_s=0.0)   # expire mid-decode
        assert got.status == RunStatus.EXPIRED
        assert run.backend_handle not in service._inflight
        eng.allocator.check()
        assert not eng.has_work
        assert eng.allocator.n_free == eng.engine_cfg.num_pages - 1

    def test_cancelled_run_frees_engine_pages(self, shared_engine):
        service, eng = self._service(shared_engine)
        run = self._run(service, text="node disk pressure", max_new=40)
        got = service.cancel_run(run.id)
        assert got.status == RunStatus.CANCELLED
        eng.allocator.check()
        assert not eng.has_work
        assert eng.allocator.n_free == eng.engine_cfg.num_pages - 1
        # the state machine stays terminal through later pumps
        service._pump()
        assert service.runs[run.id].status == RunStatus.CANCELLED


class TestMeshAddressability:
    def test_backend_rejects_non_addressable_engine(self):
        """Satellite (ADVICE low #1): EngineBackend must refuse an engine
        whose arrays span non-addressable devices — its threaded drivers
        would misalign host_np's process_allgather."""

        class FakeLeaf:
            is_fully_addressable = False

        class FakeEngine:
            params = {"w": FakeLeaf()}
            cache = None
            tokenizer = None

        with pytest.raises(ValueError, match="fully-addressable"):
            EngineBackend(FakeEngine())


class TestChunkAttentionGQAAssert:
    def test_mismatched_head_sharding_fails_loudly(self):
        """Satellite (ADVICE low #3): q-heads sharded without kv-heads
        must trip the repeat-factor assertion inside _chunk_attention."""
        from k8s_llm_rca_tpu.engine.paged import _chunk_attention

        cfg = TINY                      # n_heads=4, n_kv_heads=2 -> n_rep=2
        d = cfg.head_dim
        q = jnp.zeros((1, 4, 2, d))     # 2 local q heads (sharded)
        k = jnp.zeros((1, 8, 2, d))     # 2 kv heads (unsharded)
        mask = jnp.ones((4, 8), bool)
        with pytest.raises(AssertionError, match="GQA repeat mismatch"):
            _chunk_attention(cfg, q, k, k, mask)
        # the consistent shapes still pass
        out = _chunk_attention(cfg, jnp.zeros((1, 4, 4, d)), k, k, mask)
        assert out.shape == (1, 4, 4, d)


# ---------------------------------------------------------------------------
# chaos soak
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestChaosSoak:
    def test_engine_soak_completes_and_is_byte_identical(self):
        """The acceptance bar: the multi-incident RCA sweep under a seeded
        FaultPlan (graph faults + engine tick faults + backend stalls)
        completes with every incident either fully resolved or explicitly
        degraded-and-annotated — no hangs, no unhandled exceptions — and
        two runs with the same seed produce byte-identical reports."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        r1 = run_chaos_soak(seed=0, n_incidents=2, backend="engine")
        r2 = run_chaos_soak(seed=0, n_incidents=2, backend="engine")
        assert report_bytes(r1) == report_bytes(r2)
        assert r1["failed"] == 0
        assert r1["completed"] == 2
        assert r1["engine_clean"]
        for row in r1["incidents"]:
            assert row["status"] in ("resolved", "degraded")
            if row["status"] == "degraded":
                assert row["degraded"], "degraded incident lacks annotations"

    def test_backend_down_soak_degrades_with_annotations(self):
        """Every backend run faulted: incidents must still complete via
        the scripted-oracle/skip rungs, each annotated as degraded."""
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak

        spec = {inject.SITE_BACKEND:
                {"indices": {i: "error" for i in range(64)}}}
        r = run_chaos_soak(seed=1, n_incidents=2, backend="engine",
                           plan_spec=spec)
        assert r["failed"] == 0 and r["completed"] == 2
        assert r["degraded"] == 2
        for row in r["incidents"]:
            assert row["status"] == "degraded"
            stages = {d["stage"] for d in row["degraded"]}
            assert "locate.plan" in stages
        assert r["engine_clean"]

    def test_oracle_soak_byte_identical(self):
        """The cheap soak mode (scripted backend, graph faults only)."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        r1 = run_chaos_soak(seed=3, n_incidents=4, backend="oracle")
        r2 = run_chaos_soak(seed=3, n_incidents=4, backend="oracle")
        assert report_bytes(r1) == report_bytes(r2)
        assert r1["failed"] == 0 and r1["completed"] == 4
