"""Fleet flight-recorder tests (obs/ + cluster/proc.py telemetry seam).

Layers, cheapest first:

- **units** (no subprocess): TelemetryRing drop-oldest bounds + shed
  accounting, propagation-context shape, the extended
  ``validate_chrome_trace`` (per-pid track metadata, flow pairing with
  the unpaired flow id named loudly), critical-path decomposition on
  hand-built trees (priority waterfall, relink synthesis, exact integer
  residual), and the ``{replica=}`` Prometheus aggregation.
- **one-worker fleets** (real spawns, ~0.5 s each): span propagation
  over BOTH transports — worker ``cluster.proc.serve`` spans parent
  onto the parent's ``cluster.proc.rpc`` spans and ride the parent's
  virtual timebase; untraced fleets ship nothing; SIGKILL loses at most
  the unshipped tail; a partitioned link never carries a drain RPC.
- **acceptance bars**: one RCA sweep on a 1P+1D socket disagg fleet
  yields a single merged Chrome trace (per-incarnation pid tracks,
  paired handoff flows across tier tracks, validator-clean,
  byte-identical per seed under VirtualClock); the seeded 100-incident
  proc-cluster SIGKILL soak settles ``report_bytes`` — and every
  ``faults.polls`` counter — byte-identical with telemetry on vs off.
"""

from __future__ import annotations

import pytest

from k8s_llm_rca_tpu.cluster.proc import build_proc_replicas
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.faults.plan import FaultPlan, VirtualClock
from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak
from k8s_llm_rca_tpu.obs import (
    SEGMENTS, TelemetryRing, Tracer, chrome_trace, chrome_trace_bytes,
    critical_path, critical_path_stats, prometheus_text,
    validate_chrome_trace,
)
from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.serve.backend import GenOptions

pytestmark = pytest.mark.fleetobs


def _drive_one(transport="pipe", trace=True, pumps=20):
    """One traced oracle worker through start -> settle -> close;
    returns (tracer, replica) with the replica already closed."""
    tr = Tracer(clock=VirtualClock())
    with obs_trace.tracing(tr):
        (rep,) = build_proc_replicas(
            1, kind="oracle", transport=transport,
            **({"trace": True} if trace else {}))
        try:
            h = rep.backend.start("node notready", GenOptions())
            for _ in range(pumps):
                if h in rep.backend.pump():
                    break
        finally:
            rep.close()
    return tr, rep


# ---------------------------------------------------------------------------
# units: bounded ring + propagation context
# ---------------------------------------------------------------------------


class TestTelemetryRing:
    def test_overflow_drops_oldest_and_counts_shed(self):
        ring = TelemetryRing(capacity=4)
        for i in range(10):
            ring.push({"i": i})
        assert len(ring) == 4
        assert ring.shed == 6
        # the NEWEST pre-overflow items survive (post-SIGKILL, the last
        # thing the worker did is the valuable part)
        assert [it["i"] for it in ring.pop(10)] == [6, 7, 8, 9]
        assert len(ring) == 0

    def test_pop_respects_budget_in_fifo_order(self):
        ring = TelemetryRing(capacity=8)
        for i in range(5):
            ring.push({"i": i})
        assert [it["i"] for it in ring.pop(2)] == [0, 1]
        assert [it["i"] for it in ring.pop(10)] == [2, 3, 4]
        assert ring.shed == 0

    def test_capacity_validated_loudly(self):
        with pytest.raises(ValueError, match="capacity"):
            TelemetryRing(capacity=0)


class TestPropagationContext:
    def test_context_carries_trace_id_parent_and_clock(self):
        clock = VirtualClock()
        tr = Tracer(clock=clock, trace_id=7)
        clock.sleep(1.5)
        with tr.span("cluster.proc.rpc", cat="cluster") as sp:
            ctx = tr.context()
            assert ctx == {"id": 7, "parent": sp.span_id, "ts": 1.5}
        # outside any span the parent is None (root attachment)
        assert tr.context()["parent"] is None

    def test_ingest_remote_buckets_by_incarnation(self):
        tr = Tracer()
        item = {"k": "span", "name": "cluster.proc.serve",
                "cat": "cluster", "span_id": 1, "parent_id": None,
                "t0": 0.0, "t1": 0.0, "tid": 1, "args": {}}
        assert tr.ingest_remote(0, 0, {"pid": 10, "items": [item],
                                       "shed": 0}) == 1
        assert tr.ingest_remote(0, 1, {"pid": 11, "items": [item],
                                       "shed": 3}) == 1
        # a respawn is a NEW bucket — never merged into the corpse's
        assert sorted(tr.remote) == [(0, 0), (0, 1)]
        assert tr.remote[(0, 1)]["shed"] == 3
        assert "cluster.proc.serve" in tr.emitted_names()


# ---------------------------------------------------------------------------
# units: validator (flow pairing + per-pid track metadata)
# ---------------------------------------------------------------------------


def _mini_fleet_doc():
    tr = Tracer(clock=VirtualClock())
    with tr.span("serve.run", cat="serve", run="r-1"):
        pass
    tr.ingest_remote(0, 0, {"pid": 4242, "items": [
        {"k": "span", "name": "cluster.proc.serve", "cat": "cluster",
         "span_id": 1, "parent_id": None, "t0": 0.0, "t1": 0.0,
         "tid": 1, "args": {"op": "pump"}}], "shed": 0})
    return chrome_trace(tr)


class TestValidator:
    def test_fleet_doc_validates_and_names_worker_track(self):
        doc = _mini_fleet_doc()
        assert validate_chrome_trace(doc) == len(doc["traceEvents"])
        tracks = [e for e in doc["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "process_name"]
        # deterministic Chrome pid (2 + bucket ordinal), NOT the OS pid
        assert [(e["pid"], e["args"]["name"]) for e in tracks] == \
            [(2, "0/2/0")]

    def test_unpaired_flow_id_named_loudly(self):
        doc = _mini_fleet_doc()
        doc["traceEvents"].append(
            {"name": "cluster.handoff", "cat": "handoff", "ph": "s",
             "ts": 10 ** 9, "pid": 2, "tid": 0, "id": 7, "bp": "e",
             "args": {}})
        with pytest.raises(ValueError, match="unpaired flow id 7"):
            validate_chrome_trace(doc)

    def test_finish_without_start_rejected(self):
        doc = _mini_fleet_doc()
        doc["traceEvents"].append(
            {"name": "cluster.handoff", "cat": "handoff", "ph": "f",
             "ts": 10 ** 9, "pid": 2, "tid": 0, "id": 9, "bp": "e",
             "args": {}})
        with pytest.raises(ValueError, match="unpaired flow id 9"):
            validate_chrome_trace(doc)

    def test_unnamed_worker_pid_rejected(self):
        doc = _mini_fleet_doc()
        doc["traceEvents"] = [e for e in doc["traceEvents"]
                              if not (e["ph"] == "M"
                                      and e["name"] == "process_name")]
        with pytest.raises(ValueError, match="process_name"):
            validate_chrome_trace(doc)


# ---------------------------------------------------------------------------
# units: prometheus {replica=} aggregation of shipped worker counters
# ---------------------------------------------------------------------------


class TestPrometheusFleet:
    def test_worker_counters_render_with_replica_label(self):
        tr = Tracer()
        tr.ingest_remote(0, 0, {"pid": 10, "items": [], "shed": 0,
                                "counters": {"serve.runs": 2.0,
                                             "rpc.total_s": 1.0,
                                             "rpc.count": 4,
                                             "rpc.p50_s": 0.25}})
        # a respawned incarnation's counters SUM into the same replica
        tr.ingest_remote(0, 1, {"pid": 11, "items": [], "shed": 0,
                                "counters": {"serve.runs": 3.0}})
        text = prometheus_text(tracer=tr)
        assert 'k8s_llm_rca_serve_runs_total{replica="0"} 5' in text
        # timer-derived snapshot keys are not counters — skipped
        assert "rpc_total_s" not in text and "rpc_p50_s" not in text

    def test_no_fleet_means_no_replica_lines(self):
        text = prometheus_text(tracer=Tracer())
        assert 'replica="' not in text


# ---------------------------------------------------------------------------
# one-worker fleets: propagation + shipping over both transports
# ---------------------------------------------------------------------------


class TestFleetPropagation:
    @pytest.mark.parametrize("transport", ["pipe", "socket"])
    def test_worker_spans_parent_onto_rpc_context(self, transport):
        tr, rep = _drive_one(transport=transport)
        assert (0, 0) in tr.remote
        serve = [s for s in tr.remote[(0, 0)]["spans"]
                 if s["name"] == "cluster.proc.serve"]
        assert serve
        # causal link: every shipped serve span parents onto one of the
        # parent tracer's rpc spans — one tree across both processes
        rpc_ids = {s.span_id for s in tr.spans
                   if s.name == "cluster.proc.rpc"}
        assert {s["parent_id"] for s in serve} <= rpc_ids
        assert {s["args"]["op"] for s in serve} >= {"start", "pump"}
        # worker stamps ride the parent's (virtual) timebase, not the
        # worker's wall clock
        assert max(s["t0"] for s in serve) <= tr.now()
        assert {"cluster.telemetry.ship", "cluster.telemetry.drain"} \
            <= tr.emitted_names()
        assert rep.backend.telemetry_frames > 0
        assert rep.backend.telemetry_items >= len(serve)

    def test_untraced_fleet_ships_nothing(self):
        tr, rep = _drive_one(trace=False)
        assert not tr.remote
        assert not rep.backend.telemetry
        assert rep.backend.telemetry_frames == 0
        assert not ({"cluster.telemetry.ship", "cluster.telemetry.drain",
                     "cluster.proc.serve"} & tr.emitted_names())

    def test_telemetry_without_parent_tracer_is_harmless(self):
        # worker records + ships, parent has no tracer to ingest into:
        # payloads are dropped on the floor, nothing raises, nothing
        # leaks into a later-activated tracer
        (rep,) = build_proc_replicas(1, kind="oracle", trace=True)
        try:
            h = rep.backend.start("node notready", GenOptions())
            for _ in range(20):
                if h in rep.backend.pump():
                    break
        finally:
            rep.close()
        assert rep.backend.telemetry_items == 0


class TestSigkillDrain:
    def test_sigkill_loses_at_most_the_unshipped_tail(self):
        tr = Tracer(clock=VirtualClock())
        with obs_trace.tracing(tr):
            (rep,) = build_proc_replicas(1, kind="oracle", trace=True)
            try:
                rep.backend.start("node notready", GenOptions())
                rep.backend.pump()
                shipped = rep.backend.telemetry_items
                assert shipped > 0
                rep.backend.kill()
                # dead process: the drain short-circuits on liveness
                # evidence instead of timing out on a corpse's pipe
                assert rep.backend.drain_telemetry() == 0
            finally:
                rep.close()
        # everything shipped before the SIGKILL survives in the parent
        bucket = tr.remote[(0, 0)]
        retained = (len(bucket["spans"]) + len(bucket["events"])
                    + len(bucket["ticks"]))
        assert retained == shipped

    def test_partitioned_link_carries_no_drain_rpc(self):
        tr = Tracer(clock=VirtualClock())
        with obs_trace.tracing(tr):
            (rep,) = build_proc_replicas(1, kind="oracle",
                                         transport="socket", trace=True)
            try:
                h = rep.backend.start("node notready", GenOptions())
                for _ in range(20):
                    if h in rep.backend.pump():
                        break
                rep.partition_link()
                # link down, process alive: no RPC is attempted, so the
                # drain can never poison the link evidence
                assert rep.backend.drain_telemetry() == 0
                assert rep.backend.relink()
                # healed link ships again (the drain op's own serve
                # span rides its reply at minimum)
                assert rep.backend.drain_telemetry() > 0
            finally:
                rep.close()


# ---------------------------------------------------------------------------
# acceptance: 1P+1D socket disagg fleet -> one merged golden trace
# ---------------------------------------------------------------------------


class TestMergedFleetTrace:
    def _disagg_tracer(self):
        tr = Tracer()
        report = run_chaos_soak(seed=5, n_incidents=2,
                                backend="disagg-cluster",
                                cluster_replicas=2, tier_split=(1, 1),
                                tracer=tr, fleet_telemetry=True)
        assert report["failed"] == 0
        return tr

    def test_single_merged_trace_with_flows_golden(self):
        tr = self._disagg_tracer()
        doc = chrome_trace(tr)
        assert validate_chrome_trace(doc) > 0
        events = doc["traceEvents"]
        # one pid track per worker incarnation, deterministically named
        tracks = sorted(e["args"]["name"] for e in events
                        if e["ph"] == "M" and e["name"] == "process_name")
        assert tracks == ["0/2/0", "1/3/0"]
        assert doc["metadata"]["fleet"]["workers"] == 2
        # handoff flows pair up ACROSS the tier tracks: every committed
        # EXPORT->ADOPT->RELEASE draws one s (prefill pid) -> f (decode
        # pid) arc
        starts = {e["id"]: e for e in events if e["ph"] == "s"}
        finishes = {e["id"]: e for e in events if e["ph"] == "f"}
        assert starts and sorted(starts) == sorted(finishes)
        for fid, s_ev in starts.items():
            assert s_ev["pid"] != finishes[fid]["pid"]
        # causally linked: both workers' serve spans parent onto the
        # parent tracer's rpc spans
        rpc_ids = {s.span_id for s in tr.spans
                   if s.name == "cluster.proc.rpc"}
        for bucket in tr.remote.values():
            serve = [s for s in bucket["spans"]
                     if s["name"] == "cluster.proc.serve"]
            assert serve
            assert {s["parent_id"] for s in serve} <= rpc_ids
        # byte-identical per seed under the frozen VirtualClock — the
        # second fleet has different OS pids, same trace bytes
        again = chrome_trace_bytes(chrome_trace(self._disagg_tracer()))
        assert chrome_trace_bytes(doc) == again

    def test_critical_path_covers_every_settled_run(self):
        tr = self._disagg_tracer()
        rows = critical_path(tr)
        assert rows
        for row in rows.values():
            assert sum(row["segments_us"].values()) == row["total_us"]
            assert set(row["segments_us"]) == set(SEGMENTS)


    def test_respawned_worker_gets_a_track_of_its_own(self):
        """A traced echo fleet, one worker SIGKILLed mid-flight and
        respawned by the supervisor (moved here from the dryrun at PR 50;
        the units above hold the buckets and the validator apart): the
        merged document validates with the new incarnation on its own pid
        track, frames were shipped, and every run's critical-path segments
        sum to its total."""
        from k8s_llm_rca_tpu.cluster import (
            ClusterRouter, HealthPolicy, HealthWatchdog, ReplicaSupervisor,
        )

        tr = Tracer()
        replicas = build_proc_replicas(2, kind="echo", echo_delay_pumps=2,
                                       trace=True)
        try:
            with obs_trace.tracing(tr):
                router = ClusterRouter(replicas)
                router.attach_health(
                    HealthWatchdog(HealthPolicy(miss_budget=1,
                                                hung_tick_threshold=2),
                                   clock=VirtualClock()),
                    ReplicaSupervisor())
                t0 = tr.now()
                handles = [router.start(f"fleet obs {i}", GenOptions())
                           for i in range(3)]
                victim = max(router.alive_ids(), key=lambda r: (
                    router.replicas[r].queue_depth(), r))
                router.replicas[victim].kill_process()
                out = {}
                for _ in range(64):
                    out.update(router.pump())
                    if (all(h in out for h in handles) and all(
                            r.healthy() for r in router.replicas.values())):
                        break
                assert all(h in out for h in handles)
                tr.add_span("serve.run", t0, tr.now(), cat="serve",
                            args={"run": "fleet-obs", "status": "completed"})
        finally:
            for r in replicas:
                r.close()
        doc = chrome_trace(tr)
        validate_chrome_trace(doc)
        tracks = [e["args"]["name"] for e in doc["traceEvents"]
                  if e.get("ph") == "M" and e["name"] == "process_name"
                  and e["pid"] != 1]
        assert any(t.endswith("/1") for t in tracks), tracks
        assert sum(r.backend.telemetry_frames for r in replicas) > 0
        rows = critical_path(tr)
        assert rows and all(
            sum(row["segments_us"].values()) == row["total_us"]
            for row in rows.values())


# ---------------------------------------------------------------------------
# acceptance: telemetry changes no fault draws (SIGKILL soak identity)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestSoakTelemetryIdentity:
    def test_100_incident_sigkill_soak_identical_on_vs_off(self):
        """The flight recorder must be a pure observer: same seeds, same
        kills, same polls, same report BYTES whether or not the fleet is
        shipping telemetry — shipping rides reply frames and ops that
        poll no fault sites."""
        from k8s_llm_rca_tpu.faults.supervisor import ProcKiller

        def killer():
            return ProcKiller(FaultPlan.from_spec(
                2, {inject.SITE_PROC: {"rate": 0.03, "horizon": 100,
                                       "kinds": ("crash",)}}))

        k_off = killer()
        off = run_chaos_soak(seed=11, n_incidents=100,
                             backend="proc-cluster", cluster_replicas=4,
                             killer=k_off, selfheal=True)
        k_on = killer()
        on = run_chaos_soak(seed=11, n_incidents=100,
                            backend="proc-cluster", cluster_replicas=4,
                            killer=k_on, selfheal=True,
                            fleet_telemetry=True)
        assert k_off.kills                     # SIGKILLs actually landed
        assert k_on.kills == k_off.kills       # same kill schedule
        assert on["faults"]["polls"] == off["faults"]["polls"]
        assert report_bytes(on) == report_bytes(off)

    def test_fleet_telemetry_refused_off_proc_backends(self):
        with pytest.raises(ValueError, match="fleet_telemetry"):
            run_chaos_soak(n_incidents=1, backend="cluster-oracle",
                           fleet_telemetry=True)


# ---------------------------------------------------------------------------
# critical path: decomposition units + serve surface
# ---------------------------------------------------------------------------


class TestCriticalPath:
    def _run_span(self, tr, t0, t1, run="r-1"):
        tr.add_span("serve.run", t0, t1, cat="serve",
                    args={"run": run, "status": "completed"})

    def test_segments_sum_exactly_with_priority_waterfall(self):
        clock = VirtualClock()
        tr = Tracer(clock=clock)
        with tr.span("cluster.handoff.export", cat="handoff"):
            clock.sleep(0.010)
            # rpc INSIDE the export window: export (outermost actionable
            # cause) takes the overlap, wire gets nothing here
            with tr.span("cluster.proc.rpc", cat="cluster"):
                clock.sleep(0.005)
        with tr.span("cluster.proc.rpc", cat="cluster"):
            clock.sleep(0.007)
        clock.sleep(0.003)                    # unattributed -> queue_wait
        self._run_span(tr, 0.0, clock.time())
        row = critical_path(tr)["r-1"]
        assert row["total_us"] == 25000
        segs = row["segments_us"]
        assert segs["cp.handoff.export"] == 15000
        assert segs["cp.wire"] == 7000
        assert segs["cp.queue_wait"] == 3000
        assert sum(segs.values()) == row["total_us"]

    def test_relink_outage_synthesized_and_retries_counted(self):
        clock = VirtualClock()
        tr = Tracer(clock=clock)
        tr.event("cluster.net.partition", replica=0)
        clock.sleep(0.020)
        tr.event("cluster.net.relink", replica=0)
        tr.event("resilience.retry", dep="graph.meta")
        clock.sleep(0.004)
        self._run_span(tr, 0.0, clock.time())
        row = critical_path(tr)["r-1"]
        assert row["segments_us"]["cp.relink"] == 20000
        assert row["segments_us"]["cp.queue_wait"] == 4000
        assert row["retries"] == 1
        assert sum(row["segments_us"].values()) == row["total_us"]

    def test_window_clipping_and_run_filter(self):
        clock = VirtualClock()
        tr = Tracer(clock=clock)
        # a prefill span straddling the run's start is clipped to the
        # overlap, never attributed outside the window
        with tr.span("engine.prefill", cat="engine"):
            clock.sleep(0.010)
        clock.sleep(0.002)
        self._run_span(tr, 0.005, clock.time(), run="r-a")
        self._run_span(tr, 0.005, clock.time(), run="r-b")
        rows = critical_path(tr, runs={"r-a"})
        assert set(rows) == {"r-a"}
        segs = rows["r-a"]["segments_us"]
        assert segs["cp.prefill"] == 5000
        assert segs["cp.queue_wait"] == 2000

    def test_stats_aggregate_and_empty_tracer(self):
        clock = VirtualClock()
        tr = Tracer(clock=clock)
        with tr.span("engine.decode_step", cat="engine"):
            clock.sleep(0.006)
        self._run_span(tr, 0.0, clock.time())
        stats = critical_path_stats(tr)
        assert stats["runs"] == 1
        assert stats["end_to_end_us"] == 6000
        assert stats["total_us"]["cp.decode"] == 6000
        assert critical_path_stats(Tracer()) == {"runs": 0}

    def test_usage_for_runs_exposes_critical_path(self):
        from k8s_llm_rca_tpu.serve.api import AssistantService, RunStatus
        from k8s_llm_rca_tpu.serve.backend import EchoBackend
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        tr = Tracer(clock=VirtualClock())
        with obs_trace.tracing(tr):
            svc = AssistantService(EchoBackend(get_tokenizer()))
            a = svc.create_assistant("inst", "cp")
            t = svc.create_thread()
            svc.add_message(t.id, "node notready")
            run = svc.create_run(t.id, a.id)
            assert svc.wait_run(run.id).status == RunStatus.COMPLETED
            usage = svc.usage_for_runs([run.id], critical_path=True)
            assert run.id in usage["critical_path"]
            row = usage["critical_path"][run.id]
            assert sum(row["segments_us"].values()) == row["total_us"]
            # the default surface is unchanged (report_bytes safety)
            assert "critical_path" not in svc.usage_for_runs([run.id])
