"""Self-healing cluster (k8s_llm_rca_tpu/cluster/health.py).

Four layers of proof, mirroring the cluster test conventions
(tests/test_cluster.py):

- **watchdog determinism**: the ALIVE -> SUSPECT -> DEAD classifier is a
  pure function of the probe/beat sequence — exact verdict sequences
  under a frozen VirtualClock, fresh signals demote SUSPECT, idle
  replicas never false-positive (the pump beat IS a signal), and the
  probe interval gates evaluations on the injectable clock.
- **auto-failover + rejoin**: a wedged replica (dead process, nobody
  tells the router) is detected by silence, failed over through the SAME
  ``fail_replica`` path an external caller would use, and — with a
  restart-enabled ReplicaSupervisor — rebuilt on its original submesh so
  the fleet returns to N; the restarted engine replica serves new work
  byte-identical to the plain single engine (the parity bar every
  parallelism mode meets).
- **poison-run quarantine**: a run whose replica dies ``quarantine_after``
  times settles FAILED with a named error through the normal pump path,
  so the journal records it and recovery replay agrees.
- **kill-and-heal soak** (the ISSUE acceptance bar): a seeded
  100-incident chaos sweep where every kill is a silent wedge — NO
  external ``fail_replica`` call — completes with the fleet restored to
  N and ``report_bytes`` byte-identical to the unkilled run; plus the
  open-loop Poisson driver (faults/soak.py) and its SRE-storm
  composition with the kill-and-heal machinery.

Loud ValueError exclusions (repo convention): invalid HealthPolicy
knobs, quarantine_after < 1, a watchdog on a single-replica router
without restart, supervisor bind over overlapping submeshes, restart
without a rebuild recipe, selfheal on a non-cluster soak backend.
"""

import json

import pytest

from k8s_llm_rca_tpu.cluster import (
    ALIVE, DEAD, SUSPECT, ClusterRouter, HealthPolicy, HealthWatchdog,
    Replica, ReplicaSupervisor,
)
from k8s_llm_rca_tpu.faults.plan import VirtualClock
from k8s_llm_rca_tpu.serve.backend import EchoBackend, GenOptions
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.selfheal


def _healing_router(n=2, delay_pumps=0, tok=None, policy=None,
                    quarantine_after=2, restart=True, clock=None):
    """Echo replicas with rebuild recipes behind a self-healing router."""
    tok = tok or get_tokenizer()
    reps = [Replica(i, EchoBackend(tok, delay_pumps=delay_pumps),
                    rebuild=lambda tok=tok, d=delay_pumps: EchoBackend(
                        tok, delay_pumps=d))
            for i in range(n)]
    router = ClusterRouter(reps, quarantine_after=quarantine_after)
    wd = HealthWatchdog(policy or HealthPolicy(miss_budget=1,
                                               hung_tick_threshold=2),
                        clock=clock or VirtualClock())
    sup = ReplicaSupervisor(restart=restart)
    router.attach_health(wd, sup)
    return router, reps, wd, sup


def _settle(router, handles, pumps=64):
    out = {}
    for _ in range(pumps):
        out.update(router.pump())
        if all(h in out for h in handles):
            return out
    raise AssertionError(f"runs never settled: {out.keys()}")


# ---------------------------------------------------------------------------
# watchdog state machine: deterministic verdicts under a frozen clock
# ---------------------------------------------------------------------------


class TestWatchdogStateMachine:
    def test_verdict_sequence_is_exact(self):
        """Probe-count classification: with miss_budget=2 and
        hung_tick_threshold=4 a silent replica goes SUSPECT on the 2nd
        miss and DEAD on the 4th — exactly, run after run, on a frozen
        VirtualClock (misses are per probe evaluation, never per wall
        second)."""
        tok = get_tokenizer()
        router = ClusterRouter([Replica(i, EchoBackend(tok))
                                for i in range(2)])
        wd = HealthWatchdog(HealthPolicy(miss_budget=2,
                                         hung_tick_threshold=4),
                            clock=VirtualClock())
        for rid in (0, 1):
            wd.register(rid)
            wd.beat(rid)
        assert wd.probe(router) == []        # baseline, never a miss
        seen = []
        for _ in range(4):
            wd.beat(1)                       # replica 1 keeps signalling
            dead = wd.probe(router)
            seen.append(wd.state(0))
        assert seen == [ALIVE, SUSPECT, SUSPECT, DEAD]
        assert dead == [0]                   # DEAD surfaced exactly once
        assert wd.detections == [0]
        assert len(wd.mttd_s) == 1
        assert wd.state(1) == ALIVE
        assert wd.probe(router) == []        # already DEAD: not re-reported

    def test_fresh_signal_demotes_suspect_and_resets_misses(self):
        tok = get_tokenizer()
        router = ClusterRouter([Replica(i, EchoBackend(tok))
                                for i in range(2)])
        wd = HealthWatchdog(HealthPolicy(miss_budget=2,
                                         hung_tick_threshold=4),
                            clock=VirtualClock())
        for rid in (0, 1):
            wd.register(rid)
            wd.beat(rid)
        def probe():                         # replica 1 stays healthy
            wd.beat(1)
            return wd.probe(router)

        probe()                              # baseline
        probe()                              # miss 1
        probe()                              # miss 2 -> SUSPECT
        assert wd.state(0) == SUSPECT
        wd.beat(0)                           # the replica comes back
        probe()
        assert wd.state(0) == ALIVE
        # the miss counter reset with the demotion: three MORE silent
        # probes reach SUSPECT again, not DEAD
        for _ in range(3):
            probe()
        assert wd.state(0) == SUSPECT
        assert wd.detections == []

    def test_idle_replica_never_false_positives(self):
        """An idle healthy replica ticks nothing, but its pump completes
        — the router's pump beat keeps it ALIVE forever."""
        router, _, wd, _ = _healing_router(n=2)
        for _ in range(10):
            assert router.pump() == {}
        assert wd.states() == {0: ALIVE, 1: ALIVE}
        assert wd.detections == []

    def test_probe_interval_gates_on_the_injectable_clock(self):
        tok = get_tokenizer()
        router = ClusterRouter([Replica(i, EchoBackend(tok))
                                for i in range(2)])
        clock = VirtualClock()
        wd = HealthWatchdog(HealthPolicy(probe_interval_s=1.0,
                                         miss_budget=1,
                                         hung_tick_threshold=2),
                            clock=clock)
        for rid in (0, 1):
            wd.register(rid)
            wd.beat(rid)
        def probe():                         # replica 1 stays healthy
            wd.beat(1)
            return wd.probe(router)

        probe()                              # baseline evaluation
        for _ in range(8):                   # same instant: all gated
            probe()
        assert wd.state(0) == ALIVE
        clock.advance(1.0)
        probe()                              # miss 1 -> SUSPECT
        assert wd.state(0) == SUSPECT
        clock.advance(1.0)
        assert probe() == [0]                # miss 2 -> DEAD
        assert wd.mttd_s == [2.0]            # last beat -> verdict, virtual


# ---------------------------------------------------------------------------
# auto-failover and restart-and-rejoin on echo replicas
# ---------------------------------------------------------------------------


class TestAutoFailover:
    def test_wedge_heals_to_same_results_as_manual_fail_replica(self):
        """A silent wedge must end exactly where an external
        ``fail_replica`` call ends — same global handles, same texts —
        except the self-healed fleet is back at N."""
        tok = get_tokenizer()
        prompts = [f"p{i}" for i in range(4)]
        # manual baseline (PR 6 semantics): external kill, fleet shrinks
        manual = ClusterRouter([Replica(i, EchoBackend(tok, delay_pumps=2))
                                for i in range(2)])
        mh = [manual.start(p, GenOptions(session=f"s{i}"))
              for i, p in enumerate(prompts)]
        manual.fail_replica(0)
        m_out = _settle(manual, mh)

        router, reps, wd, sup = _healing_router(n=2, delay_pumps=2)
        h = [router.start(p, GenOptions(session=f"s{i}"))
             for i, p in enumerate(prompts)]
        assert {router._handle_map[x][0] for x in h} == {0, 1}
        reps[0].wedge()                      # process dies, nobody told
        out = _settle(router, h, pumps=16)
        assert [out[x].text for x in h] == [m_out[y].text for y in mh]
        assert all(v.error is None for v in out.values())
        # the watchdog drove the whole loop: detect -> failover -> rejoin
        assert wd.detections == [0]
        assert router.failovers == 1
        assert sup.restarts == [0]
        assert sup.incarnations == {0: 1}
        assert len(sup.mttr_s) == 1
        assert router.alive_ids() == [0, 1]  # fleet restored to N
        assert not reps[0].wedged
        # manual fleet stays shrunk — restart is the self-healing delta
        assert manual.alive_ids() == [1]

    def test_single_replica_wedge_restarts_in_place(self):
        """Last-alive heal path: fail_replica would refuse (an outage),
        but with restart the outage is recoverable — the corpse is
        rebuilt in place and its run re-starts on the fresh
        incarnation."""
        router, reps, wd, sup = _healing_router(n=1, delay_pumps=2)
        h = router.start("solo", GenOptions(session="t"))
        reps[0].wedge()
        out = _settle(router, [h], pumps=16)
        assert out[h].error is None
        assert router.failovers == 1         # kind="restart-in-place"
        assert sup.restarts == [0]
        assert router.alive_ids() == [0]
        assert not router.replicas[0].wedged

    def test_pick_routes_new_work_around_suspect(self):
        router, reps, wd, _ = _healing_router(
            n=2, delay_pumps=10 ** 9,
            policy=HealthPolicy(miss_budget=1, hung_tick_threshold=9))
        reps[0].wedge()
        router.pump()                        # baseline probe
        router.pump()                        # miss 1 -> SUSPECT
        assert wd.is_suspect(0)
        # replica 0 has the smaller depth, but new work avoids it
        h = router.start("p", GenOptions())
        assert router._handle_map[h][0] == 1

    def test_pinned_session_unpins_off_a_suspect_replica(self):
        router, reps, wd, _ = _healing_router(
            n=2, delay_pumps=10 ** 9,
            policy=HealthPolicy(miss_budget=1, hung_tick_threshold=9))
        h0 = router.start("p", GenOptions(session="t1"))
        pinned = router._handle_map[h0][0]
        reps[pinned].wedge()
        router.pump()
        router.pump()
        assert wd.is_suspect(pinned)
        h1 = router.start("p", GenOptions(session="t1"))
        other = 1 - pinned
        assert router._handle_map[h1][0] == other
        assert router._affinity["t1"] == other   # re-pinned on healthy


# ---------------------------------------------------------------------------
# restarted ENGINE replica: byte-identical service on the fresh incarnation
# ---------------------------------------------------------------------------


class TestRestartEngineParity:
    def test_restarted_replica_serves_new_work_byte_identically(
            self, cpu_devices):
        """Kill an engine replica mid-decode by wedging it; the watchdog
        detects, the orphan re-runs on the survivor byte-identically,
        the supervisor rebuilds the corpse on its ORIGINAL submesh
        (re-sharding the same host params), and the fresh incarnation
        then serves new work byte-identical to the plain single engine
        — the parity bar every parallelism mode meets."""
        import jax

        from k8s_llm_rca_tpu.cluster import build_replicas
        from k8s_llm_rca_tpu.config import TINY, EngineConfig
        from k8s_llm_rca_tpu.engine import make_engine
        from k8s_llm_rca_tpu.models import llama

        cfg = TINY.replace(max_seq_len=64)
        # paged with decode_chunk=1 (the drain-migration test's config),
        # so the run is genuinely MID-decode when the wedge lands
        ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                            prefill_buckets=(16, 32), max_new_tokens=6,
                            temperature=0.0, page_size=8,
                            num_pages=32, decode_chunk=1)
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        prompts = ["pod pending unschedulable node affinity mismatch",
                   "pvc not bound storageclass missing"]
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ref = make_engine(cfg, ecfg, params, tok,
                          use_kernel=False).generate(
            [tok.encode(p, add_bos=True) for p in prompts],
            max_new_tokens=6)

        replicas = build_replicas(cfg, ecfg, 2, devices=cpu_devices,
                                  seed=0, use_kernel=False)
        router = ClusterRouter(replicas)
        wd = HealthWatchdog(HealthPolicy(miss_budget=1,
                                         hung_tick_threshold=2),
                            clock=VirtualClock())
        sup = ReplicaSupervisor()
        router.attach_health(wd, sup)
        first_engine = replicas[0].backend.engine
        assert first_engine._hb_stamp        # heartbeats are clock-stamped

        h0 = router.start(prompts[0], GenOptions(max_new_tokens=6))
        assert router._handle_map[h0][0] == 0
        for _ in range(3):                   # mid-decode (chunk=1)
            assert not router.pump()
        replicas[0].wedge()                  # the worker process dies
        out = _settle(router, [h0], pumps=64)
        # the orphan re-ran on the survivor, byte-identical greedy text
        assert out[h0].text == ref[0].text
        assert out[h0].error is None
        assert wd.detections == [0]
        assert sup.restarts == [0]
        assert router.alive_ids() == [0, 1]
        fresh = router.replicas[0].backend.engine
        assert fresh is not first_engine     # a NEW incarnation
        assert fresh.obs_replica == 0        # obs identity re-tagged
        assert fresh._hb_stamp

        # the fresh incarnation serves new work byte-identically (both
        # replicas idle: least-depth lowest-id picks the restarted one)
        h1 = router.start(prompts[1], GenOptions(max_new_tokens=6))
        assert router._handle_map[h1][0] == 0
        out = _settle(router, [h1], pumps=64)
        assert out[h1].text == ref[1].text
        assert fresh.heartbeat > 0           # its ticks fed the watchdog


# ---------------------------------------------------------------------------
# poison-run quarantine: journaled settlement, recovery replay agrees
# ---------------------------------------------------------------------------


class TestQuarantine:
    def test_poison_run_quarantined_after_k_deaths(self):
        router, reps, wd, sup = _healing_router(n=2,
                                                delay_pumps=10 ** 9,
                                                quarantine_after=2)
        h = router.start("poison", GenOptions(session="t"))
        for death in range(2):
            rid = router._handle_map[h][0]
            router.replicas[rid].wedge()
            out = {}
            for _ in range(8):
                out.update(router.pump())
                if h in out:
                    break
        res = out[h]
        assert res.error is not None
        assert "quarantined" in res.error
        assert "died 2 times" in res.error
        assert router.quarantined == 1
        assert not router.busy(h)            # fully unmapped
        assert h not in router._deaths       # tracking cleaned up
        # the fleet healed around the poison run both times
        assert router.alive_ids() == [0, 1]
        assert sup.restarts and wd.detections

    def test_surviving_one_death_clears_the_death_count(self):
        """A run that fails over once and then COMPLETES must not leave
        a death count behind (quarantine is per in-flight life, not a
        permanent mark)."""
        router, reps, _, _ = _healing_router(n=2, delay_pumps=2,
                                             quarantine_after=2)
        h = router.start("transient", GenOptions(session="t"))
        reps[router._handle_map[h][0]].wedge()
        out = _settle(router, [h], pumps=16)
        assert out[h].error is None
        assert router._deaths == {}
        assert router.quarantined == 0

    def test_quarantine_is_journaled_and_recovery_agrees(self, tmp_path):
        from k8s_llm_rca_tpu.serve.api import AssistantService, RunStatus
        from k8s_llm_rca_tpu.serve.journal import RunJournal
        from k8s_llm_rca_tpu.serve.recover import recover_service

        path = str(tmp_path / "selfheal.wal")
        tok = get_tokenizer()
        router, reps, _, _ = _healing_router(n=2, delay_pumps=10 ** 9,
                                             tok=tok, quarantine_after=2)
        service = AssistantService(router, journal=RunJournal(path))
        a = service.create_assistant("sre", "answer briefly")
        th = service.create_thread()
        service.add_message(th.id, "what failed?")
        run = service.create_run(th.id, a.id,
                                 gen=GenOptions(max_new_tokens=8))
        h = service.runs[run.id].backend_handle
        for _ in range(2):                   # two fatal incarnations
            router.replicas[router._handle_map[h][0]].wedge()
            for _ in range(8):
                service._pump()
                if service.runs[run.id].status in RunStatus.TERMINAL:
                    break
        live = service.runs[run.id]
        assert live.status == RunStatus.FAILED
        assert "quarantined" in live.error
        service._journal.close()

        fresh_router, _, _, _ = _healing_router(n=2, tok=tok)
        svc, report = recover_service(path, fresh_router)
        # the quarantine settled through the normal pump path, so the
        # journal replay agrees byte-for-byte — never re-executed
        assert report["resubmitted"] == []
        replayed = svc.runs[run.id]
        assert replayed.status == RunStatus.FAILED
        assert replayed.error == live.error


# ---------------------------------------------------------------------------
# loud exclusions
# ---------------------------------------------------------------------------


class TestExclusions:
    @pytest.mark.parametrize("kw,match", [
        (dict(probe_interval_s=-1.0), "probe_interval_s"),
        (dict(miss_budget=0), "miss_budget"),
        (dict(miss_budget=3, hung_tick_threshold=3), "exceed"),
    ])
    def test_invalid_health_policy_rejected(self, kw, match):
        with pytest.raises(ValueError, match=match):
            HealthPolicy(**kw)

    def test_quarantine_threshold_below_one_rejected(self):
        tok = get_tokenizer()
        with pytest.raises(ValueError, match="quarantine_after"):
            ClusterRouter([Replica(0, EchoBackend(tok)),
                           Replica(1, EchoBackend(tok))],
                          quarantine_after=0)

    def test_single_replica_watchdog_without_restart_rejected(self):
        tok = get_tokenizer()
        wd = HealthWatchdog(clock=VirtualClock())
        router = ClusterRouter([Replica(0, EchoBackend(tok))])
        with pytest.raises(ValueError, match="single-replica"):
            router.attach_health(wd)
        router = ClusterRouter([Replica(0, EchoBackend(tok))])
        with pytest.raises(ValueError, match="single-replica"):
            router.attach_health(wd, ReplicaSupervisor(restart=False))
        # a restart-enabled supervisor makes the verdict recoverable
        router = ClusterRouter([Replica(0, EchoBackend(
            tok, delay_pumps=1), rebuild=lambda: EchoBackend(tok))])
        router.attach_health(HealthWatchdog(clock=VirtualClock()),
                             ReplicaSupervisor())
        assert router.health is not None

    def test_overlapping_submeshes_rejected_at_bind(self, cpu_devices):
        from k8s_llm_rca_tpu.config import MeshConfig
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh

        tok = get_tokenizer()
        a = build_mesh(MeshConfig(model=4), devices=cpu_devices[:4])
        b = build_mesh(MeshConfig(model=4), devices=cpu_devices[2:6])
        router = ClusterRouter([Replica(0, EchoBackend(tok), mesh=a),
                                Replica(1, EchoBackend(tok), mesh=b)])
        with pytest.raises(ValueError, match="overlap"):
            router.attach_health(HealthWatchdog(clock=VirtualClock()),
                                 ReplicaSupervisor())

    def test_restart_without_rebuild_recipe_is_loud(self):
        tok = get_tokenizer()
        router = ClusterRouter([Replica(i, EchoBackend(tok))
                                for i in range(2)])
        router.attach_health(
            HealthWatchdog(HealthPolicy(miss_budget=1,
                                        hung_tick_threshold=2),
                           clock=VirtualClock()),
            ReplicaSupervisor())
        router.replicas[0].wedge()
        with pytest.raises(ValueError, match="rebuild recipe"):
            for _ in range(4):
                router.pump()

    def test_restart_before_bind_rejected(self):
        with pytest.raises(ValueError, match="bind"):
            ReplicaSupervisor().restart(0)

    def test_selfheal_requires_cluster_backend(self):
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak

        with pytest.raises(ValueError, match="cluster"):
            run_chaos_soak(seed=0, n_incidents=1, backend="oracle",
                           selfheal=True)

    def test_poisson_arrivals_validates(self):
        from k8s_llm_rca_tpu.faults.soak import poisson_arrivals

        with pytest.raises(ValueError, match="rate_per_s"):
            poisson_arrivals(0, 0.0, 4)
        with pytest.raises(ValueError, match="n must"):
            poisson_arrivals(0, 1.0, -1)


# ---------------------------------------------------------------------------
# kill-and-heal chaos soak (the acceptance sweep) + open-loop Poisson driver
# ---------------------------------------------------------------------------


def _wedge_killer(seed=2, rate=0.03, horizon=100):
    from k8s_llm_rca_tpu.faults import inject
    from k8s_llm_rca_tpu.faults.plan import FaultPlan
    from k8s_llm_rca_tpu.faults.supervisor import ReplicaKiller

    return ReplicaKiller(FaultPlan.from_spec(
        seed, {inject.SITE_REPLICA: {"rate": rate, "horizon": horizon,
                                     "kinds": ("crash",)}}))


@pytest.mark.chaos
class TestKillAndHealSoak:
    def test_100_incident_kill_and_heal_byte_identical(self):
        """The ISSUE acceptance bar: a 100-incident sweep on oracle
        replicas where every seeded kill is a silent WEDGE — the
        watchdog detects, fails over and the supervisor rejoins, with
        NO external fail_replica call — ends with the fleet restored to
        N and a report byte-identical to the unkilled sweep's (and to a
        rerun of itself: the heal schedule is seeded too)."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        base = run_chaos_soak(seed=11, n_incidents=100,
                              backend="cluster-oracle",
                              cluster_replicas=4)
        assert base["completed"] == 100
        assert base["failed"] == 0

        k1 = _wedge_killer()
        healed = run_chaos_soak(seed=11, n_incidents=100,
                                backend="cluster-oracle",
                                cluster_replicas=4, killer=k1,
                                selfheal=True)
        assert k1.kills                      # wedges actually happened
        assert report_bytes(healed) == report_bytes(base)
        router = k1.router
        # the whole loop ran in-tree: one detection, one failover and
        # one restart per kill, fleet back to full strength at the end
        assert router.health.detections == k1.kills
        assert router.supervisor.restarts == k1.kills
        assert router.failovers == len(k1.kills)
        assert sorted(router.alive_ids()) == [0, 1, 2, 3]
        assert all(not r.wedged for r in router.replicas.values())

        k2 = _wedge_killer()
        again = run_chaos_soak(seed=11, n_incidents=100,
                               backend="cluster-oracle",
                               cluster_replicas=4, killer=k2,
                               selfheal=True)
        assert k2.kills == k1.kills          # the wedge schedule is seeded
        assert report_bytes(again) == report_bytes(base)

    @pytest.mark.slow
    def test_engine_cluster_kill_and_heal_byte_identical(self):
        """Engine replicas under a silent wedge: graph-faults-only plan
        (tests/test_cluster.py rationale — survivor tick drift), report
        byte-identical to the unkilled run, every CURRENT engine
        incarnation left clean, fleet restored to N."""
        from k8s_llm_rca_tpu.faults import inject
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        spec = {inject.SITE_GRAPH: {
            "rate": 0.10, "horizon": 40, "delay_s": 0.01,
            "kinds": ("error", "timeout", "empty", "slow", "poison")}}
        base = run_chaos_soak(seed=5, n_incidents=2, backend="cluster",
                              plan_spec=spec, cluster_replicas=2)
        assert base["completed"] == 2
        assert base["engine_clean"] is True

        k = _wedge_killer(seed=3, rate=0.6, horizon=2)
        healed = run_chaos_soak(seed=5, n_incidents=2, backend="cluster",
                                plan_spec=spec, cluster_replicas=2,
                                killer=k, selfheal=True)
        assert k.kills                       # the wedge fired mid-sweep
        assert healed["engine_clean"] is True
        assert report_bytes(healed) == report_bytes(base)
        assert sorted(k.router.alive_ids()) == [0, 1]
        assert k.router.supervisor.restarts == k.kills


class TestOpenLoopPoisson:
    def test_arrivals_are_seeded_and_monotone(self):
        from k8s_llm_rca_tpu.faults.soak import poisson_arrivals

        a = poisson_arrivals(7, 100.0, 50)
        assert a == poisson_arrivals(7, 100.0, 50)
        assert a != poisson_arrivals(8, 100.0, 50)
        assert len(a) == 50
        assert all(b < c for b, c in zip(a, a[1:]))
        assert poisson_arrivals(7, 100.0, 0) == []

    def test_open_loop_report_is_deterministic(self):
        from k8s_llm_rca_tpu.faults.soak import run_open_loop_soak

        r1 = run_open_loop_soak(seed=4, rate_per_s=200.0, n_runs=16)
        r2 = run_open_loop_soak(seed=4, rate_per_s=200.0, n_runs=16)
        assert json.dumps(r1, sort_keys=True) == json.dumps(r2,
                                                            sort_keys=True)
        assert r1["completed"] == 16
        assert r1["failed"] == 0
        assert r1["p50_ttr_s"] <= r1["p99_ttr_s"]
        assert r1["fleet_alive"] == 2
        assert [o["i"] for o in r1["outcomes"]] == list(range(16))

    def test_sre_storm_heals_under_open_loop_arrivals(self):
        """The composition the ISSUE names: Poisson arrivals keep
        landing while seeded wedges kill replicas and the watchdog/
        supervisor loop heals the fleet mid-storm.  Deterministic run
        over run; the fleet ends at full strength."""
        from k8s_llm_rca_tpu.faults.soak import run_open_loop_soak

        k1 = _wedge_killer(seed=6, rate=0.2, horizon=24)
        r1 = run_open_loop_soak(seed=4, rate_per_s=200.0, n_runs=24,
                                selfheal=True, killer=k1)
        assert k1.kills                      # the storm drew blood
        assert r1["completed"] + r1["failed"] == 24
        assert r1["fleet_alive"] == 2        # and the fleet healed
        # arrivals land milliseconds apart, so a kill can hit a replica
        # that is ALREADY wedged (killing a dead process) — each wedge
        # WINDOW heals exactly once, so restarts <= kills, never zero
        restarts = k1.router.supervisor.restarts
        assert restarts
        assert len(restarts) <= len(k1.kills)
        assert set(restarts) <= set(k1.kills)
        assert all(not r.wedged
                   for r in k1.router.replicas.values())

        k2 = _wedge_killer(seed=6, rate=0.2, horizon=24)
        r2 = run_open_loop_soak(seed=4, rate_per_s=200.0, n_runs=24,
                                selfheal=True, killer=k2)
        assert k2.kills == k1.kills
        assert json.dumps(r1, sort_keys=True) == json.dumps(r2,
                                                            sort_keys=True)
