"""Multi-replica serving cluster (k8s_llm_rca_tpu/cluster/).

Three layers of proof, mirroring the repo's parallelism conventions:

- **carving + loud exclusions**: every supported submesh shape on the
  8-virtual-device mesh (2×tp4, 4×tp2) carves disjointly; indivisible
  counts, overlapping device groups, and CP/PP/EP×replica compositions
  all raise ValueError at construction.
- **exact greedy parity**: each supported replica configuration emits
  byte-identical text to the plain single-engine path — the same parity
  bar every other parallelism mode meets (tests/test_parallel*.py).
- **failover**: hard kills re-start journal-recorded prompts on
  survivors under unchanged global handles; graceful drains migrate
  sequences WITH decode position via snapshot/adopt and finish
  byte-identical to an undisturbed run, re-prefilling mostly from the
  target's prefix cache; the 100-incident cluster-oracle chaos soak
  under seeded replica kills reports byte-identically to the unkilled
  sweep (the killer polls its OWN plan — faults/supervisor.py).

Echo replicas drive the pure routing tests (affinity, balancing,
backpressure) — the router is backend-agnostic by design.
"""

import pytest

from k8s_llm_rca_tpu.cluster import (
    ClusterRouter, Replica, RouterAdmissionError, build_replicas,
    carve_replica_meshes,
)
from k8s_llm_rca_tpu.config import TINY, EngineConfig, MeshConfig
from k8s_llm_rca_tpu.engine.engine import (
    validate_disjoint_submeshes, validate_replica_mesh,
)
from k8s_llm_rca_tpu.runtime.mesh import build_mesh
from k8s_llm_rca_tpu.serve.backend import EchoBackend, GenOptions
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.cluster


# ---------------------------------------------------------------------------
# submesh carving + loud exclusions
# ---------------------------------------------------------------------------


class TestCarving:
    @pytest.mark.parametrize("n,tp", [(2, 4), (4, 2)])
    def test_supported_shapes_carve_disjointly(self, cpu_devices, n, tp):
        meshes = carve_replica_meshes(n, devices=cpu_devices)
        assert len(meshes) == n
        seen = set()
        for mesh in meshes:
            assert mesh.shape["model"] == tp
            assert mesh.shape["data"] == 1
            ids = {d.id for d in mesh.devices.flat}
            assert not (ids & seen)         # disjoint
            seen |= ids
        assert len(seen) == len(cpu_devices[:8])

    def test_indivisible_count_rejected(self, cpu_devices):
        with pytest.raises(ValueError, match="do not split"):
            carve_replica_meshes(3, devices=cpu_devices)

    def test_indivisible_data_axis_rejected(self, cpu_devices):
        with pytest.raises(ValueError, match="data"):
            carve_replica_meshes(2, devices=cpu_devices, data=3)

    def test_overlapping_submeshes_rejected(self, cpu_devices):
        a = build_mesh(MeshConfig(model=4), devices=cpu_devices[:4])
        b = build_mesh(MeshConfig(model=4), devices=cpu_devices[2:6])
        with pytest.raises(ValueError, match="overlap"):
            validate_disjoint_submeshes([a, b])

    @pytest.mark.parametrize("axes,what", [
        (dict(data=2, model=2, seq=2), "CP×replica"),
        (dict(data=2, model=2, stage=2), "PP×replica"),
        (dict(data=2, model=2, expert=2), "EP×replica"),
    ])
    def test_cross_replica_compositions_rejected(self, cpu_devices, axes,
                                                 what):
        mesh = build_mesh(MeshConfig(**axes), devices=cpu_devices[:8])
        ecfg = EngineConfig(max_batch=2, max_seq_len=64)
        with pytest.raises(ValueError, match="unsupported"):
            validate_replica_mesh(mesh, TINY, ecfg)

    def test_mesh_count_mismatch_rejected(self, cpu_devices):
        meshes = carve_replica_meshes(2, devices=cpu_devices)
        with pytest.raises(ValueError, match="meshes for"):
            build_replicas(TINY.replace(max_seq_len=64),
                           EngineConfig(max_batch=2, max_seq_len=64),
                           3, meshes=meshes)


# ---------------------------------------------------------------------------
# router on echo replicas: affinity / balance / backpressure / failover
# ---------------------------------------------------------------------------


def _echo_router(n=2, cap=None, delay_pumps=0, tok=None):
    tok = tok or get_tokenizer()
    reps = [Replica(i, EchoBackend(tok, delay_pumps=delay_pumps))
            for i in range(n)]
    return ClusterRouter(reps, max_inflight_per_replica=cap), reps


def _settle(router, handles, pumps=64):
    out = {}
    for _ in range(pumps):
        out.update(router.pump())
        if all(h in out for h in handles):
            return out
    raise AssertionError(f"runs never settled: {out.keys()}")


class TestRouter:
    def test_session_affinity_sticks_while_alive(self):
        router, _ = _echo_router(n=2, delay_pumps=10 ** 9)
        h = [router.start("p", GenOptions(session="t1")) for _ in range(3)]
        rids = {router._handle_map[x][0] for x in h}
        assert len(rids) == 1               # pinned, despite load skew
        assert router._affinity["t1"] in rids

    def test_unpinned_runs_balance_to_least_depth(self):
        router, _ = _echo_router(n=2, delay_pumps=10 ** 9)
        rids = [router._handle_map[router.start("p", GenOptions())][0]
                for _ in range(4)]
        # depth-least with lowest-id tiebreak => strict alternation
        assert rids == [0, 1, 0, 1]

    def test_affinity_overflow_does_not_repin(self):
        router, _ = _echo_router(n=2, cap=1, delay_pumps=10 ** 9)
        h1 = router.start("p", GenOptions(session="t1"))
        pinned = router._handle_map[h1][0]
        h2 = router.start("p", GenOptions(session="t1"))   # pinned full
        assert router._handle_map[h2][0] != pinned         # overflowed
        assert router._affinity["t1"] == pinned            # pin kept

    def test_backpressure_sheds_loudly(self):
        router, _ = _echo_router(n=2, cap=1, delay_pumps=10 ** 9)
        router.start("p", GenOptions())
        router.start("p", GenOptions())
        with pytest.raises(RouterAdmissionError, match="inflight cap"):
            router.start("p", GenOptions())

    def test_queue_depth_and_occupancy_accessors(self):
        router, reps = _echo_router(n=2, delay_pumps=10 ** 9)
        router.start("p", GenOptions(session="a"))
        assert sorted(router.alive_ids()) == [0, 1]
        depths = router.queue_depths()
        assert sum(depths.values()) == 1
        assert set(router.occupancies()) == {0, 1}   # echo: 0.0 values

    def test_failover_keeps_global_handles_and_completes(self):
        tok = get_tokenizer()
        router, reps = _echo_router(n=2, delay_pumps=2, tok=tok)
        handles = [router.start(f"p{i}", GenOptions(session=f"s{i}"))
                   for i in range(4)]
        victim = 0
        moved = router.fail_replica(victim)
        assert moved                         # someone lived on replica 0
        assert not reps[victim].alive
        assert router.alive_ids() == [1]
        # the same global handles settle after the kill
        out = _settle(router, handles)
        assert sorted(out) == sorted(handles)
        assert all(v.error is None for v in out.values())
        # affinity repinned off the corpse
        h = router.start("p0", GenOptions(session="s0"))
        assert router._handle_map[h][0] == 1

    def test_failover_bypasses_admission_cap(self):
        router, _ = _echo_router(n=2, cap=1, delay_pumps=10 ** 9)
        router.start("a", GenOptions())      # -> replica 0
        router.start("b", GenOptions())      # -> replica 1 (cap reached)
        moved = router.fail_replica(0)
        assert len(moved) == 1               # re-homed despite the cap
        assert router.queue_depths() == {1: 2}

    def test_last_alive_replica_cannot_be_killed(self):
        router, _ = _echo_router(n=2)
        router.fail_replica(0)
        with pytest.raises(ValueError, match="last alive"):
            router.fail_replica(1)

    def test_dead_or_unknown_replica_rejected(self):
        router, _ = _echo_router(n=3)
        router.fail_replica(1)
        with pytest.raises(ValueError, match="not alive"):
            router.fail_replica(1)
        with pytest.raises(ValueError, match="not alive"):
            router.fail_replica(9)

    def test_duplicate_replica_ids_rejected(self):
        tok = get_tokenizer()
        with pytest.raises(ValueError, match="duplicate"):
            ClusterRouter([Replica(0, EchoBackend(tok)),
                           Replica(0, EchoBackend(tok))])

    def test_cancel_routes_to_owning_replica(self):
        router, reps = _echo_router(n=2, delay_pumps=10 ** 9)
        h = router.start("p", GenOptions())
        rid, lh = router._handle_map[h]
        router.cancel(h)
        assert not router.busy(h)
        assert reps[rid].queue_depth() == 0
        assert router.pump() == {}           # nothing leaks into results


# ---------------------------------------------------------------------------
# exact greedy parity per supported replica configuration (engine replicas)
# ---------------------------------------------------------------------------


_PARITY_PROMPTS = [
    "pod pending unschedulable node affinity mismatch",
    "pvc not bound storageclass missing",
    "image pull backoff registry unreachable",
    "oom killed container memory limit",
]


def _engine_cfgs():
    cfg = TINY.replace(max_seq_len=64)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32), max_new_tokens=6,
                        temperature=0.0)
    return cfg, ecfg


class TestGreedyParity:
    @pytest.mark.parametrize("n_replicas", [2, 4])
    def test_replica_cluster_matches_plain_engine(self, cpu_devices,
                                                  n_replicas):
        """Every prompt's text from the N-replica cluster must be
        byte-identical to the plain unsharded single engine's — and
        every replica must actually serve at least one prompt (else the
        parity claim silently narrows to one submesh)."""
        import jax

        from k8s_llm_rca_tpu.engine import make_engine
        from k8s_llm_rca_tpu.models import llama

        cfg, ecfg = _engine_cfgs()
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ref_engine = make_engine(cfg, ecfg, params, tok)
        prompts = _PARITY_PROMPTS[:n_replicas]
        ref = ref_engine.generate(
            [tok.encode(p, add_bos=True) for p in prompts],
            max_new_tokens=6)

        replicas = build_replicas(cfg, ecfg, n_replicas,
                                  devices=cpu_devices, seed=0)
        router = ClusterRouter(replicas)
        handles = [router.start(p, GenOptions(max_new_tokens=6))
                   for p in prompts]
        served = {router._handle_map[h][0] for h in handles}
        assert served == set(range(n_replicas))
        out = _settle(router, handles, pumps=256)
        for h, r in zip(handles, ref):
            assert out[h].text == r.text     # byte-identical greedy text
            assert out[h].error is None


# ---------------------------------------------------------------------------
# graceful drain: sequences migrate WITH decode position, byte-identical
# ---------------------------------------------------------------------------


class TestDrainMigration:
    def test_mid_decode_drain_is_byte_identical_and_prefix_hits(
            self, cpu_devices):
        import jax

        from k8s_llm_rca_tpu.engine import make_engine
        from k8s_llm_rca_tpu.models import llama

        cfg = TINY.replace(max_seq_len=64)
        ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                            prefill_buckets=(16, 32), max_new_tokens=10,
                            temperature=0.0, page_size=8,
                            num_pages=32, decode_chunk=1,
                            prefix_cache=True)
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        prompt = "pod pending unschedulable node affinity mismatch"
        opts = GenOptions(max_new_tokens=10, session="thread_7")

        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ref = make_engine(cfg, ecfg, params, tok, use_kernel=False).generate(
            [tok.encode(prompt, add_bos=True)], max_new_tokens=10)[0]

        replicas = build_replicas(cfg, ecfg, 2, devices=cpu_devices,
                                  seed=0, use_kernel=False)
        router = ClusterRouter(replicas)
        # warm BOTH prefix caches: a full run of the same session on each
        # replica (retired pages are inserted into the prefix cache), so
        # the migrated re-prefill on the target can mostly HIT
        for rid in (0, 1):
            router._affinity["thread_7"] = rid
            out = _settle(router, [router.start(prompt, opts)], pumps=256)
            assert list(out.values())[0].text == ref.text
        router._affinity["thread_7"] = 0

        h = router.start(prompt, opts)
        assert router._handle_map[h][0] == 0
        for _ in range(4):                    # mid-decode (chunk=1)
            assert not router.pump()
        target_engine = replicas[1].backend.engine
        hits_before = target_engine._counts.get(
            "engine.prefix_hit_tokens", 0)
        moved = router.drain_replica(0)
        assert moved == [h]
        assert router._handle_map[h][0] == 1
        assert router.migrated_runs == 1
        out = _settle(router, [h], pumps=256)
        # byte-identical to the undisturbed single-engine run
        assert out[h].text == ref.text
        # the re-prefill was a mostly-HIT path: at least one full page of
        # prompt+generated came from the target's prefix cache
        hits = target_engine._counts.get("engine.prefix_hit_tokens", 0)
        assert hits - hits_before >= ecfg.page_size
        # the drained source ended clean (pages freed via normal retire)
        src_engine = replicas[0].backend.engine
        assert not src_engine.has_work
        src_engine.allocator.check()

    def test_drain_needs_engine_replicas(self):
        router, _ = _echo_router(n=2)
        with pytest.raises(ValueError, match="engine replicas"):
            router.drain_replica(0)

    def test_drain_refuses_bad_target(self, cpu_devices):
        router, _ = _echo_router(n=2)
        with pytest.raises(ValueError, match="DIFFERENT"):
            router.drain_replica(0, target=0)


# ---------------------------------------------------------------------------
# journal + recovery through the router
# ---------------------------------------------------------------------------


class TestJournaledFailover:
    def test_recover_service_routes_resubmits_with_affinity(self,
                                                            tmp_path):
        from k8s_llm_rca_tpu.serve.api import AssistantService, RunStatus
        from k8s_llm_rca_tpu.serve.journal import RunJournal
        from k8s_llm_rca_tpu.serve.recover import recover_service

        path = str(tmp_path / "serve.wal")
        tok = get_tokenizer()
        router, _ = _echo_router(n=2, delay_pumps=10 ** 9, tok=tok)
        service = AssistantService(router, journal=RunJournal(path))
        a = service.create_assistant("cluster-test", "answer briefly")
        th = service.create_thread()
        service.add_message(th.id, "what failed?")
        run = service.create_run(th.id, a.id,
                                 gen=GenOptions(max_new_tokens=8))
        assert router._affinity[th.id] in (0, 1)   # session = thread id
        service._journal.close()                   # process death

        fresh_router, _ = _echo_router(n=2, tok=tok)
        svc, report = recover_service(path, fresh_router)
        assert report["resubmitted"] == [run.id]
        # the journaled session re-pins the thread on the fresh cluster
        assert fresh_router._affinity[th.id] in (0, 1)
        got = svc.wait_run(run.id)
        assert got.status == RunStatus.COMPLETED

    def test_settled_runs_never_reexecuted_through_router(self, tmp_path):
        from k8s_llm_rca_tpu.serve.api import AssistantService, RunStatus
        from k8s_llm_rca_tpu.serve.journal import RunJournal
        from k8s_llm_rca_tpu.serve.recover import recover_service

        path = str(tmp_path / "serve.wal")
        tok = get_tokenizer()
        router, _ = _echo_router(n=2, tok=tok)
        service = AssistantService(router, journal=RunJournal(path))
        a = service.create_assistant("cluster-test", "answer briefly")
        th = service.create_thread()
        service.add_message(th.id, "what failed?")
        run = service.wait_run(service.create_run(th.id, a.id).id)
        assert run.status == RunStatus.COMPLETED
        service._journal.close()

        class NeverStarts(ClusterRouter):
            def start(self, prompt, opts):
                raise AssertionError("settled run re-executed")

        fresh = NeverStarts([Replica(0, EchoBackend(tok)),
                             Replica(1, EchoBackend(tok))])
        svc, report = recover_service(path, fresh)
        assert report["resubmitted"] == []
        assert svc.runs[run.id].status == RunStatus.COMPLETED


# ---------------------------------------------------------------------------
# chaos soak under seeded replica kills (the acceptance sweep)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestClusterChaosSoak:
    def test_100_incident_kill_soak_byte_identical(self):
        """The ISSUE acceptance bar: a 100-incident sweep on oracle
        replicas, with seeded replica kills mid-sweep, completes on the
        survivors with a report byte-identical to the unkilled sweep's
        (and to a rerun of itself)."""
        from k8s_llm_rca_tpu.faults import inject
        from k8s_llm_rca_tpu.faults.plan import FaultPlan
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import ReplicaKiller

        base = run_chaos_soak(seed=11, n_incidents=100,
                              backend="cluster-oracle",
                              cluster_replicas=4)
        assert base["completed"] == 100
        assert base["failed"] == 0
        assert base["cluster_replicas"] == 4

        def killer():
            return ReplicaKiller(FaultPlan.from_spec(
                2, {inject.SITE_REPLICA: {
                    "rate": 0.03, "horizon": 100, "kinds": ("crash",)}}))

        k1 = killer()
        killed = run_chaos_soak(seed=11, n_incidents=100,
                                backend="cluster-oracle",
                                cluster_replicas=4, killer=k1)
        assert k1.kills                      # kills actually happened
        assert len(set(k1.kills)) == len(k1.kills)   # no double-kill
        assert report_bytes(killed) == report_bytes(base)

        k2 = killer()
        again = run_chaos_soak(seed=11, n_incidents=100,
                               backend="cluster-oracle",
                               cluster_replicas=4, killer=k2)
        assert k2.kills == k1.kills          # kill schedule is seeded
        assert report_bytes(again) == report_bytes(base)

    def test_killer_requires_cluster_backend(self):
        from k8s_llm_rca_tpu.faults import inject
        from k8s_llm_rca_tpu.faults.plan import FaultPlan
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import ReplicaKiller

        k = ReplicaKiller(FaultPlan.from_spec(
            0, {inject.SITE_REPLICA: {"rate": 1.0, "horizon": 4,
                                      "kinds": ("crash",)}}))
        with pytest.raises(ValueError, match="cluster"):
            run_chaos_soak(seed=0, n_incidents=1, backend="oracle",
                           killer=k)

    @pytest.mark.slow
    def test_engine_cluster_kill_soak_byte_identical(self):
        """Engine replicas under a mid-sweep kill: graph-faults-only plan
        (per-tick fault polls would legitimately shift with the
        survivor's extra ticks — fault-schedule divergence, not
        nondeterminism), report byte-identical to the unkilled run, every
        replica engine left clean."""
        from k8s_llm_rca_tpu.faults import inject
        from k8s_llm_rca_tpu.faults.plan import FaultPlan
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import ReplicaKiller

        spec = {inject.SITE_GRAPH: {
            "rate": 0.10, "horizon": 40, "delay_s": 0.01,
            "kinds": ("error", "timeout", "empty", "slow", "poison")}}
        base = run_chaos_soak(seed=5, n_incidents=2, backend="cluster",
                              plan_spec=spec, cluster_replicas=2)
        assert base["completed"] == 2
        assert base["engine_clean"] is True

        k = ReplicaKiller(FaultPlan.from_spec(
            3, {inject.SITE_REPLICA: {"rate": 0.6, "horizon": 2,
                                      "kinds": ("crash",)}}))
        killed = run_chaos_soak(seed=5, n_incidents=2, backend="cluster",
                                plan_spec=spec, cluster_replicas=2,
                                killer=k)
        assert k.kills                       # the kill fired mid-sweep
        assert killed["engine_clean"] is True
        assert report_bytes(killed) == report_bytes(base)
