"""Out-of-process replica tests (cluster/proc.py, cluster/wire.py).

Layers, cheapest first:

- **wire codec units** (no subprocess): frame round-trips, and every
  corruption class — torn frame, CRC flip, oversized header, non-JSON,
  non-dict — raises ``WireCorrupt`` instead of returning garbage, while
  a silent peer raises ``WireTimeout`` instead of wedging the reader.
- **loud exclusions** (no subprocess): proc × CP/PP composition, nested
  proc-in-proc, killer-mode misuse, and the pipelined sweep's
  proc-cluster refusal all ValueError with actionable messages.
- **worker fleet** (real spawns, scripted workers ~0.5 s each): the
  LMBackend surface over the pipe, REAL SIGKILL detected by the
  watchdog's hard-evidence path (pipe EOF / exit code — never a hung
  probe loop), failover byte-identity vs the in-process echo cluster,
  supervisor restart of the actual OS process (fresh pid, incarnation
  + 1), and the drain -> TERM -> KILL close ladder.
- **kill-and-heal soak** (the ISSUE acceptance bar): 100 incidents on
  proc-oracle replicas with seeded SIGKILLs, zero manual
  ``fail_replica`` calls, report bytes identical to the unkilled
  in-process cluster-oracle run — twice over.
- **engine parity** (slow): greedy byte-parity of a proc engine-worker
  cluster against the plain in-process engine.
"""

from __future__ import annotations

import io
import os

import pytest

from k8s_llm_rca_tpu.cluster import (
    ClusterRouter, HealthPolicy, HealthWatchdog, Replica,
    ReplicaSupervisor,
)
from k8s_llm_rca_tpu.cluster.proc import (
    WORKER_ENV, ProcReplica, build_proc_replicas,
)
from k8s_llm_rca_tpu.cluster.wire import (
    HEADER, FrameReader, WireCorrupt, WireEOF, WireTimeout, pack_frame,
    write_frame,
)
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.faults.plan import FaultPlan, VirtualClock
from k8s_llm_rca_tpu.serve.backend import EchoBackend, GenOptions
from k8s_llm_rca_tpu.utils import wal
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.procluster


def _close_all(router: ClusterRouter) -> None:
    for r in router.replicas.values():
        close = getattr(r, "close", None)
        if close is not None:
            close()


def _settle(router, handles, pumps=64):
    out = {}
    for _ in range(pumps):
        out.update(router.pump())
        if all(h in out for h in handles):
            return out
    raise AssertionError(f"runs never settled: {sorted(out)}")


def _watchdog():
    # hard-evidence escalation is one state per probe, so thresholds
    # only bound the SOFT (missed-signal) path
    return HealthWatchdog(HealthPolicy(miss_budget=1,
                                       hung_tick_threshold=2),
                          clock=VirtualClock())


def _proc_killer(seed=2, rate=0.03, horizon=100):
    from k8s_llm_rca_tpu.faults.supervisor import ProcKiller

    return ProcKiller(FaultPlan.from_spec(
        seed, {inject.SITE_PROC: {"rate": rate, "horizon": horizon,
                                  "kinds": ("crash",)}}))


# ---------------------------------------------------------------------------
# wire codec units (no subprocess)
# ---------------------------------------------------------------------------


class TestWireCodec:
    def test_frames_round_trip_in_order(self):
        buf = io.BytesIO()
        msgs = [{"op": "ping", "id": 0}, {"op": "pump", "id": 1,
                                          "nested": {"a": [1, 2]}}]
        for m in msgs:
            write_frame(buf, m)
        buf.seek(0)
        reader = FrameReader(buf)
        assert [reader.read_frame() for _ in msgs] == msgs
        with pytest.raises(WireEOF):
            reader.read_frame()

    def test_partial_chunks_are_buffered_across_fills(self):
        # a stream that trickles one frame in 3-byte chunks: the reader
        # must assemble it across fills, never mis-frame
        frame = pack_frame({"op": "start", "id": 7})

        class Trickle:
            def __init__(self, data):
                self._chunks = [data[i:i + 3]
                                for i in range(0, len(data), 3)]

            def read1(self, n):
                return self._chunks.pop(0) if self._chunks else b""

        assert FrameReader(Trickle(frame)).read_frame() == \
            {"op": "start", "id": 7}

    def test_torn_frame_raises_corrupt_not_clean_eof(self):
        frame = pack_frame({"op": "ping", "id": 0})
        reader = FrameReader(io.BytesIO(frame[:-3]))
        with pytest.raises(WireCorrupt, match="torn frame"):
            reader.read_frame()

    def test_crc_flip_raises_corrupt(self):
        frame = bytearray(pack_frame({"op": "ping", "id": 0}))
        frame[-1] ^= 0xFF
        with pytest.raises(WireCorrupt, match="CRC mismatch"):
            FrameReader(io.BytesIO(bytes(frame))).read_frame()

    def test_oversized_length_raises_corrupt(self):
        header = HEADER.pack(wal.MAX_RECORD_SIZE + 1, 0)
        with pytest.raises(WireCorrupt, match="exceeds MAX_FRAME_SIZE"):
            FrameReader(io.BytesIO(header + b"x" * 64)).read_frame()

    def test_valid_crc_non_json_raises_corrupt(self):
        with pytest.raises(WireCorrupt, match="not JSON"):
            FrameReader(io.BytesIO(
                wal.pack_record(b"\xff\xfe{"))).read_frame()

    def test_non_dict_payload_raises_corrupt(self):
        with pytest.raises(WireCorrupt, match="JSON object"):
            FrameReader(io.BytesIO(
                wal.pack_record(b"[1,2,3]"))).read_frame()

    def test_silent_peer_raises_timeout_on_real_fd(self):
        r_fd, w_fd = os.pipe()
        try:
            reader = FrameReader(os.fdopen(r_fd, "rb", buffering=0))
            with pytest.raises(WireTimeout, match="missed its protocol"):
                reader.read_frame(timeout_s=0.05)
        finally:
            os.close(w_fd)


# ---------------------------------------------------------------------------
# loud exclusions (no subprocess)
# ---------------------------------------------------------------------------


class _FakeProcReplica(Replica):
    """In-process stand-in exposing the proc surface the killer checks
    (``kill_process``) — lets the mode-policy tests run without spawning."""

    def __init__(self, rid, tok):
        super().__init__(rid, EchoBackend(tok))
        self.killed = False

    def kill_process(self):
        self.killed = True


def _always_fire_killer(mode, site=inject.SITE_REPLICA):
    from k8s_llm_rca_tpu.faults.supervisor import ReplicaKiller

    return ReplicaKiller(FaultPlan.from_spec(
        0, {site: {"rate": 1.0, "horizon": 4, "kinds": ("crash",)}}),
        mode=mode)


class TestExclusions:
    def test_proc_refuses_sharding_spec_keys(self):
        for key in ("mesh", "context_parallel", "pipeline_parallel",
                    "cp", "pp"):
            with pytest.raises(ValueError, match="do not compose"):
                build_proc_replicas(2, **{key: object()})

    def test_zero_replicas_rejected(self):
        with pytest.raises(ValueError, match="n_replicas"):
            build_proc_replicas(0)

    def test_nested_proc_in_proc_rejected(self, monkeypatch):
        monkeypatch.setenv(WORKER_ENV, "1")
        with pytest.raises(ValueError, match="nested proc-in-proc"):
            build_proc_replicas(1)

    def test_unknown_worker_kind_rejected_before_spawn(self):
        with pytest.raises(ValueError, match="unknown proc worker kind"):
            build_proc_replicas(1, kind="quantum")

    def test_unknown_kill_mode_rejected(self):
        from k8s_llm_rca_tpu.faults.supervisor import ReplicaKiller

        with pytest.raises(ValueError, match="unknown kill mode"):
            ReplicaKiller(FaultPlan.from_spec(0, {}), mode="nuke")

    def test_auto_mode_refuses_proc_victim(self):
        tok = get_tokenizer()
        router = ClusterRouter([_FakeProcReplica(0, tok),
                                _FakeProcReplica(1, tok)])
        k = _always_fire_killer("auto")
        k.router = router
        with pytest.raises(ValueError, match="refuses out-of-process"):
            k.checkpoint()

    def test_wedge_mode_requires_watchdog(self):
        tok = get_tokenizer()
        router = ClusterRouter([Replica(0, EchoBackend(tok)),
                                Replica(1, EchoBackend(tok))])
        k = _always_fire_killer("wedge")
        k.router = router
        with pytest.raises(ValueError, match="without an attached"):
            k.checkpoint()

    def test_sigkill_mode_requires_proc_victim(self):
        tok = get_tokenizer()
        router = ClusterRouter([Replica(0, EchoBackend(tok)),
                                Replica(1, EchoBackend(tok))])
        k = _always_fire_killer("sigkill")
        k.router = router
        with pytest.raises(ValueError, match="needs an out-of-process"):
            k.checkpoint()

    def test_sigkill_last_alive_without_restart_is_plan_bug(self):
        tok = get_tokenizer()
        router = ClusterRouter([_FakeProcReplica(0, tok)])
        k = _always_fire_killer("sigkill")
        k.router = router
        with pytest.raises(ValueError, match="refusing SIGKILL"):
            k.checkpoint()

    def test_pipelined_sweep_refuses_proc_cluster(self):
        from k8s_llm_rca_tpu.faults.soak import run_pipelined_sweep

        with pytest.raises(ValueError, match="chaos-soak-only"):
            run_pipelined_sweep(n_incidents=1, backend="proc-cluster")


# ---------------------------------------------------------------------------
# worker fleet (real subprocess spawns, scripted workers)
# ---------------------------------------------------------------------------


class TestWorkerFleet:
    def test_oracle_roundtrip_graceful_close_exits_zero(self):
        (rep,) = build_proc_replicas(1, kind="oracle")
        try:
            b = rep.backend
            assert rep.healthy() and b.proc_liveness() is None
            h = b.start("node notready", GenOptions())
            assert h >= 0 and b.busy(h) and b.queue_depth() == 1
            out = {}
            for _ in range(20):
                out.update(b.pump())
                if h in out:
                    break
            assert out[h].error is None and out[h].text
            assert not b.busy(h) and b.queue_depth() == 0
            assert b.count_tokens("abc def") == \
                get_tokenizer().count("abc def")
        finally:
            rep.close()
        # drain frame acked -> worker exited 0, pipes reaped
        assert rep.backend._proc.poll() == 0

    def test_sigkill_mid_flight_failover_is_byte_identical(self):
        tok = get_tokenizer()
        prompts = [f"incident p{i}" for i in range(4)]
        # reference: the SAME runs on an unkilled in-process echo cluster
        ref_router = ClusterRouter(
            [Replica(i, EchoBackend(tok, delay_pumps=2))
             for i in range(2)])
        ref_handles = [ref_router.start(p, GenOptions(session=f"s{i}"))
                       for i, p in enumerate(prompts)]
        ref = _settle(ref_router, ref_handles)

        router = ClusterRouter(
            build_proc_replicas(2, kind="echo", echo_delay_pumps=2))
        try:
            router.attach_health(_watchdog(), ReplicaSupervisor())
            handles = [router.start(p, GenOptions(session=f"s{i}"))
                       for i, p in enumerate(prompts)]
            victim = router._handle_map[handles[0]][0]
            router.replicas[victim].kill_process()
            # hard evidence (exit:-9) is already on record, mid-decode
            assert "exit:-9" in router.replicas[victim].proc_liveness()
            assert not router.replicas[victim].healthy()
            out = _settle(router, handles)
            for rh, h in zip(ref_handles, handles):
                assert out[h].text == ref[rh].text
                assert out[h].error is None
            # the whole loop ran in-tree on OS evidence
            assert router.health.hard_detections == [victim]
            assert router.supervisor.restarts == [victim]
            assert sorted(router.alive_ids()) == [0, 1]
            assert all(r.healthy() for r in router.replicas.values())
        finally:
            _close_all(router)

    def test_supervisor_restarts_the_actual_process(self):
        router = ClusterRouter(build_proc_replicas(2, kind="oracle"))
        try:
            router.attach_health(_watchdog(), ReplicaSupervisor())
            old_pid = router.replicas[0].backend.pid
            router.replicas[0].kill_process()
            for _ in range(6):
                if router.replicas[0].healthy():
                    break
                router.pump()
            fresh = router.replicas[0].backend
            assert fresh.pid != old_pid          # a NEW os process
            assert fresh.incarnation == 1
            assert fresh.proc_liveness() is None
            assert router.health.hard_detections == [0]
            assert router.supervisor.incarnations[0] == 1
            # the fresh incarnation actually serves
            h = fresh.start("node notready", GenOptions())
            out = {}
            for _ in range(20):
                out.update(fresh.pump())
                if h in out:
                    break
            assert out[h].error is None
        finally:
            _close_all(router)

    def test_corrupt_frame_marks_dead_never_hangs(self):
        # the worker writes garbage mid-stream and hard-exits after its
        # first handled request: the NEXT rpc sees a torn/corrupt frame,
        # records evidence, and the proxy black-holes instead of raising
        (rep,) = build_proc_replicas(1, kind="echo",
                                     chaos_corrupt_after=1)
        try:
            b = rep.backend
            h = b.start("p", GenOptions())      # request 1: served
            assert h >= 0
            assert b.pump() == {}               # request 2: corrupted
            evidence = b.proc_liveness()
            assert evidence is not None and "rpc failed" in evidence
            assert not rep.healthy()
            # post-mortem starts black-hole on synthetic local handles
            h2 = b.start("q", GenOptions())
            assert h2 < 0 and b.busy(h2)
        finally:
            rep.close()                          # idempotent over a corpse
        assert rep.backend._proc.poll() is not None

    def test_missed_protocol_heartbeat_times_out_dead(self):
        (rep,) = build_proc_replicas(1, kind="echo", chaos_hang_after=1,
                                     rpc_timeout_s=0.5)
        try:
            b = rep.backend
            assert b.start("p", GenOptions()) >= 0
            assert b.pump() == {}                # worker went silent
            evidence = b.proc_liveness()
            assert evidence is not None and "WireTimeout" in evidence
            assert not rep.healthy()
        finally:
            rep.close(timeout_s=0.5)             # TERM/KILL escalation
        assert rep.backend._proc.poll() is not None

    def test_watchdog_turns_corrupt_transport_into_failover(self):
        # replica 0's worker corrupts on its FIRST request; the run
        # black-holes, the watchdog escalates on evidence (SUSPECT ->
        # DEAD in two probes) and failover settles the run on replica 1
        reps = [ProcReplica(0, kind="echo", chaos_corrupt_after=0),
                ProcReplica(1, kind="echo")]
        router = ClusterRouter(reps)
        try:
            router.attach_health(_watchdog())    # no supervisor: fail over
            h = router.start("p", GenOptions())
            assert router._handle_map[h][0] == 0
            out = _settle(router, [h], pumps=8)
            assert out[h].error is None
            assert out[h].text == "echo: p"
            assert router.health.hard_detections == [0]
            assert router.alive_ids() == [1]
        finally:
            _close_all(router)

    def test_drain_refused_for_scripted_proc_replicas(self):
        router = ClusterRouter(build_proc_replicas(2, kind="oracle"))
        try:
            with pytest.raises(ValueError, match="needs engine replicas"):
                router.drain_replica(0)
        finally:
            _close_all(router)

    def test_prometheus_exports_per_process_gauges(self):
        from k8s_llm_rca_tpu.obs.export import prometheus_text

        router = ClusterRouter(build_proc_replicas(2, kind="echo"))
        try:
            router.replicas[1].kill_process()
            text = prometheus_text(router=router)
            pid0 = router.replicas[0].backend.pid
            pid1 = router.replicas[1].backend.pid
            assert (f'cluster_proc_alive{{replica="0",pid="{pid0}",'
                    f'incarnation="0"}} 1') in text
            assert (f'cluster_proc_alive{{replica="1",pid="{pid1}",'
                    f'incarnation="0"}} 0') in text
            assert "cluster_proc_rpcs" in text
        finally:
            _close_all(router)


# ---------------------------------------------------------------------------
# the acceptance bar: 100-incident SIGKILL-and-heal soak, byte-identical
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestProcKillAndHealSoak:
    def test_100_incident_sigkill_and_heal_byte_identical(self):
        """Real SIGKILLs against real worker processes, zero manual
        ``fail_replica`` calls: every kill is detected on hard OS
        evidence (pipe EOF / exit code), failed over, and the actual
        process restarted — and the report is byte-identical to the
        unkilled IN-PROCESS cluster-oracle run, twice over (transport
        and murder are deployment details, not outcomes)."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        base = run_chaos_soak(seed=11, n_incidents=100,
                              backend="cluster-oracle",
                              cluster_replicas=4)
        assert base["completed"] == 100
        assert base["failed"] == 0

        k1 = _proc_killer()
        healed = run_chaos_soak(seed=11, n_incidents=100,
                                backend="proc-cluster",
                                cluster_replicas=4, killer=k1,
                                selfheal=True)
        assert k1.kills                      # SIGKILLs actually landed
        assert report_bytes(healed) == report_bytes(base)
        router = k1.router
        # every detection carried hard OS evidence — the watchdog saw
        # actual process exits, not just wedged ticks
        assert router.health.detections == k1.kills
        assert router.health.hard_detections == k1.kills
        assert router.supervisor.restarts == k1.kills
        assert router.failovers == len(k1.kills)
        assert sorted(router.alive_ids()) == [0, 1, 2, 3]
        # the soak's reaping context closed every worker on exit
        for r in router.replicas.values():
            assert r.backend._proc.poll() is not None

        k2 = _proc_killer()
        again = run_chaos_soak(seed=11, n_incidents=100,
                               backend="proc-cluster",
                               cluster_replicas=4, killer=k2,
                               selfheal=True)
        assert k2.kills == k1.kills          # the kill schedule is seeded
        assert report_bytes(again) == report_bytes(base)

    def test_proc_soak_without_chaos_matches_in_process(self):
        """Transport invariance alone: no killer, no selfheal — the
        proc-cluster sweep's report must already be byte-identical to
        the in-process cluster-oracle run."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        base = run_chaos_soak(seed=3, n_incidents=6,
                              backend="cluster-oracle")
        proc = run_chaos_soak(seed=3, n_incidents=6,
                              backend="proc-cluster")
        assert report_bytes(proc) == report_bytes(base)
        assert proc["backend"] == "cluster-oracle"


# ---------------------------------------------------------------------------
# engine workers: greedy byte-parity over the wire (slow: worker compiles)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestEngineProcParity:
    def test_proc_engine_cluster_matches_plain_engine(self):
        """Each prompt's greedy text from a 2-worker proc engine cluster
        must be byte-identical to the plain in-process engine's on the
        identical TINY config and seed-0 params — the identical-replica
        invariant, now across a process boundary."""
        import jax

        from k8s_llm_rca_tpu.config import TINY, EngineConfig
        from k8s_llm_rca_tpu.engine import make_engine
        from k8s_llm_rca_tpu.models import llama

        cfg = TINY.replace(max_seq_len=2560)
        ecfg = EngineConfig(max_batch=4, max_seq_len=2560,
                            prefill_buckets=(2560,), max_new_tokens=96,
                            temperature=0.0, page_size=64,
                            num_pages=168, prefix_cache=False,
                            decode_chunk=16)
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ref_engine = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        prompts = ["pod pending unschedulable node affinity mismatch",
                   "pvc not bound storageclass missing"]
        ref = ref_engine.generate(
            [tok.encode(p, add_bos=True) for p in prompts],
            max_new_tokens=8)

        router = ClusterRouter(build_proc_replicas(2, kind="engine",
                                                   seed=0))
        try:
            handles = [router.start(p, GenOptions(max_new_tokens=8))
                       for p in prompts]
            assert {router._handle_map[h][0] for h in handles} == {0, 1}
            out = _settle(router, handles, pumps=256)
            for h, r in zip(handles, ref):
                assert out[h].text == r.text   # byte-identical greedy text
                assert out[h].error is None
        finally:
            _close_all(router)
