"""Cross-host replica tests (cluster/net.py, faults/netem.py, and the
socket half of cluster/proc.py).

Layers, cheapest first:

- **socket codec units** (socketpair, no subprocess): transport
  round-trips, trickle reassembly under ONE shared deadline, the
  bounded write deadline (a zero-window peer raises ``WireTimeout``
  instead of wedging the sender), the ``timeout_s <= 0`` refusal, and
  the ``max_buffered_bytes`` garbage guard.
- **netem proxy units** (socketpair, no subprocess): every SITE_NET
  fault kind — partition/halfopen/heal stickiness, delay on the
  virtual clock, trickle, duplicate, corrupt — applied deterministically
  from a seeded plan, never the armed one.
- **loud exclusions** (no subprocess): unknown transport, zero relink
  budget, partitioning a pipe replica, NetKiller misuse, and the
  pipelined sweep's net-cluster refusal.
- **socket fleet** (real spawns): the relink-vs-respawn decision matrix
  — link death heals the SAME incarnation under a fresh session nonce
  with in-flight runs replayed; SIGKILL still respawns incarnation N+1;
  relink-budget exhaustion converts the outage into hard "link"
  evidence and hands the respawn path the replica.  Plus nonce fencing:
  a stale dial is refused on ITS OWN connection, a newer dial drops the
  old link (no split-brain), and duplicate/stale reply frames are
  discarded, never desync evidence.
- **partition-and-heal soak** (the ISSUE acceptance bar): 100 incidents
  on a socket-oracle fleet under seeded partitions, zero manual
  intervention, report bytes identical to the unpartitioned in-process
  cluster-oracle run — twice over, every heal a relink.
- **engine parity** (slow): greedy byte-parity of a socket
  engine-worker cluster against the plain in-process engine.
"""

from __future__ import annotations

import io
import socket
import subprocess
import sys

import pytest

from k8s_llm_rca_tpu.cluster import (
    ClusterRouter, HealthPolicy, HealthWatchdog, Replica,
    ReplicaSupervisor,
)
from k8s_llm_rca_tpu.cluster.net import (
    SocketTransport, client_handshake, connect_transport,
    send_with_deadline,
)
from k8s_llm_rca_tpu.cluster.proc import (
    build_proc_replicas, worker_env,
)
from k8s_llm_rca_tpu.cluster.wire import (
    FrameReader, WireCorrupt, WireEOF, WireError, WireTimeout, pack_frame,
)
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.faults.netem import NetemTransport
from k8s_llm_rca_tpu.faults.plan import Fault, FaultPlan, VirtualClock
from k8s_llm_rca_tpu.serve.backend import EchoBackend, GenOptions
from k8s_llm_rca_tpu.utils.logging import METRICS
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.netcluster


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
    return HealthWatchdog(HealthPolicy(miss_budget=1,
                                       hung_tick_threshold=2),
                          clock=VirtualClock())


def _net_killer(seed=2, rate=0.03, horizon=100,
                kinds=("partition", "halfopen")):
    from k8s_llm_rca_tpu.faults.supervisor import NetKiller

    return NetKiller(FaultPlan.from_spec(
        seed, {inject.SITE_NET: {"rate": rate, "horizon": horizon,
                                 "kinds": kinds}}))


def _pair():
    """A connected SocketTransport pair over a socketpair — real fds, so
    select deadlines and trickle reassembly behave exactly as on a TCP
    link."""
    a, b = socket.socketpair()
    return SocketTransport(a), SocketTransport(b)


# ---------------------------------------------------------------------------
# socket codec units (socketpair, no subprocess)
# ---------------------------------------------------------------------------


class TestSocketCodec:
    def test_socket_transport_round_trips_frames(self):
        left, right = _pair()
        try:
            msgs = [{"op": "ping", "id": 0},
                    {"op": "start", "id": 1, "nested": {"a": [1, 2]}}]
            for m in msgs:
                left.send(m)
            assert [right.recv(timeout_s=2.0) for _ in msgs] == msgs
        finally:
            left.close()
            right.close()

    def test_trickle_bytes_reassemble_under_one_deadline(self):
        # one frame fed a byte at a time must still decode, and the
        # reader spends ONE shared deadline across all the fills — not a
        # fresh timeout per byte
        left, right = _pair()
        try:
            frame = pack_frame({"op": "pump", "id": 3})
            for i in range(len(frame)):
                left.send_raw(frame[i:i + 1])
            assert right.recv(timeout_s=2.0) == {"op": "pump", "id": 3}
        finally:
            left.close()
            right.close()

    def test_wedged_peer_write_raises_timeout_not_hang(self):
        # the peer never reads: once both kernel buffers fill, the
        # bounded write deadline must surface WireTimeout instead of
        # wedging the sender in a blocking flush
        a, b = socket.socketpair()
        try:
            a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
            b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
            with pytest.raises(WireTimeout, match="send window wedged"):
                send_with_deadline(a, b"x" * (1 << 22), timeout_s=0.2)
        finally:
            a.close()
            b.close()

    def test_write_deadline_rejects_nonpositive_timeout(self):
        a, b = socket.socketpair()
        try:
            with pytest.raises(ValueError, match="must be > 0"):
                send_with_deadline(a, b"x", timeout_s=0.0)
        finally:
            a.close()
            b.close()

    def test_read_frame_rejects_nonpositive_timeout(self):
        reader = FrameReader(io.BytesIO(pack_frame({"op": "ping"})))
        for bad in (0, 0.0, -1.0):
            with pytest.raises(ValueError, match="must be > 0"):
                reader.read_frame(timeout_s=bad)

    def test_pending_decodes_buffered_only_never_blocks(self):
        left, right = _pair()
        try:
            assert right.pending() is None       # nothing buffered
            left.send({"op": "ping", "id": 0})
            left.send({"op": "ping", "id": 1})
            # one deadlined read pulls bytes in; pending drains the rest
            assert right.recv(timeout_s=2.0)["id"] == 0
            assert right.pending() == {"op": "ping", "id": 1}
            assert right.pending() is None
        finally:
            left.close()
            right.close()

    def test_garbage_spew_bounded_by_max_buffered_bytes(self):
        # a plausible header whose payload never completes: the bounded
        # staging buffer declares corruption instead of growing forever
        from k8s_llm_rca_tpu.cluster.wire import HEADER

        header = HEADER.pack(1 << 20, 0)         # 1 MiB frame, legal size
        spew = header + b"\x00" * (1 << 16)

        class Endless:
            def read1(self, n):
                return spew[:n] if spew else b""

        reader = FrameReader(Endless(), max_buffered_bytes=32768)
        with pytest.raises(WireCorrupt, match="spewing garbage"):
            for _ in range(64):
                reader.read_frame()

    def test_closed_transport_raises_eof_loudly(self):
        left, right = _pair()
        right.close()
        try:
            with pytest.raises(WireEOF, match="already closed"):
                right.send({"op": "ping", "id": 0})
            with pytest.raises(WireEOF, match="already closed"):
                right.recv(timeout_s=0.1)
        finally:
            left.close()


# ---------------------------------------------------------------------------
# netem proxy units (socketpair, no subprocess)
# ---------------------------------------------------------------------------


def _netem_pair(faults):
    """A netem-wrapped transport facing a raw peer, with the given
    faults scheduled on the netem's OWN plan at SITE_NET (one poll per
    send)."""
    left, right = _pair()
    plan = FaultPlan([Fault(inject.SITE_NET, i, k) for i, k in
                      enumerate(faults)])
    return NetemTransport(left, plan), right


class TestNetemProxy:
    def test_partition_is_sticky_until_heal(self):
        netem, peer = _netem_pair(["partition", "heal"])
        try:
            with pytest.raises(WireTimeout, match="partitioned"):
                netem.send({"op": "ping", "id": 0})        # draw 0
            with pytest.raises(WireTimeout, match="partitioned"):
                netem.recv(timeout_s=0.1)                  # still down
            netem.send({"op": "ping", "id": 1})            # draw 1: heal
            assert peer.recv(timeout_s=2.0)["id"] == 1
            assert netem.faults_applied == {"partition": 1, "heal": 1}
        finally:
            netem.close()
            peer.close()

    def test_halfopen_sends_flow_replies_drop(self):
        netem, peer = _netem_pair(["halfopen"])
        try:
            netem.send({"op": "ping", "id": 0})            # send flows
            assert peer.recv(timeout_s=2.0)["id"] == 0
            peer.send({"id": 0, "ok": True})
            with pytest.raises(WireTimeout, match="half-open"):
                netem.recv(timeout_s=0.1)                  # reply dropped
        finally:
            netem.close()
            peer.close()

    def test_trickle_frame_reassembles(self):
        netem, peer = _netem_pair(["trickle"])
        try:
            netem.send({"op": "start", "id": 7, "prompt": "p" * 64})
            got = peer.recv(timeout_s=2.0)
            assert got["id"] == 7 and got["prompt"] == "p" * 64
        finally:
            netem.close()
            peer.close()

    def test_duplicate_reply_delivered_twice(self):
        netem, peer = _netem_pair(["duplicate"])
        try:
            netem.send({"op": "ping", "id": 0})
            peer.recv(timeout_s=2.0)
            peer.send({"id": 0, "ok": True})
            first = netem.recv(timeout_s=2.0)
            second = netem.recv(timeout_s=2.0)   # the duplicate, buffered
            assert first == second == {"id": 0, "ok": True}
        finally:
            netem.close()
            peer.close()

    def test_corrupt_surfaces_wire_corrupt(self):
        netem, peer = _netem_pair(["corrupt"])
        try:
            netem.send({"op": "ping", "id": 0})
            with pytest.raises(WireCorrupt, match="bit-flip"):
                netem.recv(timeout_s=0.5)
        finally:
            netem.close()
            peer.close()

    def test_delay_advances_the_plan_clock_not_wall_time(self):
        left, right = _pair()
        clock = VirtualClock()
        plan = FaultPlan([Fault(inject.SITE_NET, 0, "delay",
                                delay_s=1.5)], clock=clock)
        netem = NetemTransport(left, plan)
        try:
            netem.send({"op": "ping", "id": 0})
            assert clock.time() == 1.5           # virtual, not slept
            assert right.recv(timeout_s=2.0)["id"] == 0
        finally:
            netem.close()
            right.close()

    def test_non_link_fault_kind_is_a_loud_plan_bug(self):
        netem, peer = _netem_pair(["stall"])     # legal kind, wrong site
        try:
            with pytest.raises(ValueError, match="netem cannot apply"):
                netem.send({"op": "ping", "id": 0})
        finally:
            netem.close()
            peer.close()

    def test_netem_polls_its_own_plan_never_the_armed_one(self):
        armed = FaultPlan([Fault(inject.SITE_NET, 0, "partition")])
        netem, peer = _netem_pair([])            # own plan: empty
        try:
            with inject.armed(armed):
                netem.send({"op": "ping", "id": 0})   # must NOT partition
            assert peer.recv(timeout_s=2.0)["id"] == 0
            assert armed.snapshot()["polls"] == {}    # untouched
        finally:
            netem.close()
            peer.close()


# ---------------------------------------------------------------------------
# loud exclusions (no subprocess)
# ---------------------------------------------------------------------------


class TestExclusions:
    def test_unknown_transport_rejected_before_spawn(self):
        with pytest.raises(ValueError, match="unknown proc transport"):
            build_proc_replicas(1, transport="carrier-pigeon")

    def test_zero_relink_budget_rejected(self):
        with pytest.raises(ValueError, match="relink_budget must be"):
            build_proc_replicas(1, transport="socket", relink_budget=0)

    def test_netkiller_refuses_non_socket_victim(self):
        tok = get_tokenizer()
        router = ClusterRouter([Replica(0, EchoBackend(tok)),
                                Replica(1, EchoBackend(tok))])
        router.attach_health(_watchdog())
        k = _net_killer(rate=1.0, horizon=4)
        k.router = router
        with pytest.raises(ValueError, match="needs a socket-transport"):
            k.checkpoint()

    def test_pipelined_sweep_refuses_net_cluster(self):
        from k8s_llm_rca_tpu.faults.soak import run_pipelined_sweep

        with pytest.raises(ValueError, match="chaos-soak-only"):
            run_pipelined_sweep(n_incidents=1, backend="net-cluster")


# ---------------------------------------------------------------------------
# socket fleet (real spawns): the relink-vs-respawn decision matrix
# ---------------------------------------------------------------------------


class TestSocketFleet:
    def test_socket_roundtrip_graceful_close_exits_zero(self):
        (rep,) = build_proc_replicas(1, kind="oracle", transport="socket")
        try:
            b = rep.backend
            assert rep.supports_relink
            assert rep.healthy() and b.proc_liveness() is None
            assert b.link_stats() == {"nonce": 1, "alive": 1,
                                      "relinks": 0}
            h = b.start("node notready", GenOptions())
            assert h >= 0 and b.busy(h)
            out = {}
            for _ in range(20):
                out.update(b.pump())
                if h in out:
                    break
            assert out[h].error is None and out[h].text
        finally:
            rep.close()
        # drain frame crossed the socket -> worker exited 0
        assert rep.backend._proc.poll() == 0

    def test_pipe_replica_has_no_link_to_cut(self):
        (rep,) = build_proc_replicas(1, kind="oracle")   # pipe default
        try:
            assert not rep.supports_relink
            assert rep.backend.link_stats() is None
            assert rep.relink() is False
            with pytest.raises(ValueError, match="cannot partition"):
                rep.partition_link()
        finally:
            rep.close()

    def test_netkiller_without_watchdog_refused(self):
        router = ClusterRouter(build_proc_replicas(
            2, kind="oracle", transport="socket"))
        try:
            k = _net_killer(rate=1.0, horizon=4)
            k.router = router
            with pytest.raises(ValueError, match="attach_health first"):
                k.checkpoint()
        finally:
            _close_all(router)

    def test_partition_relinks_same_incarnation_byte_identical(self):
        """The tentpole path: link severed mid-flight -> link evidence
        (process alive) -> relink under a fresh nonce on the SAME
        incarnation -> orphans replayed in place -> results byte-equal
        to an unpartitioned in-process echo cluster.  No respawn, no
        death verdict."""
        tok = get_tokenizer()
        prompts = [f"incident p{i}" for i in range(4)]
        ref_router = ClusterRouter(
            [Replica(i, EchoBackend(tok, delay_pumps=2))
             for i in range(2)])
        ref_handles = [ref_router.start(p, GenOptions(session=f"s{i}"))
                       for i, p in enumerate(prompts)]
        ref = _settle(ref_router, ref_handles)

        router = ClusterRouter(build_proc_replicas(
            2, kind="echo", echo_delay_pumps=2, transport="socket"))
        try:
            router.attach_health(_watchdog(), ReplicaSupervisor())
            handles = [router.start(p, GenOptions(session=f"s{i}"))
                       for i, p in enumerate(prompts)]
            victim = router._handle_map[handles[0]][0]
            b = router.replicas[victim].backend
            pid = b.pid
            router.replicas[victim].partition_link()
            out = _settle(router, handles)
            for rh, h in zip(ref_handles, handles):
                assert out[h].text == ref[rh].text
                assert out[h].error is None
            # relink, not respawn: same pid, same incarnation, nonce +1
            assert b.pid == pid and b.incarnation == 0
            assert b.link_stats() == {"nonce": 2, "alive": 1,
                                      "relinks": 1}
            assert router.supervisor.relinks == [victim]
            assert router.supervisor.restarts == []
            assert router.health.hard_detections == []
            assert router.failovers == 0
            assert all(r.healthy() for r in router.replicas.values())
        finally:
            _close_all(router)

    def test_halfopen_link_also_heals_by_relink(self):
        router = ClusterRouter(build_proc_replicas(
            2, kind="echo", echo_delay_pumps=2, transport="socket"))
        try:
            router.attach_health(_watchdog(), ReplicaSupervisor())
            h = router.start("p", GenOptions())
            victim = router._handle_map[h][0]
            router.replicas[victim].partition_link(halfopen=True)
            out = _settle(router, [h])
            assert out[h].text == "echo: p" and out[h].error is None
            assert router.supervisor.relinks == [victim]
            assert router.supervisor.restarts == []
            assert router.replicas[victim].backend.incarnation == 0
        finally:
            _close_all(router)

    def test_sigkill_on_socket_fleet_still_respawns(self):
        """The other half of the decision matrix: poll() non-None is
        PROCESS death even on a socket transport — watchdog hard
        evidence of kind "proc", supervisor respawn at incarnation+1,
        never a relink."""
        router = ClusterRouter(build_proc_replicas(
            2, kind="oracle", transport="socket"))
        try:
            router.attach_health(_watchdog(), ReplicaSupervisor())
            old_pid = router.replicas[0].backend.pid
            router.replicas[0].kill_process()
            assert router.replicas[0].evidence_kind() == "proc"
            for _ in range(6):
                if router.replicas[0].healthy():
                    break
                router.pump()
            fresh = router.replicas[0].backend
            assert fresh.pid != old_pid
            assert fresh.incarnation == 1
            assert router.health.hard_detections == [0]
            assert router.health.hard_kinds == ["proc"]
            assert router.supervisor.restarts == [0]
            assert router.supervisor.relinks == []
        finally:
            _close_all(router)

    def test_relink_budget_exhaustion_becomes_link_death(self):
        """A worker whose listener closed after its first adoption:
        every relink dial dies at connect(), the budget converts the
        outage into hard evidence of kind "link", and the watchdog/
        supervisor respawn path takes the replica (fresh incarnation,
        fresh listener)."""
        router = ClusterRouter(build_proc_replicas(
            2, kind="oracle", transport="socket", chaos_max_accepts=1,
            relink_budget=2))
        try:
            router.attach_health(_watchdog(), ReplicaSupervisor())
            victim = 0
            b = router.replicas[victim].backend
            router.replicas[victim].partition_link()
            assert b.pump() == {}                 # records link evidence
            assert b.link_liveness() is not None
            for _ in range(12):
                if router.replicas[victim].healthy():
                    break
                router.pump()
            fresh = router.replicas[victim].backend
            assert fresh is not b and fresh.incarnation == 1
            assert "relink budget exhausted" in (b.proc_liveness() or "")
            assert router.health.hard_detections == [victim]
            assert router.health.hard_kinds == ["link"]
            assert router.supervisor.restarts == [victim]
            assert router.supervisor.relinks == []
        finally:
            _close_all(router)

    def test_stale_nonce_refused_newer_nonce_drops_old_link(self):
        """Nonce fencing, both halves: a dial at the serving nonce is
        refused on ITS OWN connection (the serving link untouched); a
        strictly-newer dial is adopted and the old link is dropped the
        instant of adoption — at most one live link per worker, and the
        superseded parent recovers by relinking above the hijacker."""
        (rep,) = build_proc_replicas(1, kind="oracle", transport="socket")
        try:
            b = rep.backend
            assert b._nonce == 1                  # the spawn-time link
            # stale dial (nonce == serving nonce): refused with a typed
            # error frame on the NEW connection
            sock = socket.create_connection(("127.0.0.1", b._port),
                                            timeout=5.0)
            sock.settimeout(None)
            probe = SocketTransport(sock)
            probe.send({"op": "hello", "inc": 0, "nonce": 1})
            refusal = probe.recv(timeout_s=5.0)
            assert refusal["err"]["type"] == "StaleNonce"
            probe.close()
            # the serving link never noticed
            assert b._rpc("ping")["ok"] is True
            # newer dial: adopted; the worker drops the old link
            hijack, ready = connect_transport("127.0.0.1", b._port,
                                              incarnation=0, nonce=2)
            assert ready["nonce"] == 2
            # clean FIN vs RST depends on whether the worker's close
            # raced our send — _rpc's contract is WireError OR OSError,
            # link evidence recorded either way
            with pytest.raises((WireError, OSError)):
                b._rpc("ping")                    # old link is dead
            assert b.link_liveness() is not None
            assert b.proc_liveness() is None      # process fine
            hijack.close()
            # relink climbs above the hijacker's nonce (attempt at 2 is
            # refused as stale, attempt at 3 adopts) within the budget
            assert rep.relink() is False
            assert rep.relink() is True
            assert b.link_stats() == {"nonce": 3, "alive": 1,
                                      "relinks": 1}
            assert b._rpc("ping")["ok"] is True
        finally:
            rep.close()

    def test_duplicate_and_stale_replies_discarded_not_desync(self):
        """netem 'duplicate' riding the REAL parent<->worker link: the
        second delivery of an already-consumed id is discarded by the
        reply loop (counted, never WireCorrupt), and the next RPC still
        pairs with its own reply."""
        (rep,) = build_proc_replicas(1, kind="oracle", transport="socket")
        try:
            b = rep.backend
            b._transport = NetemTransport(
                b._transport,
                FaultPlan([Fault(inject.SITE_NET, 0, "duplicate")]))
            with METRICS.scoped():
                assert b._rpc("ping")["ok"] is True   # reply duplicated
                assert b._rpc("ping")["ok"] is True   # dup discarded
                assert METRICS.count(
                    "cluster.net_dup_replies_discarded") == 1
        finally:
            rep.close()

    def test_connect_mode_worker_dials_listening_parent(self):
        """The cross-host inversion: the WORKER dials us.  The parent
        still initiates the hello/nonce on the accepted connection, so
        fencing is direction-agnostic; stdin EOF still ends the worker.
        """
        import json as _json

        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        port = listener.getsockname()[1]
        spec = _json.dumps({"kind": "oracle", "incarnation": 0,
                            "replica_id": 0}, sort_keys=True)
        proc = subprocess.Popen(
            [sys.executable, "-m", "k8s_llm_rca_tpu.cluster.proc",
             "--connect", f"127.0.0.1:{port}", spec],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, env=worker_env())
        transport = None
        try:
            listener.settimeout(300.0)            # worker imports first
            conn, _ = listener.accept()
            conn.settimeout(None)
            transport, ready = client_handshake(conn, incarnation=0,
                                                nonce=1)
            assert ready["op"] == "ready" and ready["nonce"] == 1
            transport.send({"op": "ping", "id": 0})
            resp = transport.recv(timeout_s=10.0)
            assert resp["ok"] is True and resp["nonce"] == 1
        finally:
            # leash FIRST: with the conn still up the worker exits 0 on
            # stdin EOF; closing the conn first would send it re-dialing
            proc.stdin.close()
            try:
                rc = proc.wait(timeout=10.0)
            finally:
                if transport is not None:
                    transport.close()
                listener.close()
                proc.stdout.close()
        assert rc == 0

    def test_prometheus_exports_link_gauge_both_ways(self):
        from k8s_llm_rca_tpu.obs.export import prometheus_text

        router = ClusterRouter(build_proc_replicas(
            2, kind="oracle", transport="socket"))
        try:
            router.replicas[1].partition_link()
            router.replicas[1].backend.pump()     # record the evidence
            text = prometheus_text(router=router)
            assert ('cluster_link_alive{replica="0",nonce="1"} 1'
                    in text)
            assert ('cluster_link_alive{replica="1",nonce="1"} 0'
                    in text)
            # link down but the process row still says alive: the
            # link-death-not-process-death signature on one scrape
            pid1 = router.replicas[1].backend.pid
            assert (f'cluster_proc_alive{{replica="1",pid="{pid1}",'
                    f'incarnation="0"}} 1') in text
        finally:
            _close_all(router)

    def test_net_trace_sites_are_registered(self):
        from k8s_llm_rca_tpu.obs.trace import SITES

        assert "cluster.net.partition" in SITES
        assert "cluster.net.relink" in SITES


# ---------------------------------------------------------------------------
# the acceptance bar: 100-incident partition-and-heal soak, byte-identical
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestPartitionAndHealSoak:
    def test_100_incident_partition_and_heal_byte_identical(self):
        """Real loopback sockets severed by a seeded NetKiller, zero
        manual intervention: every partition/halfopen heals by RELINK
        (same incarnation, fresh session nonce) with in-flight runs
        replayed through the journal boundary — and the report is
        byte-identical to the unpartitioned IN-PROCESS cluster-oracle
        run, twice over (the network is a deployment detail, not an
        outcome)."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        base = run_chaos_soak(seed=11, n_incidents=100,
                              backend="cluster-oracle",
                              cluster_replicas=4)
        assert base["completed"] == 100
        assert base["failed"] == 0

        k1 = _net_killer()
        healed = run_chaos_soak(seed=11, n_incidents=100,
                                backend="net-cluster",
                                cluster_replicas=4, killer=k1,
                                selfheal=True)
        assert k1.kills                       # partitions actually landed
        assert report_bytes(healed) == report_bytes(base)
        router = k1.router
        # every heal was a relink: same incarnations throughout, no
        # death verdicts, no respawns, no failovers — and no split-brain
        # (each replica's link ends alive under its latest nonce)
        assert router.supervisor.relinks == k1.kills
        assert router.supervisor.restarts == []
        assert router.health.hard_detections == []
        assert router.failovers == 0
        assert sorted(router.alive_ids()) == [0, 1, 2, 3]
        for r in router.replicas.values():
            assert r.backend.incarnation == 0
            stats = r.backend.link_stats()
            assert stats["relinks"] == k1.kills.count(r.replica_id)
            assert stats["nonce"] == 1 + stats["relinks"]
        # the soak's reaping context closed every worker on exit
        for r in router.replicas.values():
            assert r.backend._proc.poll() is not None

        k2 = _net_killer()
        again = run_chaos_soak(seed=11, n_incidents=100,
                               backend="net-cluster",
                               cluster_replicas=4, killer=k2,
                               selfheal=True)
        assert k2.kills == k1.kills           # the schedule is seeded
        assert report_bytes(again) == report_bytes(base)

    def test_net_soak_without_chaos_matches_in_process(self):
        """Transport invariance alone: no killer, no selfheal — the
        socket fleet's report must already be byte-identical to the
        in-process cluster-oracle run."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        base = run_chaos_soak(seed=3, n_incidents=6,
                              backend="cluster-oracle")
        net = run_chaos_soak(seed=3, n_incidents=6,
                             backend="net-cluster")
        assert report_bytes(net) == report_bytes(base)
        assert net["backend"] == "cluster-oracle"


# ---------------------------------------------------------------------------
# engine workers: greedy byte-parity over sockets (slow: worker compiles)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestEngineSocketParity:
    def test_socket_engine_cluster_matches_plain_engine(self):
        """Each prompt's greedy text from a 2-worker SOCKET engine
        cluster must be byte-identical to the plain in-process engine's
        on the identical TINY config and seed-0 params — the
        identical-replica invariant, now across a process boundary AND
        a network link."""
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

        router = ClusterRouter(build_proc_replicas(
            2, kind="engine", seed=0, transport="socket"))
        try:
            handles = [router.start(p, GenOptions(max_new_tokens=8))
                       for p in prompts]
            assert {router._handle_map[h][0] for h in handles} == {0, 1}
            out = _settle(router, handles, pumps=256)
            for h, r in zip(handles, ref):
                assert out[h].text == r.text   # byte-identical greedy
                assert out[h].error is None
        finally:
            _close_all(router)
