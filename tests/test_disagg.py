"""Disaggregated prefill/decode tier tests (cluster/disagg.py).

Layers, cheapest first:

- **TierRouter lifecycle** (in-process echo replicas): admission lands
  on the prefill tier, the EXPORT -> ADOPT -> RELEASE handoff moves
  every run to the decode tier, results match the plain cluster's, and
  failover/drain stay inside the dead replica's own tier.
- **frame faults** (the TierRouter's own SITE_HANDOFF plan): drop,
  corrupt, delay and stale-fence each discard the transfer WHOLE and
  retry — never a half-adopted sequence, never an armed-plan poll.
- **loud exclusions**: empty/overlapping tiers, cp/pp meshes, mixed
  seam/scripted fleets, cross-tier drains, pipelined-sweep-over-disagg,
  overlapping killer sites, and killer refusal messages that name the
  victim's replica id, backend kind and transport.
- **kill windows** (real subprocess workers): a HandoffKiller SIGKILLs
  (or partitions) a tier member exactly between EXPORT and ADOPT; the
  run settles with the correct text, the transfer is counted retried,
  and the watchdog attributes the death to the "handoff" evidence kind.
- **chaos soak** (the ISSUE acceptance bar): 100 incidents on a
  socket-transport disagg fleet with mid-handoff SIGKILLs — report
  bytes identical to the unkilled in-process cluster-oracle run, twice.
- **engine seam** (slow): per-run export/adopt round-trip byte-parity
  across the composition matrix (plain / prefix cache / host overlap /
  chunked prefill / spilled-while-snapshotted), and greedy byte-parity
  of 1P+2D (pipe) and 2P+1D (socket) proc engine tiers vs the plain
  engine.
"""

from __future__ import annotations

import types

import pytest

from k8s_llm_rca_tpu.cluster import (
    HealthPolicy, HealthWatchdog, Replica, ReplicaSupervisor, TierRouter,
)
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.faults.plan import FaultPlan, VirtualClock
from k8s_llm_rca_tpu.serve.backend import EchoBackend, GenOptions
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.disagg


def _close_all(router) -> None:
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


def _echo_tiers(tok, n_prefill=1, n_decode=1, delay_pumps=2, **kw):
    mk = lambda rid: Replica(rid, EchoBackend(tok,             # noqa: E731
                                              delay_pumps=delay_pumps),
                             rebuild=lambda: EchoBackend(
                                 tok, delay_pumps=delay_pumps))
    return TierRouter([mk(i) for i in range(n_prefill)],
                      [mk(n_prefill + i) for i in range(n_decode)], **kw)


def _watchdog():
    return HealthWatchdog(HealthPolicy(miss_budget=1,
                                       hung_tick_threshold=2),
                          clock=VirtualClock())


def _handoff_plan(indices):
    """A SITE_HANDOFF plan with an explicit per-attempt schedule."""
    return FaultPlan.from_spec(
        0, {inject.SITE_HANDOFF: {"indices": indices}})


def _handoff_killer(indices, **kw):
    from k8s_llm_rca_tpu.faults.supervisor import HandoffKiller

    return HandoffKiller(_handoff_plan(indices), **kw)


# ---------------------------------------------------------------------------
# TierRouter lifecycle (in-process, scripted)
# ---------------------------------------------------------------------------


class TestTierLifecycle:
    def test_run_admits_on_prefill_and_settles_on_decode(self):
        tok = get_tokenizer()
        router = _echo_tiers(tok)
        h = router.start("node notready", GenOptions())
        assert router._handle_map[h][0] == 0          # admitted on prefill
        assert router._handoff_queue == {h: 0}
        out = _settle(router, [h])
        assert out[h].error is None
        assert out[h].text == "echo: node notready"
        assert router.handoffs == 1
        assert router.handoffs_retried == 0
        assert router._handoff_queue == {}            # RELEASEd

    def test_one_prefill_many_decode_balances_adopters(self):
        tok = get_tokenizer()
        router = _echo_tiers(tok, n_prefill=1, n_decode=3, delay_pumps=4)
        handles = [router.start(f"p{i}", GenOptions()) for i in range(6)]
        out = _settle(router, handles)
        assert all(out[h].error is None for h in handles)
        assert router.handoffs == 6
        stats = router.tier_stats()
        assert stats["prefill_replicas"] == 1
        assert stats["decode_replicas"] == 3
        assert stats["pending_handoffs"] == 0

    def test_many_prefill_one_decode_funnels_through(self):
        tok = get_tokenizer()
        router = _echo_tiers(tok, n_prefill=3, n_decode=1, delay_pumps=4)
        handles = [router.start(f"p{i}", GenOptions(session=f"s{i}"))
                   for i in range(6)]
        # admissions spread over the prefill tier, never the decode tier
        assert {router._handle_map[h][0] for h in handles} <= {0, 1, 2}
        out = _settle(router, handles)
        assert all(out[h].text == f"echo: p{i}"
                   for i, h in enumerate(handles))
        assert router.handoffs == 6

    def test_prefill_death_before_handoff_fails_over_within_tier(self):
        tok = get_tokenizer()
        router = _echo_tiers(tok, n_prefill=2, n_decode=1, delay_pumps=3)
        h = router.start("p", GenOptions())
        src = router._handle_map[h][0]
        router.fail_replica(src)
        # the orphan re-started on the SURVIVING PREFILL replica, not on
        # the decode tier
        rid = router._handle_map[h][0]
        assert router.tier[rid] == "prefill" and rid != src
        out = _settle(router, [h])
        assert out[h].text == "echo: p"
        assert router.handoffs == 1               # still handed off after

    def test_decode_death_after_handoff_fails_over_within_tier(self):
        tok = get_tokenizer()
        router = _echo_tiers(tok, n_prefill=1, n_decode=2,
                             delay_pumps=10 ** 9)
        h = router.start("p", GenOptions())
        router.pump()                             # handoff commits
        rid = router._handle_map[h][0]
        assert router.tier[rid] == "decode"
        router.fail_replica(rid)
        new_rid = router._handle_map[h][0]
        assert router.tier[new_rid] == "decode" and new_rid != rid
        # the settled run never re-enters the handoff queue
        assert h not in router._handoff_queue

    def test_whole_decode_tier_down_keeps_serving_on_prefill(self):
        tok = get_tokenizer()
        router = _echo_tiers(tok, n_prefill=2, n_decode=1, delay_pumps=2)
        router.fail_replica(2)                    # the only decode replica
        h = router.start("p", GenOptions())
        out = _settle(router, [h])
        assert out[h].text == "echo: p"           # degraded but alive
        assert router.handoffs == 0               # nowhere to hand off to

    def test_drain_defaults_to_same_tier_peer(self):
        # live-sequence migration itself is the base router's engine
        # seam (snapshot/adopt); what the TierRouter adds — and what we
        # pin here — is that the DEFAULT target resolves inside the
        # drained replica's own tier, never across
        from unittest import mock

        from k8s_llm_rca_tpu.cluster import ClusterRouter

        tok = get_tokenizer()
        router = _echo_tiers(tok, n_prefill=2, n_decode=1,
                             delay_pumps=10 ** 9)
        h = router.start("p", GenOptions())
        src = router._handle_map[h][0]
        peer = ({0, 1} - {src}).pop()             # the other prefill
        with mock.patch.object(ClusterRouter, "drain_replica",
                               return_value=[h]) as base:
            moved = router.drain_replica(src)
        assert moved == [h]
        base.assert_called_once_with(src, target=peer)

    def test_cancel_clears_the_handoff_queue(self):
        tok = get_tokenizer()
        router = _echo_tiers(tok, delay_pumps=10 ** 9)
        h = router.start("p", GenOptions())
        router.cancel(h)
        assert h not in router._handoff_queue
        router.pump()                             # no stale-queue blowup
        assert router.handoffs == 0


# ---------------------------------------------------------------------------
# frame faults on the handoff plan (own-plan discipline)
# ---------------------------------------------------------------------------


class TestFrameFaults:
    def _run_one(self, indices, pumps=16):
        tok = get_tokenizer()
        plan = _handoff_plan(indices)
        router = _echo_tiers(tok, delay_pumps=4, handoff_plan=plan)
        h = router.start("p", GenOptions())
        out = _settle(router, [h], pumps=pumps)
        assert out[h].error is None
        assert out[h].text == "echo: p"
        return router, plan

    def test_dropped_frame_is_retried_whole(self):
        router, plan = self._run_one({0: "drop"})
        assert router.handoffs_retried == 1
        assert router.handoffs == 1
        assert [f.kind for f in plan.fired] == ["drop"]

    def test_corrupt_frame_is_discarded_whole_and_retried(self):
        router, _ = self._run_one({0: "corrupt"})
        assert router.handoffs_retried == 1
        assert router.handoffs == 1

    def test_stale_fenced_ack_cancels_the_adopted_twin(self):
        router, _ = self._run_one({0: "stale-fence"})
        assert router.handoffs_retried == 1
        assert router.handoffs == 1
        # the fenced twin was cancelled on the adopter: exactly ONE live
        # copy settled, and nothing is still inflight on either backend
        for r in router.replicas.values():
            assert r.backend.queue_depth() == 0

    def test_delay_advances_only_the_handoff_plans_clock(self):
        router, plan = self._run_one({0: "delay"})
        assert router.handoffs_retried == 0       # delay is not a failure
        assert router.handoffs == 1
        assert plan.clock.time() > 0.0            # virtual transfer time

    def test_handoff_polls_never_touch_the_armed_plan(self):
        # an ARMED chaos plan must see zero polls from the handoff path:
        # the transfer polls its own plan and re-admits under
        # inject.readmission, so chaos-soak byte-identity survives tiers
        tok = get_tokenizer()
        armed_plan = FaultPlan.from_spec(0, {})
        router = _echo_tiers(tok, delay_pumps=2,
                             handoff_plan=_handoff_plan({}))
        with inject.armed(armed_plan):
            h = router.start("p", GenOptions())
            _settle(router, [h])
        assert router.handoffs == 1
        assert armed_plan.snapshot()["polls"] == {}


# ---------------------------------------------------------------------------
# loud exclusions
# ---------------------------------------------------------------------------


class _SeamStub:
    """Minimal engine-seam-shaped backend (hasattr export_run) for the
    mixed-fleet exclusion test — never actually driven."""

    def start(self, prompt, opts):                # pragma: no cover
        raise NotImplementedError

    def export_run(self, handle):                 # pragma: no cover
        return None

    def adopt_run(self, frame, opts):             # pragma: no cover
        raise NotImplementedError


class TestExclusions:
    def test_empty_tier_rejected(self):
        tok = get_tokenizer()
        with pytest.raises(ValueError, match="at least one replica"):
            TierRouter([], [Replica(0, EchoBackend(tok))])
        with pytest.raises(ValueError, match="at least one replica"):
            TierRouter([Replica(0, EchoBackend(tok))], [])

    def test_overlapping_tiers_rejected(self):
        tok = get_tokenizer()
        shared = Replica(0, EchoBackend(tok))
        with pytest.raises(ValueError, match="disjoint"):
            TierRouter([shared], [shared, Replica(1, EchoBackend(tok))])

    @pytest.mark.parametrize("axis", ["cp", "pp"])
    def test_cp_pp_meshes_rejected_across_tiers(self, axis):
        # a handoff page record is ONE engine's pool layout: KV sharded
        # over a context/pipeline axis has no host-safe per-page image
        tok = get_tokenizer()
        mesh = types.SimpleNamespace(axis_names=("dp", axis))
        with pytest.raises(ValueError, match=f"mesh axes .*{axis}"):
            TierRouter([Replica(0, EchoBackend(tok), mesh=mesh)],
                       [Replica(1, EchoBackend(tok))])

    def test_mixed_seam_and_scripted_fleet_rejected(self):
        tok = get_tokenizer()
        with pytest.raises(ValueError, match="same handoff seam"):
            TierRouter([Replica(0, _SeamStub())],
                       [Replica(1, EchoBackend(tok))])

    def test_cross_tier_drain_target_rejected(self):
        tok = get_tokenizer()
        router = _echo_tiers(tok, n_prefill=1, n_decode=2)
        with pytest.raises(ValueError, match="own tier"):
            router.drain_replica(0, target=1)     # prefill -> decode

    def test_drain_without_tier_peer_rejected(self):
        tok = get_tokenizer()
        router = _echo_tiers(tok, n_prefill=1, n_decode=2)
        with pytest.raises(ValueError, match="no surviving prefill peer"):
            router.drain_replica(0)

    def test_pipelined_sweep_refuses_disagg(self):
        from k8s_llm_rca_tpu.faults.soak import run_pipelined_sweep

        with pytest.raises(ValueError, match="chaos-soak-only"):
            run_pipelined_sweep(n_incidents=1, backend="disagg-cluster")

    def test_tier_split_requires_disagg_backend(self):
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak

        with pytest.raises(ValueError, match="only applies to backend="):
            run_chaos_soak(n_incidents=1, backend="cluster-oracle",
                           tier_split=(1, 1))

    def test_tier_split_must_sum_to_fleet(self):
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak

        with pytest.raises(ValueError, match="must sum to the fleet"):
            run_chaos_soak(n_incidents=1, backend="disagg-cluster",
                           cluster_replicas=4, tier_split=(1, 2))

    def test_overlapping_killer_sites_rejected_before_any_spawn(self):
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import ProcKiller

        k1 = ProcKiller(FaultPlan.from_spec(0, {}))
        k2 = ProcKiller(FaultPlan.from_spec(1, {}))
        with pytest.raises(ValueError,
                           match=r"disjoint fault sites.*cluster\.proc"):
            run_chaos_soak(n_incidents=1, backend="proc-cluster",
                           killer=[k1, k2])

    def test_handoff_killer_requires_disagg_backend(self):
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak

        k = _handoff_killer({})
        with pytest.raises(ValueError, match="requires backend='disagg"):
            run_chaos_soak(n_incidents=1, backend="proc-cluster",
                           killer=k)

    def test_handoff_killer_unknown_target_rejected(self):
        with pytest.raises(ValueError, match="unknown handoff kill "
                                             "target"):
            _handoff_killer({}, target="bystander")

    def test_killer_refusals_name_kind_and_transport(self):
        # satellite: a refusal must tell the operator WHICH fleet shape
        # the plan mismatched — victim id, backend kind, transport
        from k8s_llm_rca_tpu.cluster import ClusterRouter
        from k8s_llm_rca_tpu.faults.supervisor import ReplicaKiller

        tok = get_tokenizer()
        router = ClusterRouter([Replica(0, EchoBackend(tok)),
                                Replica(1, EchoBackend(tok))])
        k = ReplicaKiller(FaultPlan.from_spec(
            0, {inject.SITE_REPLICA: {"indices": {0: "crash"}}}),
            router=router, mode="sigkill")
        with pytest.raises(ValueError) as exc:
            k.checkpoint()
        msg = str(exc.value)
        assert "replica 0" in msg
        assert "kind='EchoBackend'" in msg
        assert "transport='in-process'" in msg

    def test_partition_refusal_names_kind_and_transport(self):
        from k8s_llm_rca_tpu.cluster import ClusterRouter
        from k8s_llm_rca_tpu.faults.supervisor import ReplicaKiller

        tok = get_tokenizer()
        router = ClusterRouter([Replica(0, EchoBackend(tok)),
                                Replica(1, EchoBackend(tok))])
        k = ReplicaKiller(FaultPlan.from_spec(
            0, {inject.SITE_REPLICA: {"indices": {0: "partition"}}}),
            router=router)
        with pytest.raises(ValueError) as exc:
            k.checkpoint()
        msg = str(exc.value)
        assert "replica 0" in msg and "needs a socket-transport" in msg
        assert "kind='EchoBackend'" in msg
        assert "transport='in-process'" in msg


# ---------------------------------------------------------------------------
# kill windows (real subprocess workers, scripted oracles)
# ---------------------------------------------------------------------------


def _proc_tiers(n_prefill=2, n_decode=2, transport="pipe", **kw):
    # echo workers with a pump delay: an instantly-settling oracle would
    # finish on the prefill tier right after a failover re-start, before
    # the retried transfer gets a second attempt — the delay keeps the
    # run alive long enough for the retry to COMMIT, which is the path
    # these tests pin
    from k8s_llm_rca_tpu.cluster.proc import build_proc_replicas

    reps = build_proc_replicas(n_prefill + n_decode, kind="echo",
                               echo_delay_pumps=4, transport=transport)
    return TierRouter(reps[:n_prefill], reps[n_prefill:], **kw)


class TestKillWindows:
    def test_prefill_sigkill_between_export_and_adopt(self):
        """The exporter dies with the frame in flight: the pinned source
        copy rides ordinary failover back onto the surviving prefill
        replica, the transfer retries whole, and the death is attributed
        to the 'handoff' evidence kind."""
        killer = _handoff_killer({0: "crash"}, target="prefill")
        router = _proc_tiers(handoff_killer=killer)
        try:
            router.attach_health(_watchdog(), ReplicaSupervisor())
            h = router.start("node notready", GenOptions())
            victim = router._handle_map[h][0]
            out = _settle(router, [h], pumps=16)
            assert out[h].error is None
            assert out[h].text == "echo: node notready"
            assert killer.kills == [victim]
            assert router.handoffs_retried >= 1   # the killed attempt
            assert router.handoffs == 1           # the retry committed
            assert router._handoff_queue == {}
            assert "handoff" in router.health.hard_kinds
            # the fleet healed back to full strength
            for _ in range(8):
                if all(r.healthy() for r in router.replicas.values()):
                    break
                router.pump()
            assert sorted(router.alive_ids()) == [0, 1, 2, 3]
        finally:
            _close_all(router)

    def test_decode_sigkill_between_export_and_adopt(self):
        """The adopter dies before ADOPT: nothing was registered on the
        decode side, the source stays pinned, and the retry lands on the
        surviving decode replica."""
        killer = _handoff_killer({0: "crash"}, target="decode")
        router = _proc_tiers(handoff_killer=killer)
        try:
            router.attach_health(_watchdog(), ReplicaSupervisor())
            h = router.start("node notready", GenOptions())
            out = _settle(router, [h], pumps=16)
            assert out[h].error is None
            assert out[h].text == "echo: node notready"
            assert len(killer.kills) == 1
            assert router.tier[killer.kills[0]] == "decode"
            assert router.handoffs_retried >= 1
            assert router.handoffs == 1
            assert "handoff" in router.health.hard_kinds
        finally:
            _close_all(router)

    def test_mid_handoff_partition_heals_by_relink(self):
        """A partitioned (not killed) tier member mid-window: the link
        relinks under the SAME incarnation and the transfer retries —
        no process death, no restart."""
        killer = _handoff_killer({0: "partition"}, target="decode")
        router = _proc_tiers(n_prefill=1, n_decode=1, transport="socket",
                             handoff_killer=killer)
        try:
            router.attach_health(_watchdog(), ReplicaSupervisor())
            h = router.start("node notready", GenOptions())
            out = _settle(router, [h], pumps=16)
            assert out[h].error is None
            assert out[h].text == "echo: node notready"
            assert killer.kills == [1]
            assert router.handoffs == 1
            # the severed link heals INSIDE the ADOPT rpc: the transport
            # relinks under the same incarnation and replays, so the
            # router never even has to discard the attempt
            assert router.handoffs_retried == 0
            backend = router.replicas[1].backend
            assert backend.incarnation == 0       # same process throughout
            assert backend.relinks >= 1
            assert router.health.hard_kinds == [] # evidence, no death
        finally:
            _close_all(router)


# ---------------------------------------------------------------------------
# the acceptance bar: 100-incident mid-handoff-kill soak, byte-identical
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestDisaggChaosSoak:
    def _handoff_rate_killer(self, seed=13, rate=0.03):
        from k8s_llm_rca_tpu.faults.supervisor import HandoffKiller

        return HandoffKiller(FaultPlan.from_spec(
            seed, {inject.SITE_HANDOFF: {"rate": rate, "horizon": 400,
                                         "kinds": ("crash",)}}),
            target="alternate")

    # The acceptance bar is the 100-incident case (three soaks, two of them
    # against real socket workers under SIGKILL: 180 s alone, so ``slow`` as
    # the fleet's other acceptance-size soaks are).  Its tier-1 twin is the
    # smallest sweep and seeded rate at which the plan's kills still land on
    # both tiers: seed 13 at 0.15 faults the 7th window (the exporter dies)
    # and the 10th and 12th (the adopter), and 3 incidents open 13; 2 open
    # 8, and at 0.12 the first fault is the 17th.
    @pytest.mark.parametrize("n_incidents,rate", [
        (3, 0.15), pytest.param(100, 0.03, marks=pytest.mark.slow)])
    def test_mid_handoff_kill_soak_byte_identical(self, n_incidents, rate):
        """Mid-handoff SIGKILLs against real socket workers, on both
        sides of the transfer: every partial handoff resolves
        deterministically, every retried transfer is counted, zero torn
        sequences — and the report is byte-identical to the unkilled
        IN-PROCESS cluster-oracle run, twice over (tiers, transports and
        murder are deployment details, not outcomes)."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        base = run_chaos_soak(seed=13, n_incidents=n_incidents,
                              backend="cluster-oracle",
                              cluster_replicas=4)
        assert base["completed"] == n_incidents
        assert base["failed"] == 0

        k1 = self._handoff_rate_killer(rate=rate)
        healed = run_chaos_soak(seed=13, n_incidents=n_incidents,
                                backend="disagg-cluster",
                                cluster_replicas=4, killer=k1,
                                selfheal=True)
        assert k1.kills                       # mid-window kills landed
        assert report_bytes(healed) == report_bytes(base)
        router = k1.router
        # both tiers took kills (target="alternate" + seeded plan)
        assert {router.tier[rid] for rid in k1.kills} == \
            {"prefill", "decode"}
        # every discarded transfer attempt was counted, then committed:
        # nothing is left half-adopted or parked in the queue
        assert router.handoffs_retried >= len(k1.kills)
        assert router.handoffs > 0
        assert router._handoff_queue == {}
        # every mid-window death was detected on hard OS evidence and
        # attributed to the handoff window
        assert router.health.hard_kinds.count("handoff") == len(k1.kills)
        assert router.supervisor.restarts == k1.kills
        assert sorted(router.alive_ids()) == [0, 1, 2, 3]
        # the soak's reaping context closed every worker on exit
        for r in router.replicas.values():
            assert r.backend._proc.poll() is not None

        k2 = self._handoff_rate_killer(rate=rate)
        again = run_chaos_soak(seed=13, n_incidents=n_incidents,
                               backend="disagg-cluster",
                               cluster_replicas=4, killer=k2,
                               selfheal=True)
        assert k2.kills == k1.kills           # the kill schedule is seeded
        assert k2.router.handoffs_retried == router.handoffs_retried
        assert report_bytes(again) == report_bytes(base)

    def test_mixed_fault_soak_with_disjoint_killers(self):
        """ProcKiller + NetKiller + HandoffKiller side by side on one
        disagg fleet (disjoint sites): boundary SIGKILLs, boundary
        partitions and mid-handoff kills compose, and the report still
        matches the unkilled in-process run byte for byte."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import NetKiller, ProcKiller

        base = run_chaos_soak(seed=17, n_incidents=30,
                              backend="cluster-oracle",
                              cluster_replicas=4)
        pk = ProcKiller(FaultPlan.from_spec(
            5, {inject.SITE_PROC: {"rate": 0.05, "horizon": 30,
                                   "kinds": ("crash",)}}))
        nk = NetKiller(FaultPlan.from_spec(
            9, {inject.SITE_NET: {"rate": 0.05, "horizon": 30,
                                  "kinds": ("partition",)}}))
        hk = self._handoff_rate_killer(seed=19)
        mixed = run_chaos_soak(seed=17, n_incidents=30,
                               backend="disagg-cluster",
                               cluster_replicas=4,
                               killer=[pk, nk, hk], selfheal=True)
        assert report_bytes(mixed) == report_bytes(base)
        assert pk.kills or nk.kills or hk.kills
        router = hk.router
        assert sorted(router.alive_ids()) == [0, 1, 2, 3]
        kinds = router.health.hard_kinds
        if hk.kills:
            assert "handoff" in kinds
        if pk.kills:
            assert "proc" in kinds

    def test_disagg_soak_without_chaos_matches_in_process(self):
        """Tier invariance alone: no killer, no selfheal — the disagg
        sweep's report (runs admitted on prefill, handed off, settled
        on decode) must already be byte-identical to the in-process
        single-tier cluster-oracle run."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        base = run_chaos_soak(seed=3, n_incidents=6,
                              backend="cluster-oracle",
                              cluster_replicas=3)
        dis = run_chaos_soak(seed=3, n_incidents=6,
                             backend="disagg-cluster",
                             cluster_replicas=3, tier_split=(2, 1))
        assert report_bytes(dis) == report_bytes(base)
        assert dis["backend"] == "cluster-oracle"


# ---------------------------------------------------------------------------
# engine seam: per-run export/adopt round trips (slow: compiles)
# ---------------------------------------------------------------------------


# EngineConfig overrides per matrix leg — each composition must survive
# a mid-decode export/adopt round trip byte-identically
_MATRIX = {
    "plain": {},
    "prefix_cache": {"prefix_cache": True},
    "host_overlap": {"host_overlap": True},
    "chunked_prefill": {"prefill_chunk_budget": 16},
    "spilled": {"max_spilled_pages": 24},
}


def _small_pair(overrides):
    import jax

    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models import llama

    cfg = TINY.replace(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    knobs = dict(max_batch=2, max_seq_len=64,
                 page_size=8, num_pages=24, prefill_buckets=(16, 32),
                 max_new_tokens=8, temperature=0.0, decode_chunk=1,
                 prefix_cache=False)
    knobs.update(overrides)
    ecfg = EngineConfig(**knobs)
    mk = lambda: make_engine(cfg, ecfg, params, tok,    # noqa: E731
                             use_kernel=False)
    return mk(), mk(), tok


@pytest.mark.slow
class TestEngineHandoffMatrix:
    @pytest.mark.parametrize("leg", sorted(_MATRIX))
    def test_export_adopt_round_trip_is_byte_identical(self, leg):
        """Start a run on engine A, export it mid-decode (KV pages and
        all), adopt it on engine B, and the finished text must match the
        uninterrupted single-engine run byte for byte — for every
        composition in the matrix."""
        from k8s_llm_rca_tpu.serve.backend import EngineBackend

        eng_a, eng_b, tok = _small_pair(_MATRIX[leg])
        prompt = "node notready on node-3"
        opts = GenOptions(max_new_tokens=8)
        # uninterrupted reference on engine A (also warms the prefix
        # cache for the prefix_cache leg, so the handoff run exports a
        # prefix-hit admission)
        backend_a = EngineBackend(eng_a)
        ref_h = backend_a.start(prompt, opts)
        ref = {}
        while ref_h not in ref:
            ref.update(backend_a.pump())
        assert ref[ref_h].error is None

        h = backend_a.start(prompt, opts)
        frame = None
        for _ in range(6):
            res = backend_a.pump()
            assert h not in res, "run completed before it could export"
            if leg == "spilled":
                # park the sequence via the spill path FIRST, so the
                # export serves the spilled-while-snapshotted case
                assert eng_a._preempt_victim()
                assert eng_a._spilled
            frame = backend_a.export_run(h)
            if frame is not None:
                break
        assert frame is not None
        assert frame["kv"] is not None            # pages actually moved
        backend_b = EngineBackend(eng_b)
        h2 = backend_b.adopt_run(frame, opts)
        # the KV must be ADOPTED, not silently dropped to a re-prefill
        assert (eng_b._counts or {}).get("engine.handoff_kv_adopted") == 1
        out = {}
        for _ in range(64):
            out.update(backend_b.pump())
            if h2 in out:
                break
        assert out[h2].error is None
        assert out[h2].text == ref[ref_h].text
        # RELEASE: the source frees its pinned copy through the normal
        # retire path and ends allocator-clean
        backend_a.cancel(h)
        while eng_a.has_work:
            eng_a.step()
        eng_a.allocator.check()
        assert not eng_a.has_work

    def test_torn_frames_are_rejected_whole(self):
        """Every torn-frame class raises before ANY engine state moves
        on the adopter: malformed entry, corrupt base64, CRC-failing
        page blob."""
        from k8s_llm_rca_tpu.serve.backend import EngineBackend

        eng_a, eng_b, tok = _small_pair({})
        backend_a = EngineBackend(eng_a)
        backend_b = EngineBackend(eng_b)
        opts = GenOptions(max_new_tokens=8)
        h = backend_a.start("node notready on node-3", opts)
        frame = None
        for _ in range(6):
            res = backend_a.pump()
            assert h not in res
            frame = backend_a.export_run(h)
            if frame is not None:
                break
        assert frame is not None and frame["kv"] is not None

        with pytest.raises(ValueError, match="torn handoff frame"):
            backend_b.adopt_run({"seq": {"nonsense": 1}, "kv": None},
                                opts)
        torn_b64 = dict(frame, kv=dict(frame["kv"]))
        torn_b64["kv"]["b64"] = "!!!" + torn_b64["kv"]["b64"][3:]
        with pytest.raises(ValueError, match="torn handoff frame"):
            backend_b.adopt_run(torn_b64, opts)
        torn_crc = dict(frame, kv=dict(frame["kv"]))
        b64 = torn_crc["kv"]["b64"]
        torn_crc["kv"]["b64"] = ("B" if b64[0] == "A" else "A") + b64[1:]
        with pytest.raises(ValueError, match="torn handoff frame"):
            backend_b.adopt_run(torn_crc, opts)
        # nothing half-adopted: the adopter is untouched and still clean
        assert not eng_b.has_work
        assert (eng_b._counts or {}).get("engine.handoff_kv_adopted",
                                         0) == 0
        # the source run survives all three rejections and still settles
        out = {}
        for _ in range(64):
            out.update(backend_a.pump())
            if h in out:
                break
        assert out[h].error is None

    def test_export_unknown_run_is_a_loud_error(self):
        eng_a, _eng_b, _tok = _small_pair({})
        with pytest.raises(ValueError, match="not live"):
            eng_a.export_run(10 ** 9)


# ---------------------------------------------------------------------------
# engine tiers over the wire: greedy byte-parity (slow: worker compiles)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestDisaggEngineParity:
    PROMPTS = ["pod pending unschedulable node affinity mismatch",
               "pvc not bound storageclass missing"]

    def _reference(self):
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
        engine = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        return engine.generate(
            [tok.encode(p, add_bos=True) for p in self.PROMPTS],
            max_new_tokens=8)

    @pytest.mark.parametrize("n_prefill,n_decode,transport",
                             [(1, 2, "pipe"), (2, 1, "socket")])
    def test_proc_engine_tiers_match_plain_engine(self, n_prefill,
                                                  n_decode, transport):
        """Greedy byte-parity through a REAL cross-process KV handoff:
        each prompt admits on a prefill engine worker, its pages cross
        the wire as a CRC-framed page record, and the decode worker's
        finished text must equal the plain in-process engine's — for
        1P+2D over pipes AND 2P+1D over sockets."""
        from k8s_llm_rca_tpu.cluster.proc import build_proc_replicas

        ref = self._reference()
        reps = build_proc_replicas(n_prefill + n_decode, kind="engine",
                                   seed=0, transport=transport)
        router = TierRouter(reps[:n_prefill], reps[n_prefill:])
        assert router._kv_seam                    # the REAL seam, not
        try:                                      # the scripted stand-in
            handles = [router.start(p, GenOptions(max_new_tokens=8))
                       for p in self.PROMPTS]
            out = _settle(router, handles, pumps=512)
            for h, r in zip(handles, ref):
                assert out[h].error is None
                assert out[h].text == r.text      # byte-identical greedy
            assert router.handoffs == len(handles)
            assert router.handoffs_retried == 0
        finally:
            _close_all(router)
