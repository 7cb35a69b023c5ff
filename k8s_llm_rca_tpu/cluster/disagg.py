"""Disaggregated prefill/decode tiers with transactional KV handoff.

The RCA pipeline is prefill-heavy (long Cypher-result and state-audit
prompts) and decode-light (short JSON verdicts), so one homogeneous
fleet leaves whichever phase is off-ratio idle (ROADMAP item 1 move
(b)).  ``TierRouter`` splits the fleet:
a run ADMITS on the prefill tier, and once its prompt is computed its
KV moves to a decode replica as the host-safe page records
``utils/pages.py`` already gathers/restores byte-identically.

The handoff is an explicit two-phase commit over the per-run seam
(serve/backend.py ``export_run``/``adopt_run``, spoken over the proc
wire as the ``export_run``/``adopt_run`` ops):

- **EXPORT** — the prefill side freezes the run through the preemption
  path and gathers its pages into one wire frame; the source sequence
  STAYS pinned (pending queue + spill record) — export is idempotent;
- **ADOPT** — the decode side validates the ENTIRE frame before any
  engine state moves, then re-admits the run under a fresh handle; the
  ack rides the proc protocol's incarnation(+nonce) fence, so a stale
  incarnation can never acknowledge;
- **RELEASE** — only after the ack does the prefill side cancel its
  pinned copy (pages freed through the normal retire path).

Every partial-failure mode therefore resolves deterministically:

- prefill death before ADOPT-ack: the pinned source is gone WITH its
  replica; the health watchdog's ordinary failover re-prefills the run
  on a surviving prefill replica (prefix store makes it mostly-HIT),
  and the transfer retries from there;
- decode death after ADOPT: the run is ordinary in-flight work on the
  decode tier; failover re-starts it on another decode replica;
- torn/corrupt/stale-fenced frame: the adopter discards the transfer
  WHOLE (nothing was registered), the source stays pinned, the router
  counts a retried handoff and tries again — never a half-adopted
  sequence.

Fault surface: ``faults.inject.SITE_HANDOFF`` (drop / corrupt / delay /
stale-fence), polled ONCE per transfer attempt from the router's own
``handoff_plan`` — never from the armed chaos plan, so existing poll
counters stay byte-identical.  ``faults.supervisor.HandoffKiller``
opens its kill window exactly between EXPORT and ADOPT.

Scripted tiers (OracleBackend / proc oracle workers) have no KV: the
handoff degrades to a deterministic re-start on the decode side under
``inject.readmission`` (no armed-plan polls), so the seeded chaos soak
(faults/soak.py ``backend="disagg-cluster"``) stays byte-identical to
the single-tier run.

Exclusions (loud ValueError): empty tiers, overlapping tier ids, mixed
seam/scripted tiers, cp/pp meshes on any tier member (a page record is
ONE engine's pool layout — context/pipeline-sharded KV has no host-safe
per-page image), and cross-tier drain targets.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from k8s_llm_rca_tpu.cluster.replica import Replica
from k8s_llm_rca_tpu.cluster.router import ClusterRouter
from k8s_llm_rca_tpu.cluster.wire import WireError
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.runtime.rules import validate_layout
from k8s_llm_rca_tpu.serve.backend import GenOptions
from k8s_llm_rca_tpu.utils.logging import METRICS, get_logger

log = get_logger(__name__)

TIER_PREFILL = "prefill"
TIER_DECODE = "decode"


def _worker_error():
    # WorkerError lives in cluster/proc.py, which imports nothing from
    # here; resolved lazily so scripted-only stacks never pay the import
    from k8s_llm_rca_tpu.cluster.proc import WorkerError

    return WorkerError


class TierRouter(ClusterRouter):
    """ClusterRouter over a prefill tier and a decode tier.  See the
    module docstring for the handoff protocol and failure semantics.

    Admission routes to the prefill tier; failover re-starts stay
    within the dead replica's OWN tier (pre-handoff runs belong to
    prefill, post-handoff runs to decode); a whole-tier outage drops
    the tier filter and keeps serving on the survivors (degraded but
    alive — the base router's keep-serving bias).

    ``handoff_plan``: the router's OWN FaultPlan for SITE_HANDOFF frame
    faults.  ``handoff_killer``: a ``faults.supervisor.HandoffKiller``
    whose ``window()`` is opened between EXPORT and ADOPT of every
    transfer attempt.
    """

    def __init__(self, prefill: Sequence[Replica],
                 decode: Sequence[Replica],
                 max_inflight_per_replica: Optional[int] = None,
                 quarantine_after: int = 2,
                 handoff_plan=None, handoff_killer=None):
        prefill, decode = list(prefill), list(decode)
        if not prefill or not decode:
            raise ValueError(
                f"TierRouter needs at least one replica per tier, got "
                f"{len(prefill)} prefill / {len(decode)} decode")
        p_ids = {r.replica_id for r in prefill}
        d_ids = {r.replica_id for r in decode}
        if p_ids & d_ids:
            raise ValueError(
                f"prefill and decode tiers must be disjoint; replicas "
                f"{sorted(p_ids & d_ids)} appear in both")
        for r in prefill + decode:
            axes = tuple(getattr(getattr(r, "mesh", None),
                                 "axis_names", ()) or ())
            bad = [a for a in axes if a in ("cp", "pp")]
            if bad:
                raise ValueError(
                    f"TierRouter refuses replica {r.replica_id} with "
                    f"mesh axes {axes}: a handoff page record is ONE "
                    f"engine's pool layout, and {bad[0]!r}-sharded KV "
                    f"has no host-safe per-page image to move between "
                    f"tiers — use dp/tp-only replica meshes")
        seam = [hasattr(r.backend, "export_run") for r in prefill + decode]
        if any(seam) and not all(seam):
            mixed = sorted(r.replica_id for r, s in
                           zip(prefill + decode, seam) if not s)
            raise ValueError(
                f"TierRouter needs every tier member on the same handoff "
                f"seam: replicas {mixed} are scripted (no export_run/"
                f"adopt_run) while others are engine-backed — a KV frame "
                f"one side produces, the other cannot adopt")
        self._kv_seam = all(seam)
        # per-tier weight-layout pre-flight: engine replicas stamped with
        # kv_layout/layout metadata (cluster/replica.build_replicas) must
        # be handoff-compatible across tiers and sit on disjoint devices;
        # scripted replicas (no metadata) skip both checks.
        self._kv_ref: Optional[Tuple[int, Dict[str, Any]]] = None
        members = prefill + decode
        for r in members:
            self._check_kv_member(r)
            self._check_layout_member(r, members)
        super().__init__(prefill + decode,
                         max_inflight_per_replica=max_inflight_per_replica,
                         quarantine_after=quarantine_after)
        self.tier: Dict[int, str] = {}
        for r in prefill:
            self.tier[r.replica_id] = TIER_PREFILL
        for r in decode:
            self.tier[r.replica_id] = TIER_DECODE
        self.prefill_ids = sorted(p_ids)
        self.decode_ids = sorted(d_ids)
        self.handoff_plan = handoff_plan
        self.handoff_killer = handoff_killer
        if handoff_killer is not None and handoff_killer.router is None:
            handoff_killer.router = self
        self.handoffs = 0                    # committed (RELEASEd)
        self.handoffs_retried = 0            # attempts discarded whole
        # ghandle -> retry count; every admitted run enters at 0 and
        # leaves at RELEASE (or when it settles/fails over onto decode)
        self._handoff_queue: Dict[int, int] = {}
        # failover tier context: _pick routes new admissions to prefill
        # (None) and failover re-starts to the dead replica's own tier
        self._route_tier: Optional[str] = None

    # -------------------------------------------------------------- routing

    def _pick(self, session: str, admit: bool = True, priority: int = 1,
              among: Optional[List[int]] = None) -> int:
        if among is None:
            among = (self.decode_ids
                     if self._route_tier == TIER_DECODE
                     else self.prefill_ids)
        return super()._pick(session, admit=admit, priority=priority,
                             among=among)

    def start(self, prompt: str, opts: GenOptions) -> int:
        ghandle = super().start(prompt, opts)
        self._handoff_queue[ghandle] = 0
        return ghandle

    def cancel(self, handle: int) -> None:
        self._handoff_queue.pop(handle, None)
        super().cancel(handle)

    # ------------------------------------------------------------- failover

    def fail_replica(self, rid: int) -> List[int]:
        prev = self._route_tier
        self._route_tier = self.tier.get(rid)
        try:
            return super().fail_replica(rid)
        finally:
            self._route_tier = prev

    def _restart_in_place(self, rid: int) -> None:
        prev = self._route_tier
        self._route_tier = self.tier.get(rid)
        try:
            super()._restart_in_place(rid)
        finally:
            self._route_tier = prev

    def drain_replica(self, rid: int,
                      target: Optional[int] = None) -> List[int]:
        tier = self.tier.get(rid)
        peers = [r for r in self.alive_ids()
                 if r != rid and self.tier.get(r) == tier]
        if target is None:
            if not peers:
                raise ValueError(
                    f"refusing to drain replica {rid}: no surviving "
                    f"{tier} peer, and a cross-tier drain would move "
                    f"sequences into the wrong tier (kill it instead — "
                    f"fail_replica keeps tier placement via the "
                    f"failover path)")
            target = min(peers,
                         key=lambda r: (self.replicas[r].queue_depth(),
                                        r))
        elif self.tier.get(target) != tier:
            raise ValueError(
                f"drain target {target} ({self.tier.get(target)} tier) "
                f"must sit in replica {rid}'s own tier ({tier}): a "
                f"cross-tier drain would move sequences into the wrong "
                f"tier")
        prev = self._route_tier
        self._route_tier = tier
        try:
            return super().drain_replica(rid, target=target)
        finally:
            self._route_tier = prev

    # ----------------------------------------------------- fleet membership

    def _check_kv_member(self, replica: Replica) -> None:
        """Cross-tier KV-record compatibility: the FIRST replica carrying
        ``kv_layout`` metadata becomes the fleet reference; every later
        one must match it on kv_dtype / kv_dim / n_layers (frame geometry
        a page record cannot cross).  ``page_size`` MAY differ between
        tiers — the adopting engine re-chunks the record
        deterministically (engine/paged.py ``adopt_run``,
        ``engine.handoff_kv_relayout``).  Replicas without the metadata
        (scripted echo/oracle tiers) skip."""
        kv = getattr(replica, "kv_layout", None)
        if kv is None:
            return
        if self._kv_ref is None:
            self._kv_ref = (replica.replica_id, dict(kv))
            return
        ref_rid, ref = self._kv_ref
        for field in ("kv_dtype", "kv_dim", "n_layers"):
            if kv.get(field) != ref.get(field):
                raise ValueError(
                    f"TierRouter refuses replica {replica.replica_id}: its "
                    f"KV layout {field}={kv.get(field)!r} does not match "
                    f"replica {ref_rid}'s {field}={ref.get(field)!r} — a "
                    f"handoff frame crossing this pair can neither be "
                    f"adopted nor deterministically converted (page_size "
                    f"may differ between tiers; dtype/width/depth may not)")

    @staticmethod
    def _check_layout_member(replica: Replica, others) -> None:
        """Layout pre-flight for a tier member that carries a SpecLayout
        and a real mesh: re-run ``runtime.rules.validate_layout`` with
        every OTHER member's mesh as a peer, so per-tier submeshes that
        overlap on a device are a named ValueError at construction."""
        layout = getattr(replica, "layout", None)
        mesh = getattr(replica, "mesh", None)
        if layout is None or not hasattr(getattr(mesh, "devices", None),
                                         "flat"):
            return
        peers = [m for m in (getattr(o, "mesh", None) for o in others
                             if o is not replica)
                 if hasattr(getattr(m, "devices", None), "flat")]
        validate_layout(layout, mesh, peers=peers)

    def _check_tier_member(self, replica: Replica) -> None:
        """The __init__ member exclusions, applied to a late admission:
        no cp/pp mesh axes, the newcomer must sit on the SAME handoff
        seam as the incumbent fleet, its KV record geometry must be
        adoptable, and its (layout, mesh) must pass pre-flight against
        the incumbents' meshes."""
        axes = tuple(getattr(getattr(replica, "mesh", None),
                             "axis_names", ()) or ())
        bad = [a for a in axes if a in ("cp", "pp")]
        if bad:
            raise ValueError(
                f"TierRouter refuses replica {replica.replica_id} with "
                f"mesh axes {axes}: {bad[0]!r}-sharded KV has no "
                f"host-safe per-page image to move between tiers")
        seam = hasattr(replica.backend, "export_run")
        if seam != self._kv_seam:
            kind = "scripted" if not seam else "engine-backed"
            fleet = "engine-backed" if self._kv_seam else "scripted"
            raise ValueError(
                f"TierRouter refuses replica {replica.replica_id}: it is "
                f"{kind} while the fleet is {fleet} — every tier member "
                f"must sit on the same handoff seam")
        self._check_kv_member(replica)
        self._check_layout_member(replica, list(self.replicas.values()))

    def add_replica(self, replica: Replica,
                    tier: Optional[str] = None) -> None:
        """Tiered admission (the elastic scale-up seam): the newcomer
        must name its tier, pass the same member exclusions as
        ``__init__``, and lands in the sorted tier id lists."""
        if tier not in (TIER_PREFILL, TIER_DECODE):
            raise ValueError(
                f"add_replica on a TierRouter needs tier="
                f"{TIER_PREFILL!r} or {TIER_DECODE!r}, got {tier!r}")
        self._check_tier_member(replica)
        self._admit_replica(replica)
        self.tier[replica.replica_id] = tier
        self._rebuild_tier_ids()

    def remove_replica(self, rid: int) -> Replica:
        """Tiered retirement: refuses to empty a tier (the __init__
        invariant — a TierRouter without a prefill or decode tier
        cannot serve)."""
        tier = self.tier.get(rid)
        if tier is not None:
            peers = [r for r in self.replicas
                     if r != rid and self.tier.get(r) == tier]
            if not peers:
                raise ValueError(
                    f"refusing to remove replica {rid}: it is the last "
                    f"{tier} tier member (an empty tier cannot serve — "
                    f"add or reassign a peer first)")
        replica = super().remove_replica(rid)
        self.tier.pop(rid, None)
        self._rebuild_tier_ids()
        return replica

    def reassign_tier(self, rid: int, tier: str) -> None:
        """Move ``rid`` to the other tier in place (the rebalance seam,
        cluster/autoscale.py): the worker never dies, its warm engine
        state rides along.  Refuses while the replica still owns
        in-flight runs — pre-handoff sequences would silently change
        phase — and when leaving would empty its current tier."""
        if tier not in (TIER_PREFILL, TIER_DECODE):
            raise ValueError(
                f"reassign_tier needs tier={TIER_PREFILL!r} or "
                f"{TIER_DECODE!r}, got {tier!r}")
        cur = self.tier.get(rid)
        if cur is None:
            raise ValueError(
                f"replica {rid} is not in the fleet "
                f"(ids: {sorted(self.replicas)})")
        if cur == tier:
            raise ValueError(
                f"replica {rid} already sits in the {tier} tier")
        orphans = self._orphans(rid)
        if orphans:
            raise ValueError(
                f"refusing to reassign replica {rid} to the {tier} "
                f"tier: it still owns {len(orphans)} in-flight run(s) "
                f"whose phase would silently change — drain it first")
        peers = [r for r in self.replicas
                 if r != rid and self.tier.get(r) == cur]
        if not peers:
            raise ValueError(
                f"refusing to reassign replica {rid}: it is the last "
                f"{cur} tier member (an empty tier cannot serve)")
        self.tier[rid] = tier
        self._rebuild_tier_ids()
        log.info("replica %d reassigned %s -> %s tier", rid, cur, tier)

    def _rebuild_tier_ids(self) -> None:
        self.prefill_ids = sorted(
            r for r, t in self.tier.items() if t == TIER_PREFILL)
        self.decode_ids = sorted(
            r for r, t in self.tier.items() if t == TIER_DECODE)

    # -------------------------------------------------------------- handoff

    @staticmethod
    def _dead_proc(replica: Replica) -> bool:
        liveness = getattr(replica, "proc_liveness", None)
        return liveness is not None and liveness() is not None

    @staticmethod
    def _down_link(replica: Replica) -> bool:
        link = getattr(replica, "link_liveness", None)
        return link is not None and link() is not None

    def _serving(self, rid: int) -> bool:
        r = self.replicas[rid]
        return (r.healthy() and not self._dead_proc(r)
                and not self._down_link(r))

    def pump(self):
        self._advance_handoffs()
        return super().pump()

    def _advance_handoffs(self) -> None:
        """One transfer attempt per queued run per pump.  Runs that
        settled, were cancelled, or already live on the decode tier
        (whole-prefill-tier failover fallback) self-clean here."""
        if not self._handoff_queue:
            return
        for ghandle in sorted(self._handoff_queue):
            loc = self._handle_map.get(ghandle)
            if loc is None:
                del self._handoff_queue[ghandle]       # settled/cancelled
                continue
            src_rid, src_lh = loc
            if self.tier.get(src_rid) == TIER_DECODE:
                del self._handoff_queue[ghandle]       # already there
                continue
            if not self._serving(src_rid):
                continue       # the heal path owns this replica first
            dst = [rid for rid in self.decode_ids if self._serving(rid)]
            if not dst:
                return         # decode tier down: runs settle on prefill
            dst_rid = min(dst, key=lambda r:
                          (self.replicas[r].queue_depth(), r))
            self._attempt_handoff(ghandle, src_rid, src_lh, dst_rid)

    def _attempt_handoff(self, ghandle: int, src_rid: int, src_lh: int,
                         dst_rid: int) -> None:
        src = self.replicas[src_rid]
        dst = self.replicas[dst_rid]
        prompt, opts = self._runs[ghandle]
        wire_errors = (WireError, OSError, _worker_error())
        fault = None
        if self.handoff_plan is not None:
            fault = self.handoff_plan.poll(inject.SITE_HANDOFF)
        if fault is not None and fault.kind == "delay":
            # virtual transfer latency on the handoff plan's OWN clock
            # (never the soak clock — byte-identity)
            self.handoff_plan.clock.sleep(fault.delay_s or 0.05)
            fault = None
        elif fault is not None and fault.kind not in (
                "drop", "corrupt", "stale-fence"):
            log.warning("handoff fault %r ignored: frame kinds are "
                        "drop/corrupt/delay/stale-fence (kill kinds "
                        "belong on a HandoffKiller plan)", fault.kind)
            fault = None
        # ---- EXPORT: freeze on the prefill side, source stays pinned
        # (phase spans feed the critical-path pass, obs/critical_path.py:
        # zero duration under a VirtualClock, real wire time otherwise)
        with obs_trace.span("cluster.handoff.export", cat="handoff",
                            run=ghandle, src=src_rid, dst=dst_rid):
            if self._kv_seam:
                try:
                    frame = src.backend.export_run(src_lh)
                except wire_errors as e:
                    self._retry(ghandle, "export",
                                f"{type(e).__name__}: {e}")
                    return
                if frame is None:
                    return     # not exportable THIS pump — not a retry
            else:
                # scripted tiers carry no KV: a synthetic frame keeps
                # the 2PC (and its fault/kill surface) identical
                frame = {"seq": {"scripted": True, "run": ghandle},
                         "kv": None}
        if fault is not None and fault.kind == "drop":
            self._retry(ghandle, "export", "injected frame drop")
            return
        if fault is not None and fault.kind == "corrupt":
            frame = self._corrupt_frame(frame)
        # ---- the kill window: a HandoffKiller death lands exactly here,
        # between EXPORT and ADOPT, with the frame in flight
        if self.handoff_killer is not None:
            self.handoff_killer.window(self, ghandle, src_rid, dst_rid)
            loc = self._handle_map.get(ghandle)
            if loc != (src_rid, src_lh) or not self._serving(src_rid):
                # source died (or its runs were already failed over)
                # mid-window: the pinned copy is authoritative and rides
                # ordinary failover back onto the prefill tier — this
                # attempt is discarded whole
                self._retry(ghandle, "window",
                            "prefill side died before ADOPT-ack")
                return
            if not self._serving(dst_rid):
                self._retry(ghandle, "window",
                            "decode side died before ADOPT")
                return
        # ---- ADOPT: all-or-nothing on the decode side
        with obs_trace.span("cluster.handoff.adopt", cat="handoff",
                            run=ghandle, src=src_rid, dst=dst_rid):
            if self._kv_seam:
                try:
                    new_lh = dst.backend.adopt_run(frame, opts)
                except wire_errors as e:
                    # the ack never arrived; the adopter MAY hold a
                    # twin, but the incarnation(+nonce) fence discards
                    # any late reply and an orphan twin's result is
                    # dropped by the parent mirror (proc.py pump) —
                    # retry from the source
                    self._retry(ghandle, "adopt",
                                f"ack lost ({type(e).__name__}): {e}")
                    return
                except ValueError as e:
                    # torn frame: discarded whole before any engine
                    # state moved on the adopter
                    self._retry(ghandle, "adopt", f"torn frame: {e}")
                    return
            else:
                try:
                    self._scripted_frame_check(frame)
                except ValueError as e:
                    self._retry(ghandle, "adopt", f"torn frame: {e}")
                    return
                # deterministic re-start stands in for ADOPT: a
                # re-admission of an already-admitted run (no
                # armed-plan polls)
                with inject.readmission():
                    new_lh = dst.backend.start(prompt, opts)
        if fault is not None and fault.kind == "stale-fence":
            # the ack lost the fencing race (a newer incarnation/nonce
            # took over mid-transfer): the adopted twin must die, the
            # transfer retries whole
            try:
                dst.backend.cancel(new_lh)
            except (WireError, OSError):
                pass
            self._retry(ghandle, "fence", "stale-fenced ADOPT-ack "
                        "discarded; adopted twin cancelled")
            return
        # ---- RELEASE: the adopter acked — free the pinned source copy
        with obs_trace.span("cluster.handoff.release", cat="handoff",
                            run=ghandle, src=src_rid, dst=dst_rid):
            self._local.pop((src_rid, src_lh), None)
            try:
                src.backend.cancel(src_lh)
            except (WireError, OSError):
                pass           # dying source: its state is gone anyway
            self._handle_map[ghandle] = (dst_rid, new_lh)
            self._local[(dst_rid, new_lh)] = ghandle
            retries = self._handoff_queue.pop(ghandle, 0)
            self.handoffs += 1
            METRICS.inc("cluster.handoffs")
            obs_trace.event("cluster.handoff", run=ghandle, src=src_rid,
                            dst=dst_rid, retries=retries,
                            kv=bool(frame.get("kv")))

    def _retry(self, ghandle: int, stage: str, why: str) -> None:
        """Record one discarded transfer attempt; the run stays whole
        wherever it lives and the queue retries next pump."""
        self._handoff_queue[ghandle] = (
            self._handoff_queue.get(ghandle, 0) + 1)
        self.handoffs_retried += 1
        METRICS.inc("cluster.handoff_retries")
        obs_trace.event("cluster.handoff", run=ghandle, stage=stage,
                        retried=True, reason=why)
        log.warning("handoff of run %d discarded whole at %s: %s "
                    "(attempt %d)", ghandle, stage, why,
                    self._handoff_queue[ghandle])

    def _corrupt_frame(self, frame: Dict[str, Any]) -> Dict[str, Any]:
        """Deterministically tear a frame in flight: the adopter must
        reject it whole (CRC for kv frames, entry validation for
        entry-only and scripted frames)."""
        frame = dict(frame)
        if not self._kv_seam:
            frame["torn"] = True
            return frame
        kv = frame.get("kv")
        if kv:
            kv = dict(kv)
            b64 = kv["b64"]
            # flip the first base64 symbol: still valid base64, but the
            # decoded bytes fail the frame CRC deterministically
            kv["b64"] = ("B" if b64[:1] == "A" else "A") + b64[1:]
            frame["kv"] = kv
        else:
            frame["seq"] = {"torn": True}
        return frame

    @staticmethod
    def _scripted_frame_check(frame: Dict[str, Any]) -> None:
        entry = frame.get("seq")
        if (frame.get("torn") or not isinstance(entry, dict)
                or not entry.get("scripted")):
            raise ValueError("torn handoff frame: malformed scripted "
                             "sequence entry")

    # ------------------------------------------------------------ reporting

    def tier_stats(self) -> Dict[str, Any]:
        """Handoff counters for bench/obs (measured, never derived)."""
        return {"prefill_replicas": len(self.prefill_ids),
                "decode_replicas": len(self.decode_ids),
                "handoffs": self.handoffs,
                "handoffs_retried": self.handoffs_retried,
                "pending_handoffs": len(self._handoff_queue)}
