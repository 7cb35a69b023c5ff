"""Self-healing cluster layer: health watchdog, replica supervisor, and
the poison-run quarantine contract.

The reference pipeline survives incidents only because a human reruns
it (the operator re-invokes the sweep after an OpenAI failure); PR 6's
cluster made failover *possible* but still human-triggered —
``ClusterRouter.fail_replica`` must be called by someone, and a killed
replica never rejoins, so every chaos event permanently shrinks the
fleet.  This module closes the loop in-process:

- ``HealthPolicy`` / ``HealthWatchdog``: deterministic per-replica
  liveness.  The watchdog never pings a replica (a dead process cannot
  answer); it watches two *passive* signals the serving loop already
  produces — the engine's monotonic tick heartbeat
  (``EngineBase.step`` stamps ``heartbeat``/``heartbeat_t``; scripted
  replicas have no engine and contribute ``None``) and the router's
  pump-completion beat (``ClusterRouter.pump`` stamps
  ``HealthWatchdog.beat`` after each replica's successful pump).  A
  probe that observes NO fresh signal counts one miss; ``miss_budget``
  misses make the replica SUSPECT (the router routes new work around
  it), ``hung_tick_threshold`` misses make it DEAD (the router fails it
  over and — when a supervisor is attached — restarts it).  Misses are
  counted per *probe evaluation*, not per wall second, so the state
  machine is a pure function of the pump sequence and stays
  deterministic under a frozen VirtualClock (the PR 1 chaos-soak
  discipline: byte-identical reports).

- ``ReplicaSupervisor``: restart-and-rejoin.  A dead replica's engine
  is rebuilt on its ORIGINAL submesh from the recipe ``build_replicas``
  recorded (re-sharding the already-initialized params — the
  identical-replica invariant), re-registered with the router, and the
  fleet returns to N.  The supervisor validates at bind time that the
  replica submeshes are disjoint (a rebuild onto an overlapping mesh
  would race the survivors' collectives — loud ValueError, repo
  convention).

- Poison-run quarantine lives on the router (``quarantine_after``):
  a run whose replica dies K times across incarnations is settled
  FAILED with a named error instead of cascading through the fleet.
  The settlement rides the normal pump path, so serve/api.py journals
  it like any failure and recovery replay agrees byte-for-byte.

MTTD (last beat -> DEAD verdict) and MTTR (DEAD verdict -> rejoined)
are measured on the watchdog's injectable clock and surfaced as
``cluster.mttd`` / ``cluster.mttr`` spans plus lists on the objects
(``mttd_s``, ``mttr_s``) for whoever measures them on a wall clock.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.utils.logging import METRICS, get_logger

log = get_logger(__name__)

# watchdog verdicts, in escalation order
ALIVE = "alive"
SUSPECT = "suspect"
DEAD = "dead"


@dataclass(frozen=True)
class HealthPolicy:
    """Knobs of the ALIVE -> SUSPECT -> DEAD classifier.

    ``probe_interval_s``: minimum clock time between probe evaluations
    (0.0 = evaluate on every ``ClusterRouter.pump``, the deterministic
    default chaos soaks rely on — under a frozen VirtualClock a positive
    interval would evaluate exactly once).
    ``miss_budget``: consecutive signal-free probes before SUSPECT.
    ``hung_tick_threshold``: consecutive signal-free probes before DEAD;
    must exceed ``miss_budget`` so every replica passes through SUSPECT
    (and the router routes around it) before the failover fires.
    """

    probe_interval_s: float = 0.0
    miss_budget: int = 2
    hung_tick_threshold: int = 4

    def __post_init__(self):
        if self.probe_interval_s < 0.0:
            raise ValueError(
                f"probe_interval_s must be >= 0, got "
                f"{self.probe_interval_s}")
        if self.miss_budget < 1:
            raise ValueError(
                f"miss_budget must be >= 1 (a replica needs at least one "
                f"missed probe before suspicion), got {self.miss_budget}")
        if self.hung_tick_threshold <= self.miss_budget:
            raise ValueError(
                f"hung_tick_threshold ({self.hung_tick_threshold}) must "
                f"exceed miss_budget ({self.miss_budget}): a replica must "
                f"pass through SUSPECT before it is declared DEAD")


class HealthWatchdog:
    """Deterministic liveness classifier over a router's replicas.

    The router drives it: ``probe`` at the top of every ``pump`` (the
    returned list is the newly-DEAD replicas the router must heal) and
    ``beat`` after each replica's successful backend pump.  The per-
    replica signal is ``(pump beats, engine tick heartbeat)`` — beats
    keep an *idle* healthy replica ALIVE (its engine ticks nothing, but
    its pump completes), while the tick serial catches an engine that
    still answers pumps but never advances a tick.  A wedged replica
    (dead process) produces neither, misses accumulate, and the verdict
    escalates per ``HealthPolicy``.

    ``clock``: injectable time source (VirtualClock in soaks, wall time
    where it is measured) — the same discipline as ``EngineBase._now``.  The clock
    only timestamps MTTD/MTTR; classification depends on probe counts
    alone.
    """

    def __init__(self, policy: Optional[HealthPolicy] = None,
                 clock: Any = None):
        self.policy = policy or HealthPolicy()
        self.clock = clock
        self._states: Dict[int, str] = {}
        self._sig: Dict[int, tuple] = {}        # latest beat signal
        self._seen: Dict[int, tuple] = {}       # signal at last probe
        self._miss: Dict[int, int] = {}
        self._beats: Dict[int, int] = {}
        self._beat_t: Dict[int, float] = {}
        self._detected_t: Dict[int, float] = {}
        self._last_eval: Optional[float] = None
        self.detections: List[int] = []         # rid per DEAD verdict
        self.hard_detections: List[int] = []    # subset with OS evidence
        # evidence kind per hard detection, parallel to hard_detections:
        # "proc" (process death) vs "link" (relink budget exhausted) —
        # a separate list so hard_detections stays a plain rid list
        self.hard_kinds: List[str] = []
        self.mttd_s: List[float] = []           # last beat -> verdict

    def _now(self) -> float:
        if self.clock is not None:
            return self.clock.time()
        if inject._ARMED is not None:
            return inject._ARMED.clock.time()
        return time.time()

    # ------------------------------------------------------------ lifecycle

    def register(self, rid: int) -> None:
        """Start watching ``rid`` (router attach / fresh incarnation)."""
        self._states[rid] = ALIVE
        self._miss[rid] = 0
        self._seen.pop(rid, None)     # next probe re-baselines, no miss
        self._sig.pop(rid, None)
        self._beat_t[rid] = self._now()

    reset = register   # a restarted incarnation re-arms the same way

    def unregister(self, rid: int) -> None:
        """Stop watching ``rid`` (scale-down retirement,
        cluster/autoscale.py ``Autoscaler``): drop every per-replica
        signal so a stale verdict cannot leak into exports, and a later
        ``register`` of the same id starts from a clean baseline."""
        for d in (self._states, self._miss, self._seen, self._sig,
                  self._beats, self._beat_t, self._detected_t):
            d.pop(rid, None)

    # -------------------------------------------------------------- signals

    def beat(self, rid: int, ticks: Optional[int] = None) -> None:
        """One completed pump for ``rid`` (``ticks``: the engine's
        monotonic tick heartbeat, None for scripted replicas)."""
        self._beats[rid] = self._beats.get(rid, 0) + 1
        self._sig[rid] = (self._beats[rid], ticks)
        self._beat_t[rid] = self._now()

    # ------------------------------------------------------------- verdicts

    def state(self, rid: int) -> str:
        return self._states.get(rid, ALIVE)

    def states(self) -> Dict[int, str]:
        return dict(self._states)

    def is_suspect(self, rid: int) -> bool:
        return self._states.get(rid) == SUSPECT

    def detected_at(self, rid: int) -> Optional[float]:
        """Clock time of ``rid``'s latest DEAD verdict (MTTR's t0)."""
        return self._detected_t.get(rid)

    def _declare_dead(self, rid: int, now: float, misses: int,
                      evidence: Optional[str] = None,
                      kind: str = "proc") -> None:
        self._states[rid] = DEAD
        self._detected_t[rid] = now
        self.detections.append(rid)
        if evidence is not None:
            self.hard_detections.append(rid)
            self.hard_kinds.append(kind)
        t0 = self._beat_t.get(rid, now)
        self.mttd_s.append(max(0.0, now - t0))
        METRICS.inc("cluster.deaths_detected")
        obs_trace.event("cluster.health", replica=rid, state=DEAD,
                        misses=misses, evidence=evidence, kind=kind)
        tr = obs_trace._ACTIVE
        if tr is not None:
            tr.add_span("cluster.mttd", t0, now, cat="cluster",
                        args={"replica": rid})
        log.warning("watchdog: replica %d DEAD after %d missed probes%s",
                    rid, misses,
                    f" (hard evidence: {evidence})" if evidence else "")

    def probe(self, router) -> List[int]:
        """One probe evaluation; returns the newly-DEAD replica ids.

        Deterministic: a replica whose signal did not change since the
        last evaluation accrues one miss; a fresh signal clears the miss
        count (and demotes SUSPECT back to ALIVE).  The first evaluation
        after ``register`` only baselines the signal — startup is never
        a miss.

        Hard evidence (cluster/proc.py ``proc_liveness``: pipe EOF,
        ``poll()`` exit code, torn frame, missed protocol heartbeat)
        SHORT-CIRCUITS the miss budget: the OS already rendered the
        verdict, so the replica escalates one state per probe —
        ALIVE -> SUSPECT, SUSPECT -> DEAD — regardless of how fresh its
        last beat looked.  It still passes through SUSPECT (the
        invariant the router's routing-around contract relies on), but
        detection latency is 2 probes, not ``hung_tick_threshold``.
        """
        now = self._now()
        p = self.policy
        if (p.probe_interval_s > 0.0 and self._last_eval is not None
                and now - self._last_eval < p.probe_interval_s):
            return []
        self._last_eval = now
        newly_dead: List[int] = []
        for rid, replica in router.replicas.items():
            if not replica.alive or self._states.get(rid) == DEAD:
                continue   # already failed over / awaiting restart
            liveness = getattr(replica, "proc_liveness", None)
            evidence = liveness() if liveness is not None else None
            if evidence is not None:
                # "link" when the verdict came from relink-budget
                # exhaustion (cluster/proc.py death_kind), "proc" else
                ekind = getattr(replica, "evidence_kind", None)
                kind = ekind() if ekind is not None else "proc"
                self._miss[rid] = self._miss.get(rid, 0) + 1
                if self._states.get(rid) == SUSPECT:
                    self._declare_dead(rid, now, self._miss[rid],
                                       evidence=evidence, kind=kind)
                    newly_dead.append(rid)
                else:
                    self._states[rid] = SUSPECT
                    obs_trace.event("cluster.health", replica=rid,
                                    state=SUSPECT, misses=self._miss[rid],
                                    evidence=evidence)
                    log.warning("watchdog: replica %d SUSPECT on hard "
                                "evidence (%s)", rid, evidence)
                continue
            sig = self._sig.get(rid)
            if rid not in self._seen:
                self._seen[rid] = sig
                continue
            if sig != self._seen[rid]:
                self._seen[rid] = sig
                self._miss[rid] = 0
                if self._states.get(rid) == SUSPECT:
                    self._states[rid] = ALIVE
                    obs_trace.event("cluster.health", replica=rid,
                                    state=ALIVE, misses=0)
                continue
            self._miss[rid] = self._miss.get(rid, 0) + 1
            misses = self._miss[rid]
            if misses >= p.hung_tick_threshold:
                self._declare_dead(rid, now, misses)
                newly_dead.append(rid)
            elif misses >= p.miss_budget and self._states[rid] == ALIVE:
                self._states[rid] = SUSPECT
                obs_trace.event("cluster.health", replica=rid,
                                state=SUSPECT, misses=misses)
                log.warning("watchdog: replica %d SUSPECT after %d missed "
                            "probes (routing around it)", rid, misses)
        return newly_dead


class ReplicaSupervisor:
    """Restart-and-rejoin for DEAD replicas.

    On ``restart(rid)`` the supervisor runs the replica's recorded
    ``rebuild`` recipe (``build_replicas`` closes over the host params,
    partition specs and the replica's ORIGINAL submesh, so the fresh
    incarnation is byte-identical to the first — greedy decode on
    identical weights), re-tags observability, clears the wedge, and
    marks the replica alive so the router's next ``_pick`` sees the
    fleet back at N.

    ``restart=False`` keeps the supervisor as a recorder only: the
    router then treats it as absent — ``fail_replica``'s last-alive
    refusal stays in force (the pre-self-healing fallback).

    ``warmup_prompt``: optional prompt generated for 1 token on the
    fresh engine before rejoin, forcing compilation out of the serving
    path; never use it under an armed FaultPlan (the warmup ticks would
    shift ``SITE_ENGINE_TICK`` poll counters).  Rebuild + warmup wall
    cost lands in ``restart_s``.
    """

    def __init__(self, restart: bool = True,
                 warmup_prompt: Optional[str] = None):
        self.restart_enabled = bool(restart)
        self.warmup_prompt = warmup_prompt
        self.router = None
        self.restarts: List[int] = []           # rid per restart, in order
        # rid per successful RELINK (same incarnation, new nonce) — the
        # router's _replay_relinked records these; a soak asserting
        # "every heal was a relink" checks relinks against the killer's
        # kills and restarts == []
        self.relinks: List[int] = []
        self.incarnations: Dict[int, int] = {}  # rid -> rebuild count
        self.restart_s: List[float] = []        # wall rebuild(+warmup) cost
        self.mttr_s: List[float] = []           # verdict -> rejoined

    def bind(self, router) -> None:
        """Attach to a router (``ClusterRouter.attach_health`` calls
        this).  Validates the engine replicas' submeshes are disjoint —
        restarting onto an overlapping submesh would race the survivors'
        collectives, so it is rejected loudly up front."""
        from k8s_llm_rca_tpu.engine.engine import validate_disjoint_submeshes

        meshes = [r.mesh for r in router.replicas.values()
                  if r.mesh is not None]
        if meshes:
            validate_disjoint_submeshes(meshes)
        self.router = router

    def restart(self, rid: int) -> None:
        """Rebuild ``rid`` on its original submesh and rejoin it."""
        if not self.restart_enabled:
            return
        router = self.router
        if router is None:
            raise ValueError("ReplicaSupervisor.restart before bind(): "
                             "attach via ClusterRouter.attach_health")
        replica = router.replicas[rid]
        if replica.rebuild is None:
            raise ValueError(
                f"replica {rid} has no rebuild recipe: build_replicas "
                f"records one per engine replica; scripted replicas need "
                f"Replica(..., rebuild=...) for restart-and-rejoin")
        t0 = time.perf_counter()
        backend = replica.rebuild()
        engine = getattr(backend, "engine", None)
        if engine is not None:
            engine.obs_replica = rid
            if router.health is not None:
                engine._hb_stamp = True
            if self.warmup_prompt is not None:
                sid = engine.submit(
                    engine.tokenizer.encode(self.warmup_prompt),
                    max_new_tokens=1)
                while engine.has_work:
                    engine.step()
                del sid
        replica.backend = backend
        replica.wedged = False
        replica.alive = True
        self.restart_s.append(time.perf_counter() - t0)
        inc = self.incarnations.get(rid, 0) + 1
        self.incarnations[rid] = inc
        self.restarts.append(rid)
        health = router.health
        if health is not None:
            detected = health.detected_at(rid)
            health.reset(rid)
            now = health._now()
            if detected is not None:
                self.mttr_s.append(max(0.0, now - detected))
                tr = obs_trace._ACTIVE
                if tr is not None:
                    tr.add_span("cluster.mttr", detected, now,
                                cat="cluster",
                                args={"replica": rid, "incarnation": inc})
        METRICS.inc("cluster.replica_restarts")
        obs_trace.event("cluster.restart", replica=rid, incarnation=inc)
        log.warning("supervisor: replica %d rebuilt and rejoined "
                    "(incarnation %d, fleet %d alive)", rid, inc,
                    len(router.alive_ids()))
