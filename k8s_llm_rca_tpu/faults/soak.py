"""Chaos soak driver: the multi-incident RCA sweep under a seeded
FaultPlan, reported deterministically.

``run_chaos_soak(seed=...)`` builds a fresh stack — engine (or oracle)
backend behind the assistants service, resilient graph executors, the
RCA pipeline with the degradation ladder armed — then drives every
incident with the fault plan armed and returns a report whose bytes are a
pure function of ``(seed, spec, config)``:

- the FaultPlan is sampled once from the seed (plan.from_spec);
- decode is greedy on a fresh engine with a fixed PRNG seed;
- retry backoff runs on the plan's VirtualClock (no real sleeps, no
  wall-clock dependence);
- the report carries only deterministic fields (statuses, degradation
  annotations, attempt counts, fault/retry counters) — wall-clock costs
  and windowed token usage are intentionally excluded.

Two calls with the same seed therefore produce byte-identical
``json.dumps(report, sort_keys=True)`` — the chaos soak test's acceptance
bar — while every incident completes either fully resolved or explicitly
degraded-and-annotated (the ladder's bottom rungs are infallible).
"""

from __future__ import annotations

import contextlib
import json
from typing import Any, Dict, List, Optional, Tuple

from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.faults.plan import FaultPlan, VirtualClock
from k8s_llm_rca_tpu.faults.policy import (
    ResiliencePolicy, ResilientExecutor, RetryPolicy,
)


def default_plan_spec() -> Dict[str, Dict[str, Any]]:
    """The standard chaos mix: Neo4j-shaped graph faults, backend run
    faults (incl. stalls the serve deadline must reap), and engine tick
    faults (preemption waves, allocator exhaustion, host stalls)."""
    return {
        inject.SITE_GRAPH: {
            "rate": 0.10, "horizon": 160, "delay_s": 0.01,
            "kinds": ("error", "timeout", "empty", "slow", "poison"),
        },
        inject.SITE_BACKEND: {
            "rate": 0.15, "horizon": 48,
            "kinds": ("error", "stall", "budget"),
        },
        inject.SITE_ENGINE_TICK: {
            "rate": 0.02, "horizon": 400, "delay_s": 0.01, "wave": 1,
            "kinds": ("preempt", "oom", "stall"),
        },
    }


def _build_engine_service(run_timeout_s: float, clock, journal=None,
                          engine_overrides=None):
    import jax

    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.serve.api import AssistantService
    from k8s_llm_rca_tpu.serve.backend import EngineBackend
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    # sized for the tier-1 budget: ONE prefill bucket (one compile shape),
    # no prefix cache (prefix-hit admission has its own compile shapes and
    # its own tests), a cache just big enough for the stage prompts.
    # ``engine_overrides``: EngineConfig field overrides for the pipelined
    # sweep's composition matrix (prefix_cache, host_overlap, chunked
    # prefill, speculative decode ... — tests/test_sweep_sched.py).
    cfg = TINY.replace(max_seq_len=2560)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=4, max_seq_len=2560,
                        prefill_buckets=(2560,),
                        max_new_tokens=96, temperature=0.0,
                        page_size=64, num_pages=168,
                        prefix_cache=False, decode_chunk=16)
    if engine_overrides:
        import dataclasses as _dc

        ecfg = _dc.replace(ecfg, **engine_overrides)
    engine = make_engine(cfg, ecfg, params, tok, use_kernel=False)
    # deadlines on the soak's virtual clock, ARMED OR NOT: without this
    # the engine falls back to the armed plan's clock (same object) or —
    # in plan-free pipelined sweeps — to WALL time, where the first
    # compile alone blows the 1.5 s run deadline
    engine.clock = clock
    # the factory hands the SAME engine to a restarted backend: it stands
    # in for the restarted worker's recompiled engine (identical weights,
    # identical compile) without paying a per-crash recompile
    factory = lambda: EngineBackend(engine)        # noqa: E731
    return AssistantService(factory(), run_timeout_s=run_timeout_s,
                            clock=clock, journal=journal), engine, factory


def _build_oracle_service(run_timeout_s: float, clock, journal=None):
    from k8s_llm_rca_tpu.rca.oracle import OracleBackend
    from k8s_llm_rca_tpu.serve.api import AssistantService
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    factory = lambda: OracleBackend(get_tokenizer())   # noqa: E731
    return AssistantService(factory(), run_timeout_s=run_timeout_s,
                            clock=clock, journal=journal), None, factory


def _build_cluster_service(run_timeout_s: float, clock, journal=None,
                           n_replicas: int = 2, oracle: bool = False,
                           selfheal: bool = False, health_policy=None,
                           proc: bool = False, transport: str = "pipe",
                           tier_split: Optional[Tuple[int, int]] = None,
                           handoff_plan=None,
                           fleet_telemetry: bool = False):
    """N-replica serving behind a ClusterRouter (cluster/).  ``oracle``
    replicas are scripted backends — the cheap mode the 100-incident
    replica-kill soak runs on (tier-1 budget); engine replicas reuse the
    single-engine soak's TINY config, sharded onto disjoint submeshes.

    ``proc``: out-of-process replicas (cluster/proc.py) — each replica's
    scripted-oracle backend runs in its OWN interpreter behind the wire
    protocol, so a killer can deliver REAL SIGKILLs and the watchdog
    detects actual process death.  The workers poll no fault sites
    (exactly like the in-process OracleBackend) and the serving
    semantics are transport-invariant, which is why the proc soak's
    report is byte-identical to the in-process cluster-oracle run (the
    report even says ``cluster-oracle`` — transport is a deployment
    detail, not an outcome).  ``transport`` picks the wire ("pipe" or
    "socket", cluster/net.py): socket workers serve the same framed
    protocol over a loopback TCP link, which a NetKiller can partition
    and the router relink — the report stays byte-identical either way.

    ``selfheal``: arm the self-healing loop (cluster/health.py) — a
    HealthWatchdog on the soak's VirtualClock plus a restart-enabled
    ReplicaSupervisor, so wedged replicas are detected, failed over and
    rejoined in-tree with no external ``fail_replica`` call.

    ``tier_split``: ``(n_prefill, n_decode)`` — split the fleet into
    disaggregated prefill/decode tiers behind a TierRouter
    (cluster/disagg.py); every run admits on the prefill tier and its
    KV (for scripted workers: its placement) moves to a decode replica
    through the transactional EXPORT -> ADOPT -> RELEASE handoff.
    ``handoff_plan``: the TierRouter's own SITE_HANDOFF FaultPlan.

    ``fleet_telemetry``: opt proc workers into the fleet flight
    recorder (cluster/proc.py telemetry shipping) — each worker runs
    its own Tracer and ships spans/ticks back on reply frames.  OFF by
    default and deliberately NOT inferred from an active tracer, so a
    soak's spec (and therefore its worker argv) only changes when the
    caller asks; shipping polls no fault sites either way, which is the
    telemetry-on-vs-off report byte-identity bar
    (tests/test_fleet_obs.py).

    Returns ``(service, engines, factory, router)`` — ``engines`` is the
    per-replica engine list ([] for oracle replicas) so the caller can
    assert EVERY replica ends clean, and ``factory`` returns the SAME
    router (replica engines stand in for restarted workers, exactly like
    the single-engine soak's factory)."""
    from k8s_llm_rca_tpu.cluster import ClusterRouter, Replica
    from k8s_llm_rca_tpu.serve.api import AssistantService

    if proc:
        from k8s_llm_rca_tpu.cluster.proc import build_proc_replicas

        # telemetry-off keeps the spec (and worker argv) byte-identical
        # to the pre-flight-recorder fleet: the flag only exists when on
        replicas = build_proc_replicas(
            n_replicas, kind="oracle", transport=transport,
            **({"trace": True} if fleet_telemetry else {}))
        engines = []
    elif oracle:
        from k8s_llm_rca_tpu.rca.oracle import OracleBackend
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        tok = get_tokenizer()
        replicas = [Replica(i, OracleBackend(tok),
                            rebuild=lambda tok=tok: OracleBackend(tok))
                    for i in range(n_replicas)]
        engines = []
    else:
        from k8s_llm_rca_tpu.cluster import build_replicas
        from k8s_llm_rca_tpu.config import TINY, EngineConfig

        cfg = TINY.replace(max_seq_len=2560)
        replicas = build_replicas(
            cfg,
            EngineConfig(max_batch=4, max_seq_len=2560,
                         prefill_buckets=(2560,),
                         max_new_tokens=96, temperature=0.0,
                         page_size=64, num_pages=168,
                         prefix_cache=False, decode_chunk=16),
            n_replicas, seed=0, use_kernel=False)
        engines = [r.backend.engine for r in replicas]
        for eng in engines:
            # virtual-clock deadlines even without an armed plan (see
            # _build_engine_service)
            eng.clock = clock
    if tier_split is not None:
        from k8s_llm_rca_tpu.cluster import TierRouter

        n_prefill, n_decode = int(tier_split[0]), int(tier_split[1])
        if n_prefill + n_decode != n_replicas:
            raise ValueError(
                f"tier_split {tier_split} must sum to the fleet size "
                f"({n_replicas}): tiers partition the SAME replicas, "
                f"they do not add capacity")
        router = TierRouter(replicas[:n_prefill], replicas[n_prefill:],
                            handoff_plan=handoff_plan)
    else:
        router = ClusterRouter(replicas)
    if selfheal:
        from k8s_llm_rca_tpu.cluster import (
            HealthWatchdog, ReplicaSupervisor,
        )

        router.attach_health(HealthWatchdog(health_policy, clock=clock),
                             ReplicaSupervisor())
    factory = lambda: router                           # noqa: E731
    return (AssistantService(router, run_timeout_s=run_timeout_s,
                             clock=clock, journal=journal),
            engines, factory, router)


@contextlib.contextmanager
def _reaping_workers(router):
    """Close any out-of-process replica workers when the block exits —
    even on a sweep failure, a soak must never leak worker processes.
    ``ProcReplica.close`` runs the drain -> TERM -> KILL ladder and
    touches no replica flags, so the caller's post-soak fleet
    assertions (alive/restart counts) see the healed state."""
    try:
        yield
    finally:
        if router is not None:
            for r in router.replicas.values():
                close = getattr(r, "close", None)
                if close is not None:
                    close()


def _incident_row(message: str, result: Dict[str, Any]) -> Dict[str, Any]:
    """Deterministic report row for one completed incident — the fields
    every sweep report carries (wall-clock cost and windowed token usage
    intentionally excluded, see module docstring)."""
    row: Dict[str, Any] = {"error_message": message}
    degraded = result.get("degraded", [])
    row["status"] = "degraded" if degraded else "resolved"
    row["degraded"] = degraded
    row["locator_attempts"] = result.get("locator_attempts")
    if "flight" in result:    # traced soak: deterministic digest
        row["flight"] = result["flight"]
    row["analyses"] = [
        {"cypher_attempts": a.get("cypher_attempts"),
         "used_fallback": "human_cypher_query" in a,
         "n_statepaths": len(a.get("statepath", []))}
        for a in result.get("analysis", [])]
    return row


def run_chaos_soak(seed: int = 0, n_incidents: int = 3,
                   backend: str = "engine",
                   plan_spec: Optional[Dict[str, Any]] = None,
                   run_timeout_s: float = 1.5,
                   tracer: Optional[Any] = None,
                   durable_dir: Optional[str] = None,
                   supervisor: Optional[Any] = None,
                   cluster_replicas: int = 2,
                   killer: Optional[Any] = None,
                   selfheal: bool = False,
                   concurrency: int = 1,
                   tier_split: Optional[Tuple[int, int]] = None,
                   handoff_plan: Optional[FaultPlan] = None,
                   fleet_telemetry: bool = False,
                   store_fabric: Optional[Any] = None
                   ) -> Dict[str, Any]:
    """Drive ``n_incidents`` of the canned corpus through the resilient
    pipeline under an armed FaultPlan; return the deterministic report.

    ``backend``: "engine" (the real paged TINY engine — tick faults and
    stalls bite) or "oracle" (scripted backend — graph faults only; the
    cheap mode), or their
    multi-replica forms "cluster" / "cluster-oracle" — ``cluster_replicas``
    engines (or scripted oracles) on disjoint submeshes behind a
    ClusterRouter (cluster/router.py).  "proc-cluster" runs the oracle
    replicas out-of-process over stdio pipes (cluster/proc.py);
    "net-cluster" runs them over loopback TCP sockets (cluster/net.py),
    the fleet a NetKiller can partition and the router relinks;
    "disagg-cluster" splits the proc-oracle fleet into disaggregated
    prefill/decode tiers behind a TierRouter (cluster/disagg.py,
    ``tier_split`` — default splits the fleet in half, prefill-heavy) —
    all three report as "cluster-oracle" (byte-identity is the
    acceptance bar; tiers and transports are deployment detail).

    ``killer``: optional faults.supervisor.ReplicaKiller — or a LIST of
    killers with pairwise-disjoint fault sites (e.g. a ProcKiller, a
    NetKiller and a HandoffKiller side by side; two killers on one site
    would double-count its plan per incident, a loud ValueError) —
    cluster modes only, each polled once at every incident boundary on
    its OWN FaultPlan; on a scheduled "crash" one replica dies and the
    router fails its work over to survivors.  A HandoffKiller
    (``backend="disagg-cluster"`` only) is instead bound to the
    TierRouter and fires inside the EXPORT -> ADOPT window of KV
    handoffs, never at boundaries.  Like the supervisor, kill stats
    live on the killer objects, never in the report — the kill-soak
    report must stay byte-identical to the unkilled run's (use a
    plan_spec without SITE_ENGINE_TICK for engine clusters: per-tick
    polls shift with the survivor's extra ticks, which is
    fault-schedule divergence, not nondeterminism).

    ``tracer``: optional obs.Tracer — activated for the whole soak with
    its clock REBOUND to the soak's VirtualClock, so every span/event
    timestamp is virtual and the exported Chrome trace is byte-identical
    run over run (the flight recorder's golden acceptance bar).  The
    report then carries a deterministic ``flight`` summary.

    ``fleet_telemetry`` (proc backends only): opt the out-of-process
    workers into the fleet flight recorder — each worker runs its own
    Tracer and ships spans/ticks back piggybacked on reply frames, so a
    traced soak's merged Chrome trace gains one pid track per worker
    incarnation.  Shipping polls NO fault sites and adds NO report
    fields: ``faults.polls`` and ``report_bytes`` stay byte-identical
    with telemetry on or off (tests/test_fleet_obs.py proves the bar).

    ``durable_dir``: optional directory for the write-ahead run journal
    (serve/journal.py) — every service mutation becomes a durable record.
    The report stays byte-identical with or without it (journaling adds
    no report fields and touches no virtual clock).

    ``supervisor``: optional faults.supervisor.CrashSupervisor (requires
    ``durable_dir``) polled at every incident boundary; on a scheduled
    "crash" fault the serving stack is torn down and rebuilt from the
    journal mid-sweep — the kill/restart chaos scenario.  The supervisor
    runs its OWN FaultPlan, so the armed plan's poll counters (and hence
    the report) match the uninterrupted run exactly; crash/recovery stats
    live on the supervisor object, not in the report.

    ``selfheal`` (cluster modes only): arm the self-healing loop
    (cluster/health.py).  A ``killer`` then *wedges* its victims
    instead of calling ``fail_replica`` — the watchdog detects the
    silence over subsequent pumps, fails the corpse over in-tree and
    the supervisor rejoins a fresh incarnation, so the fleet repeatedly
    returns to full strength (the kill-and-heal soak: report bytes
    still match the unkilled run, and heal stats live on
    ``router.health`` / ``router.supervisor``, never in the report).
    After the sweep the router is pumped a few extra (plan-free) times
    so a wedge landed at the last boundary still heals before the
    engine-clean check.

    ``store_fabric``: optional cluster.store.StoreFabric (build via
    ``build_store_fabric``) — attaches the cross-host prefix-store
    service to the soak.  Exercised exactly once per incident on both
    outcome paths (one put/get round trip through the live server);
    every outcome — hit, miss, dead store — lands ONLY in the fabric's
    own counters, never in the report, so ``report_bytes`` stays
    byte-identical to the store-less run (the cache-fabric acceptance
    bar).  A ``StoreKiller`` in ``killer`` is bound to this fabric and
    SIGKILLs/respawns the real store process at incident boundaries on
    its OWN plan; passing a StoreKiller WITHOUT a fabric, or putting
    SITE_STORE in the armed ``plan_spec`` (it belongs on the store's
    own plan), is refused loudly before any worker spawns.

    ``concurrency``: incidents in flight at once (rca/scheduler.py).  At
    1 (the default) the historical sequential loop runs unchanged.
    Above 1 the sweep is driven by the pipelined SweepScheduler — K slot
    pipelines over the one service — which is only legal without chaos
    machinery: a plan with scheduled faults (fault-to-incident
    attribution is interleaving-dependent), a supervisor/killer
    (boundary polls need a global incident order), or selfheal all raise
    loud ValueErrors.  An EMPTY plan stays armed, so the report's
    ``faults.polls`` counters (per-site sums, interleaving-invariant)
    match the sequential run's and report bytes stay comparable across
    concurrencies.
    """
    from k8s_llm_rca_tpu.config import RCAConfig
    from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
    from k8s_llm_rca_tpu.graph.fixtures import (
        INCIDENTS, build_metagraph, build_stategraph,
    )
    from k8s_llm_rca_tpu.rca import RCAPipeline

    clock = VirtualClock()
    plan = FaultPlan.from_spec(seed, plan_spec or default_plan_spec(),
                               clock=clock)
    if concurrency < 1:
        raise ValueError(f"concurrency must be >= 1, got {concurrency}")
    if concurrency > 1:
        if plan.has_faults:
            raise ValueError(
                "chaos soak with concurrency > 1 is not supported: "
                "scheduled faults are attributed to incidents by poll "
                "order, which is interleaving-dependent — the report "
                "could never match the sequential run.  Run chaos at "
                "concurrency=1, or pass an empty plan_spec (plan-free "
                "pipelined sweeps: run_pipelined_sweep)")
        if supervisor is not None or killer is not None or selfheal:
            raise ValueError(
                "crash/kill/selfheal machinery polls once per incident "
                "BOUNDARY — a pipelined sweep has no global incident "
                "order, so the schedules could never match; concurrency "
                "> 1 requires supervisor=None, killer=None, "
                "selfheal=False")
    policy = ResiliencePolicy(
        retry=RetryPolicy(max_attempts=3, base_delay_s=0.01,
                          max_delay_s=0.1, deadline_s=5.0, seed=seed,
                          clock=clock),
        failure_threshold=4, reset_timeout_s=0.5, reduced_tokens=256)

    journal = None
    if durable_dir is not None:
        import os

        from k8s_llm_rca_tpu.serve.journal import RunJournal

        os.makedirs(durable_dir, exist_ok=True)
        journal = RunJournal(os.path.join(durable_dir, "serve.wal"))
    if supervisor is not None and journal is None:
        raise ValueError("supervisor requires durable_dir: the run "
                         "journal is the only recovery source a crash "
                         "leaves behind")

    if tier_split is not None and backend != "disagg-cluster":
        raise ValueError(
            f"tier_split only applies to backend='disagg-cluster' "
            f"(got backend={backend!r}): only a TierRouter has tiers "
            f"to split the fleet into")
    if handoff_plan is not None and backend != "disagg-cluster":
        raise ValueError(
            f"handoff_plan only applies to backend='disagg-cluster' "
            f"(got backend={backend!r}): SITE_HANDOFF is only polled "
            f"inside a TierRouter's transfer attempts")
    if fleet_telemetry and backend not in ("proc-cluster", "net-cluster",
                                           "disagg-cluster"):
        raise ValueError(
            f"fleet_telemetry only applies to out-of-process backends "
            f"('proc-cluster'/'net-cluster'/'disagg-cluster', got "
            f"backend={backend!r}): in-process replicas already share "
            f"the parent tracer — there is nothing to ship")
    if backend == "disagg-cluster" and tier_split is None:
        # prefill-heavy default: the RCA corpus is long-prompt/short-
        # verdict, so ceil(n/2) exporters feed floor(n/2) adopters
        n_prefill = max(1, (cluster_replicas + 1) // 2)
        tier_split = (n_prefill, cluster_replicas - n_prefill)

    # store-fabric validation BEFORE any worker spawns (same leak
    # discipline as the killer checks below): SITE_STORE belongs on the
    # STORE's own plan — an armed chaos plan polling it would shift the
    # armed plan's poll counters with every store op and the fabric run
    # could never settle byte-identical to the store-less run
    if plan_spec and inject.SITE_STORE in plan_spec:
        raise ValueError(
            f"plan_spec must not schedule {inject.SITE_STORE!r}: store "
            f"faults are polled from the RemoteStore's OWN plan "
            f"(cluster.store.RemoteStore(plan=...)), never from the "
            f"armed chaos plan — build the fabric with its own "
            f"FaultPlan and pass it as store_fabric")
    if store_fabric is not None and concurrency > 1:
        raise ValueError(
            "store_fabric is exercised once per incident BOUNDARY — a "
            "pipelined sweep has no global incident order, so the "
            "fabric's op schedule could never match the sequential "
            "run; concurrency > 1 requires store_fabric=None")

    # killer-list validation BEFORE any worker spawns: a ValueError here
    # must not leak subprocesses (_reaping_workers is not entered yet)
    killers: List[Any] = []
    if killer is not None:
        from k8s_llm_rca_tpu.faults.supervisor import HandoffKiller

        killers = (list(killer) if isinstance(killer, (list, tuple))
                   else [killer])
        sites = [k.site for k in killers]
        dup = sorted({s for s in sites if sites.count(s) > 1})
        if dup:
            raise ValueError(
                f"killers must poll pairwise-disjoint fault sites, but "
                f"{dup} appear on more than one killer: two killers on "
                f"one site would double-count its plan per incident and "
                f"the kill schedule could never match a single-killer "
                f"run")
        from k8s_llm_rca_tpu.faults.supervisor import StoreKiller
        for k in killers:
            if (isinstance(k, HandoffKiller)
                    and backend != "disagg-cluster"):
                raise ValueError(
                    f"HandoffKiller requires backend='disagg-cluster' "
                    f"(got {backend!r}): its kill window only opens "
                    f"between EXPORT and ADOPT of a TierRouter handoff")
            if isinstance(k, StoreKiller):
                if store_fabric is None:
                    raise ValueError(
                        "StoreKiller requires store_fabric: there is no "
                        "remote store process to SIGKILL — build one "
                        "with cluster.store.build_store_fabric and pass "
                        "it as store_fabric")
                k.store = store_fabric

    router = None
    if backend == "engine":
        service, engine, factory = _build_engine_service(
            run_timeout_s, clock, journal)
        engines = [engine]
    elif backend in ("cluster", "cluster-oracle", "proc-cluster",
                     "net-cluster", "disagg-cluster"):
        service, engines, factory, router = _build_cluster_service(
            run_timeout_s, clock, journal,
            n_replicas=cluster_replicas,
            oracle=(backend == "cluster-oracle"),
            proc=(backend in ("proc-cluster", "net-cluster",
                              "disagg-cluster")),
            # disagg workers sit on sockets so the mixed-fault soak can
            # point a NetKiller at a tier member (and a HandoffKiller
            # can partition mid-window) — the report is transport-
            # invariant either way
            transport=("socket" if backend in ("net-cluster",
                                               "disagg-cluster")
                       else "pipe"),
            selfheal=selfheal,
            tier_split=tier_split, handoff_plan=handoff_plan,
            fleet_telemetry=fleet_telemetry)
        engine = None   # "engine_clean" is per-replica below
    elif selfheal:
        raise ValueError("selfheal requires a cluster backend: the "
                         "watchdog/supervisor loop heals replicas, not "
                         "a single engine")
    else:
        service, engine, factory = _build_oracle_service(
            run_timeout_s, clock, journal)
        engines = []
    if killers:
        if router is None:
            raise ValueError("killer requires a cluster backend: replica "
                             "kills need a router to fail over through")
        from k8s_llm_rca_tpu.faults.supervisor import HandoffKiller
        for k in killers:
            k.router = router
            if isinstance(k, HandoffKiller):
                router.handoff_killer = k
    meta = ResilientExecutor(InMemoryGraphExecutor(build_metagraph()),
                             policy, dep="graph.meta")
    state = ResilientExecutor(InMemoryGraphExecutor(build_stategraph()),
                              policy, dep="graph.state")
    # construct (and seed) the pipeline BEFORE arming: the vocabulary
    # bootstrap queries are setup, not chaos surface
    pipeline = RCAPipeline(
        service, meta, state,
        RCAConfig(locator_max_new_tokens=192, cypher_max_new_tokens=96,
                  analyzer_max_new_tokens=96, fresh_threads=True),
        resilience=policy)
    pipelines: List[RCAPipeline] = [pipeline]
    if concurrency > 1:
        # K slot pipelines over the ONE service; slot 0 is the
        # already-seeded pipeline.  Built HERE, before arming, for the
        # same reason as slot 0: __post_init__'s vocabulary bootstrap
        # issues a graph.query, and counting K-1 extra setup polls in
        # ``faults.polls`` would make the report depend on concurrency.
        # Clones share cfg and executors but get their OWN ladder
        # policy (same constants, same shared retry object):
        # ResiliencePolicy.degradations is per-incident state reset by
        # begin_incident, so one shared instance across interleaved
        # machines would let machine A's reset wipe machine B's
        # accumulating annotations
        pipelines += [
            RCAPipeline(service, meta, state, pipeline.cfg,
                        resilience=ResiliencePolicy(
                            retry=policy.retry,
                            failure_threshold=policy.failure_threshold,
                            reset_timeout_s=policy.reset_timeout_s,
                            reduced_tokens=policy.reduced_tokens))
            for _ in range(concurrency - 1)]

    obs_ctx: Any = contextlib.nullcontext()
    if tracer is not None:
        from k8s_llm_rca_tpu.obs import trace as obs_trace

        tracer.clock = clock          # virtual timestamps (see docstring)
        obs_ctx = obs_trace.tracing(tracer)

    incidents: List[Dict[str, Any]] = []
    n_resolved = n_degraded = n_failed = 0
    with inject.armed(plan), obs_ctx, _reaping_workers(
            router if backend in ("proc-cluster", "net-cluster",
                                  "disagg-cluster")
            else None):
        if concurrency > 1:
            from k8s_llm_rca_tpu.rca.scheduler import (
                IncidentFailure, SweepScheduler,
            )

            messages = [INCIDENTS[i % len(INCIDENTS)].message
                        for i in range(n_incidents)]
            for message, result in zip(
                    messages, SweepScheduler(pipelines).run(messages)):
                if isinstance(result, IncidentFailure):
                    incidents.append({"error_message": message,
                                      "status": "failed",
                                      "error": result.error})
                    n_failed += 1
                    continue
                row = _incident_row(message, result)
                if row["status"] == "degraded":
                    n_degraded += 1
                else:
                    n_resolved += 1
                incidents.append(row)
        else:
            for i in range(n_incidents):
                message = INCIDENTS[i % len(INCIDENTS)].message
                try:
                    result = pipeline.analyze_incident(message)
                except Exception as e:  # noqa: BLE001 — must never happen:
                    # the ladder's bottom rungs are infallible; a row here
                    # is a soak FAILURE the test asserts against
                    incidents.append({"error_message": message,
                                      "status": "failed",
                                      "error": f"{type(e).__name__}: {e}"})
                    n_failed += 1
                    if supervisor is not None:
                        # keep supervisor polls at exactly one per incident
                        # (both outcome paths), so its schedule is a pure
                        # function of (plan, n_incidents)
                        service = supervisor.checkpoint(
                            pipeline, service, factory, run_timeout_s,
                            clock)
                    for k in killers:
                        k.checkpoint()
                    if store_fabric is not None:
                        store_fabric.exercise(i)
                    continue
                row = _incident_row(message, result)
                if row["status"] == "degraded":
                    n_degraded += 1
                else:
                    n_resolved += 1
                incidents.append(row)
                if supervisor is not None:
                    # incident boundary: the supervisor's own plan decides
                    # whether the "process" dies here; on crash the
                    # recovered service replaces ours (pipeline rebound
                    # inside)
                    service = supervisor.checkpoint(
                        pipeline, service, factory, run_timeout_s, clock)
                # same discipline, replica granularity: exactly one poll
                # per incident per killer on both outcome paths (each
                # killer's own plan; the router fails the victim over in
                # place).  List order is the caller's — stable, so a
                # multi-killer schedule is a pure function of the plans
                for k in killers:
                    k.checkpoint()
                # fabric traffic AFTER the killer boundary, so a store
                # killed at boundary i is exercised dead during incident
                # i (counted cold misses on the fabric object) and a
                # heal at a later boundary restores hits — the report
                # never sees either (byte-identity bar)
                if store_fabric is not None:
                    store_fabric.exercise(i)

        if router is not None and router.health is not None:
            # kill-and-heal drain: a wedge landed at the LAST incident
            # boundary has not accrued its missed probes yet — keep
            # pumping (idle replicas: no armed-plan polls) until the
            # watchdog's verdict lands and the supervisor returns the
            # fleet to N.  Bounded: one wedge needs at most
            # hung_tick_threshold probes plus the healing pump.
            budget = router.health.policy.hung_tick_threshold + 2
            for _ in range(budget):
                # healthy(), not alive-and-not-wedged: a SIGKILLed proc
                # replica is alive-looking until the watchdog's verdict
                # (cluster/replica.py) — the old predicate would break
                # out with a corpse still in the fleet
                if all(r.healthy() for r in router.replicas.values()):
                    break
                router.pump()

    if journal is not None:
        # close the CURRENT journal (a supervised crash may have swapped
        # in a reopened one on the same path)
        live_journal = getattr(service, "_journal", None)
        if live_journal is not None:
            live_journal.close()

    report = {
        "seed": seed,
        # proc-cluster, net-cluster AND disagg-cluster report as
        # cluster-oracle ON PURPOSE: the workers run the same scripted
        # oracle over a different transport (pipe or socket) or tier
        # topology, and the acceptance bar is byte-identity against the
        # in-process run — a transport/tier tag would be the one
        # engineered difference
        "backend": ("cluster-oracle"
                    if backend in ("proc-cluster", "net-cluster",
                                   "disagg-cluster")
                    else backend),
        "n_incidents": n_incidents,
        "completed": n_resolved + n_degraded,
        "resolved": n_resolved,
        "degraded": n_degraded,
        "failed": n_failed,
        "retries": policy.counters["retries"],
        "policy": policy.snapshot(),
        "faults": plan.snapshot(),
        "virtual_elapsed_s": round(clock.time(), 6),
        "incidents": incidents,
    }
    if tracer is not None:
        report["flight"] = tracer.flight_summary()
    if router is not None and engines:
        # restarts swap fresh engines into the replicas; the clean check
        # must look at the CURRENT incarnations (the corpses were cancel-
        # drained through the failover path)
        engines = [r.backend.engine for r in router.replicas.values()
                   if getattr(r.backend, "engine", None) is not None]
    if engines:
        # the chaos run must leave EVERY engine clean — killed replicas
        # included (failover cancels through the normal retire path, so a
        # leaked page on a dead replica is a failover bug): drained,
        # allocator invariants intact, no pages beyond prefix residency
        clean = True
        for eng in engines:
            eng.allocator.check()
            resident = (eng.prefix_cache.n_resident
                        if eng.prefix_cache else 0)
            clean = clean and bool(
                not eng.has_work
                and eng.allocator.n_free + resident
                == eng.engine_cfg.num_pages - 1)
        report["engine_clean"] = clean
    if router is not None:
        report["cluster_replicas"] = cluster_replicas
    return report


def report_bytes(report: Dict[str, Any]) -> bytes:
    """Canonical bytes of a soak report (the byte-identity check)."""
    return json.dumps(report, sort_keys=True,
                      separators=(",", ":")).encode()


def run_pipelined_sweep(seed: int = 0, n_incidents: int = 10,
                        backend: str = "engine", concurrency: int = 4,
                        run_timeout_s: float = 1.5,
                        incidents: Optional[List[str]] = None,
                        tracer: Optional[Any] = None,
                        durable_dir: Optional[str] = None,
                        resilience: bool = False,
                        cluster_replicas: int = 2,
                        engine_overrides: Optional[Dict[str, Any]] = None,
                        rca_overrides: Optional[Dict[str, Any]] = None
                        ) -> Dict[str, Any]:
    """Plan-free pipelined RCA sweep: ``concurrency`` incidents in flight
    over one shared backend (rca/scheduler.py::SweepScheduler).

    This is the scheduling-parity surface of ISSUE 11: the
    returned ``report`` carries only scheduling-INVARIANT fields — per-
    incident statuses, degradation annotations, attempt counts, the
    decoded cypher queries and audit report texts, and exact run-id-
    attributed token usage — so ``report_bytes(out["report"])`` must be
    byte-identical across concurrencies (1 vs 4 vs 16) under greedy
    decode.  Everything scheduling-DEPENDENT (pump counts, inflight
    samples, queue-wait spans, flight summaries, resilience counters)
    lives in ``out["stats"]`` instead.

    ``backend``: "engine" | "oracle" | "cluster" | "cluster-oracle" (the
    chaos soak's stacks, built plan-free).  ``incidents``: explicit
    message list (tests interleave retry-with-feedback and resilience-
    ladder incidents); default is the canned corpus cycled
    ``n_incidents`` times.  ``resilience``: arm the degradation ladder
    (identical policy constants to the chaos soak).
    ``engine_overrides`` / ``rca_overrides``: EngineConfig / RCAConfig
    field overrides for the composition matrix (prefix cache, host
    overlap, chunked prefill, speculative decode, concurrent audits).

    Returns ``{"report", "stats", "service", "engines", "router"}`` —
    the live handles let tests run the journal/recovery agreement and
    engine-clean checks against the exact stack the sweep used.
    """
    from k8s_llm_rca_tpu.config import RCAConfig
    from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
    from k8s_llm_rca_tpu.graph.fixtures import (
        INCIDENTS, build_metagraph, build_stategraph,
    )
    from k8s_llm_rca_tpu.rca import RCAPipeline
    from k8s_llm_rca_tpu.rca.scheduler import (
        IncidentFailure, SweepScheduler,
    )

    if concurrency < 1:
        raise ValueError(f"concurrency must be >= 1, got {concurrency}")

    clock = VirtualClock()
    journal = None
    if durable_dir is not None:
        import os

        from k8s_llm_rca_tpu.serve.journal import RunJournal

        os.makedirs(durable_dir, exist_ok=True)
        journal = RunJournal(os.path.join(durable_dir, "serve.wal"))

    router = None
    if backend == "engine":
        service, engine, _factory = _build_engine_service(
            run_timeout_s, clock, journal,
            engine_overrides=engine_overrides)
        engines = [engine]
    elif backend in ("cluster", "cluster-oracle"):
        if engine_overrides:
            raise ValueError("engine_overrides applies to the single-"
                             "engine backend only (cluster replicas pin "
                             "the soak's TINY config)")
        service, engines, _factory, router = _build_cluster_service(
            run_timeout_s, clock, journal, n_replicas=cluster_replicas,
            oracle=(backend == "cluster-oracle"))
    elif backend == "oracle":
        if engine_overrides:
            raise ValueError("engine_overrides applies to the single-"
                             "engine backend only")
        service, _engine, _factory = _build_oracle_service(
            run_timeout_s, clock, journal)
        engines = []
    elif backend in ("proc-cluster", "net-cluster", "disagg-cluster"):
        raise ValueError(
            f"backend={backend!r} is chaos-soak-only (run_chaos_soak): "
            "the pipelined sweep returns live run handles that would "
            "outlive the worker processes (and a mid-handoff run has no "
            "stable home for a live handle) — use "
            "backend='cluster-oracle' here, or run_chaos_soak for the "
            "out-of-process / disaggregated fleet")
    else:
        raise ValueError(f"unknown backend {backend!r}")

    policy = None
    slot_policies: List[Optional[ResiliencePolicy]] = [None] * concurrency
    meta: Any = InMemoryGraphExecutor(build_metagraph())
    state: Any = InMemoryGraphExecutor(build_stategraph())
    if resilience:
        policy = ResiliencePolicy(
            retry=RetryPolicy(max_attempts=3, base_delay_s=0.01,
                              max_delay_s=0.1, deadline_s=5.0, seed=seed,
                              clock=clock),
            failure_threshold=4, reset_timeout_s=0.5, reduced_tokens=256)
        meta = ResilientExecutor(meta, policy, dep="graph.meta")
        state = ResilientExecutor(state, policy, dep="graph.state")
        # each slot gets its OWN ladder policy (same constants, shared
        # retry): degradations is per-incident state reset by
        # begin_incident — one shared instance across interleaved
        # machines would cross-wipe annotations (see run_chaos_soak)
        slot_policies = [policy] + [
            ResiliencePolicy(retry=policy.retry,
                             failure_threshold=policy.failure_threshold,
                             reset_timeout_s=policy.reset_timeout_s,
                             reduced_tokens=policy.reduced_tokens)
            for _ in range(concurrency - 1)]

    cfg = RCAConfig(locator_max_new_tokens=192, cypher_max_new_tokens=96,
                    analyzer_max_new_tokens=96, fresh_threads=True)
    if rca_overrides:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, **rca_overrides)
    if not cfg.fresh_threads:
        # refused even at concurrency=1: the K=1 leg is the parity
        # BASELINE, so it must run the same scheduling-invariant prompt
        # regime the K>1 legs are held to
        raise ValueError("run_pipelined_sweep requires fresh_threads="
                         "True: persistent stage threads make prompts "
                         "depend on incident completion order")

    pipelines = [RCAPipeline(service, meta, state, cfg,
                             resilience=slot_policies[i])
                 for i in range(concurrency)]

    obs_ctx: Any = contextlib.nullcontext()
    if tracer is not None:
        from k8s_llm_rca_tpu.obs import trace as obs_trace

        tracer.clock = clock          # virtual timestamps, like the soak
        obs_ctx = obs_trace.tracing(tracer)

    messages = (list(incidents) if incidents is not None
                else [INCIDENTS[i % len(INCIDENTS)].message
                      for i in range(n_incidents)])

    sched = SweepScheduler(pipelines)
    with obs_ctx:
        results = sched.run(messages)

    if journal is not None:
        live_journal = getattr(service, "_journal", None)
        if live_journal is not None:
            live_journal.close()

    rows: List[Dict[str, Any]] = []
    n_resolved = n_degraded = n_failed = 0
    for message, result in zip(messages, results):
        if isinstance(result, IncidentFailure):
            rows.append({"error_message": message, "status": "failed",
                         "error": result.error})
            n_failed += 1
            continue
        row = _incident_row(message, result)
        # the per-incident flight digest is scheduling-dependent (it sees
        # the tracer mid-sweep) — stats territory, never report territory
        row.pop("flight", None)
        # carry the decoded artifacts too: byte-identity then attests
        # actual greedy decode parity, not just structural agreement
        row["token_usage"] = result.get("token_usage")
        for ra, a in zip(row["analyses"], result.get("analysis", [])):
            ra["cypher_query"] = a.get("human_cypher_query",
                                       a.get("cypher_query"))
            ra["reports"] = [sp.get("report")
                             for sp in a.get("statepath", [])]
        if row["status"] == "degraded":
            n_degraded += 1
        else:
            n_resolved += 1
        rows.append(row)

    report: Dict[str, Any] = {
        "seed": seed,
        "backend": backend,
        "n_incidents": len(messages),
        "completed": n_resolved + n_degraded,
        "resolved": n_resolved,
        "degraded": n_degraded,
        "failed": n_failed,
        "incidents": rows,
    }
    if router is not None and engines:
        engines = [r.backend.engine for r in router.replicas.values()
                   if getattr(r.backend, "engine", None) is not None]
    if engines:
        # same bar as the chaos soak: the sweep must leave every engine
        # drained with allocator invariants intact
        clean = True
        for eng in engines:
            eng.allocator.check()
            resident = (eng.prefix_cache.n_resident
                        if eng.prefix_cache else 0)
            clean = clean and bool(
                not eng.has_work
                and eng.allocator.n_free + resident
                == eng.engine_cfg.num_pages - 1)
        report["engine_clean"] = clean
    if router is not None:
        report["cluster_replicas"] = cluster_replicas

    stats: Dict[str, Any] = dict(sched.stats.snapshot())
    stats["concurrency"] = concurrency
    if policy is not None:
        # ladder counters accumulate per SLOT policy; the sums are
        # interleaving-invariant even though the split across slots isn't
        snap = policy.snapshot()
        for p in slot_policies[1:]:
            for k, v in p.counters.items():
                snap["counters"][k] = snap["counters"].get(k, 0) + v
        stats["policy"] = snap
    if tracer is not None:
        stats["flight"] = tracer.flight_summary()
        # per-run latency decomposition (obs/critical_path.py): like the
        # flight digest it reads the tracer, so it is stats territory —
        # scheduling changes queue-wait shares, never report bytes
        from k8s_llm_rca_tpu.obs import critical_path_stats
        stats["critical_path"] = critical_path_stats(tracer)
    return {"report": report, "stats": stats, "service": service,
            "engines": engines, "router": router}


def run_overload_soak(seed: int = 0, n_runs: int = 100, spill: bool = True,
                      max_spilled_pages: int = 96,
                      max_new_tokens: int = 32) -> Dict[str, Any]:
    """Mixed-priority overload soak on the paged TINY engine: ``n_runs``
    incident prompts submitted up front (priorities cycling CRITICAL /
    NORMAL / BATCH) under a scheduled preempt/oom tick-fault schedule, so
    preemption waves bite while the queue is deep.

    Returns ``{"report": ..., "stats": ...}``.  ``report`` is the
    byte-identity surface: its bytes are IDENTICAL with ``spill`` on or
    off, because greedy decode is path-independent — a preemption (KV
    spill/restore OR free/re-prefill) never changes what any sequence
    generates, only WHEN ticks happen (a restore admission samples no
    token, so the spilled run's tick count shifts by one per resume).
    The report therefore carries only per-run outcomes (priority, finish
    reason, text, token counts) and NO tick-sensitive data — no fault
    polls, no tick totals, and not the spill knob itself.  ``stats``
    holds the tick-sensitive numbers (spilled/restored pages,
    preemptions, engine_clean) for assertions OUTSIDE the identity
    check."""
    import jax

    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.faults.plan import Fault
    from k8s_llm_rca_tpu.graph.fixtures import INCIDENTS
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.serve.backend import Priority
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    engine = make_engine(
        cfg, EngineConfig(max_batch=4, max_seq_len=256,
                          prefill_buckets=(256,),
                          max_new_tokens=max_new_tokens, temperature=0.0,
                          page_size=16, num_pages=96,
                          prefix_cache=False, decode_chunk=8,
                          max_spilled_pages=(max_spilled_pages if spill
                                             else 0)),
        params, tok, use_kernel=False)
    # explicit tick-fault schedule (indices, not rate-sampled): the two
    # runs' tick counts drift once a spill lands, so a shared RATE plan
    # would fire on different ticks — which is fine for byte-identity
    # (outputs are path-independent) but explicit waves guarantee the
    # spill path is actually exercised early, while the queue is deep
    waves = [Fault(inject.SITE_ENGINE_TICK, i, kind, 0.0, wave=2)
             for i, kind in ((6, "preempt"), (14, "oom"), (22, "preempt"),
                             (30, "oom"), (45, "preempt"), (70, "preempt"))]
    plan = FaultPlan(waves, seed=seed, clock=VirtualClock())
    classes = (Priority.CRITICAL, Priority.NORMAL, Priority.BATCH)
    order: List[int] = []
    priorities: Dict[int, int] = {}
    with inject.armed(plan):
        for i in range(n_runs):
            msg = INCIDENTS[i % len(INCIDENTS)].message
            pri = classes[i % len(classes)]
            sid = engine.submit(tok.encode(f"[inc {i}] {msg}")[:128],
                                priority=pri)
            order.append(sid)
            priorities[sid] = pri
        results = {}
        while engine.has_work:
            for r in engine.step():
                results[r.seq_id] = r
    runs = [{"priority": priorities[sid],
             "finish": results[sid].finish_reason,
             "text": results[sid].text,
             "completion_tokens": results[sid].completion_tokens}
            for sid in order]
    report = {
        "seed": seed, "n_runs": n_runs,
        "runs": runs,
        "by_status": {
            s: sum(1 for r in runs if r["finish"] == s)
            for s in sorted({r["finish"] for r in runs})},
    }
    engine.allocator.check()
    counts = engine._counts or {}
    stats = {
        "spill_enabled": spill,
        "spilled_pages": counts.get("engine.spilled_pages", 0.0),
        "restored_pages": counts.get("engine.restored_pages", 0.0),
        "spill_budget_fallbacks": counts.get(
            "engine.spill_budget_fallbacks", 0.0),
        "preemptions": counts.get("engine.preemptions", 0.0),
        "engine_clean": bool(not engine.has_work
                             and engine.allocator.n_free
                             == engine.engine_cfg.num_pages - 1),
    }
    return {"report": report, "stats": stats}


def run_saturation_scenario(n_replicas: int = 2, max_inflight: int = 2,
                            n_requests: int = 12) -> Dict[str, Any]:
    """Priority-tiered backpressure under saturation: a mixed-priority
    burst against a small EchoBackend cluster WITHOUT pumping between
    starts, so queue depths only grow.  CRITICAL is cap-exempt (always
    admits while a replica is alive), NORMAL fills to the inflight cap,
    BATCH stops one slot short — so the shed order is strictly BATCH
    before NORMAL and never CRITICAL, each shed surfacing as the typed
    ``RouterAdmissionError``.  Every admitted run then pumps to
    completion (CRITICAL always completes)."""
    from k8s_llm_rca_tpu.cluster import ClusterRouter, Replica
    from k8s_llm_rca_tpu.cluster.router import RouterAdmissionError
    from k8s_llm_rca_tpu.serve.backend import (
        EchoBackend, GenOptions, Priority,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    tok = get_tokenizer()
    router = ClusterRouter(
        [Replica(i, EchoBackend(tok)) for i in range(n_replicas)],
        max_inflight_per_replica=max_inflight)
    classes = (Priority.CRITICAL, Priority.NORMAL, Priority.BATCH)
    outcomes: List[Dict[str, Any]] = []
    handles: Dict[int, int] = {}
    for i in range(n_requests):
        pri = classes[i % len(classes)]
        row: Dict[str, Any] = {"i": i, "priority": pri}
        try:
            handles[i] = router.start(f"incident {i}",
                                      GenOptions(max_new_tokens=4,
                                                 priority=pri))
            row["admitted"] = True
        except RouterAdmissionError as e:
            row["admitted"] = False
            row["error"] = type(e).__name__
            row["detail"] = str(e)
        outcomes.append(row)
    results = {}
    while any(router.busy(h) for h in handles.values()):
        results.update(router.pump())
    admitted = {p: sum(1 for o in outcomes
                       if o["priority"] == p and o["admitted"])
                for p in classes}
    shed = {p: sum(1 for o in outcomes
                   if o["priority"] == p and not o["admitted"])
            for p in classes}
    return {
        "n_replicas": n_replicas, "max_inflight": max_inflight,
        "outcomes": outcomes,
        "admitted_by_class": admitted, "shed_by_class": shed,
        "completed": sum(1 for i, h in handles.items()
                         if results.get(h) is not None
                         and results[h].error is None),
    }


def poisson_arrivals(seed: int, rate_per_s: float, n: int) -> List[float]:
    """Seeded exponential inter-arrival gaps, cumulated to absolute
    arrival offsets — the open-loop schedule (arrivals never wait on
    completions, ROADMAP item 4).  Pure function of ``(seed,
    rate_per_s, n)``; stdlib Mersenne, so byte-stable across hosts."""
    if rate_per_s <= 0.0:
        raise ValueError(f"rate_per_s must be > 0, got {rate_per_s}")
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    import random

    rng = random.Random(seed)
    t, out = 0.0, []
    for _ in range(n):
        t += rng.expovariate(rate_per_s)
        out.append(round(t, 9))
    return out


def run_open_loop_soak(seed: int = 0, rate_per_s: float = 200.0,
                       n_runs: int = 24, n_replicas: int = 2,
                       selfheal: bool = False,
                       killer: Optional[Any] = None,
                       run_timeout_s: float = 30.0,
                       tick_s: float = 0.005,
                       durable_dir: Optional[str] = None) -> Dict[str, Any]:
    """Open-loop Poisson traffic through serve/api.py: seeded
    exponential inter-arrivals feed ``create_run`` at ``rate_per_s``
    regardless of completions, and the report carries p50/p99
    time-to-report on the VirtualClock (each pump advances ``tick_s``,
    so latency is a deterministic function of pump counts, never a
    measured wall time).

    Composable with the kill-and-heal machinery for the SRE-storm
    scenario: ``killer`` (faults.supervisor.ReplicaKiller) is polled
    exactly once per ARRIVAL on its own FaultPlan — with ``selfheal``
    the victims are wedged and the watchdog/supervisor loop heals the
    fleet while the storm keeps arriving.  Kill/heal stats stay on the
    killer/router objects; the report is a pure function of its
    arguments.
    """
    clock = VirtualClock()
    journal = None
    if durable_dir is not None:
        import os

        from k8s_llm_rca_tpu.serve.journal import RunJournal

        os.makedirs(durable_dir, exist_ok=True)
        journal = RunJournal(os.path.join(durable_dir, "openloop.wal"))
    service, _, _, router = _build_cluster_service(
        run_timeout_s, clock, journal, n_replicas=n_replicas,
        oracle=True, selfheal=selfheal)
    if killer is not None:
        killer.router = router
    from k8s_llm_rca_tpu.graph.fixtures import INCIDENTS
    from k8s_llm_rca_tpu.serve.api import RunStatus
    from k8s_llm_rca_tpu.serve.backend import GenOptions

    asst = service.create_assistant(
        "You are an SRE root-cause analyst.", "openloop",
        gen=GenOptions(max_new_tokens=64))
    arrivals = poisson_arrivals(seed, rate_per_s, n_runs)
    pending = list(enumerate(arrivals))
    live: Dict[str, tuple] = {}               # run id -> (i, arrival_t)
    rows: List[Dict[str, Any]] = []
    while pending or live:
        now = clock.time()
        if pending and pending[0][1] <= now:
            i, t_arr = pending.pop(0)
            thread = service.create_thread()
            service.add_message(
                thread.id, INCIDENTS[i % len(INCIDENTS)].message)
            run = service.create_run(thread.id, asst.id)
            live[run.id] = (i, t_arr)
            if killer is not None:
                # arrival boundary: the kill schedule is a pure function
                # of (killer plan, arrival index) — same discipline as
                # the incident-boundary poll in run_chaos_soak
                killer.checkpoint()
            continue
        service._pump()
        now = clock.time()
        for run_id in [r for r in live
                       if service.runs[r].status in RunStatus.TERMINAL]:
            i, t_arr = live.pop(run_id)
            run = service.runs[run_id]
            rows.append({"i": i, "status": run.status,
                         "ttr_s": round(now - t_arr, 9)})
        if pending and not live:
            clock.sleep(max(0.0, pending[0][1] - now))  # idle: jump ahead
        else:
            clock.sleep(tick_s)
    if router.health is not None:
        budget = router.health.policy.hung_tick_threshold + 2
        for _ in range(budget):      # heal a storm-tail wedge (see
            if all(r.healthy()       # run_chaos_soak drain)
                   for r in router.replicas.values()):
                break
            router.pump()
    if journal is not None:
        live_journal = getattr(service, "_journal", None)
        if live_journal is not None:
            live_journal.close()
    rows.sort(key=lambda r: r["i"])
    ttrs = sorted(r["ttr_s"] for r in rows)

    def _pct(q: float) -> Optional[float]:
        if not ttrs:
            return None
        return round(ttrs[min(len(ttrs) - 1, int(q * len(ttrs)))], 9)

    return {
        "seed": seed, "rate_per_s": rate_per_s, "n_runs": n_runs,
        "n_replicas": n_replicas, "selfheal": bool(selfheal),
        "outcomes": rows,
        "completed": sum(1 for r in rows
                         if r["status"] == RunStatus.COMPLETED),
        "failed": sum(1 for r in rows
                      if r["status"] == RunStatus.FAILED),
        "p50_ttr_s": _pct(0.50),
        "p99_ttr_s": _pct(0.99),
        "virtual_elapsed_s": round(clock.time(), 6),
        "fleet_alive": len(router.alive_ids()),
    }


_METERED_ECHO_CLS = None


def metered_echo_class():
    """``_MeteredEcho``: an EchoBackend with FINITE per-pump service
    capacity — it settles at most ``settle_per_pump`` ready runs per
    pump, FIFO by handle.  The plain Echo/Oracle backends settle EVERY
    ready run each pump (infinite parallelism), so fleet size would
    never move time-to-report and an elastic-vs-static comparison would
    be vacuous; metering makes queue depth the latency driver, which is
    exactly the gauge the autoscaler watches.  Built lazily (soak
    convention: serve-layer imports stay inside functions)."""
    global _METERED_ECHO_CLS
    if _METERED_ECHO_CLS is not None:
        return _METERED_ECHO_CLS

    from k8s_llm_rca_tpu.serve.backend import BackendResult, EchoBackend

    class _MeteredEcho(EchoBackend):
        def __init__(self, tokenizer, settle_per_pump: int = 1, **kw):
            if settle_per_pump < 1:
                raise ValueError(
                    f"settle_per_pump must be >= 1 (a backend that "
                    f"settles nothing never drains), got "
                    f"{settle_per_pump}")
            super().__init__(tokenizer, **kw)
            self.settle_per_pump = settle_per_pump

        def pump(self):
            results = {}
            settled = 0
            for handle in sorted(self._inflight):
                if settled >= self.settle_per_pump:
                    break
                prompt, opts, remaining = self._inflight[handle]
                if remaining > 0:
                    self._inflight[handle] = (prompt, opts, remaining - 1)
                    continue
                del self._inflight[handle]
                if self.fail:
                    results[handle] = BackendResult(
                        "", 0, error="echo backend failure")
                    settled += 1
                    continue
                text = (self.reply if self.reply is not None
                        else f"echo: {prompt[-64:]}")
                text = opts.forced_prefix + text + opts.suffix
                results[handle] = BackendResult(
                    text=text,
                    completion_tokens=self.tokenizer.count(text))
                settled += 1
            return results

    _METERED_ECHO_CLS = _MeteredEcho
    return _MeteredEcho


def diurnal_arrivals(seed: int, rate_low_per_s: float,
                     rate_high_per_s: float, period_s: float,
                     n: int) -> List[float]:
    """Seeded non-homogeneous Poisson arrivals under a sinusoidal
    diurnal rate ramp: rate(t) = low + (high - low)·(1 - cos(2πt/T))/2
    — the night trough at t=0, the midday peak at t=T/2.  Sampled by
    thinning against the ``rate_high_per_s`` majorant, so it is a pure
    function of ``(seed, rates, period, n)`` on the stdlib Mersenne
    generator (byte-stable across hosts, like ``poisson_arrivals``)."""
    if rate_low_per_s <= 0.0 or rate_high_per_s < rate_low_per_s:
        raise ValueError(
            f"need 0 < rate_low_per_s <= rate_high_per_s, got "
            f"low={rate_low_per_s}, high={rate_high_per_s}")
    if period_s <= 0.0:
        raise ValueError(f"period_s must be > 0, got {period_s}")
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    import math
    import random

    rng = random.Random(seed)
    t, out = 0.0, []
    while len(out) < n:
        t += rng.expovariate(rate_high_per_s)
        lam = rate_low_per_s + (rate_high_per_s - rate_low_per_s) * 0.5 \
            * (1.0 - math.cos(2.0 * math.pi * t / period_s))
        if rng.random() * rate_high_per_s <= lam:
            out.append(round(t, 9))
    return out


def run_elastic_soak(seed: int = 0, rate_low_per_s: float = 60.0,
                     rate_high_per_s: float = 1500.0,
                     period_s: float = 0.6, n_runs: int = 520,
                     n_min: int = 1, n_max: int = 4,
                     elastic: bool = True,
                     policy: Optional[Any] = None,
                     killer: Optional[Any] = None,
                     settle_per_pump: int = 1,
                     run_timeout_s: float = 30.0,
                     tick_s: float = 0.005) -> Dict[str, Any]:
    """Open-loop diurnal-ramp soak over an ELASTIC fleet — the
    acceptance surface of the autoscaler (cluster/autoscale.py):

    - ``elastic=True``: the router starts with ``n_min`` metered-echo
      replicas; the remaining ``n_max - n_min`` are parked on the
      Autoscaler's reserve (free submeshes).  ``evaluate()`` runs once
      per idle loop iteration, so the fleet grows into the ramp and
      drains back down the far side.
    - ``elastic=False``: the static twin — all ``n_max`` replicas
      serve from t=0, no autoscaler.

    Both modes integrate ``chip_seconds`` identically (alive replicas ×
    every virtual-clock advance), so the bar "elastic p99 time-to-report
    <= static with strictly fewer chip-seconds" compares like with like.
    ``killer`` is polled once per ARRIVAL (run_open_loop_soak
    discipline) — with killers armed DURING scale events the report must
    still come out byte-identical run over run: scale/kill/heal stats
    live on the autoscaler/killer/router objects, never in the report.

    Returns ``{"report": ..., "stats": ...}`` — byte-identity is
    ``report_bytes(out["report"])``; ``stats`` carries the scale/kill
    counters (deterministic too, but harness-side by convention).
    """
    if not 1 <= n_min < n_max:
        raise ValueError(
            f"need 1 <= n_min < n_max (an elastic band), got "
            f"n_min={n_min}, n_max={n_max}")
    clock = VirtualClock()
    from k8s_llm_rca_tpu.cluster import (ClusterRouter, HealthWatchdog,
                                         Replica, ReplicaSupervisor)
    from k8s_llm_rca_tpu.cluster.autoscale import Autoscaler, ScalePolicy
    from k8s_llm_rca_tpu.serve.api import AssistantService, RunStatus
    from k8s_llm_rca_tpu.serve.backend import GenOptions
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cls = metered_echo_class()
    tok = get_tokenizer()
    replicas = [
        Replica(i, cls(tok, settle_per_pump),
                rebuild=lambda t=tok, c=cls, k=settle_per_pump: c(t, k))
        for i in range(n_max)]
    router = ClusterRouter(replicas[:n_min] if elastic else replicas)
    router.attach_health(HealthWatchdog(None, clock=clock),
                         ReplicaSupervisor())
    scaler = None
    if elastic:
        pol = policy or ScalePolicy(
            high_water=0.5, low_water=0.15, depth_capacity=2,
            sustain_ticks=2, cooldown_ticks=2,
            min_replicas=n_min, max_replicas=n_max)
        scaler = Autoscaler(router, pol, reserve=replicas[n_min:],
                            clock=clock)
    if killer is not None:
        killer.router = router
    service = AssistantService(router, run_timeout_s=run_timeout_s,
                               clock=clock)
    asst = service.create_assistant(
        "You are an SRE root-cause analyst.", "elastic",
        gen=GenOptions(max_new_tokens=16))
    arrivals = diurnal_arrivals(seed, rate_low_per_s, rate_high_per_s,
                                period_s, n_runs)
    from k8s_llm_rca_tpu.graph.fixtures import INCIDENTS

    pending = list(enumerate(arrivals))
    live: Dict[str, tuple] = {}               # run id -> (i, arrival_t)
    rows: List[Dict[str, Any]] = []
    chip_seconds = 0.0

    def _advance(dt: float) -> None:
        # chips burn whenever virtual time passes, busy or idle — the
        # like-with-like integral both fleet modes share
        nonlocal chip_seconds
        if dt <= 0.0:
            return
        chip_seconds += len(router.alive_ids()) * dt
        clock.sleep(dt)

    while pending or live:
        now = clock.time()
        if pending and pending[0][1] <= now:
            i, t_arr = pending.pop(0)
            thread = service.create_thread()
            service.add_message(
                thread.id, INCIDENTS[i % len(INCIDENTS)].message)
            run = service.create_run(thread.id, asst.id)
            live[run.id] = (i, t_arr)
            if killer is not None:
                killer.checkpoint()     # arrival-boundary discipline
            continue
        if pending and not live:
            if scaler is not None:
                scaler.evaluate()       # troughs are where drain-down
            _advance(max(tick_s, pending[0][1] - now))  # fires; idle jump
            continue
        # one service tick: the pump COSTS tick_s of virtual time BEFORE
        # results land, so a replica serves settle_per_pump/tick_s runs
        # per second — finite service capacity is what lets the diurnal
        # peak build the queue the autoscaler watches (a free pump would
        # model an infinitely fast server and the elastic-vs-static
        # comparison would be vacuous)
        if scaler is not None:
            scaler.evaluate()           # one control tick per loop tick
        _advance(tick_s)
        service._pump()
        now = clock.time()
        for run_id in [r for r in live
                       if service.runs[r].status in RunStatus.TERMINAL]:
            i, t_arr = live.pop(run_id)
            run = service.runs[run_id]
            rows.append({"i": i, "status": run.status,
                         "ttr_s": round(now - t_arr, 9)})
    if router.health is not None:
        budget = router.health.policy.hung_tick_threshold + 2
        for _ in range(budget):          # heal a storm-tail wedge
            if all(r.healthy() for r in router.replicas.values()):
                break
            router.pump()
    rows.sort(key=lambda r: r["i"])
    ttrs = sorted(r["ttr_s"] for r in rows)

    def _pct(q: float) -> Optional[float]:
        if not ttrs:
            return None
        return round(ttrs[min(len(ttrs) - 1, int(q * len(ttrs)))], 9)

    report = {
        "seed": seed, "rate_low_per_s": rate_low_per_s,
        "rate_high_per_s": rate_high_per_s, "period_s": period_s,
        "n_runs": n_runs, "n_min": n_min, "n_max": n_max,
        "elastic": bool(elastic), "settle_per_pump": settle_per_pump,
        "outcomes": rows,
        "completed": sum(1 for r in rows
                         if r["status"] == RunStatus.COMPLETED),
        "failed": sum(1 for r in rows
                      if r["status"] == RunStatus.FAILED),
        "p50_ttr_s": _pct(0.50),
        "p99_ttr_s": _pct(0.99),
        "chip_seconds": round(chip_seconds, 9),
        "virtual_elapsed_s": round(clock.time(), 6),
        "fleet_alive": len(router.alive_ids()),
    }
    stats = {
        "scale_ups": scaler.scale_ups if scaler else 0,
        "scale_downs": scaler.scale_downs if scaler else 0,
        "rebalances": scaler.rebalances if scaler else 0,
        "decisions": len(scaler.decisions) if scaler else 0,
        "reserve_free": len(scaler.reserve) if scaler else 0,
        "kills": len(killer.kills) if killer is not None else 0,
    }
    return {"report": report, "stats": stats, "router": router,
            "autoscaler": scaler}
