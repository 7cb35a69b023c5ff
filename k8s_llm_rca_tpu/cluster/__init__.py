"""Multi-replica serving cluster: disjoint submesh replicas, an
affinity/load-balancing router, and journal-consistent failover.

Layer map (PARITY.md §cluster, docs/cluster.md):

- ``submesh.carve_replica_meshes`` — carve the device list into N
  disjoint dp×tp submeshes (loud ValueError on indivisibility/overlap);
- ``replica.build_replicas`` / ``Replica`` — one engine per submesh,
  params initialized once and sharded per replica;
- ``router.ClusterRouter`` — the LMBackend facade the assistants
  service talks to: session affinity on thread id, queue-depth
  balancing, ``RouterAdmissionError`` backpressure, ``fail_replica``
  (kill + re-start on survivors) and ``drain_replica``
  (snapshot/adopt migration with decode position);
- ``health.HealthWatchdog`` / ``HealthPolicy`` /
  ``ReplicaSupervisor`` — the self-healing loop
  (``router.attach_health``): deterministic ALIVE -> SUSPECT -> DEAD
  liveness from tick/pump heartbeats, in-tree failover on DEAD,
  restart-and-rejoin on the original submesh, and poison-run
  quarantine after ``quarantine_after`` fatal incarnations;
- ``proc.ProcReplica`` / ``proc.build_proc_replicas`` — out-of-process
  replicas: each backend runs in its own OS process (spawned with
  ``proc.worker_env``: pinned to the CPU, its own virtual device count)
  behind the length-prefixed CRC-framed
  wire protocol (``wire.py``); the watchdog's liveness verdicts gain
  hard OS evidence (pipe EOF / exit codes) and the supervisor's
  ``rebuild`` restarts the actual process;
- ``disagg.TierRouter`` — disaggregated prefill/decode tiers over any
  of the above replica shapes, with a transactional (EXPORT -> ADOPT ->
  RELEASE) per-run KV handoff between the tiers that survives
  mid-handoff kills (``faults.supervisor.HandoffKiller``);
- ``autoscale.Autoscaler`` / ``ScalePolicy`` — the elastic control
  loop: watermark-driven scale-up (supervisor rebuild-recipe spawn
  onto a free submesh), drain-down (live sequences migrate, staged
  ``close()``, submesh parked back on the reserve), and prefill<->
  decode tier rebalancing via ``TierRouter.reassign_tier`` — all a
  pure function of the gauge sequence under a frozen VirtualClock.
"""

from k8s_llm_rca_tpu.cluster.autoscale import Autoscaler, ScalePolicy
from k8s_llm_rca_tpu.cluster.disagg import (TIER_DECODE, TIER_PREFILL,
                                            TierRouter)
from k8s_llm_rca_tpu.cluster.health import (ALIVE, DEAD, SUSPECT,
                                            HealthPolicy, HealthWatchdog,
                                            ReplicaSupervisor)
from k8s_llm_rca_tpu.cluster.proc import ProcReplica, build_proc_replicas
from k8s_llm_rca_tpu.cluster.replica import (EngineReplica, Replica,
                                             build_replicas)
from k8s_llm_rca_tpu.cluster.router import (ClusterRouter,
                                            RouterAdmissionError)
from k8s_llm_rca_tpu.cluster.submesh import carve_replica_meshes

__all__ = [
    "carve_replica_meshes", "build_replicas", "Replica", "EngineReplica",
    "ClusterRouter", "RouterAdmissionError",
    "HealthPolicy", "HealthWatchdog", "ReplicaSupervisor",
    "ALIVE", "SUSPECT", "DEAD",
    "ProcReplica", "build_proc_replicas",
    "TierRouter", "TIER_PREFILL", "TIER_DECODE",
    "Autoscaler", "ScalePolicy",
]
