"""One serving replica: an LM backend bound to a replica id (and, for
engine replicas, its submesh).

``build_replicas`` is the engine path: the TINY weights are initialized
ONCE on host and sharded onto each replica's submesh
(runtime/sharding.py ``shard_pytree`` — GSPMD then keeps every replica's
compute on its own devices, the same committed-input propagation the TP
parity test relies on), so N replicas cost one param init and N device
transfers, not N inits.  Each replica gets its own engine, tokenizer
handle, and ``EngineBackend``; the engine is stamped with
``obs_replica`` so its ``engine.tick`` spans and TickSamples carry the
replica id (per-replica Chrome tracks, obs/export.py).

``Replica`` itself is backend-agnostic: the router only needs
``queue_depth()`` / ``occupancy()``, duck-typed here so scripted
backends (OracleBackend, EchoBackend — ``_inflight`` dicts) and the real
``EngineBackend`` (``_live`` + engine slots) all serve as replicas; the
cluster chaos soak runs 100 incidents on oracle replicas for exactly
this reason (tier-1 budget).

Overload composition (docs/serving.md "overload & priorities"): the
router admits by priority class against ``queue_depth()`` (CRITICAL
cap-exempt, BATCH one slot short), and migration preserves the class —
``fail_replica`` re-starts with the run's original GenOptions (priority
AND deadline_s ride along) while ``drain_replica`` adopts engine
snapshots whose sequence entries now carry priority and the absolute
engine deadline.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

from k8s_llm_rca_tpu.engine.engine import validate_replica_mesh
from k8s_llm_rca_tpu.utils.logging import get_logger

log = get_logger(__name__)


class Replica:
    """A replica slot in the cluster: id, backend, optional submesh.

    ``rebuild``: optional zero-arg recipe returning a FRESH backend for
    this slot — the restart-and-rejoin source (cluster/health.py
    ``ReplicaSupervisor``).  ``build_replicas`` records one per engine
    replica (re-shard the shared host params onto the SAME submesh);
    scripted replicas pass their own.

    ``wedged``: the in-tree stand-in for a dead worker process — the
    backend object still exists (its engine stands in for the corpse's
    device state) but the router stops pumping it, so it stops beating
    and the health watchdog must detect it.  ``fail_replica`` is the
    *consequence* of a wedge, never the injection itself.

    ``draining`` / ``retiring``: the scale-event window markers
    (cluster/autoscale.py).  ``draining`` is set while the replica's
    sequences are mid-migration (drain snapshot in flight), ``retiring``
    while its staged ``close()`` runs; both clear when the replica
    leaves the fleet (or rejoins a tier).  Fault killers REFUSE victims
    inside either window (faults/supervisor.py) — a kill there would
    orphan the drain snapshot.
    """

    def __init__(self, replica_id: int, backend: Any, mesh=None,
                 rebuild=None, layout=None, kv_layout=None):
        self.replica_id = replica_id
        self.backend = backend
        self.mesh = mesh
        self.rebuild = rebuild
        # weight-layout metadata for tiered serving (cluster/disagg.py):
        # ``layout`` is the runtime.rules.SpecLayout the params were
        # sharded under; ``kv_layout`` describes the KV-record geometry
        # a handoff peer must be able to adopt ({"page_size","kv_dtype",
        # "kv_dim","n_layers"}).  Scripted replicas (echo/oracle) leave
        # both None and skip the tier compatibility checks.
        self.layout = layout
        self.kv_layout = kv_layout
        self.alive = True
        self.wedged = False
        self.draining = False
        self.retiring = False

    def wedge(self) -> None:
        """Simulate this replica's process dying: it stays nominally
        alive (nobody told the router) but never beats again."""
        self.wedged = True

    def healthy(self) -> bool:
        """Serving right now, as far as the router knows.  Subclasses
        with a REAL process behind them (cluster/proc.py ProcReplica)
        also check hard liveness — drain loops that wait for the fleet
        to settle must use this, not ``alive``/``wedged`` directly, or a
        SIGKILLed worker would satisfy the predicate while dead."""
        return self.alive and not self.wedged

    def queue_depth(self) -> int:
        b = self.backend
        if hasattr(b, "queue_depth"):
            return int(b.queue_depth())
        if hasattr(b, "_live"):
            return len(b._live)
        if hasattr(b, "_inflight"):
            return len(b._inflight)
        raise TypeError(
            f"replica {self.replica_id}: backend "
            f"{type(b).__name__} exposes no queue-depth signal "
            f"(queue_depth() / _live / _inflight)")

    def occupancy(self) -> float:
        b = self.backend
        if hasattr(b, "occupancy"):
            return float(b.occupancy())
        return 0.0

    def __repr__(self) -> str:
        return (f"Replica({self.replica_id}, "
                f"{type(self.backend).__name__}, "
                f"alive={self.alive}, depth={self.queue_depth()})")


# kept as an alias for call sites that want to say what the replica IS
EngineReplica = Replica


def build_replicas(model_cfg, engine_cfg, n_replicas: int,
                   devices: Optional[Sequence[Any]] = None,
                   data: int = 1, fsdp: int = 1, seed: int = 0,
                   meshes=None, prefix_store=None, layout=None,
                   **engine_kw) -> List[Replica]:
    """N engine replicas on disjoint submeshes, one shared param init.

    ``meshes``: pre-carved submeshes (else ``carve_replica_meshes`` runs
    with ``devices``/``data``/``fsdp``).  Every mesh passes
    ``validate_replica_mesh`` — CP/PP/EP × replica compositions and
    submeshes the TINY head layout cannot shard are rejected loudly
    before any device work.  ``engine_kw`` forwards to ``make_engine``
    (e.g. ``use_kernel=False`` on the CPU test mesh).

    ``layout``: a ``runtime.rules.SpecLayout`` naming which mesh axes
    the logical data/fsdp/tp axes land on — the per-tier weight-layout
    hook (docs/cluster.md): a prefill tier can build TP-heavy replicas
    and a decode tier KV-wide ones from the SAME host params.  Defaults
    to ``FSDP_LAYOUT`` when the submeshes carry an fsdp axis > 1, else
    ``TP_LAYOUT``.  Every (layout, mesh) pair passes
    ``runtime.rules.validate_layout`` pre-flight — undefined axes and
    non-default mappings onto size-1 axes are named ValueErrors before
    any weight moves, and the supervisor ``rebuild`` recipe re-runs the
    same check so a restarted incarnation cannot silently change layout.

    ``prefix_store``: one SHARED ``engine.prefix.PrefixStore`` handed to
    every replica's engine (docs/cluster.md "warm-start"): pages any
    replica demotes (or ``flush_prefix_store``-publishes) become L1/L2
    hits on every other, so a new replica — and a supervisor-restarted
    incarnation, which rides the same ``engine_kw`` through the
    ``rebuild`` recipe below — warm-starts by h2d page promotion instead
    of re-prefilling the fleet's shared prompt preambles.
    """
    import jax

    from k8s_llm_rca_tpu.cluster.submesh import carve_replica_meshes
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.runtime.sharding import (
        FSDP_LAYOUT, TP_LAYOUT, llama_param_specs, shard_pytree,
        validate_layout,
    )
    from k8s_llm_rca_tpu.serve.backend import EngineBackend

    if meshes is None:
        meshes = carve_replica_meshes(n_replicas, devices=devices,
                                      data=data, fsdp=fsdp)
    if len(meshes) != n_replicas:
        raise ValueError(f"{len(meshes)} meshes for {n_replicas} replicas")
    if layout is None:
        has_fsdp = fsdp > 1 or any(
            m is not None and m.shape.get("fsdp", 1) > 1 for m in meshes)
        layout = FSDP_LAYOUT if has_fsdp else TP_LAYOUT
    for mesh in meshes:
        validate_replica_mesh(mesh, model_cfg, engine_cfg)
        validate_layout(layout, mesh)

    if prefix_store is not None:
        engine_kw = dict(engine_kw, prefix_store=prefix_store)
    tok = engine_kw.pop("tokenizer", None)
    if tok is None:
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        tok = get_tokenizer(vocab_size=model_cfg.vocab_size)
    params = llama.init_params(model_cfg, jax.random.PRNGKey(seed))
    specs = llama_param_specs(model_cfg, layout=layout)
    kv_layout = {
        "page_size": engine_cfg.page_size,
        "kv_dtype": engine_cfg.kv_cache_dtype,
        "kv_dim": model_cfg.kv_dim,
        "n_layers": model_cfg.n_layers,
    }

    replicas: List[Replica] = []
    for rid, mesh in enumerate(meshes):
        sharded = shard_pytree(params, specs, mesh)
        engine = make_engine(model_cfg, engine_cfg, sharded, tok,
                             **engine_kw)
        engine.obs_replica = rid      # per-replica span/TickSample tag

        def _rebuild(mesh=mesh, rid=rid, kw=dict(engine_kw)):
            # restart-and-rejoin recipe (cluster/health.py): re-shard the
            # SAME host params onto the replica's ORIGINAL submesh — the
            # identical-replica invariant, so a restarted incarnation
            # generates byte-identically to the first.  The layout
            # pre-flight re-runs too: a rebuild can never adopt a layout
            # the original mesh would have refused.
            validate_layout(layout, mesh)
            eng = make_engine(model_cfg, engine_cfg,
                              shard_pytree(params, specs, mesh), tok, **kw)
            eng.obs_replica = rid
            return EngineBackend(eng)

        replicas.append(Replica(rid, EngineBackend(engine), mesh=mesh,
                                rebuild=_rebuild, layout=layout,
                                kv_layout=kv_layout))
    log.info("built %d engine replicas: %s devices each",
             len(replicas), meshes[0].devices.size if replicas else 0)
    return replicas
