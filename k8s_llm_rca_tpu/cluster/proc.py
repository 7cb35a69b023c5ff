"""Out-of-process replicas: each replica's backend runs in its OWN
interpreter, so a real OS-process death (SIGKILL mid-decode) is finally a
fault the fleet can experience — and survive — in-tree.

Until now every "replica" was an in-process object and the worst a chaos
plan could do was *pretend* a process died (``Replica.wedge``).  This
module makes the fault domain real:

- **worker**: ``python -m k8s_llm_rca_tpu.cluster.proc '<spec-json>'``
  builds one backend (scripted oracle / echo, or a real TINY engine) and
  serves the framed request/response protocol of cluster/wire.py over
  its stdin/stdout pipes.  The parent spawns it with ``PYTHONPATH``
  set to the repo root and ``JAX_PLATFORMS=cpu``: process replicas are
  a CPU-only test fleet today.  A chip belongs to one process at a
  time, so a worker must never reach for the parent's; giving each
  worker its own chip is unbuilt (ROADMAP C6).
- **ProcBackend**: the parent-side proxy presenting the exact
  ``LMBackend`` surface (start/pump/busy/cancel/count_tokens plus the
  queue_depth/occupancy gauges), so ``ClusterRouter`` plugs it in
  unchanged.  Every response frame carries the worker's incarnation and
  a protocol heartbeat; a transport failure (pipe EOF, torn/corrupt
  frame, RPC timeout, nonzero ``poll()``) is recorded as hard death
  EVIDENCE — the proxy goes silent instead of raising into the router,
  and the health watchdog turns silence + evidence into SUSPECT -> DEAD
  (cluster/health.py), never a hang.
- **ProcReplica**: a ``Replica`` whose rebuild recipe spawns a fresh OS
  process (incarnation + 1), so ``ReplicaSupervisor.restart`` restarts
  the *actual process* and rejoins it.  Recovery is journal-fenced at
  two levels: orphaned runs re-start on survivors under their original
  global handles via the router's recorded ``(prompt, opts)`` twin of
  the run journal (``fail_replica`` + ``inject.readmission``), and every
  response frame's incarnation is checked so a stale worker's bytes can
  never be attributed to the new incarnation.

Protocol (one JSON frame per message, cluster/wire.py framing):

  parent -> worker: ``{"op", "id", ...}`` (plus an optional ``trace``
  propagation context when the spec opts into telemetry); worker ->
  parent: ``{"id", "inc", "hb", ...}`` (or ``{"err": {"type",
  "msg"}}``), optionally carrying a piggybacked ``tel`` telemetry
  payload.  Ops: ready (handshake, worker-initiated), ping, start,
  pump, cancel, snapshot, adopt, export_run, adopt_run,
  drain_telemetry, drain.  GenOptions cross the wire as serve/journal.py's
  ``encode_gen`` dicts (grammar as SPEC — compiled FSMs never cross a
  process boundary); engine state crosses as the JSON-safe
  ``snapshot_sequences`` export.

Fault-injection parity (the soak byte-identity contract): the armed
FaultPlan lives in the PARENT, so ProcBackend polls ``SITE_BACKEND`` for
engine-kind workers exactly where ``EngineBackend.start`` would
(budget/error/stall, plus the stalled-run virtual-clock sleep in pump) —
injected runs never reach the worker, mirroring the in-process backend
where they never reach the engine.  Scripted kinds poll NOTHING, exactly
like OracleBackend/EchoBackend, which is why the proc-cluster oracle
soak's report is byte-identical to the in-process cluster-oracle run.
"""

from __future__ import annotations

import itertools
import json
import os
import select
import signal
import socket
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from k8s_llm_rca_tpu.cluster.net import (
    DEFAULT_HANDSHAKE_TIMEOUT_S, PipeTransport, SocketTransport,
    connect_transport,
)
from k8s_llm_rca_tpu.cluster.replica import Replica
from k8s_llm_rca_tpu.cluster.wire import (
    FrameReader, WireEOF, WireError, WireTimeout, write_frame,
)
from k8s_llm_rca_tpu.utils.logging import METRICS, get_logger

log = get_logger(__name__)

# set in every worker's environment; a worker trying to spawn its own
# proc replicas is refused loudly (nested proc-in-proc)
WORKER_ENV = "K8S_RCA_PROC_WORKER"

WORKER_KINDS = ("oracle", "echo", "engine")

# how the parent reaches the worker: the PR 12 stdio pipes, or a TCP
# socket (cluster/net.py) — the cross-host shape, relinkable on link
# failure because a dead SOCKET is not a dead PROCESS
TRANSPORTS = ("pipe", "socket")

# relink attempts (one per router pump) before a down link becomes hard
# death evidence of kind "link" and the respawn path takes over
DEFAULT_RELINK_BUDGET = 3

# engine workers compile their TINY engine before answering the ready
# handshake; scripted workers only pay the import of the serving stack
DEFAULT_SPAWN_TIMEOUT_S = 300.0
DEFAULT_RPC_TIMEOUT_S = 60.0


class WorkerError(RuntimeError):
    """A worker op raised; the error crossed the wire by name/message."""


def _repo_root() -> str:
    """The directory that contains the ``k8s_llm_rca_tpu`` package — the
    ONLY entry the worker's PYTHONPATH gets, so the worker imports this
    checkout and nothing the parent's path happened to carry."""
    import k8s_llm_rca_tpu

    return os.path.dirname(os.path.dirname(
        os.path.abspath(k8s_llm_rca_tpu.__file__)))


def _with_host_device_count(flags: str, n: int) -> str:
    """XLA_FLAGS with --xla_force_host_platform_device_count pinned to
    n, replacing any existing (possibly mismatched) value (the twin of
    ``__graft_entry__._with_host_device_count``: package code must not
    import the top-level driver)."""
    parts = [p for p in flags.split()
             if not p.startswith("--xla_force_host_platform_device_count")]
    parts.append(f"--xla_force_host_platform_device_count={n}")
    return " ".join(parts)


def worker_env(devices: int = 1) -> Dict[str, str]:
    """The spawn environment: the parent's env pinned to the CPU with
    ``devices`` virtual devices.  Workers are a CPU-only test fleet: the
    chip stays with the parent process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _repo_root()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = _with_host_device_count(env.get("XLA_FLAGS", ""),
                                               devices)
    env[WORKER_ENV] = "1"
    return env


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------


def _build_worker_backend(spec: Dict[str, Any]):
    """Build the worker's backend from its spec.  Returns ``(backend,
    heartbeat_fn)`` — the heartbeat is the engine's monotonic tick serial
    for engine workers (so a worker that answers pumps but whose engine
    never advances is still caught) and a per-pump counter otherwise."""
    kind = spec.get("kind", "oracle")
    if kind == "oracle":
        from k8s_llm_rca_tpu.rca.oracle import OracleBackend
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        backend = OracleBackend(get_tokenizer(),
                                chaos=spec.get("oracle_chaos"))
        return backend, None
    if kind == "echo":
        from k8s_llm_rca_tpu.serve.backend import EchoBackend
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        backend = EchoBackend(get_tokenizer(),
                              reply=spec.get("echo_reply"),
                              delay_pumps=int(spec.get("echo_delay_pumps",
                                                       0)))
        return backend, None
    if kind == "engine":
        import jax

        # engine workers are CPU-only (worker_env); say so at CONFIG
        # level too, before any computation
        jax.config.update("jax_platforms", "cpu")

        from k8s_llm_rca_tpu.config import TINY, EngineConfig
        from k8s_llm_rca_tpu.engine import make_engine
        from k8s_llm_rca_tpu.models import llama
        from k8s_llm_rca_tpu.serve.backend import EngineBackend
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        # the soak/cluster TINY shape (faults/soak.py
        # _build_engine_service), one compile bucket, greedy — the
        # identical-replica invariant: every incarnation of every proc
        # replica initializes the same params from the same seed, so a
        # restarted process generates byte-identically to the first
        cfg = TINY.replace(max_seq_len=2560)
        ecfg = EngineConfig(max_batch=4, max_seq_len=2560,
                            prefill_buckets=(2560,),
                            max_new_tokens=96, temperature=0.0,
                            page_size=64, num_pages=168,
                            prefix_cache=False, decode_chunk=16)
        overrides = spec.get("engine_overrides") or {}
        if overrides:
            import dataclasses as _dc

            ecfg = _dc.replace(ecfg, **overrides)
        params = llama.init_params(cfg,
                                   jax.random.PRNGKey(spec.get("seed", 0)))
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        # per-tier weight layout (spec "layout"/"mesh_shape", validated
        # parent-side by build_proc_replicas): the worker builds a
        # data×fsdp×tp mesh over its OWN virtual CPU devices (the spec's
        # "devices" count, pinned by worker_env), rule-shards the params
        # under the shipped SpecLayout, and hands the mesh to the engine
        # for cache/pool placement — same params, same seed, different
        # layout per tier; greedy outputs stay byte-identical (GSPMD
        # committed-input propagation, tests/test_sharding_rules.py).
        mesh_kw: Dict[str, Any] = {}
        layout_d = spec.get("layout")
        mesh_shape = spec.get("mesh_shape") or {}
        if layout_d is not None or mesh_shape:
            from k8s_llm_rca_tpu.config import MeshConfig
            from k8s_llm_rca_tpu.runtime.mesh import build_mesh
            from k8s_llm_rca_tpu.runtime.rules import (
                FSDP_LAYOUT, SpecLayout, TP_LAYOUT, validate_layout,
            )
            from k8s_llm_rca_tpu.runtime.sharding import (
                llama_param_specs, shard_pytree,
            )

            mcfg = MeshConfig(**{k: int(v) for k, v in mesh_shape.items()})
            mesh = build_mesh(mcfg, devices=jax.devices()[:mcfg.n_devices])
            layout = (SpecLayout.from_dict(layout_d)
                      if layout_d is not None
                      else (FSDP_LAYOUT if mcfg.fsdp > 1 else TP_LAYOUT))
            validate_layout(layout, mesh)
            params = shard_pytree(
                params, llama_param_specs(cfg, layout=layout), mesh)
            mesh_kw["tp_mesh"] = mesh
            if mcfg.fsdp > 1:
                mesh_kw["fsdp_mesh"] = mesh
        # cache-fabric attachment (docs/cluster.md "Cache fabric"): a
        # ``store_addr`` [host, port] in the spec dials the shared
        # cross-host StoreServer and plugs it in as the engine's prefix
        # store — the same PrefixStore surface the in-process tiers use,
        # so warm starts / store-backed restores work identically from a
        # worker process.  A dead store degrades every op to a counted
        # cold miss (cluster/store.py failure contract), so worker
        # byte-parity never depends on the fabric's health.
        store = None
        if spec.get("store_addr") is not None:
            from k8s_llm_rca_tpu.cluster.store import RemoteStore

            host, port = spec["store_addr"]
            store = RemoteStore(addr=(str(host), int(port)))
        backend = EngineBackend(make_engine(cfg, ecfg, params, tok,
                                            use_kernel=False,
                                            prefix_store=store,
                                            **mesh_kw))
        return backend, (lambda: int(backend.engine.heartbeat))
    raise ValueError(f"unknown proc worker kind {kind!r}: expected one "
                     f"of {WORKER_KINDS}")


def _result_to_json(res) -> Dict[str, Any]:
    return {"text": res.text, "completion_tokens": res.completion_tokens,
            "prompt_tokens": res.prompt_tokens, "error": res.error,
            "expired": bool(res.expired)}


# telemetry shipping (spec {"trace": true}): the worker buffers completed
# spans / events / TickSamples in a bounded ring and piggybacks up to
# REPLY_BUDGET items on every reply frame; drain ops flush DRAIN_BUDGET
# per turn.  Both budgets keep a reply frame far under
# wire.MAX_FRAME_SIZE; a SIGKILL loses at most the ring (bounded loss).
DEFAULT_TELEMETRY_RING = 4096
TELEMETRY_REPLY_BUDGET = 64
TELEMETRY_DRAIN_BUDGET = 1024


class _WorkerTelemetry:
    """Worker half of telemetry shipping: watches the worker's own
    Tracer for newly-COMPLETED spans (the worker is single-threaded, so
    the span store is a completed prefix between ops), new events, and
    new TickSamples, converts them to wire form, and buffers them in a
    TelemetryRing until a reply frame carries them out."""

    def __init__(self, tracer, ring_capacity: int = DEFAULT_TELEMETRY_RING):
        from k8s_llm_rca_tpu.obs import trace as obs_trace

        self.tracer = tracer
        self.ring = obs_trace.TelemetryRing(ring_capacity)
        self._wire = (obs_trace.span_to_wire, obs_trace.event_to_wire,
                      obs_trace.tick_to_wire)
        self._spans_seen = 0
        self._events_seen = 0
        self._ticks_seen = 0

    def collect(self) -> None:
        span_fn, event_fn, tick_fn = self._wire
        spans = self.tracer.spans
        i = self._spans_seen
        while i < len(spans) and spans[i].t1 is not None:
            self.ring.push(span_fn(spans[i]))
            i += 1
        self._spans_seen = i
        for ev in self.tracer.events[self._events_seen:]:
            self.ring.push(event_fn(ev))
        self._events_seen = len(self.tracer.events)
        delta = self.tracer.timeline.total - self._ticks_seen
        if delta > 0:
            samples = self.tracer.timeline.samples()
            fresh = samples[max(0, len(samples) - delta):]
            # ticks the timeline ring overwrote before we got here are
            # loss too — count them with the ring's own shed
            self.ring.shed += delta - len(fresh)
            for s in fresh:
                self.ring.push(tick_fn(s))
            self._ticks_seen = self.tracer.timeline.total

    def payload(self, budget: int,
                counters: bool = False) -> Optional[Dict[str, Any]]:
        items = self.ring.pop(budget)
        if not items and not counters:
            return None
        p: Dict[str, Any] = {
            "pid": os.getpid(), "items": items,
            "shed": self.ring.shed + self.tracer.dropped,
            "more": len(self.ring) > 0}
        if counters:
            p["counters"] = METRICS.snapshot()
        return p


def _build_worker_telemetry(spec: Dict[str, Any]):
    """Worker tracer + shipping ring when the spec opts in
    (``{"trace": true}``) — the worker tracer runs on a PropagatedClock
    so its spans are stamped in the parent's (possibly virtual)
    timebase, and it is module-activated so the engine's existing
    instrumentation records into it untouched."""
    if not spec.get("trace"):
        return None
    from k8s_llm_rca_tpu.obs import trace as obs_trace

    tracer = obs_trace.Tracer(clock=obs_trace.PropagatedClock())
    obs_trace.activate(tracer)
    return _WorkerTelemetry(
        tracer,
        ring_capacity=int(spec.get("telemetry_ring",
                                   DEFAULT_TELEMETRY_RING)))


def _handle_op(msg: Dict[str, Any], backend, state: Dict[str, Any],
               inc: int, hb) -> Tuple[Dict[str, Any], bool]:
    """One decoded request -> ``(reply, drain)`` — shared by the pipe
    loop and both socket serve loops so every transport speaks the exact
    same op surface.  The reply is hb-stamped; the serve loop that owns
    the link stamps the session nonce (socket modes only).

    When the worker runs a tracer (``state["tel"]``, spec
    ``{"trace": true}``), each handled op is bracketed by a
    ``cluster.proc.serve`` span parented onto the request's propagated
    trace context, and the reply frame piggybacks a bounded telemetry
    payload — shipping rides frames that exist anyway, so it can never
    change a fault draw."""
    from k8s_llm_rca_tpu.serve.journal import decode_gen

    op = msg.get("op")
    reply: Dict[str, Any] = {"id": msg.get("id"), "inc": inc}
    drain = False
    tel = state.get("tel")
    serve_span = None
    if tel is not None:
        ctx = msg.get("trace") or {}
        if "ts" in ctx:
            tel.tracer.clock.advance_to(ctx["ts"])
        serve_span = tel.tracer.begin(
            "cluster.proc.serve", cat="cluster",
            args={"op": op, "trace": ctx.get("id"),
                  "link": ctx.get("parent")})
        if serve_span is not None and ctx.get("parent") is not None:
            # parent onto the PROPAGATED context: the serve span is a
            # worker-side root, so its parent is the parent process's
            # cluster.proc.rpc span (args.link keeps the id visible in
            # the merged trace UI, where X events hide parentage)
            serve_span.parent_id = int(ctx["parent"])
    try:
        if op == "ping":
            reply["ok"] = True
        elif op == "start":
            reply["handle"] = backend.start(msg["prompt"],
                                            decode_gen(msg["gen"]))
        elif op == "pump":
            state["pumps"] += 1
            results = backend.pump()
            reply["results"] = {str(h): _result_to_json(r)
                                for h, r in results.items()}
            # Replica.queue_depth's duck typing, worker-side
            if hasattr(backend, "queue_depth"):
                reply["depth"] = int(backend.queue_depth())
            else:
                reply["depth"] = len(getattr(backend, "_live", None)
                                     or getattr(backend, "_inflight",
                                                ()))
            occ = getattr(backend, "occupancy", None)
            reply["occupancy"] = float(occ()) if occ else 0.0
        elif op == "cancel":
            backend.cancel(int(msg["handle"]))
            reply["ok"] = True
        elif op == "snapshot":
            snap, handles = backend.snapshot_sequences()
            reply["snap"] = snap
            reply["handles"] = handles
        elif op == "adopt":
            opts = [decode_gen(g) for g in msg["gens"]]
            reply["handles"] = backend.adopt_sequences(msg["snap"],
                                                       opts)
        elif op == "export_run":
            # per-run handoff EXPORT (cluster/disagg.py); None frame =
            # not exportable this pump (settled / mid-prefill) — the
            # caller treats that as try-again, not failure
            reply["frame"] = backend.export_run(int(msg["handle"]))
        elif op == "adopt_run":
            # per-run handoff ADOPT: a torn frame raises inside
            # adopt_run and crosses the wire as err (WorkerError
            # parent-side) BEFORE any engine state moved
            reply["handle"] = backend.adopt_run(msg["frame"],
                                                decode_gen(msg["gen"]))
        elif op == "drain_telemetry":
            # explicit flush (parent close() / watchdog relink heal):
            # touches ONLY the telemetry ring — no backend call, no
            # fault-site poll, so shipping can never change a fault draw
            reply["ok"] = True
        elif op == "drain":
            # graceful shutdown: finish nothing, ack, exit 0 — the
            # parent has already migrated/cancelled what it wanted
            reply["ok"] = True
            drain = True
        else:
            raise ValueError(f"unknown wire op {op!r}")
    except Exception as e:                    # noqa: BLE001 — crosses wire
        reply = {"id": msg.get("id"), "inc": inc,
                 "err": {"type": type(e).__name__, "msg": str(e)}}
    if tel is not None:
        # close the serve span BEFORE collecting, so op N's own span is
        # part of the completed prefix and ships in reply N
        tel.tracer.end(serve_span)
        tel.collect()
        big = op in ("drain", "drain_telemetry")
        payload = tel.payload(
            TELEMETRY_DRAIN_BUDGET if big else TELEMETRY_REPLY_BUDGET,
            counters=big)
        if payload is not None:
            reply["tel"] = payload
    reply["hb"] = hb()
    return reply, drain


def _adopt_connection(sock: socket.socket, inc: int, cur_nonce: int, hb,
                      kind: str):
    """Worker half of the link-fencing handshake on one fresh
    connection.  Returns ``(transport, nonce)`` when adopted,
    ``(None, cur_nonce)`` when refused — refusal answers on the NEW
    connection and closes it, leaving any serving link untouched.

    The fencing rule: adopt only a session nonce STRICTLY greater than
    the one currently served.  A stale nonce is a connection the parent
    already superseded (or a partitioned twin of the parent) — refusing
    it here is the no-split-brain half the WORKER owns; the parent owns
    the other half by discarding stale-nonce reply frames."""
    transport = SocketTransport(sock)
    try:
        hello = transport.recv(timeout_s=DEFAULT_HANDSHAKE_TIMEOUT_S)
    except (WireError, OSError):
        transport.close()
        return None, cur_nonce
    nonce = hello.get("nonce")
    if (hello.get("op") != "hello" or hello.get("inc") != inc
            or not isinstance(nonce, int)):
        _refuse(transport, inc, "BadHello",
                f"expected hello(inc={inc}, nonce=int), got {hello!r}")
        return None, cur_nonce
    if nonce <= cur_nonce:
        _refuse(transport, inc, "StaleNonce",
                f"nonce {nonce} <= serving nonce {cur_nonce}: link "
                f"already superseded")
        return None, cur_nonce
    transport.nonce = nonce
    try:
        transport.send({"op": "ready", "id": -1, "inc": inc,
                        "pid": os.getpid(), "kind": kind, "nonce": nonce,
                        "hb": hb()})
    except (WireError, OSError):
        transport.close()
        return None, cur_nonce
    return transport, nonce


def _refuse(transport, inc: int, err_type: str, msg: str) -> None:
    try:
        transport.send({"id": -1, "inc": inc,
                        "err": {"type": err_type, "msg": msg}})
    except (WireError, OSError):
        pass
    transport.close()


def _serve_frames(conn, backend, state: Dict[str, Any], inc: int, hb,
                  corrupt_after, hang_after) -> str:
    """Answer every frame currently available on a readable link (one
    select wakeup can deliver many frames — drain via ``pending()``).
    Returns ``"ok"``, ``"linkdown"`` (the LINK died; the worker keeps
    its state warm for a relink) or ``"drain"`` (exit requested)."""
    try:
        msg = conn.recv(timeout_s=DEFAULT_RPC_TIMEOUT_S)
    except (WireError, OSError):
        return "linkdown"
    while msg is not None:
        state["handled"] += 1
        if corrupt_after is not None and state["handled"] > int(corrupt_after):
            try:
                conn.send_raw(b"\x00garbage-not-a-frame\xff\xfe")
            except (WireError, OSError):
                pass
            os._exit(3)
        if hang_after is not None and state["handled"] > int(hang_after):
            while True:
                time.sleep(3600)
        reply, drain = _handle_op(msg, backend, state, inc, hb)
        reply["nonce"] = conn.nonce
        try:
            conn.send(reply)
        except (WireError, OSError):
            return "linkdown"
        if drain:
            return "drain"
        msg = conn.pending()
    return "ok"


_LEASH_CHUNK = 4096


def _serve_listen(spec: Dict[str, Any], out, backend,
                  state: Dict[str, Any], inc: int, hb) -> int:
    """``--listen`` socket mode: bind loopback (or ``listen_host``),
    announce the port in a ``listening`` bootstrap frame on stdout (the
    ONLY frame stdout ever carries in socket mode), then serve the op
    protocol over whichever connection holds the highest session nonce.

    Link death is NOT worker death: on conn EOF/corruption the worker
    drops that link and keeps accepting, state warm, so the parent can
    relink to the SAME incarnation.  stdin is the lifetime leash — EOF
    there means the parent is gone and the worker exits 0 (a worker
    never outlives its parent, even with no link up)."""
    corrupt_after = spec.get("chaos_corrupt_after")
    hang_after = spec.get("chaos_hang_after")
    # chaos knob for the relink-budget-exhaustion tests: stop accepting
    # (close the listener) after N adopted links, so every further
    # relink dial dies at connect()
    max_accepts = spec.get("chaos_max_accepts")
    kind = spec.get("kind", "oracle")
    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind((spec.get("listen_host", "127.0.0.1"),
                   int(spec.get("listen_port", 0))))
    listener.listen(8)
    port = listener.getsockname()[1]
    write_frame(out, {"op": "listening", "id": -1, "inc": inc,
                      "pid": os.getpid(), "port": port, "kind": kind,
                      "hb": hb()})
    leash = sys.stdin.buffer
    conn = None                   # link serving the highest nonce
    nonce = 0
    adopted = 0
    try:
        while True:
            rlist = [leash]
            if listener is not None:
                rlist.append(listener)
            if conn is not None:
                rlist.append(conn)
            readable, _, _ = select.select(rlist, [], [])
            if leash in readable:
                if not os.read(leash.fileno(), _LEASH_CHUNK):
                    return 0      # parent went away
            if listener is not None and listener in readable:
                fresh, _ = listener.accept()
                transport, nonce = _adopt_connection(fresh, inc, nonce,
                                                     hb, kind)
                if transport is not None:
                    if conn is not None:
                        # no split-brain: at most one live link per
                        # worker — the newer nonce drops the old
                        # connection the instant it is adopted
                        conn.close()
                    conn = transport
                    adopted += 1
                    if (max_accepts is not None
                            and adopted >= int(max_accepts)):
                        listener.close()
                        listener = None
                    continue      # re-select: old conn is gone
            if conn is not None and conn in readable:
                verdict = _serve_frames(conn, backend, state, inc, hb,
                                        corrupt_after, hang_after)
                if verdict == "drain":
                    return 0
                if verdict == "linkdown":
                    conn.close()
                    conn = None
    finally:
        if conn is not None:
            conn.close()
        if listener is not None:
            listener.close()


def _serve_connect(spec: Dict[str, Any], peer: Tuple[str, int], backend,
                   state: Dict[str, Any], inc: int, hb) -> int:
    """``--connect`` socket mode: the cross-host inversion where the
    WORKER dials a listening parent (NAT/firewall-friendly) and serves
    the identical fenced protocol — the parent still initiates the
    ``hello``/nonce, so the fencing rule is direction-agnostic.  On link
    death the worker re-dials (the relink initiative flips sides with
    the dial direction), giving up after ``connect_retries`` consecutive
    failures; stdin EOF still exits."""
    corrupt_after = spec.get("chaos_corrupt_after")
    hang_after = spec.get("chaos_hang_after")
    kind = spec.get("kind", "oracle")
    retries = int(spec.get("connect_retries", 3))
    leash = sys.stdin.buffer
    nonce = 0
    failures = 0
    while True:
        try:
            sock = socket.create_connection(
                peer, timeout=DEFAULT_HANDSHAKE_TIMEOUT_S)
            sock.settimeout(None)
        except OSError:
            failures += 1
            if failures > retries:
                return 1
            time.sleep(0.05 * failures)
            continue
        conn, nonce = _adopt_connection(sock, inc, nonce, hb, kind)
        if conn is None:
            failures += 1
            if failures > retries:
                return 1
            continue
        failures = 0
        try:
            while conn is not None:
                readable, _, _ = select.select([leash, conn], [], [])
                if leash in readable:
                    if not os.read(leash.fileno(), _LEASH_CHUNK):
                        return 0
                if conn is not None and conn in readable:
                    verdict = _serve_frames(conn, backend, state, inc,
                                            hb, corrupt_after,
                                            hang_after)
                    if verdict == "drain":
                        return 0
                    if verdict == "linkdown":
                        conn.close()
                        conn = None
        finally:
            if conn is not None:
                conn.close()


def worker_main(argv: Sequence[str]) -> int:
    """Serve the wire protocol until a drain frame or stdin EOF.

    The real stdout fd is claimed for frames FIRST and ``sys.stdout`` is
    repointed at stderr, so a stray ``print`` anywhere in the serving
    stack garbles a log line instead of a frame.

    Modes: bare ``'<spec-json>'`` serves over the stdio pipes (PR 12,
    byte-identical); ``--listen '<spec-json>'`` binds a TCP listener and
    announces the port on stdout; ``--connect HOST:PORT '<spec-json>'``
    dials a listening parent.  Both socket modes serve the same framed
    protocol with session-nonce link fencing (cluster/net.py).
    """
    out = sys.stdout.buffer
    sys.stdout = sys.stderr
    args = list(argv)
    mode = "pipe"
    peer: Optional[Tuple[str, int]] = None
    if args and args[0] == "--listen":
        mode = "listen"
        args = args[1:]
    elif args and args[0] == "--connect":
        if len(args) < 2 or ":" not in args[1]:
            raise SystemExit(
                "usage: python -m k8s_llm_rca_tpu.cluster.proc "
                "--connect HOST:PORT '<spec-json>'")
        host, _, port = args[1].rpartition(":")
        peer = (host, int(port))
        mode = "connect"
        args = args[2:]
    if len(args) != 1:
        raise SystemExit("usage: python -m k8s_llm_rca_tpu.cluster.proc "
                         "[--listen | --connect HOST:PORT] '<spec-json>'")
    spec = json.loads(args[0])
    inc = int(spec.get("incarnation", 0))
    # chaos knobs for the wire-failure tests: after N handled requests,
    # corrupt the stream (garbage bytes, hard exit) or go silent forever
    # (the missed-protocol-heartbeat path) — deterministic, no signals
    corrupt_after = spec.get("chaos_corrupt_after")
    hang_after = spec.get("chaos_hang_after")

    backend, hb_fn = _build_worker_backend(spec)
    state: Dict[str, Any] = {"pumps": 0, "handled": 0,
                             "tel": _build_worker_telemetry(spec)}

    def hb() -> int:
        return hb_fn() if hb_fn is not None else state["pumps"]

    if mode == "listen":
        return _serve_listen(spec, out, backend, state, inc, hb)
    if mode == "connect":
        return _serve_connect(spec, peer, backend, state, inc, hb)

    write_frame(out, {"op": "ready", "id": -1, "inc": inc, "pid": os.getpid(),
                      "kind": spec.get("kind", "oracle"), "hb": hb()})
    reader = FrameReader(sys.stdin.buffer)
    while True:
        try:
            msg = reader.read_frame()
        except WireEOF:
            return 0      # parent went away: a worker never outlives it
        state["handled"] += 1
        if corrupt_after is not None and state["handled"] > int(corrupt_after):
            out.write(b"\x00garbage-not-a-frame\xff\xfe")
            out.flush()
            os._exit(3)
        if hang_after is not None and state["handled"] > int(hang_after):
            while True:
                time.sleep(3600)
        reply, drain = _handle_op(msg, backend, state, inc, hb)
        write_frame(out, reply)
        if drain:
            return 0
    return 0


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------


class ProcBackend:
    """Parent-side proxy for one worker process (LMBackend surface).

    Local (synthetic, NEGATIVE) handles exist for runs that never reach
    the worker: injected-failed/stalled engine-kind runs (the parent
    polls the armed plan, mirroring EngineBackend.start) and runs routed
    here after the process died but before the watchdog's verdict
    (black-holed — exactly like a request on the wire to a dead box; the
    failover re-start under the same global handle recovers it).
    """

    def __init__(self, spec: Dict[str, Any],
                 spawn_timeout_s: float = DEFAULT_SPAWN_TIMEOUT_S,
                 rpc_timeout_s: float = DEFAULT_RPC_TIMEOUT_S):
        from k8s_llm_rca_tpu.obs import trace as obs_trace
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        self.spec = dict(spec)
        self.kind = self.spec.get("kind", "oracle")
        if self.kind not in WORKER_KINDS:
            raise ValueError(f"unknown proc worker kind {self.kind!r}: "
                             f"expected one of {WORKER_KINDS}")
        self.incarnation = int(self.spec.get("incarnation", 0))
        self.replica_id = int(self.spec.get("replica_id", 0))
        self.rpc_timeout_s = rpc_timeout_s
        self.transport_kind = self.spec.get("transport", "pipe")
        if self.transport_kind not in TRANSPORTS:
            raise ValueError(
                f"unknown proc transport {self.transport_kind!r}: "
                f"expected one of {TRANSPORTS}")
        self.relink_budget = int(self.spec.get("relink_budget",
                                               DEFAULT_RELINK_BUDGET))
        if self.relink_budget < 1:
            raise ValueError(
                f"relink_budget must be >= 1, got {self.relink_budget}: "
                f"a zero budget makes every link blip process death, "
                f"which is the pipe transport's semantics — use "
                f"transport='pipe' instead")
        # session-nonce link fencing (socket transports): monotonic per
        # connection; the worker adopts only strictly-greater nonces
        self._nonce = 0
        self.relinks = 0
        self.relink_attempts = 0
        self._link_evidence: Optional[str] = None
        # evidence kind for health.hard_kinds: "proc" (death observed /
        # inferred at the process) vs "link" (relink budget exhausted)
        self.death_kind: Optional[str] = None
        self._transport = None
        self._port: Optional[int] = None
        self._ids = itertools.count()
        # parent-side run mirror: handle -> True (remote) / False (local)
        self._live: Dict[int, bool] = {}
        self._local_handles = itertools.count(-1, -1)
        self._failed: Dict[int, str] = {}     # injected run failures
        self._stalled: set = set()            # injected stalls
        self._dead_evidence: Optional[str] = None
        self._occupancy = 0.0
        self.last_heartbeat: Optional[int] = None
        self.rpcs = 0
        self.spawn_s: Optional[float] = None
        # fleet flight recorder (spec {"trace": true}): outbound frames
        # carry the active tracer's propagation context; reply frames
        # carry back worker telemetry, ingested into the tracer's
        # remote store keyed (replica_id, incarnation)
        self.telemetry = bool(self.spec.get("trace"))
        self.telemetry_frames = 0
        self.telemetry_items = 0
        self._tel_more = False
        if self.kind == "engine":
            # count_tokens stays parent-side (one RPC per usage line
            # would dominate the protocol); the tokenizer is the
            # deterministic byte-fallback one, so parent and worker
            # counts agree exactly
            from k8s_llm_rca_tpu.config import TINY

            self._tokenizer = get_tokenizer(vocab_size=TINY.vocab_size)
            # drain/adopt seam, bound per-kind so ``hasattr`` keeps the
            # router's scripted-replica drain refusal intact; the
            # per-run handoff seam (cluster/disagg.py) follows the same
            # pattern — TierRouter detects it with hasattr too
            self.snapshot_sequences = self._snapshot_sequences
            self.adopt_sequences = self._adopt_sequences
            self.export_run = self._export_run
            self.adopt_run = self._adopt_run
        else:
            self._tokenizer = get_tokenizer()
        t0 = time.perf_counter()
        with obs_trace.span("cluster.proc.spawn", cat="cluster",
                            replica=self.replica_id, kind=self.kind,
                            incarnation=self.incarnation):
            argv = [sys.executable, "-m", "k8s_llm_rca_tpu.cluster.proc"]
            if self.transport_kind == "socket":
                argv.append("--listen")
            argv.append(json.dumps(self.spec, sort_keys=True))
            self._proc = subprocess.Popen(
                argv,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL,
                env=worker_env(int(self.spec.get("devices", 1))))
            if self.transport_kind == "pipe":
                self._transport = PipeTransport(self._proc.stdin,
                                                self._proc.stdout)
                try:
                    ready = self._transport.recv(timeout_s=spawn_timeout_s)
                except WireError as e:
                    rc = self._proc.poll()
                    self._reap()
                    raise WorkerError(
                        f"proc replica {self.replica_id} worker failed "
                        f"its ready handshake (rc={rc}): {e}") from e
                if (ready.get("op") != "ready"
                        or ready.get("inc") != self.incarnation):
                    self._reap()
                    raise WorkerError(
                        f"proc replica {self.replica_id}: bad ready "
                        f"frame {ready!r}")
            else:
                # socket bootstrap: the worker's only stdout frame
                # announces its port; stdin stays open afterwards as
                # the worker's lifetime leash (EOF there = parent gone)
                boot_reader = FrameReader(self._proc.stdout)
                try:
                    boot = boot_reader.read_frame(
                        timeout_s=spawn_timeout_s)
                except WireError as e:
                    rc = self._proc.poll()
                    self._reap()
                    raise WorkerError(
                        f"proc replica {self.replica_id} worker failed "
                        f"its listening bootstrap (rc={rc}): {e}") from e
                if (boot.get("op") != "listening"
                        or boot.get("inc") != self.incarnation):
                    self._reap()
                    raise WorkerError(
                        f"proc replica {self.replica_id}: bad listening "
                        f"frame {boot!r}")
                self._port = int(boot["port"])
                try:
                    ready = self._connect()
                except (WireError, OSError) as e:
                    self._reap()
                    raise WorkerError(
                        f"proc replica {self.replica_id} worker refused "
                        f"the fenced connect on port {self._port}: {e}"
                    ) from e
        self.pid = int(ready["pid"])
        self.last_heartbeat = ready.get("hb")
        self.spawn_s = time.perf_counter() - t0
        METRICS.inc("cluster.proc_spawns")
        log.info("proc replica %d: %s worker pid %d up (incarnation %d, "
                 "%.2fs)", self.replica_id, self.kind, self.pid,
                 self.incarnation, self.spawn_s)

    # ------------------------------------------------------------ transport

    def _mark_dead(self, evidence: str) -> None:
        if self._dead_evidence is None:
            rc = self._proc.poll()
            if rc is not None:
                evidence = f"{evidence}; exit:{rc}"
            self._dead_evidence = evidence
            if self.death_kind is None:
                self.death_kind = "proc"
            METRICS.inc("cluster.proc_deaths_observed")
            log.warning("proc replica %d: transport down (%s)",
                        self.replica_id, evidence)

    def proc_liveness(self) -> Optional[str]:
        """Hard death evidence, or None while the process looks alive.
        Checks the OS first (``poll()`` sees a SIGKILL before any RPC
        does) — this is the signal the watchdog's hard-evidence path
        escalates on (pipe EOF / exit code, not just wedged ticks)."""
        if self._dead_evidence is not None:
            return self._dead_evidence
        rc = self._proc.poll()
        if rc is not None:
            self._mark_dead("process exited")
            return self._dead_evidence
        return None

    def _connect(self) -> Dict[str, Any]:
        """Dial the worker's listener and fence a fresh link under the
        NEXT session nonce.  Replaces (and closes) any previous
        transport only AFTER the handshake succeeds, so a failed relink
        attempt leaves the evidence state untouched.  The nonce burns
        even on failure — monotonicity is all the fence needs."""
        self._nonce += 1
        transport, ready = connect_transport(
            "127.0.0.1", self._port, self.incarnation, self._nonce,
            timeout_s=min(self.rpc_timeout_s, DEFAULT_HANDSHAKE_TIMEOUT_S),
            write_timeout_s=self.rpc_timeout_s)
        old, self._transport = self._transport, transport
        if old is not None:
            old.close()
        if ready.get("hb") is not None:
            self.last_heartbeat = int(ready["hb"])
        return ready

    def _mark_link_down(self, evidence: str) -> None:
        """Record LINK evidence: ``poll()`` just said the process is
        alive, only the socket between us died.  The router's relink
        path consumes this; it never feeds the watchdog's hard-death
        escalation until the relink budget is exhausted."""
        if (self._dead_evidence is not None
                or self._link_evidence is not None):
            return
        from k8s_llm_rca_tpu.obs import trace as obs_trace

        self._link_evidence = evidence
        if self._transport is not None:
            self._transport.close()
        METRICS.inc("cluster.net_link_downs")
        obs_trace.event("cluster.net.partition", replica=self.replica_id,
                        incarnation=self.incarnation, nonce=self._nonce,
                        evidence=evidence)
        log.warning("proc replica %d: LINK down, process alive (%s)",
                    self.replica_id, evidence)

    def link_liveness(self) -> Optional[str]:
        """Link-down evidence, or None while the link is up.  Proc
        evidence outranks link evidence — callers (router pump, health
        probe) check ``proc_liveness`` first."""
        return self._link_evidence

    def relink(self) -> bool:
        """Reconnect a down link to the SAME incarnation under a fresh
        session nonce.  Returns True when the link is (now) up.  Budget
        exhaustion converts the outage into hard death evidence of kind
        "link", handing the watchdog/supervisor respawn path the
        replica — 'not DEAD until the relink budget is exhausted'."""
        from k8s_llm_rca_tpu.obs import trace as obs_trace

        if self._dead_evidence is not None:
            return False
        if self.transport_kind != "socket":
            return False
        if self._proc.poll() is not None:
            self._mark_dead("process exited")
            return False
        if self._link_evidence is None:
            return True
        self.relink_attempts += 1
        try:
            self._connect()
        except (WireError, OSError) as e:
            if self.relink_attempts >= self.relink_budget:
                self.death_kind = "link"
                self._mark_dead(
                    f"relink budget exhausted "
                    f"({self.relink_attempts}/{self.relink_budget} "
                    f"attempts): {type(e).__name__}: {e}")
            return False
        healed = self._link_evidence
        self._link_evidence = None
        self.relink_attempts = 0
        self.relinks += 1
        METRICS.inc("cluster.net_relinks")
        obs_trace.event("cluster.net.relink", replica=self.replica_id,
                        incarnation=self.incarnation, nonce=self._nonce,
                        healed=healed)
        log.warning("proc replica %d: relinked (incarnation %d, nonce "
                    "%d) after %s", self.replica_id, self.incarnation,
                    self._nonce, healed)
        return True

    def drop_link(self, halfopen: bool = False) -> None:
        """Sever the parent side of the link WITHOUT touching the
        process — the killer's partition/halfopen fault.  Full partition
        closes the socket (both directions die); halfopen shuts only our
        receive direction (sends still flow), so the failure surfaces as
        the reply that never arrives (``WireTimeout``/EOF), not a send
        error."""
        if self.transport_kind != "socket":
            raise ValueError(
                f"proc replica {self.replica_id}: cannot partition a "
                f"{self.transport_kind!r} transport — a pipe to a child "
                f"cannot die without the child dying (spawn with "
                f"transport='socket')")
        if self._transport is None:
            return
        if halfopen:
            self._transport.shutdown_read()
        else:
            self._transport.close()
        METRICS.inc("cluster.net_partitions")
        log.warning("proc replica %d: link %s injected (nonce %d)",
                    self.replica_id,
                    "half-open" if halfopen else "partition",
                    self._nonce)

    def replayable(self, handle: int) -> bool:
        """Whether a relink replay may re-start this handle: injected
        failed/stalled runs settle locally — replaying them would erase
        their injected outcomes and break soak byte-identity."""
        return handle not in self._failed and handle not in self._stalled

    def link_stats(self) -> Optional[Dict[str, Any]]:
        """Per-link gauges for obs/export.py (socket transports only)."""
        if self.transport_kind != "socket":
            return None
        alive = (self._link_evidence is None
                 and self._dead_evidence is None)
        return {"nonce": self._nonce, "alive": 1 if alive else 0,
                "relinks": self.relinks}

    def _recv_reply(self, req: Dict[str, Any], timeout_s: float
                    ) -> Dict[str, Any]:
        """Receive the reply to ``req`` under ONE overall deadline.

        Pipe mode returns the next frame — the transport is lockstep by
        construction, so any mismatch downstream is a protocol desync.
        Socket mode tolerates what a network can legally do to a fenced
        link: frames tagged with a stale session nonce (a link this
        parent already abandoned) and duplicate deliveries of already-
        consumed ids (netem ``duplicate``) are DISCARDED, never desync
        evidence; a FUTURE id is still a breach."""
        if self.transport_kind != "socket":
            return self._transport.recv(timeout_s=timeout_s)
        deadline = time.monotonic() + timeout_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise WireTimeout(
                    f"no current-nonce reply to {req['op']} id "
                    f"{req['id']} within {timeout_s}s")
            resp = self._transport.recv(timeout_s=remaining)
            rnonce = resp.get("nonce")
            if rnonce != self._nonce:
                METRICS.inc("cluster.net_stale_replies_discarded")
                log.info("proc replica %d: discarded stale-nonce reply "
                         "(%r != %d)", self.replica_id, rnonce,
                         self._nonce)
                continue
            rid = resp.get("id")
            if isinstance(rid, int) and rid < req["id"]:
                METRICS.inc("cluster.net_dup_replies_discarded")
                continue
            return resp

    def _rpc(self, op: str, timeout_s: Optional[float] = None,
             **fields) -> Dict[str, Any]:
        """One request/response turn.  Raises WorkerError for an error
        the WORKER reported; raises WireError/OSError for transport
        death AFTER recording the evidence (callers on the router path
        catch and go silent; the watchdog owns the verdict).  On a
        socket transport, a wire failure with a LIVE ``poll()`` records
        link evidence instead — relink territory, not respawn."""
        from k8s_llm_rca_tpu.obs import trace as obs_trace
        from k8s_llm_rca_tpu.serve.backend import BudgetError

        if self._dead_evidence is not None:
            raise WireEOF(f"proc replica {self.replica_id} transport "
                          f"already down: {self._dead_evidence}")
        if self._link_evidence is not None:
            raise WireTimeout(
                f"proc replica {self.replica_id} link down (awaiting "
                f"relink): {self._link_evidence}")
        req = dict(fields)
        req["op"] = op
        req["id"] = next(self._ids)
        effective = (timeout_s if timeout_s is not None
                     else self.rpc_timeout_s)
        with obs_trace.span("cluster.proc.rpc", cat="cluster", op=op,
                            replica=self.replica_id) as rpc_span:
            tr = obs_trace.active()
            if self.telemetry and tr is not None:
                # span-context propagation: the worker's serve span
                # parents onto THIS rpc span, so one run's tree spans
                # router -> wire -> worker engine ticks
                req["trace"] = tr.context(parent=rpc_span)
            try:
                self._transport.send(req, timeout_s=effective)
                resp = self._recv_reply(req, effective)
            except (WireError, OSError, ValueError) as e:
                # ValueError: write to a pipe closed mid-Popen teardown
                if (self.transport_kind == "socket"
                        and self._proc.poll() is None):
                    self._mark_link_down(
                        f"{op} rpc failed: {type(e).__name__}: {e}")
                else:
                    self._mark_dead(
                        f"{op} rpc failed: {type(e).__name__}: {e}")
                raise
        self.rpcs += 1
        if resp.get("inc") != self.incarnation:
            # incarnation fence: bytes from a stale worker must never be
            # attributed to this incarnation's runs
            self._mark_dead(
                f"fenced: response incarnation {resp.get('inc')!r} != "
                f"{self.incarnation}")
            raise WireEOF(self._dead_evidence)
        if resp.get("id") != req["id"]:
            self._mark_dead(
                f"protocol desync: response id {resp.get('id')!r} != "
                f"{req['id']}")
            raise WireEOF(self._dead_evidence)
        if resp.get("hb") is not None:
            self.last_heartbeat = int(resp["hb"])
        tel = resp.get("tel")
        if tel is not None:
            # past both fences: this payload provably belongs to this
            # incarnation's worker
            self._ingest_telemetry(tel)
        err = resp.get("err")
        if err is not None:
            if err.get("type") == "BudgetError":
                raise BudgetError(err.get("msg", ""))
            raise WorkerError(
                f"proc replica {self.replica_id} worker {op} failed: "
                f"{err.get('type')}: {err.get('msg')}")
        return resp

    def _ingest_telemetry(self, payload: Dict[str, Any]) -> None:
        from k8s_llm_rca_tpu.obs import trace as obs_trace

        self._tel_more = bool(payload.get("more"))
        tr = obs_trace.active()
        if tr is None:
            return
        n = tr.ingest_remote(self.replica_id, self.incarnation, payload)
        self.telemetry_frames += 1
        self.telemetry_items += n
        if n:
            obs_trace.event("cluster.telemetry.ship",
                            replica=self.replica_id,
                            incarnation=self.incarnation, items=n)

    def drain_telemetry(self, max_frames: int = 64) -> int:
        """Flush the worker's remaining buffered telemetry with
        dedicated ``drain_telemetry`` ops (each polls NO fault sites).
        Called by ``close()`` and by the router's relink-heal path; a
        transport failure mid-drain is swallowed — the at-most-bounded-
        loss contract already covers whatever stayed in the ring.
        Returns the number of items recovered this flush."""
        from k8s_llm_rca_tpu.obs import trace as obs_trace

        if not self.telemetry:
            return 0
        before = self.telemetry_items
        if (self.proc_liveness() is None
                and self.link_liveness() is None):
            for _ in range(max_frames):
                try:
                    self._rpc("drain_telemetry")
                except (WireError, OSError, WorkerError):
                    break
                if not self._tel_more:
                    break
        n = self.telemetry_items - before
        obs_trace.event("cluster.telemetry.drain",
                        replica=self.replica_id,
                        incarnation=self.incarnation, items=n)
        return n

    # -------------------------------------------------------------- backend

    def start(self, prompt: str, opts) -> int:
        from k8s_llm_rca_tpu.faults import inject
        from k8s_llm_rca_tpu.serve.backend import BudgetError
        from k8s_llm_rca_tpu.serve.journal import encode_gen

        if self.kind == "engine":
            # the armed plan lives in THIS process: poll exactly where
            # EngineBackend.start would, so injected runs never reach the
            # worker (and the plan's poll counters match the in-process
            # cluster run draw for draw)
            fault = None
            if inject._ARMED is not None:
                fault = inject._ARMED.poll(inject.SITE_BACKEND)
            if fault is not None and fault.kind == "budget":
                raise BudgetError(
                    f"injected budget fault at {fault.site}[{fault.index}]: "
                    f"no valid output exists under this budget")
            if fault is not None and fault.kind == "error":
                handle = next(self._local_handles)
                self._failed[handle] = (
                    f"injected engine-run failure at "
                    f"{fault.site}[{fault.index}]")
                self._live[handle] = False
                return handle
            if fault is not None and fault.kind == "stall":
                handle = next(self._local_handles)
                self._stalled.add(handle)
                self._live[handle] = False
                return handle
        if self.proc_liveness() is not None:
            # routed here between the process death and the watchdog's
            # verdict: black-hole the run like a request on the wire to
            # a dead box — the failover re-start (same global handle)
            # recovers it on a survivor
            handle = next(self._local_handles)
            self._live[handle] = False
            return handle
        try:
            resp = self._rpc("start", prompt=prompt, gen=encode_gen(opts))
        except (WireError, OSError):
            handle = next(self._local_handles)
            self._live[handle] = False
            return handle
        handle = int(resp["handle"])
        self._live[handle] = True
        return handle

    def pump(self) -> Dict[int, Any]:
        from k8s_llm_rca_tpu.faults import inject
        from k8s_llm_rca_tpu.serve.backend import BackendResult

        results: Dict[int, BackendResult] = {}
        for handle in list(self._failed):
            msg = self._failed.pop(handle)
            if self._live.pop(handle, None) is not None:
                results[handle] = BackendResult("", 0, error=msg)
        if self._stalled and inject._ARMED is not None:
            # EngineBackend.pump's deterministic-deadline discipline: a
            # stalled run ends only via the serve deadline, which must
            # arrive after a fixed number of pumps, not wall seconds
            inject._ARMED.clock.sleep(0.05)
        if self.proc_liveness() is not None:
            return results
        try:
            resp = self._rpc("pump")
        except (WireError, OSError):
            return results
        self._occupancy = float(resp.get("occupancy", 0.0))
        for h_str, r in resp.get("results", {}).items():
            handle = int(h_str)
            if self._live.pop(handle, None) is None:
                continue          # settled after a local cancel: drop
            results[handle] = BackendResult(
                text=r["text"], completion_tokens=r["completion_tokens"],
                prompt_tokens=r.get("prompt_tokens"),
                error=r.get("error"), expired=bool(r.get("expired")))
        return results

    def busy(self, handle: int) -> bool:
        return handle in self._live

    def cancel(self, handle: int) -> None:
        remote = self._live.pop(handle, None)
        self._failed.pop(handle, None)
        self._stalled.discard(handle)
        if not remote or self.proc_liveness() is not None:
            return
        try:
            self._rpc("cancel", handle=handle)
        except (WireError, OSError):
            pass          # dying worker: its state is gone anyway

    def count_tokens(self, text: str) -> int:
        return self._tokenizer.count(text)

    def queue_depth(self) -> int:
        return len(self._live)

    def occupancy(self) -> float:
        return self._occupancy if self.kind == "engine" else 0.0

    def proc_stats(self) -> Dict[str, Any]:
        """Per-process gauges for obs/export.py prometheus_text."""
        return {"pid": self.pid, "incarnation": self.incarnation,
                "alive": 0 if self.proc_liveness() is not None else 1,
                "rpcs": self.rpcs}

    # ------------------------------------------- drain/adopt seam (engine)

    def _snapshot_sequences(self) -> Tuple[Dict[str, Any], List[int]]:
        resp = self._rpc("snapshot")
        return resp["snap"], [int(h) for h in resp["handles"]]

    def _adopt_sequences(self, snap: Dict[str, Any],
                         opts: Sequence[Any]) -> List[int]:
        from k8s_llm_rca_tpu.serve.journal import encode_gen

        resp = self._rpc("adopt", snap=snap,
                         gens=[encode_gen(o) for o in opts])
        handles = [int(h) for h in resp["handles"]]
        for h in handles:
            self._live[h] = True
        return handles

    def _export_run(self, handle: int) -> Optional[Dict[str, Any]]:
        """Per-run EXPORT over the wire (cluster/disagg.py).  A handle
        that is parent-local (injected fault) or no longer live exports
        as None — the run settled between pumps, which is a self-clean
        for the handoff queue, never a retry."""
        if handle < 0 or not self._live.get(handle, False):
            return None
        resp = self._rpc("export_run", handle=handle)
        return resp.get("frame")

    def _adopt_run(self, frame: Dict[str, Any], opts: Any) -> int:
        """Per-run ADOPT over the wire: the worker validates the whole
        frame before touching engine state; a torn frame surfaces here
        as WorkerError(ValueError) with nothing adopted.  The reply
        rides the incarnation(+nonce) fence like every RPC — a late ack
        from a dead incarnation can never register a handle."""
        from k8s_llm_rca_tpu.serve.journal import encode_gen

        resp = self._rpc("adopt_run", frame=frame, gen=encode_gen(opts))
        handle = int(resp["handle"])
        self._live[handle] = True
        return handle

    # ------------------------------------------------------------ lifecycle

    def kill(self) -> None:
        """Real SIGKILL — the ProcKiller fault path.  No teardown, no
        cleanup: the point is that the parent finds out the hard way."""
        try:
            os.kill(self.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self._proc.wait()         # reap immediately; poll() now has rc

    def close(self, timeout_s: float = 5.0) -> None:
        """Graceful shutdown: drain frame -> bounded wait -> TERM ->
        KILL.  Idempotent; never raises over a corpse."""
        from k8s_llm_rca_tpu.obs import trace as obs_trace

        if self._proc.poll() is None and self._dead_evidence is None:
            if self._link_evidence is not None:
                # no link to carry the drain frame: drop the stdin leash
                # instead — the worker exits 0 on leash EOF
                try:
                    self._proc.stdin.close()
                except OSError:
                    pass
            else:
                if self.telemetry:
                    # last flush before the worker exits — the drain
                    # reply below carries one more big payload too
                    self.drain_telemetry()
                try:
                    self._rpc("drain", timeout_s=timeout_s)
                except (WireError, OSError, WorkerError):
                    pass
            try:
                self._proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self._proc.terminate()
                try:
                    self._proc.wait(timeout=timeout_s)
                except subprocess.TimeoutExpired:
                    self._proc.kill()
        self._reap()
        obs_trace.event("cluster.proc.exit", replica=self.replica_id,
                        rc=self._proc.poll(),
                        incarnation=self.incarnation)

    def _reap(self) -> None:
        try:
            if self._proc.poll() is None:
                self._proc.kill()
            self._proc.wait()
        except Exception:         # noqa: BLE001 — teardown best-effort
            pass
        if self._transport is not None:
            self._transport.close()
        for stream in (self._proc.stdin, self._proc.stdout):
            try:
                if stream is not None:
                    stream.close()
            except OSError:
                pass


class ProcReplica(Replica):
    """A ``Replica`` whose backend lives in its own OS process.

    Presents the exact Replica surface (so ClusterRouter and the
    watchdog plug in unchanged) plus:

    - ``proc_liveness()``: hard death evidence the router's pump skip
      and the watchdog's hard-evidence escalation consume;
    - ``kill_process()``: deliver a real SIGKILL (the ProcKiller path);
    - ``close()``: the graceful drain -> TERM -> KILL ladder;
    - a ``rebuild`` recipe that spawns a FRESH process at incarnation+1
      — ``ReplicaSupervisor.restart`` therefore restarts the actual OS
      process and rejoins it, with the old corpse reaped first.
    """

    def __init__(self, replica_id: int, kind: str = "oracle",
                 spawn_timeout_s: float = DEFAULT_SPAWN_TIMEOUT_S,
                 rpc_timeout_s: float = DEFAULT_RPC_TIMEOUT_S,
                 **spec: Any):
        if os.environ.get(WORKER_ENV):
            raise ValueError(
                "nested proc-in-proc: a proc worker must not spawn its "
                "own proc replicas (one process boundary per replica; "
                "compose scale with more replicas, not deeper trees)")
        spec = dict(spec, kind=kind, replica_id=replica_id)
        spec.setdefault("incarnation", 0)
        backend = ProcBackend(spec, spawn_timeout_s=spawn_timeout_s,
                              rpc_timeout_s=rpc_timeout_s)

        def _rebuild() -> ProcBackend:
            old = self.backend
            if isinstance(old, ProcBackend):
                old._reap()       # never leak the corpse's pipes/zombie
                next_inc = old.incarnation + 1
            else:
                next_inc = 1
            return ProcBackend(dict(spec, incarnation=next_inc),
                               spawn_timeout_s=spawn_timeout_s,
                               rpc_timeout_s=rpc_timeout_s)

        super().__init__(replica_id, backend, mesh=None, rebuild=_rebuild)

    def healthy(self) -> bool:
        return (super().healthy()
                and self.backend.proc_liveness() is None
                and self.backend.link_liveness() is None)

    def proc_liveness(self) -> Optional[str]:
        return self.backend.proc_liveness()

    def link_liveness(self) -> Optional[str]:
        return self.backend.link_liveness()

    def relink(self) -> bool:
        return self.backend.relink()

    def partition_link(self, halfopen: bool = False) -> None:
        self.backend.drop_link(halfopen=halfopen)

    @property
    def supports_relink(self) -> bool:
        return self.backend.transport_kind == "socket"

    def evidence_kind(self) -> str:
        """``"link"`` when the death verdict came from relink-budget
        exhaustion, ``"proc"`` otherwise (health.hard_kinds)."""
        return self.backend.death_kind or "proc"

    def kill_process(self) -> None:
        self.backend.kill()

    def close(self, timeout_s: float = 5.0) -> None:
        self.backend.close(timeout_s=timeout_s)


def build_proc_replicas(n_replicas: int, kind: str = "oracle",
                        **spec: Any) -> List[ProcReplica]:
    """N out-of-process replicas of one kind.

    ``transport="socket"`` in the spec puts each worker behind a TCP
    loopback listener with session-nonce link fencing (the cross-host
    shape; link death relinks instead of respawning); the default
    ``"pipe"`` keeps the PR 12 stdio protocol byte-identical.

    Loud exclusions (repo convention): proc replicas compose with the
    router/watchdog/supervisor stack, NOT with cross-worker sharding —
    a worker owns its whole engine, so CP/PP/mesh arguments are
    rejected here instead of failing deep in a worker.

    ``layout`` (a ``runtime.rules.SpecLayout`` or its ``to_dict`` form)
    plus ``mesh_shape`` (axis-size dict over data/fsdp/model) give each
    ENGINE worker a per-tier weight layout over its own virtual CPU
    devices: the worker builds the mesh, rule-shards the shared-seed
    params under the layout, and places its KV pool accordingly — the
    proc-fleet face of the per-tier layouts ``build_replicas`` offers
    in-process.  Validated HERE (typo'd axes, non-engine kinds,
    device-count mismatches, fsdp layouts without an fsdp axis) so a
    bad spec fails in the parent, not as a worker spawn corpse.
    """
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    for key in ("mesh", "meshes", "devices_list", "context_parallel",
                "pipeline_parallel", "cp", "pp", "data", "model"):
        if key in spec:
            raise ValueError(
                f"proc replicas do not compose with {key!r}: each worker "
                f"owns its whole single-process engine (CP/PP/submesh "
                f"sharding is the in-process build_replicas path); spawn "
                f"more replicas instead")
    layout = spec.get("layout")
    mesh_shape = spec.get("mesh_shape")
    if layout is not None or mesh_shape is not None:
        from k8s_llm_rca_tpu.runtime.rules import SpecLayout

        if kind != "engine":
            raise ValueError(
                f"layout/mesh_shape compose with kind='engine' proc "
                f"workers only (kind={kind!r} carries no params to lay "
                f"out)")
        if isinstance(layout, SpecLayout):
            layout = spec["layout"] = layout.to_dict()
        if layout is not None:
            SpecLayout.from_dict(layout)      # typo'd axes die parent-side
        shape = dict(mesh_shape or {})
        bad = sorted(set(shape) - {"data", "fsdp", "model"})
        if bad:
            raise ValueError(
                f"proc worker mesh_shape supports data/fsdp/model axes "
                f"only, got {bad}: CP/PP/EP do not compose with proc "
                f"replicas")
        n_dev = 1
        for v in shape.values():
            n_dev *= int(v)
        if int(spec.get("devices", n_dev)) != n_dev:
            raise ValueError(
                f"spec devices={spec.get('devices')} does not match the "
                f"mesh_shape device product {n_dev}")
        spec["devices"] = n_dev
        if (layout or {}).get("fsdp") and shape.get("fsdp", 1) <= 1:
            raise ValueError(
                f"layout maps fsdp to axis {layout['fsdp']!r} but "
                f"mesh_shape carries no fsdp axis > 1: the layout "
                f"requests sharding that cannot happen")
    return [ProcReplica(rid, kind=kind, **spec)
            for rid in range(n_replicas)]


if __name__ == "__main__":
    sys.exit(worker_main(sys.argv[1:]))
