"""Span tracer: the flight recorder's event source.

The reference's only instrumentation is ``print`` banners and wall-clock
bracketing (reference test_all.py:143-151); utils/logging.py upgraded that
to flat counters/timers, but neither can answer "what did the engine do,
tick by tick, while incident N's auditor stage was waiting?".  This module
records the causal tree the stack actually executes:

    rca.incident  (run id)
      └─ rca.stage.locate / .metapath / .cypher / .audit
           └─ serve.run  (one assistants-API run, explicit start/end)
           └─ engine.tick
                └─ engine.prefill / engine.decode_step (profiling.annotate)
           └─ graph.query

Design rules (mirroring faults/inject.py):

- **always-on-cheap**: hot call sites guard on the module slot
  ``trace._ACTIVE is not None`` (engine ticks) or call the ``span()`` /
  ``event()`` helpers, which collapse to one global load + identity test
  and a shared ``nullcontext`` when no tracer is active — nothing
  allocates on the disarmed path;
- **deterministic**: span/event ids come from a per-tracer counter, never
  from object identity or randomness, and every timestamp is read from an
  injectable ``clock`` (the real ``time`` module in production,
  ``faults.plan.VirtualClock`` under chaos soaks) — so a seeded soak run
  yields byte-identical Chrome trace JSON (obs/export.py), the golden
  test's acceptance bar;
- **bounded**: the span store is capped (``max_spans``); past the cap new
  spans/events are counted in ``dropped`` instead of recorded, so an
  always-on tracer cannot grow without bound in a long soak.

``SITES`` is the registry of every name the in-tree instrumentation is
expected to emit; ``coverage_missing()`` is the self-check tests invoke so
instrumentation cannot silently rot (a renamed call site fails the test,
not the dashboard).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time as _time
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Set, Tuple

from k8s_llm_rca_tpu.obs.timeline import TickTimeline

# Every name the in-tree instrumentation emits (spans AND instant events).
# tests/test_obs.py drives each layer and asserts coverage_missing() is
# empty — add the site HERE when instrumenting a new call site.
SITES = frozenset({
    # engine layer: every span goes through profiling.annotate, so each
    # name is also a TraceAnnotation in an XProf capture and a METRICS
    # timer (<name>.total_s / .count).  engine.tick wraps the whole tick
    # (EngineBase.step) and engine.tick.* are its phases, siblings in
    # this order with no time of one inside another: .reap (deadlines,
    # results of an out-of-tick flush, the lag flush before a sync path;
    # opened only when there is a deadline, a flushed result or a lag),
    # .prefill_chunk, .admission, .first_tokens (the fetch that waits
    # for the tick's prefills and the first tokens' commit), .eviction,
    # .decode (whichever decode program runs, from its set-up through
    # its commit)
    "engine.tick",
    "engine.tick.reap",
    "engine.tick.prefill_chunk",
    "engine.tick.admission",
    "engine.tick.first_tokens",
    "engine.tick.eviction",
    "engine.tick.decode",
    # under the phases: engine.prefill and engine.decode_step time the
    # DISPATCH of a program, engine.fetch the time the host then blocks
    # on the chip; engine.admission.stage / .activate the host work of
    # one admission group before its prefill dispatch (pages, slots, the
    # numpy rows) and after it (the slot's registration, the first
    # token's way to the resident state); engine.scan_setup everything a
    # decode tick does before its dispatch (DFA tables, the key split,
    # the uploads of the host mirrors, the work counters);
    # engine.grammar_mask the FSM masks and DFA tables built on the host
    # for a tick; engine.commit the host loop that appends the tick's
    # tokens and retires finished sequences
    "engine.prefill",
    "engine.decode_step",
    "engine.admission.stage",
    "engine.admission.activate",
    "engine.scan_setup",
    "engine.fetch",
    "engine.grammar_mask",
    "engine.commit",
    # one span per retired sequence from its arrival to its newest token
    # (explicit times on the engine's clock; args carry seq, queue_wait_s,
    # prefill_s, decode_s, stall_s, tokens, preemptions) — the record
    # obs/critical_path.py reads for the run that owns the seq
    "engine.request",
    # overload survival (engine/paged.py): KV page spill-to-host on
    # preemption and the h2d page restore that resumes the sequence
    "engine.spill",
    "engine.restore",
    # tiered prefix cache (engine/paged.py hooks): eviction's d2h page
    # demotion into the PrefixStore and the h2d promotion that serves a
    # warm L1/L2 match without re-prefill
    "engine.prefix_demote",
    "engine.prefix_promote",
    # pipelined sweep (serve/backend.py pump idle branch + the scheduler
    # in rca/scheduler.py): pumps that found live handles but nothing
    # decodable, and the park interval between a stage submitting its run
    # and the scheduler resuming that incident's machine
    "engine.idle_ticks",
    "rca.stage.queue_wait",
    # serve layer
    "serve.run_started",
    "serve.run",
    "serve.settled",
    "backend.settled",
    # durability layer (serve/journal.py, serve/recover.py)
    "serve.journal.append",
    "serve.recover.replay",
    # cluster layer (cluster/router.py)
    "cluster.route",
    "cluster.failover",
    # self-healing (cluster/health.py): watchdog verdict transitions,
    # supervisor rejoin, poison-run quarantine, and the MTTD/MTTR spans
    # measured on the watchdog's injectable clock
    "cluster.health",
    "cluster.restart",
    "cluster.quarantine",
    "cluster.mttd",
    "cluster.mttr",
    # out-of-process replicas (cluster/proc.py): worker spawn (ready
    # handshake included), every parent->worker RPC over the framed
    # pipe, and the worker's exit (clean close or reaped corpse)
    "cluster.proc.spawn",
    "cluster.proc.rpc",
    "cluster.proc.exit",
    # cross-host links (cluster/proc.py socket transport): a link going
    # down with the process still alive (evidence, not a death verdict)
    # and the relink that heals the SAME incarnation under a fresh
    # session nonce
    "cluster.net.partition",
    "cluster.net.relink",
    # fleet flight recorder (cluster/proc.py telemetry shipping): the
    # WORKER-side span wrapping one handled RPC (recorded in the
    # worker's own tracer, parented onto the propagated trace context,
    # ingested parent-side into Tracer.remote), the parent-side event
    # per non-empty telemetry payload that rode a reply frame, and the
    # explicit drain flush (ProcBackend.close / watchdog relink heal)
    "cluster.proc.serve",
    "cluster.telemetry.ship",
    "cluster.telemetry.drain",
    # disaggregated tiers (cluster/disagg.py): one event per handoff
    # outcome — a committed EXPORT -> ADOPT -> RELEASE transfer, or a
    # retried attempt discarded whole (args carry the stage and reason)
    "cluster.handoff",
    # the three phases of one transfer attempt as SPANS around the
    # actual backend calls (disagg._attempt_handoff), so the
    # critical-path pass can attribute per-phase handoff time (zero
    # duration under a VirtualClock, real wire time in production)
    "cluster.handoff.export",
    "cluster.handoff.adopt",
    "cluster.handoff.release",
    # elastic fleet (cluster/autoscale.py): one event per autoscaler
    # action — scale-up spawn, drain-down retirement, or tier rebalance
    # (args carry kind/tier/replica/fleet size/free submeshes)
    "cluster.scale",
    # cache fabric (cluster/store.py): one event per SUCCESSFUL store op
    # from the client (RemoteStore.put / .get — args carry the truncated
    # page key and, for gets, the serving tier) and one per store-server
    # (re)spawn (StoreServer._spawn — args carry pid/incarnation/
    # transport/port).  Failed ops emit nothing: they degrade to counted
    # cold misses (engine.prefix_store_misses_remote) by contract
    "cluster.store.put",
    "cluster.store.get",
    "cluster.store.serve",
    # graph layer
    "graph.query",
    # rca pipeline stages
    "rca.incident",
    "rca.stage.locate",
    "rca.stage.metapath",
    "rca.stage.cypher",
    "rca.stage.audit",
    # resilience events (faults/policy.py)
    "resilience.retry",
    "resilience.degraded",
    "resilience.breaker_open",
    "resilience.breaker_close",
})


@dataclass
class SpanEvent:
    """Instant event, optionally attached under a span (parent_id)."""

    event_id: int
    parent_id: Optional[int]
    name: str
    ts: float
    tid: int
    args: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Span:
    span_id: int
    parent_id: Optional[int]
    name: str
    cat: str
    t0: float
    tid: int
    t1: Optional[float] = None
    args: Dict[str, Any] = field(default_factory=dict)


class Tracer:
    """Deterministic span/event recorder with an injectable clock.

    Thread-safe: the store mutates under one lock; the current-span stack
    (parentage) is thread-local, so spans opened on worker threads parent
    correctly within their own thread and never race another thread's
    stack.  Thread ids are densified in first-seen order, which makes the
    single-threaded soak's output reproducible (tid 1 everywhere).
    """

    def __init__(self, clock: Any = None, max_spans: int = 100_000,
                 trace_id: int = 1):
        self.clock = clock if clock is not None else _time
        self.max_spans = max_spans
        self.trace_id = int(trace_id)
        self.spans: List[Span] = []
        self.events: List[SpanEvent] = []
        self.dropped = 0
        self.timeline = TickTimeline()
        # telemetry shipped back from out-of-process workers, keyed
        # (replica_id, incarnation) in ingestion order — a respawned
        # worker lands in a NEW bucket, which the Chrome exporter renders
        # as a visibly new pid track (obs/export.py).  Items stay in wire
        # form (plain dicts from span_to_wire/event_to_wire/tick_to_wire);
        # os_pid is recorded for the track name but never used as a key.
        self.remote: Dict[Tuple[int, int], Dict[str, Any]] = {}
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._tids: Dict[int, int] = {}

    # ------------------------------------------------------------ internals

    def now(self) -> float:
        return self.clock.time()

    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids) + 1
        return tid

    def _full(self) -> bool:
        if len(self.spans) + len(self.events) >= self.max_spans:
            self.dropped += 1
            return True
        return False

    # ------------------------------------------------------------- recording

    def begin(self, name: str, cat: str = "app",
              args: Optional[Dict[str, Any]] = None) -> Optional[Span]:
        """Open a span (returns None past the cap — ``end`` tolerates it)."""
        stack = self._stack()
        with self._lock:
            if self._full():
                return None
            parent = stack[-1].span_id if stack else None
            sp = Span(next(self._ids), parent, name, cat, self.now(),
                      self._tid(), args=dict(args or {}))
            self.spans.append(sp)
        stack.append(sp)
        return sp

    def end(self, sp: Optional[Span]) -> None:
        if sp is None:
            return
        stack = self._stack()
        if stack and stack[-1] is sp:
            stack.pop()
        with self._lock:
            sp.t1 = self.now()

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "app", **args):
        sp = self.begin(name, cat, args)
        try:
            yield sp
        finally:
            self.end(sp)

    def add_span(self, name: str, t0: float, t1: float, cat: str = "app",
                 args: Optional[Dict[str, Any]] = None) -> Optional[Span]:
        """Record an already-elapsed span with explicit times (e.g. a
        serve run, whose start and settle are separate pump calls)."""
        stack = self._stack()
        with self._lock:
            if self._full():
                return None
            parent = stack[-1].span_id if stack else None
            sp = Span(next(self._ids), parent, name, cat, float(t0),
                      self._tid(), t1=float(t1), args=dict(args or {}))
            self.spans.append(sp)
            return sp

    def event(self, name: str, **args) -> None:
        stack = self._stack()
        with self._lock:
            if self._full():
                return
            parent = stack[-1].span_id if stack else None
            self.events.append(SpanEvent(next(self._ids), parent, name,
                                         self.now(), self._tid(),
                                         dict(args)))

    # ----------------------------------------------------- fleet propagation

    def context(self, parent: Optional[Span] = None) -> Dict[str, Any]:
        """Wire-ready propagation context for an outbound request frame:
        trace id, parent span id (the current thread's innermost open
        span unless given explicitly), and the injectable clock's NOW so
        the worker's PropagatedClock stamps its spans in this tracer's
        (possibly virtual) timebase."""
        if parent is None:
            st = self._stack()
            parent = st[-1] if st else None
        return {"id": self.trace_id,
                "parent": parent.span_id if parent is not None else None,
                "ts": self.now()}

    def ingest_remote(self, replica: int, incarnation: int,
                      payload: Dict[str, Any]) -> int:
        """Ingest one telemetry payload shipped off a worker reply frame
        (cluster/proc.py).  Returns the number of items accepted; ``shed``
        keeps the worker-reported high-water mark of ring overflow +
        worker-tracer drops (the at-most-bounded-loss accounting)."""
        key = (int(replica), int(incarnation))
        with self._lock:
            bucket = self.remote.get(key)
            if bucket is None:
                bucket = self.remote[key] = {
                    "os_pid": payload.get("pid"),
                    "spans": [], "events": [], "ticks": [],
                    "shed": 0, "counters": {}}
            n = 0
            for item in payload.get("items") or ():
                kind = item.get("k")
                if kind == "span":
                    bucket["spans"].append(item)
                elif kind == "event":
                    bucket["events"].append(item)
                elif kind == "tick":
                    bucket["ticks"].append(item)
                else:
                    continue
                n += 1
            bucket["shed"] = max(bucket["shed"],
                                 int(payload.get("shed", 0)))
            counters = payload.get("counters")
            if counters:
                bucket["counters"] = dict(counters)
        return n

    # --------------------------------------------------------------- queries

    def mark(self) -> Tuple[int, int, int]:
        """Current (spans, events, ticks) position — pass to
        ``flight_summary(since=...)`` to summarize just the work after it."""
        with self._lock:
            return (len(self.spans), len(self.events), self.timeline.total)

    def emitted_names(self) -> Set[str]:
        with self._lock:
            names = {s.name for s in self.spans}
            names |= {e.name for e in self.events}
            for bucket in self.remote.values():
                names |= {s["name"] for s in bucket["spans"]}
                names |= {e["name"] for e in bucket["events"]}
        return names

    def flight_summary(self, since: Optional[Tuple[int, int, int]] = None
                       ) -> Dict[str, Any]:
        """Compact flight-recorder digest (embedded in RCA reports): span/
        event/tick counts and the per-name span histogram.  Deterministic
        under a VirtualClock — byte-stable inside soak reports."""
        s0, e0, t0 = since if since is not None else (0, 0, 0)
        with self._lock:
            spans = self.spans[s0:]
            events = self.events[e0:]
            ticks = self.timeline.total - t0
            by_name: Dict[str, int] = {}
            for sp in spans:
                by_name[sp.name] = by_name.get(sp.name, 0) + 1
            ts = ([sp.t0 for sp in spans]
                  + [sp.t1 for sp in spans if sp.t1 is not None]
                  + [e.ts for e in events])
            duration = (max(ts) - min(ts)) if ts else 0.0
        return {
            "spans": len(spans),
            "events": len(events),
            "ticks": int(ticks),
            "dropped": self.dropped,
            "duration_s": round(duration, 6),
            "by_name": {k: by_name[k] for k in sorted(by_name)},
        }


# ---------------------------------------------------------------------------
# fleet telemetry: worker-side clock/ring + wire converters
# ---------------------------------------------------------------------------


class PropagatedClock:
    """Monotone clock pinned to propagated parent timestamps.

    The worker-side tracer (cluster/proc.py) runs under this clock:
    every request frame's trace context carries the parent tracer's NOW,
    and ``advance_to`` adopts it, so worker spans and ticks are stamped
    in the PARENT's timebase — under a frozen ``VirtualClock`` that
    makes the merged Chrome trace byte-identical per seed instead of
    polluted by worker wall-clock noise.  Never moves backwards.
    """

    def __init__(self, t0: float = 0.0):
        self._t = float(t0)

    def advance_to(self, t: Any) -> None:
        try:
            t = float(t)
        except (TypeError, ValueError):
            return
        if t > self._t:
            self._t = t

    def time(self) -> float:
        return self._t

    def sleep(self, seconds: float) -> None:
        # clock-protocol parity with VirtualClock: advancing is the only
        # honest "sleep" a propagated timebase can offer
        self._t += float(seconds)


class TelemetryRing:
    """Bounded FIFO of wire-ready telemetry items (the worker half of
    telemetry shipping, cluster/proc.py).

    ``push`` past capacity drops the OLDEST item and counts it in
    ``shed`` — after a SIGKILL the newest pre-kill activity is the part
    an RCA needs, so the ring sheds history, not the tail.  ``pop``
    drains at most ``budget`` items in FIFO order (the per-reply-frame
    piggyback budget keeps frames bounded under wire.MAX_FRAME_SIZE).
    """

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"TelemetryRing capacity must be >= 1, "
                             f"got {capacity}")
        self.capacity = int(capacity)
        self.shed = 0
        self._items: Deque[Dict[str, Any]] = collections.deque()

    def __len__(self) -> int:
        return len(self._items)

    def push(self, item: Dict[str, Any]) -> None:
        if len(self._items) >= self.capacity:
            self._items.popleft()
            self.shed += 1
        self._items.append(item)

    def pop(self, budget: int) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        while self._items and len(out) < budget:
            out.append(self._items.popleft())
        return out


def span_to_wire(sp: Span) -> Dict[str, Any]:
    """Wire form of a completed span — plain JSON-safe dict with a ``k``
    discriminator, ingested as-is by ``Tracer.ingest_remote``."""
    return {"k": "span", "name": sp.name, "cat": sp.cat,
            "span_id": sp.span_id, "parent_id": sp.parent_id,
            "t0": sp.t0, "t1": sp.t1, "tid": sp.tid,
            "args": dict(sp.args)}


def event_to_wire(ev: SpanEvent) -> Dict[str, Any]:
    return {"k": "event", "name": ev.name, "event_id": ev.event_id,
            "parent_id": ev.parent_id, "ts": ev.ts, "tid": ev.tid,
            "args": dict(ev.args)}


def tick_to_wire(sample: Any) -> Dict[str, Any]:
    """Wire form of a TickSample (obs/timeline.py) — every field is
    already a JSON scalar, so asdict + discriminator suffices."""
    d = dataclasses.asdict(sample)
    d["k"] = "tick"
    return d


# ---------------------------------------------------------------------------
# module activation slot (the inject._ARMED pattern)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[Tracer] = None

_NULL = contextlib.nullcontext()


def activate(tracer: Tracer) -> Tracer:
    global _ACTIVE
    if _ACTIVE is not None:
        raise RuntimeError("a Tracer is already active")
    _ACTIVE = tracer
    return tracer


def deactivate() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> Optional[Tracer]:
    return _ACTIVE


@contextlib.contextmanager
def tracing(tracer: Tracer):
    """``with trace.tracing(tracer): ...`` — activates for the block."""
    activate(tracer)
    try:
        yield tracer
    finally:
        deactivate()


def span(name: str, cat: str = "app", **args):
    """Span under the active tracer; a shared no-op context otherwise."""
    tr = _ACTIVE
    if tr is None:
        return _NULL
    return tr.span(name, cat, **args)


def event(name: str, **args) -> None:
    """Instant event under the active tracer; no-op otherwise."""
    tr = _ACTIVE
    if tr is not None:
        tr.event(name, **args)


def coverage_missing(*tracers: Tracer) -> List[str]:
    """Registry names not emitted by any of the given tracers — the
    instrumentation-rot self-check (tests drive each layer under a tracer
    and assert this is empty)."""
    emitted: Set[str] = set()
    for tr in tracers:
        emitted |= tr.emitted_names()
    return sorted(SITES - emitted)
