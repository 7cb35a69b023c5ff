"""Local assistants API: Assistant / Thread / Message / Run on a local backend.

This is the drop-in replacement surface for the reference's
``OpenAIGenericAssistant`` (common/openai_generic_assistant.py) — the same
object model and the same 13 client methods — except the compute behind it is
the in-tree TPU engine instead of HTTPS to api.openai.com:

- the run-state machine is preserved exactly: ``queued | in_progress |
  completed | cancelled | failed | expired`` (reference :100-112 branches on
  these);
- ``get_token_usage(tmin, tmax, limit)`` keeps the reference's window
  semantics (:117-135): sum usage over runs whose created_at AND completed_at
  both fall in ``[tmin, tmax)``, newest-first, capped at ``limit``;
- ``wait_get_last_k_message`` keeps the blocking contract but pumps the
  scheduler instead of sleeping 5·i seconds per poll (:92-115) — the 5 s
  polling floor per LLM call simply disappears;
- message listings are newest-first and messages expose
  ``.content[0].text.value`` so stage code written against the OpenAI shapes
  ports without edits (reference usage: find_srckind_metapath_neo4j.py:189).

Threads support concurrent runs from one thread (the reference serializes
per-thread; SURVEY §3.4 notes stage 3 issues independent per-entity audits on
a shared thread — here they can overlap in the batch).

The service is thread-safe: one coarse re-entrant lock serializes every
public method and the backend pump, so N sweep workers can drive their own
pipelines against ONE shared service/engine and the continuous batcher
merges their runs into shared decode ticks (the configs[2] sweep shape —
see sweeps/run_file.py --workers).  A worker blocked on the lock while
another worker's pump ticks the engine is not wasted time: that tick
decodes every in-flight run, including the blocked worker's.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.serve.backend import GenOptions, LMBackend
from k8s_llm_rca_tpu.utils.logging import METRICS, get_logger

log = get_logger(__name__)


class RunStatus:
    QUEUED = "queued"
    IN_PROGRESS = "in_progress"
    COMPLETED = "completed"
    CANCELLED = "cancelled"
    FAILED = "failed"
    EXPIRED = "expired"

    TERMINAL = (COMPLETED, CANCELLED, FAILED, EXPIRED)


# --- OpenAI-shaped message content (stage code reads .content[0].text.value)


@dataclass
class _Text:
    value: str


@dataclass
class _ContentPart:
    text: _Text
    type: str = "text"


@dataclass
class Message:
    id: str
    role: str
    raw_content: str
    created_at: float
    content: List[_ContentPart] = field(default_factory=list)

    def __post_init__(self):
        if not self.content:
            self.content = [_ContentPart(text=_Text(value=self.raw_content))]


@dataclass
class MessageList:
    data: List[Message]        # newest first, like the OpenAI listing


@dataclass
class Assistant:
    id: str
    name: str
    instructions: str
    model: str
    gen: GenOptions = field(default_factory=GenOptions)


@dataclass
class Thread:
    id: str
    messages: List[Message] = field(default_factory=list)  # oldest first


@dataclass
class Run:
    id: str
    thread_id: str
    assistant_id: str
    status: str = RunStatus.QUEUED
    created_at: Optional[int] = None
    completed_at: Optional[int] = None
    usage: Dict[str, int] = field(default_factory=lambda: {
        "prompt_tokens": 0, "completion_tokens": 0, "total_tokens": 0})
    # the engine's own clock on the run (None until it settles, and from
    # backends that time nothing): seconds queued before a slot, to the
    # first token, from the first token to the last, and of those the
    # seconds it stood behind the ticks' prefill phases (``stall_s``);
    # with the engine's ``seq``, which the run's ``engine.request`` span
    # carries
    timing: Optional[Dict[str, Any]] = None
    error: Optional[str] = None
    # book-keeping
    instructions_override: Optional[str] = None
    backend_handle: Optional[int] = None
    deadline: Optional[float] = None
    response_message_id: Optional[str] = None
    # precise (float) start time on the service clock, for the flight
    # recorder's "serve.run" span (created_at is int seconds for the
    # reference's window semantics and too coarse for span durations)
    t_started: Optional[float] = None


def render_prompt(assistant: Assistant, thread: Thread,
                  instructions_override: Optional[str] = None) -> str:
    """Chat-template rendering of instructions + thread history.

    The whole thread is replayed every run, matching the reference's
    monotonically growing assistant threads (SURVEY §5 long-context note) —
    this is precisely what makes CP/ring-attention prefill worth having.
    """
    instructions = instructions_override or assistant.instructions
    parts = [f"<|system|>\n{instructions}\n"]
    for m in thread.messages:
        parts.append(f"<|{m.role}|>\n{m.raw_content}\n")
    parts.append("<|assistant|>\n")
    return "".join(parts)


def _locked(fn):
    """Serialize a service method on the instance's re-entrant lock."""
    import functools

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)
    return wrapper


class AssistantService:
    """The 'server': owns assistants/threads/runs and drives an LMBackend."""

    def __init__(self, backend: LMBackend, run_timeout_s: float = 600.0,
                 clock=None, journal=None):
        # ``clock``: injectable time source (time()/sleep()) for run
        # timestamps and deadlines — the real ``time`` module by default,
        # a faults.plan.VirtualClock under chaos runs so deadline expiry
        # happens after a deterministic number of pumps, not wall seconds
        # ``journal``: optional serve.journal.RunJournal.  Every mutation
        # hook below is guarded by a single ``is None`` check (same
        # discipline as faults/inject.py) — the default path does zero
        # journal work, builds zero records, touches zero files.
        self.backend = backend
        self.run_timeout_s = run_timeout_s
        self._clock = clock if clock is not None else time
        self._journal = journal
        self.assistants: Dict[str, Assistant] = {}
        self.threads: Dict[str, Thread] = {}
        self.runs: Dict[str, Run] = {}
        self._thread_runs: Dict[str, List[str]] = {}
        self._inflight: Dict[int, str] = {}   # backend handle -> run id
        self._ids = itertools.count()
        self._lock = threading.RLock()
        self._waiters = 0       # concurrent wait_run count (handoff sleep)

    @_locked
    def _next_id(self, prefix: str) -> str:
        return f"{prefix}_{next(self._ids):08d}"

    # ------------------------------------------------------------ lifecycle

    @_locked
    def create_assistant(self, instructions: str, name: str,
                         model: str = "local",
                         gen: Optional[GenOptions] = None) -> Assistant:
        a = Assistant(self._next_id("asst"), name, instructions, model,
                      gen or GenOptions())
        self.assistants[a.id] = a
        if self._journal is not None:
            from k8s_llm_rca_tpu.serve.journal import encode_gen
            self._journal.append("create_assistant", id=a.id, name=a.name,
                                 instructions=a.instructions, model=a.model,
                                 gen=encode_gen(a.gen))
        return a

    @_locked
    def retrieve_assistant(self, assistant_id: str) -> Assistant:
        return self.assistants[assistant_id]

    @_locked
    def create_thread(self) -> Thread:
        t = Thread(self._next_id("thread"))
        self.threads[t.id] = t
        self._thread_runs[t.id] = []
        if self._journal is not None:
            self._journal.append("create_thread", id=t.id)
        return t

    @_locked
    def retrieve_thread(self, thread_id: str) -> Thread:
        return self.threads[thread_id]

    @_locked
    def add_message(self, thread_id: str, content: str,
                    role: str = "user") -> Message:
        m = Message(self._next_id("msg"), role, content, time.time())
        self.threads[thread_id].messages.append(m)
        if self._journal is not None:
            self._journal.append("add_message", thread_id=thread_id,
                                 id=m.id, role=m.role, content=m.raw_content,
                                 created_at=m.created_at)
        return m

    @_locked
    def create_run(self, thread_id: str, assistant_id: str,
                   instructions: Optional[str] = None,
                   gen: Optional[GenOptions] = None) -> Run:
        assistant = self.assistants[assistant_id]
        run = Run(self._next_id("run"), thread_id, assistant_id,
                  created_at=int(self._clock.time()),
                  instructions_override=instructions)
        run.t_started = self._clock.time()
        self.runs[run.id] = run
        self._thread_runs[thread_id].append(run.id)

        prompt = render_prompt(assistant, self.threads[thread_id], instructions)
        # session = thread id: the cluster router's affinity key, so every
        # run of a thread lands on the replica already holding its prefix.
        # Every run carries a concrete deadline into the ENGINE (eager
        # in-tick reaping frees pages the moment it passes): the caller's
        # GenOptions.deadline_s when set, else run_timeout_s — the serve-
        # level poll expiry stays as a backstop at the tighter of the two.
        base = gen or assistant.gen
        deadline_s = (base.deadline_s if base.deadline_s is not None
                      else self.run_timeout_s)
        run.deadline = self._clock.time() + min(self.run_timeout_s,
                                                deadline_s)
        opts = dataclasses.replace(base,
                                   assistant_name=assistant.name,
                                   session=thread_id,
                                   deadline_s=deadline_s)
        run.usage["prompt_tokens"] = self.backend.count_tokens(prompt)
        run.backend_handle = self.backend.start(prompt, opts)
        run.status = RunStatus.IN_PROGRESS
        self._inflight[run.backend_handle] = run.id
        if self._journal is not None:
            # journaled AFTER backend.start: a submission the backend
            # rejected (BudgetError) never reaches the journal, so replay
            # cannot resurrect a run that was never accepted
            from k8s_llm_rca_tpu.serve.journal import encode_gen
            self._journal.append(
                "run_submit", id=run.id, thread_id=thread_id,
                assistant_id=assistant_id, created_at=run.created_at,
                instructions=instructions, gen=encode_gen(gen),
                prompt=prompt)
        METRICS.inc("serve.runs_started")
        obs_trace.event("serve.run_started", run=run.id,
                        assistant=assistant.name)
        return run

    @_locked
    def retrieve_run(self, run_id: str) -> Run:
        self._pump()
        return self.runs[run_id]

    @_locked
    def poll_run(self, run_id: str) -> Run:
        """Non-blocking probe: advance the backend by ONE pump and return
        the run, terminal or not.  This is the future-style half of the
        run API — ``wait_run`` spins this in a loop; a sweep scheduler
        calls it once per slot visit and interleaves other incidents'
        stages while the run decodes (the reference's 5 s ``sleep`` poll,
        common/openai_generic_assistant.py:92-115, with the sleep deleted
        and the wait externalized)."""
        self._pump()
        return self.runs[run_id]

    @_locked
    def pump_once(self) -> None:
        """Public single pump: advance the backend one tick and settle any
        finished runs, without reference to a particular run.  The sweep
        scheduler's shared pump loop calls this when every in-flight
        incident is blocked on an unsettled run — one tick decodes ALL of
        them (the continuous batcher doesn't care which caller pumps)."""
        self._pump()

    @_locked
    def reap_dropped_run(self, run_id: str) -> Run:
        """Settle a non-terminal run whose backend no longer tracks its
        handle — the ``_wait_run_loop`` 'backend dropped the run' path,
        exposed for non-blocking pollers: the sweep scheduler cannot sit
        inside ``wait_run`` (it has other incidents to advance), so it
        applies the same liveness check between pumps.  Unlike the wait
        loop this also drops the handle from ``_inflight``, so a later
        deadline sweep in ``_pump`` cannot flip the FAILED run to
        EXPIRED."""
        run = self.runs[run_id]
        if (run.status not in RunStatus.TERMINAL
                and not self.backend.busy(run.backend_handle)):
            run.status = RunStatus.FAILED
            run.error = "backend dropped the run"
            self._inflight.pop(run.backend_handle, None)
            if self._journal is not None:
                self._journal_settle(run)
        return run

    @_locked
    def cancel_run(self, run_id: str) -> Run:
        run = self.runs[run_id]
        if run.status not in RunStatus.TERMINAL:
            self.backend.cancel(run.backend_handle)
            run.status = RunStatus.CANCELLED
            run.completed_at = int(self._clock.time())
            self._inflight.pop(run.backend_handle, None)
            if self._journal is not None:
                self._journal_settle(run)
            self._trace_run_settled(run)
        return run

    def _journal_settle(self, run: Run) -> None:
        """Append the run's terminal transition.  Only ever called behind
        ``self._journal is not None`` — never on the default path."""
        response = None
        if run.response_message_id is not None:
            for m in self.threads[run.thread_id].messages:
                if m.id == run.response_message_id:
                    response = {"id": m.id, "role": m.role,
                                "content": m.raw_content,
                                "created_at": m.created_at}
                    break
        self._journal.append(
            "run_settle", id=run.id, status=run.status,
            completed_at=run.completed_at, usage=dict(run.usage),
            error=run.error, response=response)

    def _trace_run_settled(self, run: Run) -> None:
        """Record the run's whole lifetime as one explicit-times
        'serve.run' span (start = create_run, end = settle — the two are
        separate pump calls, so the context-manager span API cannot
        bracket them).  No-op without an active tracer."""
        tr = obs_trace._ACTIVE
        if tr is None:
            return
        assistant = self.assistants.get(run.assistant_id)
        now = self._clock.time()
        t0 = run.t_started if run.t_started is not None else now
        args = {"run": run.id, "status": run.status,
                "assistant": assistant.name if assistant else "",
                "completion_tokens": run.usage["completion_tokens"]}
        if run.timing is not None:
            # the key of the engine's ``engine.request`` span for this run
            # (obs/critical_path.py reads its stamps instead of guessing)
            args["seq"] = run.timing["seq"]
            args["seq_t0"] = run.timing["t_arrival"]
        tr.add_span("serve.run", t0, now, cat="serve", args=args)

    @_locked
    def list_runs(self, thread_id: str, limit: int = 20,
                  order: str = "desc") -> List[Run]:
        ids = self._thread_runs.get(thread_id, [])
        runs = [self.runs[i] for i in ids]
        if order == "desc":
            runs = runs[::-1]
        return runs[:limit]

    @_locked
    def assistant_token_usage(self, assistant_id: str, tmin: int, tmax: int,
                              limit: int = 20) -> Dict[str, int]:
        """Windowed usage over ALL of an assistant's runs (any thread) —
        the reference's window semantics (created_at AND completed_at in
        [tmin, tmax), newest-first, capped) applied assistant-wide, so
        runs on audit sub-threads stay counted."""
        usage = {"prompt_tokens": 0, "completion_tokens": 0,
                 "total_tokens": 0}
        # newest `limit` runs FIRST, then window-filter — the reference's
        # order of operations (list_runs(limit) then the window test,
        # reference common/openai_generic_assistant.py:117-135)
        newest = sorted(
            (r for r in self.runs.values()
             if r.assistant_id == assistant_id and r.created_at is not None),
            key=lambda r: r.created_at, reverse=True)[:limit]
        for run in newest:
            if (run.completed_at is not None
                    and tmin <= run.created_at < tmax
                    and tmin <= run.completed_at < tmax):
                for k in usage:
                    usage[k] += run.usage[k]
        return usage

    @_locked
    def usage_for_runs(self, run_ids: Sequence[str],
                       critical_path: bool = False) -> Dict[str, Any]:
        """Exact usage attribution: sum the usage of precisely the named
        runs (terminal only — in-flight usage is still moving).  The
        wall-clock window of ``assistant_token_usage`` double-counts when
        incidents overlap in time (pipelined sweeps); summing by the run
        ids an incident actually created cannot.  Same 3-key schema as the
        reference's windowed accounting.

        ``critical_path=True`` additionally attaches the per-run latency
        decomposition (obs/critical_path.py over the ACTIVE tracer's
        merged fleet tree) under a ``"critical_path"`` key.  Strictly
        opt-in: the default 3-key schema is embedded in the pipelined
        sweep's byte-compared ``report_bytes`` and must never change
        shape."""
        usage: Dict[str, Any] = {"prompt_tokens": 0,
                                 "completion_tokens": 0,
                                 "total_tokens": 0}
        for rid in run_ids:
            run = self.runs.get(rid)
            if run is not None and run.status in RunStatus.TERMINAL:
                for k in ("prompt_tokens", "completion_tokens",
                          "total_tokens"):
                    usage[k] += run.usage[k]
        if critical_path:
            from k8s_llm_rca_tpu.obs.critical_path import (
                critical_path as _decompose)

            tr = obs_trace._ACTIVE
            usage["critical_path"] = (
                _decompose(tr, runs=set(run_ids)) if tr is not None
                else {})
        return usage

    @_locked
    def list_messages(self, thread_id: str, limit: Optional[int] = None
                      ) -> MessageList:
        msgs = self.threads[thread_id].messages[::-1]  # newest first
        if limit is not None:
            msgs = msgs[:limit]
        return MessageList(data=msgs)

    # -------------------------------------------------------- observability

    @_locked
    def prometheus_metrics(self) -> str:
        """Prometheus text exposition for this service: the global METRICS
        store (serve/engine/rca counters + phase-latency summaries) plus
        live engine gauges (running/queued seqs, free/evictable pages,
        prefix-hit tokens) when the backend carries an engine.  This is
        the serve API's scrape surface — an HTTP wrapper only needs to
        return this string with content type text/plain; version=0.0.4.
        A cluster backend (cluster.ClusterRouter — duck-typed on its
        ``queue_depths`` accessor) additionally yields ``cluster_*``
        gauges: replicas alive, per-replica queue depth and occupancy.
        Under an active tracer, worker counters shipped over the fleet
        telemetry seam render into the same families with ``{replica=}``
        labels."""
        from k8s_llm_rca_tpu.obs.export import prometheus_text

        router = (self.backend
                  if hasattr(self.backend, "queue_depths") else None)
        return prometheus_text(METRICS,
                               engine=getattr(self.backend, "engine", None),
                               router=router,
                               tracer=obs_trace._ACTIVE)

    # ------------------------------------------------------------ execution

    @_locked
    def _pump(self) -> None:
        """Advance the backend and settle any finished runs.  O(in-flight
        runs), not O(all runs ever created)."""
        results = self.backend.pump()
        now = self._clock.time()
        for handle, run_id in list(self._inflight.items()):
            run = self.runs[run_id]
            if handle in results:
                res = results[handle]
                if res.error is not None:
                    # engine-reaped deadline expiry surfaces as its own
                    # terminal status (pages already freed in-tick);
                    # journal/recovery replay it verbatim
                    run.status = (RunStatus.EXPIRED
                                  if getattr(res, "expired", False)
                                  else RunStatus.FAILED)
                    run.error = res.error
                else:
                    run.status = RunStatus.COMPLETED
                    msg = Message(self._next_id("msg"), "assistant",
                                  res.text, now)
                    self.threads[run.thread_id].messages.append(msg)
                    run.response_message_id = msg.id
                if res.prompt_tokens is not None:
                    # prefer the engine's ground truth (includes BOS, forced
                    # prefix, and any overflow truncation)
                    run.usage["prompt_tokens"] = res.prompt_tokens
                run.usage["completion_tokens"] = res.completion_tokens
                run.usage["total_tokens"] = (
                    run.usage["prompt_tokens"] + res.completion_tokens)
                timing = getattr(res, "timing", None)
                if timing is not None:
                    run.timing = {"seq": timing.seq_id,
                                  "t_arrival": timing.t_arrival,
                                  "queue_wait_s": timing.queue_wait_s,
                                  "ttft_s": timing.ttft_s,
                                  "decode_s": timing.decode_s,
                                  "stall_s": timing.stall_s}
                run.completed_at = int(self._clock.time())
                del self._inflight[handle]
                if self._journal is not None:
                    self._journal_settle(run)
                self._trace_run_settled(run)
            elif run.deadline is not None and now > run.deadline:
                self.backend.cancel(run.backend_handle)
                run.status = RunStatus.EXPIRED
                run.completed_at = int(self._clock.time())
                del self._inflight[handle]
                if self._journal is not None:
                    self._journal_settle(run)
                self._trace_run_settled(run)
        if results:
            obs_trace.event("serve.settled", n=len(results))

    def wait_run(self, run_id: str, timeout_s: Optional[float] = None) -> Run:
        # NOT @_locked: the lock is taken per pump iteration, never for the
        # whole wait, so concurrent waiters interleave — each tick one of
        # them drives decodes EVERY in-flight run forward
        run = self.runs[run_id]
        t0 = self._clock.time()
        with self._lock:               # += is not atomic across threads
            self._waiters += 1
        try:
            return self._wait_run_loop(run, t0, timeout_s)
        finally:
            with self._lock:
                self._waiters -= 1

    def _wait_run_loop(self, run: Run, t0: float,
                       timeout_s: Optional[float]) -> Run:
        while run.status not in RunStatus.TERMINAL:
            with self._lock:
                if run.status in RunStatus.TERMINAL:
                    break
                self._pump()
                if run.status in RunStatus.TERMINAL:
                    break
                if not self.backend.busy(run.backend_handle):
                    # backend lost the handle without a result
                    run.status = RunStatus.FAILED
                    run.error = "backend dropped the run"
                    if self._journal is not None:
                        self._journal_settle(run)
                    break
                if timeout_s is not None and self._clock.time() - t0 > timeout_s:
                    # mirror _pump's deadline path: cancel the backend run
                    # and drop it from _inflight, else the abandoned run
                    # keeps occupying a batch slot and a peer worker's
                    # later _pump would flip this EXPIRED run to COMPLETED
                    self.backend.cancel(run.backend_handle)
                    self._inflight.pop(run.backend_handle, None)
                    run.status = RunStatus.EXPIRED
                    run.completed_at = int(self._clock.time())
                    if self._journal is not None:
                        self._journal_settle(run)
                    self._trace_run_settled(run)
                    break
            # with PEER waiters, a REAL sleep (not sleep(0)): lock release
            # does not hand off — this thread would re-acquire before a
            # peer blocked on create_run/add_message gets scheduled,
            # serializing the whole sweep onto one worker's runs.  1 ms
            # against multi-ms pump ticks guarantees handoff; the
            # single-waiter case skips the sleep entirely (no contention
            # to break, and +1 ms per tick would tax fast backends).
            if self._waiters > 1:
                time.sleep(0.001)
        return run


def drive_steps(gen, service: AssistantService):
    """Run a step generator (rca/pipeline.py::incident_steps and friends)
    to completion by BLOCKING on each yielded run — the sequential
    scheduling of the exact code the sweep scheduler (rca/scheduler.py)
    interleaves.  ``StopIteration.value`` is the generator's result.
    Exceptions raised inside the generator (failed runs are detected at
    the parse halves) propagate unchanged."""
    try:
        pending = next(gen)
        while True:
            service.wait_run(pending.id)
            pending = gen.send(None)
    except StopIteration as stop:
        return stop.value


def run_reply_text(service: AssistantService, run: Run) -> str:
    """Reply text of a COMPLETED run, located by its response_message_id
    (robust to concurrent runs settling interleaved on a shared thread —
    the same disambiguation ``wait_get_last_k_message`` applies).  The
    parse halves of the split stage functions (rca/locator.py,
    rca/cyphergen.py) read their settled runs through this."""
    for m in service.list_messages(run.thread_id).data:
        if m.id == run.response_message_id:
            return m.content[0].text.value
    raise RuntimeError(f"reply message for run {run.id} not found")


class GenericAssistant:
    """Reference-compatible client: the 13 methods of
    common/openai_generic_assistant.py:10-135, same names, same shapes."""

    def __init__(self, service: AssistantService):
        self.service = service
        self.assistant: Optional[Assistant] = None
        self.thread: Optional[Thread] = None
        self.message: Optional[Message] = None
        self.run: Optional[Run] = None

    # --- lifecycle (reference :16-35)

    def create_assistant(self, instructions: str, name: str,
                         model: str = "local",
                         gen: Optional[GenOptions] = None) -> None:
        self.assistant = self.service.create_assistant(
            instructions, name, model, gen)

    def retrieve_assistant(self, assistant_id: str) -> None:
        self.assistant = self.service.retrieve_assistant(assistant_id)

    def create_thread(self) -> None:
        self.thread = self.service.create_thread()

    def retrieve_thread(self, thread_id: str) -> None:
        self.thread = self.service.retrieve_thread(thread_id)

    # --- messages & runs (reference :37-58)

    def add_message(self, content: str) -> None:
        self.message = self.service.add_message(self.thread.id, content)

    def run_assistant(self, instructions: Optional[str] = None,
                      gen: Optional[GenOptions] = None) -> None:
        """``gen``: per-run GenOptions override (e.g. a request-specific
        grammar — the cypher skeleton grammar differs per metapath)."""
        self.run = self.service.create_run(
            self.thread.id, self.assistant.id, instructions, gen)

    def get_run_status(self) -> Run:
        return self.service.retrieve_run(self.run.id)

    # --- listings (reference :60-90)

    def display_response(self) -> None:
        print(self.get_last_message().data[0])

    def get_last_message(self) -> MessageList:
        return self.service.list_messages(self.thread.id, limit=1)

    def get_all_message(self) -> MessageList:
        return self.service.list_messages(self.thread.id, limit=20)

    def get_last_k_message(self, num: int) -> MessageList:
        return self.service.list_messages(self.thread.id, limit=num)

    # --- blocking wait (reference :92-115; polling becomes a pumped future)

    def wait_get_last_k_message(self, num: int = 1) -> Optional[MessageList]:
        run = self.service.wait_run(self.run.id)
        if run.status == RunStatus.COMPLETED:
            msgs = self.get_last_k_message(num)
            # Concurrent runs on a shared thread may have settled in the same
            # pump; make sure data[0] is THIS run's reply (stage code reads
            # data[0].content[0].text.value as the awaited answer).
            if run.response_message_id is not None and (
                    not msgs.data or msgs.data[0].id != run.response_message_id):
                all_msgs = self.service.list_messages(self.thread.id)
                mine = [m for m in all_msgs.data
                        if m.id == run.response_message_id]
                rest = [m for m in all_msgs.data
                        if m.id != run.response_message_id]
                msgs = MessageList(data=(mine + rest)[:num])
            return msgs
        log.warning("run %s terminated with status=%s error=%s",
                    run.id, run.status, run.error)
        return None

    # --- token accounting (reference :117-135, same window semantics)

    def get_token_usage(self, tmin: int, tmax: int, limit: int = 20
                        ) -> Dict[str, int]:
        usage = {"prompt_tokens": 0, "completion_tokens": 0, "total_tokens": 0}
        for run in self.service.list_runs(self.thread.id, limit=limit,
                                          order="desc"):
            if (run.created_at is not None and run.completed_at is not None
                    and tmin <= run.created_at < tmax
                    and tmin <= run.completed_at < tmax):
                for k in usage:
                    usage[k] += run.usage[k]
        return usage


# ---------------------------------------------------------------------------
# persistence (session checkpoint/resume)
# ---------------------------------------------------------------------------


def save_service_state(service: AssistantService, path: str) -> None:
    """Persist assistants, threads (full message history) and TERMINAL runs
    to a JSON file.

    The reference kept OpenAI thread/assistant ids in comments so sessions
    could be resumed by ``retrieve_*`` (reference
    find_srckind_metapath_neo4j.py:52-53, generate_query.py:25-29, live use
    bkp_find...:190-192); here the whole store round-trips instead.
    In-flight runs are not persisted (their engine state is not
    serializable mid-decode); callers should drain first.
    """
    import json

    # peek the id counter without consuming (itertools.count can only be
    # advanced, so re-seed it with the observed value)
    next_id = next(service._ids)
    service._ids = itertools.count(next_id)
    state = {
        "next_id": next_id,              # keeps restored ids collision-free
        "assistants": [
            {"id": a.id, "name": a.name, "instructions": a.instructions,
             "model": a.model,
             "gen": {"max_new_tokens": a.gen.max_new_tokens,
                     "stop": list(a.gen.stop),
                     "forced_prefix": a.gen.forced_prefix,
                     "suffix": a.gen.suffix,
                     "grammar": a.gen.grammar}}
            for a in service.assistants.values()
        ],
        "threads": [
            {"id": t.id,
             "messages": [
                 {"id": m.id, "role": m.role, "content": m.raw_content,
                  "created_at": m.created_at}
                 for m in t.messages
             ]}
            for t in service.threads.values()
        ],
        "runs": [
            {"id": r.id, "thread_id": r.thread_id,
             "assistant_id": r.assistant_id, "status": r.status,
             "created_at": r.created_at, "completed_at": r.completed_at,
             "usage": r.usage, "error": r.error,
             "response_message_id": r.response_message_id}
            for r in service.runs.values() if r.status in RunStatus.TERMINAL
        ],
        "thread_runs": service._thread_runs,
    }
    with open(path, "w") as f:
        json.dump(state, f)


def load_service_state(path: str, backend: LMBackend,
                       run_timeout_s: float = 600.0) -> AssistantService:
    """Rebuild an AssistantService from ``save_service_state`` output.

    Restored threads keep their ids, so stage code holding thread/assistant
    ids across a process restart resumes transparently (and
    ``get_token_usage`` windows over past runs still answer correctly).
    """
    import json

    with open(path) as f:
        state = json.load(f)

    service = AssistantService(backend, run_timeout_s=run_timeout_s)
    service._ids = itertools.count(state["next_id"])
    for a in state["assistants"]:
        g = a.get("gen", {})
        gen = GenOptions(
            max_new_tokens=g.get("max_new_tokens", 256),
            stop=tuple(g.get("stop", ())),
            forced_prefix=g.get("forced_prefix", ""),
            suffix=g.get("suffix", ""),
            grammar=g.get("grammar"))
        service.assistants[a["id"]] = Assistant(
            a["id"], a["name"], a["instructions"], a["model"], gen)
    for t in state["threads"]:
        thread = Thread(t["id"], [
            Message(m["id"], m["role"], m["content"], m["created_at"])
            for m in t["messages"]
        ])
        service.threads[thread.id] = thread
    for r in state["runs"]:
        run = Run(r["id"], r["thread_id"], r["assistant_id"],
                  status=r["status"], created_at=r["created_at"],
                  completed_at=r["completed_at"], usage=r["usage"],
                  error=r["error"])
        run.response_message_id = r["response_message_id"]
        service.runs[run.id] = run
    terminal = set(service.runs)
    service._thread_runs = {
        tid: [rid for rid in rids if rid in terminal]
        for tid, rids in state["thread_runs"].items()
    }
    for tid in service.threads:
        service._thread_runs.setdefault(tid, [])
    return service
