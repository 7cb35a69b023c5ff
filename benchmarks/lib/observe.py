"""The benchmark's seat between the serve layer and the engine.

``ObservedBackend`` is an ``LMBackend`` that wraps the program's
``EngineBackend``: every ``start`` and every ``pump`` (one engine tick) goes
through it, so it can time each request on the benchmark's own clock without
a line of instrumentation inside the program.  The engine records no
per-request time and streams nothing; a token can leave it only at a tick
boundary, so after each tick the live-sequence table is read (read-only:
``engine._active[slot].seq_id`` / ``.generated``, and
``EngineBackend._handle_seq`` for the handle's sequence) and the tick's end
is the time a streaming client would have seen the new tokens.

``SteeredEngineBackend`` adds the agent-loop steering: the scripted oracle
decides WHAT each stage answers, the engine does every prefill and every
constrained token of an answer of that length.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax


class WindowClosed(BaseException):
    """Raised from ``pump`` at the window's close to end a driver that has no
    stop hook (``SweepScheduler.run``).  A BaseException, so that no
    ``except Exception`` of the program's retry ladders swallows it."""


@dataclass
class Req:
    handle: int
    seq_id: Optional[int]
    assistant: str
    session: str
    constrained: bool
    schema: Any
    prefix: str
    suffix: str
    t_submit: float
    t_due: float
    t_admit_tick: Optional[float] = None   # start of the tick that admitted it
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    n_seen: int = 0
    # (tick end, tokens held) at every tick that brought new tokens
    marks: List[Tuple[float, int]] = field(default_factory=list)
    tokens: int = 0
    prompt_tokens: Optional[int] = None
    error: Optional[str] = None
    valid: Optional[bool] = None

    @property
    def failed(self) -> bool:
        return self.error is not None or self.valid is False


def validates(schema: Any, body: str) -> bool:
    """Does ``body`` satisfy the structured-output ``schema``
    (engine/constrain.py's dialect)?  Written apart from the grammar code it
    checks: JSON is parsed by ``json``, shapes are walked here."""
    if schema is None:
        return True
    if schema == "json":
        return _parses(body)
    if isinstance(schema, dict) and schema.get("type") == "choice":
        options = schema.get("options", [])
        if all(isinstance(o, str) for o in options):
            return body in options
        return True
    if isinstance(schema, dict) and schema.get("type") == "seq":
        return True            # raw template: no independent reading of it
    try:
        return _conforms(schema, json.loads(body))
    except ValueError:
        return False


def _parses(body: str) -> bool:
    try:
        json.loads(body)
        return True
    except ValueError:
        return False


def _conforms(schema: Dict[str, Any], value: Any) -> bool:
    if "enum" in schema:
        return value in schema["enum"]
    if "const" in schema:
        return value == schema["const"]
    t = schema.get("type")
    if t == "object":
        props = schema.get("properties", [])
        return (isinstance(value, dict)
                and list(value) == [k for k, _ in props]
                and all(_conforms(s, value[k]) for k, s in props))
    if t == "array":
        return (isinstance(value, list)
                and schema.get("min_items", 0) <= len(value)
                <= schema.get("max_items", len(value))
                and all(_conforms(schema["items"], v) for v in value))
    if t == "string":
        return (isinstance(value, str)
                and len(value) <= schema.get("max_len", len(value)))
    if t == "integer":
        return isinstance(value, int) and not isinstance(value, bool)
    if t == "boolean":
        return isinstance(value, bool)
    return True                # a node this walker does not know: parsed JSON


class ObservedBackend:
    """``LMBackend`` over ``EngineBackend`` that times every request."""

    def __init__(self, inner, clock=time.perf_counter):
        self.inner = inner
        self.engine = inner.engine
        self.tokenizer = inner.tokenizer
        self.clock = clock
        self.reqs: Dict[int, Req] = {}
        self._by_seq: Dict[int, Req] = {}
        # per engine tick: (start, end, tokens committed, live sequences,
        # tokens of context the live sequences hold, decode steps taken)
        self.ticks: List[Tuple[float, float, int, int, int, int]] = []
        self.stop_at: Optional[float] = None
        self.due: Optional[float] = None    # set by an open-loop generator
        self.after_tick: List[Any] = []     # callables of the tick's end time

    # ------------------------------------------------------------ protocol

    def start(self, prompt: str, opts) -> int:
        return self._start(prompt, opts, opts)

    def _start(self, prompt: str, opts, engine_opts) -> int:
        t = self.clock()
        handle = self.inner.start(prompt, engine_opts)
        seq_id = self.inner._handle_seq.get(handle)
        req = Req(handle, seq_id, opts.assistant_name, opts.session,
                  opts.grammar is not None, opts.grammar,
                  opts.forced_prefix, opts.suffix, t,
                  self.due if self.due is not None else t)
        self.due = None
        self.reqs[handle] = req
        if seq_id is not None:
            self._by_seq[seq_id] = req
        return handle

    def pump(self):
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.pump"):
            results = self.inner.pump()
        t1 = self.clock()
        self._observe(results, t0, t1)
        for fn in self.after_tick:
            fn(t1)
        if self.stop_at is not None and t1 >= self.stop_at:
            raise WindowClosed()
        return self._deliver(results)

    def busy(self, handle: int) -> bool:
        return self.inner.busy(handle)

    def cancel(self, handle: int) -> None:
        self.inner.cancel(handle)

    def count_tokens(self, text: str) -> int:
        return self.inner.count_tokens(text)

    # ------------------------------------------------------------ observing

    def _deliver(self, results):
        return results

    def _observe(self, results, t0: float, t1: float) -> None:
        committed = live = context = steps = 0
        for st in self.engine._active.values():
            live += 1
            context += st.prompt_tokens + len(st.generated)
            req = self._by_seq.get(st.seq_id)
            if req is not None:
                new = self._seen(req, len(st.generated), t0, t1)
                committed += new
                steps = max(steps, new)
        for handle, res in results.items():
            req = self.reqs.get(handle)
            if req is None:
                continue
            new = self._seen(req, res.completion_tokens, t0, t1)
            committed += new
            steps = max(steps, new)
            req.tokens = res.completion_tokens
            req.prompt_tokens = res.prompt_tokens
            req.t_done = t1
            req.error = res.error
            if req.error is None and req.constrained:
                body = res.text[len(req.prefix):len(res.text)
                                - len(req.suffix)]
                req.valid = validates(req.schema, body)
            self._by_seq.pop(req.seq_id, None)
        self.ticks.append((t0, t1, committed, live, context, steps))

    @staticmethod
    def _seen(req: Req, n: int, t0: float, t1: float) -> int:
        if req.t_admit_tick is None:
            req.t_admit_tick = t0
        if n <= req.n_seen:       # nothing new (or a preemption's restart)
            return 0
        new = n - req.n_seen
        if req.t_first is None:
            req.t_first = t1
        req.n_seen = n
        req.marks.append((t1, n))
        return new


class SteeredEngineBackend(ObservedBackend):
    """The engine does the work, the oracle decides what happens next.

    ``start`` asks ``OracleBackend`` for the stage's answer and submits the
    same prompt, grammar and options to the engine with ``max_new_tokens =
    max(tokens of the oracle's body, the grammar's minimal budget)``;
    ``pump`` returns the engine's counts with the oracle's text.  With
    seeded random weights the agent loop's control flow would otherwise be
    random; steered, every incident walks locate -> Cypher -> audits ->
    report with output lengths a trained model would emit (the fixed-length,
    ignore-EOS device of serving benchmarks, for an agent loop)."""

    def __init__(self, inner, oracle, clock=time.perf_counter):
        super().__init__(inner, clock)
        self.oracle = oracle
        self._answers: Dict[int, str] = {}

    def start(self, prompt: str, opts) -> int:
        import dataclasses

        from k8s_llm_rca_tpu.engine.constrain import make_grammar

        answer = self._ask(prompt, opts)
        body = answer[len(opts.forced_prefix):len(answer) - len(opts.suffix)]
        budget = max(1, len(self.tokenizer.encode(body)))
        grammar = make_grammar(opts.grammar, self.tokenizer,
                               prefer_native=self.engine.engine_cfg.native)
        if grammar is not None:
            budget = max(budget, grammar.min_budget())
        handle = self._start(
            prompt, opts, dataclasses.replace(opts, max_new_tokens=budget))
        self._answers[handle] = answer
        return handle

    def _ask(self, prompt: str, opts) -> str:
        handle = self.oracle.start(prompt, opts)
        return self.oracle.pump()[handle].text

    def _deliver(self, results):
        import dataclasses

        return {h: dataclasses.replace(r, text=self._answers.pop(h, r.text))
                if r.error is None else r
                for h, r in results.items()}
