"""LM backends for the assistants service.

``EngineBackend`` is the real path: requests stream through the
continuous-batching engine, so concurrent runs (e.g. stage 3's
per-entity audits, SURVEY §3.4) share decode steps in one batch.

``EchoBackend`` is a trivial deterministic backend for serve-layer tests.
The RCA-aware scripted oracle lives in rca/oracle.py (it needs the stage
prompt contracts, which belong to the rca layer).

Forced prefixes implement the fenced-output contracts on the engine side:
the fence opener (e.g. "```json\\n") is prefilled as forced tokens and the
closing fence is a stop string, so the model cannot emit an unfenced reply —
this kills the JSONDecodeError retry loop the reference needs
(test_all.py:70-76).
"""

from __future__ import annotations

import base64
import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Protocol, Sequence, Set, Tuple

from k8s_llm_rca_tpu.engine.constrain import make_grammar
from k8s_llm_rca_tpu.engine.engine import EngineBase
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.utils import pages, wal
from k8s_llm_rca_tpu.utils.logging import METRICS
from k8s_llm_rca_tpu.utils.tokenizer import Tokenizer


class Priority:
    """Request priority classes (small ints: LOWER value = MORE urgent,
    so ``sorted()`` over (priority, seq_id) is the scheduling order).
    The engine buckets anything <= CRITICAL as critical and anything
    >= BATCH as batch for the per-priority queue gauges."""

    CRITICAL = 0      # interactive / SLO-bound: never shed by the router
    NORMAL = 1        # default
    BATCH = 2         # offline sweeps: first shed under backpressure


@dataclass(frozen=True)
class GenOptions:
    max_new_tokens: int = 256
    stop: Tuple[str, ...] = ()
    forced_prefix: str = ""     # emitted verbatim, prefilled as forced tokens
    suffix: str = ""            # appended verbatim after generation stops
    # grammar-constrained decode of the BODY (engine/constrain.py): "json"
    # guarantees the generated text parses; a schema dict
    # (constrain.SchemaGrammar) additionally forces the exact shape
    # (structured outputs).  Composes with forced_prefix / suffix carrying
    # the fences.  None = unconstrained.
    grammar: Optional[object] = None
    # routing metadata: the name of the assistant the run belongs to,
    # populated by AssistantService.create_run.  Engine backends ignore it;
    # the scripted oracle routes on it (prompt-substring routing is brittle
    # to harmless rewordings and kept only as its fallback).
    assistant_name: str = ""
    # cluster routing metadata: the session key (the thread id) the run
    # belongs to, populated by AssistantService.create_run.  Single-engine
    # backends ignore it; the cluster router pins a session to one replica
    # (cluster/router.py affinity) so a thread's monotonically growing
    # prompt keeps hitting the replica whose prefix cache already holds
    # its history.
    session: str = ""
    # overload scheduling (docs/serving.md "overload & priorities"):
    # ``priority`` orders engine admission and preemption-victim selection
    # (Priority.CRITICAL/NORMAL/BATCH; lower = more urgent) and tiers the
    # cluster router's backpressure (BATCH sheds before NORMAL, CRITICAL
    # never sheds).  ``deadline_s`` is a per-run budget in seconds on the
    # injectable clock (faults.plan.VirtualClock under chaos); the engine
    # reaps an expired sequence inside its own tick — pages freed
    # immediately, finish_reason "expired" — instead of waiting for the
    # serve-layer poll.  None = serve default (RCAConfig.run_timeout_s).
    priority: int = Priority.NORMAL
    deadline_s: Optional[float] = None


class BudgetError(ValueError):
    """The effective token budget cannot hold the grammar's minimal
    document — no valid output exists, so retrying the SAME request is
    futile by construction (callers should fall back, not retry)."""


@dataclass
class BackendResult:
    text: str
    completion_tokens: int
    prompt_tokens: Optional[int] = None   # actual prefilled tokens if known
    error: Optional[str] = None
    # the engine reaped the sequence past its deadline (finish_reason
    # "expired"): the service settles the run as EXPIRED, not FAILED
    expired: bool = False
    # the engine's own lifecycle record of the sequence
    # (engine.SequenceTiming); None from backends that run no engine in
    # this process
    timing: Optional[object] = None


class LMBackend(Protocol):
    def start(self, prompt: str, opts: GenOptions) -> int: ...
    def pump(self) -> Dict[int, BackendResult]: ...
    def busy(self, handle: int) -> bool: ...
    def cancel(self, handle: int) -> None: ...
    def count_tokens(self, text: str) -> int: ...


def _assert_fully_addressable(engine) -> None:
    """The engine's threaded serving driver (EngineBackend under worker
    threads, e.g. ``sweeps.run_file --workers``) has nondeterministic tick
    interleaving, while ``host_np``'s process_allgather path requires every
    process to issue identical host syncs in identical order — driving a
    process-spanning mesh through this backend would misalign the
    collective and hang/corrupt all processes.  Multi-process meshes must
    use a deterministic single-threaded SPMD driver instead
    (tests/test_distributed.py); fail loudly at construction."""
    import jax

    leaves = list(jax.tree.leaves(engine.params))
    cache = getattr(engine, "cache", None)
    if cache is None:
        cache = getattr(engine, "pool", None)
    if cache is not None:
        leaves += jax.tree.leaves(cache)
    for leaf in leaves:
        if not getattr(leaf, "is_fully_addressable", True):
            raise ValueError(
                "EngineBackend requires a fully-addressable engine mesh: "
                "an array spans non-addressable devices (multi-process "
                "mesh), and this backend's threaded drivers tick the "
                "engine in nondeterministic order, which would misalign "
                "host_np's process_allgather across the cluster.  Drive "
                "multi-process meshes with a deterministic single-"
                "threaded SPMD loop instead (see engine.host_np and "
                "tests/test_distributed.py).")


class EngineBackend:
    """Continuous-batching engine behind the assistants API.

    Fault injection (faults/inject.py): when a plan is armed, every
    ``start`` polls ``SITE_BACKEND`` — "error" fails the run at the next
    pump, "budget" raises BudgetError at submission, "stall" accepts the
    run but never progresses it (a hung engine), so only the serve-layer
    deadline ends it; ``cancel`` then reaps it.  Cancelling any live run
    retires its engine sequence immediately (``EngineBase.cancel_seq``),
    freeing its batch slot and — on the paged engine — its pages.
    """

    def __init__(self, engine: EngineBase):
        _assert_fully_addressable(engine)
        self.engine = engine
        self.tokenizer = engine.tokenizer
        self._handles = itertools.count()
        self._seq_to_handle: Dict[int, int] = {}
        self._handle_seq: Dict[int, int] = {}
        self._opts: Dict[int, GenOptions] = {}
        self._live: Dict[int, bool] = {}
        self._failed: Dict[int, str] = {}    # injected run failures
        self._stalled: Set[int] = set()      # injected stalls (no result)

    def start(self, prompt: str, opts: GenOptions) -> int:
        fault = None
        if inject._ARMED is not None:
            fault = inject._ARMED.poll(inject.SITE_BACKEND)
        if fault is not None and fault.kind == "budget":
            raise BudgetError(
                f"injected budget fault at {fault.site}[{fault.index}]: "
                f"no valid output exists under this budget")
        if fault is not None and fault.kind == "error":
            # the run "fails" engine-side: surfaces as BackendResult.error
            # at the next pump, which the service maps to status=failed
            handle = next(self._handles)
            self._failed[handle] = (
                f"injected engine-run failure at "
                f"{fault.site}[{fault.index}]")
            self._live[handle] = True
            return handle
        if fault is not None and fault.kind == "stall":
            # a hung run: accepted, never progressed — stays busy until
            # the serve-layer deadline cancels it.  Nothing is submitted
            # to the engine, so the stall cannot perturb tick counts (the
            # soak's byte-identity depends on that)
            handle = next(self._handles)
            self._stalled.add(handle)
            self._live[handle] = True
            return handle
        handle = next(self._handles)
        ids = self.tokenizer.encode(prompt + opts.forced_prefix, add_bos=True)
        grammar = make_grammar(opts.grammar, self.tokenizer,
                               prefer_native=self.engine.engine_cfg.native)
        # how this run will decode: a compiled DFA rides the jitted scan,
        # an interpreted FSM holds the WHOLE batch to one step per tick
        # (engine._scan_chunk)
        mode = ("free" if grammar is None
                else "dfa" if getattr(grammar, "tables", None) is not None
                else "interpreted")
        METRICS.inc(f"serve.grammar.{mode}.{opts.assistant_name}")
        min_budget = getattr(grammar, "min_budget", None)
        if min_budget is not None:
            # check the budget AFTER engine clamping: a long prompt shrinks
            # max_new below the request (engine._clamp_prompt), and a
            # sub-minimal effective budget can only produce truncated,
            # unparseable output — fail loudly instead
            _, effective = self.engine._clamp_prompt(ids,
                                                     opts.max_new_tokens)
            if effective < min_budget():
                raise BudgetError(
                    f"effective token budget {effective} (requested "
                    f"{opts.max_new_tokens}, clamped by prompt length "
                    f"{len(ids)} vs cache cap "
                    f"{self.engine.engine_cfg.max_seq_len}) cannot hold "
                    f"the schema's minimal document ({min_budget()} tokens "
                    f"worst case); no valid output exists under this "
                    f"budget")
        # a grammar owns termination (forced EOS when the value closes);
        # stop strings must not also apply — e.g. "```" is a legal substring
        # INSIDE a JSON string, and a stop match there would truncate the
        # body mid-string and break the parse guarantee
        stop = () if grammar is not None else opts.stop
        seq_id = self.engine.submit(
            ids, max_new_tokens=opts.max_new_tokens, stop_strings=stop,
            grammar=grammar, priority=opts.priority,
            deadline_s=opts.deadline_s)
        self._seq_to_handle[seq_id] = handle
        self._handle_seq[handle] = seq_id
        self._opts[handle] = opts
        self._live[handle] = True
        return handle

    def pump(self) -> Dict[int, BackendResult]:
        results: Dict[int, BackendResult] = {}
        for handle in list(self._failed):
            msg = self._failed.pop(handle)
            if self._live.pop(handle, False):
                results[handle] = BackendResult("", 0, error=msg)
        if self._stalled and inject._ARMED is not None:
            # a stalled run only ends via the serve deadline; advance the
            # plan's virtual clock so that deadline arrives after a
            # DETERMINISTIC number of pumps instead of wall seconds
            inject._ARMED.clock.sleep(0.05)
        if not self.engine.has_work:
            if self._live:
                # pumped with live handles but nothing decodable: every
                # live run is stalled (injected fault) or orphaned.  Count
                # it so sweep timelines show WAITED ticks, not just busy
                # ones (registered obs site; TickSample.idle_ticks picks
                # the counter up on the next real tick).
                self.engine._count("engine.idle_ticks")
                obs_trace.event("engine.idle_ticks", live=len(self._live))
            return results
        for res in self.engine.step():
            handle = self._seq_to_handle.pop(res.seq_id, None)
            if handle is None:
                continue
            self._handle_seq.pop(handle, None)
            opts = self._opts.pop(handle, GenOptions())
            live = self._live.pop(handle, False)
            if not live:                   # cancelled: drop, don't leak
                continue
            text = opts.forced_prefix + res.text + opts.suffix
            if res.finish_reason == "expired":
                results[handle] = BackendResult(
                    text=text,
                    completion_tokens=res.completion_tokens,
                    prompt_tokens=res.prompt_tokens,
                    error="deadline exceeded (engine deadline reap)",
                    expired=True, timing=res.timing)
                continue
            results[handle] = BackendResult(
                text=text,
                completion_tokens=res.completion_tokens,
                prompt_tokens=res.prompt_tokens,
                timing=res.timing)
        if results:
            obs_trace.event("backend.settled", n=len(results))
        return results

    def busy(self, handle: int) -> bool:
        return self._live.get(handle, False)

    def cancel(self, handle: int) -> None:
        # abort for real: the engine sequence retires NOW (the paged
        # engine frees its pages through the normal _retire path), so an
        # expired/cancelled run cannot leak allocator blocks or keep
        # occupying a batch slot
        if handle not in self._live and handle not in self._failed:
            return
        self._failed.pop(handle, None)
        self._stalled.discard(handle)
        self._live.pop(handle, None)
        self._opts.pop(handle, None)
        seq_id = self._handle_seq.pop(handle, None)
        if seq_id is not None:
            self._seq_to_handle.pop(seq_id, None)
            self.engine.cancel_seq(seq_id)

    def count_tokens(self, text: str) -> int:
        return self.tokenizer.count(text)

    def queue_depth(self) -> int:
        """Live runs on this backend — the router's load-balancing
        signal (cluster/router.py picks the alive replica with the
        smallest depth for a session it has not seen)."""
        return len(self._live)

    def occupancy(self) -> float:
        """Fraction of the engine's batch slots occupied (0..1) — the
        per-replica gauge the router mirrors into the tick timeline and
        Prometheus ``cluster_replica_occupancy``."""
        return (len(self.engine._active)
                / max(1, self.engine.engine_cfg.max_batch))

    def adopt_sequences(self, snap: Dict[str, object],
                        opts: Sequence[GenOptions]) -> List[int]:
        """Adopt another engine's ``snapshot_sequences`` export into THIS
        backend: the cluster failover path (cluster/router.py
        ``drain_replica``).  Three things make adoption different from a
        raw ``restore_sequences`` on the target engine:

        - seq ids are REMAPPED into the target engine's namespace (the
          replicas' independent ``_seq_counter``s collide, and
          ``restore_sequences`` raises loudly on collision by design);
        - grammars are recompiled from each run's GenOptions SPEC and
          rebuilt by advancing over the generated tokens (compiled FSMs
          are host objects owned by the dead replica);
        - the source RNG key is dropped (``rng_key: None``): migration
          must never clobber the target replica's key mid-decode —
          greedy parity holds regardless, by the snapshot contract.

        Fresh backend handles are registered per sequence so ``pump``
        settles the migrated runs exactly like native ones (results for
        unknown seq_ids are dropped there — adoption must come through
        here, never through the engine directly).  Returns the new
        handles in snapshot order."""
        seqs = list(snap.get("sequences", []))
        if len(opts) != len(seqs):
            raise ValueError(
                f"adopt_sequences needs one GenOptions per snapshotted "
                f"sequence: got {len(opts)} for {len(seqs)}")
        remapped = []
        grammars: Dict[int, object] = {}
        for s, o in zip(seqs, opts):
            new_id = next(self.engine._seq_counter)
            s2 = dict(s)
            s2["seq_id"] = new_id
            if s.get("grammar"):
                if o.grammar is None:
                    raise ValueError(
                        f"seq {s['seq_id']} was grammar-constrained but "
                        f"its GenOptions carries no grammar spec; the "
                        f"FSM is rebuilt from the spec at adoption")
                grammars[new_id] = make_grammar(
                    o.grammar, self.tokenizer,
                    prefer_native=self.engine.engine_cfg.native)
            remapped.append(s2)
        self.engine.restore_sequences(
            {"rng_key": None, "sequences": remapped}, grammars=grammars)
        handles: List[int] = []
        for s2, o in zip(remapped, opts):
            handle = next(self._handles)
            seq_id = s2["seq_id"]
            self._seq_to_handle[seq_id] = handle
            self._handle_seq[handle] = seq_id
            self._opts[handle] = o
            self._live[handle] = True
            handles.append(handle)
        return handles

    def snapshot_sequences(self) -> Tuple[Dict[str, object], List[int]]:
        """Snapshot every live engine sequence for migration, returning
        ``(snapshot, handles)`` — the JSON-safe engine export plus THIS
        backend's handle for each snapshotted sequence, in snapshot
        order.  The backend-level seam ``ClusterRouter.drain_replica``
        works through (proc replicas answer it over the wire — the
        router must not reach for ``engine._seq_to_handle`` internals
        that live in another process).  Resident prefix pages are
        published to the shared PrefixStore FIRST, so the adopter's
        re-prefill promotes them by h2d page writes (the warm-start
        contract, docs/cluster.md)."""
        if hasattr(self.engine, "flush_prefix_store"):
            self.engine.flush_prefix_store()
        snap = self.engine.snapshot_sequences()
        handles = [self._seq_to_handle[s["seq_id"]]
                   for s in snap.get("sequences", [])]
        return snap, handles

    def export_run(self, handle: int) -> Optional[Dict[str, object]]:
        """Per-run EXPORT for the disaggregated handoff
        (cluster/disagg.py): freeze ONE live run and return its wire
        frame ``{"seq": <snapshot entry>, "kv": None | {"b64", "length",
        "cur_token"}}`` — the entry is the durable token state, the kv
        block (when the paged engine could spill it) is the CRC-framed
        ``utils/pages.py`` disk codec, base64'd so the frame stays
        JSON-safe over the proc transports.  The run STAYS live here
        until the adopter acks and the caller cancels this handle
        (RELEASE).  None = nothing to export right now: unknown/settled
        handle (the run raced to completion — not a retry), an injected
        stall/failure, or an engine state that cannot freeze this pump
        (chunked prefill in flight).  Never raises for a missing run:
        the handoff queue self-cleans on the next pump."""
        seq_id = self._handle_seq.get(handle)
        if seq_id is None or not self._live.get(handle, False):
            return None
        if hasattr(self.engine, "flush_prefix_store"):
            # publish resident prefix pages first so a re-prefill after
            # a failed handoff is a mostly-HIT path on any replica
            self.engine.flush_prefix_store()
        exported = self.engine.export_run(seq_id)
        if exported is None:
            return None
        entry, kv = exported
        frame: Dict[str, object] = {"seq": entry, "kv": None}
        if kv is not None:
            try:
                blob = pages.encode_page_record(
                    {k: kv[k] for k in
                     ("n_pages",) + pages.record_fields(kv)})
            except ValueError:
                blob = None     # record too large to frame: entry-only
            if blob is not None:
                b64 = base64.b64encode(blob).decode("ascii")
                if len(b64) + 4096 <= wal.MAX_RECORD_SIZE:
                    frame["kv"] = {"b64": b64,
                                   "length": int(kv["length"]),
                                   "cur_token": int(kv["cur_token"])}
        return frame

    def adopt_run(self, frame: Dict[str, object],
                  opts: GenOptions) -> int:
        """Per-run ADOPT: validate the ENTIRE frame before any engine
        state moves, then re-admit the run under a fresh seq id/handle.
        A malformed entry or a torn/corrupt kv blob raises ValueError —
        the transfer is discarded whole and the caller retries from the
        still-pinned source; this backend is left untouched.  A kv
        record that decodes but was gathered under a different PAGE
        SIZE is re-chunked deterministically by the engine's adopt
        (``engine.handoff_kv_relayout``); one whose dtype/kv_dim/layer
        geometry differs raises ValueError (a misconfigured tier pair —
        TierRouter refuses to build one); torn frames (length mismatch,
        page overflow) drop to a counted re-prefill, byte-identical
        output."""
        entry = frame.get("seq") if isinstance(frame, dict) else None
        if (not isinstance(entry, dict)
                or not {"seq_id", "prompt_ids", "generated",
                        "remaining_new_tokens",
                        "stop_strings"} <= set(entry)):
            raise ValueError(
                "torn handoff frame: malformed sequence entry")
        rec = None
        kv = frame.get("kv")
        if kv is not None:
            try:
                blob = base64.b64decode(kv["b64"], validate=True)
                rec = pages.decode_page_record(blob)
            except Exception:
                raise ValueError(
                    "torn handoff frame: kv blob failed base64/frame "
                    "decoding; transfer discarded whole")
            if rec is None:
                raise ValueError(
                    "torn handoff frame: kv page record failed CRC/"
                    "layout checks; transfer discarded whole")
            rec["n_shared"] = 0
            rec["shared_pages"] = []
            rec["length"] = int(kv["length"])
            rec["cur_token"] = int(kv["cur_token"])
        new_id = next(self.engine._seq_counter)
        grammar = None
        if entry.get("grammar"):
            if opts.grammar is None:
                raise ValueError(
                    f"seq {entry['seq_id']} was grammar-constrained but "
                    f"its GenOptions carries no grammar spec; the FSM "
                    f"is rebuilt from the spec at adoption")
            grammar = make_grammar(
                opts.grammar, self.tokenizer,
                prefer_native=self.engine.engine_cfg.native)
        self.engine.adopt_run(dict(entry, seq_id=new_id), kv=rec,
                              grammar=grammar)
        handle = next(self._handles)
        self._seq_to_handle[new_id] = handle
        self._handle_seq[handle] = new_id
        self._opts[handle] = opts
        self._live[handle] = True
        return handle

    def host_counters(self) -> Dict[str, float]:
        """Cumulative host<->device traffic counters of the backing
        engine (engine.h2d_uploads / d2h_syncs / dispatches /
        decode_tokens — docs/performance.md).  The serve layer exposes
        them so bench/ops can compute syncs-per-decoded-token without
        reaching into engine internals.  With ``host_overlap`` engines
        note the counters run one flush behind the last committed token
        (lagged commit); read after drain (``has_work`` False) for exact
        totals."""
        counts = getattr(self.engine, "_counts", None) or {}
        return {key: float(counts.get(key, 0.0))
                for key in ("engine.h2d_uploads", "engine.d2h_syncs",
                            "engine.dispatches", "engine.decode_tokens")}


class EchoBackend:
    """Deterministic test backend: replies with a fixed or prompt-derived
    string after ``delay_pumps`` pump calls (to exercise the run-state
    machine's in_progress window)."""

    def __init__(self, tokenizer: Tokenizer, reply: Optional[str] = None,
                 delay_pumps: int = 0, fail: bool = False):
        self.tokenizer = tokenizer
        self.reply = reply
        self.fail = fail
        self.delay_pumps = delay_pumps
        self._handles = itertools.count()
        self._inflight: Dict[int, Tuple[str, GenOptions, int]] = {}

    def start(self, prompt: str, opts: GenOptions) -> int:
        handle = next(self._handles)
        self._inflight[handle] = (prompt, opts, self.delay_pumps)
        return handle

    def pump(self) -> Dict[int, BackendResult]:
        results: Dict[int, BackendResult] = {}
        for handle in list(self._inflight):
            prompt, opts, remaining = self._inflight[handle]
            if remaining > 0:
                self._inflight[handle] = (prompt, opts, remaining - 1)
                continue
            del self._inflight[handle]
            if self.fail:
                results[handle] = BackendResult("", 0, error="echo backend failure")
                continue
            text = self.reply if self.reply is not None else f"echo: {prompt[-64:]}"
            text = opts.forced_prefix + text + opts.suffix
            results[handle] = BackendResult(
                text=text, completion_tokens=self.tokenizer.count(text))
        return results

    def busy(self, handle: int) -> bool:
        return handle in self._inflight

    def cancel(self, handle: int) -> None:
        self._inflight.pop(handle, None)

    def count_tokens(self, text: str) -> int:
        return self.tokenizer.count(text)

    def queue_depth(self) -> int:
        # same load signal EngineBackend exposes, so the cluster router's
        # capacity tiering is testable without a real engine
        return len(self._inflight)
