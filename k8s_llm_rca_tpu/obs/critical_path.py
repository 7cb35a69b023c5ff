"""Per-run critical-path attribution over the merged fleet trace.

The reference explains a slow RCA run with a wall-clock print around the
whole pipeline (reference test_all.py:143-151) — one number, no story.
With the fleet flight recorder (span propagation + worker telemetry
shipping, cluster/proc.py) a single run's causal tree spans router →
wire → worker engine ticks → handoff → decode tier, so its end-to-end
latency can be DECOMPOSED instead of reported: this module is the pure
post-processing pass that does it.

For every settled ``serve.run`` span it attributes each elementary
interval of the run's [t0, t1] window to exactly one named segment:

    cp.handoff.export / cp.handoff.adopt / cp.handoff.release
        the three phases of a KV handoff (cluster/disagg.py spans)
    cp.relink        link outage: cluster.net.partition -> .relink
    cp.retry         retry/degradation ladder activity
    cp.prefill       the run's own slot grant -> first token
    cp.decode        the run's own first token -> newest token
    cp.wire          cluster.proc.rpc spans (frame round-trips)
    cp.queue_wait    the unattributed residual — time the run spent
                     waiting for anything above to happen to IT

``cp.prefill`` and ``cp.decode`` come from the engine's record of THAT
request where there is one: a ``serve.run`` span whose ``seq`` /
``seq_t0`` args name an ``engine.request`` span (engine.py
``_settle_timing``) takes the record's stamps, so a batch-mate's prefill
is never counted as the run's own and the time the host spends blocked
on an asynchronous dispatch is decode, not queue wait.  A run without a
record (echo and oracle workers, spans shipped from worker processes)
falls back to overlaying every ``engine.prefill`` / ``engine.decode_step``
span, which time the DISPATCH and carry no request id.

Overlaps resolve by fixed priority (SEGMENT_PRIORITY order: a decode
step inside an RPC inside a relink outage counts as the outage — the
outermost cause the operator can act on).  All arithmetic is integer
microseconds on the same ``_us`` grid as obs/export.py, so the segments
of every run sum EXACTLY to its end-to-end total — the acceptance bar,
and the reason this never uses floats.

Kept OUT of ``report_bytes``: the decomposition reaches users via
``AssistantService.usage_for_runs(..., critical_path=True)`` and the
pipelined sweep's stats block, never the byte-compared report body.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

# highest-priority first: when intervals overlap, the earliest name in
# this tuple wins the elementary interval
SEGMENT_PRIORITY: Tuple[str, ...] = (
    "cp.handoff.export",
    "cp.handoff.adopt",
    "cp.handoff.release",
    "cp.relink",
    "cp.retry",
    "cp.prefill",
    "cp.decode",
    "cp.wire",
)

# every segment name; cp.queue_wait is the exact integer residual, never
# an interval source
SEGMENTS: Tuple[str, ...] = SEGMENT_PRIORITY + ("cp.queue_wait",)

_SPAN_SEGMENT = {
    "cluster.handoff.export": "cp.handoff.export",
    "cluster.handoff.adopt": "cp.handoff.adopt",
    "cluster.handoff.release": "cp.handoff.release",
    "engine.prefill": "cp.prefill",
    "engine.decode_step": "cp.decode",
    "cluster.proc.rpc": "cp.wire",
    "cluster.mttr": "cp.retry",
}


def _us(t: float) -> int:
    # the exporter's microsecond grid (obs/export.py::_us): sharing it
    # keeps this pass consistent with what the Chrome trace displays
    return int(round(float(t) * 1e6))


def _intervals(tracer) -> List[Tuple[int, int, str]]:
    """Labeled (t0_us, t1_us, segment) intervals from the merged tree:
    parent spans, shipped worker spans (Tracer.remote wire dicts), and
    the synthesized link-outage intervals (partition event -> relink
    event per replica)."""
    ivs: List[Tuple[int, int, str]] = []
    for sp in tracer.spans:
        seg = _SPAN_SEGMENT.get(sp.name)
        if seg is not None and sp.t1 is not None:
            ivs.append((_us(sp.t0), _us(sp.t1), seg))
    for bucket in (getattr(tracer, "remote", None) or {}).values():
        for sp in bucket["spans"]:
            seg = _SPAN_SEGMENT.get(sp.get("name"))
            if seg is not None and sp.get("t1") is not None:
                ivs.append((_us(sp["t0"]), _us(sp["t1"]), seg))
    downs: Dict[Any, int] = {}
    for ev in tracer.events:
        if ev.name == "cluster.net.partition":
            downs.setdefault(ev.args.get("replica"), _us(ev.ts))
        elif ev.name == "cluster.net.relink":
            t0 = downs.pop(ev.args.get("replica"), None)
            if t0 is not None:
                ivs.append((t0, _us(ev.ts), "cp.relink"))
    return ivs


_ENGINE_SEGMENTS = ("cp.prefill", "cp.decode")


def _requests(tracer) -> Dict[Tuple[Any, int], List[Tuple[int, int, str]]]:
    """(seq, arrival_us) -> that request's own prefill and decode
    intervals, from the ``engine.request`` spans.  The arrival stamp is
    part of the key because every engine numbers its sequences from 0."""
    out: Dict[Tuple[Any, int], List[Tuple[int, int, str]]] = {}
    for sp in tracer.spans:
        if sp.name != "engine.request" or sp.t1 is None:
            continue
        t_admitted = sp.t0 + sp.args["queue_wait_s"]
        t_first = _us(t_admitted + sp.args["prefill_s"])
        out[(sp.args["seq"], _us(sp.t0))] = [
            (_us(t_admitted), t_first, "cp.prefill"),
            (t_first, _us(sp.t1), "cp.decode")]
    return out


def critical_path(tracer, runs: Optional[Any] = None
                  ) -> Dict[Any, Dict[str, Any]]:
    """Decompose every settled run's end-to-end latency into SEGMENTS.

    Returns ``{run_id: breakdown}`` where ``breakdown["segments_us"]``
    maps each segment name to integer microseconds summing exactly to
    ``breakdown["total_us"]``.  ``runs`` restricts to those run ids.
    Pure: the tracer is only read.
    """
    ivs = _intervals(tracer)
    not_engine = [iv for iv in ivs if iv[2] not in _ENGINE_SEGMENTS]
    requests = _requests(tracer)
    retry_ts = [_us(e.ts) for e in tracer.events
                if e.name == "resilience.retry"
                or (e.name == "cluster.handoff"
                    and e.args.get("retried"))]
    degraded_ts = [_us(e.ts) for e in tracer.events
                   if e.name == "resilience.degraded"]
    want = set(runs) if runs is not None else None
    out: Dict[Any, Dict[str, Any]] = {}
    for sp in tracer.spans:
        if sp.name != "serve.run" or sp.t1 is None:
            continue
        run = sp.args.get("run")
        if want is not None and run not in want:
            continue
        t0, t1 = _us(sp.t0), _us(sp.t1)
        segs = {name: 0 for name in SEGMENTS}
        own = None
        if sp.args.get("seq") is not None:
            own = requests.get((sp.args["seq"], _us(sp.args["seq_t0"])))
        run_ivs = ivs if own is None else not_engine + own
        clipped = [(max(a, t0), min(b, t1), seg) for a, b, seg in run_ivs
                   if b > t0 and a < t1 and b > a]
        # sweep the elementary intervals between all clip points; on
        # overlap the highest-priority segment takes the whole slice,
        # so labeled time can never exceed the window and the residual
        # is exact by integer construction
        points = sorted({t0, t1, *(a for a, _, _ in clipped),
                         *(b for _, b, _ in clipped)})
        for lo, hi in zip(points, points[1:]):
            active = [seg for a, b, seg in clipped
                      if a <= lo and b >= hi]
            if active:
                segs[min(active, key=SEGMENT_PRIORITY.index)] += hi - lo
        labeled = sum(segs[name] for name in SEGMENT_PRIORITY)
        segs["cp.queue_wait"] = (t1 - t0) - labeled
        out[run] = {
            "run": run,
            "status": sp.args.get("status"),
            "t0_us": t0,
            "t1_us": t1,
            "total_us": t1 - t0,
            "segments_us": segs,
            "retries": sum(1 for ts in retry_ts if t0 <= ts <= t1),
            "degraded": sum(1 for ts in degraded_ts if t0 <= ts <= t1),
        }
    return out


def critical_path_stats(tracer, runs: Optional[Any] = None
                        ) -> Dict[str, Any]:
    """Fleet-level aggregate for sweep stats (faults/soak.py): per-
    segment totals and means across every decomposed run.  Deterministic
    under a VirtualClock; lives in the sweep's ``stats`` block, never in
    the byte-compared report."""
    rows = critical_path(tracer, runs=runs)
    if not rows:
        return {"runs": 0}
    totals = {name: 0 for name in SEGMENTS}
    for row in rows.values():
        for name in SEGMENTS:
            totals[name] += row["segments_us"][name]
    n = len(rows)
    return {
        "runs": n,
        "end_to_end_us": sum(r["total_us"] for r in rows.values()),
        "total_us": {k: totals[k] for k in sorted(totals)},
        "mean_us": {k: round(totals[k] / n, 3) for k in sorted(totals)},
        "retries": sum(r["retries"] for r in rows.values()),
        "degraded": sum(r["degraded"] for r in rows.values()),
    }
