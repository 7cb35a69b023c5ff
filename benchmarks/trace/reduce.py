"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle time,
time per program and per device operation, and the idle gaps by what the host
was doing.  Read with ``jax.profiler.ProfileData`` and nothing else.

How a TPU v5e trace of this program is laid out (jax 0.9.0; looked at by hand
in PR 22, PERF.md section 3):

- one plane per chip, ``/device:TPU:<n>``.  Its line ``XLA Modules`` has one
  event per executed program, named ``jit_<function>(<fingerprint>)``; the
  engine jits ``functools.partial`` objects, which have no name, so all its
  programs are ``jit__unknown(<fingerprint>)`` and only the fingerprint tells
  them apart.  Its line ``XLA Ops`` has one event per HLO operation, named by
  the operation's whole HLO text (``%fusion.12 = bf16[...] fusion(...)``);
  a ``while`` (the decode scan) CONTAINS the events of its body, so times are
  taken as self times.  A Pallas kernel is a custom call named after its
  Python function (``%paged_attention_quant``).  ``Async XLA Ops`` holds the
  copies that overlap them.
- the host is the plane ``/host:CPU``; its line ``python`` holds every
  ``TraceAnnotation`` (``engine.decode_step``, ``engine.prefill``, the
  benchmark's ``bench.pump`` ...), ``np.asarray(jax.Array)`` for a blocking
  fetch, and one ``PjitFunction(<function>)`` per jitted call, which DOES
  carry the function's name (``paged_decode_scan``).
- all planes share one clock.  Programs run in the order they were launched,
  so the host's calls, in order, name the device's programs, in order: that
  is how a fingerprint gets its function's name here.
"""

from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict
from typing import Any, Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CALL = re.compile(r"^PjitFunction\((.*)\)$")
# host spans a gap can be charged to, innermost first: the blocking fetch
# inside the engine's fetch phase, the phases of a tick inside the tick, the
# tick inside the benchmark's pump
HOST_SPANS = ("np.asarray(jax.Array)", "engine.fetch", "engine.grammar_mask",
              "engine.commit", "engine.decode_step", "engine.prefill",
              "engine.tick.admission", "engine.tick.eviction", "engine.tick",
              "bench.submit", "generator.wait", "bench.pump")
OTHER = "host.other"
GAP_FLOOR_NS = 20_000        # shorter gaps are launch latency, not waiting


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps_ns(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def program_name(event_name: str) -> str:
    """``jit_paged_decode_scan(123456)`` -> ``paged_decode_scan``."""
    name = event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def base_name(short: str) -> str:
    """``fusion.12`` -> ``fusion``: one row per kind of operation."""
    return re.sub(r"\.\d+$", "", short)


def self_times(events: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, str]]:
    """(self nanoseconds, name) per event of one line, where an event that
    contains others (a ``while`` and its body) keeps only what they leave."""
    out: List[List[Any]] = []
    stack: List[int] = []
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            out[stack[-1]][0] -= b - a
        out.append([b - a, name, b])
        stack.append(len(out) - 1)
    return [(max(0.0, s), name) for s, name, _ in out]


def name_programs(modules: List[Tuple[float, float, str]],
                  calls: List[Tuple[float, float, str]]) -> Dict[str, str]:
    """Fingerprinted module name -> the jitted function's name, by laying the
    host's calls (in launch order) beside the device's programs (in run
    order) at the offset where the programs that do carry a name agree
    best."""
    # a call is traced twice, one event inside the other: keep the outer
    outer, end = [], None
    for a, b, name in sorted(calls, key=lambda e: (e[0], -e[1])):
        if end is None or a >= end:
            outer.append(name)
            end = b
    mods = [name for _, _, name in sorted(modules)]

    def agree(d):
        return sum(1 for i, m in enumerate(mods)
                   if 0 <= i + d < len(outer)
                   and program_name(m) == outer[i + d])

    best = max(range(-64, 65), key=lambda d: (agree(d), -abs(d)))
    votes: Dict[str, Counter] = defaultdict(Counter)
    for i, m in enumerate(mods):
        if 0 <= i + best < len(outer):
            votes[m][outer[i + best]] += 1
    return {m: c.most_common(1)[0][0] for m, c in votes.items()}


def reduce_file(path: str) -> Dict[str, Any]:
    import jax

    return reduce(jax.profiler.ProfileData.from_file(path))


def reduce(data) -> Dict[str, Any]:
    chips: List[Dict[str, Any]] = []
    host_spans: List[Tuple[float, float, str]] = []
    calls: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    key = "ops" if line.name == OPS_LINE else "modules"
                    for ev in line.events:
                        chip[key].append((ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          ev.name))
            chips.append(chip)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name in HOST_SPANS:
                        host_spans.append((*span, ev.name))
                    else:
                        m = CALL.match(ev.name)
                        if m:
                            calls.append((*span, m.group(1)))
    if not chips or not any(c["ops"] for c in chips):
        raise ValueError("the trace holds no device operation: no plane "
                         "named /device:TPU:<n> with an 'XLA Ops' line")
    spans = [s for c in chips for s in c["ops"] + c["modules"]] + host_spans
    lo = min(s[0] for s in spans)
    hi = max(s[1] for s in spans)

    n = len(chips)
    ns = 1e-9
    busy = [union_ns((a, b) for a, b, _ in c["ops"]) for c in chips]
    op_time: Dict[str, float] = defaultdict(float)
    op_count: Dict[str, float] = defaultdict(float)
    op_text: Dict[str, str] = {}
    prog_time: Dict[str, float] = defaultdict(float)
    prog_count: Dict[str, float] = defaultdict(float)
    names = name_programs(chips[0]["modules"], calls)
    for c in chips:
        for s, text in self_times(c["ops"]):
            short = op_name(text)
            op_time[short] += s
            op_count[short] += 1
            op_text.setdefault(short, text)
        for a, b, name in c["modules"]:
            prog = names.get(name, program_name(name))
            prog_time[prog] += b - a
            prog_count[prog] += 1

    # idle gaps of the first chip, each charged to the innermost host span
    # that covers half of it, else to the one that covers most of it, or to
    # nobody under a fifth
    gap_time: Dict[str, float] = defaultdict(float)
    rank = {name: i for i, name in enumerate(HOST_SPANS)}
    host_spans.sort()
    starts = [s[0] for s in host_spans]
    longest = max((y - x for x, y, _ in host_spans), default=0.0)
    for a, b in gaps_ns([(x, y) for x, y, _ in chips[0]["ops"]], lo, hi):
        if b - a < GAP_FLOOR_NS:
            gap_time["launch gaps under 20 us"] += b - a
            continue
        cover: Dict[str, float] = defaultdict(float)
        i = bisect.bisect_left(starts, a - longest)
        while i < len(host_spans) and host_spans[i][0] < b:
            x, y, name = host_spans[i]
            cover[name] += max(0.0, min(b, y) - max(a, x))
            i += 1
        half = [k for k, v in cover.items() if v >= 0.5 * (b - a)]
        if half:
            label = min(half, key=rank.get)
        else:
            label = max(cover, key=cover.get, default=OTHER)
            if cover.get(label, 0.0) < 0.2 * (b - a):
                label = OTHER
        gap_time[label] += b - a

    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) * ns / n,
        "chips": n,
        # self seconds per kind of operation (numbered copies of one name
        # summed: one per layer), averaged over the chips
        "device_ops": _by_kind(op_time, ns / n),
        "op_seconds": {k: v * ns / n for k, v in op_time.items()},
        # how often each operation ran (a kernel called once per layer and
        # step counts the steps, whatever the length of the scan around it)
        "op_counts": {k: v / n for k, v in op_count.items()},
        "op_text": op_text,
        "programs": {k: {"seconds": v * ns / n, "count": prog_count[k] / n}
                     for k, v in prog_time.items()},
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gap_time.items(), key=lambda kv: -kv[1])],
    }


def _by_kind(op_time: Dict[str, float], scale: float) -> List[List[Any]]:
    kinds: Dict[str, float] = defaultdict(float)
    for short, t in op_time.items():
        kinds[base_name(short)] += t
    return [[k, v * scale] for k, v in
            sorted(kinds.items(), key=lambda kv: -kv[1])]


def describe(data, top: int = 12) -> str:
    """A trace's planes, lines and most expensive event names, for a reader
    who has to learn how a new chip or JAX version names things."""
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            names: Dict[str, List[float]] = defaultdict(list)
            for ev in line.events:
                names[ev.name].append(ev.duration_ns)
            out.append(f"  line {line.name!r}: "
                       f"{sum(map(len, names.values()))} events, "
                       f"{len(names)} names")
            for name, ds in sorted(names.items(),
                                   key=lambda kv: -sum(kv[1]))[:top]:
                out.append(f"    {name[:100]!r} x{len(ds)} "
                           f"total {sum(ds) / 1e6:.3f} ms")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys

    import jax

    pd = jax.profiler.ProfileData.from_file(sys.argv[1])
    if "--describe" in sys.argv:
        print(describe(pd))
    r = reduce(pd)
    r["device_ops"] = r["device_ops"][:10]
    r.pop("op_text"), r.pop("op_seconds"), r.pop("op_counts")
    print(json.dumps(r, indent=1))
