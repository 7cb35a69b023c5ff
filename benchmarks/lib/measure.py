"""From what a run observed to the numbers on its line.

End-to-end metrics come from the benchmark's own clock around work that ends
in ``block_until_ready`` (``Session.end_window``) and from the per-request
times ``ObservedBackend`` took; per-layer metrics are read by the files under
``benchmarks/layer_metrics`` from the ``Context`` built here.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
from typing import Any, Dict, List, Optional

from benchmarks.lib import stats

TRACE_SECONDS = 5.0      # the traced part of the window: its last seconds


class Context:
    """What a per-layer reader may look at."""

    def __init__(self, session, cell, conf, traffic, device):
        self.session, self.cell, self.conf = session, cell, conf
        self.traffic, self.device = traffic, device
        self.counters: Dict[str, float] = session.counters
        self.window_s: float = session.window_s
        self.ticks = session.window_ticks()
        self.engine = session.engine
        self.extras = session.extras
        self.trace: Optional[Dict[str, Any]] = None
        o, e = session.t_open, session.t_end
        reqs = list(session.backend.reqs.values())
        self.first_in_window = [r for r in reqs if r.t_first is not None
                                and o < r.t_first <= e]
        self.done_in_window = [r for r in reqs if r.t_done is not None
                               and o < r.t_done <= e]
        self.admitted_in_window = [r for r in reqs
                                   if r.t_admit_tick is not None
                                   and o <= r.t_admit_tick <= e]

    def incident_seconds(self) -> List[float]:
        """Seconds of each incident that finished inside the window."""
        o, e = self.session.t_open, self.session.t_end
        return [t1 - t0 for t0, t1 in self.extras.get("incidents", [])
                if o < t1 <= e]

    def ttfts(self) -> List[float]:
        return [r.t_first - r.t_due for r in self.first_in_window]

    def gaps_ms(self) -> List[float]:
        """Per request, the milliseconds per token between the first and the
        last tick inside the window that brought it tokens."""
        o, e = self.session.t_open, self.session.t_end
        out = []
        for r in self.session.backend.reqs.values():
            inside = [m for m in r.marks if o < m[0] <= e]
            if len(inside) >= 2 and r.error is None:
                (t_a, n_a), (t_b, n_b) = inside[0], inside[-1]
                out.append(1e3 * (t_b - t_a) / (n_b - n_a))
        return out


def end_to_end(ctx: Context) -> Dict[str, Optional[float]]:
    out_tokens = sum(t[2] for t in ctx.ticks)
    ttfts = ctx.ttfts()
    return {
        "out_tokens_per_s": out_tokens / ctx.window_s,
        "ttft_s_p50": stats.median(ttfts),
        "gap_ms_p50": stats.median(ctx.gaps_ms()),
        "setup_s": ctx.session.setup_s,
    }


def generator_report(ctx: Context) -> Dict[str, Any]:
    late = ctx.extras.get("generator_late_s") or []
    return {
        "first_tokens_in_window": len(ctx.first_in_window),
        "settled_in_window": len(ctx.done_in_window),
        "ticks_in_window": len(ctx.ticks),
        "late_ms_p50": None if not late else 1e3 * stats.median(late),
        "late_ms_max": None if not late else 1e3 * max(late),
    }


class Tracer:
    """Profiles the last ``TRACE_SECONDS`` of the window (a whole window's
    trace is too large and slows the host) and reduces it once the window
    has ended."""

    def __init__(self, session, keep: Optional[str] = None,
                 profile: bool = True):
        self.session, self.keep = session, keep
        self.running = False
        self.reduced: Optional[Dict[str, Any]] = None
        if not profile:
            return
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        session.on_open.append(self._opened)
        session.on_end.append(self._stop)
        session.backend.after_tick.append(self._after_tick)

    def _opened(self) -> None:
        if self.session.seconds <= TRACE_SECONDS:
            self._start()

    def _after_tick(self, t1: float) -> None:
        s = self.session
        if (not self.running and self.reduced is None
                and s.t_close is not None
                and t1 >= s.t_close - TRACE_SECONDS):
            self._start()

    def _start(self) -> None:
        import jax

        from k8s_llm_rca_tpu.utils.logging import METRICS

        self.t_start = self.session.clock()
        self.counters_start = METRICS.snapshot()
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 2
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.running = True

    def _stop(self) -> None:
        import jax

        from k8s_llm_rca_tpu.utils.logging import METRICS

        from benchmarks.trace import reduce

        if not self.running:
            raise RuntimeError("the window ended before the trace began")
        jax.profiler.stop_trace()
        self.running = False
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        if self.keep:
            os.makedirs(os.path.dirname(os.path.abspath(self.keep)),
                        exist_ok=True)
            shutil.copy(paths[0], self.keep)
        self.reduced = reduce.reduce_file(paths[0])
        # what the program counted and the benchmark timed while traced
        self.reduced["counters"] = {
            k: v - self.counters_start.get(k, 0.0)
            for k, v in METRICS.snapshot().items()
            if isinstance(v, (int, float)) and not k.endswith(".p50_s")}
        self.reduced["ticks"] = [t for t in self.session.backend.ticks
                                 if t[0] >= self.t_start]
        shutil.rmtree(self.dir, ignore_errors=True)
