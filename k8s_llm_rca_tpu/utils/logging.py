"""Structured logging + lightweight timing/metrics.

The reference's observability is bare ``print`` banners plus wall-clock
bracketing (test_all.py:143-151, test_with_file.py:173-175).  This module
keeps that per-phase timing but as structured, queryable records, and adds
engine-side counters (tokens, steps, queue depth) that the sweep drivers
report and the benchmark's readers (benchmarks/lib/observe.py) turn into its
per-layer metrics.
"""

from __future__ import annotations

import contextlib
import statistics
import threading
import logging
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List


def get_logger(name: str = "k8s_llm_rca_tpu") -> logging.Logger:
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(name)s %(levelname)s %(message)s")
        )
        logger.addHandler(handler)
        logger.setLevel(logging.INFO)
        logger.propagate = False
    return logger


def _median(xs: List[float]) -> float:
    return float(statistics.median(xs)) if xs else 0.0


# retained samples per timer name: p50 is computed over this newest-window
# reservoir while total/count stay exact running aggregates, so a long soak
# cannot grow a per-name list without bound (previously: unbounded append)
TIMING_RESERVOIR = 512


class _Reservoir:
    """Bounded timing store: exact ``total``/``count`` forever, plus a
    fixed-size ring of the newest samples for quantiles.  List-like over
    the retained window (len/index/iter), so existing consumers reading
    ``metrics.timings[name]`` keep working."""

    __slots__ = ("total", "count", "_ring", "_cap", "_i")

    def __init__(self, capacity: int = TIMING_RESERVOIR):
        self.total = 0.0
        self.count = 0
        self._cap = capacity
        self._ring: List[float] = []
        self._i = 0

    def append(self, dt: float) -> None:
        self.total += dt
        self.count += 1
        if len(self._ring) < self._cap:
            self._ring.append(dt)
        else:
            self._ring[self._i] = dt
            self._i = (self._i + 1) % self._cap

    def window(self) -> List[float]:
        """Retained samples, oldest first."""
        return self._ring[self._i:] + self._ring[:self._i]

    def __len__(self) -> int:
        return len(self._ring)

    def __getitem__(self, i):
        return self.window()[i]

    def __iter__(self):
        return iter(self.window())


@dataclass
class Metrics:
    """Process-local counters + phase timers.

    Mutations take a lock: the DP sweep (sweeps/run_file.py --replicas)
    drives this global from N replica threads, and ``counters[name] +=``
    is a read-modify-write that loses increments under a thread switch."""

    counters: Dict[str, float] = field(default_factory=lambda: defaultdict(float))
    timings: Dict[str, _Reservoir] = field(
        default_factory=lambda: defaultdict(_Reservoir))
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def inc(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self.counters[name] += value

    def gauge(self, name: str, value: float) -> None:
        """Set (not add to) a value that is a level, not a count: it
        reads like a counter in ``count`` and ``snapshot``."""
        with self._lock:
            self.counters[name] = float(value)

    @contextlib.contextmanager
    def timer(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(name, time.perf_counter() - t0)

    def observe(self, name: str, seconds: float) -> None:
        """Append one duration to the reservoir a ``timer`` of the same
        name would use: for durations that are a difference of two
        stamps and not a ``with`` block."""
        with self._lock:
            self.timings[name].append(seconds)

    def count(self, name: str) -> float:
        """Current value of an ``inc`` counter (0 if never incremented)."""
        with self._lock:
            return self.counters.get(name, 0.0)

    def total(self, name: str) -> float:
        """Summed duration of a ``timer`` phase (0 if never timed) —
        exact over the phase's whole life, not just the reservoir."""
        with self._lock:
            r = self.timings.get(name)
            return r.total if r is not None else 0.0

    def p50(self, name: str) -> float:
        """Median over the retained reservoir window (the newest
        TIMING_RESERVOIR samples — representative for long soaks without
        unbounded growth)."""
        with self._lock:
            r = self.timings.get(name)
            xs = r.window() if r is not None else []
        return _median(xs)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self.counters)
            timings = {k: (v.total, v.count, v.window())
                       for k, v in self.timings.items()}
        for k, (total, count, window) in timings.items():
            out[f"{k}.total_s"] = total
            out[f"{k}.count"] = float(count)
            out[f"{k}.p50_s"] = _median(window)
        return out

    def reset(self) -> None:
        """Drop every counter and timer (scoped tests / soak isolation)."""
        with self._lock:
            self.counters.clear()
            self.timings.clear()

    @contextlib.contextmanager
    def scoped(self):
        """Run a block against FRESH counters/timers, restoring the prior
        state afterwards — tests stop leaking into each other through the
        global METRICS while module-level imports of it stay valid (the
        object identity never changes, only its stores swap)."""
        with self._lock:
            saved_counters, saved_timings = self.counters, self.timings
            self.counters = defaultdict(float)
            self.timings = defaultdict(_Reservoir)
        try:
            yield self
        finally:
            with self._lock:
                self.counters, self.timings = saved_counters, saved_timings


METRICS = Metrics()
