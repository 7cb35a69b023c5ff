"""One run's state: the clock, the window, the counters at its two ends.

A generator gets a ``Session``, ramps its traffic up during set-up, calls
``open_window()`` at the instant measurement starts and returns when
``closed()``.  Everything the metrics are computed from is read here, on the
benchmark's side: program counters as deltas over the window, compilations
from ``jax.monitoring``, the ticks and requests ``ObservedBackend`` timed.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np


class CompileMeter:
    """Programs the jit caches missed (compiled, or loaded from the
    persistent cache), from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Session:
    def __init__(self, engine, backend, service, seed: int, seconds: float,
                 meter: CompileMeter, t_process: float,
                 clock=time.perf_counter):
        self.engine, self.backend, self.service = engine, backend, service
        self.seed, self.seconds = seed, float(seconds)
        self.rng = np.random.default_rng(seed)
        self.meter = meter
        self.clock = clock
        self.t_process = t_process        # process start, on ``clock``
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.t_end: Optional[float] = None
        self.counters_open: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self.compiles_in_window = 0
        self._compiles_open = 0
        self.on_open: List[Callable[[], None]] = []
        self.on_end: List[Callable[[], None]] = []
        self.extras: Dict[str, Any] = {}

    def open_window(self) -> None:
        from k8s_llm_rca_tpu.utils.logging import METRICS

        import jax

        for fn in self.on_open:
            fn()
        # from here on a compilation is a fault of the warm list: have JAX
        # name it on stderr
        jax.config.update("jax_log_compiles", True)
        self.counters_open = METRICS.snapshot()
        self._compiles_open = self.meter.count
        self.t_open = self.clock()
        self.t_close = self.t_open + self.seconds

    def closed(self) -> bool:
        return self.t_close is not None and self.clock() >= self.t_close

    def end_window(self) -> None:
        """Called once the generator has returned: the device finishes what
        was dispatched, then the window's end is read."""
        import jax

        from k8s_llm_rca_tpu.utils.logging import METRICS

        if self.t_open is None:
            raise RuntimeError("the generator returned without opening the "
                               "window")
        jax.block_until_ready(self.engine.pool)
        self.t_end = self.clock()
        for fn in self.on_end:
            fn()
        snap = METRICS.snapshot()
        self.counters = {
            k: v - self.counters_open.get(k, 0.0) for k, v in snap.items()
            if isinstance(v, (int, float)) and not k.endswith(".p50_s")}
        self.compiles_in_window = self.meter.count - self._compiles_open

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_open

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_process

    # -------------------------------------------------- what the window saw

    def window_ticks(self):
        return [t for t in self.backend.ticks
                if t[1] > self.t_open and t[1] <= self.t_end]

    def worked_on_in_window(self):
        """Requests the window saw: due before its end and not settled
        before it opened."""
        return [r for r in self.backend.reqs.values()
                if r.t_due <= self.t_end
                and (r.t_done is None or r.t_done > self.t_open)]
