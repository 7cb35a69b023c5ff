"""Closed loop of RCA incidents: ``concurrency`` incidents in flight through
``RCAPipeline`` + ``SweepScheduler`` over one steered engine backend.

The incident stream is an endless seeded draw over the fixture incidents
(``graph/fixtures.py::INCIDENTS``) against the in-memory graphs.
``SweepScheduler.run`` takes a finite sequence and has no stop hook, so the
list is made too long to drain and the sweep ends when the backend raises
``WindowClosed`` at the window's close.  The sweep starts during set-up; the
window opens once ``ramp_runs`` LLM runs have settled.
"""

from __future__ import annotations

from typing import Any, Dict

BACKEND = "steered"


def draw_incidents(rng, n: int):
    """The seeded incident stream: ``n`` messages drawn over the fixtures."""
    from k8s_llm_rca_tpu.graph.fixtures import INCIDENTS

    return [INCIDENTS[i].message for i in rng.integers(0, len(INCIDENTS), n)]


def run(session, params: Dict[str, Any]) -> None:
    from k8s_llm_rca_tpu.config import RCAConfig
    from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
    from k8s_llm_rca_tpu.graph.fixtures import (
        build_metagraph, build_stategraph,
    )
    from k8s_llm_rca_tpu.rca import RCAPipeline
    from k8s_llm_rca_tpu.rca.scheduler import SweepScheduler

    from benchmarks.lib.observe import WindowClosed

    k = int(params["concurrency"])
    cfg = RCAConfig(fresh_threads=True, concurrent_audits=True,
                    constrained=True)
    pipelines = [
        RCAPipeline(session.service,
                    InMemoryGraphExecutor(build_metagraph()),
                    InMemoryGraphExecutor(build_stategraph()), cfg)
        for _ in range(k)]
    incidents = session.extras.setdefault("incidents", [])
    clock = session.clock

    def timed(steps):
        def machine(message, **kw):
            t0 = clock()
            result = yield from steps(message, **kw)
            incidents.append((t0, clock()))
            return result
        return machine

    for p in pipelines:          # the benchmark's clock around each incident
        p.incident_steps = timed(p.incident_steps)
    sched = SweepScheduler(pipelines)
    messages = draw_incidents(session.rng, int(params["incidents_drawn"]))

    backend = session.backend
    ramp = int(params["ramp_runs"])

    def open_after_ramp(_tick_end: float) -> None:
        # the window opens between two ticks, once the ramp has settled
        if session.t_open is None and sum(
                1 for r in backend.reqs.values()
                if r.t_done is not None) >= ramp:
            session.open_window()
            backend.stop_at = session.t_close

    backend.after_tick.append(open_after_ramp)
    try:
        sched.run(messages)
        raise RuntimeError(
            f"the sweep drained {len(messages)} incidents before the window "
            f"closed: raise incidents_drawn in the traffic file")
    except WindowClosed:
        pass
    session.extras["sweep"] = sched.stats
