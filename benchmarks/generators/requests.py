"""Independent requests through ``AssistantService``, as a user sends them:
fresh thread -> message -> run, free decode, seeded unshared prompts.

``arrivals.kind`` is ``poisson`` (open loop at ``rate_rps``: a Poisson process
given its count, that is rate x horizon arrivals at seeded uniform times, so
that every seed offers the same load; latency is timed from the DUE time and
the generator's lateness is reported) or
``closed`` (``clients`` callers, each sending its next request when the last
one settles).  Prompt and output lengths are seeded lognormal draws; the
output length is the request's ``max_new_tokens``.  Every request carries its
own few-character system line, so not even the first cache page is shared and
the prefix cache is out of the way.  One thread drives everything: requests
enter between engine ticks, as they would behind the service's lock.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import jax
import numpy as np

from benchmarks.lib import text

BACKEND = "observed"


def make_requests(rng, params: Dict[str, Any], n: int,
                  horizon_s: float = 60.0):
    """The seeded request stream: per request its system line, prompt
    tokens, output tokens and the seed of its text, and for an open loop the
    due times in seconds."""
    p_len = text.lognormal_int(rng, params["prompt_tokens"], n)
    o_len = text.lognormal_int(rng, params["output_tokens"], n)
    nonces = [text.words(rng, 8).replace(" ", "x") for _ in range(n)]
    text_seeds = rng.integers(0, 2 ** 32, n)
    due = None
    if params["arrivals"]["kind"] == "poisson":
        due = np.sort(rng.uniform(0.0, horizon_s, n))
    return nonces, p_len, o_len, text_seeds, due


def run(session, params: Dict[str, Any]) -> None:
    from k8s_llm_rca_tpu.serve.api import RunStatus, render_prompt
    from k8s_llm_rca_tpu.serve.backend import GenOptions

    service, backend, clock = session.service, session.backend, session.clock
    arrivals = params["arrivals"]
    open_loop = arrivals["kind"] == "poisson"
    horizon = params.get("ramp_s", 0) + session.seconds + 5.0
    n = (round(arrivals["rate_rps"] * horizon) if open_loop
         else int(params["max_requests"]))
    nonces, p_len, o_len, text_seeds, due = make_requests(
        session.rng, params, n, horizon)

    assistant = service.create_assistant("x", params.get("assistant",
                                                         "bench-chat"))
    # tokens the chat template adds around an empty message (BOS included)
    probe = service.create_thread()
    service.add_message(probe.id, "")
    overhead = backend.count_tokens(
        render_prompt(assistant, probe, nonces[0])) + 1

    def submit(i: int, t_due: float):
        with jax.profiler.TraceAnnotation("bench.submit"):
            thread = service.create_thread()
            service.add_message(thread.id, text.words(
                np.random.default_rng(int(text_seeds[i])),
                int(p_len[i]) - overhead))
            backend.due = t_due
            return service.create_run(
                thread.id, assistant.id, instructions=nonces[i],
                gen=GenOptions(max_new_tokens=int(o_len[i])))

    t_start = clock()
    sent = done = 0
    late = []
    live = [None] * (0 if open_loop else int(arrivals["clients"]))
    while True:
        now = clock()
        if session.t_open is None and (
                now - t_start >= params["ramp_s"] if open_loop
                else done >= params["ramp_requests"]):
            session.open_window()
        if session.closed():
            break
        if open_loop:
            while sent < n and t_start + due[sent] <= now:
                submit(sent, t_start + float(due[sent]))
                late.append((t_start + float(due[sent]), clock()))
                sent += 1
        else:
            for c, run_ in enumerate(live):
                if run_ is None or run_.status in RunStatus.TERMINAL:
                    done += run_ is not None
                    live[c] = submit(sent, clock())
                    sent += 1
        if sent >= n and not open_loop:
            raise RuntimeError("the request stream ran dry before the "
                               "window closed")
        if backend.engine.has_work or not open_loop:
            service.pump_once()
        else:
            with jax.profiler.TraceAnnotation("generator.wait"):
                time.sleep(0.002)
    session.extras["generator_late_s"] = [
        t_sent - t_due for t_due, t_sent in late
        if session.t_open <= t_due]
