"""Median seconds from a request's due time (open loop) or submission (closed
loop) to the end of the first engine tick after which it holds a token: what
a streaming client would see as its time to a first token.

A per-layer metric since PR 22 and not an end-to-end one: a 45 s window holds
32 to 45 first tokens, and their median spread by 5.5% (closed loop) to 28%
(open loop) over runs of the same code, which no bound of at most 10% admits
(PERF.md section 6).  In a closed loop it is part of every request's latency,
so it moves the tokens per second completed."""

from benchmarks.lib import stats

LAYER = "Serve (serve/api.py, serve/backend.py)"
UNIT = "s"
MOVES = "out_tokens_per_s"


def read(ctx):
    return stats.median(ctx.ttfts())
