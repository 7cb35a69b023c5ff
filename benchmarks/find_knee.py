"""The rate sweep that finds an open-loop cell's knee, once, on the chip.

    chiprun -- python benchmarks/find_knee.py --workload mistral7b.chat-open \
        --rates 1 2 3 4 --seconds 40

One process builds and warms the cell's engine once, then offers the cell's
traffic at each rate in turn for ``--seconds`` (after the mix's own ramp),
draining the engine between rates.  A rate is SUSTAINED when the backlog does
not grow through the window: requests finish as fast as they arrive (at
least 95% of the arrival rate) and no more are unfinished at the close than
the engine has slots.  The knee is the highest sustained rate; the cell then
runs at 0.8 of it, frozen as ``arrivals.rate_rps`` in
``benchmarks/cells/<cell>.json``.  The benchmark itself never searches.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--stop-after", type=float, default=None,
                    help="start no further rate once the process is this "
                         "many seconds old (a chip call's time is bounded)")
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import logging

    from benchmarks import run as harness
    from benchmarks.layer_metrics import queue_wait_ms_p50
    from benchmarks.lib import build, correct, measure, observe, warmup
    from benchmarks.lib.session import CompileMeter, Session

    logging.disable(logging.INFO)
    _, cell, conf, traffic = harness.load_cell(args.benchmark, args.workload)
    if traffic["arrivals"]["kind"] != "poisson":
        raise SystemExit("find_knee: only an open-loop mix has a knee")
    device = build.describe_device(cell["chips"], args.allow_cpu)
    build.enable_compile_cache()
    meter = CompileMeter()

    from k8s_llm_rca_tpu.serve.api import AssistantService
    from k8s_llm_rca_tpu.serve.backend import EngineBackend

    # the same set-up as a run of the cell, check included: it is part of
    # what warms the engine
    engine, _ = build.build_engine(conf, cell["config"], args.seed)
    correct.check(engine, conf, seed=args.seed, **traffic["check"])
    warmup.warm(engine, traffic, build.check_driver(conf))
    generator = importlib.import_module(
        "benchmarks.generators." + traffic["generator"])
    slots = engine.engine_cfg.max_batch
    rows = []
    for i, rate in enumerate(args.rates):
        if args.stop_after is not None and (
                time.perf_counter() - T_PROCESS > args.stop_after):
            break
        # a seed of its own per rate: the same stream twice would be served
        # from the prefix cache the second time
        backend = observe.ObservedBackend(EngineBackend(engine))
        session = Session(engine, backend, AssistantService(backend),
                          args.seed + i, args.seconds, meter, T_PROCESS)
        generator.run(session, harness.merge(
            traffic, {"arrivals": {"rate_rps": rate}}))
        session.end_window()
        ctx = measure.Context(session, cell, conf, traffic, device)
        e2e = measure.end_to_end(ctx)
        arrived = sum(1 for r in backend.reqs.values()
                      if session.t_open <= r.t_due <= session.t_end)
        unfinished = sum(1 for r in backend.reqs.values()
                         if r.t_done is None)
        finished = len(ctx.done_in_window)
        row = {
            "rate_rps": rate, "arrived": arrived, "finished": finished,
            "unfinished_at_close": unfinished,
            "sustained": bool(finished >= 0.95 * arrived
                              and unfinished <= slots),
            "out_tokens_per_s": e2e["out_tokens_per_s"],
            "ttft_s_p50": e2e["ttft_s_p50"],
            "ttft_s_max": max(ctx.ttfts(), default=None),
            "gap_ms_p50": e2e["gap_ms_p50"],
            "queue_wait_ms_p50": queue_wait_ms_p50.read(ctx),
            "compiles_in_window": session.compiles_in_window,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
        out = summary(cell, device, args, rows)
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               f"knee_{cell['name']}.json"), "w") as f:
            json.dump(out, f, indent=1)     # after every rate: a call may
            # be cut
        while engine.has_work:           # drain before the next rate
            engine.step()
    out = summary(cell, device, args, rows)
    print(json.dumps({k: out[k] for k in ("cell", "knee_rps",
                                          "rate_rps_at_0.8")}), flush=True)
    return 0


def summary(cell, device, args, rows):
    sustained = [r["rate_rps"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    return {"cell": cell["name"], "device": device, "seconds": args.seconds,
            "seed": args.seed, "knee_rps": knee,
            "rate_rps_at_0.8": None if knee is None else round(0.8 * knee,
                                                               2),
            "rows": rows}


if __name__ == "__main__":
    sys.exit(main())
