"""One process, one cell, one JSON line last.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is data: its ``workloads`` entry in ``BENCHMARK.json`` names a
configuration file (``configs``) and a traffic mix
(``benchmarks/traffic/<traffic>.json``, with the cell's own frozen numbers in
``benchmarks/cells/<cell>.json`` where it has any); the traffic file names a
generator module (``benchmarks/generators``); per-layer metrics are listed by
name and read by ``benchmarks/layer_metrics/<name>.py``.  Nothing here knows a
cell, a configuration, a mix or a per-layer metric by name.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and a breakdown.  Without a TPU it exits
non-zero and prints nothing (``--allow-cpu`` is the CPU rehearsal's switch,
which no cell of ``BENCHMARK.json`` is run with).
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


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r}")


def load_cell(bench_path: str, workload: str):
    from benchmarks.lib import build

    bench = build.load_json(bench_path)
    cell = find(bench["workloads"], workload, "workload")
    config = find(bench["configs"], cell["config"], "config")
    conf = build.load_json(os.path.join(ROOT, config["file"]))
    traffic = build.load_json(os.path.join(
        build.BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    own = os.path.join(build.BENCH_DIR, "cells", cell["name"] + ".json")
    if os.path.exists(own):
        traffic = merge(traffic, build.load_json(own))
    return bench, cell, conf, traffic


def merge(base, over):
    """``over``'s values replace ``base``'s, group by group."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) and isinstance(
            base.get(k), dict) else v
    return out


def applies(metric, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark", default=os.path.join(ROOT,
                                                        "BENCHMARK.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="CPU rehearsal: control flow and counts only")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler's .xplane.pb to this path")
    args = ap.parse_args(argv)

    import logging

    from benchmarks.lib import build, correct, measure, observe, warmup
    from benchmarks.lib.session import CompileMeter, Session

    # the program logs every generated query and clue at INFO
    logging.disable(logging.INFO)
    bench, cell, conf, traffic = load_cell(args.benchmark, args.workload)
    device = build.describe_device(cell["chips"], args.allow_cpu)
    build.enable_compile_cache()
    meter = CompileMeter()

    from k8s_llm_rca_tpu.serve.api import AssistantService
    from k8s_llm_rca_tpu.serve.backend import EngineBackend

    engine, build_s = build.build_engine(conf, cell["config"], args.seed)
    generator = importlib.import_module(
        "benchmarks.generators." + traffic["generator"])
    if generator.BACKEND == "steered":
        from k8s_llm_rca_tpu.rca.oracle import OracleBackend

        backend = observe.SteeredEngineBackend(
            EngineBackend(engine), OracleBackend(engine.tokenizer))
    else:
        backend = observe.ObservedBackend(EngineBackend(engine))
    service = AssistantService(backend)
    session = Session(engine, backend, service, args.seed, args.seconds,
                      meter, T_PROCESS)

    t0 = time.perf_counter()
    check = correct.check(engine, conf, seed=args.seed, **traffic["check"])
    t1 = time.perf_counter()
    n_warm = warmup.warm(engine, traffic, build.check_driver(conf))
    t2 = time.perf_counter()

    tracer = None
    if args.trace:
        # the CPU rehearsal has no device to trace: its per-layer line holds
        # counts and host spans only, never a device metric
        tracer = measure.Tracer(session, args.keep_trace,
                                profile=device["platform"] != "cpu")
    generator.run(session, traffic)
    session.end_window()

    device["memory_peak_bytes"] = build.memory_peak_bytes(cell["chips"])
    attempted = session.worked_on_in_window()
    settled = [r for r in backend.reqs.values() if r.t_done is not None
               and session.t_open < r.t_done <= session.t_end]
    failed = sum(1 for r in settled if r.failed)
    line = {
        "correct": bool(check["ok"] and failed == 0
                        and session.compiles_in_window == 0),
        "attempted": len(attempted),
        "failed": failed,
    }
    ctx = measure.Context(session, cell, conf, traffic, device)
    values = measure.end_to_end(ctx)
    if tracer is None:
        wanted = [m for m in bench["end_to_end"] if applies(m, cell["name"])]
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in wanted if values.get(m["name"]) is not None}
    else:
        ctx.trace = tracer.reduced
        reported = {m["name"] for m in bench["end_to_end"]
                    if applies(m, cell["name"])}
        line["metrics"] = {}
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]) or m["moves"] not in reported:
                continue
            reader = importlib.import_module(
                "benchmarks.layer_metrics." + m["name"])
            value = reader.read(ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
        if ctx.trace is not None:
            device["busy_s"] = ctx.trace["busy_s"]
            device["window_s"] = ctx.trace["window_s"]
            line["breakdown"] = {
                "device_ops": ctx.trace["device_ops"][:10],
                "idle_gaps": ctx.trace["idle_gaps"][:10]}
    line["device"] = device
    # last in the line and last on standard error: each number that decided
    # ``correct`` beside its limit, the check's as ``lib/correct.py`` gives
    # them (the benchmark's contract asks for it, past its five keys)
    line["compared"] = {
        **check["compared"],
        "failed": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": session.compiles_in_window,
                               "limit": 0}}
    # beside the contract's line, on a line of its own before it: what a
    # reader of the run wants to know about it
    report = {"run": {
        "cell": cell["name"], "seed": args.seed,
        "window_s": session.window_s,
        "compiles_in_window": session.compiles_in_window,
        # a preempted sequence prefills again in shapes a derived warm list
        # does not hold: a cell is given a rate at which this stays 0
        "preemptions_in_window": session.counters.get("engine.preemptions",
                                                      0.0),
        # every candidate end-to-end value, gated in this cell or not
        "values": values,
        "check": check,
        "setup": {**build_s, "check_s": t1 - t0, "warm_s": t2 - t1,
                  "warm_programs": n_warm, "programs": meter.count,
                  "cache_hits": meter.cache_hits,
                  "compile_s": meter.seconds},
        "generator": measure.generator_report(ctx),
        # what the program counted while traced: the denominators of the
        # readers that divide a trace's seconds
        "traced_counters": ctx.trace and {
            k: v for k, v in ctx.trace["counters"].items() if v}}}
    print(json.dumps(report), flush=True)
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
