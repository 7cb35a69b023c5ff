"""Measure a cell as the driver does: sets of runs of the same code, each run
a process of its own with its own seed, and for every end-to-end metric the
spread of each set (distance between the quartiles over the median) and how
far the second set's median lies from the first's.

    chiprun -- python benchmarks/spread.py --workload mistral7b.chat-open \
        --sets 2 --runs 3 --seed 11

The parent never touches JAX (one process per chip).  Every run's two output
lines are kept under ``chiprun_out/runs/``; the summary is the last line.  A
bound is set to about five times the widest spread over the cells, never
under 1% (the builder's instructions).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmarks.lib import stats

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--trace-last", action="store_true",
                    help="one more run with --trace 1 at the end")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "runs")
    os.makedirs(out_dir, exist_ok=True)

    def one(seed: int, trace: int):
        tag = f"{args.workload}.s{seed}.t{trace}"
        t0 = time.time()
        with open(os.path.join(out_dir, tag + ".err"), "w") as err:
            done = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", str(trace)],
                stdout=subprocess.PIPE, stderr=err, text=True, cwd=ROOT)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            f.write(done.stdout)
        lines = done.stdout.strip().splitlines()
        print(f"# {tag}: exit {done.returncode} in {time.time() - t0:.0f} s: "
              f"{lines[-1][:600] if lines else ''}", flush=True)
        if done.returncode or not lines:
            return None
        return json.loads(lines[-1])

    sets = []
    for k in range(args.sets):
        sets.append([one(args.seed + 10 * k + i, 0)
                     for i in range(args.runs)])
    if args.trace_last:
        one(args.seed + 10 * args.sets, 1)

    summary = {"workload": args.workload, "seconds": seconds,
               "runs": [len([r for r in s if r]) for s in sets],
               "all_correct": all(r and r["correct"] and not r["failed"]
                                  for s in sets for r in s),
               "metrics": {}}
    names = sorted({m for s in sets for r in s if r for m in r["metrics"]})
    for name in names:
        per_set = [[r["metrics"][name]["value"] for r in s
                    if r and name in r["metrics"]] for s in sets]
        medians = [stats.median(v) for v in per_set]
        row = {"values": per_set, "medians": medians,
               "spreads": [stats.spread(v) for v in per_set]}
        if len(medians) > 1 and medians[0]:
            row["second_over_first"] = medians[1] / medians[0] - 1.0
        summary["metrics"][name] = row
    with open(os.path.join(ROOT, "chiprun_out",
                           f"spread_{args.workload}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
