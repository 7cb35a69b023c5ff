"""The logits check's control, at a cell's own size, once, on the chip.

    chiprun -- python benchmarks/control.py --workload mistral7b.chat-open \
        --seeds 11 12 13 --sound-seeds 14 15 16 17 18 19 20 21 22

For each seed the cell's engine is built twice, from the same weights: as
its configuration states, and with the cache in the nearest precision below
the stated one (int4 for int8; int8 for a cache the file leaves in the
activations' type), the step that would tempt a later PR.  Both go through
the cell's own logits check (``lib/correct.py``, the mix's ``check`` group,
the architecture's driver) against the same plain reference.  Nothing is
warmed and no window is opened: the check's readings need neither.

Printed for each number the check compares: the largest reading of the sound
engine over the seeds (``--sound-seeds`` adds seeds read on that side alone),
the smallest of the control, and their ratio.  A limit
belongs above the first and below the second, with room on both sides; where
the second is under three times the first no limit will hold.  The exit code
is 0 only where the check passed the sound engine and refused the control on
every seed.  ``--prompt-tokens`` states the check's lengths in place of the
mix's ``check`` group (each takes the path the engine's admission takes for
a prompt of that length).  Not part of a run of the benchmark: PERF.md keeps
the readings, ``tests/test_checks.py`` keeps the control at a size a CPU
holds.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LOWER = {None: "int8", "int8": "int4"}
COMPARED = ("rel_err", "median_rel_err", "positions_over", "cache_rel_err")


def lowered(conf):
    """The configuration with its cache one precision down."""
    stated = conf.get("kv_cache_dtype")
    if stated not in LOWER:
        raise SystemExit(f"control: no precision below a {stated!r} cache")
    return dict(conf, kv_cache_dtype=LOWER[stated])


def summary(rows):
    out = {}
    for key in COMPARED:
        sound = [r["check"][key] for r in rows if r["side"] == "sound"]
        control = [r["check"][key] for r in rows if r["side"] == "control"]
        out[key] = {"sound_largest": max(sound),
                    "control_smallest": min(control),
                    "ratio": min(control) / max(sound) if max(sound) else None}
    out["sound_ok"] = [r["check"]["ok"] for r in rows if r["side"] == "sound"]
    out["control_ok"] = [r["check"]["ok"] for r in rows
                         if r["side"] == "control"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--prompt-tokens", type=int, nargs="+")
    ap.add_argument("--sound-seeds", type=int, nargs="*", default=[],
                    help="further seeds, read on the sound engine alone")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import logging

    from benchmarks import run as harness
    from benchmarks.lib import build, correct

    logging.disable(logging.INFO)
    _, cell, conf, traffic = harness.load_cell(args.benchmark, args.workload)
    device = build.describe_device(cell["chips"], args.allow_cpu)
    build.enable_compile_cache()
    group = ({"prompt_tokens": args.prompt_tokens} if args.prompt_tokens
             else traffic["check"])
    rows = []
    for seed in args.seeds + args.sound_seeds:
        sides = [("sound", conf)]
        if seed in args.seeds:
            sides.append(("control", lowered(conf)))
        for side, built in sides:
            engine, _ = build.build_engine(built, cell["config"], seed)
            # the reference reads the file as it stands: the control is the
            # program's departure from it
            check = correct.check(engine, conf, seed=seed, **group)
            rows.append({"cell": cell["name"], "seed": seed, "side": side,
                         "kv_cache_dtype": built.get("kv_cache_dtype"),
                         "check": check})
            print(json.dumps(rows[-1]), flush=True)
            del engine
            gc.collect()
    out = {"cell": cell["name"], "device": device, "rows": rows,
           "summary": summary(rows)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{cell['name']}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["summary"]), flush=True)
    return 0 if (all(out["summary"]["sound_ok"])
                 and not any(out["summary"]["control_ok"])) else 1


if __name__ == "__main__":
    sys.exit(main())
