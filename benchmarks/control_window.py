"""``benchmarks/control.py`` for a model with sliding-window layers: the
control is the engine built with another WINDOW than the configuration's file
states, the precisions as stated.  Two of them, each a step that would tempt
a later PR and that the cell's check has to refuse on every seed:

- ``--control full``: every sliding layer computed as a full one (the band
  left out: ``layer_types`` all ``full_attention``), which is what a prefill
  that forgets the band, or a decode kernel that walks the whole table,
  computes; the pool is cut to the pages the same memory holds with every
  layer in pages;
- ``--control wider``: the window one page wider (144 for 128), which is
  what a ring read from its first page instead of the window's first
  position computes.

Which published keys state the layers' kinds and the window is the
architecture's file's to say (``fields``: the keys of
``ModelConfig.attn_layer_types`` and ``attn_window``); the lists that restate
them layer by layer are unread by the door and left as they are.

Everything else is ``control.py``'s: the same check against the reference of
the file AS IT STANDS, the same summary for each control and the same exit
code (0 only where the check passed the sound engine and refused every
control on every seed); kept as ``chiprun_out/control_window_<cell>.json``.
``--control int8`` is ``control.py``'s own step (pages and rings in int8
under a file that leaves them in the activations' type), here so that one
build of a seed's weights serves it too.

    chiprun -- python benchmarks/control_window.py --control full wider \
        --workload k-exaone-d5.longdump-reason --seeds 11 12 13
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _key(conf, field: str) -> str:
    """The published key that the configuration's architecture maps to the
    program's ``field``: the control names fields, as the harness does."""
    from benchmarks.lib import build

    fields = build.architecture(conf)["fields"]
    found = [key for key, f in fields.items() if f == field]
    if not found:
        raise SystemExit(f"control: the architecture of model_type "
                         f"{conf.get('model_type')!r} has no key for "
                         f"ModelConfig.{field}: no window to move")
    return found[0]


def full(conf):
    """The configuration with every sliding layer a full one."""
    kinds_key = _key(conf, "attn_layer_types")
    kinds = conf[kinds_key]
    if "sliding_attention" not in kinds:
        raise SystemExit("control: the file has no sliding_attention layer")
    engine = dict(conf["engine"])
    # the memory the full layers' pages took, over every layer
    engine["num_pages"] = max(
        -(-engine["max_seq_len"] // engine["page_size"]) + 1,
        engine["num_pages"] * kinds.count("full_attention") // len(kinds))
    return dict(conf, **{kinds_key: ["full_attention"] * len(kinds)},
                engine=engine)


def wider(conf):
    """The configuration with its window one page wider."""
    window_key = _key(conf, "attn_window")
    return dict(conf, **{
        window_key: conf[window_key] + conf["engine"]["page_size"]})


def int8(conf):
    """``control.py``'s own step beside the two: pages and rings one
    precision down, so that one build of the weights serves all three."""
    from benchmarks import control

    return control.lowered(conf)


CONTROLS = {"full": full, "wider": wider, "int8": int8}


def main(argv=None) -> int:
    """``control.py``'s run with the seed's weights made ONCE and every
    side (the engine as the file states it, then each control asked for)
    built over them: the sides differ in their attention's mask and their
    cache, not in a weight, and at the cell's size the weights are most of
    a build."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--control", choices=sorted(CONTROLS), nargs="+",
                    required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--prompt-tokens", type=int, nargs="+")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import logging

    import jax

    from benchmarks import control
    from benchmarks import run as harness
    from benchmarks.lib import build, correct
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.utils import get_tokenizer

    logging.disable(logging.INFO)
    _, cell, conf, traffic = harness.load_cell(args.benchmark, args.workload)
    device = build.describe_device(cell["chips"], args.allow_cpu)
    build.enable_compile_cache()
    group = ({"prompt_tokens": args.prompt_tokens} if args.prompt_tokens
             else traffic["check"])
    sides = [("sound", conf)] + [(name, CONTROLS[name](conf))
                                 for name in args.control]
    rows = []
    for seed in args.seeds:
        params = build.init_params_fn(conf)(
            build.model_config(conf, cell["config"]), jax.random.PRNGKey(seed))
        for side, built in sides:
            mcfg = build.model_config(built, cell["config"])
            engine = make_engine(mcfg, build.engine_config(built), params,
                                 get_tokenizer(vocab_size=mcfg.vocab_size))
            # the reference reads the file as it stands: the control is the
            # program's departure from it
            check = correct.check(engine, conf, seed=seed, **group)
            rows.append({"cell": cell["name"], "seed": seed, "side": side,
                         "attn_windows": list(mcfg.attn_windows),
                         "check": check})
            print(json.dumps(rows[-1]), flush=True)
            del engine
            gc.collect()
        del params
    out = {"cell": cell["name"], "device": device, "rows": rows, "summary": {
        name: control.summary([dict(r, side="control" if r["side"] == name
                                    else r["side"]) for r in rows
                               if r["side"] in ("sound", name)])
        for name in args.control}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_window_{cell['name']}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["summary"]), flush=True)
    return 0 if all(all(s["sound_ok"]) and not any(s["control_ok"])
                    for s in out["summary"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
