"""The readers of what the engine itself records (``engine.*`` timers and
work counters, PR 23): each on made-up counters, ``None`` where its
denominator is zero or the program counts no such thing (the parent of
PR 23 does not), and all eight in the line of the CPU rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmarks", "tests")

# reader -> (counters, the value it reads from them)
CASES = {
    "engine_tpot_ms": ({"engine.tpot.total_s": 0.3, "engine.tpot.count": 4.0},
                       75.0),
    "engine_ttft_s": ({"engine.ttft.total_s": 6.0, "engine.ttft.count": 4.0},
                      1.5),
    "engine_queue_wait_ms": ({"engine.queue_wait.total_s": 0.01,
                              "engine.queue_wait.count": 4.0}, 2.5),
    "tick_host_ms": ({"engine.tick.total_s": 5.0, "engine.tick.count": 20.0,
                      "engine.fetch.total_s": 4.9}, 5.0),
    "scan_steps_per_dispatch": ({"engine.decode_steps": 96.0,
                                 "engine.decode_step.count": 24.0}, 4.0),
    "scan_cut_by_pages_share": ({"engine.scan_limit.full": 6.0,
                                 "engine.scan_limit.pages": 3.0,
                                 "engine.scan_limit.headroom": 3.0}, 25.0),
    "paged_attn_live_page_share": ({"engine.attn_pages_live": 720.0,
                                    "engine.attn_pages_grid": 8192.0},
                                   100.0 * 720 / 8192),
    "prefill_pad_share": ({"engine.prefill_tokens": 6000.0,
                           "engine.prefill_padded_tokens": 8192.0},
                          100.0 * (1 - 6000 / 8192)),
}
# what the parent of PR 23 counts in a window: none of the above
PARENT = {"engine.decode_step.count": 24.0, "engine.decode_step.total_s": 0.1,
          "engine.decode_tokens": 900.0, "engine.prefill_tokens": 6000.0,
          "engine.prefill.count": 3.0, "engine.dispatches": 27.0}


def read(name, counters):
    reader = importlib.import_module("benchmarks.layer_metrics." + name)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_on_made_up_counters(name):
    counters, want = CASES[name]
    assert read(name, dict(PARENT, **counters)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_finds_nothing_without_its_counters(name):
    assert read(name, {}) is None
    assert read(name, dict(PARENT)) is None
    zeroed = dict(PARENT, **{k: 0.0 for k in CASES[name][0]})
    assert read(name, zeroed) is None


def test_a_tick_that_no_bound_cut_reads_zero_not_nothing():
    assert read("scan_cut_by_pages_share",
                {"engine.scan_limit.full": 9.0}) == 0.0


def test_entries_are_appended_and_nothing_else_changed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(CASES):] == [
        "engine_tpot_ms", "engine_ttft_s", "engine_queue_wait_ms",
        "tick_host_ms", "scan_steps_per_dispatch", "scan_cut_by_pages_share",
        "paged_attn_live_page_share", "prefill_pad_share"]
    used = {m["source"] for m in bench["per_layer"][:-len(CASES)]}
    assert {m["source"] for m in bench["per_layer"][-len(CASES):]} <= used


def test_rehearsal_prints_all_eight():
    """The CPU rehearsal of ``benchmarks/README.md`` with the rehearsal file
    that lists the new entries: counts and host spans, never a device
    metric."""
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--benchmark", os.path.join(HERE, "rehearsal-engine.json"),
           "--workload", "tiny.audit-prefill", "--seed", "2147483659",
           "--seconds", "4", "--trace", "1", "--allow-cpu"]
    done = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    report, last = [json.loads(x)
                    for x in done.stdout.strip().splitlines()][-2:]
    assert last["correct"] and last["failed"] == 0
    assert set(last["metrics"]) == set(CASES)
    values = {k: m["value"] for k, m in last["metrics"].items()}
    assert 1.0 <= values["scan_steps_per_dispatch"] <= 16.0
    for share in ("scan_cut_by_pages_share", "paged_attn_live_page_share",
                  "prefill_pad_share"):
        assert 0.0 <= values[share] <= 100.0
    assert report["run"]["compiles_in_window"] == 0
