"""The readers of the tick's phases and of the stall behind prefills (PR
40): each on made-up counters, ``None`` where the program lacks the name the
reader is for (the parent of PR 40 records ``engine.tick``,
``engine.tick.admission`` and ``engine.tick.eviction`` and none of the new
names), never an exception, and all seven in the line of the CPU
rehearsal."""

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

# what the parent of PR 40 counts in a window: none of the new names
PARENT = {"engine.tick.count": 20.0, "engine.tick.total_s": 5.0,
          "engine.tick.admission.count": 10.0,
          "engine.tick.admission.total_s": 0.5,
          "engine.tick.eviction.count": 20.0,
          "engine.tick.eviction.total_s": 0.01,
          "engine.prefill.count": 12.0, "engine.prefill.total_s": 0.04,
          "engine.fetch.count": 30.0, "engine.fetch.total_s": 4.0,
          "engine.commit.count": 30.0, "engine.commit.total_s": 0.1,
          "engine.decode_step.count": 20.0,
          "engine.decode_step.total_s": 0.06,
          "engine.decode_steps": 320.0, "engine.decode_tokens": 4000.0,
          "engine.dispatches": 32.0}
# what PR 40 adds
NEW = {"engine.tick.first_tokens.count": 10.0,
       "engine.tick.first_tokens.total_s": 1.5,
       "engine.tick.decode.count": 20.0, "engine.tick.decode.total_s": 2.9,
       "engine.admission.stage.count": 12.0,
       "engine.admission.stage.total_s": 0.2,
       "engine.admission.activate.count": 12.0,
       "engine.admission.activate.total_s": 0.1,
       "engine.scan_setup.count": 20.0, "engine.scan_setup.total_s": 0.05,
       "engine.prefill_stall_seq_s": 8.0}
FULL = dict(PARENT, **NEW)
# reader -> the value it reads from FULL (no reap, no prefill_chunk: they
# never ran)
WANT = {"tick_prefill_phase_ms": 1e3 * (0.5 + 1.5) / 20,
        "tick_decode_phase_ms": 1e3 * 2.9 / 20,
        "tick_unnamed_ms": 1e3 * (5.0 - 0.5 - 1.5 - 0.01 - 2.9) / 20,
        "tick_admission_stage_ms": 1e3 * 0.2 / 20,
        "tick_admission_activate_ms": 1e3 * 0.1 / 20,
        "tick_scan_setup_ms": 1e3 * 0.05 / 20,
        "prefill_stall_ms_per_token": 1e3 * 8.0 / 4000}


def read(name, counters):
    reader = importlib.import_module("benchmarks.layer_metrics." + name)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_made_up_counters(name):
    assert read(name, dict(FULL)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_on_the_parents_counters(name):
    assert read(name, {}) is None
    assert read(name, dict(PARENT)) is None
    zeroed = dict(PARENT, **{k: 0.0 for k in NEW
                             if k != "engine.prefill_stall_seq_s"})
    assert read(name, zeroed) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_never_raises_with_one_name_missing(name):
    for gone in FULL:
        counters = {k: v for k, v in FULL.items() if k != gone}
        value = read(name, counters)
        assert value is None or isinstance(value, float)


def test_phases_that_ran_are_counted_and_a_stall_of_nothing_reads_zero():
    ran = dict(FULL, **{"engine.tick.reap.total_s": 0.02,
                        "engine.tick.prefill_chunk.total_s": 0.3})
    assert read("tick_prefill_phase_ms", ran) == pytest.approx(
        WANT["tick_prefill_phase_ms"] + 1e3 * 0.3 / 20)
    assert read("tick_unnamed_ms", ran) == pytest.approx(
        WANT["tick_unnamed_ms"] - 1e3 * 0.32 / 20)
    assert read("prefill_stall_ms_per_token",
                dict(FULL, **{"engine.prefill_stall_seq_s": 0.0})) == 0.0


def test_the_seven_entries_are_appended_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]
             if w["name"] in ("mistral7b.chat-open",
                              "mixtral-d8.audit-prefill",
                              "nemotron3-super-d11.audit-report",
                              "k-exaone-d5.longdump-reason")]
    for name in WANT:
        m = by_name[name]
        reader = importlib.import_module("benchmarks.layer_metrics." + name)
        assert (m["layer"], m["unit"], m["moves"]) == (
            reader.LAYER, reader.UNIT, reader.MOVES)
        assert m["source"] == "program_counter"
        assert m["workloads"][:4] == cells


def test_rehearsal_prints_all_seven():
    """The CPU rehearsal of ``benchmarks/README.md`` with the rehearsal file
    that lists the new entries: host spans and counts, never a device
    metric."""
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--benchmark", os.path.join(HERE, "rehearsal-tick.json"),
           "--workload", "tiny.audit-prefill", "--seed", "2147483659",
           "--seconds", "4", "--trace", "1", "--allow-cpu"]
    done = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    report, last = [json.loads(x)
                    for x in done.stdout.strip().splitlines()][-2:]
    assert last["correct"] and last["failed"] == 0
    # a program that lacks the names prints none of the seven, and that is
    # no fault (the parent's side of the driver's comparison)
    assert set(last["metrics"]) in (set(WANT), set())
    if last["metrics"]:
        values = {k: m["value"] for k, m in last["metrics"].items()}
        assert all(v >= 0.0 for k, v in values.items()
                   if k != "tick_unnamed_ms")
        tick_ms = 1e3 * (report["run"]["window_s"]
                         / report["run"]["generator"]["ticks_in_window"])
        assert abs(values["tick_unnamed_ms"]) < 0.05 * tick_ms
    assert report["run"]["compiles_in_window"] == 0
