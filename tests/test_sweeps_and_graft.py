"""Driver-level tests: batch sweep output schema + resumability, and the
graft entry points (single-chip compile check, multi-chip dry run)."""

import json
import os

import pytest

from k8s_llm_rca_tpu.sweeps import run_file


def test_run_file_schema_and_resume(tmp_path):
    inp = str(tmp_path / "incidents.csv")
    out = str(tmp_path / "results.json")

    summary = run_file.main([
        "--input", inp, "--output", out, "--slice", "0:2"])
    assert summary["incidents"] == 2
    assert summary["p50_incident_s"] > 0

    # output: concatenated pretty-printed JSON records, reference schema
    assert run_file.completed_incidents(out) == 2
    first = json.loads(open(out).read().split("}\n{")[0] + "}")
    assert {"error_message", "locator_attempts", "analysis", "time_cost",
            "token_usage"} <= set(first)
    a = first["analysis"][0]
    assert {"extend_metapath", "cypher_query", "cypher_attempts",
            "statepath"} <= set(a)
    assert {"report", "clue"} <= set(a["statepath"][0])

    # resume: skips the two finished incidents, appends the rest
    summary2 = run_file.main([
        "--input", inp, "--output", out, "--resume"])
    assert summary2["incidents"] == 2          # 4 total - 2 done
    assert run_file.completed_incidents(out) == 4


def test_graft_entry_jits():
    import jax

    import __graft_entry__ as graft

    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == 2 and out.ndim == 3


def test_graft_dryrun_multichip():
    import __graft_entry__ as graft

    graft.dryrun_multichip(8)


def test_run_file_replicated_oracle(tmp_path):
    """DP sweep serving (round-1 review item 6): N pipeline replicas drain one
    queue; every incident lands exactly once, per-replica accounting sums."""
    inp = str(tmp_path / "incidents.csv")
    out = str(tmp_path / "results.json")
    run_file.write_default_corpus(inp, repeat=2)    # 8 incidents

    summary = run_file.main([
        "--input", inp, "--output", out, "--replicas", "3"])
    assert summary["incidents"] == 8
    assert summary["failures"] == 0
    assert run_file.completed_incidents(out) == 8
    reps = summary["replicas"]
    assert [r["replica"] for r in reps] == [0, 1, 2]
    assert sum(r["incidents"] for r in reps) == 8
    # records parse individually (concurrent appends serialized by the lock)
    text = open(out).read()
    decoder = json.JSONDecoder()
    idx, seen = 0, 0
    while idx < len(text.rstrip()):
        obj, idx = decoder.raw_decode(text, idx)
        while idx < len(text) and text[idx].isspace():
            idx += 1
        assert "error_message" in obj
        seen += 1
    assert seen == 8


def test_run_file_replicated_engine(tmp_path):
    """DP x engine: two device-pinned TINY engine replicas share the queue
    (the virtual-CPU stand-in for one-replica-per-chip pod serving)."""
    import jax

    inp = str(tmp_path / "incidents.csv")
    out = str(tmp_path / "results.json")

    summary = run_file.main([
        "--input", inp, "--output", out, "--slice", "0:2",
        "--backend", "engine", "--replicas", "2",
        "--max-seq-len", "1024"])
    assert summary["incidents"] == 2
    assert run_file.completed_incidents(out) == 2
    reps = summary["replicas"]
    assert sum(r["incidents"] for r in reps) == 2
    devs = {r["device"] for r in reps}
    assert len(devs) == 2              # round-robin actually pinned 2 devices


def test_run_file_shared_workers_oracle(tmp_path):
    """Shared-service concurrent sweep (--workers): N threads drive their
    own pipelines against ONE AssistantService; every incident lands
    exactly once and each record is a full, valid report."""
    inp = str(tmp_path / "incidents.csv")
    out = str(tmp_path / "results.json")
    run_file.write_default_corpus(inp, repeat=2)    # 8 incidents

    summary = run_file.main([
        "--input", inp, "--output", out, "--workers", "4"])
    assert summary["incidents"] == 8
    assert summary["failures"] == 0
    assert summary["workers"] == 4
    assert run_file.completed_incidents(out) == 8


def test_run_file_chaos_kill_and_resume(tmp_path):
    """Chaos: SIGKILL the shared-engine sweep process mid-flight, then
    --resume.  The resumed run must complete the sweep with NO duplicated
    and NO lost incidents — even though concurrent workers complete
    incidents out of input order (so a count-based "skip the first N"
    would corrupt the sweep) and the kill can leave a partial tail record
    (which resume truncates)."""
    import os
    import signal
    import subprocess
    import sys
    import time
    from collections import Counter

    inp = str(tmp_path / "incidents.csv")
    out = str(tmp_path / "results.json")
    run_file.write_default_corpus(inp, repeat=6)    # 24 incidents
    corpus = run_file.load_corpus(inp)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "k8s_llm_rca_tpu.sweeps.run_file",
         "--input", inp, "--output", out, "--workers", "4"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            done = run_file.completed_incidents(out)
            if done >= 4:
                break
            if proc.poll() is not None:
                break
            time.sleep(0.05)
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)    # hard kill, mid-append ok
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()

    survivors, _ = run_file.scan_output(out)
    # the kill must land mid-sweep for the test to mean anything
    assert 0 < len(survivors) < len(corpus), len(survivors)

    summary = run_file.main([
        "--input", inp, "--output", out, "--workers", "4", "--resume"])
    assert summary["incidents"] == len(corpus) - len(survivors)

    final, _ = run_file.scan_output(out)
    # exactly-once at incident granularity: multiset equality with input
    assert Counter(final) == Counter(corpus), (
        Counter(final) - Counter(corpus), Counter(corpus) - Counter(final))


def test_run_file_shared_workers_engine(tmp_path):
    """Concurrent workers over ONE TINY engine: the continuous batcher
    carries runs from different incidents in the same ticks, and the
    per-incident reports match a serial run of the same slice (greedy
    decode => order-independent outputs)."""
    inp = str(tmp_path / "incidents.csv")
    out_shared = str(tmp_path / "shared.json")
    out_serial = str(tmp_path / "serial.json")

    common = ["--input", inp, "--slice", "0:3", "--backend", "engine",
              "--max-seq-len", "1024", "--max-batch", "6"]
    s1 = run_file.main(common + ["--output", out_shared, "--workers", "3"])
    assert s1["incidents"] == 3 and s1["failures"] == 0
    s2 = run_file.main(common + ["--output", out_serial])
    assert s2["incidents"] == 3 and s2["failures"] == 0

    def reports(path):
        text, decoder, idx, objs = open(path).read(), json.JSONDecoder(), 0, []
        while idx < len(text.rstrip()):
            obj, idx = decoder.raw_decode(text, idx)
            while idx < len(text) and text[idx].isspace():
                idx += 1
            objs.append(obj)
        return objs

    shared = {r["error_message"]: r for r in reports(out_shared)}
    serial = {r["error_message"]: r for r in reports(out_serial)}
    assert shared.keys() == serial.keys()
    for msg, rec in serial.items():
        # timing/token fields differ; the analysis content must not
        assert shared[msg]["analysis"] == rec["analysis"], msg


def test_workers_and_replicas_mutually_exclusive(tmp_path):
    import pytest

    inp = str(tmp_path / "incidents.csv")
    run_file.write_default_corpus(inp)
    with pytest.raises(SystemExit):
        run_file.main(["--input", inp, "--workers", "2", "--replicas", "2"])


def test_stage_harnesses(capsys):
    """The four stage-isolated operator harnesses (the reference's
    test_find_metapath/test_generate_query/test_check_state/test_token
    equivalents) each run hermetically and print a JSON result."""
    import json as _json

    from k8s_llm_rca_tpu.sweeps import stage

    out = stage.main(["locate"])
    assert out["srcKind"] == "Pod"
    assert out["plan"]["DestinationKind"] == "Secret"
    assert ["Pod", "Secret"] in out["metapaths"]

    out = stage.main(["cypher"])
    assert out["records"] >= 1 and out["human_records"] >= 1
    assert "MATCH" in out["human_cypher_query"]

    out = stage.main(["audit"])
    assert out["entity"] == "Secret(sec-0001)"
    assert any("apparent error" in c for c in out["clues"])

    out = stage.main(["token"])
    assert out["run_status"] == "completed"
    assert out["token_usage"]["total_tokens"] > 0
    # every harness printed a JSON document (last one is parseable as-is)
    printed = capsys.readouterr().out.strip()
    assert printed.endswith("}")
    _json.loads(printed[printed.rindex("\n{"):])
