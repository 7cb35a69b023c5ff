"""The benchmark's own tests (CPU, not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They check the yardstick, not the program: the seeded streams, the percentile
rule, the output contract, the refusal to print device metrics from a CPU,
the steering backend, the data-driven lookup, and the trace reduction against
a small trace recorded on a TPU v5e.
"""

from __future__ import annotations

import gzip
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.join(BENCH, "tests")

from benchmarks.lib import observe, stats  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# the harness's own, last in the line: each number compared beside its limit
COMPARED = "compared"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(path):
    with open(path) as f:
        return json.load(f)


def run_cell(args, benchmark=None, timeout=900):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"),
           "--benchmark", benchmark or os.path.join(HERE, "rehearsal.json"),
           *args]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=ROOT)


# ------------------------------------------------------------ seeded streams


@pytest.mark.parametrize("mix", ["chat-open", "audit-prefill"])
def test_same_seed_same_request_stream(mix):
    from benchmarks.generators import requests

    params = load(os.path.join(BENCH, "traffic", mix + ".json"))

    def stream(seed):
        out = requests.make_requests(np.random.default_rng(seed), params, 64)
        return [None if x is None else [str(v) for v in x] for x in out]

    assert stream(7) == stream(7)
    assert stream(7) != stream(8)
    _, p_len, o_len, _, _ = requests.make_requests(
        np.random.default_rng(7), params, 512)
    assert p_len.min() >= params["prompt_tokens"]["min"]
    assert p_len.max() <= params["prompt_tokens"]["max"]
    assert o_len.min() >= params["output_tokens"]["min"]


def test_same_seed_same_incident_stream():
    from benchmarks.generators import rca_sweep

    a = rca_sweep.draw_incidents(np.random.default_rng(3), 200)
    assert a == rca_sweep.draw_incidents(np.random.default_rng(3), 200)
    assert a != rca_sweep.draw_incidents(np.random.default_rng(4), 200)
    assert len(set(a)) == 4


def test_text_has_exact_length():
    from benchmarks.lib import text

    for n in (0, 1, 17, 2048):
        assert len(text.words(np.random.default_rng(n), n)) == n


# ----------------------------------------------------------- percentile rule


def test_no_p90_under_100_samples():
    assert stats.percentile(list(range(99)), 90.0) is None
    assert stats.percentile(list(range(100)), 90.0) == pytest.approx(89.1)
    assert stats.percentile(list(range(199)), 95.0) is None
    assert stats.median([3.0, 1.0, 2.0]) == 2.0
    assert stats.median([]) is None


def test_spread_is_interquartile_over_median():
    assert stats.spread([10.0, 10.0, 10.0, 10.0]) == 0.0
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(2 / 3)


# ------------------------------------------------------- schema validation


def test_validator_reads_the_schema_dialect():
    schema = {"type": "object", "properties": [
        ("kind", {"enum": ["Pod", "Node"]}),
        ("path", {"type": "array", "min_items": 1, "max_items": 2,
                  "items": {"type": "integer"}})]}
    assert observe.validates(schema, '{"kind": "Pod", "path": [1]}')
    assert not observe.validates(schema, '{"kind": "Job", "path": [1]}')
    assert not observe.validates(schema, '{"kind": "Pod", "path": []}')
    assert not observe.validates(schema, '{"kind": "Pod", "path": [1]')
    choice = {"type": "choice", "options": ["MATCH a", "MATCH b"]}
    assert observe.validates(choice, "MATCH b")
    assert not observe.validates(choice, "MATCH c")


# ---------------------------------------------------------------- steering


class _FakeEngine:
    class engine_cfg:
        native = False

    def __init__(self):
        self._active = {}


class _FakeInner:
    """What ``SteeredEngineBackend`` needs of ``EngineBackend``."""

    def __init__(self, tokenizer):
        self.engine, self.tokenizer = _FakeEngine(), tokenizer
        self._handle_seq = {}
        self.started = []

    def start(self, prompt, opts):
        self.started.append((prompt, opts))
        self._handle_seq[len(self.started) - 1] = 100 + len(self.started)
        return len(self.started) - 1

    def pump(self):
        from k8s_llm_rca_tpu.serve.backend import BackendResult

        return {h: BackendResult(text=o.forced_prefix + "engine noise"
                                 + o.suffix,
                                 completion_tokens=o.max_new_tokens,
                                 prompt_tokens=len(p))
                for h, (p, o) in enumerate(self.started)}


def test_steered_backend_oracle_text_engine_length():
    from k8s_llm_rca_tpu.rca.oracle import OracleBackend
    from k8s_llm_rca_tpu.serve.backend import GenOptions
    from k8s_llm_rca_tpu.utils import get_tokenizer

    tok = get_tokenizer(vocab_size=512)
    inner = _FakeInner(tok)
    backend = observe.SteeredEngineBackend(inner, OracleBackend(tok))
    prompt = ("<|system|>\ns\n<|user|>\nThe following JSON comes from a Pod "
              "object: {'status': 'Pending'}\n<|assistant|>\n")
    opts = GenOptions(max_new_tokens=512, forced_prefix="```\n",
                      suffix="\n```",
                      assistant_name="k8s-state-semantic-analyzer")
    oracle = OracleBackend(tok)
    asked = oracle.start(prompt, opts)
    want = oracle.pump()[asked].text
    handle = backend.start(prompt, opts)
    body = want[len("```\n"):-len("\n```")]
    # the engine gets the same prompt and options at the oracle's length
    sent_prompt, sent = inner.started[0]
    assert sent_prompt == prompt
    assert sent.max_new_tokens == len(tok.encode(body)) != 512
    assert sent.forced_prefix == opts.forced_prefix
    # the pipeline gets the oracle's text with the engine's counts
    result = backend.pump()[handle]
    assert result.text == want
    assert result.completion_tokens == sent.max_new_tokens
    assert backend.reqs[handle].tokens == sent.max_new_tokens


# ------------------------------------------------------------ the contract


def test_benchmark_json_meets_the_contract():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/")
        conf = load(os.path.join(ROOT, c["file"]))
        assert all(k in conf and NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    seen = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert all(w in cells for w in m.get("workloads", []))
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        reader = importlib.import_module("benchmarks.layer_metrics."
                                         + m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            m["layer"], m["unit"], m["moves"])
    for name, w in cells.items():       # every cell reports of each kind
        assert any("workloads" not in m or name in m["workloads"]
                   for m in bench["per_layer"])


def test_every_traffic_file_names_a_generator_and_its_shapes():
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        traffic = load(os.path.join(BENCH, "traffic", name))
        generator = importlib.import_module("benchmarks.generators."
                                            + traffic["generator"])
        assert generator.BACKEND in ("observed", "steered")
        # one prefill shape, or the lengths to check at (lib/correct.py)
        assert set(traffic["check"]) in ({"rows", "bucket"},
                                         {"prompt_tokens"})
        warm = traffic["warm"]
        # shapes are derived from the mix's own length range, or listed
        assert warm.get("derive") is True or warm.get("miss")
        if warm.get("derive"):
            assert {"min", "max"} <= set(traffic["prompt_tokens"])


class _ShapeEngine:
    """What ``warmup.shapes`` reads off a built engine."""

    def __init__(self, buckets, page=16, max_batch=32, decode_chunk=16):
        from types import SimpleNamespace

        self.engine_cfg = SimpleNamespace(max_batch=max_batch,
                                          decode_chunk=decode_chunk)
        self._buckets, self._page = buckets, page

    def _bucket(self, n):
        return next(-(-b // self._page) * self._page
                    for b in self._buckets if n <= b)


@pytest.mark.parametrize("mix, miss", [
    ("chat-open", [[r, b] for b in (512, 1024, 2048) for r in (1, 2, 4, 8)]),
    ("audit-prefill", [[r, b] for b in (2048, 3072, 4096)
                       for r in (1, 2, 4)]),
])
def test_warm_shapes_follow_the_mix_and_the_engine(mix, miss):
    from benchmarks.lib import warmup

    engine = _ShapeEngine((512, 1024, 2048, 3072, 4096))
    traffic = load(os.path.join(BENCH, "traffic", mix + ".json"))
    spec = warmup.shapes(engine, traffic)
    assert spec["miss"] == miss
    assert spec["decode_scan"] == [1, 2, 4, 8, 16]
    # another length range or bucket table gives other shapes, no edit
    longer = dict(traffic, prompt_tokens=dict(traffic["prompt_tokens"],
                                              max=4096))
    assert [1, 4096] in warmup.shapes(engine, longer)["miss"]
    coarse = warmup.shapes(_ShapeEngine((4096,), decode_chunk=4), traffic)
    assert {b for _, b in coarse["miss"]} == {4096}
    assert coarse["decode_scan"] == [1, 2, 4]


def test_warm_shapes_of_three_callers_pad_to_four_rows():
    from benchmarks.lib import warmup

    traffic = load(os.path.join(BENCH, "traffic", "audit-prefill.json"))
    traffic["arrivals"]["clients"] = 3
    spec = warmup.shapes(_ShapeEngine((2048, 4096)), traffic)
    assert [r for r, b in spec["miss"] if b == 2048] == [1, 2, 4]


def test_unknown_device_kind_is_an_error():
    from benchmarks.trace import costs

    assert costs.peaks("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(ValueError, match="no published peaks"):
        costs.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("env_cap, kept", [
    (None, -1),                       # JAX's default: no cap
    (str(192 << 20), -1),             # under what the cells need: lifted
    (str(4 << 30), 4 << 30),          # holds the cells: the machine's stays
])
def test_compile_cache_cap_is_kept_or_lifted_never_raised(env_cap, kept,
                                                          tmp_path):
    """A finite cap fails every write into a directory that holds an entry
    written under none (PERF.md section 6, PR 22), so a cap that is too small
    is lifted, not replaced by a larger one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    if env_cap is not None:
        env["JAX_COMPILATION_CACHE_MAX_SIZE"] = env_cap
    done = subprocess.run(
        [sys.executable, "-c",
         "import jax; from benchmarks.lib import build; "
         "print(build.enable_compile_cache()); "
         "print(jax.config.jax_compilation_cache_max_size)"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    path, cap = done.stdout.split()
    assert path == str(tmp_path) and int(cap) == kept


# ------------------------------------------------ the harness, end to end


def test_refuses_to_run_a_cell_on_a_cpu():
    """No accelerator: another exit code than 0 and no result."""
    done = run_cell(["--workload", "tiny.chat-open", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert done.returncode != 0
    assert done.stdout.strip() == ""


@pytest.fixture(scope="module")
def throwaway_cell():
    """An architecture, a configuration, a mix, a per-layer metric and a
    cell of their own, added as files and entries with no edit to a file
    that is there.  The architecture is ``tiny``'s under another
    ``model_type``, with another name for its expert count."""
    arch = load(os.path.join(BENCH, "architectures", "mixtral.json"))
    del arch["fields"]["num_local_experts"]
    arch["fields"]["n_routed_experts"] = "n_experts"
    conf = load(os.path.join(BENCH, "configs", "tiny.json"))
    conf["model_type"] = "throwaway"
    conf["n_routed_experts"] = conf.pop("num_local_experts")
    added = {
        os.path.join(BENCH, "architectures", "throwaway.json"): arch,
        os.path.join(BENCH, "configs", "throwaway.json"): conf,
        os.path.join(BENCH, "traffic", "throwaway-mix.json"): dict(
            load(os.path.join(BENCH, "traffic", "chat-open.json")),
            ramp_s=2, arrivals={"kind": "poisson", "rate_rps": 3.0}),
    }
    reader = os.path.join(BENCH, "layer_metrics", "throwaway_ticks.py")
    bench = load(os.path.join(HERE, "rehearsal.json"))
    bench["configs"].append(
        {"name": "throwaway", "source": "none",
         "file": "benchmarks/configs/throwaway.json", "reduced": [],
         "why": "test"})
    bench["workloads"].append(
        {"name": "throwaway.cell", "config": "throwaway",
         "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    bench["per_layer"] = [
        {"name": "throwaway_ticks", "unit": "ticks", "better": "higher",
         "source": "host_clock", "layer": "Engine tick (engine/paged.py)",
         "moves": "out_tokens_per_s"},
        {"name": "tokens_per_tick", "unit": "tokens", "better": "higher",
         "source": "program_counter",
         "layer": "Engine tick (engine/paged.py)",
         "moves": "out_tokens_per_s"},
        {"name": "device_idle_share", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "Device",
         "moves": "out_tokens_per_s"}]
    bench_path = os.path.join(HERE, "throwaway_benchmark.json")
    try:
        for path, content in added.items():
            with open(path, "w") as f:
                json.dump(content, f)
        with open(reader, "w") as f:
            f.write('LAYER = "Engine tick (engine/paged.py)"\n'
                    'UNIT = "ticks"\nMOVES = "out_tokens_per_s"\n\n\n'
                    'def read(ctx):\n    return len(ctx.ticks)\n')
        with open(bench_path, "w") as f:
            json.dump(bench, f)
        yield bench_path
    finally:
        for path in [*added, reader, bench_path]:
            if os.path.exists(path):
                os.remove(path)


def _lines(done):
    assert done.returncode == 0, done.stderr[-3000:]
    return [json.loads(x) for x in done.stdout.strip().splitlines()]


def test_new_files_run_a_new_cell_and_the_last_line_keeps_the_contract(
        throwaway_cell):
    done = run_cell(["--workload", "throwaway.cell", "--seed", "5",
                     "--seconds", "3", "--trace", "0", "--allow-cpu"],
                    benchmark=throwaway_cell)
    *_, last = _lines(done)
    assert set(last) == CONTRACT_KEYS | {COMPARED}
    assert list(last)[-1] == COMPARED
    for name, c in last[COMPARED].items():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
        assert f"compared {name} {c['value']} limit {c['limit']}" in \
            done.stderr.strip().splitlines()[-len(last[COMPARED]):]
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["correct"], bool)
    assert {"out_tokens_per_s", "setup_s"} <= set(last["metrics"])
    assert "ttft_s_p90" not in last["metrics"]       # under 100 samples
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_traced_line_on_a_cpu_holds_no_device_metric(throwaway_cell):
    done = run_cell(["--workload", "throwaway.cell", "--seed", "5",
                     "--seconds", "3", "--trace", "1", "--allow-cpu"],
                    benchmark=throwaway_cell)
    report, last = _lines(done)[-2:]
    assert set(last) == CONTRACT_KEYS | {COMPARED}
    assert last["metrics"]["throwaway_ticks"]["value"] >= 1
    assert "tokens_per_tick" in last["metrics"]
    assert "device_idle_share" not in last["metrics"]
    assert "busy_s" not in last["device"]
    assert report["run"]["compiles_in_window"] == 0
    assert report["run"]["check"]["ok"]
    # near-ties are allowed because the architecture's file says why
    assert report["run"]["check"]["positions_allowed_over"] == 18 // 3
    assert report["run"]["check"]["driver"] == "paged_kv"


SWEEP_READERS = ("report_s_p50", "incidents_inflight_mean",
                 "runs_per_incident", "grammar_dfa_share",
                 "prefix_hit_share")


def test_sweep_rehearsal_keeps_the_sweep_readers_alive(tmp_path):
    """No cell of ``BENCHMARK.json`` runs the ``rca_sweep`` generator yet
    (PERF.md section 6, PR 22: the by-rows admission cap), so the rehearsal
    is what keeps the generator, the steering backend and the sweep's
    readers running together until a PR adds the cell as data."""
    bench = load(os.path.join(HERE, "rehearsal.json"))
    for name in SWEEP_READERS:
        reader = importlib.import_module("benchmarks.layer_metrics." + name)
        bench["per_layer"].append(
            {"name": name, "unit": reader.UNIT, "better": "lower",
             "source": "program_counter", "layer": reader.LAYER,
             "moves": reader.MOVES, "workloads": ["tiny.rca-sweep"]})
    path = tmp_path / "sweep_benchmark.json"
    path.write_text(json.dumps(bench))
    done = run_cell(["--workload", "tiny.rca-sweep", "--seed", "4",
                     "--seconds", "4", "--trace", "1", "--allow-cpu"],
                    benchmark=str(path))
    report, last = _lines(done)[-2:]
    assert last["correct"] and last["failed"] == 0
    assert report["run"]["compiles_in_window"] == 0
    # one incident in flight, its grammars interpreted at this vocabulary;
    # a reader with nothing to read (no incident ends in 4 s) is left out
    assert last["metrics"]["incidents_inflight_mean"]["value"] == 1.0
    assert last["metrics"]["grammar_dfa_share"]["value"] == 0.0
    assert set(last["metrics"]) <= set(SWEEP_READERS)


# --------------------------------------------------------- trace reduction


@pytest.fixture(scope="module")
def sample_trace(tmp_path_factory):
    packed = os.path.join(HERE, "data", "sample.xplane.pb.gz")
    path = tmp_path_factory.mktemp("trace") / "sample.xplane.pb"
    with gzip.open(packed, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_interval_arithmetic():
    from benchmarks.trace import reduce

    assert reduce.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert reduce.gaps_ns([(5, 10), (20, 30)], 0, 40) == [
        (0, 5), (10, 20), (30, 40)]
    assert reduce.program_name("jit_paged_decode_scan(123)") == \
        "paged_decode_scan"


def test_reduce_recorded_v5e_trace(sample_trace):
    from benchmarks.trace import costs, reduce

    expected = load(os.path.join(HERE, "data", "sample.expected.json"))
    got = reduce.reduce_file(sample_trace)
    assert got["chips"] == 1
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    for name, p in expected["programs"].items():
        assert got["programs"][name]["count"] == p["count"]
        assert got["programs"][name]["seconds"] == pytest.approx(
            p["seconds"], rel=1e-9)
    assert [k for k, _ in got["idle_gaps"]] == \
        [k for k, _ in expected["idle_gaps"]]
    # 41 scans of 16 steps on the 2-layer ``tiny`` configuration; the sample
    # predates the engine's step counter (PR 23), so the steps it would have
    # counted are the kernel's calls over the layers
    seconds = costs.decode_program_time(got["programs"])
    steps = sum(c for name, c in got["op_counts"].items()
                if costs.PAGED_ATTENTION.search(name)) / 2
    assert steps == 41 * 16 and seconds > 0
    assert costs.kernel_time(got["op_seconds"], costs.PAGED_ATTENTION) > 0
    from types import SimpleNamespace

    from benchmarks.layer_metrics import decode_step_ms

    ctx = SimpleNamespace(trace=dict(
        got, counters={"engine.decode_steps": steps}))
    assert decode_step_ms.read(ctx) == pytest.approx(1e3 * seconds / steps)
    ctx.trace["counters"] = {}              # a program that counts no steps
    assert decode_step_ms.read(ctx) is None


class _Ev:
    def __init__(self, name, start, duration):
        self.name, self.start_ns, self.duration_ns = name, start, duration


class _Named:
    def __init__(self, name, **kw):
        self.name = name
        self.__dict__.update(kw)


def test_decode_steps_come_from_the_engine_and_gaps_name_its_phases():
    """Scans of 16 and of 4 steps and a stepwise program are the same
    function to the trace; the steps are what the engine counted while
    traced (21, not 2 x 16 + 1), whether or not a layer calls the
    paged-attention kernel.  The idle gap before each program lies inside
    ``bench.pump`` and ``engine.tick`` and is charged to the innermost
    phase that covers it."""
    from types import SimpleNamespace

    from benchmarks.layer_metrics import decode_step_ms
    from benchmarks.trace import reduce

    layers, ops, modules, host = 2, [], [], []
    t = 1_000

    def program(name, fingerprint, steps, scan, phase):
        nonlocal t
        start = t
        if scan:
            t += 10
        body = t
        for _ in range(steps):
            for layer in range(layers):
                ops.append(_Ev(f"%some_attention.{layer} = bf16[32,32,128] "
                               f"custom-call(...)", t, 50))
                ops.append(_Ev(f"%fusion.{layer} = bf16[32,4096] fusion(...)",
                               t + 50, 30))
                t += 100
        if scan:
            ops.append(_Ev("%while.1 = (...) while(...)", body - 5,
                           t - body + 10))
            t += 10
        modules.append(_Ev(f"jit_{name}({fingerprint})", start, t - start))
        host.append(_Ev(f"PjitFunction({name})", start - 500, 100))
        # the 100 us in which the device waits for the next program
        host.append(_Ev("bench.pump", t - 1_000, 102_000))
        host.append(_Ev("engine.tick", t, 100_000))
        host.append(_Ev(phase, t + 10_000, 90_000))
        t += 100_000

    program("paged_decode_scan", 16, 16, True, "engine.fetch")
    program("paged_decode_scan", 4, 4, True, "engine.commit")
    program("paged_decode_step", 1, 1, False, "engine.grammar_mask")
    ops.append(_Ev("%fusion.9 = bf16[32,4096] fusion(...)", t, 30))
    data = _Named("trace", planes=[
        _Named("/device:TPU:0", lines=[
            _Named("XLA Ops", events=ops),
            _Named("XLA Modules", events=modules)]),
        _Named("/host:CPU", lines=[_Named("python", events=host)])])
    got = reduce.reduce(data)
    assert got["programs"]["paged_decode_scan"]["count"] == 2
    assert got["programs"]["paged_decode_step"]["count"] == 1
    got["counters"] = {"engine.decode_steps": 21.0}
    assert decode_step_ms.read(SimpleNamespace(trace=got)) == pytest.approx(
        1e3 * 1e-9 * sum(m.duration_ns for m in modules) / 21)
    gaps = dict(got["idle_gaps"])
    assert {"engine.fetch", "engine.commit", "engine.grammar_mask"} <= set(
        gaps)
    assert "bench.pump" not in gaps and "engine.tick" not in gaps
    assert reduce.HOST_SPANS.index("engine.fetch") < \
        reduce.HOST_SPANS.index("engine.tick") < \
        reduce.HOST_SPANS.index("bench.pump")
