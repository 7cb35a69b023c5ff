"""The seam between the logits check and a model's cache
(``benchmarks/checks/``, named by an architecture's ``check`` key): that the
door refuses a missing or unknown driver by name, that ``paged_kv`` gives what
the check gave before the seam, to the digit, that the lengths a mix states
reach the driver and take the engine's own path, that a second driver is a
new file and no edit, and that the check refuses what it is there to refuse:
a dropped layer, a broken decode program, and a cache one precision below
the one the file states (the lower-precision control, at a size a CPU holds).
"""

import dataclasses
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.join(BENCH, "tests")
SEED = 5

from benchmarks.lib import build, correct  # noqa: E402
from benchmarks.tests import parent_check  # noqa: E402

# what decides, and what the parent's check reported beside it
DECIDES = ("ok", "max_abs_err", "ref_max_abs", "rel_err", "median_rel_err",
           "positions_over", "positions_allowed_over", "argmax_equal",
           "positions", "prompt_tokens")
# what PR 27's review added beside them
SINCE = ("cache_rel_err", "cache_rel_errs", "compared")


def tiny(**changes):
    return dict(build.load_json(os.path.join(BENCH, "configs", "tiny.json")),
                **changes)


@pytest.fixture(scope="module")
def engine():
    return build.build_engine(tiny(), "tiny", SEED)[0]


# ------------------------------------------------------------------ the door


@pytest.mark.parametrize("change, named", [
    (lambda arch: arch.pop("check"), "the key 'check' is missing"),
    (lambda arch: arch.update(check="state_per_slot"),
     "no check driver 'state_per_slot'"),
    (lambda arch: arch.update(check="__init__"),
     "no check driver '__init__'"),
])
def test_the_door_refuses_a_missing_or_unknown_driver_by_name(
        change, named, tmp_path, monkeypatch):
    arch = build.load_json(os.path.join(BENCH, "architectures",
                                        "mixtral.json"))
    change(arch)
    (tmp_path / "mixtral.json").write_text(json.dumps(arch))
    monkeypatch.setattr(build, "ARCH_DIR", str(tmp_path))
    with pytest.raises(ValueError, match=re.escape(named)) as refused:
        build.model_config(tiny(), "tiny")       # before anything is built
    assert "paged_kv" in str(refused.value)      # what it could have named


@pytest.mark.parametrize("name", ["mistral", "mixtral"])
def test_every_architecture_names_a_driver_that_is_there(name):
    arch = build.load_json(os.path.join(BENCH, "architectures",
                                        name + ".json"))
    driver = build.check_driver({"model_type": name})
    assert driver.__name__ == "benchmarks.checks." + arch["check"]
    assert callable(driver.run) and callable(driver.cached)
    assert callable(driver.decode_once)
    # near-ties are allowed where the file gives the reason, nowhere else
    assert ("near_ties" in arch) == (name == "mixtral")


# -------------------------------------------- the same check, to the digit


def test_paged_kv_gives_what_the_check_gave_before_the_seam(engine):
    conf = tiny()
    before = parent_check.check(engine, conf, rows=2, bucket=512, seed=SEED)
    after = correct.check(engine, conf, seed=SEED, rows=2, bucket=512)
    assert after.pop("driver") == "paged_kv"
    # beside the parent's keys: the cache's own number, and every number
    # that decides with its limit
    since = {k: after.pop(k) for k in SINCE}
    assert tuple(before) == DECIDES
    assert after == before and before["ok"]
    compared = since["compared"]
    assert set(compared) == {"positions_over", "median_rel_err",
                             "cache_rel_err", "positions_not_finite"}
    assert compared["positions_over"] == {
        "value": before["positions_over"],
        "limit": before["positions_allowed_over"]}
    assert compared["median_rel_err"] == {
        "value": before["median_rel_err"], "limit": correct.TOLERANCE / 3}
    assert compared["cache_rel_err"] == {
        "value": since["cache_rel_err"], "limit": correct.CACHE_TOLERANCE}
    # keys and values, the tokens a prefill wrote and those the steps wrote
    assert set(since["cache_rel_errs"]) == {"k.prefill", "k.decode",
                                            "v.prefill", "v.decode"}
    assert since["cache_rel_err"] == max(since["cache_rel_errs"].values())
    assert after["ok"] == all(c["value"] <= c["limit"]
                              for c in compared.values())
    assert before["positions_allowed_over"] == 18 // 3     # tiny has experts


def test_without_the_sentence_no_position_may_be_over(engine, tmp_path,
                                                      monkeypatch):
    """The allowance hangs on the architecture's ``near_ties``, not on the
    built model's expert count."""
    arch = build.load_json(os.path.join(BENCH, "architectures",
                                        "mixtral.json"))
    del arch["near_ties"]
    (tmp_path / "mixtral.json").write_text(json.dumps(arch))
    monkeypatch.setattr(build, "ARCH_DIR", str(tmp_path))
    got = correct.check(engine, tiny(), seed=SEED, rows=2, bucket=512)
    assert engine.model_cfg.n_experts > 0
    assert got["positions_allowed_over"] == 0 and got["ok"]


# ------------------------------------------------- the lengths a mix states


def _spy_on_prefills(engine, replace):
    """Note (program, token shape) of every prefill program the engine is
    asked for; ``replace(engine, name, fn)`` puts the spy in."""
    programs = []
    for name in ("_prefill", "_prefill_batch", "_prefill_chunk"):
        def spy(*args, _name=name, _fn=getattr(engine, name)):
            programs.append((_name, args[3].shape))
            return _fn(*args)
        replace(engine, name, spy)
    return programs


class _Recorder:
    """A driver that notes what it was handed and passes it on."""

    def __init__(self):
        from benchmarks.checks import paged_kv

        self.inner, self.calls = paged_kv, []

    def run(self, engine, prompts, steps, **shape):
        self.calls.append(("run", [len(p) for p in prompts], steps, shape))
        return self.inner.run(engine, prompts, steps, **shape)

    def cached(self, engine, prompts, steps, **shape):
        self.calls.append(("cached", [len(p) for p in prompts], steps, shape))
        return self.inner.cached(engine, prompts, steps, **shape)


def test_prompt_tokens_reach_the_driver_and_take_the_engines_path(
        engine, monkeypatch):
    """Two prompts of one bucket go as one batched prefill, one alone in
    its bucket as the single-row program; all agree with the reference."""
    seen = _Recorder()
    monkeypatch.setattr(build, "check_driver", lambda conf: seen)
    programs = _spy_on_prefills(engine, monkeypatch.setattr)
    got = correct.check(engine, tiny(), seed=SEED,
                        prompt_tokens=[700, 300, 290])
    assert seen.calls == [
        (call, [700, 300, 290], correct.STEPS, {})
        for call in ("run", "cached")]
    assert programs == [("_prefill_batch", (2, 512)), ("_prefill", (1, 1024))]
    assert got["ok"] and got["positions"] == 3 * (correct.STEPS + 1)
    assert got["prompt_tokens"] == [700, 300, 290]


def test_a_prompt_over_the_chunk_budget_is_checked_through_chunks():
    conf = tiny()
    conf["engine"] = dict(conf["engine"], prefill_chunk_budget=256)
    chunked = build.build_engine(conf, "tiny", SEED)[0]
    programs = _spy_on_prefills(chunked, setattr)
    got = correct.check(chunked, conf, seed=SEED, prompt_tokens=[700, 200])
    assert programs == [("_prefill_chunk", (1, 256))] * 3 + [
        ("_prefill", (1, 512))]
    assert got["ok"] and got["positions_over"] == 0


@pytest.mark.parametrize("lengths, named", [
    ([300, 4090], "4090 tokens"),      # tiny's max_seq_len is 4096
    ([0], "0 tokens"),
])
def test_a_length_the_engine_cannot_hold_is_refused_by_name(engine, lengths,
                                                            named):
    with pytest.raises(ValueError, match=named) as refused:
        correct.check(engine, tiny(), seed=SEED, prompt_tokens=lengths)
    assert "check.prompt_tokens" in str(refused.value)
    assert "max_seq_len 4096" in str(refused.value)


@pytest.mark.parametrize("group", [
    {}, {"rows": 2}, {"bucket": 512},
    {"rows": 2, "bucket": 512, "prompt_tokens": [300]}])
def test_a_check_group_that_states_neither_form_or_both_is_refused_by_name(
        engine, group):
    with pytest.raises(ValueError, match="either prompt_tokens or rows and "
                                         "bucket") as refused:
        correct.check(engine, tiny(), seed=SEED, **group)
    for key, value in group.items():
        assert f"{key}={value!r}" in str(refused.value)


def test_a_prompt_in_the_top_bucket_is_checked(engine):
    """4,000 tokens and the steps fill 251 of the table's 256 pages: the
    bucket's pages and the steps' are the same ones there."""
    got = correct.check(engine, tiny(), seed=SEED, prompt_tokens=[4000])
    assert engine.pages_per_seq == 256 and engine._bucket(4000) == 4096
    assert got["ok"] and got["positions_over"] == 0
    assert got["prompt_tokens"] == [4000]


# ------------------------------------------ what the check is there to refuse


def test_a_dropped_layer_is_refused_by_every_rule(engine, monkeypatch):
    """The program serves one layer of the two the file states; the
    reference keeps the stated weights."""
    from benchmarks.reference import decoder
    from k8s_llm_rca_tpu.engine import make_engine

    stated, plain = engine.params, decoder.forward
    served = make_engine(
        dataclasses.replace(engine.model_cfg, n_layers=1), engine.engine_cfg,
        {**stated, "layers": stated["layers"][:1]}, engine.tokenizer)

    def forward(conf, params, tokens, at):
        logits, held = plain(conf, stated, tokens, at)
        # the served pool has the one layer, whose cache is sound
        return logits, {name: a[:1] for name, a in held.items()}

    monkeypatch.setattr(decoder, "forward", forward)
    got = correct.check(served, tiny(), seed=SEED, rows=2, bucket=512)
    assert not got["ok"]
    assert got["positions_over"] == got["positions"]
    assert got["median_rel_err"] > 10 * correct.TOLERANCE / 3
    assert got["cache_rel_err"] < correct.CACHE_TOLERANCE


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_an_int4_cache_under_a_file_that_states_int8_is_refused(seed):
    """The lower-precision control, at a size a CPU holds: the cache one
    step below the precision the file states, weights and reference as
    stated.  The logits hardly notice it (under the check's 2% and 6%, on
    the chip too: PERF.md section 6, PR 27); the cache read back does, in
    keys and in values, in what a prefill wrote and in what the decode
    steps wrote."""
    conf = tiny()
    assert conf["kv_cache_dtype"] == "int8"
    sound = correct.check(build.build_engine(conf, "tiny", seed)[0], conf,
                          seed=seed, rows=2, bucket=512)
    control = correct.check(
        build.build_engine(dict(conf, kv_cache_dtype="int4"), "tiny",
                           seed)[0], conf, seed=seed, rows=2, bucket=512)
    assert sound["ok"] and not control["ok"]
    limit = correct.CACHE_TOLERANCE
    for name, err in control["cache_rel_errs"].items():
        assert err > 1.5 * limit > 4.5 * sound["cache_rel_errs"][name], name
    assert control["compared"]["median_rel_err"]["value"] < \
        control["compared"]["median_rel_err"]["limit"]


def test_a_cache_of_another_shape_than_the_references_is_named(
        engine, monkeypatch):
    from benchmarks.reference import decoder

    plain = decoder.forward
    monkeypatch.setattr(
        decoder, "forward",
        lambda *a: (plain(*a)[0], {k: v[:, :, :-1]
                                   for k, v in plain(*a)[1].items()}))
    with pytest.raises(ValueError, match="k of shape") as refused:
        correct.check(engine, tiny(), seed=SEED, rows=2, bucket=512)
    assert "paged_kv" in str(refused.value)


# ---------------------------------------- a run, with the timed path broken

BROKEN_RUN = """
import sys
sys.path.insert(0, {root!r})
import jax.numpy as jnp
from k8s_llm_rca_tpu.engine import paged

plain = paged.paged_decode_step


def shifted(*args, **kw):
    # every token's logits moved by one place where they are produced
    pool, logits = plain(*args, **kw)
    return pool, jnp.roll(logits, 1, axis=-1)


shifted.__name__ = plain.__name__
paged.paged_decode_step = shifted
from benchmarks import run
sys.exit(run.main({argv!r}))
"""


def _run_lines(code, timeout=900):
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=ROOT, timeout=timeout,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-3000:]
    return [json.loads(x) for x in done.stdout.strip().splitlines()]


def test_a_run_over_a_broken_decode_program_is_not_correct():
    """Past the harness's look for a chip (``--allow-cpu``) the rest of a
    run goes as on the chip; the stepwise decode program, which the window's
    ticks and the check both drive, returns its logits rolled by one."""
    argv = ["--benchmark", os.path.join(HERE, "rehearsal.json"),
            "--workload", "tiny.chat-open", "--seed", str(SEED),
            "--seconds", "1", "--trace", "0", "--allow-cpu"]
    report, last = _run_lines(BROKEN_RUN.format(root=ROOT, argv=argv))[-2:]
    assert last["correct"] is False and last["failed"] == 0
    check = report["run"]["check"]
    assert not check["ok"] and check["positions_over"] > 6
    assert report["run"]["compiles_in_window"] == 0


# --------------------------------------------- a second driver is a new file

SECOND_DRIVER = '''"""A model whose cache the test pretends is something else: what a
``model_config`` PR would bring beside ``paged_kv.py``."""

from benchmarks.checks import paged_kv

RAN = []


def run(engine, prompts, steps, **shape):
    RAN.append(shape)
    seqs, logits = paged_kv.run(engine, prompts, steps, **shape)
    print("second_driver ran", len(prompts), sorted(shape), flush=True)
    return seqs, logits


def cached(engine, prompts, steps, **shape):
    return paged_kv.cached(engine, prompts, steps, **shape)


def decode_once(engine):
    paged_kv.decode_once(engine)
'''


def _digests(top):
    out = {}
    for folder, _, files in os.walk(top):
        for name in files:
            path = os.path.join(folder, name)
            if "__pycache__" not in path and ".jax_cache" not in path:
                with open(path, "rb") as f:
                    out[os.path.relpath(path, top)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def test_a_second_driver_is_files_added_and_no_edit(tmp_path):
    """A temporary benchmark tree: the one that is there, copied, plus an
    architecture that names a second driver, its configuration, a mix whose
    ``check`` group states lengths, and a cell.  The CPU rehearsal runs the
    cell, ``correct`` comes through the second driver, and every file that
    was there is as it was."""
    tree = tmp_path / "benchmarks"
    shutil.copytree(BENCH, tree, ignore=shutil.ignore_patterns(
        "__pycache__", "data", ".jax_cache"))
    before = _digests(tree)

    arch = build.load_json(os.path.join(BENCH, "architectures",
                                        "mixtral.json"))
    arch["check"] = "second_driver"
    (tree / "architectures" / "secondtype.json").write_text(json.dumps(arch))
    (tree / "checks" / "second_driver.py").write_text(SECOND_DRIVER)
    (tree / "configs" / "second.json").write_text(json.dumps(
        tiny(model_type="secondtype")))
    mix = build.load_json(os.path.join(BENCH, "traffic", "chat-open.json"))
    mix.update(ramp_s=1, check={"prompt_tokens": [600, 200]},
               arrivals={"kind": "poisson", "rate_rps": 3.0})
    (tree / "traffic" / "second-mix.json").write_text(json.dumps(mix))
    bench = build.load_json(os.path.join(HERE, "rehearsal.json"))
    bench["configs"].append(
        {"name": "second", "source": "none",
         "file": "benchmarks/configs/second.json", "reduced": [],
         "why": "test"})
    bench["workloads"].append(
        {"name": "second.cell", "config": "second", "traffic": "second-mix",
         "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    done = subprocess.run(
        [sys.executable, str(tree / "run.py"), "--workload", "second.cell",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0",
         "--allow-cpu"],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    assert "second_driver ran 2 []" in lines
    report, last = (json.loads(x) for x in lines[-2:])
    assert last["correct"] is True
    check = report["run"]["check"]
    assert check["driver"] == "second_driver" and check["ok"]
    assert check["prompt_tokens"] == [600, 200]
    after = _digests(tree)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "architectures/secondtype.json", "checks/second_driver.py",
        "configs/second.json", "traffic/second-mix.json"]
