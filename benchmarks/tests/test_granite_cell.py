"""``granitemoehybrid`` as files of the benchmark: its architecture through
the door, its configuration against the catalog's row (nothing cut), the cell
and its readers as ``BENCHMARK.json`` declares them, the two new readers on
the parent's counters (None) and on the change's (a number), the check at a
size a CPU holds (what it passes; a dropped MLP sublayer, a dropped factor
and a state one precision down, each refused), and the CPU rehearsal of the
tiny twin through ``run.py``."""

import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.join(BENCH, "tests")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME, CELL = "granite-4.0-h-micro", "granite4-h-micro.chat-open"
TINY = "tiny-granite-hybrid"
SEED = 5
LENGTHS = [100, 120, 300]

from benchmarks import control_state  # noqa: E402
from benchmarks.lib import build, correct  # noqa: E402
from benchmarks.trace import hybrid_block_costs, ssm_costs  # noqa: E402


def configuration(name=NAME, **changes):
    return dict(build.load_json(os.path.join(BENCH, "configs",
                                             name + ".json")), **changes)


# ------------------------------------------------------------------ the door


def test_the_configuration_builds_the_published_widths():
    cfg = build.model_config(configuration(), NAME)
    assert len(cfg.mixer_types) == cfg.n_layers == 40
    assert cfg.layer_table == ("MMMMM*MMMM" * 4) and cfg.layer_pattern == ""
    assert (cfg.n_ssm_layers, cfg.n_kv_layers) == (36, 4)
    assert (cfg.hidden_size, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 32, 8, 64)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state_size, cfg.ssm_conv_kernel, cfg.ssm_chunk) == (
        64, 64, 1, 128, 4, 256)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim, cfg.block_mlp_size) == (
        4096, 4352, 8192)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attn_scale, cfg.q_fold) == (
        12.0, 0.22, 8.0, 0.015625, 0.125)
    assert (cfg.use_rope, cfg.tie_embeddings, cfg.n_experts) == (
        False, True, 0)
    assert (cfg.dtype, cfg.ssm_state_dtype, cfg.vocab_size) == (
        "bfloat16", "float32", 100352)


@pytest.mark.parametrize("key, value", [
    ("position_embedding_type", "rope"), ("num_local_experts", 72),
    ("num_experts_per_tok", 10), ("hidden_act", "gelu"),
    ("normalization_function", "layernorm"), ("mamba_expand", 4),
    ("mamba_proj_bias", True), ("mamba_conv_bias", False),
    ("attention_bias", True), ("rope_scaling", {"type": "linear"}),
])
def test_a_value_the_program_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=re.escape(f"{key} = {value!r}")):
        build.model_config(configuration(**{key: value}), NAME)


def test_an_unknown_key_and_a_table_that_does_not_fit_are_refused():
    with pytest.raises(ValueError, match="nothing reads the key 'muup'"):
        build.model_config(configuration(muup=True), NAME)
    with pytest.raises(ValueError, match="40 entries for n_layers=39"):
        build.model_config(configuration(num_hidden_layers=39), NAME)
    with pytest.raises(ValueError, match="unknown layer kind 'mlp'"):
        build.model_config(configuration(
            layer_types=["mlp"] + configuration()["layer_types"][1:]), NAME)
    with pytest.raises(ValueError, match="attn_scale=0.02 with head_dim=64"):
        build.model_config(configuration(attention_multiplier=0.02), NAME)


def test_the_file_holds_the_catalog_row_and_cuts_nothing():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == NAME)
    conf = configuration()
    bench = build.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["source"] == conf["source"] == row["source_url"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert {k for k, v in row["config"].items() if conf.get(k) != v} == set()
    assert entry["reduced"] == [] and conf["reduced_why"].startswith(
        "nothing is cut")
    assert {"head_dim", "torch_dtype", "ssm_state_dtype", "dt_clamp",
            "seeded_weights", "intermediate_size"} <= set(conf["assumed"])
    assert conf["weight_quant_bits"] is None and conf["kv_cache_dtype"] is None
    arch = build.architecture(conf)
    assert "near_ties" not in arch and arch["check"] == "paged_kv_state"


def test_the_cell_is_declared_with_its_readers():
    bench = build.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "chat-open", 1)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["block_mlp_busy_share"]["workloads"] == [CELL]
    assert by_name["ssm_live_slot_share"]["workloads"] == [
        "nemotron3-super-d11.audit-report", CELL]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"ssm_decode_roofline", "ssm_busy_share", "engine_tpot_ms",
            "tick_host_ms", "scan_steps_per_dispatch",
            "scan_cut_by_pages_share", "paged_attn_live_page_share",
            "tick_prefill_phase_ms", "tick_decode_phase_ms",
            "tick_unnamed_ms", "tick_admission_stage_ms",
            "tick_admission_activate_ms", "tick_scan_setup_ms",
            "prefill_stall_ms_per_token"} < listed
    # every metric that lists the cell moves what the cell reports; the
    # byte count of paged_attn_roofline multiplies by every layer
    assert {by_name[n]["moves"] for n in listed} == {"gap_ms_p50"}
    assert "paged_attn_roofline" not in listed
    tokens_per_s = next(m for m in bench["end_to_end"]
                        if m["name"] == "out_tokens_per_s")
    assert CELL not in tokens_per_s["workloads"]
    # the mix is the one mistral7b.chat-open runs, unedited; the cell
    # states its own rate, knee and check
    from benchmarks import run as harness

    _, _, _, traffic = harness.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                                         CELL)
    own = build.load_json(os.path.join(BENCH, "cells", CELL + ".json"))
    assert traffic["generator"] == "requests" and traffic["ramp_s"] == 15
    assert traffic["check"] == {"rows": None, "bucket": None,
                                "prompt_tokens": [384, 480, 1800]}
    assert traffic["arrivals"] == {"kind": "poisson",
                                   "rate_rps": own["arrivals"]["rate_rps"]}
    assert own["knee"]["rule"] == "rate_rps = 0.8 x knee"


# ------------------------------------------------------------------ the readers

# what the parent of PR 44 counts for a model with a state while traced:
# every slot's updates, and no live ones
PARENT = {"engine.ssm_decode_slot_steps": 64 * 96 * 5.0,
          "engine.ssm_prefill_tokens": 5 * 8192.0,
          "engine.decode_steps": 96.0, "engine.tick.count": 6.0}
NEW = {"engine.ssm_decode_live_slot_steps": 48 * 96 * 5.0}


def _reader(name):
    import importlib

    return importlib.import_module("benchmarks.layer_metrics." + name)


def test_the_live_slot_share_stands_on_the_parent():
    read = _reader("ssm_live_slot_share").read
    assert read(SimpleNamespace(counters={})) is None
    assert read(SimpleNamespace(counters=PARENT)) is None
    assert read(SimpleNamespace(counters=dict(PARENT, **NEW))) == 75.0
    # a counter that reads 0 is present in a window's counters
    assert read(SimpleNamespace(counters=dict(
        PARENT, **{"engine.ssm_decode_live_slot_steps": 0.0}))) == 0.0


def test_the_block_mlp_share_stands_on_the_parent():
    """The parent's ``ModelConfig`` has no ``block_mlp_size``; a model
    without the sublayer has it at 0; neither is read, and nothing is
    raised.  With it, the operations whose text carries the MLP's shapes
    are its share, and the mixer's own are not."""
    from k8s_llm_rca_tpu.config import TINY_NEMOTRON_H

    read = _reader("block_mlp_busy_share").read
    cfg = build.model_config(configuration(), NAME)
    text = {"fusion.1": "bf16[64,8192] fusion(bf16[64,2048], "
                        "bf16[2048,8192])",
            "fusion.2": "bf16[64,2048] fusion(bf16[64,8192], "
                        "bf16[8192,2048])",
            "fusion.3": "bf16[64,8512] fusion(bf16[64,2048], "
                        "bf16[2048,8512])",
            "fusion.4": "f32[64,64,64,128] fusion(f32[64,64,64,128])"}
    trace = {"op_text": text, "busy_s": 4.0,
             "op_seconds": {"fusion.1": 0.5, "fusion.2": 0.3,
                            "fusion.3": 0.7, "fusion.4": 1.5}}
    parent_cfg = SimpleNamespace(hidden_size=2048, n_ssm_layers=36)
    for model_cfg in (parent_cfg, TINY_NEMOTRON_H):
        engine = SimpleNamespace(model_cfg=model_cfg)
        assert read(SimpleNamespace(engine=engine, trace=trace)) is None
    engine = SimpleNamespace(model_cfg=cfg)
    assert read(SimpleNamespace(engine=engine, trace=None)) is None
    assert read(SimpleNamespace(engine=engine, trace=trace)) == 20.0
    assert hybrid_block_costs.block_mlp_pattern(parent_cfg) is None
    # and the other half of the block, by the readers the tree had
    assert ssm_costs.seconds_of(
        trace, ssm_costs.state_update_pattern(cfg, 64),
        ssm_costs.mamba_rest_pattern(cfg)) == 2.2


# ------------------------------------------------------------------- the check


@pytest.fixture(scope="module")
def tiny():
    return configuration(TINY)


@pytest.fixture(scope="module")
def sound(tiny):
    engine = build.build_engine(tiny, TINY, SEED)[0]
    return correct.check(engine, tiny, seed=SEED, prompt_tokens=LENGTHS)


def test_the_check_passes_the_engine_as_the_file_states_it(sound):
    assert sound["ok"] and sound["driver"] == "paged_kv_state"
    assert sound["positions_over"] == 0 and sound["positions"] == 27
    assert sound["positions_allowed_over"] == 0      # no near_ties: strict
    assert set(sound["cache_rel_errs"]) == {
        f"{name}.{part}" for name in ("k", "v", "ssm_state", "conv_state",
                                      "ssm_grain", "k_grain", "v_grain")
        for part in ("prefill", "decode")}
    for name, err in sound["cache_rel_errs"].items():
        assert err < (1e-2 if "_grain" in name else 1e-4), name


def test_a_dropped_mlp_sublayer_is_refused(tiny, monkeypatch):
    """The second sublayer left out of the engine's programs, prefill and
    decode alike."""
    from k8s_llm_rca_tpu.models import nemotron_h

    monkeypatch.setattr(nemotron_h, "block_mlp", lambda cfg, layer, x: x)
    engine = build.build_engine(tiny, TINY, SEED)[0]
    check = correct.check(engine, tiny, seed=SEED, prompt_tokens=LENGTHS)
    assert not check["ok"]
    assert check["positions_over"] == check["positions"]


@pytest.mark.parametrize("key", ["embedding_multiplier",
                                 "residual_multiplier", "logits_scaling"])
def test_a_dropped_factor_is_refused(tiny, key):
    """The engine built with one factor at 1 under a file that states it."""
    engine = build.build_engine(dict(tiny, **{key: 1}), TINY, SEED)[0]
    check = correct.check(engine, tiny, seed=SEED, prompt_tokens=LENGTHS)
    assert not check["ok"]
    assert check["positions_over"] == check["positions"]


def test_a_state_one_precision_down_is_refused(sound, tiny):
    """``control_state.py`` at a size a CPU holds: the state in bfloat16
    under a file that states float32 passes every other reading; what it
    keeps below bfloat16's grain is nothing, and that reading refuses it."""
    built = control_state.lowered(tiny)
    assert (tiny["ssm_state_dtype"], built["ssm_state_dtype"]) == (
        "float32", "bfloat16")
    engine = build.build_engine(built, TINY, SEED)[0]
    check = correct.check(engine, tiny, seed=SEED, prompt_tokens=LENGTHS)
    assert not check["ok"]
    over = {name for name, c in check["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over == {"cache_rel_err"}
    for name, err in check["cache_rel_errs"].items():
        if name.startswith("ssm_grain"):
            assert err > 0.99 and sound["cache_rel_errs"][name] < 1e-2, name
        else:
            assert err < correct.CACHE_TOLERANCE / 5, name


# ---------------------------------------------------------------- the rehearsal


@pytest.mark.slow
def test_the_cpu_rehearsal_of_the_tiny_twin_runs_the_readers():
    """``run.py`` on the tiny twin, as the verify skill rehearses a cell:
    correct, nothing failed, nothing compiled in the window, and the
    counter-fed readers on the line (a CPU has no device trace)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(HERE, "rehearsal-granite.json"), "--workload",
         "tiny-granite.chat-open", "--seed", "2147483999", "--seconds",
         "10", "--trace", "1", "--allow-cpu"],
        capture_output=True, text=True, timeout=1500,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    got = line["metrics"]
    assert 0.0 < got["ssm_live_slot_share"]["value"] < 100.0   # an open loop
    assert {"paged_attn_live_page_share", "tick_decode_phase_ms",
            "scan_steps_per_dispatch"} <= set(got)
    assert "block_mlp_busy_share" not in got       # no device, no trace
