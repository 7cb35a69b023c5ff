"""``nemotron_h`` as files of the benchmark: its architecture through the
door, its configuration against the catalog's row, its check driver at a size
a CPU holds (what the check passes, what it refuses, and what the state one
precision down reads), and its five readers on a recorded trace that has no
such layer (None) and on a trace that has (a number, never over 100)."""

import gzip
import json
import os
import re
import shutil
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.join(BENCH, "tests")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME, CELL = "nemotron3-super-120b-d11", "nemotron3-super-d11.audit-report"
SEED = 5

from benchmarks import control, control_state  # noqa: E402
from benchmarks.lib import build, correct  # noqa: E402
from benchmarks.trace import ssm_costs  # noqa: E402


def configuration(name=NAME, **changes):
    return dict(build.load_json(os.path.join(BENCH, "configs",
                                             name + ".json")), **changes)


# ------------------------------------------------------------------ the door


def test_the_configuration_builds_the_published_widths():
    cfg = build.model_config(configuration(), NAME)
    assert (cfg.layer_pattern, cfg.n_layers) == ("MEMEMEM*EME", 11)
    assert (cfg.n_kv_layers, cfg.n_ssm_layers) == (1, 5)
    assert (cfg.hidden_size, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state_size, cfg.ssm_conv_kernel, cfg.ssm_chunk) == (
        128, 64, 8, 128, 4, 128)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim) == (8192, 10240)
    assert (cfg.n_router, cfg.n_experts, cfg.expert_first,
            cfg.n_experts_per_tok, cfg.routed_scaling) == (512, 128, 0, 22,
                                                           5.0)
    assert (cfg.moe_latent_size, cfg.expert_size,
            cfg.shared_expert_size) == (1024, 2688, 5376)
    assert (cfg.router_kind, cfg.mlp_act, cfg.use_rope) == (
        "sigmoid", "relu2", False)
    assert (cfg.dtype, cfg.ssm_state_dtype, cfg.vocab_size) == (
        "bfloat16", "float32", 32768)


@pytest.mark.parametrize("key, value", [
    ("mlp_hidden_act", "silu"), ("mamba_hidden_act", "gelu"),
    ("n_group", 8), ("topk_group", 4), ("norm_topk_prob", False),
    ("num_nextn_predict_layers", 1), ("sliding_window", 4096),
    ("use_conv_bias", False), ("attention_bias", True),
    ("mamba_proj_bias", True), ("n_shared_experts", 2),
])
def test_a_value_the_program_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=re.escape(f"{key} = {value!r}")):
        build.model_config(configuration(**{key: value}), NAME)


def test_the_pattern_has_to_fit_the_depth():
    with pytest.raises(ValueError, match="11 letters for n_layers=10"):
        build.model_config(configuration(num_hidden_layers=10), NAME)


def test_the_file_holds_the_catalog_row_but_for_what_it_lists_as_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    bench = build.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    conf = configuration()
    assert entry["source"] == row["source_url"] == conf["source"]
    differ = {k for k, v in row["config"].items() if conf.get(k, k) != v}
    assert differ == set(entry["reduced"]) == set(conf["reduced_why"])
    # a whole period, an eighth of the vocabulary, 8 routed experts at least
    assert row["config"]["hybrid_override_pattern"].startswith(
        conf["hybrid_override_pattern"])
    assert conf["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert conf["n_routed_experts"] >= 8
    assert conf["router_n_experts"] == row["config"]["n_routed_experts"]
    assert {"rope", "dt_clamp", "ssm_state_dtype"} <= set(conf["assumed"])


def test_the_cell_is_declared_with_its_readers():
    bench = build.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "audit-report", 1)
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"ssm_decode_roofline", "ssm_prefill_roofline",
                    "ssm_busy_share", "latent_moe_busy_share",
                    "moe_local_pair_share"}
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    # a ratio of page counts holds with pages in one layer; the roofline's
    # byte count (keys and values in EVERY layer) and Mixtral's stacked
    # expert shape do not
    assert "paged_attn_live_page_share" in listed
    assert not listed & {"paged_attn_roofline", "expert_mlp_busy_share"}
    traffic = build.load_json(os.path.join(BENCH, "traffic",
                                           "audit-report.json"))
    assert traffic["arrivals"] == {"kind": "closed", "clients": 64}
    assert traffic["ramp_requests"] == 128
    # two of the check's prompts share a bucket (the batched prefill
    # program), the third has one to itself (the single-row program)
    assert traffic["check"] == {"prompt_tokens": [1536, 1800, 3072]}


# ------------------------------------------------------------ the check driver


@pytest.fixture(scope="module")
def tiny():
    return configuration("tiny-nemotron-h")


@pytest.fixture(scope="module")
def sound(tiny):
    engine = build.build_engine(tiny, "tiny-nemotron-h", SEED)[0]
    return engine, correct.check(engine, tiny, seed=SEED,
                                 prompt_tokens=[100, 300, 290])


def test_the_driver_passes_the_engine_as_the_file_states_it(sound):
    _, check = sound
    assert check["ok"] and check["driver"] == "paged_kv_state"
    assert check["positions_over"] == 0 and check["positions"] == 27
    assert check["positions_allowed_over"] == 27 // 3    # near_ties, stated
    assert set(check["cache_rel_errs"]) == {
        f"{name}.{part}" for name in ("k", "v", "ssm_state", "conv_state",
                                      "ssm_grain", "k_grain", "v_grain")
        for part in ("prefill", "decode")}
    # float32 on both sides: the states and pages to the last places, and
    # what they keep below the next precision down to a rounding that fell
    # the other way in an element or two of a mean's thousands
    for name, err in check["cache_rel_errs"].items():
        assert err < (1e-2 if "_grain" in name else 1e-4), name


def test_a_state_the_decode_steps_do_not_move_is_refused(sound, tiny,
                                                        monkeypatch):
    """The fault this driver is there for: the pages move on and the state
    stays where the prefill left it."""
    from k8s_llm_rca_tpu.models import nemotron_h

    engine = build.build_engine(tiny, "tiny-nemotron-h", SEED)[0]
    real = nemotron_h.mamba_decode

    def stale(cfg, layer, x, ssm_state, conv_state):
        out, _, tail = real(cfg, layer, x, ssm_state, conv_state)
        return out, ssm_state, tail

    monkeypatch.setattr(nemotron_h, "mamba_decode", stale)
    check = correct.check(engine, tiny, seed=SEED,
                          prompt_tokens=[100, 300, 290])
    assert not check["ok"]
    assert check["compared"]["cache_rel_err"]["value"] > correct.CACHE_TOLERANCE
    assert max(check["cache_rel_errs"], key=check["cache_rel_errs"].get) \
        .startswith("ssm_state")


def test_a_dropped_layer_is_refused(tiny, monkeypatch):
    """The expert layers left out of the engine's programs, prefill and
    decode alike: every position's logits move and the check lands far
    outside its tolerance."""
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.models import nemotron_h

    monkeypatch.setattr(nemotron_h, "expert_layer",
                        lambda cfg, layer, x: (x, jnp.int32(0)))
    engine = build.build_engine(tiny, "tiny-nemotron-h", SEED)[0]
    check = correct.check(engine, tiny, seed=SEED,
                          prompt_tokens=[100, 300, 290])
    assert not check["ok"]
    assert check["positions_over"] == check["positions"]


@pytest.mark.parametrize("lowered, key, stated, lower, grains", [
    (control_state.lowered, "ssm_state_dtype", "float32", "bfloat16",
     ("ssm_grain",)),
    (control.lowered, "kv_cache_dtype", None, "int8",
     ("k_grain", "v_grain")),
], ids=["state", "pages"])
def test_a_cache_one_precision_down_is_refused(sound, tiny, lowered, key,
                                               stated, lower, grains):
    """The two controls at a size a CPU holds: the state in bfloat16 under a
    file that states float32 (``control_state.py``), the pages in int8 under
    a file that leaves them in the activations' type (``control.py``).  What
    the cache holds is a part of a percent off and passes every other
    reading; what it keeps below the lower precision's grain is nothing, and
    that reading, and no other, refuses it."""
    _, want = sound
    built = lowered(tiny)
    assert tiny[key] == stated and built[key] == lower
    engine = build.build_engine(built, "tiny-nemotron-h", SEED)[0]
    check = correct.check(engine, tiny, seed=SEED,
                          prompt_tokens=[100, 300, 290])
    assert not check["ok"]
    over = {name for name, c in check["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over == {"cache_rel_err"}
    errs, sound_errs = check["cache_rel_errs"], want["cache_rel_errs"]
    for name, err in errs.items():
        if name.split(".")[0] in grains:
            assert err > 0.99 and sound_errs[name] < 1e-2, name
        else:           # seen (or untouched), and under the limit
            assert err < correct.CACHE_TOLERANCE / 5, name
    with pytest.raises(SystemExit, match="no precision below"):
        lowered(lowered(built) if key == "kv_cache_dtype" else built)


def test_the_grain_is_what_the_lower_precision_would_lose():
    """Float32 values lose 0.14% of an element to bfloat16 and a quarter of
    a step to a token's int8 grid, whatever their scale; values that are
    one precision down already lose nothing."""
    import ml_dtypes

    from benchmarks.reference.nemotron_h import below_bfloat16, below_int8

    rng = np.random.default_rng(SEED)
    state = (rng.standard_normal((2, 8, 64, 128))
             * np.exp(rng.standard_normal((2, 8, 1, 1)))).astype(np.float32)
    fine = below_bfloat16(state, 5)
    assert fine.shape == (2, 5, 1) and np.all(fine[:, 0] == fine[:, 4])
    assert np.all(np.abs(fine - 0.0014) < 1e-4)
    coarse = state.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert not below_bfloat16(coarse, 5).any()
    assert below_bfloat16(np.tile(state, (1, 8, 1, 1)), 5).shape == (2, 5, 8)

    kv = rng.standard_normal((1, 200, 256)).astype(np.float32)
    fine = below_int8(kv)
    assert fine.shape == (1, 200, 1)
    assert np.all(np.abs(fine - 0.25) < 0.01)
    # blocks of 64 tokens counted from the last: 8 + 64 + 64 + 64
    assert len(np.unique(fine)) == 4 and fine[0, 7, 0] != fine[0, 8, 0]
    step = np.abs(kv).max(-1, keepdims=True) / np.float32(127)
    assert below_int8(np.round(kv / step) * step).max() < 1e-5


# ------------------------------------------------------------------ the readers

READERS = ("ssm_decode_roofline", "ssm_prefill_roofline", "ssm_busy_share",
           "latent_moe_busy_share", "moe_local_pair_share")


def _reader(name):
    import importlib

    return importlib.import_module("benchmarks.layer_metrics." + name)


@pytest.fixture(scope="module")
def sample_trace(tmp_path_factory):
    from benchmarks.trace import reduce

    path = str(tmp_path_factory.mktemp("trace") / "sample.xplane.pb")
    with gzip.open(os.path.join(HERE, "data", "sample.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return reduce.reduce_file(path)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_the_model_has_no_such_layer(
        name, sample_trace):
    """The recorded trace is ``tiny``'s (a Llama-family model, the parent's
    counters): nothing to read, None, and nothing raised; so too with no
    trace at all."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig

    engine = SimpleNamespace(model_cfg=TINY_MOE,
                             engine_cfg=EngineConfig(max_batch=32))
    trace = dict(sample_trace, counters={"engine.decode_steps": 656.0})
    for t in (trace, None):
        ctx = SimpleNamespace(engine=engine, trace=t, counters={},
                              device={"kind": "TPU v5 lite"})
        assert _reader(name).read(ctx) is None


def _nemotron_ctx():
    """A trace as the cell's looks: one operation of each kind, by the
    shapes in its text, with the time its roofline would take twice over."""
    from k8s_llm_rca_tpu.config import EngineConfig

    cfg = build.model_config(configuration(), NAME)
    ecfg = EngineConfig(max_batch=64)
    slot_steps, layer_tokens = 64 * 16 * 5.0, 4096 * 5.0
    update_s = ssm_costs.state_update_bytes(cfg, slot_steps) / 819e9
    ops, nbytes = ssm_costs.chunk_scan_work(cfg, layer_tokens)
    scan_s = max(ops / 197e12, nbytes / 819e9)
    text = {
        "multiply_add_fusion": "%multiply_add_fusion = f32[5,64,128,64,128]"
                               "{4,3,2,1,0} fusion(f32[5,64,128,64,128] %p)",
        "fusion.7": "%fusion.7 = f32[1,32,8,16,128,128]{5,4,3,2,1,0} "
                    "fusion(f32[1,32,8,16,128] %cum)",
        "fusion.8": "%fusion.8 = bf16[64,18560]{1,0} fusion(bf16[64,4096] "
                    "%x, bf16[4096,18560] %w_in)",
        "fusion.9": "%fusion.9 = bf16[64,128,2688]{2,1,0} fusion(bf16[64,1024]"
                    " %v, bf16[128,1024,2688] %w_up)",
        "ragged-dot-none.1": "%ragged-dot-none.1 = bf16[90112,2688] "
                             "custom-call(bf16[90112,1024] %rows)",
        "fusion.10": "%fusion.10 = bf16[64,4096]{1,0} fusion(bf16[64,5376] "
                     "%shared, bf16[5376,4096] %w)",
    }
    seconds = {"multiply_add_fusion": 2 * update_s, "fusion.7": 2 * scan_s,
               "fusion.8": 0.01, "fusion.9": 0.02, "ragged-dot-none.1": 0.03,
               "fusion.10": 0.04}
    trace = {"op_text": text, "op_seconds": seconds, "busy_s": 1.0,
             "counters": {"engine.ssm_decode_slot_steps": slot_steps,
                          "engine.ssm_prefill_tokens": layer_tokens}}
    return SimpleNamespace(
        engine=SimpleNamespace(model_cfg=cfg, engine_cfg=ecfg), trace=trace,
        counters={"engine.moe_routed_pairs": 4000.0,
                  "engine.moe_local_pairs": 1000.0},
        device={"kind": "TPU v5 lite"}), update_s, scan_s


def test_the_readers_read_what_the_cost_functions_count():
    ctx, update_s, scan_s = _nemotron_ctx()
    assert _reader("ssm_decode_roofline").read(ctx) == pytest.approx(50.0)
    assert _reader("ssm_prefill_roofline").read(ctx) == pytest.approx(50.0)
    assert _reader("moe_local_pair_share").read(ctx) == pytest.approx(25.0)
    assert _reader("latent_moe_busy_share").read(ctx) == pytest.approx(5.0)
    assert _reader("ssm_busy_share").read(ctx) == pytest.approx(
        100.0 * (2 * update_s + 2 * scan_s + 0.01))
    for name in READERS:
        assert 0 < _reader(name).read(ctx) <= 100.0


def test_the_cost_functions_count_the_recurrence_not_the_program():
    cfg = build.model_config(configuration(), NAME)
    # a slot's step of one layer: the state in and out in float32, and one
    # position's x, B, C and dt in bfloat16
    state = 128 * 64 * 128
    assert ssm_costs.state_update_bytes(cfg, 1.0) == (
        2 * 4 * state + 2 * (10240 + 128))
    ops, nbytes = ssm_costs.chunk_scan_work(cfg, 1.0)
    assert ops == 2 * 128 * 8 * 128 + 2 * 128 * 128 * 64 + 4 * 128 * 64 * 128
    assert nbytes == 2 * (2 * 8192 + 2 * 1024 + 128)
    from k8s_llm_rca_tpu.config import TINY

    assert not ssm_costs.has_ssm(TINY)
    assert ssm_costs.state_update_pattern(TINY, 32) is None
    assert ssm_costs.latent_moe_pattern(TINY) is None
    assert ssm_costs.seconds_of(None, re.compile("x")) is None
