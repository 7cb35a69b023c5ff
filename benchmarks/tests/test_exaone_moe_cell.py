"""``exaone_moe`` as files of the benchmark: its architecture through the door,
its configuration against the catalog's row, its check driver at a size a CPU
holds (what the check passes, the two window controls and the cache one
precision down, each refused), its six readers on a recorded trace that has no
such layer (None) and on a trace that has (a number, never over 100), the cost
functions by hand-worked cases, and the CPU rehearsal of the tiny twin through
``run.py``."""

import gzip
import json
import os
import re
import shutil
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
NAME, CELL = "k-exaone-236b-d5", "k-exaone-d5.longdump-reason"
TINY = "tiny-exaone-moe"
SEED = 5
LENGTHS = [100, 300, 290]

from benchmarks import control, control_window  # noqa: E402
from benchmarks.lib import build, correct  # noqa: E402
from benchmarks.trace import mixed_attn_costs  # noqa: E402


def configuration(name=NAME, **changes):
    return dict(build.load_json(os.path.join(BENCH, "configs",
                                             name + ".json")), **changes)


# ------------------------------------------------------------------ the door


def test_the_configuration_builds_the_published_widths():
    cfg = build.model_config(configuration(), NAME)
    assert cfg.n_layers == 5 and cfg.layer_pattern == ""
    assert cfg.attn_windows == (128, 128, 128, 0, 128)
    assert (cfg.n_window_layers, cfg.n_kv_layers, cfg.n_dense_layers) == (
        4, 1, 1)
    assert cfg.ring_pages(16) == 9
    assert (cfg.hidden_size, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        6144, 64, 8, 128)
    assert (cfg.intermediate_size, cfg.expert_size,
            cfg.shared_expert_size) == (18432, 2048, 2048)
    assert (cfg.n_router, cfg.n_experts, cfg.expert_first,
            cfg.n_experts_per_tok, cfg.routed_scaling) == (128, 16, 0, 8, 2.5)
    assert (cfg.router_kind, cfg.mlp_act, cfg.qk_norm, cfg.use_rope,
            cfg.rope_full_layers, cfg.rope_theta) == (
        "sigmoid", "swiglu", True, True, False, 1e6)
    assert (cfg.dtype, cfg.vocab_size, cfg.init_layers,
            cfg.tie_embeddings) == ("bfloat16", 19200, 48, False)


@pytest.mark.parametrize("key, value", [
    ("hidden_act", "gelu"), ("scoring_func", "softmax"), ("n_group", 8),
    ("topk_group", 4), ("norm_topk_prob", False), ("num_shared_experts", 2),
    ("sliding_window_pattern", "LG"), ("num_nextn_predict_layers", 1),
    ("rope_parameters", {"rope_theta": 10000, "rope_type": "default"}),
])
def test_a_value_the_program_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=re.escape(f"{key} = {value!r}")):
        build.model_config(configuration(**{key: value}), NAME)


def test_the_table_has_to_fit_the_depth():
    with pytest.raises(ValueError, match="5 entries for n_layers=4"):
        build.model_config(configuration(num_hidden_layers=4), NAME)


@pytest.mark.parametrize("name", [NAME, TINY])
def test_the_unread_lists_restate_what_is_read(name):
    """``sliding_windows`` and ``mlp_layer_types`` are in the architecture's
    ``ignored``: they say layer by layer what ``layer_types`` x
    ``sliding_window`` and ``first_k_dense_replace`` say, and the files are
    held to that here."""
    conf = configuration(name)
    cfg = build.model_config(conf, name)
    assert list(cfg.attn_windows) == conf["sliding_windows"]
    assert conf["mlp_layer_types"] == [
        "dense" if i < cfg.n_dense_layers else "sparse"
        for i in range(cfg.n_layers)]
    assert conf["shared_expert_intermediate_size"] == (
        conf["num_shared_experts"] * conf["moe_intermediate_size"])


def test_the_file_holds_the_catalog_row_but_for_what_it_lists_as_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    bench = build.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    conf = configuration()
    assert entry["source"] == row["source_url"] == conf["source"]
    differ = {k for k, v in row["config"].items() if conf.get(k, k) != v}
    assert differ == set(entry["reduced"]) == set(conf["reduced_why"])
    assert len(differ) == 7
    # the leading dense layer and one whole period, an eighth of the
    # vocabulary and of the experts
    for key in ("layer_types", "sliding_windows", "mlp_layer_types"):
        assert row["config"][key][:5] == conf[key]
    assert conf["layer_types"].count("sliding_attention") == 4
    assert conf["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert conf["num_experts"] * 8 == row["config"]["num_experts"] \
        == conf["router_n_experts"]
    assert conf["published_num_hidden_layers"] \
        == row["config"]["num_hidden_layers"]
    assert {"norm_placement", "rope", "qk_norm"} <= set(conf["assumed"])


def test_the_cell_is_declared_with_its_readers():
    bench = build.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longdump-reason", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1][
        "name"] == NAME
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == list(READERS) == [m["name"] for m in
                                     bench["per_layer"][-6:]]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"moe_local_pair_share", "paged_attn_live_page_share",
            "prefill_pad_share", "tick_host_ms"} <= listed
    # their byte count multiplies by every layer; their pattern takes the
    # dense layer's width
    assert not listed & {"paged_attn_roofline", "expert_mlp_busy_share"}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
    traffic = build.load_json(os.path.join(BENCH, "traffic",
                                           "longdump-reason.json"))
    assert traffic["arrivals"] == {"kind": "closed", "clients": 64}
    assert traffic["prompt_tokens"] == {"median": 3584, "sigma": 0.35,
                                        "min": 1536, "max": 6144}
    assert traffic["output_tokens"] == {"median": 1024, "sigma": 0.25,
                                        "min": 768, "max": 1536}
    assert (traffic["ramp_requests"], traffic["max_requests"]) == (64, 320)
    assert traffic["generator"] == "requests"
    assert traffic["warm"] == {"derive": True}
    # ISSUE 36's three lengths and 2,100: 2,048 fills its bucket to the
    # edge, 2,100 and 2,300 share the next (the batched program at a timed
    # size), 6,000 has the largest timed one to itself
    assert traffic["check"] == {"prompt_tokens": [2048, 2100, 2300, 6000]}
    conf = configuration()
    assert conf["engine"]["max_seq_len"] >= 6144 + 1536
    assert conf["engine"]["num_pages"] * conf["engine"]["page_size"] \
        >= 64 * (6144 + 1536)                    # no preemption in a window


# ---------------------------------------------------------------- the stream


def test_every_seed_offers_the_same_work_over_the_whole_stream():
    """``requests`` sends each distribution's quantiles in one seeded order:
    the 320 prompts and answers are the same ones whatever the seed, and the
    seed decides which of them a window's stretch of the stream holds
    (PERF.md section 6, PR 36: the part a 45 s window admits, some 112
    requests behind the ramp's 64, differs in prompt tokens by several
    percent between seeds, which is the spread the cell shows on the chip
    and no fault of the program's)."""
    import numpy as np

    from benchmarks.generators import requests

    traffic = build.load_json(os.path.join(BENCH, "traffic",
                                           "longdump-reason.json"))
    n, ramp = traffic["max_requests"], traffic["ramp_requests"]

    def stream(seed):
        return requests.make_requests(np.random.default_rng(seed), traffic,
                                      n)[1:3]

    streams = [stream(seed) for seed in range(1, 13)]
    for a, b in zip(streams[0], streams[1]):
        assert sorted(a) == sorted(b) and list(a) != list(b)
    prompts = streams[0][0]
    assert (min(prompts), max(prompts)) == (1536, 6144)
    assert max(streams[0][1]) == 1536 and min(streams[0][1]) == 768
    in_window = [int(np.sum(p[ramp:ramp + 112])) for p, _ in streams]
    assert 0.02 < (max(in_window) - min(in_window)) / np.mean(in_window) < 0.2


# ------------------------------------------------------------ the check driver


@pytest.fixture(scope="module")
def tiny():
    return configuration(TINY)


@pytest.fixture(scope="module")
def sound(tiny):
    engine = build.build_engine(tiny, TINY, SEED)[0]
    return engine, correct.check(engine, tiny, seed=SEED,
                                 prompt_tokens=LENGTHS)


def test_the_driver_passes_the_engine_as_the_file_states_it(sound):
    engine, check = sound
    assert check["ok"] and check["driver"] == "paged_kv_window"
    assert check["positions_over"] == 0 and check["positions"] == 27
    assert check["positions_allowed_over"] == 27 // 3    # near_ties, stated
    assert set(check["cache_rel_errs"]) == {
        f"{name}.{part}" for name in ("k", "v", "k_grain", "v_grain")
        for part in ("prefill", "decode")}
    for name, err in check["cache_rel_errs"].items():
        assert err < (1e-2 if "_grain" in name else 1e-4), name
    assert engine.pool.ring.k.shape == (4, 8 * 3, 16, 64)   # window 32


@pytest.mark.parametrize("which", ["full", "wider"])
def test_another_window_than_the_file_states_is_refused(tiny, which):
    """The two controls at a size a CPU holds: every sliding layer computed
    as a full one, and the window a page wider.  Both move every logit and
    hold other tokens than the reference's last window."""
    built = control_window.CONTROLS[which](tiny)
    engine = build.build_engine(built, TINY, SEED)[0]
    assert engine.model_cfg.attn_windows == {
        "full": (0,) * 5, "wider": (48, 48, 48, 0, 48)}[which]
    check = correct.check(engine, tiny, seed=SEED, prompt_tokens=LENGTHS)
    assert not check["ok"]
    assert check["positions_over"] == check["positions"]
    assert check["cache_rel_errs"]["k.prefill"] > 10 * correct.CACHE_TOLERANCE


def test_a_cache_one_precision_down_is_refused_by_the_grain_alone(sound,
                                                                  tiny):
    """``control.py``'s step on this model: pages AND rings in int8 under a
    file that leaves them in the activations' type."""
    built = control.lowered(tiny)
    engine = build.build_engine(built, TINY, SEED)[0]
    assert engine.pool.ring.k.dtype == engine.pool.k.dtype == "int8"
    check = correct.check(engine, tiny, seed=SEED, prompt_tokens=LENGTHS)
    assert not check["ok"]
    over = {name for name, c in check["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over == {"cache_rel_err"}
    for name, err in check["cache_rel_errs"].items():
        if "_grain" in name:
            assert err > 0.99, name
        else:
            assert err < correct.CACHE_TOLERANCE / 2, name


def test_a_ring_the_decode_steps_do_not_write_is_refused(tiny, monkeypatch):
    """The fault this driver is there for: the pages move on and the ring
    stays where the prefill left it."""
    from k8s_llm_rca_tpu.engine import paged

    real = paged._write_pool_rows

    def stale(cfg, pool, li, page_ids, offsets, k_rows, v_rows):
        if pool.ring is None and pool.k.shape[0] == cfg.n_window_layers:
            return pool                                  # the ring's write
        return real(cfg, pool, li, page_ids, offsets, k_rows, v_rows)

    monkeypatch.setattr(paged, "_write_pool_rows", stale)
    engine = build.build_engine(tiny, TINY, SEED)[0]
    check = correct.check(engine, tiny, seed=SEED, prompt_tokens=LENGTHS)
    assert not check["ok"]
    assert check["cache_rel_errs"]["k.decode"] > correct.CACHE_TOLERANCE


# ------------------------------------------------------------------ the readers

READERS = ("window_attn_decode_roofline", "full_attn_decode_roofline",
           "window_attn_prefill_roofline", "attn_busy_share",
           "routed_expert_busy_share", "window_cache_share")


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
    """The recorded trace is ``tiny``'s (a Llama-family model without a
    window layer, the parent's counters): nothing to read, None, and nothing
    raised; so too with no trace at all."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.utils.logging import METRICS

    engine = SimpleNamespace(model_cfg=TINY_MOE,
                             engine_cfg=EngineConfig(max_batch=32))
    trace = dict(sample_trace, counters={"engine.decode_steps": 656.0})
    with METRICS.scoped():
        for t in (trace, None):
            ctx = SimpleNamespace(engine=engine, trace=t, counters={},
                                  device={"kind": "TPU v5 lite"})
            assert _reader(name).read(ctx) is None


def _exaone_ctx():
    """A trace as the cell's looks: one operation of each kind, by name or
    by the shapes in its text, with the time its roofline would take twice
    over."""
    cfg = build.model_config(configuration(), NAME)
    ecfg = build.engine_config(configuration())
    window_tokens, full_tokens = 64 * 16 * 128 * 4.0, 64 * 16 * 5000 * 1.0
    positions = 4096 * 4.0
    window_s = mixed_attn_costs.decode_bytes(cfg, ecfg, window_tokens) / 819e9
    full_s = mixed_attn_costs.decode_bytes(cfg, ecfg, full_tokens) / 819e9
    band_s = mixed_attn_costs.band_ops(cfg, positions) / 197e12
    text = {
        "window_paged_attention.3": "%window_paged_attention.3 = bf16[64,64,"
                                    "1024] custom-call(s32[1] %l)",
        "paged_attention.1": "%paged_attention.1 = bf16[64,64,1024] "
                             "custom-call(s32[1] %l)",
        "flash_attention_window.2": "%flash_attention_window.2 = bf16[1,64,"
                                    "4096,128] custom-call(s32[1] %n)",
        "flash_attention.4": "%flash_attention.4 = bf16[1,64,4096,128] "
                             "custom-call(s32[1] %n)",
        "fusion.9": "%fusion.9 = bf16[1,64,16,2048]{3,2,1,0} fusion(bf16[64,"
                    "6144] %x, bf16[16,6144,2048] %w_gate)",
        "ragged-dot-none.1": "%ragged-dot-none.1 = bf16[32768,2048] "
                             "custom-call(bf16[32768,6144] %rows)",
        "fusion.10": "%fusion.10 = bf16[64,6144]{1,0} fusion(bf16[64,2048] "
                     "%shared, bf16[2048,6144] %w_shared_down)",
        "fusion.11": "%fusion.11 = bf16[64,18432]{1,0} fusion(bf16[64,6144] "
                     "%x, bf16[6144,18432] %w_gate)",
    }
    seconds = {"window_paged_attention.3": 2 * window_s,
               "paged_attention.1": 2 * full_s,
               "flash_attention_window.2": 2 * band_s,
               "flash_attention.4": 0.05, "fusion.9": 0.02,
               "ragged-dot-none.1": 0.03, "fusion.10": 0.04,
               "fusion.11": 0.06}
    trace = {"op_text": text, "op_seconds": seconds, "busy_s": 1.0,
             "counters": {"engine.attn_window_tokens": window_tokens,
                          "engine.attn_full_tokens": full_tokens,
                          "engine.attn_window_prefill_tokens": positions}}
    return SimpleNamespace(
        engine=SimpleNamespace(model_cfg=cfg, engine_cfg=ecfg), trace=trace,
        counters={}, device={"kind": "TPU v5 lite"}), window_s, full_s, band_s


def test_the_readers_read_what_the_cost_functions_count():
    from k8s_llm_rca_tpu.utils.logging import METRICS

    ctx, window_s, full_s, band_s = _exaone_ctx()
    assert _reader("window_attn_decode_roofline").read(ctx) \
        == pytest.approx(50.0)
    assert _reader("full_attn_decode_roofline").read(ctx) \
        == pytest.approx(50.0)
    assert _reader("window_attn_prefill_roofline").read(ctx) \
        == pytest.approx(50.0)
    assert _reader("attn_busy_share").read(ctx) == pytest.approx(
        100.0 * (2 * window_s + 2 * full_s + 2 * band_s + 0.05))
    # the stacked weights and the grouped kernel; not the shared expert
    # (fusion.10) nor the dense layer (fusion.11)
    assert _reader("routed_expert_busy_share").read(ctx) \
        == pytest.approx(5.0)
    with METRICS.scoped():
        # 64 slots of 5,000 tokens: one full layer of 4 KB a token, and a
        # ring of 9 pages x 16 tokens in each of 4 layers
        METRICS.gauge("engine.cache_bytes_full", 64 * 5008 * 4096.0)
        METRICS.gauge("engine.cache_bytes_window", 64 * 144 * 4096.0 * 4)
        assert _reader("window_cache_share").read(ctx) == pytest.approx(
            100.0 * (5008 + 4 * 144) / (5 * 5008))          # 22.3%
    for name in READERS[:5]:
        assert 0 < _reader(name).read(ctx) <= 100.0


def test_the_cost_functions_count_the_mask_not_the_program():
    cfg = build.model_config(configuration(), NAME)
    ecfg = build.engine_config(configuration())
    # one token in one layer: 8 heads x 128 of keys and of values in bf16
    assert mixed_attn_costs.kv_token_bytes(cfg, ecfg) == 2 * 1024 * 2 == 4096
    # a step of 64 slots past the window: 128 tokens each in 4 layers
    assert mixed_attn_costs.decode_bytes(cfg, ecfg, 64 * 128 * 4) \
        == 64 * 128 * 4 * 4096 == 134_217_728
    # one position of one window layer: 64 heads x 128 keys x 128 wide, a
    # multiply-add for the score and one for the value
    assert mixed_attn_costs.band_ops(cfg, 1.0) == 4 * 128 * 64 * 128
    # a 6144-token prompt's four banded calls: 0.10 TFLOP, 0.5 ms at peak
    assert mixed_attn_costs.band_ops(cfg, 6144 * 4.0) == pytest.approx(
        1.03e11, rel=0.01)
    assert mixed_attn_costs.all_full_bytes(cfg, 100.0) == 500.0
    int8 = SimpleNamespace(kv_cache_dtype="int8")
    assert mixed_attn_costs.kv_token_bytes(cfg, int8) == 2 * (1024 + 4)
    assert mixed_attn_costs.WINDOW_DECODE.search("window_paged_attention.7")
    assert not mixed_attn_costs.FULL_DECODE.search("window_paged_attention")
    assert mixed_attn_costs.FULL_DECODE.search("paged_attention.2")
    assert mixed_attn_costs.WINDOW_PREFILL.search("flash_attention_window")
    assert not mixed_attn_costs.FULL_PREFILL.search("flash_attention_window.1")
    assert mixed_attn_costs.FULL_PREFILL.search("flash_attention.1")
    from k8s_llm_rca_tpu.config import TINY as llama_tiny

    assert not mixed_attn_costs.has_window(llama_tiny)
    assert mixed_attn_costs.routed_expert_pattern(llama_tiny) is None
    # the experts' pattern hangs on the experts, not on the window: the
    # same block without window layers has it, a Mixtral-like one (experts
    # of the MLP's own width) and a latent one have not
    from k8s_llm_rca_tpu.config import TINY_MOE, TINY_NEMOTRON_H

    no_window = cfg.replace(attn_layer_types=(), attn_window=0)
    assert not mixed_attn_costs.has_window(no_window)
    assert mixed_attn_costs.routed_expert_pattern(no_window).pattern \
        == mixed_attn_costs.routed_expert_pattern(cfg).pattern
    assert mixed_attn_costs.routed_expert_pattern(TINY_MOE) is None
    assert mixed_attn_costs.routed_expert_pattern(TINY_NEMOTRON_H) is None
    assert mixed_attn_costs.seconds_of(None, re.compile("x")) is None


# ---------------------------------------------------------------- the rehearsal


@pytest.mark.slow
def test_the_cpu_rehearsal_of_the_tiny_twin_runs_the_readers():
    """``run.py`` on the tiny twin, as the verify skill rehearses a cell:
    correct, nothing failed, nothing compiled in the window, and the
    counter-fed readers on the line (a CPU has no device trace)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(HERE, "rehearsal-exaone.json"), "--workload",
         "tiny-exaone.longdump-reason", "--seed", "2147483999", "--seconds",
         "10", "--trace", "1", "--allow-cpu"],
        capture_output=True, text=True, timeout=1500,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    got = line["metrics"]
    assert 15.0 < got["window_cache_share"]["value"] < 100.0
    assert 20.0 < got["moe_local_pair_share"]["value"] < 30.0   # 8 of 32
    assert "paged_attn_live_page_share" in got
