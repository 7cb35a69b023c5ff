"""``deepseek_v3`` (kanana-2-30b-a3b) as files of the benchmark: its
architecture through the door, its configuration against the catalog's row,
its check driver at a size a CPU holds (what the check passes, and the latent
one precision down refused), its four readers on a recorded trace that has no
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
NAME, CELL = "kanana-2-30b-a3b-d12", "kanana2-d12.deepdump-reason"
TINY = "tiny-kanana-moe"
SEED = 5
LENGTHS = [100, 300, 290]
READERS = ("mla_decode_roofline", "mla_prefill_roofline", "mla_busy_share",
           "latent_cache_bytes_per_token")

from benchmarks import control_latent  # noqa: E402
from benchmarks.lib import build, correct  # noqa: E402
from benchmarks.trace import mla_costs  # noqa: E402


def configuration(name=NAME, **changes):
    return dict(build.load_json(os.path.join(BENCH, "configs",
                                             name + ".json")), **changes)


# ------------------------------------------------------------------ the door


def test_the_configuration_builds_the_published_widths():
    cfg = build.model_config(configuration(), NAME)
    assert cfg.n_layers == 12 and cfg.layer_table == ""
    assert (cfg.hidden_size, cfg.n_heads, cfg.head_dim) == (2048, 32, 64)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.qk_head_dim) == (512, 128, 64, 128, 192)
    assert (cfg.latent_row, cfg.kv_dim, cfg.q_dim) == (576, 576, 4096)
    assert cfg.rope_interleave and cfg.rope_theta == 1e6
    assert (cfg.intermediate_size, cfg.expert_size,
            cfg.shared_expert_size) == (6144, 768, 1536)
    assert (cfg.n_router, cfg.n_experts, cfg.expert_first,
            cfg.n_experts_per_tok, cfg.routed_scaling) == (
        128, 16, 0, 6, 2.448)
    assert (cfg.n_dense_layers, cfg.n_window_layers, cfg.n_kv_layers) == (
        1, 0, 12)
    assert (cfg.router_kind, cfg.mlp_act, cfg.rms_norm_eps) == (
        "sigmoid", "swiglu", 1e-6)
    assert (cfg.dtype, cfg.vocab_size, cfg.init_layers,
            cfg.tie_embeddings) == ("bfloat16", 16032, 48, False)


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("n_group", 8), ("topk_group", 4), ("topk_method", "greedy"),
    ("scoring_func", "softmax"), ("norm_topk_prob", False),
    ("moe_layer_freq", 2), ("attention_bias", True),
    ("rope_interleave", False), ("n_shared_experts", 1),
    ("hidden_act", "gelu"),
])
def test_a_value_the_program_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=re.escape(f"{key} = {value!r}")):
        build.model_config(configuration(**{key: value}), NAME)


def test_a_key_nothing_reads_is_refused_by_name():
    with pytest.raises(ValueError, match="nothing reads the key 'index_topk'"):
        build.model_config(configuration(index_topk=2048), NAME)


@pytest.mark.parametrize("name", [NAME, TINY])
def test_the_unread_keys_restate_what_is_read(name):
    """``qk_head_dim`` is under ``ignored``; ``num_key_value_heads`` and
    ``head_dim`` are read and have to agree with what they restate."""
    conf = configuration(name)
    assert conf["qk_head_dim"] == conf["qk_nope_head_dim"] \
        + conf["qk_rope_head_dim"]
    assert conf["num_key_value_heads"] == conf["num_attention_heads"]
    assert conf["head_dim"] == conf["qk_rope_head_dim"]
    assert conf["shared_expert_intermediate_size"] \
        == conf["n_shared_experts"] * conf["moe_intermediate_size"]
    with pytest.raises(ValueError, match="the rotary table's width"):
        build.model_config(dict(conf, head_dim=128), name)


def test_the_file_holds_the_catalog_row_but_for_what_it_lists_as_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    bench = build.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    conf = configuration()
    assert entry["source"] == row["source_url"] == conf["source"]
    differ = {k for k, v in row["config"].items() if conf.get(k, k) != v}
    assert differ == set(entry["reduced"]) == set(conf["reduced_why"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert conf["num_hidden_layers"] * 4 == row["config"]["num_hidden_layers"] \
        == conf["published_num_hidden_layers"]
    assert conf["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert conf["n_routed_experts"] * 8 == row["config"]["n_routed_experts"] \
        == conf["router_n_experts"]
    assert {"rope_pairs", "softmax_scale", "latent_norm",
            "router"} <= set(conf["assumed"])
    assert "chip 0 of stage 0" in conf["deployment"]
    assert "TBD" not in conf["memory"]


def test_the_cell_is_declared_with_its_readers():
    bench = build.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "deepdump-reason", 1)
    # by name, never by place: the next cell is appended behind this one
    # (test_entries_are_appended_and_nothing_else_changed holds the order)
    assert [c["name"] for c in bench["configs"]].count(NAME) == 1
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(mine) == set(READERS)
    assert all(CELL in m["workloads"] for m in mine.values())
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"moe_local_pair_share", "prefill_pad_share", "tick_host_ms",
            "prefill_ms_per_ktoken", "queue_wait_ms_p50"} <= listed
    # they reckon keys and values per head
    assert not listed & {"paged_attn_roofline", "attn_busy_share",
                         "full_attn_decode_roofline", "expert_mlp_busy_share"}
    out = next(m for m in bench["end_to_end"]
               if m["name"] == "out_tokens_per_s")
    assert CELL in out["workloads"]
    traffic = build.load_json(os.path.join(BENCH, "traffic",
                                           "deepdump-reason.json"))
    assert traffic["arrivals"] == {"kind": "closed", "clients": 64}
    assert traffic["prompt_tokens"] == {"median": 7168, "sigma": 0.3,
                                        "min": 4096, "max": 12288}
    assert traffic["output_tokens"] == {"median": 1536, "sigma": 0.25,
                                        "min": 1024, "max": 2048}
    assert (traffic["ramp_requests"], traffic["max_requests"]) == (64, 320)
    assert traffic["generator"] == "requests"
    assert traffic["warm"] == {"derive": True}
    conf = configuration()
    engine = conf["engine"]
    lengths = traffic["check"]["prompt_tokens"]
    buckets = engine["prefill_buckets"]
    # one in the smallest bucket the mix reaches, one just over a bucket's
    # edge, one of 12,000 or more
    assert min(lengths) <= buckets[0] and max(lengths) >= 12000
    assert any(0 < n - b <= 64 for n in lengths for b in buckets)
    assert (engine["page_size"], engine["max_batch"], engine["max_seq_len"],
            engine["decode_chunk"], engine["prefix_cache"]) == (
        16, 64, 16384, 16, False)
    assert buckets[-1] == 12288 and conf["kv_cache_dtype"] is None
    # ISSUE 46: a pool of 600-655k tokens
    assert 600_000 <= engine["num_pages"] * engine["page_size"] <= 655_000


# ------------------------------------------------------- the driver, on a CPU


@pytest.fixture(scope="module")
def tiny():
    return configuration(TINY)


def _check(conf, wrap=None):
    import contextlib

    import jax

    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.utils import get_tokenizer

    mcfg = build.model_config(conf, TINY)
    params = build.init_params_fn(conf)(mcfg, jax.random.PRNGKey(SEED))
    with (wrap or contextlib.nullcontext)():
        engine = make_engine(mcfg, build.engine_config(conf), params,
                             get_tokenizer(vocab_size=mcfg.vocab_size))
        return correct.check(engine, conf, seed=SEED, prompt_tokens=LENGTHS)


@pytest.fixture(scope="module")
def sound(tiny):
    return _check(tiny)


def test_the_driver_passes_the_engine_as_the_file_states_it(sound):
    assert sound["ok"] and sound["driver"] == "paged_latent"
    assert sound["positions_allowed_over"] == 9          # near_ties
    assert set(sound["cache_rel_errs"]) == {
        "latent.prefill", "latent.decode", "latent_grain.prefill",
        "latent_grain.decode"}
    assert sound["cache_rel_err"] < 1e-4


def test_a_latent_one_precision_down_is_refused_by_the_grain_alone(sound,
                                                                   tiny):
    """The latent rows on their int8 grid (``control_latent.int8_rows``):
    the rows themselves stay inside the cache tolerance (an int8 row is 0.5%
    off), the logits inside theirs, and what the rows keep below the grid
    is gone."""
    import jax

    jax.clear_caches()        # the engine's programs trace the rounding in
    try:
        lowered = _check(tiny, control_latent.int8_rows)
    finally:
        jax.clear_caches()
    assert not lowered["ok"]
    errs = lowered["cache_rel_errs"]
    assert errs["latent.prefill"] < correct.CACHE_TOLERANCE
    assert errs["latent.decode"] < correct.CACHE_TOLERANCE
    assert errs["latent_grain.prefill"] > 0.9
    assert errs["latent_grain.decode"] > 0.9
    assert lowered["compared"]["median_rel_err"]["value"] \
        <= lowered["compared"]["median_rel_err"]["limit"]
    assert lowered["median_rel_err"] > 100 * sound["median_rel_err"]


def test_a_pool_with_values_is_not_this_drivers(tiny):
    from benchmarks.checks import paged_latent

    engine = SimpleNamespace(
        pool=SimpleNamespace(v=object(), quantized=False),
        engine_cfg=SimpleNamespace(page_size=16))
    with pytest.raises(ValueError, match="holds values or scales"):
        paged_latent.cached(engine, [[1, 2]], 1)


# ---------------------------------------------------------------- the readers


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
def test_a_reader_finds_nothing_where_the_program_has_no_such_thing(
        name, sample_trace):
    """The recorded trace is ``tiny``'s (keys and values per head, the
    parent's counters), and a ``ModelConfig`` without the field is the
    parent's: nothing to read, None, and nothing raised; so too with no trace
    at all."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.utils.logging import METRICS

    parent_cfg = SimpleNamespace(n_heads=4, dtype="float32")
    trace = dict(sample_trace, counters={"engine.decode_steps": 656.0})
    with METRICS.scoped():
        for cfg in (TINY_MOE, parent_cfg):
            engine = SimpleNamespace(model_cfg=cfg,
                                     engine_cfg=EngineConfig(max_batch=32))
            for t in (trace, None):
                ctx = SimpleNamespace(engine=engine, trace=t, counters={},
                                      device={"kind": "TPU v5 lite"})
                assert _reader(name).read(ctx) is None


def _kanana_ctx():
    """A trace as the cell's looks: one operation of each kind, by name or
    by the shapes in its text, the kernels at twice their roofline's time."""
    cfg = build.model_config(configuration(), NAME)
    ecfg = build.engine_config(configuration())
    rows = 64 * 16 * 8000 * 12.0           # a 16-step scan of 64 x 8k tokens
    pairs = 12 * 7000 * 7001 / 2.0         # one 7k prompt
    decode_s = mla_costs.decode_bytes(cfg, rows) / 819e9
    prefill_s = mla_costs.prefill_ops(cfg, pairs) / 197e12
    text = {
        "mla_paged_attention.3": "%mla_paged_attention.3 = bf16[64,32,512] "
                                 "custom-call(s32[1] %l)",
        "flash_attention.4": "%flash_attention.4 = bf16[1,32,8192,128] "
                             "custom-call(s32[1] %n)",
        "fusion.7": "%fusion.7 = bf16[64,32,512]{2,1,0} fusion(bf16[64,32,"
                    "128] %q, bf16[512,32,256] %w_kvb)",
        "fusion.8": "%fusion.8 = bf16[64,32,128]{2,1,0} fusion(bf16[64,32,"
                    "512] %o, bf16[512,32,128] %w_uv)",
        "fusion.9": "%fusion.9 = bf16[64,6144]{1,0} fusion(bf16[64,2048] "
                    "%x, bf16[2048,6144] %wq)",
    }
    seconds = {"mla_paged_attention.3": 2 * decode_s,
               "flash_attention.4": 2 * prefill_s, "fusion.7": 0.01,
               "fusion.8": 0.02, "fusion.9": 0.3}
    trace = {"op_text": text, "op_seconds": seconds, "busy_s": 1.0,
             "counters": {"engine.mla_decode_row_reads": rows,
                          "engine.mla_prefill_pairs": pairs}}
    return SimpleNamespace(
        engine=SimpleNamespace(model_cfg=cfg, engine_cfg=ecfg), trace=trace,
        counters={}, device={"kind": "TPU v5 lite"}), decode_s, prefill_s


def test_the_readers_read_what_the_cost_functions_count():
    from k8s_llm_rca_tpu.utils.logging import METRICS

    ctx, decode_s, prefill_s = _kanana_ctx()
    assert _reader("mla_decode_roofline").read(ctx) == pytest.approx(50.0)
    assert _reader("mla_prefill_roofline").read(ctx) == pytest.approx(50.0)
    # the projection (fusion.9) is not attention's
    assert _reader("mla_busy_share").read(ctx) == pytest.approx(
        100.0 * (2 * decode_s + 2 * prefill_s + 0.03))
    with METRICS.scoped():
        assert _reader("latent_cache_bytes_per_token").read(ctx) is None
        METRICS.gauge("engine.latent_cache_bytes_per_token", 15360.0)
        assert _reader("latent_cache_bytes_per_token").read(ctx) == 15360.0
    # a walk at the ridge: where its operations take longer than its bytes
    # the roofline is theirs
    ctx.device = {"kind": "TPU v5 lite"}
    fast = dict(ctx.trace, op_seconds=dict(
        ctx.trace["op_seconds"], **{"mla_paged_attention.3": decode_s}))
    ctx.trace = fast
    assert _reader("mla_decode_roofline").read(ctx) == pytest.approx(100.0)


def test_the_cost_functions_count_the_arithmetic_not_the_program():
    cfg = build.model_config(configuration(), NAME)
    assert mla_costs.row_bytes(cfg) == 1152.0            # 576 bf16 values
    assert mla_costs.decode_bytes(cfg, 1000.0) == 1152e3
    # every head's score over the row and its sum of the latent
    assert mla_costs.decode_ops(cfg, 1.0) == 2 * 32 * (576 + 512) == 69632
    # 60 operations a byte: the bytes' time is the larger on a v5e
    assert mla_costs.decode_ops(cfg, 1.0) / 197e12 \
        < mla_costs.decode_bytes(cfg, 1.0) / 819e9
    assert mla_costs.prefill_ops(cfg, 1.0) == 2 * 32 * (192 + 128)
    pattern = mla_costs.absorb_pattern(cfg)
    assert pattern.search("bf16[512,32,256]") and pattern.search(
        "f32[512,32,128]") and not pattern.search("bf16[2048,6144]")
    assert mla_costs.DECODE.search("%mla_paged_attention.12")
    assert mla_costs.PREFILL.search("flash_attention.3")
    assert not mla_costs.PREFILL.search("flash_attention_window.3")
    assert not mla_costs.has_latent(SimpleNamespace())
    assert mla_costs.absorb_pattern(SimpleNamespace()) is None


# ---------------------------------------------------------------- the rehearsal


@pytest.mark.slow
def test_the_cpu_rehearsal_of_the_tiny_twin_runs_the_readers():
    """``run.py`` on the tiny twin, as the verify skill rehearses a cell:
    correct, nothing failed, nothing compiled in the window, and the
    counter-fed readers on the line (a CPU has no device trace)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(HERE, "rehearsal-kanana.json"), "--workload",
         "tiny-kanana.deepdump-reason", "--seed", "2147483999", "--seconds",
         "10", "--trace", "1", "--allow-cpu"],
        capture_output=True, text=True, timeout=1500,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    got = line["metrics"]
    # a row of 48 float32 values kept at 128 lanes, in 3 layers
    assert got["latent_cache_bytes_per_token"]["value"] == 3 * 128 * 4
    assert 20.0 < got["moe_local_pair_share"]["value"] < 30.0   # 8 of 32
    assert "paged_attn_live_page_share" in got
    assert not {"mla_decode_roofline", "mla_busy_share"} & set(got)
