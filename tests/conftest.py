"""Hermetic test harness: force an 8-virtual-device CPU platform before the
JAX backend initializes, so mesh/collective/sharding logic is exercised
without TPUs (SURVEY.md §4's prescription).  Bench/serve on the real chip use
the default platform instead."""

import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _with_host_device_count  # noqa: E402

os.environ["XLA_FLAGS"] = _with_host_device_count(
    os.environ.get("XLA_FLAGS", ""), 8)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Tests compile tiny programs by the thousand, and every engine a test
# builds makes its jits anew, so JAX's in-memory cache never hits across
# engines: they share their compiles through ONE directory instead.  Fixed,
# because every xdist worker has to name the same one and the path is part
# of the cache key; outside the checkout, because a tree that is copied must
# not grow.  A later run finds what an earlier one left.
COMPILE_CACHE_DIR = os.path.join(tempfile.gettempdir(),
                                 "k8s_llm_rca_tpu-test-compiles")
# Held to this size by ``pytest_configure``, once a run, and not by
# ``jax_compilation_cache_max_size``: with that set, every write locks the
# directory and reads every entry's size and age first (1 s a write at
# 10,000 entries, measured here at PR 50; a whole run writes 9,500 entries,
# 108 MB, from six workers).
COMPILE_CACHE_MAX_BYTES = 1 << 30
jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import pytest  # noqa: E402


def open_compile_cache():
    """JAX opens its cache at a process's first compile (the first after a
    ``reset_cache()``) and keeps that directory from then on: make that
    compile now, before an entry point under test
    (runtime/compile_cache.py) can name the checkout's own."""
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.jit(lambda x: x)(0)


@pytest.fixture(scope="session", autouse=True)
def _compile_cache_opened():
    """Before the first test of every process that runs tests (a fixture,
    so the workers' parent starts no backend, and XLA's two log lines for
    a program it loads are captured with a test's output)."""
    open_compile_cache()


@pytest.fixture(autouse=True)
def _compile_cache_dir_named_again():
    """The directory's name is part of every cache key: after a test whose
    entry point named another (``enable_compile_cache``), name ours again,
    or the rest of the process misses what the other workers wrote."""
    yield
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


# Seconds a file's tests take together on six workers (the junit of PR 50's
# whole run from an empty compile directory, rounded; files under a minute
# are left out).  ``--dist loadfile`` hands out whole files, by default the
# ones with the most tests first, so a file of few long tests would start
# last and one worker would end the run alone.  Longest first, the short
# files fill the end and the run takes what the work takes (ROADMAP.md C6).
# A file that grows past a minute belongs here; one that is missing only
# runs later than it might.
FILE_SECONDS = {
    "test_kernels": 330, "test_aot_compile": 330, "test_exaone_moe": 280,
    "test_parallel_pp": 280, "test_ssm": 220, "test_parallel": 200,
    "test_store_fabric": 140, "test_nemotron_h": 130,
    "test_mla_attention": 130, "test_speculative": 130, "test_disagg": 120,
    "test_moe_grouped": 120, "test_paged": 110, "test_proc_cluster": 110,
    "test_quant_matmul": 100, "test_distill_e2e": 100,
    "test_granite_hybrid": 100, "test_kanana_moe": 100,
    "test_net_cluster": 90, "test_fleet_obs": 80,
    "test_parallel_composed": 70, "test_constrain": 70,
    "test_prefix_tiers": 70, "test_sweep_sched": 70, "test_overlap": 60,
    "test_encoder_rerank": 60,
}


def pytest_configure(config):
    # xdist would sort the files by their number of tests again
    # (--loadscope-reorder, on by default) and undo the order below
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    # once a run (a worker has ``workerinput``): a directory past its cap
    # is emptied, and the run that does it is a cold one
    if not hasattr(config, "workerinput") and os.path.isdir(COMPILE_CACHE_DIR):
        held = sum(e.stat().st_size for e in os.scandir(COMPILE_CACHE_DIR))
        if held > COMPILE_CACHE_MAX_BYTES:
            shutil.rmtree(COMPILE_CACHE_DIR)


def pytest_collection_modifyitems(items):
    """Whole files, the longest first; inside a file, and among the files
    the table does not name, the order stays as collected (the sort is
    stable), so every worker still sees one and the same collection."""
    items.sort(key=lambda item: -FILE_SECONDS.get(item.path.stem, 0))


@pytest.fixture(scope="session")
def cpu_devices():
    devs = [d for d in jax.devices() if d.platform == "cpu"]
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs


def reference_greedy(cfg, params, prompt_ids, n_new, max_seq_len=None):
    """The model's own greedy continuation of one prompt: ``llama.prefill``
    then ``llama.decode_step`` over a one-slot ``KVCache``.  The plain
    reference the engine is held to: no pages, no batch, no scan."""
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.models import llama

    n = len(prompt_ids)
    cache = llama.init_cache(cfg, 1, max_seq_len or cfg.max_seq_len)
    padded = jnp.zeros((1, -(-n // 16) * 16), jnp.int32)
    padded = padded.at[0, :n].set(jnp.asarray(prompt_ids, jnp.int32))
    cache, logits = llama.prefill(cfg, params, cache, padded,
                                  jnp.int32(n), jnp.int32(0))
    out = [int(jnp.argmax(logits[0]))]
    lengths = jnp.array([n], jnp.int32)
    for _ in range(n_new - 1):
        cache, logits = llama.decode_step(
            cfg, params, cache, jnp.array([out[-1]], jnp.int32), lengths)
        out.append(int(jnp.argmax(logits[0])))
        lengths = lengths + 1
    return out


def tiny_on_a_tp_mesh(cpu_devices, key):
    """``(cfg, params, tokenizer, mesh)``: TINY at a 64-token cache with its
    weights sharded over dp2 x tp4 of the virtual devices, for the one case
    each subsystem's file runs on a mesh (the GSPMD paged TP engine:
    ``make_engine(..., tp_mesh=mesh, use_kernel=False)``)."""
    from k8s_llm_rca_tpu.config import TINY, MeshConfig
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.runtime.mesh import build_mesh
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=4), devices=cpu_devices)
    params = shard_pytree(llama.init_params(cfg, jax.random.PRNGKey(key)),
                          llama_param_specs(cfg), mesh)
    return cfg, params, get_tokenizer(vocab_size=cfg.vocab_size), mesh


def scan_kernel_in_the_engine(monkeypatch, n_ssm_layers, run):
    """The prefill's chunked scan as its kernel inside an engine, for the
    two layer-table models' test files.  ``run()`` builds an engine, runs
    its prompts and returns their tokens.  On a CPU ``flash_prefill_plan``
    says no: the programs are today's (their StableHLO is pinned in
    tests/test_exaone_moe.py), the kernel's entry point is never reached
    and ``engine.ssm_prefill_kernel_tokens`` is absent.  With the plan
    forced to yes (the kernel interpreted) the tokens are the same, every
    prefill program traced calls the kernel once a Mamba layer, and the
    counter equals ``engine.ssm_prefill_tokens``: positions x Mamba
    layers."""
    from k8s_llm_rca_tpu.engine import paged
    from k8s_llm_rca_tpu.ops import ssm
    from k8s_llm_rca_tpu.utils.logging import METRICS

    def refuse(*_, **__):
        raise AssertionError("the scan's kernel on a CPU engine")

    with METRICS.scoped(), monkeypatch.context() as off:
        off.setattr(ssm, "ssm_chunk_scan", refuse)
        want = run()
        assert METRICS.count("engine.ssm_prefill_tokens") > 0
        assert "engine.ssm_prefill_kernel_tokens" not in METRICS.snapshot()
    kernel, traced = ssm.ssm_chunk_scan, []

    def spy(*args, **kw):
        traced.append(args[0].shape)
        return kernel(*args, **kw)

    monkeypatch.setattr(ssm, "ssm_chunk_scan", spy)
    monkeypatch.setattr(paged, "flash_prefill_plan",
                        lambda *_, **__: (True, None))
    with METRICS.scoped():
        assert run() == want
        assert traced and len(traced) % n_ssm_layers == 0
        assert METRICS.count("engine.ssm_prefill_kernel_tokens") == (
            METRICS.count("engine.ssm_prefill_tokens"))
        assert METRICS.count("engine.ssm_prefill_tokens") == (
            n_ssm_layers * METRICS.count("engine.prefill_padded_tokens"))
