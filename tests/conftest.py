"""Hermetic test harness: force an 8-virtual-device CPU platform before the
JAX backend initializes, so mesh/collective/sharding logic is exercised
without TPUs (SURVEY.md §4's prescription).  Bench/serve on the real chip use
the default platform instead."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _with_host_device_count  # noqa: E402

os.environ["XLA_FLAGS"] = _with_host_device_count(
    os.environ.get("XLA_FLAGS", ""), 8)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# tests compile tiny programs by the thousand: keep the persistent compile
# cache off whatever the entry points under test ask for
# (runtime/compile_cache.py)
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


# Seconds a file's tests take together on six workers (the junit of PR 46's
# whole run, rounded; files under a minute are left out).  ``--dist loadfile``
# hands out whole files, by default the ones with the most tests first, and
# so the run ended with one worker alone for 200 s in
# ``test_sweeps_and_graft`` (10 tests, one of them 280 s) while five stood
# idle.  Longest first, the short files fill the end and the run takes what
# the work takes (ROADMAP.md C6).  A file that grows past a minute belongs
# here; one that is missing only runs later than it might.
FILE_SECONDS = {
    "test_parallel_pp": 530, "test_kernels": 480, "test_exaone_moe": 420,
    "test_aot_compile": 420, "test_ssm": 380, "test_parallel": 360,
    "test_store_fabric": 330, "test_disagg": 320,
    "test_sweeps_and_graft": 310, "test_nemotron_h": 310,
    "test_speculative": 290, "test_granite_hybrid": 230,
    "test_paged": 190, "test_kanana_moe": 180,
    "test_moe_grouped": 180, "test_net_cluster": 150,
    "test_prefix_tiers": 150, "test_overlap": 140, "test_proc_cluster": 130,
    "test_quant": 130, "test_distill_e2e": 130, "test_quant_matmul": 120,
    "test_constrain": 100, "test_fleet_obs": 100, "test_sweep_sched": 90,
    "test_engine": 90, "test_parallel_composed": 90, "test_rca_pipeline": 90,
    "test_overload": 80, "test_encoder_rerank": 80, "test_engine_timing": 80,
    "test_mla_attention": 70, "test_faults": 70, "test_model_llama": 60,
}


def pytest_configure(config):
    # xdist would sort the files by their number of tests again
    # (--loadscope-reorder, on by default) and undo the order below
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


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
