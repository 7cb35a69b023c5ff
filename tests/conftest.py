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
