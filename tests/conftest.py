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
