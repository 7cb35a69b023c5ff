"""From a configuration file to a built engine: the benchmark's only door
into the program's build path.

A configuration file (``benchmarks/configs/<name>.json``) holds the published
``config.json`` keys as they are run, the two serving precisions, and an
``engine`` group that becomes the ``EngineConfig``.  The engine is built as
``sweeps/common.py::build_service`` builds it: ``init_params`` with the
quantizing transform, the byte tokenizer padded to the vocabulary,
``make_engine(paged=True)``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


# What the persistent cache has to hold so that a cell's second run compiles
# nothing: one cell's programs are 200-250 MiB (the whole unrolled layer
# stack per shape) and both cells' together about 350 MiB (the chip tool's
# count after PR 22's call 25).
CACHE_BYTES_NEEDED = 1 << 30


def enable_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set, else the fixed
    ``<checkout>/.jax_cache``.  Every program is kept whatever its compile
    time.  A size cap from the environment is kept where it holds what the
    cells need (``CACHE_BYTES_NEEDED``).  A smaller one (the chip tool's
    machines set 192 MiB, less than one cell's programs, so every run would
    evict programs of its own and start cold) is LIFTED, not raised: JAX's
    eviction reads an access-time file beside every entry and fails every
    write into a directory that holds an entry written under no cap (the
    default), which is what any directory another JAX process has used
    holds.  Raising the cap to 1 GiB was tried in PR 22 and cost its last
    chip call: no new program was written, every run compiled 180 s anew.
    The cache then grows by the programs the cells have, once: the shapes
    are fixed, so a later run adds nothing."""
    import jax

    path = os.environ.get(CACHE_ENV) or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    if jax.config.jax_compilation_cache_max_size < CACHE_BYTES_NEEDED:
        jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def describe_device(chips: int, allow_cpu: bool = False) -> Dict[str, Any]:
    """The devices as JAX reports them.  Fewer chips than the cell asks for,
    or no accelerator, is an error: no result is printed from a CPU unless
    the rehearsal (``--allow-cpu``, never the driver) asked for it."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu" and not allow_cpu:
        raise SystemExit("benchmark: JAX found no accelerator; a cell runs "
                         "only on the chip (CPU rehearsal: --allow-cpu)")
    if platform != "cpu" and len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX "
                         f"found {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips if platform != "cpu" else 1}


def memory_peak_bytes(chips: int = 1) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def model_config(conf: Dict[str, Any], name: str):
    from k8s_llm_rca_tpu.config import ModelConfig

    return ModelConfig(
        name=name,
        vocab_size=conf["vocab_size"],
        hidden_size=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        intermediate_size=conf["intermediate_size"],
        rope_theta=float(conf["rope_theta"]),
        rms_norm_eps=float(conf["rms_norm_eps"]),
        max_seq_len=conf["max_position_embeddings"],
        dtype=conf["torch_dtype"],
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        n_experts=conf.get("num_local_experts", 0),
        n_experts_per_tok=conf.get("num_experts_per_tok", 2),
        fused_quant_matmul=bool(conf["engine"].get("fused_quant_matmul",
                                                   False)))


def engine_config(conf: Dict[str, Any]):
    from k8s_llm_rca_tpu.config import EngineConfig

    kw = {k: v for k, v in conf["engine"].items()
          if k != "fused_quant_matmul"}
    kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
    return EngineConfig(kv_cache_dtype=conf.get("kv_cache_dtype"), **kw)


def build_engine(conf: Dict[str, Any], name: str, seed: int
                 ) -> Tuple[Any, Dict[str, float]]:
    """Weights from ``seed``, quantized tensor by tensor as they are made
    (the program's own build path), then the paged engine."""
    import jax

    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.models.quant import quantizing_transform
    from k8s_llm_rca_tpu.utils import get_tokenizer

    mcfg, ecfg = model_config(conf, name), engine_config(conf)
    bits = conf.get("weight_quant_bits")
    t0 = time.perf_counter()
    params = llama.init_params(
        mcfg, jax.random.PRNGKey(seed),
        tensor_transform=quantizing_transform(bits=bits) if bits else None)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    engine = make_engine(mcfg, ecfg, params,
                         get_tokenizer(vocab_size=mcfg.vocab_size))
    jax.block_until_ready(engine.pool)
    return engine, {"weights_s": t1 - t0,
                    "engine_s": time.perf_counter() - t1}
