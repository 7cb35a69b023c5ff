"""From a configuration file to a built engine: the benchmark's only door
into the program's build path.

A configuration file (``benchmarks/configs/<name>.json``) holds the published
``config.json`` keys as they are run, the two serving precisions, and an
``engine`` group that becomes the ``EngineConfig``.  Which published key
becomes which field of the program's ``ModelConfig``, which values the
program can honour, and which function of the program makes the weights is
the ARCHITECTURE's, one file for each ``model_type``
(``benchmarks/architectures/<model_type>.json``): nothing here names a
published key.  The engine is built as ``sweeps/common.py::build_service``
builds it: that weight builder with the quantizing transform, the byte
tokenizer padded to the vocabulary, ``make_engine(paged=True)``.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import time
from typing import Any, Callable, Dict, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
ARCH_DIR = os.path.join(BENCH_DIR, "architectures")
CHECKS_DIR = os.path.join(BENCH_DIR, "checks")
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


# top-level keys of a configuration file that are the harness's own; every
# other one is a published key and has to be known to the architecture's file
HARNESS_KEYS = ("source", "model_type", "weight_quant_bits", "kv_cache_dtype",
                "engine", "reference", "assumed", "deployment", "reduced_why",
                "memory")
# keys every published ``config.json`` may carry that say nothing about the
# model's shape; an architecture lists its own such keys under ``ignored``
SHAPELESS_KEYS = ("architectures", "transformers_version", "bos_token_id",
                  "eos_token_id", "pad_token_id", "use_cache",
                  "initializer_range")


def architecture(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The file of the configuration's ``model_type``:

    - ``fields``: published key -> ``ModelConfig`` field; each key has to be
      in the configuration file;
    - ``honoured``: published key -> the values the program computes
      correctly; another value is refused, never ignored;
    - ``fixed``: ``ModelConfig`` fields the architecture implies and no key
      states;
    - ``ignored``: its keys that say nothing about shape;
    - ``init_params``: ``module:function`` of the program that makes the
      weights, ``f(model_cfg, key, tensor_transform=None)``;
    - ``check``: the module of ``benchmarks/checks/`` through which the
      logits check and the warm-up reach the model's cache; a missing or
      unknown one is refused here, before anything is built;
    - ``near_ties`` (optional): the sentence that allows the logits check a
      third of its positions over the tolerance (``lib/correct.py``).
    """
    known = sorted(f[:-len(".json")] for f in os.listdir(ARCH_DIR)
                   if f.endswith(".json"))
    model_type = conf.get("model_type")
    if model_type not in known:
        raise ValueError(
            f"no architecture for model_type {model_type!r} (known: "
            f"{', '.join(known)}); a new one adds benchmarks/architectures/"
            f"<model_type>.json beside the program code that computes it")
    arch = load_json(os.path.join(ARCH_DIR, model_type + ".json"))
    drivers = sorted(f[:-len(".py")] for f in os.listdir(CHECKS_DIR)
                     if f.endswith(".py") and not f.startswith("_"))
    if arch.get("check") not in drivers:
        raise ValueError(
            f"architecture {model_type!r}: "
            + (f"no check driver {arch['check']!r}" if "check" in arch
               else "the key 'check' is missing")
            + f" (known: {', '.join(drivers)}); a model with another kind "
            f"of cache adds benchmarks/checks/<name>.py")
    return arch


def check_driver(conf: Dict[str, Any]):
    """The module of ``benchmarks/checks/`` that the file of the
    configuration's architecture names."""
    return importlib.import_module(
        "benchmarks.checks." + architecture(conf)["check"])


def model_config(conf: Dict[str, Any], name: str):
    """The program's ``ModelConfig`` for a configuration file, strict both
    ways: a published key the architecture's file does not know, a value it
    does not list as honoured and a key it needs and the file lacks are each
    a ``ValueError`` that names the key."""
    from k8s_llm_rca_tpu.config import ModelConfig

    arch = architecture(conf)
    what = f"{name} (model_type {conf['model_type']!r})"
    published = {k: v for k, v in conf.items() if k not in HARNESS_KEYS}
    fields, honoured = arch["fields"], arch.get("honoured", {})
    known = {*fields, *honoured, *arch.get("ignored", ()), *SHAPELESS_KEYS}
    for key, value in published.items():
        if key not in known:
            raise ValueError(
                f"{what}: nothing reads the key {key!r}; the program has to "
                f"compute what it stands for before the architecture's file "
                f"may list it")
        if key in honoured and value not in honoured[key]:
            raise ValueError(
                f"{what}: {key} = {value!r}, and the program honours only "
                f"{honoured[key]!r}")
    defaults = {f.name: f.default for f in dataclasses.fields(ModelConfig)}
    kw = dict(arch.get("fixed", {}))
    for key, field in fields.items():
        if key not in published:
            raise ValueError(f"{what}: the key {key!r} is missing")
        value = published[key]
        if isinstance(value, list):     # a ModelConfig is a static argument
            value = tuple(value)
        elif isinstance(defaults.get(field), float):
            value = float(value)
        kw[field] = value
    unknown = sorted(set(kw) - set(defaults))
    if unknown:
        raise ValueError(f"{what}: ModelConfig has no field {unknown[0]!r}")
    return ModelConfig(
        name=name, **kw,
        fused_quant_matmul=bool(conf["engine"].get("fused_quant_matmul",
                                                   False)))


def init_params_fn(conf: Dict[str, Any]) -> Callable:
    """The program's weight builder for the configuration's architecture."""
    module, _, function = architecture(conf)["init_params"].partition(":")
    return getattr(importlib.import_module(module), function)


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
    from k8s_llm_rca_tpu.models.quant import quantizing_transform
    from k8s_llm_rca_tpu.utils import get_tokenizer

    mcfg, ecfg = model_config(conf, name), engine_config(conf)
    bits = conf.get("weight_quant_bits")
    t0 = time.perf_counter()
    params = init_params_fn(conf)(
        mcfg, jax.random.PRNGKey(seed),
        tensor_transform=quantizing_transform(bits=bits) if bits else None)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    engine = make_engine(mcfg, ecfg, params,
                         get_tokenizer(vocab_size=mcfg.vocab_size))
    jax.block_until_ready(engine.pool)
    return engine, {"weights_s": t1 - t0,
                    "engine_s": time.perf_counter() - t1}
