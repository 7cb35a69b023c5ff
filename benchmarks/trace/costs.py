"""The arithmetic between a trace and a roofline share: the table of peaks,
which trace names belong to which kernel, and the bytes a kernel has to move,
computed from shapes.  Kept with the benchmark so that no PR that claims a
gain can move its own yardstick.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Iterable, Tuple

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")

# XLA module names (reduce.program_name) of the engine's programs
DECODE_SCANS = ("paged_decode_scan", "paged_decode_scan_dfa")
DECODE_STEPS = ("paged_decode_step",)
PREFILLS = ("paged_prefill", "paged_prefill_batch", "paged_prefill_chunk",
            "paged_prefill_chunk_batch")
# device operation names (the program's kernels carry no stable name yet:
# PERF.md section 7 asks the tracing PR for one)
PAGED_ATTENTION = re.compile(r"^paged_attention")


def expert_mlp_pattern(n_experts: int, hidden: int, inter: int):
    """Operations of the expert MLP, told by the shapes in their HLO text: a
    stacked expert weight ([E, H, I] or [E, I, H], its last dimension halved
    where nibble-packed) or a per-expert activation ([..., E, I] or
    [..., E, H])."""
    dims = []
    for a, b in ((hidden, inter), (inter, hidden)):
        dims += [f"[{n_experts},{a},{b}]", f"[{n_experts},{a},{b // 2}]"]
    dims += [f",{n_experts},{inter}]", f",{n_experts},{hidden}]"]
    return re.compile("|".join(re.escape(d) for d in dims))


def peaks(device_kind: str) -> Dict[str, Any]:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r} (known: {sorted(table)}); add the "
                         f"chip to benchmarks/trace/peaks.json with its "
                         f"source")
    return table[device_kind]


def kernel_time(op_seconds: Dict[str, float], pattern,
                op_text: Dict[str, str] = None) -> float:
    """Self seconds of the operations whose name (or, given ``op_text``,
    whose whole HLO text) matches ``pattern``."""
    return sum(s for name, s in op_seconds.items()
               if pattern.search(op_text[name] if op_text else name))


def decode_program_time(programs: Dict[str, Dict[str, float]]) -> float:
    """Seconds of the decode programs in the trace.  A scan program runs 1,
    2, 4, 8 or ``decode_chunk`` steps (its length is a static argument,
    bound by each slot's allocated pages) and every length carries the same
    module name, so the steps they ran are not counted from the programs:
    the engine counts them where it dispatches (``engine.decode_steps``)."""
    return sum(p["seconds"] for name, p in programs.items()
               if name in DECODE_SCANS or name in DECODE_STEPS)


def prefill_program_time(programs: Dict[str, Dict[str, float]]) -> float:
    return sum(p["seconds"] for n, p in programs.items() if n in PREFILLS)


def kv_bytes_per_token(model_cfg, engine_cfg) -> float:
    """Bytes of cached keys and values one token holds across all layers,
    scales included.  ASSUMES that every one of ``n_layers`` layers caches
    keys and values of ``kv_dim`` for every token of the context.  A model
    for which that is untrue (layers that keep a recurrent state and no
    keys, a latent cache, a window, a selector that reads some blocks only)
    would be counted too many bytes here and read over 100% of a roofline:
    it brings a bytes-and-operations module of its own beside this one, and
    a roofline reader that uses it (``benchmarks/README.md``)."""
    per_elem = {"int8": 1.0, "int4": 0.5}.get(
        engine_cfg.kv_cache_dtype, 2.0)
    scales = 4.0 if engine_cfg.kv_cache_dtype in ("int8", "int4") else 0.0
    return 2.0 * model_cfg.n_layers * (model_cfg.kv_dim * per_elem + scales)


def paged_attention_bytes(model_cfg, engine_cfg,
                          ticks: Iterable[Tuple[float, float, int, int, int]]
                          ) -> float:
    """Bytes of keys and values the decode kernel has to read over the given
    ticks: every decode step of a tick reads the whole cached context of
    every live sequence (``live_tokens``), in every layer
    (``kv_bytes_per_token``'s assumption, and that the kernel reads all live
    tokens and selects none)."""
    per_token = kv_bytes_per_token(model_cfg, engine_cfg)
    return sum(t[5] * t[4] * per_token for t in ticks)
