"""Profiling & chip-level observability.

The reference's only instrumentation is wall-clock bracketing with
``time.time()`` (reference test_all.py:52,143-151 and
test_with_file.py:173-175); utils/logging.py already upgrades that to
structured counters/timers.  This module adds the chip-level layer SURVEY
§5 calls for: ``jax.profiler`` trace capture (TensorBoard/XProf), device
memory stats, and an analytic MFU/flops model for the decoder so benches
and sweeps can report tokens/sec/chip against the hardware ceiling.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, Iterator, NamedTuple, Optional

import jax

from k8s_llm_rca_tpu.config import ModelConfig
from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.utils.logging import METRICS, get_logger

log = get_logger(__name__)

class ChipPeaks(NamedTuple):
    bf16_tflops: float
    hbm_gbps: float


# Published peaks per chip, keyed by the exact ``device_kind`` JAX reports.
# Source: Google Cloud documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16, 819 GB/s HBM bandwidth per chip).  A chip is added
# here with its source once the repo has run on it.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_tflops=197.0, hbm_gbps=819.0),   # v5e
}


def chip_peaks(device: Optional[Any] = None) -> Optional[ChipPeaks]:
    """Peaks of ``device`` (default: the first local device).  None on a
    CPU; a TPU whose ``device_kind`` is not in the table is an error, not
    a borrowed row."""
    dev = device or jax.devices()[0]
    if dev.platform != "tpu":
        return None
    peaks = CHIP_PEAKS.get(dev.device_kind)
    if peaks is None:
        raise ValueError(
            f"no published peaks for TPU device_kind {dev.device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)}); add the chip to "
            f"runtime/profiling.py CHIP_PEAKS with its source")
    return peaks


@contextlib.contextmanager
def trace(log_dir: str, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a jax.profiler trace viewable in TensorBoard/XProf:

        with profiling.trace("/tmp/rca-trace"):
            engine.step()
    """
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s", log_dir)


@contextlib.contextmanager
def annotate(name: str, **args) -> Iterator[None]:
    """The one way a span is made: a named region in the profiler
    timeline (``TraceAnnotation``: on the device trace's clock, free
    while no profile runs), the METRICS timers (always on:
    ``<name>.total_s`` / ``<name>.count``) AND the obs span tracer (when
    a ``Tracer`` is active) — ONE name shared by XProf captures, counters
    and flight records, so a region found slow in one shows up under the
    same name in the others.  ``args`` go to the obs span only: the
    annotation's name never carries them, because trace readers match
    names exactly."""
    with jax.profiler.TraceAnnotation(name):
        with METRICS.timer(name):
            with obs_trace.span(name, cat="xprof", **args):
                yield


def named_partial(fn, **kwargs):
    """``functools.partial(fn, **kwargs)`` that keeps ``fn``'s name, so
    ``jax.jit`` of it compiles ``jit_<fn name>`` and not ``jit__unknown``:
    the device trace's ``XLA Modules`` line then names the engine's
    programs by themselves.  (The name is part of the compile-cache
    key.)"""
    part = functools.partial(fn, **kwargs)
    # not functools.wraps: its ``__wrapped__`` would make
    # ``inspect.signature`` (which jit reads for static arguments) show
    # the bound keywords again
    part.__name__, part.__qualname__ = fn.__name__, fn.__qualname__
    return part


def device_memory_stats(device: Optional[Any] = None) -> Dict[str, float]:
    """HBM usage for one device (bytes): bytes_in_use, peak_bytes_in_use,
    bytes_limit where the backend reports them ({} otherwise)."""
    dev = device or jax.devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    if not stats:
        return {}
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size")
    return {k: float(stats[k]) for k in keys if k in stats}


# ---------------------------------------------------------------------------
# analytic flops / MFU model (decoder)
# ---------------------------------------------------------------------------


def _layer_matmul_weights(cfg: ModelConfig, routed_only: bool) -> float:
    """Matmul weight count of ONE decoder layer (attn + MLP + router).

    ``routed_only``: for MoE, count only the top-k routed experts' MLPs —
    the per-token active set (FLOPs / best-case bytes) — instead of all
    experts (parameter count).  The single source for the per-layer
    architecture arithmetic shared by the param/FLOP/bytes models below.
    """
    h, q, kv, inter = (cfg.hidden_size, cfg.q_dim, cfg.kv_dim,
                       cfg.intermediate_size)
    w = h * q + 2 * h * kv + q * h                         # qkv + out proj
    if cfg.n_experts > 0:
        w += h * cfg.n_experts                             # router
        n_mlp = cfg.n_experts_per_tok if routed_only else cfg.n_experts
        w += n_mlp * 3 * h * inter                         # expert MLPs
    else:
        w += 3 * h * inter
    return float(w)


def decoder_param_count(cfg: ModelConfig) -> int:
    """Parameter count of the Llama/Mixtral stack (embeddings included)."""
    h = cfg.hidden_size
    per_layer = _layer_matmul_weights(cfg, routed_only=False) + 2 * h  # norms
    total = cfg.n_layers * per_layer
    total += cfg.vocab_size * h                            # embedding
    total += h                                             # final norm
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * h                        # lm_head
    return int(total)


def decode_flops_per_token(cfg: ModelConfig, context_len: int) -> float:
    """FLOPs to decode ONE token at a given KV context length.

    Matmul-dominated model: 2 FLOPs per MAC.  For MoE only the top-k
    routed experts' MLPs count (hard dispatch); attention adds the
    O(context) KV dot products.
    """
    per_layer = 2.0 * _layer_matmul_weights(cfg, routed_only=True)
    # attention scores + weighted values: q·K^T and P·V over the context
    per_layer += 2.0 * 2 * cfg.n_heads * cfg.head_dim * context_len
    total = cfg.n_layers * per_layer
    total += 2.0 * cfg.hidden_size * cfg.vocab_size        # logits matmul
    return total


def mfu(cfg: ModelConfig, tokens_per_sec: float, context_len: int,
        device: Optional[Any] = None) -> Optional[float]:
    """Model FLOPs utilization in [0, 1] against the chip's bf16 peak;
    None on a CPU (see ``chip_peaks``)."""
    peaks = chip_peaks(device)
    if peaks is None:
        return None
    flops = decode_flops_per_token(cfg, context_len) * tokens_per_sec
    return flops / (peaks.bf16_tflops * 1e12)


def decode_bytes_per_token(cfg: ModelConfig, context_len: int, batch: int,
                           weight_bits: int = 16, kv_bits: int = 16) -> float:
    """Minimum HBM bytes moved to decode ONE token at a given context.

    Decode traffic per step: every live weight byte is read once (shared
    across the batch — that sharing is the entire continuous-batching
    win), and each sequence reads its own KV history and writes one new
    KV entry.  Quantized tensors carry per-channel/per-token scales;
    those are second-order (<1%) and folded into a 1% overhead factor
    rather than modeled exactly.  Activations are negligible at batch
    decode sizes.  For MoE, only the top-k routed experts' weights are
    read per token in the best case (each token needs its experts; at
    large batch every expert is resident but the per-token read cost is
    still the routed fraction when experts fit in VMEM-sized tiles —
    we model the optimistic bound, which keeps the roofline an upper
    bound on achievable tok/s).
    """
    wbytes = weight_bits / 8.0
    per_layer = _layer_matmul_weights(cfg, routed_only=True)
    # the logits matmul streams one vocab*h table whether or not the
    # embedding is tied; the input-embedding gather reads one row per
    # sequence (negligible), not the table
    weight_per_token = (cfg.n_layers * per_layer
                        + cfg.vocab_size * cfg.hidden_size) * wbytes / batch
    kv_per_token = (cfg.n_layers * 2 * cfg.kv_dim
                    * (context_len + 1) * kv_bits / 8.0)
    return 1.01 * (weight_per_token + kv_per_token)


def stage_local_cp_vs_tp(cfg: ModelConfig, context_len: int, batch: int,
                         n_intra: int, weight_bits: int = 16,
                         kv_bits: int = 16) -> Dict[str, float]:
    """Per-device decode cost of spending a pipeline stage's INTRA-stage
    devices on TP vs on CP — the quantitative basis for excluding PP×CP
    (docs/parallelism.md "PP×CP: a quantified no").

    The asymmetry: TP divides the matmul FLOPs and weight bytes by
    ``n_intra`` AND the attention/KV terms by their head-granularity
    limits (q-head compute by min(n, n_heads); KV-cache bytes by
    min(n, n_kv_heads) — beyond the GQA limit the KV stream replicates
    across the devices sharing a kv head), while stage-local CP divides
    ONLY the attention/KV terms — every seq shard still runs the full
    matmuls for the decoded token and streams the full weights.  Below
    the GQA limit TP is therefore strictly cheaper on both axes at
    every context length; past it (n_intra > n_kv_heads, S ≳ 100k) CP
    genuinely wins on KV bytes — the regime served by the existing
    non-PP CP×TP composition, which this model also demonstrates
    (tests/test_profiling.py::TestStageLocalCpVsTp).

    The matmul/weight terms derive from the SAME canonical cost
    functions the bench rooflines use (``decode_flops_per_token`` /
    ``decode_bytes_per_token``), so the exclusion numbers cannot drift
    from the roofline model.  They include the logits matmul, which on
    a real pipeline lives only in the LAST stage — non-final stages
    have a slightly smaller matmul share and thus a cp/tp ratio
    slightly closer to (but still above) 1, so the whole-stack ratios
    reported here are an upper bound on each stage's.

    Returns per-device per-token {flops,bytes}_{tp,cp} and the cp/tp
    ratios (>1 = CP loses).
    """
    f_attn = cfg.n_layers * 2.0 * 2 * cfg.n_heads * cfg.head_dim \
        * context_len
    f_matmul = decode_flops_per_token(cfg, context_len) - f_attn
    kv_per_token = (cfg.n_layers * 2 * cfg.kv_dim
                    * (context_len + 1) * kv_bits / 8.0)
    w_per_token = decode_bytes_per_token(
        cfg, context_len, batch, weight_bits, kv_bits) / 1.01 \
        - kv_per_token
    n_q = min(n_intra, cfg.n_heads)
    n_kv = min(n_intra, cfg.n_kv_heads)
    out = {
        "flops_tp": f_matmul / n_intra + f_attn / n_q,
        "flops_cp": f_matmul + f_attn / n_intra,
        "bytes_tp": w_per_token / n_intra + kv_per_token / n_kv,
        "bytes_cp": w_per_token + kv_per_token / n_intra,
    }
    out["flops_cp_over_tp"] = out["flops_cp"] / out["flops_tp"]
    out["bytes_cp_over_tp"] = out["bytes_cp"] / out["bytes_tp"]
    return out


def roofline_decode_tps(cfg: ModelConfig, context_len: int, batch: int,
                        weight_bits: int = 16, kv_bits: int = 16,
                        device: Optional[Any] = None) -> Optional[float]:
    """Hardware ceiling on whole-chip decode tokens/sec: the min of the
    compute roofline (bf16 peak / FLOPs-per-token) and the memory
    roofline (HBM bandwidth / bytes-per-token).  A measured number above
    this is *physically impossible* — the measurement, not the machine,
    is broken.  None on a CPU."""
    peaks = chip_peaks(device)
    if peaks is None:
        return None
    compute = (peaks.bf16_tflops * 1e12
               / decode_flops_per_token(cfg, context_len))
    memory = peaks.hbm_gbps * 1e9 / decode_bytes_per_token(
        cfg, context_len, batch, weight_bits, kv_bits)
    return min(compute, memory)


def roofline_prefill_tps(cfg: ModelConfig, prompt_len: int,
                         device: Optional[Any] = None) -> Optional[float]:
    """Hardware ceiling on prefill tokens/sec: the compute roofline (bf16
    peak over FLOPs-per-token at the mean causal context prompt_len/2).
    Prefill at bench batch·seq sizes is compute-bound — every weight byte
    is amortized over thousands of tokens, so the memory leg sits far
    above this one and the compute ceiling is the binding upper bound a
    prefill tok/s claim must clear.  None on a CPU."""
    peaks = chip_peaks(device)
    if peaks is None:
        return None
    return (peaks.bf16_tflops * 1e12
            / decode_flops_per_token(cfg, prompt_len // 2))
