"""Fused weight-dequant matmul kernels (Pallas TPU) + the ``qmm`` shim.

Decode on the quantized engines is weight-streaming bound, but the
``x @ dq(w)`` use sites re-materialize dense bf16 weights whenever XLA
fails to fuse ``_unpack_nibbles``'s lane-axis concat into the matmul
operand read — paying ~4x the int4 bytes the quantization bought
(ROADMAP item 1, the 4.8%-MFU gap).  These kernels stream the PACKED
int8/int4 weight tiles HBM->VMEM and dequantize in-register inside the
K-loop, with the per-channel scale folded into the accumulator epilogue.

Layouts (all three scale layouts quantize_params emits):

  kn  (wq/wk/wv/wo, MLP gate/up/down, MoE router)
      q [K, N] int8          scale [1, N]    y = x @ (q * s)
      int4: q [K, N/2] packed split-half — byte j holds column j in its
      low nibble and column j + N/2 in its high nibble, so the kernel's
      unpack is two shifts and the lo/hi products write the [M, 2, N/2]
      output halves directly (the layout was designed for exactly this:
      quant._pack_nibbles).

  nk  (lm head / tied embedding, per-ROW scales)
      q [V, K] int8          scale [V, 1]    y = x @ (q * s)^T
      int4: q [V, K/2] packed along K — x splits into (x_lo, x_hi)
      halves and the row product is x_lo @ lo^T + x_hi @ hi^T.

  ekn (stacked experts, per-(expert, column) scales)
      q [E, K, N]            scale [E, 1, N]
      the kn kernel with a leading expert grid dimension; serves both
      stacked einsums ("bsh,ehi->bsei" with x broadcast across experts,
      "bsei,eih->bseh" with per-expert x).

Every kernel accumulates in an f32 VMEM scratch across the K grid
(``dimension_semantics`` marks K "arbitrary") and applies the scale once
at the last K step: mathematically identical to scaling the weights
first (the scale is constant over K), numerically within bf16/f32
accumulation tolerance of the dq() reference — what
tests/test_quant_matmul.py pins for every (bits x layout x shape) cell.

Dispatch: the ``qmm*`` shims take the kernel path only on a TPU backend
and use the byte-identical ``dq()`` XLA expressions everywhere else —
CPU engines with ``ModelConfig.fused_quant_matmul=True`` stay greedy
byte-identical by construction, and GSPMD-sharded consumption (which
pallas_call cannot partition) also lands on the XLA expression.  Shard-LOCAL
consumption inside shard_map stage bodies (PP×TP, weights repacked by
quant.repack_nibbles_grouped and unwrapped at the boundary) runs the
kernel on its self-contained split-half shard.  Grouped-repacked tensors
consumed GLOBALLY raise a loud ValueError (quant._reject_grouped).
Kernels themselves are validated in interpret mode on CPU
(tests/test_quant_matmul.py) and compiled for a described v5e chip at
Llama-3-8B widths (tests/test_aot_compile.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# models/quant.py is imported LAZILY (inside _q()): models/__init__ pulls
# in llama.py which imports this module's shims, so a module-level import
# here would close an import cycle through the two package __init__s.
# ops/ stays models-free at import time, like every other ops module.


def _q():
    from k8s_llm_rca_tpu.models import quant
    return quant


# block-size targets: K tiles deep (weight streaming amortizes the
# revisit of x), M/N moderate so the f32 scratch stays small.  _blk
# clamps each to the largest divisor of the actual dim, so tiny test
# shapes run single-block while 8B shapes tile properly.
_BM, _BN, _BK = 256, 256, 512


def _interp(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _sem(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _blk(dim: int, target: int) -> int:
    b = min(dim, target)
    while dim % b:
        b -= 1
    return b


# Mosaic has no int8 vector shift ("failed to legalize 'arith.shli'" on
# vector<..xi8>), so the nibbles are split on int32 lanes, the same way
# ops/paged_attention.py unpacks int4 KV pages.


def _lo_nibbles(p):
    # (p << 28) >> 28 sign-extends the low nibble without a select — the
    # arithmetic-shift twin of quant._unpack_nibbles's where()
    p = p.astype(jnp.int32)
    return jnp.right_shift(jnp.left_shift(p, 28), 28)


def _hi_nibbles(p):
    return jnp.right_shift(p.astype(jnp.int32), 4)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _kn8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, q_ref[...].astype(x.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = (acc_ref[...]
                      * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _kn4_kernel(x_ref, q_ref, s_ref, o_ref, lo_ref, hi_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    x = x_ref[...]
    p = q_ref[...]
    lo_ref[...] += jnp.dot(x, _lo_nibbles(p).astype(x.dtype),
                           preferred_element_type=jnp.float32)
    hi_ref[...] += jnp.dot(x, _hi_nibbles(p).astype(x.dtype),
                           preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        s = s_ref[...].astype(jnp.float32)            # [2, bnp]
        o_ref[:, 0, :] = (lo_ref[...] * s[0:1]).astype(o_ref.dtype)
        o_ref[:, 1, :] = (hi_ref[...] * s[1:2]).astype(o_ref.dtype)


def _nk8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        x, q_ref[...].astype(x.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = (acc_ref[...]
                      * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _nk4_kernel(xlo_ref, xhi_ref, q_ref, s_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = q_ref[...]
    dims = (((1,), (1,)), ((), ()))
    xlo = xlo_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        xlo, _lo_nibbles(p).astype(xlo.dtype), dims,
        preferred_element_type=jnp.float32)
    xhi = xhi_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        xhi, _hi_nibbles(p).astype(xhi.dtype), dims,
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = (acc_ref[...]
                      * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _ekn8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]
    acc_ref[...] += jnp.dot(x, q_ref[0].astype(x.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[0] = (acc_ref[...]
                    * s_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


def _ekn4_kernel(x_ref, q_ref, s_ref, o_ref, lo_ref, hi_ref, *, nk):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    x = x_ref[0]
    p = q_ref[0]
    lo_ref[...] += jnp.dot(x, _lo_nibbles(p).astype(x.dtype),
                           preferred_element_type=jnp.float32)
    hi_ref[...] += jnp.dot(x, _hi_nibbles(p).astype(x.dtype),
                           preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        s = s_ref[0].astype(jnp.float32)              # [2, bnp]
        o_ref[0, :, 0, :] = (lo_ref[...] * s[0:1]).astype(o_ref.dtype)
        o_ref[0, :, 1, :] = (hi_ref[...] * s[1:2]).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers (one per storage layout)
# ---------------------------------------------------------------------------


def _matmul_kn(x2, w, interpret: bool):
    m, kdim = x2.shape
    bm, bk = _blk(m, _BM), _blk(kdim, _BK)
    if isinstance(w, _q().QuantTensor):
        n = w.q.shape[1]
        bn = _blk(n, _BN)
        grid = (m // bm, n // bn, kdim // bk)
        return pl.pallas_call(
            functools.partial(_kn8_kernel, nk=grid[2]),
            name="quant_matmul_kn8",
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
                pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
                pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
            out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
            compiler_params=_sem("parallel", "parallel", "arbitrary"),
        )(x2, w.q, w.scale.reshape(1, n))
    n_packed = w.q.shape[1]                           # logical N / 2
    bnp = _blk(n_packed, _BN)
    grid = (m // bm, n_packed // bnp, kdim // bk)
    out = pl.pallas_call(
        functools.partial(_kn4_kernel, nk=grid[2]),
        name="quant_matmul_kn4",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bnp), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((2, bnp), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, 2, bnp),
                               lambda mi, ni, ki: (mi, 0, ni)),
        out_shape=jax.ShapeDtypeStruct((m, 2, n_packed), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bnp), jnp.float32),
                        pltpu.VMEM((bm, bnp), jnp.float32)],
        interpret=interpret,
        compiler_params=_sem("parallel", "parallel", "arbitrary"),
    )(x2, w.q, w.scale.reshape(2, n_packed))
    # [M, 2, N/2] -> [M, N]: row-major flatten restores the split-half
    # column order (lo block = columns [0, N/2), hi = [N/2, N))
    return out.reshape(m, 2 * n_packed)


def _matmul_nk(x2, w, interpret: bool):
    m, kdim = x2.shape
    n = w.q.shape[0]
    bm, bn = _blk(m, _BM), _blk(n, _BN)
    scale = w.scale.reshape(1, n)
    if isinstance(w, _q().QuantTensor):
        bk = _blk(kdim, _BK)
        grid = (m // bm, n // bn, kdim // bk)
        return pl.pallas_call(
            functools.partial(_nk8_kernel, nk=grid[2]),
            name="quant_matmul_nk8",
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
                pl.BlockSpec((bn, bk), lambda mi, ni, ki: (ni, ki)),
                pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
            out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
            compiler_params=_sem("parallel", "parallel", "arbitrary"),
        )(x2, w.q, scale)
    k_packed = w.q.shape[1]                           # K / 2
    bkp = _blk(k_packed, _BK)
    grid = (m // bm, n // bn, k_packed // bkp)
    # the packed axis pairs (k, k + K/2): feed the x halves as separate
    # operands so each streams block-aligned with the packed tiles
    x_lo, x_hi = x2[:, :k_packed], x2[:, k_packed:]
    return pl.pallas_call(
        functools.partial(_nk4_kernel, nk=grid[2]),
        name="quant_matmul_nk4",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkp), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bm, bkp), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bn, bkp), lambda mi, ni, ki: (ni, ki)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=_sem("parallel", "parallel", "arbitrary"),
    )(x_lo, x_hi, w.q, scale)


def _matmul_ekn(xe, w, interpret: bool):
    e, m, kdim = xe.shape
    bm, bk = _blk(m, _BM), _blk(kdim, _BK)
    if isinstance(w, _q().QuantTensor):
        n = w.q.shape[2]
        bn = _blk(n, _BN)
        grid = (e, m // bm, n // bn, kdim // bk)
        return pl.pallas_call(
            functools.partial(_ekn8_kernel, nk=grid[3]),
            name="quant_matmul_ekn8",
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bk),
                             lambda ei, mi, ni, ki: (ei, mi, ki)),
                pl.BlockSpec((1, bk, bn),
                             lambda ei, mi, ni, ki: (ei, ki, ni)),
                pl.BlockSpec((1, 1, bn),
                             lambda ei, mi, ni, ki: (ei, 0, ni)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda ei, mi, ni, ki: (ei, mi, ni)),
            out_shape=jax.ShapeDtypeStruct((e, m, n), xe.dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
            compiler_params=_sem("parallel", "parallel", "parallel",
                                 "arbitrary"),
        )(xe, w.q, w.scale.reshape(e, 1, n))
    n_packed = w.q.shape[2]
    bnp = _blk(n_packed, _BN)
    grid = (e, m // bm, n_packed // bnp, kdim // bk)
    out = pl.pallas_call(
        functools.partial(_ekn4_kernel, nk=grid[3]),
        name="quant_matmul_ekn4",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk),
                         lambda ei, mi, ni, ki: (ei, mi, ki)),
            pl.BlockSpec((1, bk, bnp),
                         lambda ei, mi, ni, ki: (ei, ki, ni)),
            pl.BlockSpec((1, 2, bnp),
                         lambda ei, mi, ni, ki: (ei, 0, ni)),
        ],
        out_specs=pl.BlockSpec((1, bm, 2, bnp),
                               lambda ei, mi, ni, ki: (ei, mi, 0, ni)),
        out_shape=jax.ShapeDtypeStruct((e, m, 2, n_packed), xe.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bnp), jnp.float32),
                        pltpu.VMEM((bm, bnp), jnp.float32)],
        interpret=interpret,
        compiler_params=_sem("parallel", "parallel", "parallel",
                                 "arbitrary"),
    )(xe, w.q, w.scale.reshape(e, 2, n_packed))
    return out.reshape(e, m, 2 * n_packed)


# ---------------------------------------------------------------------------
# public kernel entry points (always take the kernel; tests drive these
# in interpret mode on CPU)
# ---------------------------------------------------------------------------


def _require_quant(w, who: str):
    quant = _q()
    quant._reject_grouped(w, f"{who} over")
    if not isinstance(w, (quant.QuantTensor, quant.QuantTensor4)):
        raise ValueError(
            f"{who} needs a QuantTensor/QuantTensor4 weight, got "
            f"{type(w).__name__} (plain arrays take the XLA matmul — "
            f"use the qmm shim for transparent dispatch)")


def quant_matmul(x: jnp.ndarray, w, *,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """``x @ dq(w)`` through the fused kn kernel.

    ``w``: 2-D QuantTensor/QuantTensor4 ``[K, N]`` with per-output-COLUMN
    scales (quantize axis=-1); ``x`` [..., K].  Per-row tables (lm head /
    embedding) go through ``quant_matmul_head``; stacked experts through
    ``quant_matmul_experts``.  ``interpret=None`` auto-selects interpret
    mode off-TPU (the ops/paged_attention.py convention)."""
    _require_quant(w, "quant_matmul")
    if w.ndim != 2:
        raise ValueError(
            f"quant_matmul takes 2-D weights, got {w.ndim}-D "
            f"{w.shape} (stacked experts: quant_matmul_experts)")
    kdim, n = w.shape
    if w.scale.shape != (1, n):
        raise ValueError(
            f"quant_matmul needs per-column scales [1, {n}], got "
            f"{w.scale.shape} for weight {w.shape} (per-row tables: "
            f"quant_matmul_head)")
    if x.shape[-1] != kdim:
        raise ValueError(f"shape mismatch: x {x.shape} @ w {w.shape}")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, kdim)
    out = _matmul_kn(x2, w, _interp(interpret))
    return out.reshape(*lead, n)


def quant_matmul_head(x: jnp.ndarray, w, *,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """``einsum("...h,vh->...v", x, dq(w))`` through the fused nk kernel:
    ``w`` [V, K] with per-ROW scales [V, 1] (quantize axis=0 — the lm
    head / tied embedding layout).  int4 packs along K, so the kernel
    splits x into split-half K blocks instead of the output columns."""
    _require_quant(w, "quant_matmul_head")
    if w.ndim != 2:
        raise ValueError(
            f"quant_matmul_head takes 2-D tables, got {w.ndim}-D {w.shape}")
    v, kdim = w.shape
    if w.scale.shape != (v, 1):
        raise ValueError(
            f"quant_matmul_head needs per-row scales [{v}, 1], got "
            f"{w.scale.shape} for table {w.shape} (per-column weights: "
            f"quant_matmul)")
    if x.shape[-1] != kdim:
        raise ValueError(f"shape mismatch: x {x.shape} @ w^T {w.shape}")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, kdim)
    out = _matmul_nk(x2, w, _interp(interpret))
    return out.reshape(*lead, v)


def quant_matmul_experts(x: jnp.ndarray, w, *,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """The stacked-expert einsums through the fused ekn kernel.

    ``w`` [E, K, N] with per-(expert, column) scales [E, 1, N] (quantize
    axis=(0, -1)).  ``x`` 3-D [B, S, K] computes ``"bsh,ehi->bsei"``
    (every token through every expert — the dense soft-dispatch form of
    ``llama._moe_mlp``, which the fused flag keeps at every call size);
    4-D [B, S, E, K] computes ``"bsei,eih->bseh"`` (per-expert rows)."""
    _require_quant(w, "quant_matmul_experts")
    if w.ndim != 3:
        raise ValueError(
            f"quant_matmul_experts takes stacked [E, K, N] weights, got "
            f"{w.ndim}-D {w.shape} (2-D weights: quant_matmul)")
    e, kdim, n = w.shape
    if w.scale.shape != (e, 1, n):
        raise ValueError(
            f"quant_matmul_experts needs per-(expert, column) scales "
            f"[{e}, 1, {n}], got {w.scale.shape} for weight {w.shape}")
    interpret = _interp(interpret)
    if x.ndim == 3:
        b, s, xk = x.shape
        if xk != kdim:
            raise ValueError(f"shape mismatch: x {x.shape} @ w {w.shape}")
        xe = jnp.broadcast_to(x.reshape(1, b * s, kdim), (e, b * s, kdim))
        out = _matmul_ekn(xe, w, interpret)           # [E, B*S, N]
        return out.reshape(e, b, s, n).transpose(1, 2, 0, 3)
    if x.ndim == 4:
        b, s, xe_, xk = x.shape
        if xe_ != e or xk != kdim:
            raise ValueError(f"shape mismatch: x {x.shape} @ w {w.shape}")
        xe = x.transpose(2, 0, 1, 3).reshape(e, b * s, kdim)
        out = _matmul_ekn(xe, w, interpret)           # [E, B*S, N]
        return out.reshape(e, b, s, n).transpose(1, 2, 0, 3)
    raise ValueError(
        f"quant_matmul_experts takes 3-D [B,S,K] or 4-D [B,S,E,K] "
        f"activations, got {x.shape}")


# ---------------------------------------------------------------------------
# dispatch shims — the ModelConfig.fused_quant_matmul use-site surface
# ---------------------------------------------------------------------------


def _kernel_path(w) -> bool:
    """Run the Pallas kernel only for quantized weights on a real TPU
    backend.  Everything else — plain arrays, CPU/virtual-device hosts
    (where interpret mode would be pure overhead), GSPMD-jitted sharded
    params (pallas_call has no SPMD partitioning rule) — falls back to
    the byte-identical dq() XLA expression.  Grouped-repacked weights
    never reach here: the shims reject them first (a global qmm over a
    shard-local layout), and shard_map stage bodies unwrap them to plain
    QuantTensor4 before their GEMMs."""
    quant = _q()
    return (isinstance(w, (quant.QuantTensor, quant.QuantTensor4))
            and jax.default_backend() == "tpu")


def qmm(x: jnp.ndarray, w) -> jnp.ndarray:
    """Dispatch shim for every ``x @ dq(w)`` GEMM site."""
    _q()._reject_grouped(w, "qmm (global fused matmul) over")
    if _kernel_path(w):
        return quant_matmul(x, w, interpret=False)
    return x @ _q().dq(w)


def qmm_head(x: jnp.ndarray, w) -> jnp.ndarray:
    """Dispatch shim for the lm-head ``einsum("bsh,vh->bsv")`` site."""
    _q()._reject_grouped(w, "qmm_head (global fused matmul) over")
    if _kernel_path(w):
        return quant_matmul_head(x, w, interpret=False)
    return jnp.einsum("bsh,vh->bsv", x, _q().dq(w))


def qmm_experts(x: jnp.ndarray, w) -> jnp.ndarray:
    """Dispatch shim for the stacked-expert einsum sites (3-D x:
    ``"bsh,ehi->bsei"``; 4-D x: ``"bsei,eih->bseh"``)."""
    _q()._reject_grouped(w, "qmm_experts (global fused matmul) over")
    if _kernel_path(w):
        return quant_matmul_experts(x, w, interpret=False)
    if x.ndim == 3:
        return jnp.einsum("bsh,ehi->bsei", x, _q().dq(w))
    return jnp.einsum("bsei,eih->bseh", x, _q().dq(w))
