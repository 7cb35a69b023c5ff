"""Weight-dequant matmul kernels (Pallas TPU) and their dispatch shims.

``einsum(x, dq(w))`` makes XLA unpack (and, for int4, sign-extend and
concatenate) every weight element in HBM before the matmul reads it.  These
kernels stream the PACKED int8/int4 weight tiles HBM -> VMEM, unpack them on
the way to the MXU and apply the per-channel scale once, to the float32
output tile.

What the chip has shown (one TPU v5e; PERF.md section 6, PR 35).  Only the
stacked int4 expert kernels have been timed, and only they run in a
benchmark cell: one layer's expert MLP at Mixtral-8x7B widths (8 experts,
4096 x 14336, 705 MB packed) at 32 rows takes 1.16 ms through
``quant_swiglu_experts`` (gate and up in one call 0.76 ms, down 0.40 ms:
three quarters of the HBM bandwidth) where the XLA dense form takes 5.19 ms,
most of it the unpack of all eight experts in HBM.  The 2-D kernels
(``quant_matmul``, ``quant_matmul_head``) and the int8 variants compile for a
described v5e (tests/test_aot_compile.py) and have never been timed; they
stay behind ``ModelConfig.fused_quant_matmul``, which no benchmark
configuration sets (the dense model's weight reads are at 95% of the
bandwidth bound without them).

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
      stacked einsums ("bsh,ehi->bsei", where every expert reads the same
      rows: the index map ignores the expert, nothing is broadcast;
      "bsei,eih->bseh" with per-expert x).  int4 has a second kernel in
      which gate and up share a call and the SwiGLU product is taken on
      the float32 sums (``quant_swiglu_experts``).

Every kernel accumulates in float32 and applies the scale once, after the
last K step: mathematically identical to scaling the weights first (the
scale is constant over K), with one rounding fewer than ``dq``'s bf16
``q * scale``, within bf16/f32 accumulation tolerance of the dq() reference
— what tests/test_quant_matmul.py pins for every (bits x layout x shape)
cell.

Dispatch: the ``qmm*`` shims take the kernel path only on a TPU backend
and use the byte-identical ``dq()`` XLA expressions everywhere else, so CPU
engines stay greedy byte-identical by construction.  ``pallas_call`` has no
SPMD partitioning rule: the 2-D shims are reached only through
``ModelConfig.fused_quant_matmul``; the expert shim (``qmm_swiglu_experts``)
only where ``llama._experts`` is told ``expert_kernel`` by an engine with no
mesh and weights whole on one device, and chooses the form from the call's
shape (``llama.moe_fused``).  Shard-LOCAL consumption inside shard_map stage
bodies (PP×TP, weights repacked by quant.repack_nibbles_grouped and
unwrapped at the boundary) runs the kernel on its self-contained split-half
shard.  Grouped-repacked tensors consumed GLOBALLY raise a loud ValueError
(quant._reject_grouped).  Kernels are validated in interpret mode on CPU
(tests/test_quant_matmul.py, tests/test_moe_grouped.py) and compiled for a
described v5e (tests/test_aot_compile.py: the 2-D kernels at Llama-3-8B
widths, the expert kernels inside the decode scan at Mixtral-8x7B widths).
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


# Block-size targets of the 2-D and int8 kernels, never timed on a chip
# (written on a CPU, PR 7): at 32 rows they make grid steps of 128 KB, which
# a step's fixed cost outweighs.  _blk clamps each to the largest divisor of
# the actual dim, so tiny test shapes run single-block.
_BM, _BN, _BK = 256, 256, 512

# The stacked int4 kernels' tiles: up to _EKN4_BK rows of K and
# _EKN4_TILE_BYTES of packed weight a tile (so as many packed columns as fit),
# unpacked _EKN4_SUB rows at a time inside the kernel; _EKN4_VMEM_BYTES is
# what a call may take of the v5e's 128 MiB (two weights' tiles double
# buffered are 16 MiB, the default scoped limit).  Set from 32 rows of
# Mixtral-8x7B's experts on one TPU v5e (my chip run, PR 35; ms a call,
# tiles as (K rows, packed columns)): gate and up in one call (K = 4096,
# 7168 packed columns) 0.810 at (4096, 256), 0.767 at (4096, 512), **0.763
# at (4096, 1024)**, 0.774 at (2048, 1024), 0.810 at (1024, 1792), 0.824 at
# (512, 3584): a tile wants the whole K, then width; down (K = 14336, 2048
# packed columns) 0.462 at (3584, 512), **0.400 at (3584, 1024)**, 0.393 at
# (2048, 2048), 0.406 at (1024, 2048), 0.447 at (512, 2048), 0.431 at
# (14336, 128).  The sub-tile hardly matters (256, 512, 1024, 2048 rows:
# 0.765-0.771).  The parent's 256 x 512 tiles were never run on the chip.
# The floor is the packed bytes: 0.574 and 0.287 ms at 819 GB/s.
_EKN4_BK = 4096
_EKN4_TILE_BYTES = 4 << 20
_EKN4_SUB = 512
_EKN4_VMEM_BYTES = 64 << 20


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


def _ekn4_partial(x_ref, q_ref, sub):
    """One grid step's share of ``x @ unpack(q)``: (lo, hi) float32
    ``[bm, bnp]`` for the columns in the low and the high nibbles.  The
    packed tile is unpacked ``sub`` rows of K at a time, so the int32 view
    (4 B a packed byte, once for each nibble) stays a small temporary
    beside a tile of a megabyte or more."""
    lo = hi = None
    for k0 in range(0, q_ref.shape[0], sub):
        x = x_ref[:, k0:k0 + sub]
        p = q_ref[k0:k0 + sub, :]
        dlo = jnp.dot(x, _lo_nibbles(p).astype(x.dtype),
                      preferred_element_type=jnp.float32)
        dhi = jnp.dot(x, _hi_nibbles(p).astype(x.dtype),
                      preferred_element_type=jnp.float32)
        lo = dlo if lo is None else lo + dlo
        hi = dhi if hi is None else hi + dhi
    return lo, hi


def _over_k(k, nk, partials, acc_refs, finish):
    """Sum each grid step's ``partials`` over the K axis of the grid in
    the float32 scratch ``acc_refs`` and call ``finish(*sums)`` at the
    last step; a grid with one K step (the whole K in a tile) never
    touches the scratch."""
    if nk == 1:
        finish(*partials)
        return

    @pl.when(k == 0)
    def _():
        for ref, part in zip(acc_refs, partials):
            ref[...] = part

    @pl.when(k > 0)
    def _():
        for ref, part in zip(acc_refs, partials):
            ref[...] += part

    @pl.when(k == nk - 1)
    def _():
        finish(*(ref[...] for ref in acc_refs))


def _ekn4_kernel(x_ref, q_ref, s_ref, o_ref, lo_ref, hi_ref, *, nk, sub):
    def finish(lo, hi):
        s = s_ref[...].astype(jnp.float32)            # [2, bnp]
        o_ref[:, 0, :] = (lo * s[0:1]).astype(o_ref.dtype)
        o_ref[:, 1, :] = (hi * s[1:2]).astype(o_ref.dtype)

    _over_k(pl.program_id(3), nk, _ekn4_partial(x_ref, q_ref, sub),
            (lo_ref, hi_ref), finish)


def _ekn4_swiglu_kernel(x_ref, qg_ref, qu_ref, sg_ref, su_ref, o_ref,
                        *acc_refs, nk, sub):
    """``silu(x @ gate) * (x @ up)`` for one expert's column tile: the two
    weights meet the same x tile, and the product is taken on the float32
    sums, after the scales."""
    def finish(glo, ghi, ulo, uhi):
        sg = sg_ref[...].astype(jnp.float32)          # [2, bnp]
        su = su_ref[...].astype(jnp.float32)
        o_ref[:, 0, :] = (jax.nn.silu(glo * sg[0:1])
                          * (ulo * su[0:1])).astype(o_ref.dtype)
        o_ref[:, 1, :] = (jax.nn.silu(ghi * sg[1:2])
                          * (uhi * su[1:2])).astype(o_ref.dtype)

    _over_k(pl.program_id(3), nk,
            _ekn4_partial(x_ref, qg_ref, sub)
            + _ekn4_partial(x_ref, qu_ref, sub), acc_refs, finish)


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


def _ekn4_tiles(m: int, kdim: int, n_packed: int):
    """(bm, bk, bnp, sub) of the stacked int4 kernels, from the shapes.  Up
    to ``_BM`` rows are one tile, whatever their number; more are cut into
    equal tiles of a multiple of 16 rows (a bf16 tile's sublanes), the last
    one padded (``_pad_rows``)."""
    n_tiles = pl.cdiv(m, _BM)
    bm = m if n_tiles == 1 else pl.cdiv(pl.cdiv(m, n_tiles), 16) * 16
    bk = _blk(kdim, _EKN4_BK)
    bnp = _blk(n_packed, max(128, _EKN4_TILE_BYTES // bk))
    return bm, bk, bnp, _blk(bk, _EKN4_SUB)


def _pad_rows(x, bm: int):
    """``x`` ``[..., M, K]`` with zero rows up to a multiple of ``bm``."""
    pad = -x.shape[-2] % bm
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])


def _ekn4_call(kernel, name: str, x, *ws, interpret: bool):
    """One stacked int4 kernel over ``x`` and the stacked weights ``ws`` of
    one shape ``[E, K, N]`` -> ``[E, M, N]``.  ``x`` is ``[M, K]`` (every
    expert reads the same rows: the index map ignores the expert, nothing
    is broadcast in HBM) or ``[E, M, K]``; each weight brings its packed
    tiles, its scales and two float32 accumulators (low and high
    nibbles)."""
    e, kdim, n_packed = ws[0].q.shape
    m = x.shape[-2]
    bm, bk, bnp, sub = _ekn4_tiles(m, kdim, n_packed)
    x = _pad_rows(x, bm)
    grid = (e, x.shape[-2] // bm, n_packed // bnp, kdim // bk)
    if x.ndim == 2:
        x_spec = pl.BlockSpec((bm, bk), lambda ei, mi, ni, ki: (mi, ki))
    else:
        x_spec = pl.BlockSpec((None, bm, bk),
                              lambda ei, mi, ni, ki: (ei, mi, ki))
    q_spec = pl.BlockSpec((None, bk, bnp),
                          lambda ei, mi, ni, ki: (ei, ki, ni))
    s_spec = pl.BlockSpec((None, 2, bnp), lambda ei, mi, ni, ki: (ei, 0, ni))
    out = pl.pallas_call(
        functools.partial(kernel, nk=grid[3], sub=sub),
        name=name,
        grid=grid,
        in_specs=[x_spec] + [q_spec] * len(ws) + [s_spec] * len(ws),
        out_specs=pl.BlockSpec((None, bm, 2, bnp),
                               lambda ei, mi, ni, ki: (ei, mi, 0, ni)),
        out_shape=jax.ShapeDtypeStruct((e, x.shape[-2], 2, n_packed),
                                       x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bnp), jnp.float32)] * 2 * len(ws),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_EKN4_VMEM_BYTES),
    )(x, *(w.q for w in ws),
      *(w.scale.reshape(e, 2, n_packed) for w in ws))
    # [E, M, 2, N/2] -> [E, M, N]: the row-major flatten restores the
    # split-half column order
    return out.reshape(e, -1, 2 * n_packed)[:, :m]


def _matmul_ekn(xe, w, interpret: bool):
    """``xe`` ``[E, M, K]`` (or ``[M, K]``, the same rows for every expert)
    times stacked ``w`` ``[E, K, N]`` -> ``[E, M, N]``."""
    if isinstance(w, _q().QuantTensor4):
        return _ekn4_call(_ekn4_kernel, "quant_matmul_ekn4", xe, w,
                          interpret=interpret)
    if xe.ndim == 2:
        xe = jnp.broadcast_to(xe, (w.q.shape[0], *xe.shape))
    e, m, kdim = xe.shape
    bm, bk = _blk(m, _BM), _blk(kdim, _BK)
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
    (every token through every expert — one projection of the dense
    soft-dispatch form of ``llama._experts``); 4-D [B, S, E, K] computes
    ``"bsei,eih->bseh"`` (per-expert rows).  The model's int4 experts go
    through ``quant_swiglu_experts``, which shares a call between gate and
    up."""
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
        out = _matmul_ekn(x.reshape(b * s, kdim), w, interpret)
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


def quant_swiglu_experts(x: jnp.ndarray, w_gate, w_up, w_down, *,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Every stacked int4 expert's SwiGLU MLP on every row, from the
    packed weights: ``x`` ``[B, S, H]`` -> ``[B, S, E, H]``, the
    ``per_expert`` of ``llama._experts``' dense form
    (``einsum("bsei,eih->bseh", silu(x @ gate) * (x @ up), down)``).  Two
    kernel calls: gate and up share one (``quant_matmul_ekn4_swiglu``),
    and its ``[E, B*S, I]`` result feeds down (``quant_matmul_ekn4``) as
    it lies."""
    quant = _q()
    for name, w in (("w_gate", w_gate), ("w_up", w_up), ("w_down", w_down)):
        _require_quant(w, "quant_swiglu_experts")
        if not isinstance(w, quant.QuantTensor4) or w.ndim != 3:
            raise ValueError(
                f"quant_swiglu_experts takes stacked int4 [E, K, N] "
                f"weights, got {type(w).__name__} {w.shape} for {name}")
    e, h, i = w_gate.shape
    b, s, xk = x.shape
    if xk != h or w_up.shape != (e, h, i) or w_down.shape != (e, i, h):
        raise ValueError(
            f"shape mismatch: x {x.shape}, gate {w_gate.shape}, up "
            f"{w_up.shape}, down {w_down.shape}")
    interpret = _interp(interpret)
    hid = _ekn4_call(_ekn4_swiglu_kernel, "quant_matmul_ekn4_swiglu",
                     x.reshape(b * s, h), w_gate, w_up, interpret=interpret)
    out = _matmul_ekn(hid, w_down, interpret)         # [E, B*S, H]
    return out.reshape(e, b, s, h).transpose(1, 2, 0, 3)


# ---------------------------------------------------------------------------
# dispatch shims — the use-site surface (2-D sites: behind
# ModelConfig.fused_quant_matmul; experts: llama.moe_fused)
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


def qmm_swiglu_experts(x: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """Dispatch shim for the fused form of ``llama._experts``: x [B, S, H]
    and three stacked expert weights -> ``per_expert`` [B, S, E, H].  On a
    TPU, int4 weights take the two packed kernels; everything else is the
    dense form's own expression, projection by projection."""
    quant = _q()
    if all(_kernel_path(w) and isinstance(w, quant.QuantTensor4)
           for w in (w_gate, w_up, w_down)):
        return quant_swiglu_experts(x, w_gate, w_up, w_down, interpret=False)
    gate = jax.nn.silu(qmm_experts(x, w_gate))
    return qmm_experts(gate * qmm_experts(x, w_up), w_down)


def qmm_experts(x: jnp.ndarray, w) -> jnp.ndarray:
    """Dispatch shim for the stacked-expert einsum sites (3-D x:
    ``"bsh,ehi->bsei"``; 4-D x: ``"bsei,eih->bseh"``)."""
    _q()._reject_grouped(w, "qmm_experts (global fused matmul) over")
    if _kernel_path(w):
        return quant_matmul_experts(x, w, interpret=False)
    if x.ndim == 3:
        return jnp.einsum("bsh,ehi->bsei", x, _q().dq(w))
    return jnp.einsum("bsei,eih->bseh", x, _q().dq(w))
