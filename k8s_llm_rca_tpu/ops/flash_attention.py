"""Pallas TPU flash-attention kernel (prefill path).

Tiled online-softmax attention: the [S_q, S_k] score matrix is never
materialised in HBM.  Grid is (batch, q_head, q_block, k_block) with the
k_block axis innermost so the running max / denominator / accumulator for
one q tile stay resident in VMEM scratch across the whole k sweep.  GQA is
expressed in the BlockSpec index map (q head h reads kv head h // n_rep) —
no repeat_kv materialisation.

Numerics match ops.attention.causal_attention (the pure-XLA reference path
used on CPU and in tests); see tests/test_kernels.py.  The reference
repository has no kernels at all — its attention runs server-side behind
the OpenAI API (reference common/openai_generic_assistant.py:45-51) — so
this file is the "native kernel" layer SURVEY §2.2 requires the TPU build
to add.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128          # VPU lane width: scratch rows are padded to this


def _flash_kernel(
    seq_lens_ref,       # SMEM [B]  (valid kv length per batch row)
    q_off_ref,          # SMEM [B]  (absolute position of q block row 0)
    q_ref,              # VMEM [1, 1, block_q, d]   (head-major layout)
    k_ref,              # VMEM [1, 1, block_k, d]
    v_ref,              # VMEM [1, 1, block_k, d_v]
    o_ref,              # VMEM [1, 1, block_q, d_v]
    acc_ref,            # VMEM scratch [block_q, d_v] f32
    m_ref,              # VMEM scratch [block_q, _LANES] f32
    l_ref,              # VMEM scratch [block_q, _LANES] f32
    *,
    block_q: int,
    block_k: int,
    window: int = 0,
    lean: bool = False,
):
    bi = pl.program_id(0)
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _step(qi=None):
        if lean:
            # operands as they come (bfloat16: one pass of the MXU),
            # float32 accumulation, the scale on the product
            q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        else:
            q = q_ref[0, 0].astype(jnp.float32)        # [bq, d]
            k = k_ref[0, 0].astype(jnp.float32)        # [bk, d]
            v = v_ref[0, 0].astype(jnp.float32)        # [bk, d]

        d = q.shape[-1]
        scale = jax.lax.rsqrt(jnp.float32(d))
        s = jax.lax.dot_general(
            q if lean else q * scale, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [bq, bk]
        if lean:
            s = s * scale

        if qi is None:
            qi = pl.program_id(2)
        q_pos = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                 + qi * block_q + q_off_ref[bi])
        # a band's k axis walks the key blocks of the q block's window
        # only, from ``_band_back`` blocks before its own; one before the
        # sequence's start is all masked
        kb = qi - _band_back(block_k, window) + ki if window else ki
        k_pos = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                 + kb * block_k)
        mask = (q_pos >= k_pos) & (k_pos < seq_lens_ref[bi])
        if window:
            mask &= (q_pos - k_pos < window) & (k_pos >= 0)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]                         # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)     # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # fully-masked rows keep m == NEG_INF; shift so exp() stays finite
        p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
        correction = jnp.exp(
            m_prev - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))

        l_prev = l_ref[:, 0:1]
        l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if lean:
        # a key block no query of this q block sees (wholly in its future,
        # before the sequence's start, or past the row's length) and a q
        # block wholly of padding do nothing: the accumulators stand
        qi = pl.program_id(2)
        q_first = qi * block_q + q_off_ref[bi]
        kb = qi - _band_back(block_k, window) + ki if window else ki
        k_first = kb * block_k
        n = seq_lens_ref[bi]
        pl.when((k_first <= q_first + block_q - 1) & (k_first >= 0)
                & (k_first < n) & (q_first < n))(
                    functools.partial(_step, qi))
    else:
        _step()

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        safe_l = jnp.where(l == 0.0, 1.0, l)           # padded q rows
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _band_back(block: int, window: int) -> int:
    """Key blocks of a band that lie before a q block's own (q and key
    blocks of one size): how far back its first position's window
    reaches.  One for a window of up to ``block + 1``."""
    return -(-(window - 1) // block)


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "interpret", "window", "lean"),
)
def flash_attention(
    q: jnp.ndarray,          # [B, S_q, n_heads, d]
    k: jnp.ndarray,          # [B, S_k, n_kv, d]
    v: jnp.ndarray,          # [B, S_k, n_kv, d_v]
    seq_lens: jnp.ndarray,   # [B] valid kv lengths
    q_offset: jnp.ndarray | None = None,   # [B] absolute pos of q[:, 0]
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    window: int = 0,
    lean: bool = False,
) -> jnp.ndarray:
    """Drop-in for ops.attention.causal_attention on TPU.

    ``interpret=None`` auto-selects the Pallas interpreter off-TPU so the
    same code path is exercised hermetically in CPU tests.

    The values' width is their own (``v.shape[-1]``, the output's): latent
    attention's heads have keys of 192 and values of 128.  The scale is
    the query's, ``1 / sqrt(d)``.

    ``window`` > 0 is a band (a sliding layer: position ``i`` sees the
    ``window`` positions up to itself): the grid's k axis covers only the
    key blocks a q block's window lies across (``_band_back`` + 1: two of
    128 for a window of 128), so the key blocks outside the band are
    SKIPPED, not masked, and a call's time goes with ``S x window`` and
    not ``S x S``.  The call carries its own name,
    ``flash_attention_window``.  A band starts at position 0 of the
    sequence: no ``q_offset``.

    ``lean`` leaves out what a call need not do, and is what makes blocks
    larger than 128 worth their VMEM: the two matmuls take q, k and v as
    they come (bfloat16 operands are one pass of the MXU where float32
    ones are several; accumulation stays float32 and the scale goes on
    the product), and a grid step whose key block no query of its q block
    sees (wholly in their future or past the row's length), or whose q
    block is all padding, computes nothing; a fresh sequence does not
    fetch those key blocks either.  Its output differs from the plain
    call's by the rounding of the probabilities to the values' dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    b, s_q, n_heads, d = q.shape
    s_k = k.shape[1]
    n_kv = k.shape[2]
    n_rep = n_heads // n_kv
    d_v = v.shape[-1]

    block_q = min(block_q, max(8, s_q))
    block_k = min(block_k, max(8, s_k))
    if window and (q_offset is not None or s_q != s_k
                   or block_q != block_k):
        raise ValueError(
            "a banded flash_attention call is a whole fresh sequence "
            "(no q_offset, as many keys as queries) in q and key blocks "
            f"of one size: got s_q={s_q}, s_k={s_k}, block_q={block_q}, "
            f"block_k={block_k}")
    fresh = q_offset is None
    if fresh:
        q_offset = jnp.zeros((b,), jnp.int32)
    # head-major layout [B, H, S, d]: Mosaic requires the last two block
    # dims to be (8k, 128k) multiples or the full array dim — (block_q, d)
    # qualifies (d is the full dim), whereas the natural [B, S, H, d]
    # blocks (.., block_q, 1, d) do not (the head axis block of 1).
    qp = _pad_to(q.transpose(0, 2, 1, 3), 2, block_q)   # [B, H, Sq', d]
    kp = _pad_to(k.transpose(0, 2, 1, 3), 2, block_k)   # [B, Kv, Sk', d]
    vp = _pad_to(v.transpose(0, 2, 1, 3), 2, block_k)
    n_q_blocks = qp.shape[2] // block_q
    n_k_blocks = kp.shape[2] // block_k

    if window:
        # the k axis counts from the band's first block, held at 0 where
        # the band starts before the sequence does (the kernel masks it)
        back = _band_back(block_k, window)
        n_k_blocks = back + 1
        kernel = functools.partial(_flash_kernel, block_q=block_q,
                                   block_k=block_k, window=window)

        def kv_block(bi, h, qi, ki):
            return (bi, h // n_rep, jnp.maximum(qi - back + ki, 0), 0)
    else:
        kernel = functools.partial(_flash_kernel, block_q=block_q,
                                   block_k=block_k)

        def kv_block(bi, h, qi, ki):
            return (bi, h // n_rep, ki, 0)

    if lean:
        kernel = functools.partial(kernel, lean=True)
        if fresh and not window:
            # the q block's last key block again for every one behind it:
            # a block index that repeats is not fetched anew
            def kv_block(bi, h, qi, ki):
                last = ((qi + 1) * block_q - 1) // block_k
                return (bi, h // n_rep, jnp.minimum(ki, last), 0)

    grid = (b, n_heads, n_q_blocks, n_k_blocks)

    out = pl.pallas_call(
        kernel,
        name="flash_attention_window" if window else "flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, h, qi, ki: (bi, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, d), kv_block,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, d_v), kv_block,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d_v),
                               lambda bi, h, qi, ki: (bi, h, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((*qp.shape[:3], d_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d_v), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(
        seq_lens.astype(jnp.int32),
        q_offset.astype(jnp.int32),
        qp, kp, vp,
    )
    return out[:, :, :s_q].transpose(0, 2, 1, 3)


def flash_attention_sharded(
    q: jnp.ndarray,          # [B, S_q, n_heads, d]
    k: jnp.ndarray,          # [B, S_k, n_kv, d]
    v: jnp.ndarray,          # [B, S_k, n_kv, d]
    seq_lens: jnp.ndarray,   # [B]
    mesh,
    q_offset: jnp.ndarray | None = None,
    head_axis: str = "model",
    **kw,
) -> jnp.ndarray:
    """``flash_attention`` under tensor parallelism.

    ``pallas_call`` has no SPMD partitioning rule, so calling the kernel
    on TP-sharded activations would silently replicate full attention on
    every device (the reason engine.flash_prefill_safe conceded sharded
    prefill to XLA).  The fix is the standard shard_map pattern: heads are
    independent in attention, so each device runs the kernel on ITS head
    block — q/k/v enter head-sharded over ``head_axis`` (their natural
    layout under column-parallel wq/wk/wv, so no resharding happens at
    the boundary) and GQA grouping is preserved per shard.  Both head
    counts must divide the axis; batch stays unsharded (admission groups
    are small and need no data split).
    """
    n_tp = mesh.shape[head_axis]
    if q.shape[2] % n_tp or k.shape[2] % n_tp:
        raise ValueError(
            f"heads {q.shape[2]}/{k.shape[2]} not divisible by "
            f"{head_axis}={n_tp}")
    if q_offset is None:
        q_offset = jnp.zeros((q.shape[0],), jnp.int32)

    def local(q, k, v, lens, off):
        return flash_attention(q, k, v, lens, off, **kw)

    spec = jax.sharding.PartitionSpec(None, None, head_axis, None)
    vec = jax.sharding.PartitionSpec(None)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec, vec, vec),
        out_specs=spec, check_vma=False,
    )(q, k, v, seq_lens, q_offset)
