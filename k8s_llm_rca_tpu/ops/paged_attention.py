"""Pallas TPU paged-attention kernel (decode path).

Decode attention where each sequence's KV lives in non-contiguous
fixed-size pages of a shared pool (vLLM-style block tables, re-designed
for the TPU: the page gather is expressed through a scalar-prefetched
BlockSpec index map, so Pallas's own pipelining DMAs exactly the pages
named by the block table — no host-side gather, no dense [B, S_max]
cache).

Layouts:
- ``k_pages``/``v_pages``: [n_pages, page_size, n_kv*d] — the kv-head and
  head-dim axes are stored MERGED on the lane axis.  TPU tiles the last
  two axes to (sublane, 128-lane) tiles; a per-head [..., page, d=64]
  layout would pad d 64 -> 128 and double both pool HBM and page DMA
  traffic.  With the merged axis the lane dim is n_kv*d (a multiple of
  128 for every real config) and pages are stored/streamed unpadded.
- ``block_tables``: [B, pages_per_seq] int32 page ids; entries past a
  sequence's length MUST still be valid ids (the allocator uses 0) —
  they are fetched but masked out of the softmax.
- ``lengths``: [B] valid kv tokens per sequence (including the current
  decode position).

Because a page block now carries ALL kv heads side by side on lanes, the
kernel processes every query head in one grid step using a
block-diagonal-q trick: queries are pre-expanded to [n_heads, n_kv*d]
with each row zero everywhere except its own kv-head's d-slice, so the
single [n_heads, n_kv*d] x [page, n_kv*d]^T matmul contracts over the
merged axis and the zeros kill every cross-head term.  The p @ v matmul
produces [n_heads, n_kv*d] whose valid output lives on the row's own
d-slice; the caller extracts that block diagonal with one cheap gather.
This trades a constant-factor of extra MXU work (the zero blocks) for
halved DMA on an op that is bandwidth-bound — the right trade on TPU.

Grid is (batch, page); the page axis is innermost and carries running
max / denominator / accumulator scratch across the sweep (online
softmax, same scheme as ops/flash_attention.py).

The reference has no KV cache at all (server-side, reference
common/openai_generic_assistant.py:45-51); SURVEY §2.2 names the paged
KV cache + kernel as a required TPU-native component.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _flash_init(acc_ref, m_ref, l_ref):
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)


def _flash_accumulate(s, v, acc_ref, m_ref, l_ref, p_scale=None):
    """One online-softmax accumulation over this page's scores ``s``
    [n_heads, page] and values ``v`` [page, KV] (shared by the bf16 and
    quantized kernels).  ``p_scale`` [page]: optional per-token value
    scale folded into the softmax weights (quantized pools)."""
    m_prev = m_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - shift)
    correction = jnp.exp(m_prev - shift)

    l_ref[:, 0:1] = l_ref[:, 0:1] * correction + jnp.sum(
        p, axis=-1, keepdims=True)
    pv = p if p_scale is None else p * p_scale[None, :]
    acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
        pv, v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # [n_heads, KV]
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)


def _flash_finalize(o_ref, acc_ref, l_ref):
    l = l_ref[:, 0:1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _paged_kernel(
    lengths_ref,        # SMEM [B]
    tables_ref,         # SMEM [B, pages_per_seq]  (index-map only)
    q_ref,              # VMEM [1, n_heads, KV]  (block-diagonal expanded)
    k_ref,              # VMEM [1, page_size, KV]
    v_ref,              # VMEM [1, page_size, KV]
    o_ref,              # VMEM [1, n_heads, KV]
    acc_ref,            # VMEM scratch [n_heads, KV] f32
    m_ref,              # VMEM scratch [n_heads, _LANES] f32
    l_ref,              # VMEM scratch [n_heads, _LANES] f32
    *,
    page_size: int,
    head_dim: int,
):
    del tables_ref
    bi = pl.program_id(0)
    j = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    length = lengths_ref[bi]

    @pl.when(j * page_size < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [n_heads, KV]
        k = k_ref[0].astype(jnp.float32)               # [page, KV]
        v = v_ref[0].astype(jnp.float32)               # [page, KV]
        n_heads = q.shape[0]

        # rows of q are zero outside their own kv-head's d-slice, so
        # contracting over the merged axis equals the per-head q.k dot
        scale = jax.lax.rsqrt(jnp.float32(head_dim))
        s = jax.lax.dot_general(
            q * scale, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [n_heads, page]

        k_pos = (jax.lax.broadcasted_iota(jnp.int32, (n_heads, page_size), 1)
                 + j * page_size)
        s = jnp.where(k_pos < length, s, NEG_INF)
        _flash_accumulate(s, v, acc_ref, m_ref, l_ref)

    @pl.when(j == n_pages - 1)
    def _finalize():
        _flash_finalize(o_ref, acc_ref, l_ref)


def _paged_kernel_quant(
    lengths_ref,        # SMEM [B]
    tables_ref,         # SMEM [B, pages_per_seq]
    q_ref,              # VMEM [1, n_heads, KV]  (block-diagonal expanded)
    k_ref,              # VMEM [1, page_size, KV'] int8 (KV' = KV or KV/2)
    v_ref,              # VMEM [1, page_size, KV'] int8
    ks_ref,             # VMEM [8, page_size]  scale rows around this page
    vs_ref,             # VMEM [8, page_size]
    o_ref,              # VMEM [1, n_heads, KV]
    acc_ref,            # VMEM scratch [n_heads, KV] f32
    m_ref,              # VMEM scratch [n_heads, _LANES] f32
    l_ref,              # VMEM scratch [n_heads, _LANES] f32
    *,
    page_size: int,
    head_dim: int,
    packed: bool,
):
    """Quantized-pool variant of ``_paged_kernel``: pages are int8 (or
    split-half nibble-packed int4) with one scale per token.  The scales
    never touch the [page, KV] operands — the k scale multiplies the
    [n_heads, page] score columns and the v scale folds into the softmax
    weights, so dequantization costs two small row broadcasts.  Scale rows
    arrive as (8, page_size) blocks (a (1, page_size) block would violate
    the sublane tiling rule); the row select is a one-hot contraction."""
    bi = pl.program_id(0)
    j = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    length = lengths_ref[bi]

    @pl.when(j * page_size < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [n_heads, KV]
        n_heads = q.shape[0]

        def unpack(ref):
            raw = ref[0].astype(jnp.int32)             # [page, KV']
            if not packed:
                return raw.astype(jnp.float32)
            lo = ((raw << 28) >> 28).astype(jnp.float32)   # sign-extended
            hi = (raw >> 4).astype(jnp.float32)
            return jnp.concatenate([lo, hi], axis=-1)  # [page, KV]

        k = unpack(k_ref)
        v = unpack(v_ref)

        # select this page's scale row from the (8, page_size) block.
        # where-then-sum, NOT multiply-by-onehot: rows past the pool's end
        # are uninitialized block padding that may hold inf/NaN, and
        # NaN * 0 would poison the sum
        row = tables_ref[bi, j] % 8
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0) == row)
        ks = jnp.sum(jnp.where(onehot, ks_ref[:, :], 0.0), axis=0)
        vs = jnp.sum(jnp.where(onehot, vs_ref[:, :], 0.0), axis=0)

        scale = jax.lax.rsqrt(jnp.float32(head_dim))
        s = jax.lax.dot_general(
            q * scale, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * ks[None, :]                                # [n_heads, page]

        k_pos = (jax.lax.broadcasted_iota(jnp.int32, (n_heads, page_size), 1)
                 + j * page_size)
        s = jnp.where(k_pos < length, s, NEG_INF)
        _flash_accumulate(s, v, acc_ref, m_ref, l_ref, p_scale=vs)

    @pl.when(j == n_pages - 1)
    def _finalize():
        _flash_finalize(o_ref, acc_ref, l_ref)


def _expand_block_diag(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """[B, n_heads, d] -> [B, n_heads, n_kv*d] with row i nonzero only on
    kv-head (i // n_rep)'s d-slice."""
    b, n_heads, d = q.shape
    n_rep = n_heads // n_kv
    head_kv = jnp.arange(n_heads) // n_rep                     # [n_heads]
    onehot = jax.nn.one_hot(head_kv, n_kv, dtype=q.dtype)      # [n_heads, n_kv]
    return (q[:, :, None, :] * onehot[None, :, :, None]).reshape(
        b, n_heads, n_kv * d)


def _extract_block_diag(out: jnp.ndarray, n_kv: int, d: int) -> jnp.ndarray:
    """[B, n_heads, n_kv*d] -> [B, n_heads, d], keeping each row's own
    kv-head d-slice."""
    b, n_heads, _ = out.shape
    n_rep = n_heads // n_kv
    head_kv = jnp.arange(n_heads) // n_rep
    out = out.reshape(b, n_heads, n_kv, d)
    return out[:, jnp.arange(n_heads), head_kv]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: jnp.ndarray,             # [B, n_heads, d]
    k_pages: jnp.ndarray,       # [n_pages, page_size, n_kv*d]
    v_pages: jnp.ndarray,       # [n_pages, page_size, n_kv*d]
    lengths: jnp.ndarray,       # [B] int32
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Single-step decode attention over a paged KV pool: [B, n_heads, d]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    b, n_heads, d = q.shape
    _, page_size, kv_dim = k_pages.shape
    assert kv_dim % d == 0, (kv_dim, d)
    n_kv = kv_dim // d
    assert n_heads % n_kv == 0, (n_heads, n_kv)
    pages_per_seq = block_tables.shape[1]

    q_exp = _expand_block_diag(q, n_kv)
    grid = (b, pages_per_seq)

    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page_size, head_dim=d),
        name="paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, n_heads, kv_dim),
                             lambda bi, j, lens, tabs: (bi, 0, 0)),
                pl.BlockSpec((1, page_size, kv_dim),
                             lambda bi, j, lens, tabs: (tabs[bi, j], 0, 0)),
                pl.BlockSpec((1, page_size, kv_dim),
                             lambda bi, j, lens, tabs: (tabs[bi, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, n_heads, kv_dim),
                                   lambda bi, j, lens, tabs: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_heads, kv_dim), jnp.float32),
                pltpu.VMEM((n_heads, _LANES), jnp.float32),
                pltpu.VMEM((n_heads, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_heads, kv_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        q_exp, k_pages, v_pages,
    )
    return _extract_block_diag(out, n_kv, d)


@functools.partial(jax.jit, static_argnames=("packed", "interpret"))
def paged_attention_quant(
    q: jnp.ndarray,             # [B, n_heads, d]
    k_pages: jnp.ndarray,       # [n_pages, page_size, KV'] int8
    v_pages: jnp.ndarray,       # [n_pages, page_size, KV'] int8
    k_scales: jnp.ndarray,      # [n_pages, page_size]
    v_scales: jnp.ndarray,      # [n_pages, page_size]
    lengths: jnp.ndarray,       # [B] int32
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    *,
    packed: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Decode attention over a QUANTIZED paged pool (int8, or split-half
    nibble-packed int4 when ``packed``): [B, n_heads, d]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    b, n_heads, d = q.shape
    _, page_size, kv_store = k_pages.shape
    kv_dim = kv_store * 2 if packed else kv_store
    assert kv_dim % d == 0, (kv_dim, d)
    n_kv = kv_dim // d
    assert n_heads % n_kv == 0, (n_heads, n_kv)
    pages_per_seq = block_tables.shape[1]

    q_exp = _expand_block_diag(q, n_kv)
    grid = (b, pages_per_seq)

    out = pl.pallas_call(
        functools.partial(_paged_kernel_quant, page_size=page_size,
                          head_dim=d, packed=packed),
        name="paged_attention_quant",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, n_heads, kv_dim),
                             lambda bi, j, lens, tabs: (bi, 0, 0)),
                pl.BlockSpec((1, page_size, kv_store),
                             lambda bi, j, lens, tabs: (tabs[bi, j], 0, 0)),
                pl.BlockSpec((1, page_size, kv_store),
                             lambda bi, j, lens, tabs: (tabs[bi, j], 0, 0)),
                # scale rows: (8, page) blocks — a (1, page) block would
                # break the sublane tiling rule; the kernel one-hot-selects
                # row tabs[bi, j] % 8
                pl.BlockSpec((8, page_size),
                             lambda bi, j, lens, tabs: (tabs[bi, j] // 8, 0)),
                pl.BlockSpec((8, page_size),
                             lambda bi, j, lens, tabs: (tabs[bi, j] // 8, 0)),
            ],
            out_specs=pl.BlockSpec((1, n_heads, kv_dim),
                                   lambda bi, j, lens, tabs: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_heads, kv_dim), jnp.float32),
                pltpu.VMEM((n_heads, _LANES), jnp.float32),
                pltpu.VMEM((n_heads, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_heads, kv_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        q_exp, k_pages, v_pages,
        # scales enter as f32 regardless of the pool's compute dtype: the
        # (8, page_size) scale BlockSpec is validated on-chip for f32
        # sublane tiling, and the cast is O(n_pages * page_size) — noise
        k_scales.astype(jnp.float32), v_scales.astype(jnp.float32),
    )
    return _extract_block_diag(out, n_kv, d)


def _validate_head_shard(n_heads: int, n_kv: int, n_tp: int) -> None:
    if n_heads % n_tp or n_kv % n_tp:
        raise ValueError(
            f"paged attention under TP needs n_heads={n_heads} and "
            f"n_kv={n_kv} divisible by the head axis size {n_tp} "
            f"(GQA groups must stay whole per shard)")


def paged_attention_sharded(
    q: jnp.ndarray,             # [B, n_heads, d]
    k_pages: jnp.ndarray,       # [n_pages, page_size, n_kv*d]
    v_pages: jnp.ndarray,       # [n_pages, page_size, n_kv*d]
    lengths: jnp.ndarray,       # [B] int32
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    mesh,
    head_axis: str = "model",
    **kw,
) -> jnp.ndarray:
    """``paged_attention`` under tensor parallelism.

    ``pallas_call`` has no SPMD partitioning rule, so calling the kernel
    on a TP-sharded pool would silently replicate full attention on every
    device (the reason the paged engine used to concede sharded decode to
    the XLA gather).  Same fix as ops.flash_attention_sharded: heads are
    independent, so each device runs the kernel over ITS kv-head shard —
    q enters head-sharded over ``head_axis`` and the pool enters sharded
    on its merged kv lane axis (their natural layouts under
    column-parallel wq/wk/wv and the engine's
    ``P(None, None, None, "model")`` pool placement, so no resharding at
    the boundary).  GQA grouping is preserved per shard: both head counts
    must divide the axis.  Batch stays unsharded, matching the decode
    activations (replicated across the TP group).
    """
    _validate_head_shard(q.shape[1], k_pages.shape[-1] // q.shape[-1],
                         mesh.shape[head_axis])

    def local(q, kp, vp, lens, bt):
        return paged_attention(q, kp, vp, lens, bt, **kw)

    q_spec = jax.sharding.PartitionSpec(None, head_axis, None)
    pool_spec = jax.sharding.PartitionSpec(None, None, head_axis)
    vec = jax.sharding.PartitionSpec(None)
    bt_spec = jax.sharding.PartitionSpec(None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, vec, bt_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k_pages, v_pages, lengths, block_tables)


def paged_attention_quant_sharded(
    q: jnp.ndarray,             # [B, n_heads, d]
    k_pages: jnp.ndarray,       # [n_pages, page_size, n_kv*d] int8
    v_pages: jnp.ndarray,       # [n_pages, page_size, n_kv*d] int8
    k_scales: jnp.ndarray,      # [n_pages, page_size]
    v_scales: jnp.ndarray,      # [n_pages, page_size]
    lengths: jnp.ndarray,       # [B] int32
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    mesh,
    head_axis: str = "model",
    **kw,
) -> jnp.ndarray:
    """``paged_attention_quant`` under tensor parallelism (int8 pools).

    The per-token scale is a FULL-ROW scalar (one per written token,
    recovered by pmax over the TP group at write time), so the scale
    pools replicate across ``head_axis`` and each shard's dequant
    ``int8 * scale`` is exact — per-shard attention then matches the
    global computation bit-for-bit up to the reduction order.

    Split-half nibble-packed int4 pools are NOT supported here: packing
    pairs lane i with lane i + kv_dim/2, so a contiguous shard of the
    PACKED lane axis unpacks to two non-contiguous head ranges — the
    shard-local unpack would attend the wrong heads.  The engine keeps
    int4 pools on the XLA gather path under TP (engine/paged.py gating).
    """
    if kw.pop("packed", False):
        raise ValueError(
            "paged_attention_quant_sharded does not support packed int4 "
            "pools (split-half packing does not commute with the head "
            "shard); use the XLA path")
    _validate_head_shard(q.shape[1], k_pages.shape[-1] // q.shape[-1],
                         mesh.shape[head_axis])

    def local(q, kp, vp, ks, vs, lens, bt):
        return paged_attention_quant(q, kp, vp, ks, vs, lens, bt,
                                     packed=False, **kw)

    q_spec = jax.sharding.PartitionSpec(None, head_axis, None)
    pool_spec = jax.sharding.PartitionSpec(None, None, head_axis)
    scale_spec = jax.sharding.PartitionSpec(None, None)
    vec = jax.sharding.PartitionSpec(None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, scale_spec, scale_spec,
                  vec, scale_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k_pages, v_pages, k_scales, v_scales, lengths, block_tables)


def paged_attention_xla(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
) -> jnp.ndarray:
    """Pure-XLA reference implementation (gather + masked softmax).

    Ground truth for the kernel's unit tests and the fallback for
    platforms without Mosaic.
    """
    b, n_heads, d = q.shape
    _, page_size, kv_dim = k_pages.shape
    assert kv_dim % d == 0, (kv_dim, d)
    n_kv = kv_dim // d
    assert n_heads % n_kv == 0, (n_heads, n_kv)
    n_rep = n_heads // n_kv

    # [B, pp, page, KV] -> [B, S_max, n_kv, d]
    k = jnp.take(k_pages, block_tables, axis=0)
    v = jnp.take(v_pages, block_tables, axis=0)
    k = k.reshape(b, -1, n_kv, d)
    v = v.reshape(b, -1, n_kv, d)

    k = jnp.repeat(k, n_rep, axis=2).astype(jnp.float32)
    v = jnp.repeat(v, n_rep, axis=2).astype(jnp.float32)
    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.float32(d))

    s = jnp.einsum("bhd,bkhd->bhk", qf, k)
    k_pos = jnp.arange(k.shape[1])[None, None, :]
    s = jnp.where(k_pos < lengths[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, v)
    return out.astype(q.dtype)
