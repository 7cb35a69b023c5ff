"""Pallas TPU paged-attention kernel (decode path).

Decode attention where each sequence's KV lives in non-contiguous
fixed-size pages of a shared pool (vLLM-style block tables, re-designed
for the TPU).  The kernel's work follows the live context: the pools stay
in HBM (``pl.ANY``), the grid has one step a slot, and inside it a loop
walks the slot's block table in BLOCKS of P consecutive entries, for
``ceil(ceil(length / page_size) / P)`` blocks only.  Each iteration
starts the P page copies of the next block (``pltpu.make_async_copy``
from ``pool.at[layer, table[b, i]]`` into the other half of a VMEM
double buffer, one DMA semaphore a half) and attends the block that has
arrived.  A slot of length 0 copies nothing and multiplies nothing; no
table entry past the last live block is read.  This is the structure of
``jax.experimental.pallas.ops.tpu.paged_attention`` (pages per compute
block, kernel-issued copies) on this repo's merged-lane layout.

The block size is no knob.  ``block_pages`` gives P from what the call
shows: as many pages as hold ``_BLOCK_TOKENS`` (256) tokens, at most the
table's width, at least one.  A table whose width is no multiple of P is
walked with its tail clamped to the last entry; those columns lie past
every length and are masked.  On a v5e at 1024 merged lanes a block of
256 tokens took 16% less time a call than one of 128, and 512 another 3%
at twice the padding of each slot's last block (PERF.md, PR 24).  VMEM
grows with P x page_size x lanes: two halves of the stored pages for k
and for v, and the f32 copies the matmuls read (1 MiB each at 256 tokens
and 1024 lanes).

What is left of the fixed costs: the first block of every live slot
starts cold (its copy is waited for with nothing to overlap, a few
microseconds a slot; the JAX reference kernel hides it by starting the
next slot's first block early, this one does not), and every grid step,
live or not, brings its q block and writes its output block.

Layouts:
- ``k_pages``/``v_pages``: [L, n_pages, page_size, n_kv*d], every
  layer's pages as the engine's ``PagePool`` keeps them, and ``layer``,
  one more scalar-prefetch operand, says which to read: the pool is
  handed over by reference, because a layer sliced out of it is a copy
  of the layer (50 MB a call at 3,072 pages of a 7B model; PERF.md,
  PR 28).  The layer is a traced value, so every layer of a model runs
  ONE kernel.  Without ``layer`` the entry points take one layer's
  pages [n_pages, page_size, n_kv*d] (a free reshape to L = 1).  The
  kv-head and head-dim axes are stored MERGED on the lane axis.  TPU
  tiles the last
  two axes to (sublane, 128-lane) tiles; a per-head [..., page, d=64]
  layout would pad d 64 -> 128 and double both pool HBM and page DMA
  traffic.  With the merged axis the lane dim is n_kv*d (a multiple of
  128 for every real config) and pages are stored/streamed unpadded.
- ``block_tables``: [B, pages_per_seq] int32 page ids; entries past a
  sequence's length MUST still be valid ids (the allocator uses 0): the
  last live block is copied whole, so the entries that share it with
  live pages are fetched and masked out of the softmax.
- ``lengths``: [B] valid kv tokens per sequence (including the current
  decode position); 0 for a slot that holds no sequence.
- ``starts`` (optional): [B], the first table position each slot's query
  sees, for a sliding-window layer: the table is then the slot's ring of
  pages laid out from the page that holds the window's first position
  (engine/paged.py::_ring_view), a handful of entries whatever the
  context, so the walk is one block and the call's time does not grow
  with the sequence; positions before ``starts[b]`` are masked as those
  past ``lengths[b]`` are.  One more scalar-prefetch operand, and a name
  of its own (``window_paged_attention``), so a trace tells a window
  layer's calls from a full layer's.
- quantized pools: int8 pages (or split-half nibble-packed int4) and one
  f32 scale a token, ``[L, n_pages, page_size]``, 1/256 of the pages'
  bytes.  A row of fewer than 128 lanes cannot be sliced out of an HBM
  array by a kernel's own copy (Mosaic: "must be aligned to tiling
  (128)"), so the wrapper slices the layer's scales out and reshapes
  them to rows of 128 lanes (``_lane_dense_scales``: 8 pages of 16
  tokens share a row; ``page_size`` has to divide 128 or be a multiple
  of it), the kernel copies the row that holds each page of the block,
  and a lane rotation puts the page's scales under its columns of the
  block's scores (``_scale_row``).

Because a page carries ALL kv heads side by side on lanes, the kernel
processes every query head at once using a block-diagonal-q trick:
queries are pre-expanded to [n_heads, n_kv*d] with each row zero
everywhere except its own kv-head's d-slice, so the single
[n_heads, n_kv*d] x [P*page, n_kv*d]^T matmul contracts over the merged
axis and the zeros kill every cross-head term.  The p @ v matmul
produces [n_heads, n_kv*d] whose valid output lives on the row's own
d-slice; the caller extracts that block diagonal with one cheap gather.
This trades a constant-factor of extra MXU work (the zero blocks) for
halved DMA.  Scores, running max, denominator and accumulator are f32
(online softmax, same scheme as ops/flash_attention.py); the bf16 and
the quantized pool share the loop and the helpers and differ in the
unpack and the scales.

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
# tokens one loop iteration attends: on a v5e, at 1024 merged lanes, 256
# took 16% less time a call than 128 and 512 another 3%, at twice the
# padding of every slot's last block (PERF.md, Findings, PR 24)
_BLOCK_TOKENS = 256


def _flash_init(acc_ref, m_ref, l_ref):
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)


def _flash_accumulate(s, v, acc_ref, m_ref, l_ref, p_scale=None):
    """One online-softmax accumulation over a block's scores ``s``
    [n_heads, T] and values ``v`` [T, KV] (shared by the bf16 and
    quantized kernels).  ``p_scale`` [1, T]: optional per-token value
    scale folded into the softmax weights (quantized pools)."""
    m_prev = m_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - shift)
    correction = jnp.exp(m_prev - shift)

    l_ref[:, 0:1] = l_ref[:, 0:1] * correction + jnp.sum(
        p, axis=-1, keepdims=True)
    pv = p if p_scale is None else p * p_scale
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


def block_pages(page_size: int, pages_per_seq: int) -> int:
    """P, the table entries one loop iteration of the kernel covers: as
    many pages as make ``_BLOCK_TOKENS`` tokens, at least one, at most the
    table.  A slot's live pages are visited rounded up to a multiple of
    it (the engine's ``engine.attn_pages_grid`` counts just that)."""
    return max(1, min(pages_per_seq, _BLOCK_TOKENS // page_size))


def _scale_row(buf_ref, slot, page_ids, page_size: int):
    """The block's per-token scales as one lane-major row [1, P * page].

    ``buf_ref[slot, i]`` is the [1, W] row of the lane-dense scale pool
    that holds page ``page_ids[i]``'s scales among those of its
    ``W // page`` neighbours; a lane rotation moves them to where the
    block's scores have that page's columns."""
    n = len(page_ids)
    width = buf_ref.shape[-1]
    group = width // page_size
    lane_page = jax.lax.broadcasted_iota(
        jnp.int32, (1, width), 1) // page_size
    chunks = []
    for c in range(0, n, group):
        chunk = None
        for i in range(c, min(n, c + group)):
            row = buf_ref[slot, i]                         # [1, W]
            if group > 1:
                shift = ((i % group) - page_ids[i] % group) * page_size
                row = pltpu.roll(row, jax.lax.rem(shift + width, width), 1)
            chunk = (row if chunk is None
                     else jnp.where(lane_page == i % group, row, chunk))
        chunks.append(chunk)
    row = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)
    return row[:, :n * page_size]


def _paged_kernel(
    layer_ref,          # SMEM [1]
    lengths_ref,        # SMEM [B]
    tables_ref,         # SMEM [B, pages_per_seq]
    *refs,              # [starts SMEM [B] where ``windowed``,] then
                        # q VMEM [1, n_heads, KV] (block-diagonal expanded)
    page_size: int,
    head_dim: int,
    n_block: int,
    quant: bool,
    packed: bool,
    windowed: bool = False,
):
    """One slot a grid step: loop over the blocks of ``n_block``
    table entries that hold live context, copying the next block's pages
    from the pool (HBM) into the other half of a double buffer while
    this block is attended.

    ``refs``: the pools in HBM (the k and v pages of EVERY layer,
    [L, n_pages, page, KV'], of which ``layer_ref[0]`` is read; with
    ``quant`` also that layer's lane-dense k, v scale rows), the output
    block, one VMEM double buffer per pool, a DMA semaphore per buffer
    half, and the running accumulator / max / denominator.

    Quantized pages are int8 (or split-half nibble-packed int4) with one
    scale per token.  The scales never touch the [T, KV] operands: the k
    scale multiplies the [n_heads, T] score columns and the v scale
    folds into the softmax weights.

    ``windowed``: one more scalar-prefetch operand, ``starts`` [B], the
    first table position each slot's query sees; the head of the first
    block before it is masked as the tail of the last one past the length
    is."""
    start = None
    if windowed:
        starts_ref, *refs = refs
        start = starts_ref[pl.program_id(0)]
    q_ref, *refs = refs
    n_pools = 4 if quant else 2
    pools, o_ref = refs[:n_pools], refs[n_pools]
    bufs = refs[n_pools + 1:2 * n_pools + 1]
    sems, acc_ref, m_ref, l_ref = refs[2 * n_pools + 1:]

    bi = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[bi]
    pages_per_seq = tables_ref.shape[1]
    block_tokens = n_block * page_size
    n_blocks = (length + block_tokens - 1) // block_tokens

    def page_ids(blk):
        # a table no multiple of the block: the tail repeats its last
        # entry, whose columns lie past every length
        return [tables_ref[bi, jnp.minimum(blk * n_block + i,
                                           pages_per_seq - 1)]
                for i in range(n_block)]

    def copies(pids, slot):
        out = []
        for i, pid in enumerate(pids):
            for pool, buf in zip(pools[:2], bufs[:2]):
                out.append(pltpu.make_async_copy(
                    pool.at[layer, pid], buf.at[slot, i], sems.at[slot]))
            for pool, buf in zip(pools[2:], bufs[2:]):
                group = buf.shape[-1] // page_size
                out.append(pltpu.make_async_copy(
                    pool.at[pl.ds(pid // group, 1)], buf.at[slot, i],
                    sems.at[slot]))
        return out

    def unpack(ref, slot):
        """[P, page, KV'] as stored -> f32 [P * page, KV]."""
        x = ref[slot]
        if packed:
            # widened first: Mosaic has no int8 vector shifts
            x = x.astype(jnp.int32)
            x = jnp.concatenate([(x << 28) >> 28, x >> 4], axis=-1)
        x = x.astype(jnp.float32)
        return x.reshape(block_tokens, x.shape[-1])

    _flash_init(acc_ref, m_ref, l_ref)

    @pl.when(n_blocks > 0)
    def _first():
        for c in copies(page_ids(0), 0):
            c.start()

    # rows of q are zero outside their own kv-head's d-slice, so
    # contracting over the merged axis equals the per-head q.k dot
    q = q_ref[0].astype(jnp.float32) * jax.lax.rsqrt(jnp.float32(head_dim))
    n_heads = q.shape[0]

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _next():
            for c in copies(page_ids(blk + 1), 1 - slot):
                c.start()

        pids = page_ids(blk)
        for c in copies(pids, slot):
            c.wait()

        s = jax.lax.dot_general(
            q, unpack(bufs[0], slot),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [n_heads, T]
        p_scale = None
        if quant:
            s = s * _scale_row(bufs[2], slot, pids, page_size)
            p_scale = _scale_row(bufs[3], slot, pids, page_size)
        k_pos = (jax.lax.broadcasted_iota(
            jnp.int32, (n_heads, block_tokens), 1) + blk * block_tokens)
        live = k_pos < length
        if windowed:
            live &= k_pos >= start
        s = jnp.where(live, s, NEG_INF)
        _flash_accumulate(s, unpack(bufs[1], slot), acc_ref, m_ref, l_ref,
                          p_scale=p_scale)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
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


def _lane_dense_scales(scales: jnp.ndarray) -> jnp.ndarray:
    """[n_pages, page] -> f32 [rows, W] with W = max(128, page): the
    kernel copies rows of the pool itself, and a row of fewer than 128
    lanes cannot be sliced out of HBM (Mosaic: "must be aligned to
    tiling (128)"), so pages narrower than that share a row."""
    n_pages, page_size = scales.shape
    if _LANES % page_size and page_size % _LANES:
        raise ValueError(
            f"the quantized paged-attention kernel needs a page_size that "
            f"divides {_LANES} or is a multiple of it, got {page_size}")
    group = max(1, _LANES // page_size)
    scales = jnp.pad(scales.astype(jnp.float32),
                     ((0, -n_pages % group), (0, 0)))
    return scales.reshape(-1, group * page_size)


def _paged_call(name, q, pools, layer, lengths, block_tables, *, packed,
                interpret, starts=None):
    """The one ``pallas_call`` both pools' kernels are.  ``pools`` are the
    k and v pages and, for a quantized pool, the two scale pools: with
    ``layer`` None one layer's ([n_pages, page, KV'] and
    [n_pages, page]), else every layer's, stacked on a leading axis as
    the engine's pool keeps them, of which the kernel reads layer
    ``layer`` (an int or a traced scalar) where it lies.  The pages are
    handed over whole and by reference; of the scales, 1/256 of the
    bytes, the layer is sliced out here for its lane-dense rows.
    ``starts`` [B] makes it the window layers' call: each slot's query
    sees table positions ``starts[b] .. lengths[b] - 1``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if layer is None:
        pages, scales, layer = [p[None] for p in pools[:2]], pools[2:], 0
    else:
        pages, scales = pools[:2], [s[layer] for s in pools[2:]]

    b, n_heads, d = q.shape
    _, _, page_size, kv_store = pages[0].shape
    kv_dim = kv_store * 2 if packed else kv_store
    assert kv_dim % d == 0, (kv_dim, d)
    n_kv = kv_dim // d
    assert n_heads % n_kv == 0, (n_heads, n_kv)
    n_block = block_pages(page_size, block_tables.shape[1])

    scales = [_lane_dense_scales(s) for s in scales]
    buffers = [pltpu.VMEM((2, n_block, page_size, kv_store), p.dtype)
               for p in pages]
    buffers += [pltpu.VMEM((2, n_block, 1, s.shape[-1]), s.dtype)
                for s in scales]
    slot_block = pl.BlockSpec((1, n_heads, kv_dim),
                              lambda bi, *scalars: (bi, 0, 0))
    windowed = starts is not None
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1),
               lengths.astype(jnp.int32), block_tables.astype(jnp.int32))
    if windowed:
        scalars += (starts.astype(jnp.int32),)

    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page_size, head_dim=d,
                          n_block=n_block, quant=bool(scales),
                          packed=packed, windowed=windowed),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b,),
            in_specs=[slot_block] + [pl.BlockSpec(memory_space=pl.ANY)
                                     for _ in (*pages, *scales)],
            out_specs=slot_block,
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((n_heads, kv_dim), jnp.float32),
                pltpu.VMEM((n_heads, _LANES), jnp.float32),
                pltpu.VMEM((n_heads, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_heads, kv_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(*scalars, _expand_block_diag(q, n_kv), *pages, *scales)
    return _extract_block_diag(out, n_kv, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: jnp.ndarray,             # [B, n_heads, d]
    k_pages: jnp.ndarray,       # [n_pages, page_size, n_kv*d], or [L, ...]
    v_pages: jnp.ndarray,       # as k_pages
    lengths: jnp.ndarray,       # [B] int32
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    *,
    layer=None,
    interpret: bool | None = None,
    starts: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Single-step decode attention over a paged KV pool: [B, n_heads, d].
    With ``layer`` the pools are every layer's pages
    [L, n_pages, page_size, n_kv*d] and the kernel reads that layer of
    them in place; without, one layer's.  With ``starts`` [B] it is a
    window layer's call (``window_paged_attention``): the query of slot
    ``b`` sees the table's positions ``starts[b] .. lengths[b] - 1``, the
    table being the slot's ring laid out from the page that holds the
    window's first position (engine/paged.py::_ring_view)."""
    return _paged_call("paged_attention" if starts is None
                       else "window_paged_attention", q, (k_pages, v_pages),
                       layer, lengths, block_tables, packed=False,
                       interpret=interpret, starts=starts)


@functools.partial(jax.jit, static_argnames=("packed", "interpret"))
def paged_attention_quant(
    q: jnp.ndarray,             # [B, n_heads, d]
    k_pages: jnp.ndarray,       # [n_pages, page_size, KV'] int8, or [L, ...]
    v_pages: jnp.ndarray,       # as k_pages
    k_scales: jnp.ndarray,      # [n_pages, page_size], or [L, ...]
    v_scales: jnp.ndarray,      # as k_scales
    lengths: jnp.ndarray,       # [B] int32
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    *,
    layer=None,
    packed: bool = False,
    interpret: bool | None = None,
    starts: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Decode attention over a QUANTIZED paged pool (int8, or split-half
    nibble-packed int4 when ``packed``): [B, n_heads, d].  ``layer`` and
    ``starts`` as in ``paged_attention``: the four pools then carry a
    leading layer axis, and the window layers' call is
    ``window_paged_attention_quant``."""
    return _paged_call("paged_attention_quant" if starts is None
                       else "window_paged_attention_quant", q,
                       (k_pages, v_pages, k_scales, v_scales), layer,
                       lengths, block_tables, packed=packed,
                       interpret=interpret, starts=starts)


def _validate_head_shard(n_heads: int, n_kv: int, n_tp: int) -> None:
    if n_heads % n_tp or n_kv % n_tp:
        raise ValueError(
            f"paged attention under TP needs n_heads={n_heads} and "
            f"n_kv={n_kv} divisible by the head axis size {n_tp} "
            f"(GQA groups must stay whole per shard)")


def paged_attention_sharded(
    q: jnp.ndarray,             # [B, n_heads, d]
    k_pages: jnp.ndarray,       # [n_pages, page_size, n_kv*d], or [L, ...]
    v_pages: jnp.ndarray,       # as k_pages
    lengths: jnp.ndarray,       # [B] int32
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    mesh,
    head_axis: str = "model",
    **kw,
) -> jnp.ndarray:
    """``paged_attention`` under tensor parallelism (``layer=`` and the
    stacked pools as there).

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
    pool_spec = jax.sharding.PartitionSpec(
        *(None,) * (k_pages.ndim - 1), head_axis)
    vec = jax.sharding.PartitionSpec(None)
    bt_spec = jax.sharding.PartitionSpec(None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, vec, bt_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k_pages, v_pages, lengths, block_tables)


def paged_attention_quant_sharded(
    q: jnp.ndarray,             # [B, n_heads, d]
    k_pages: jnp.ndarray,       # [n_pages, page_size, n_kv*d] int8, or [L, ...]
    v_pages: jnp.ndarray,       # as k_pages
    k_scales: jnp.ndarray,      # [n_pages, page_size], or [L, ...]
    v_scales: jnp.ndarray,      # as k_scales
    lengths: jnp.ndarray,       # [B] int32
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    mesh,
    head_axis: str = "model",
    **kw,
) -> jnp.ndarray:
    """``paged_attention_quant`` under tensor parallelism (int8 pools;
    ``layer=`` and the stacked pools as there).

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
    pool_spec = jax.sharding.PartitionSpec(
        *(None,) * (k_pages.ndim - 1), head_axis)
    scale_spec = jax.sharding.PartitionSpec(*(None,) * k_scales.ndim)
    vec = jax.sharding.PartitionSpec(None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, scale_spec, scale_spec,
                  vec, jax.sharding.PartitionSpec(None, None)),
        out_specs=q_spec, check_vma=False,
    )(q, k_pages, v_pages, k_scales, v_scales, lengths, block_tables)


def paged_attention_xla(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Pure-XLA reference implementation (gather + masked softmax).

    Ground truth for the kernel's unit tests and the fallback for
    platforms without Mosaic.  ``starts`` as in ``paged_attention``.
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
    live = k_pos < lengths[:, None, None]
    if starts is not None:
        live &= k_pos >= starts[:, None, None]
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, v)
    return out.astype(q.dtype)
