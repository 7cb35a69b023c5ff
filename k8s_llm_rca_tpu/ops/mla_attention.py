"""Decode attention over a paged LATENT cache (multi-head latent attention,
``deepseek_v3``), absorbed.

A model with latent attention caches ONE row a token and layer: the latent
``c`` (``kv_lora_rank`` values, under its own norm) and behind it the one
rotated key all heads share.  Every head's keys and values are
up-projections of ``c`` (``w_kvb`` = ``[W_uk, W_uv]`` per head), so a decode
step need not write them out: a head's score against a cached token is

    q_nope . (W_uk c) + q_rope . k_rope  =  [W_uk^T q_nope, q_rope] . row

and its output ``W_uv (sum_t p_t c_t)``.  ``absorb_query`` carries the
unrotated query into the latent's space, the kernel walks the rows with that
``[n_heads, row]`` query, using each fetched block as KEYS (all its columns)
and as VALUES (its first ``n_value`` columns, the latent), and
``unabsorb_values`` takes the heads' latent outputs through ``W_uv``.

The kernel is ops/paged_attention.py's loop (the pool in HBM by reference,
one grid step a slot, kernel-issued page copies into a double buffer, a trip
count from the slot's length, a slot of length 0 fetching nothing) with what
a latent row changes: one pool and one copy a page where keys and values
took two, no block-diagonal query (every head reads the whole row), the
operands AS STORED on the MXU (bfloat16 rows and query are one pass;
accumulation, running max and denominator are float32, and the
probabilities are rounded to the rows' type for the second product), and
the softmax scale an argument: ``1 / sqrt(qk_head_dim)`` of the published
form is no function of the row's width.  A token costs 2 x n_heads x (row +
n_value) operations for ``row`` stored values, 60 FLOP/B in bfloat16 at 32
heads: near enough the ridge that float32 operands would be compute-bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_llm_rca_tpu.ops.paged_attention import (
    _LANES, NEG_INF, _flash_finalize, _flash_init,
)

# tokens one loop iteration attends.  On a v5e, 64 slots of 5-14k tokens in
# rows of 576 kept at 640 lanes, one layer's call (my chip run, PR 46; ms |
# GB/s of the 1,152 B a row needs): pages of 16 in blocks of 256 2.28 | 310,
# 512 1.76 | 401, 1024 1.52 | 465; pages of 64 in blocks of 1024 1.24 | 570.
# 1024 is 1.3 MB a half of the buffer
BLOCK_TOKENS = 1024

# table entries the kernel looks at together: where their ids ascend by one
# (a run) they are ONE copy, else one each (mla_page_copies counts by the
# same rule).  The same call at pages of 16 in blocks of 1024 (my chip run,
# PR 47; ms on a table that is all runs | all scattered | runs of twelve
# pages with strangers between | as the engine's allocator leaves it after
# 640 requests; the kernel before: 1.51 | 1.52 | 1.51 | 1.33): 2 1.78 |
# 1.78 | 1.78 | 1.57; 4 1.62 | 1.63 | 1.62 | 1.43; 8 1.26 | 1.46 | 1.26 |
# 1.11; 16 1.26 | 1.41 | 1.41 | 1.11.  Not the copies set the pace but the
# branches: some hundred cycles of the scalar unit each, whatever is in
# them, in front of the block's products; eight a block hide behind the
# block's copies, sixteen do not, and 1.26 is what HBM gives (620 GB/s of
# the lanes fetched).  16 wins nothing over 8 but on a table with no run at
# all, and loses the runs shorter than 16
RUN_PAGES = 8


def mla_block_pages(page_size: int, pages_per_seq: int,
                    block_tokens: int = BLOCK_TOKENS) -> int:
    """Table entries one loop iteration of the kernel covers: as many
    pages as make ``block_tokens`` tokens, at least one, at most the
    table (the engine's ``engine.attn_pages_grid`` rounds by it)."""
    return max(1, min(pages_per_seq, block_tokens // page_size))


def _group_runs(tables, n_block: int):
    """Which groups of ``RUN_PAGES`` entries of a table [B, pages_per_seq]
    are runs, ids that ascend by one: [B, n_blocks * (n_block // RUN_PAGES)],
    beside the table in whole blocks [B, n_blocks * n_block] (a table no
    multiple of the block repeats its last entry, whose columns lie past
    every length).  numpy or jax.numpy, by the table's type."""
    xp = jnp if isinstance(tables, jax.Array) else np
    b, pad = tables.shape[0], -tables.shape[1] % n_block
    if pad:
        tables = xp.pad(tables, ((0, 0), (0, pad)), mode="edge")
    groups = tables.reshape(b, -1, n_block)[
        :, :, :n_block - n_block % RUN_PAGES].reshape(b, -1, RUN_PAGES)
    # a group's entries along the FIRST axis: numpy reduces a long last
    # axis several times faster than a last axis of eight
    groups = xp.moveaxis(groups, -1, 0)
    if xp is np:
        groups = np.ascontiguousarray(groups)
    return (groups[1:] == groups[:-1] + 1).all(axis=0), tables


def mla_page_copies(tables: np.ndarray, blocks: np.ndarray,
                    n_block: int) -> int:
    """Copies the kernel starts to fetch the first ``blocks[i]`` blocks of
    ``n_block`` entries of row i of ``tables`` (host arrays), by the
    kernel's own rule: within a block one for a group of ``RUN_PAGES``
    entries that is a run, ``RUN_PAGES`` for a group that is not, one for
    each entry behind the last whole group."""
    run, _ = _group_runs(tables, n_block)
    n_groups = n_block // RUN_PAGES
    fetched = np.arange(run.shape[1])[None, :] < (blocks * n_groups)[:, None]
    runs, n_blocks = np.count_nonzero(run & fetched), int(blocks.sum())
    return (runs + RUN_PAGES * (n_blocks * n_groups - runs)
            + n_blocks * (n_block % RUN_PAGES))


def stored_lanes(row: int) -> int:
    """The width a pool keeps a row of ``row`` values at: whole tiles of
    128 lanes.  The TPU pads an array's last axis to that in HBM whatever
    its shape says, and a kernel's own copy cannot slice the padding off
    (Mosaic: "Slice shape along dimension 3 must be aligned to tiling
    (128), but is 576"), so the pool states the width it occupies and the
    lanes behind the row stay zero."""
    return -(-row // _LANES) * _LANES


def absorb_query(q_nope: jnp.ndarray, w_up: jnp.ndarray,
                 n_nope: int) -> jnp.ndarray:
    """[B, n_heads, n_nope] unrotated queries -> [B, n_heads, rank]: each
    head's query through its own key up-projection, ``w_up`` [rank,
    n_heads, n_nope + n_v] being ``w_kvb`` by head."""
    return jnp.einsum("bhd,rhd->bhr", q_nope, w_up[..., :n_nope])


def unabsorb_values(o_latent: jnp.ndarray, w_up: jnp.ndarray,
                    n_nope: int) -> jnp.ndarray:
    """[B, n_heads, rank] attended latents -> [B, n_heads, n_v]: each
    head's through its own value up-projection."""
    return jnp.einsum("bhr,rhd->bhd", o_latent, w_up[..., n_nope:])


def _mla_kernel(
    layer_ref,          # SMEM [1]
    lengths_ref,        # SMEM [B]
    tables_ref,         # SMEM [B, n_blocks * n_block]: whole blocks
    run_ref,            # SMEM [B, n_blocks * (n_block // RUN_PAGES)]
    q_ref,              # VMEM [1, n_heads, row]
    pool,               # HBM  [L, n_pages, page, row]
    o_ref,              # VMEM [1, n_heads, n_value]
    buf,                # VMEM [2, n_block, page, row]
    sems,               # DMA semaphores [2]
    acc_ref,            # VMEM [n_heads, n_value] f32
    m_ref,              # VMEM [n_heads, _LANES] f32
    l_ref,              # VMEM [n_heads, _LANES] f32
    *,
    page_size: int,
    n_block: int,
    n_value: int,
    scale: float,
):
    bi = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[bi]
    block_tokens = n_block * page_size
    n_blocks = (length + block_tokens - 1) // block_tokens
    n_groups = n_block // RUN_PAGES

    def start(blk, slot):
        def page_id(i):
            return tables_ref[bi, blk * n_block + i]

        def fetch(first, n, page):
            # n adjacent pages lie one behind the other in the pool as
            # they do in the buffer: one copy puts the same rows at the
            # same places as the n it stands for
            pltpu.make_async_copy(
                pool.at[layer, pl.ds(page, n)],
                buf.at[slot, pl.ds(first, n)], sems.at[slot]).start()

        def apart(first, n):
            for i in range(first, first + n):
                fetch(i, 1, page_id(i))

        # a branch costs the scalar unit some hundred cycles whatever is
        # in it: what the branches need is read in front of them all
        groups = [(g * RUN_PAGES, run_ref[bi, blk * n_groups + g],
                   page_id(g * RUN_PAGES)) for g in range(n_groups)]
        for first, run, page in groups:
            jax.lax.cond(
                run != 0,
                functools.partial(fetch, first, RUN_PAGES, page),
                functools.partial(apart, first, RUN_PAGES))
        apart(n_groups * RUN_PAGES, n_block % RUN_PAGES)

    def wait(slot):
        # a DMA semaphore counts what has arrived and a wait takes off
        # its destination's size: one wait on the whole half of the
        # buffer is the sum of the copies started into it, whichever
        # branches ran
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sems.at[slot]).wait()

    _flash_init(acc_ref, m_ref, l_ref)

    @pl.when(n_blocks > 0)
    def _first():
        start(0, 0)

    q = q_ref[0]
    n_heads = q.shape[0]

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _next():
            start(blk + 1, 1 - slot)

        wait(slot)

        rows = buf[slot].reshape(block_tokens, buf.shape[-1])
        s = jax.lax.dot_general(
            q, rows, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [n_heads, T]
        k_pos = (jax.lax.broadcasted_iota(
            jnp.int32, (n_heads, block_tokens), 1) + blk * block_tokens)
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - shift)
        correction = jnp.exp(m_prev - shift)
        l_ref[:, 0:1] = l_ref[:, 0:1] * correction + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :n_value],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [n_heads, V]
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    _flash_finalize(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("scale", "n_value", "interpret",
                                             "block_tokens"))
def mla_paged_attention(
    q: jnp.ndarray,             # [B, n_heads, row] absorbed queries
    pages: jnp.ndarray,         # [n_pages, page, stored_lanes(row)], or
                                # [L, ...]
    lengths: jnp.ndarray,       # [B] int32, this step's token included
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    *,
    scale: float,
    n_value: int,
    layer=None,
    interpret: bool | None = None,
    block_tokens: int = BLOCK_TOKENS,
) -> jnp.ndarray:
    """Single-step absorbed decode attention over a paged latent pool:
    [B, n_heads, n_value], the softmax of ``scale * q . row`` over each
    slot's cached rows applied to their first ``n_value`` columns.  With
    ``layer`` the pool is every layer's [L, n_pages, page, row] and the
    kernel reads that layer in place (a traced value: one kernel for every
    layer).  ``q`` is taken in the pool's type.  ``block_tokens`` is for
    the tests, which need several blocks a slot at a size a CPU holds: the
    engine never gives it and counts its grid by the same default
    (``mla_block_pages``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if layer is None:
        pages, layer = pages[None], 0
    _, _, page_size, stored = pages.shape
    assert n_value <= q.shape[-1] <= stored, (q.shape, stored, n_value)
    # the lanes behind the row are zero in the pool and in the query
    q = jnp.pad(q, ((0, 0), (0, 0), (0, stored - q.shape[-1])))
    b, n_heads, row = q.shape
    n_block = mla_block_pages(page_size, block_tables.shape[1], block_tokens)
    # which groups of the table are runs is told here, in XLA: a flag costs
    # the kernel's scalar unit one read where the ids cost it one each and
    # their compares (a block under a group has none: one flag nothing reads)
    run, tables = _group_runs(block_tables.astype(jnp.int32), n_block)
    run = run.astype(jnp.int32) if run.size else jnp.zeros((b, 1), jnp.int32)

    def q_block(width):
        return pl.BlockSpec((1, n_heads, width),
                            lambda bi, *scalars: (bi, 0, 0))

    return pl.pallas_call(
        functools.partial(_mla_kernel, page_size=page_size, n_block=n_block,
                          n_value=n_value, scale=scale),
        name="mla_paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[q_block(row), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_block(n_value),
            scratch_shapes=[
                pltpu.VMEM((2, n_block, page_size, row), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((n_heads, n_value), jnp.float32),
                pltpu.VMEM((n_heads, _LANES), jnp.float32),
                pltpu.VMEM((n_heads, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_heads, n_value), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32),
      tables, run, q.astype(pages.dtype), pages)


def mla_paged_attention_xla(
    q: jnp.ndarray,             # [B, n_heads, row]
    pages: jnp.ndarray,         # [n_pages, page, row]
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
    *,
    scale: float,
    n_value: int,
) -> jnp.ndarray:
    """The same arithmetic in XLA (gather, masked float32 softmax): the
    kernel's test oracle and the form platforms without Mosaic run."""
    b = q.shape[0]
    rows = jnp.take(pages, block_tables, axis=0).reshape(
        b, -1, pages.shape[-1])[..., :q.shape[-1]].astype(jnp.float32)
    s = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), rows) * scale
    live = jnp.arange(rows.shape[1])[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, NEG_INF), axis=-1)
    # a slot of length 0 attends nothing (the kernel's accumulator stands)
    p = jnp.where(lengths[:, None, None] > 0, p, 0.0)
    return jnp.einsum("bhk,bkr->bhr", p, rows[..., :n_value]).astype(q.dtype)
