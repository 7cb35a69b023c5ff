"""Mamba-2 state-space ops: the chunked scan a prefill runs, the one-step
update a decode step runs, and the depthwise causal convolution in front
of both.  Plain XLA (einsums and one ``lax.scan`` over chunks) on a CPU,
under a mesh and where a gradient is taken; where the engine runs its
kernels the decode step's update is one Pallas call on the slots' pool
(``ssm_state_update_in_place``) and the prefill's scan one Pallas call a
layer (``ssm_chunk_scan``), both below.  The recurrence is

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (x) B_t
    y_t = h_t . C_t + D * x_t

per head, with ``h`` ``[head_dim, state]`` in float32, ``A`` a negative
scalar per head and ``B``/``C`` shared by the heads of a group.  Both
forms equal it (tests/test_ssm.py): the chunked form splits the sequence
into chunks of ``chunk`` positions, computes inside a chunk by one masked
[chunk, chunk] product per head (the decay between two positions is
``exp`` of a difference of cumulative sums) and carries one state across
chunks.  A position whose ``dt`` is 0 leaves the state as it was
(``exp(0) = 1`` and nothing is added), which is how a bucket's pad
positions are kept out of it.

``ssm_chunk_scan`` and ``ssm_state_update`` are the names the benchmark's
readers know the two by (benchmarks/trace/ssm_costs.py); a kernel that
replaces either keeps its name, as both kernels do (the XLA form of the
scan is ``ssm_chunk_scan_xla`` here and ``ssm_chunk_scan`` in its HLO).

The prefill's kernel (``ssm_chunk_scan``).  The XLA form writes, for every
chunk and head, the ``[chunk, chunk]`` float32 decay between the chunk's
positions to HBM and reads it again (64 KB a position and layer at both
cells' widths) in front of a product that needs 2.1 MFLOP.  The kernel's
grid walks (row, tile of heads, chunk), a tile's chunks one after
another, with the tile's state carried in VMEM: per step the group's
``C.B^T`` on the MXU, per head the decay ``exp(cum_t - cum_s)`` under the
causal mask built in VMEM (subtracted first, then ``exp``: the factors
alone overflow), the masked product with ``x``, what the carried state
adds to ``y`` and what the chunk adds to the state.  ``x``, ``B`` and
``C`` go to the MXU as the bfloat16 values they arrive as; whatever is
float32 by construction (the decay, ``dt``, the carried state) goes as
three bfloat16 pieces that add up to it (``_bf16_pieces``), accumulated
in float32, so no float32 factor is rounded to one bfloat16.  A tile is
the heads whose state is ``_SCAN_TILE_BYTES`` (thirty-two at both cells'
widths: half of granite's one group, two of nemotron's eight), the
carried state lies transposed (``[state, heads x head_dim]``) so that
the two products with it fill the MXU's 128 columns, and the heads go two
at a time, as many as fill 128 lanes.  The decay goes by blocks of 128
positions, so the blocks over a chunk's diagonal are never built, and a
chunk in which every ``dt`` is 0 (the tail of a padded bucket: a quarter
to a third of the cells' positions), told by a flag XLA computes from
``dt`` in front of the call, costs the carried state's product alone.
The cumulative sums (a [B, S, H] float32 array) stay in XLA in front of
the call too, and reach the kernel as XLA has them, heads on lanes: the
kernel rotates its tile's heads to the front and transposes them for the
rows it needs.  Two other layouts did not work (chip runs of PR 48): a
tile's sums handed over 16 lanes wide, as columns and as rows, cost 942 us
of cumulative sum over an array padded eightfold in HBM in front of a
705 us kernel (nemotron, 4,096 positions), and one operand with positions
on lanes made XLA lay the whole in-projection out with positions minor,
so that the convolution and the out-projection behind it ran a third
slower (granite's layer 3.6% slower than with the XLA scan).

The decode step's kernel (``ssm_state_update_in_place``).  XLA runs the
update as two passes over every slot's state (``h' = decay h + dx (x) B``
in place, then ``y = h' . C`` over what it wrote), for a slot that holds
no sequence as for one that does: at 2.1 MB a slot and layer
(granite-4.0-h-micro) the state is most of what a decode step moves.  The
kernel takes the pool's ``ssm_state`` [layers, slots, heads, head_dim,
state] whole and by reference, with the layer as a prefetched scalar (as
ops/paged_attention.py takes the pages: a layer sliced out and set back is
two copies of it), and hands it back through ``input_output_aliases``.
Its grid walks a LIST of the slots, the live ones first, and the tiles of
heads of each: a live slot's tile is read once, moved on in float32,
multiplied against ``C`` while it is held, and written where it was read;
the steps past the last live slot name the block the last live step left,
so nothing of a dead slot's state is read or written, and its ``y`` is
zeros.  A tile is as many heads as ``_TILE_BYTES`` holds, in whole groups
or in equal parts of one: a slot whole for granite-4.0-h-micro (one group
of 64 heads, 2.1 MB), four groups of sixteen for nemotron-3-super (2.1 MB,
two tiles a slot).  The state keeps its layout, head_dim on sublanes and
the state's axis on lanes, so what is a vector over head_dim (``x`` in,
``y`` out) enters and leaves as columns: the wrapper hands ``x`` over as
[slots, head_dim, heads] and takes ``y`` back so (two transposes of a few
hundred KB in XLA), a head's column is a static lane slice, and where a
slot has several tiles a lane rotation brings the tile's heads to the
front.  A head's decay is a scalar and comes through SMEM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES = 128
# the largest tile of one slot's state the kernel holds at once (it keeps
# four: two on their way in, two on their way out)
_TILE_BYTES = 2 << 20
# the state of the heads one grid step of the prefill's kernel works on
# (32 heads at both cells' widths; 16 cost nemotron's scan a tenth more, in
# fixed costs of twice the steps, 64 win 5% for twice the unrolled code)
_SCAN_TILE_BYTES = 1 << 20


def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                tail: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Depthwise causal convolution and SiLU over a sequence.

    x [B, S, C]; w [K, C] (tap ``K - 1`` meets the current position);
    b [C]; ``tail`` [B, K - 1, C] the inputs before ``x`` (zeros when
    None: a fresh sequence).  Returns silu(conv(x) + b) [B, S, C]."""
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    acc = b.astype(F32)[None, None, :]
    for j in range(k):
        acc = acc + padded[:, j:j + s].astype(F32) * w[j].astype(F32)
    return jax.nn.silu(acc).astype(x.dtype)


def conv_tail(x: jnp.ndarray, lengths: jnp.ndarray, k: int) -> jnp.ndarray:
    """The last ``k - 1`` TRUE inputs of each row: x [B, S, C] right-padded,
    lengths [B] -> [B, k - 1, C] (zeros stand before a row shorter than
    that), what a decode step's convolution needs of the past."""
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
        row, n, k - 1, axis=0))(padded, lengths)


def conv_step(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              tail: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One position of ``causal_conv``: x [B, C], tail [B, K - 1, C] ->
    (silu(conv) [B, C], the tail moved on by one)."""
    window = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    acc = jnp.einsum("bkc,kc->bc", window.astype(F32), w.astype(F32))
    out = jax.nn.silu(acc + b.astype(F32)).astype(x.dtype)
    return out, window[:, 1:].astype(tail.dtype)


def ssm_recurrence(x, dt, a, b, c, d, h0=None):
    """The recurrence itself, one position after another (the definition
    the other two forms are tested against; never on a serving path).

    x [B, S, H, P]; dt [B, S, H] (after softplus); a [H]; b, c
    [B, S, G, N]; d [H].  Returns (y [B, S, H, P] float32,
    h [B, H, P, N] float32)."""
    bt, _, h, p = x.shape
    if h0 is None:
        h0 = jnp.zeros((bt, h, p, b.shape[3]), F32)

    def step(hs, inp):
        x_t, dt_t, b_t, c_t = inp
        y_t, hs = ssm_state_update(hs, x_t, dt_t, a, b_t, c_t, d)
        return hs, y_t

    seq = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
           jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0))
    hs, ys = jax.lax.scan(step, h0.astype(F32), seq)
    return jnp.moveaxis(ys, 0, 1), hs


@jax.named_call
def ssm_state_update(h, x, dt, a, b, c, d):
    """One step of the recurrence for every sequence of a batch.

    h [B, H, P, N] (any float dtype; computed in float32); x [B, H, P];
    dt [B, H]; a [H]; b, c [B, G, N]; d [H].  Returns (y [B, H, P]
    float32, h' [B, H, P, N] in ``h``'s dtype)."""
    heads, groups = x.shape[1], b.shape[1]
    rep = heads // groups
    dt = dt.astype(F32)
    xf = x.astype(F32)
    bh = jnp.repeat(b.astype(F32), rep, axis=1)                # [B, H, N]
    ch = jnp.repeat(c.astype(F32), rep, axis=1)
    decay = jnp.exp(dt * a.astype(F32))                        # [B, H]
    new = (h.astype(F32) * decay[:, :, None, None]
           + (dt[:, :, None] * xf)[..., None] * bh[:, :, None, :])
    y = jnp.sum(new * ch[:, :, None, :], axis=-1) \
        + d.astype(F32)[None, :, None] * xf
    return y, new.astype(h.dtype)


class LiveSlots(NamedTuple):
    """The slots of a decode step as its state kernel walks them: ``order``
    [slots] int32, the slots that hold a sequence first (each group in
    slot order), and ``count`` [1] int32, how many of them do."""
    order: jnp.ndarray
    count: jnp.ndarray


def live_slots(live: jnp.ndarray) -> LiveSlots:
    """``live`` [slots] bool -> the list the kernel's grid walks (on the
    device: a stable sort of 64 flags, once a step)."""
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    return LiveSlots(order.astype(jnp.int32),
                     jnp.sum(live, dtype=jnp.int32).reshape(1))


def head_tile(heads: int, groups: int, head_bytes: int,
              tile_bytes: Optional[int] = None) -> int:
    """Heads a tile of a state kernel holds: as many as ``tile_bytes``
    (the decode step's ``_TILE_BYTES`` where None) holds, one at least,
    in whole groups or in equal parts of one."""
    rep = heads // groups
    if tile_bytes is None:
        tile_bytes = _TILE_BYTES
    return max(t for t in range(1, heads + 1)
               if heads % t == 0 and (rep % t == 0 or t % rep == 0)
               and (t == 1 or t * head_bytes <= tile_bytes))


def _bf16_pieces(v, n: int):
    """``v`` float32 as ``n`` bfloat16 arrays that add up to it, eight
    bits of it a piece: three carry a float32 whole."""
    pieces = []
    for _ in range(n):
        pieces.append(v.astype(jnp.bfloat16))
        v = v - pieces[-1].astype(F32)
    return pieces


def _state_update_kernel(layer_ref, order_ref, count_ref, decay_ref, xt_ref,
                         dt_ref, d_ref, b_ref, c_ref, h_ref, yt_ref, ho_ref,
                         *, tile: int, rep: int, n_tiles: int,
                         c_pieces: int):
    """Grid step (i, j): tile ``j`` of the ``i``-th slot of the list.

    ``decay_ref`` [slots, H] in SMEM (a head's decay is a scalar),
    ``xt_ref`` [1, P, Hp] the slot's ``x`` with heads on lanes, ``dt_ref``
    [1, 1, Hp], ``d_ref`` [1, Hp], ``b_ref``/``c_ref`` [1, G, N],
    ``h_ref``/``ho_ref`` [1, 1, tile, P, N] the tile of the state where it
    lies in the pool, ``yt_ref`` [1, P, Hp] the slot's ``y``, which stays
    in VMEM over the slot's tiles.  A step past the live slots is given
    the state block of the last live step, which it leaves alone, and its
    own slot's ``y`` block, which it zeroes.

    ``y = h' . C`` is a product on the MXU, which has nothing else to do
    here, against ``C`` laid on 128 rows, so that every lane of the result
    holds the head's ``y`` column: the 64 x 64 lane reductions a slot and
    layer on the XLU set the kernel's pace where the copies should (my
    chip run, PR 45).  ``h'`` goes in as three bfloat16 pieces and ``C``
    as ``c_pieces`` (one where the model's activations are bfloat16), the
    products of the leading orders summed in float32: a float32 product,
    in three MXU passes where ``C`` is one piece."""
    i, j = pl.program_id(0), pl.program_id(1)
    n_live = count_ref[0]
    width = xt_ref.shape[-1]

    def to_front(v):
        # this tile's heads to lanes 0 .. tile - 1
        if n_tiles == 1:
            return v
        return pltpu.roll(v, jax.lax.rem(width - j * tile, width), 1)

    @pl.when(i < n_live)
    def _live():
        slot = order_ref[i]
        xt = xt_ref[0]                                         # [P, Hp]
        dx = to_front(xt * dt_ref[0])
        lane = jax.lax.broadcasted_iota(jnp.int32, xt.shape, 1)
        yt = jnp.zeros_like(xt)
        for hh in range(tile):
            if hh % rep == 0:
                # the head's group: the tile's first, or a later whole one
                group = (j * tile) // rep + hh // rep
                b = b_ref[0, pl.ds(group, 1), :]               # [1, N]
                cs = [jnp.broadcast_to(piece, (_LANES, piece.shape[-1]))
                      for piece in _bf16_pieces(
                          c_ref[0, pl.ds(group, 1), :], c_pieces)]
            new = (h_ref[0, 0, hh].astype(F32)
                   * decay_ref[slot, j * tile + hh]
                   + dx[:, hh:hh + 1] * b)                     # [P, N]
            ho_ref[0, 0, hh] = new.astype(ho_ref.dtype)
            ycol = sum(
                jax.lax.dot_general(p, q, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32)
                for a, p in enumerate(_bf16_pieces(new, 3))
                for k, q in enumerate(cs) if a + k < 3)        # [P, 128]
            yt = jnp.where(lane == hh, ycol[:, :width], yt)
        if n_tiles > 1:
            yt = pltpu.roll(yt, j * tile, 1)

        @pl.when(j == 0)
        def _first():
            yt_ref[0] = xt * d_ref[...]

        yt_ref[0] += yt

    @pl.when(i >= n_live)
    def _dead():
        yt_ref[...] = jnp.zeros_like(yt_ref)

    @pl.when(n_live == 0)
    def _nothing_live():
        # every step names one block of the state, which is written back
        # once: as it was
        ho_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_state_update_in_place(state, layer, x, dt, a, b, c, d,
                              slots: LiveSlots, *,
                              interpret: Optional[bool] = None):
    """``ssm_state_update`` for the slots that hold a sequence, on the
    pool: ``state`` [L, slots, H, P, N] every Mamba layer's state as the
    engine's pool keeps it, of which layer ``layer`` (an int or a traced
    scalar) is moved on where it lies; x [slots, H, P]; dt [slots, H];
    a, d [H]; b, c [slots, G, N]; ``slots`` from ``live_slots``.  Returns
    (y [slots, H, P] float32, zeros for a slot that holds nothing,
    state' in the buffer ``state`` came in where that was donated).
    Nothing of a dead slot's state, and nothing of another layer, is
    read or written."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_slots, heads, p = x.shape
    groups, n = b.shape[1], b.shape[2]
    head_bytes = p * n * state.dtype.itemsize
    tile = head_tile(heads, groups, head_bytes)
    n_tiles = heads // tile
    # heads on lanes; a rotation wants whole vregs of them
    width = heads if n_tiles == 1 else -(-heads // _LANES) * _LANES

    def lanes(v):
        v = v.astype(F32)
        return jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, width - heads)])

    dt = dt.astype(F32)
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), slots.order,
               slots.count, jnp.exp(dt * a.astype(F32)))

    def listed(i, order_ref, count_ref):
        # the list's i-th slot, and past the live ones the last of them
        return order_ref[jnp.minimum(i, jnp.maximum(count_ref[0] - 1, 0))]

    def of_slot(*block):
        return pl.BlockSpec(block, lambda i, j, _, order_ref, count_ref, __: (
            listed(i, order_ref, count_ref),) + (0,) * (len(block) - 1))

    tile_spec = pl.BlockSpec(
        (1, 1, tile, p, n), lambda i, j, layer_ref, order_ref, count_ref, _: (
            layer_ref[0], listed(i, order_ref, count_ref),
            jnp.where(i < count_ref[0], j, n_tiles - 1), 0, 0))
    yt, state = pl.pallas_call(
        functools.partial(_state_update_kernel, tile=tile,
                          rep=heads // groups, n_tiles=n_tiles,
                          c_pieces=1 if c.dtype == jnp.bfloat16 else 3),
        name="ssm_state_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_slots, n_tiles),
            in_specs=[of_slot(1, p, width), of_slot(1, 1, width),
                      pl.BlockSpec((1, width), lambda i, j, *s: (0, 0)),
                      of_slot(1, groups, n), of_slot(1, groups, n),
                      tile_spec],
            out_specs=[
                pl.BlockSpec((1, p, width), lambda i, j, _, order_ref, *s: (
                    order_ref[i], 0, 0)),
                tile_spec],
        ),
        out_shape=(jax.ShapeDtypeStruct((n_slots, p, width), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={len(scalars) + 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # two tiles on their way in, two on their way out
            vmem_limit_bytes=4 * tile * head_bytes + (16 << 20)),
        interpret=interpret,
    )(*scalars, lanes(jnp.swapaxes(x, 1, 2)), lanes(dt)[:, None],
      lanes(d)[None], b.astype(F32), c.astype(F32), state)
    return jnp.swapaxes(yt[:, :, :heads], 1, 2), state


def _whole_chunks(x, dt, b, c, chunk: int):
    """The scan's sequences padded to whole chunks (``dt = 0`` there)."""
    pad = -x.shape[1] % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    return x, dt, b, c


@functools.partial(jax.named_call, name="ssm_chunk_scan")
def ssm_chunk_scan_xla(x, dt, a, b, c, d, chunk: int):
    """The recurrence over whole sequences from a zero state, in chunks:
    the XLA form (a CPU, a mesh, differentiation, the tests' yardstick;
    ``ssm_chunk_scan`` is the kernel).

    Arguments as ``ssm_recurrence``; ``chunk`` positions a chunk (the
    sequence is padded to a multiple with ``dt = 0``).  Returns
    (y [B, S, H, P] float32, h [B, H, P, N] float32 after the last
    position whose ``dt`` is not 0)."""
    bt, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    q = chunk
    x, dt, b, c = _whole_chunks(x, dt, b, c, q)
    nc = x.shape[1] // q
    xf = x.astype(F32).reshape(bt, nc, q, g, rep, p)
    dtf = dt.astype(F32).reshape(bt, nc, q, g, rep)
    bf = b.astype(F32).reshape(bt, nc, q, g, n)
    cf = c.astype(F32).reshape(bt, nc, q, g, n)
    # cumulative log-decay inside a chunk, heads before positions
    cum = jnp.cumsum(dtf * a.astype(F32).reshape(g, rep), axis=2)
    cum = jnp.moveaxis(cum, 2, -1)                     # [B, nc, G, R, q]
    dx = dtf[..., None] * xf                           # [B, nc, q, G, R, P]

    # inside a chunk: y[t] += sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) dx_s
    cb = jnp.einsum("bctgn,bcsgn->bcgts", cf, bf)      # [B, nc, G, q, q]
    seg = cum[..., :, None] - cum[..., None, :]        # [B, nc, G, R, t, s]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = jnp.einsum("bcgrts,bcsgrp->bctgrp", decay * cb[:, :, :, None], dx)

    # what a chunk adds to the state, as seen at the chunk's end
    to_end = jnp.exp(cum[..., -1:] - cum)              # [B, nc, G, R, q]
    added = jnp.einsum("bcgrs,bcsgrp,bcsgn->bcgrpn", to_end, dx, bf)
    whole = jnp.exp(cum[..., -1])                      # [B, nc, G, R]

    def carry(hs, inp):
        add_c, whole_c = inp
        return hs * whole_c[..., None, None] + add_c, hs

    h0 = jnp.zeros((bt, g, rep, p, n), F32)
    hs, before = jax.lax.scan(
        carry, h0, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                # [B, nc, G, R, P, N]
    # the state a chunk starts from, decayed to each of its positions
    y = y + jnp.einsum("bctgn,bcgrpn,bcgrt->bctgrp", cf, before,
                       jnp.exp(cum))
    y = y + d.astype(F32).reshape(g, rep)[:, :, None] * xf
    y = y.reshape(bt, nc * q, h, p)[:, :s]
    return y, hs.reshape(bt, h, p, n)


def _pieces_of(v):
    """A matmul operand as the bfloat16 arrays that add up to it: itself
    where it is bfloat16, three pieces of a float32."""
    return [v] if v.dtype == jnp.bfloat16 else _bf16_pieces(v.astype(F32), 3)


def _dot(lhs, rhs, contract):
    """``lhs . rhs`` over the ``contract`` axes as a float32 product on the
    MXU: every pair of pieces whose orders add up to under three, summed in
    float32 (one pass for two bfloat16 operands, three where one of them
    is float32)."""
    return sum(
        jax.lax.dot_general(l, r, (contract, ((), ())),
                            preferred_element_type=F32)
        for i, l in enumerate(_pieces_of(lhs))
        for k, r in enumerate(_pieces_of(rhs)) if i + k < 3)


def _chunk_scan_kernel(live_ref, x_ref, b_ref, c_ref, cols_ref, d_ref,
                       y_ref, h_ref, state, *, tile: int, rep: int, p: int,
                       n: int, slab: int):
    """Grid step (row, tile of heads, chunk), the chunks of a tile one
    after another: ``state`` [N, tile x P] float32 carries the tile's state
    from chunk to chunk, transposed (the state's axis on sublanes, heads x
    head_dim on lanes) so that the two products with it are as wide as the
    MXU.

    ``live_ref`` [B, tiles, chunks] in SMEM: whether the chunk holds a
    position whose ``dt`` is not 0; ``x_ref``/``y_ref`` [1, Q, tile x P],
    ``b_ref``/``c_ref`` [1, Q, groups of the tile x N], ``cols_ref`` [1, 2,
    Q, H] the cumulative log-decay and ``dt`` of EVERY head as XLA has
    them, positions on sublanes (the tile's heads are rotated to the
    front; a head's decay between two positions needs one as a column and
    one as a row, and the rows are the tile's columns transposed, here:
    an operand with the positions on lanes made XLA lay the whole
    in-projection out that way), ``d_ref`` [1, 1, tile], ``h_ref`` [1,
    tile, P, N] the state after the row's last chunk.

    The heads go ``slab`` at a time, as many as fill 128 lanes: what is a
    scalar a head and position is spread over the slab's lanes by selects,
    and a head's masked product meets ``x`` with the other heads' lanes
    zeroed, so a slab's ``y`` is one sum.  Every float32 factor (the decay,
    ``dt``, the carried state) goes to the MXU as three bfloat16 pieces
    (``_dot``).  A chunk that is all padding (a bucket's tail) leaves the
    state alone and adds nothing of its own to ``y``: it costs the carried
    state's product alone."""
    j, ci = pl.program_id(1), pl.program_id(2)
    live = live_ref[pl.program_id(0), j, ci] != 0
    per_group = min(tile, rep)
    d = d_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, slab * p), 1)

    def spread(v, first):
        # v [rows, tile] -> [rows, slab x P]: each head's column over its P
        out = v[:, first:first + 1]
        for k in range(1, slab):
            out = jnp.where(lane >= k * p, v[:, first + k:first + k + 1], out)
        return out

    def slabs():
        # (group of the tile, first head of the slab, the slab's lanes)
        for g in range(tile // per_group):
            for s in range(per_group // slab):
                first = g * per_group + s * slab
                yield (slice(g * n, (g + 1) * n), first,
                       slice(first * p, (first + slab) * p))

    @pl.when(ci == 0)
    def _fresh():
        state[...] = jnp.zeros_like(state)

    @pl.when(jnp.logical_not(live))
    def _all_padding():
        # y is what the standing state gives against each position's C,
        # and D x
        for group, first, lanes in slabs():
            y_ref[0, :, lanes] = (
                _dot(c_ref[0, :, group], state[:, lanes], ((1,), (0,)))
                + spread(d, first) * x_ref[0, :, lanes].astype(F32))

    @pl.when(live)
    def _chunk():
        q, heads = cols_ref.shape[2], cols_ref.shape[3]

        def of_tile(v):
            # [Q, H] -> the tile's columns [Q, tile] and, transposed by
            # whole tiles of 128, its rows [tile, Q]
            fill = -heads % _LANES
            if fill:
                v = jnp.concatenate([v, jnp.zeros((q, fill), F32)], axis=1)
            if tile < heads:
                width = heads + fill
                v = pltpu.roll(v, jax.lax.rem(width - j * tile, width), 1)
            return v[:, :tile], v[:, :tile + -tile % _LANES].T[:tile]

        (cum_q, cum_r), (dt_q, dt_r) = (of_tile(cols_ref[0, 0]),
                                        of_tile(cols_ref[0, 1]))
        last = cum_q[q - 1:q, :]
        from_start = jnp.exp(cum_q)
        to_end = jnp.exp(last - cum_q) * dt_q
        whole = jnp.exp(last)
        causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
                  >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
        # the decay goes by blocks of 128 positions: the blocks over the
        # diagonal are never built
        blk = _LANES if q % _LANES == 0 else q
        cb = None
        for group, first, lanes in slabs():
            bg, cg = b_ref[0, :, group], c_ref[0, :, group]        # [Q, N]
            if first % per_group == 0:
                cb = jnp.where(causal, _dot(cg, bg, ((1,), (1,))), 0.0)
            xs = x_ref[0, :, lanes]                        # [Q, slab x P]
            xf = xs.astype(F32)
            before = state[:, lanes]                       # [N, slab x P]
            y = (spread(from_start, first) * _dot(cg, before, ((1,), (0,)))
                 + spread(d, first) * xf)
            own = [xs if slab == 1 else jnp.where(
                (lane >= k * p) & (lane < (k + 1) * p), xs,
                jnp.zeros_like(xs)) for k in range(slab)]
            for t0 in range(0, q, blk):
                yt = y[t0:t0 + blk]
                for k in range(slab):
                    h = first + k
                    for s0 in range(0, t0 + blk, blk):
                        # the decay between two positions: subtracted,
                        # then exp
                        seg = (cum_q[t0:t0 + blk, h:h + 1]
                               - cum_r[h:h + 1, s0:s0 + blk])
                        m = (jnp.exp(jnp.minimum(seg, 0.0))
                             * cb[t0:t0 + blk, s0:s0 + blk]
                             * dt_r[h:h + 1, s0:s0 + blk])
                        yt = yt + _dot(m, own[k][s0:s0 + blk],
                                       ((1,), (0,)))
                y_ref[0, t0:t0 + blk, lanes] = yt
            state[:, lanes] = spread(whole, first) * before + _dot(
                bg, xf * spread(to_end, first), ((0,), (0,)))

    @pl.when(ci == pl.num_programs(2) - 1)
    def _last():
        h_ref[0] = state[...].T.reshape(tile, p, n)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_chunk_scan(x, dt, a, b, c, d, chunk: int, *,
                   interpret: Optional[bool] = None):
    """``ssm_chunk_scan_xla`` as one Pallas kernel (same arguments, same
    results): a chunk's decay between its positions is built in VMEM and
    never written out, and the state is carried from chunk to chunk in
    VMEM.  The cumulative sums (a [B, S, H] float32 array) are XLA's, in
    front of the call."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bt, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep, q = h // g, chunk
    x, dt, b, c = _whole_chunks(x, dt, b, c, q)
    nc = x.shape[1] // q
    tile = head_tile(h, g, p * n * 4, _SCAN_TILE_BYTES)
    nt, groups, per_group = h // tile, max(1, tile // rep), min(tile, rep)
    # heads that go side by side: as many of a group as fill 128 lanes
    slab = max(k for k in range(1, per_group + 1)
               if per_group % k == 0 and k * p <= max(p, _LANES))
    dtf = dt.astype(F32).reshape(bt, nc, q, h)
    cum = jnp.cumsum(dtf * a.astype(F32), axis=2)
    cols = jnp.stack([cum, dtf], axis=1).reshape(bt, 2, nc * q, h)
    # the chunks of a tile that hold a position whose dt is not 0
    live = jnp.any((dtf != 0).reshape(bt, nc, q, nt, tile), axis=(2, 4))
    live = jnp.swapaxes(live, 1, 2).astype(jnp.int32)          # [B, nt, nc]

    def of_group(bi, j, ci, _):
        return bi, ci, (j * tile) // rep // groups

    positions = pl.BlockSpec((1, q, tile * p),
                             lambda bi, j, ci, _: (bi, ci, j))
    y, state = pl.pallas_call(
        functools.partial(_chunk_scan_kernel, tile=tile, rep=rep, p=p, n=n,
                          slab=slab),
        name="ssm_chunk_scan",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bt, nt, nc),
            in_specs=[
                positions,
                pl.BlockSpec((1, q, groups * n), of_group),
                pl.BlockSpec((1, q, groups * n), of_group),
                pl.BlockSpec((1, 2, q, h),
                             lambda bi, j, ci, _: (bi, 0, ci, 0)),
                pl.BlockSpec((1, 1, tile), lambda bi, j, ci, _: (j, 0, 0)),
            ],
            out_specs=[
                positions,
                pl.BlockSpec((1, tile, p, n),
                             lambda bi, j, ci, _: (bi, j, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((n, tile * p), F32)]),
        out_shape=(jax.ShapeDtypeStruct((bt, nc * q, h * p), F32),
                   jax.ShapeDtypeStruct((bt, h, p, n), F32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(live, x.reshape(bt, nc * q, h * p), b.reshape(bt, nc * q, g * n),
      c.reshape(bt, nc * q, g * n), cols,
      d.astype(F32).reshape(nt, 1, tile))
    return y.reshape(bt, nc * q, h, p)[:, :s], state
