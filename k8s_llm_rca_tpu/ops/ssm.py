"""Mamba-2 state-space ops: the chunked scan a prefill runs, the one-step
update a decode step runs, and the depthwise causal convolution in front
of both.  Plain XLA (einsums and one ``lax.scan`` over chunks): the
recurrence is

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
replaces either keeps its name.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


@jax.named_call
def ssm_chunk_scan(x, dt, a, b, c, d, chunk: int):
    """The recurrence over whole sequences from a zero state, in chunks.

    Arguments as ``ssm_recurrence``; ``chunk`` positions a chunk (the
    sequence is padded to a multiple with ``dt = 0``).  Returns
    (y [B, S, H, P] float32, h [B, H, P, N] float32 after the last
    position whose ``dt`` is not 0)."""
    bt, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    q = chunk
    pad = -s % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (s + pad) // q
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
