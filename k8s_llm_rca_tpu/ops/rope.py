"""Rotary position embeddings (rotate-half / NeoX convention, as used by the
Llama & Mixtral families)."""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq_len: int, theta: float) -> jnp.ndarray:
    """[max_seq_len, head_dim//2] complex-free angle table (fp32)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    return jnp.outer(t, inv_freq)  # [S, D/2]


def apply_rope(x: jnp.ndarray, angles: jnp.ndarray, positions: jnp.ndarray,
               interleaved: bool = False) -> jnp.ndarray:
    """Rotate ``x`` [B, S, n_heads, head_dim] by per-token angles.

    ``positions`` is [B, S] absolute token positions (continuous batching means
    each slot sits at its own offset, so positions are data, not an iota).

    ``interleaved``: the pairs a projection emits are ``(2i, 2i + 1)``
    (``rope_interleave`` of deepseek_v3) and not ``(i, i + d/2)``: the
    values are de-interleaved (evens, then odds) and then rotated as
    halves, as the published code does, and STAY in that order: queries
    and keys are permuted alike, so their products are the published ones.
    """
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = angles[positions]                      # [B, S, D/2]
    cos = jnp.cos(ang)[:, :, None, :]            # [B, S, 1, D/2]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    return jnp.concatenate([rx1, rx2], axis=-1).astype(x.dtype)
