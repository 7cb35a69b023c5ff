"""Attention ops (XLA path).

These are the reference implementations every kernel must match: pure
jnp/lax, static shapes, fused by XLA onto MXU/VPU.  The Pallas flash /
paged-attention kernels (ops/flash_attention.py, ops/paged_attention.py)
are drop-in replacements validated against these in tests.

Two entry points because inference has two phases:
- ``causal_attention``  — prefill: [B, S] queries attend causally to [B, S].
- ``decode_attention``  — decode: [B, 1] queries attend to a KV cache of
  [B, S_max] with per-slot valid lengths (continuous batching: every slot
  sits at a different position).
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def repeat_kv(kv: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[B, S, n_kv, d] -> [B, S, n_kv*n_rep, d] (GQA head expansion)."""
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def causal_attention(
    q: jnp.ndarray,          # [B, S, n_heads, d]
    k: jnp.ndarray,          # [B, S, n_kv, d]
    v: jnp.ndarray,          # [B, S, n_kv, d]
    seq_lens: jnp.ndarray,   # [B] valid lengths (right-padded inputs)
    q_offset: jnp.ndarray | None = None,  # [B] absolute pos of q[...,0,...]
    window: int = 0,
) -> jnp.ndarray:
    """Causal softmax attention for prefill.  Returns [B, S, n_heads, d].

    ``window`` > 0: a sliding layer, position ``i`` sees the ``window``
    positions up to itself (``max(0, i - window + 1) .. i``).

    ``q_offset`` supports chunked prefill: queries at absolute positions
    offset+i attend to cached keys 0..offset+i (keys here are the chunk only
    when offset==0 covers the plain case).
    """
    b, s, n_heads, d = q.shape
    s_k = k.shape[1]          # == s for plain prefill; cache width if chunked
    n_rep = n_heads // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)

    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    # [B, H, S, S_k]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale

    q_pos = jnp.arange(s)[None, :]                       # [1, S]
    if q_offset is not None:
        q_pos = q_pos + q_offset[:, None]                # [B, S]
    k_pos = jnp.arange(s_k)[None, :]                     # [1, S_k]
    causal = q_pos[:, :, None] >= k_pos[:, None, :]      # [B, S, S_k]
    if window:
        causal &= q_pos[:, :, None] - k_pos[:, None, :] < window
    valid = k_pos[:, None, :] < seq_lens[:, None, None]  # [B, 1->S, S_k]
    mask = (causal & valid)[:, None, :, :]               # [B, 1, S, S_k]

    logits = jnp.where(mask, logits, NEG_INF)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,          # [B, 1, n_heads, d]
    k_cache: jnp.ndarray,    # [B, S_max, n_kv, d]
    v_cache: jnp.ndarray,    # [B, S_max, n_kv, d]
    lengths: jnp.ndarray,    # [B] tokens valid in cache (incl. current)
    starts: jnp.ndarray | None = None,   # [B] first position seen
) -> jnp.ndarray:
    """Single-step decode attention over the slot cache.  [B, 1, n_heads, d].

    The T=1 case of ``decode_attention_multi`` (delegated so the two paths
    cannot drift numerically).  ``starts``: a window layer's query sees
    cache positions ``starts[b] .. lengths[b] - 1``."""
    return decode_attention_multi(q, k_cache, v_cache, lengths, starts)


def decode_attention_multi(
    q: jnp.ndarray,          # [B, T, n_heads, d] queries at pos lengths-1+i
    k_cache: jnp.ndarray,    # [B, S_max, n_kv, d]
    v_cache: jnp.ndarray,    # [B, S_max, n_kv, d]
    lengths: jnp.ndarray,    # [B] tokens valid incl. the FIRST query token
    starts: jnp.ndarray | None = None,   # [B] first position seen
) -> jnp.ndarray:
    """Multi-token decode attention (speculative verification): query i of
    slot b attends to cache positions < lengths[b] + i.  [B, T, n_heads, d].
    """
    b, s_max, n_kv, d = k_cache.shape
    t, n_heads = q.shape[1], q.shape[2]
    n_rep = n_heads // n_kv
    k = repeat_kv(k_cache, n_rep)
    v = repeat_kv(v_cache, n_rep)

    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale    # [B, H, T, S_max]
    k_pos = jnp.arange(s_max)[None, None, :]              # [1, 1, S]
    limit = lengths[:, None, None] + jnp.arange(t)[None, :, None]  # [B, T, 1]
    mask = (k_pos < limit)[:, None]                       # [B, 1, T, S]
    if starts is not None:
        mask &= (k_pos >= starts[:, None, None])[:, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
