"""Plain reference of the decoder family both configurations belong to
(Mistral-7B dense, Mixtral-8x7B sparse experts): the full forward pass over
a whole sequence in float32 ``jax.numpy`` at ``highest`` matmul precision,
with no cache, no kernel, no batching and none of the program's model code.

Pre-norm blocks: RMSNorm -> grouped-query causal attention with rotate-half
RoPE -> residual; RMSNorm -> SwiGLU MLP, or a top-k router over SwiGLU experts
whose softmax is taken over the selected logits (Mixtral's) -> residual;
final RMSNorm -> untied output head.  Logits in float32 for every position.

The weights are the ones the engine serves, read from their storage form
(``q`` int8 or split-half nibble-packed int4, with a broadcast-ready
``scale``) into float32: quantization is part of the configuration, so both
sides see the same numbers.  Departure from the published model: none in the
mathematics; the KV cache's int8 rounding exists only on the engine's side
and is part of what the tolerances in ``lib/correct.py`` cover.  ``forward``
also hands out each layer's keys and values as a cache would hold them, for
the check to hold the engine's cache against.

One jitted layer is called once per layer (the layers share their shapes), and
the experts run one at a time under ``lax.map``, so that a layer's float32
weights never sit in memory all at once.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def weight(w) -> jnp.ndarray:
    """Storage form -> float32 matrix."""
    if not hasattr(w, "q"):
        return jnp.asarray(w, F32)
    q = w.q
    if type(w).__name__ == "QuantTensor4":
        lo = jnp.bitwise_and(q, jnp.int8(0x0F))
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = jnp.right_shift(q, 4)
        q = jnp.concatenate([lo, hi], axis=-1)
    return q.astype(F32) * w.scale.astype(F32)


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(F32)


def _rope(x, theta):
    """x [S, heads, d], positions 0..S-1, rotate-half convention."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps",
                                   "theta", "top_k"))
def layer(x, p, *, n_heads, n_kv, head_dim, eps, theta, top_k):
    """One block over x [S, H] (float32): the block's output, and the keys
    (rotated) and values [S, n_kv * head_dim] that a cache would hold."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        h = _rms_norm(x, p["attn_norm"], eps)
        q = _rope((h @ weight(p["wq"])).reshape(s, n_heads, head_dim), theta)
        k = _rope((h @ weight(p["wk"])).reshape(s, n_kv, head_dim), theta)
        v = (h @ weight(p["wv"])).reshape(s, n_kv, head_dim)
        held = k.reshape(s, -1), v.reshape(s, -1)
        group = n_heads // n_kv
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)
        x = x + attn @ weight(p["wo"])

        h = _rms_norm(x, p["mlp_norm"], eps)
        if "router" not in p:
            return (x + _swiglu(h, weight(p["w_gate"]), weight(p["w_up"]),
                                weight(p["w_down"])), *held)
        logits = h @ weight(p["router"])                       # [S, E]
        top_v, top_i = jax.lax.top_k(logits, top_k)
        share = jnp.zeros_like(logits).at[
            jnp.arange(s)[:, None], top_i].set(jax.nn.softmax(top_v, -1))
        n_experts = logits.shape[-1]

        def one_expert(e):
            pick = lambda w: type(w)(*(a[e] for a in w)) \
                if hasattr(w, "q") else w[e]
            return _swiglu(h, weight(pick(p["w_gate"])),
                           weight(pick(p["w_up"])),
                           weight(pick(p["w_down"])))

        outs = jax.lax.map(one_expert, jnp.arange(n_experts))  # [E, S, H]
        return (x + jnp.einsum("esh,se->sh", outs, share), *held)


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ weight(lm_head).T


@jax.jit
def embed(table, tokens):
    if not hasattr(table, "q"):
        return table[tokens].astype(F32)
    rows = type(table)(table.q[tokens], table.scale[tokens])
    return weight(rows)


def forward(conf, params, tokens, positions):
    """One sequence ``tokens`` [S], from the configuration file's published
    keys: float32 logits [len(positions), V] at the given positions, and
    what a cache of keys and values holds of the sequence, ``{"k", "v"}``,
    each float32 [layers, S, n_kv * head_dim] (keys rotated)."""
    x = embed(params["embedding"], jnp.asarray(tokens, jnp.int32))
    kw = dict(n_heads=conf["num_attention_heads"],
              n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
              eps=float(conf["rms_norm_eps"]),
              theta=float(conf["rope_theta"]),
              top_k=conf.get("num_experts_per_tok", 0))
    keys, values = [], []
    for p in params["layers"]:
        x, k, v = layer(x, p, **kw)
        keys.append(np.asarray(k))
        values.append(np.asarray(v))
    out_w = params["embedding"] if conf["tie_word_embeddings"] \
        else params["lm_head"]
    out = head(x[jnp.asarray(positions)], params["final_norm"], out_w,
               eps=float(conf["rms_norm_eps"]))
    return out, {"k": np.stack(keys), "v": np.stack(values)}


def logits(conf, params, tokens, positions) -> jnp.ndarray:
    """The logits of ``forward`` alone."""
    return forward(conf, params, tokens, positions)[0]
