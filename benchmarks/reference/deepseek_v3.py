"""Plain reference of ``deepseek_v3`` as kanana-2-30b-a3b publishes it
(``q_lora_rank`` null): the full forward pass over one whole sequence in
float32 ``jax.numpy`` at ``highest`` matmul precision, in the UN-ABSORBED
form, with no cache, no kernel, no batching, an explicit mask in every
attention layer and none of the program's model code.

Every layer ``l`` is ``x = x + Attn_l(RMSNorm(x))``, ``x = x +
MLP_l(RMSNorm(x))`` (eps ``rms_norm_eps``); a final RMSNorm, then the untied
head.

- ``Attn_l`` (latent attention), ``h`` the normed stream: ``q = h Wq``
  (heads x ``qk_head_dim``), split a head into ``q_nope``
  (``qk_nope_head_dim``) and ``q_rope`` (``qk_rope_head_dim``); ``h W_kva``
  splits into the latent ``c`` (``kv_lora_rank``) and ONE ``k_rope``
  (``qk_rope_head_dim``) a token, shared by all heads; ``c <- RMSNorm(c)``
  with its own gain; the rotary embedding at ``rope_theta`` on ``q_rope`` and
  ``k_rope``, the pairs taken interleaved (``rope_interleave``: values
  ``2i`` and ``2i + 1`` are a pair; they are de-interleaved, evens then odds,
  and rotated as halves, as the published code does); ``c W_kvb`` (heads x
  (``qk_nope_head_dim`` + ``v_head_dim``)) splits a head into ``k_nope`` and
  ``v``; ``k = [k_nope, k_rope]``; scores ``q . k / sqrt(qk_head_dim)`` under
  the causal mask, a head (its slices of ``Wq``, ``W_kvb`` and ``Wo``
  included) and a block of ``QUERY_BLOCK`` queries at a time so that one
  head's keys and values and ``[QUERY_BLOCK, S]`` scores are all that is
  held; ``x + sum over heads of (P v) Wo_head``.
- ``MLP_l`` is ``reference/exaone_moe.py``'s own (the same router in other
  numbers: a dense SwiGLU in the ``first_k_dense_replace`` leading layers;
  behind them sigmoid scores over ``router_n_experts``, the top
  ``num_experts_per_tok`` of score + bias, weights ``routed_scaling_factor x
  s / sum(s chosen)``, the chosen experts HELD HERE one at a time, and the
  shared expert, ``n_shared_experts`` of them as one SwiGLU of their summed
  width).

Departures from the published model, each also in the configuration file:
the chip's share of the experts and of the vocabulary (``reduced``: the
router scores all ``router_n_experts`` and normalises over all the chosen,
the sum runs over those held, ``first_routed_expert`` .. ``+
n_routed_experts - 1``, and nothing stands in for the absent ones; embedding
and head are the chip's ``vocab_size`` rows), and the stack's first
``num_hidden_layers`` layers.

``forward`` also hands out what a cache must hold of the sequence, in
float32: ``latent`` ``[layers, S, kv_lora_rank + qk_rope_head_dim]``, each
token's normed latent and behind it its rotated key (in the de-interleaved
order the products are taken in), and nothing else; and ``latent_grain``,
what those rows keep below the grain of a token's int8 grid
(``reference/nemotron_h.py::below_int8``), where the file leaves the cache in
the activations' type: the reading that tells int8 rows from what the file
states.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.decoder import _rms_norm, embed, head, weight
from benchmarks.reference.exaone_moe import mlp
from benchmarks.reference.nemotron_h import below_int8

F32 = jnp.float32
QUERY_BLOCK = 2048


def _rope_interleaved(x, theta):
    """x [S, heads, d], positions 0..S-1, the pairs (2i, 2i + 1):
    de-interleaved, then rotated as halves; the result stays
    de-interleaved."""
    s, _, d = x.shape
    x1, x2 = x[..., 0::2], x[..., 1::2]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_heads", "rank", "nope", "rope", "v_dim",
                                   "eps", "theta"))
def attention(x, p, *, n_heads, rank, nope, rope, v_dim, eps, theta):
    """x [S, H] -> (x', the rows a cache holds [S, rank + rope]).  One head
    at a time, its slices of the three projections included, so that a
    12k-token sequence holds no array of every head's queries, keys or
    values."""
    with jax.default_matmul_precision("highest"):
        s, hidden = x.shape
        h = _rms_norm(x, p["attn_norm"], eps)
        kva = h @ weight(p["w_kva"])
        c = _rms_norm(kva[:, :rank], p["kv_norm"], eps)
        k_rope = _rope_interleaved(kva[:, None, rank:], theta)[:, 0]
        blocks = -(-s // QUERY_BLOCK)
        j = jnp.arange(s)[None, :]
        # by head: [heads, H, nope + rope], [heads, rank, nope + v], [heads, v, H]
        wq = weight(p["wq"]).reshape(hidden, n_heads, -1).transpose(1, 0, 2)
        w_kvb = weight(p["w_kvb"]).reshape(rank, n_heads, -1).transpose(
            1, 0, 2)
        wo = weight(p["wo"]).reshape(n_heads, v_dim, hidden)

        def one_head(out, w):
            wq_h, w_kvb_h, wo_h = w
            q = h @ wq_h                                       # [S, nope+rope]
            q = jnp.concatenate([q[:, :nope], _rope_interleaved(
                q[:, None, nope:], theta)[:, 0]], -1)
            q = jnp.pad(q, ((0, blocks * QUERY_BLOCK - s), (0, 0)))
            kv = c @ w_kvb_h                                   # [S, nope+v]
            k = jnp.concatenate([kv[:, :nope], k_rope], -1)

            def one_block(b):
                qb = jax.lax.dynamic_slice_in_dim(q, b * QUERY_BLOCK,
                                                  QUERY_BLOCK)
                i = b * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)[:, None]
                scores = qb @ k.T / jnp.sqrt(F32(nope + rope))
                probs = jax.nn.softmax(
                    jnp.where(j <= i, scores, -jnp.inf), -1)   # the mask
                return probs @ kv[:, nope:]

            attn = jax.lax.map(one_block, jnp.arange(blocks)).reshape(
                blocks * QUERY_BLOCK, v_dim)[:s]
            return out + attn @ wo_h, None

        out, _ = jax.lax.scan(one_head, x, (wq, w_kvb, wo))
        return out, jnp.concatenate([c, k_rope], -1)


def forward(conf, params, tokens, positions):
    """One sequence ``tokens`` [S], from the configuration file's published
    keys: float32 logits [len(positions), V] at the given positions, and
    what a cache must hold of the sequence (the top of the file)."""
    eps = float(conf["rms_norm_eps"])
    if conf["qk_head_dim"] != conf["qk_nope_head_dim"] \
            + conf["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    x = embed(params["embedding"], jnp.asarray(tokens, jnp.int32))
    rows = []
    for p in params["layers"][:conf["num_hidden_layers"]]:
        x, row = attention(
            x, p, n_heads=conf["num_attention_heads"],
            rank=conf["kv_lora_rank"], nope=conf["qk_nope_head_dim"],
            rope=conf["qk_rope_head_dim"], v_dim=conf["v_head_dim"],
            eps=eps, theta=float(conf["rope_theta"]))
        x = mlp(x, p, top_k=conf["num_experts_per_tok"],
                scaling=float(conf["routed_scaling_factor"]),
                first=conf["first_routed_expert"], eps=eps)
        rows.append(np.asarray(row))
    out = head(x[jnp.asarray(positions)], params["final_norm"],
               params["lm_head"], eps=eps)
    held = {"latent": np.stack(rows)}
    if conf.get("kv_cache_dtype") is None:
        held["latent_grain"] = below_int8(held["latent"])
    return out, held


def logits(conf, params, tokens, positions) -> jnp.ndarray:
    """The logits of ``forward`` alone."""
    return forward(conf, params, tokens, positions)[0]
