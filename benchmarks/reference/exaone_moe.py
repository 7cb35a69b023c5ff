"""Plain reference of ``exaone_moe`` (K-EXAONE-236B-A23B): the full forward
pass over one whole sequence in float32 ``jax.numpy`` at ``highest`` matmul
precision, with no cache, no kernel, no batching, an explicit ``[S, S]`` mask
in every attention layer and none of the program's model code.

Every layer ``l`` is ``x = x + Attn_l(RMSNorm(x))``, ``x = x +
MLP_l(RMSNorm(x))`` (eps ``rms_norm_eps``); a final RMSNorm, then the untied
head.

- ``Attn_l``: ``q = h Wq`` (heads x head_dim), ``k = h Wk``, ``v = h Wv``
  (kv heads x head_dim), no bias; an RMSNorm over the head_dim of every query
  and key head with one learned gain each (``q_norm``, ``k_norm``); the
  rotary embedding at ``rope_parameters.rope_theta`` (rotate-half) on the
  layers ``layer_types`` calls ``sliding_attention`` and none on
  ``full_attention`` layers; scores scaled by ``1 / sqrt(head_dim)``; a
  sliding layer lets position ``i`` see ``max(0, i - sliding_window + 1) ..
  i``, a full layer ``0 .. i``; grouped-query, one key-value head at a time
  so that one group's ``[heads / kv, S, S]`` scores are all that is held.
- ``MLP_l`` for the ``first_k_dense_replace`` leading layers: SwiGLU at
  ``intermediate_size``.  For the rest: ``s = sigmoid(h W_r)`` over all
  ``router_n_experts`` experts; the chosen are the top
  ``num_experts_per_tok`` of ``s + b`` (``b`` moves the choice only);
  ``w = routed_scaling_factor * s[chosen] / sum(s[chosen])``; ``y = sum over
  the chosen experts HELD HERE of w_e SwiGLU_e(h) + SwiGLU_shared(h)``.  The
  experts run one at a time under ``lax.scan`` on every token, so a layer's
  float32 experts never sit in memory together.

Departures from the published model, each also in the configuration file:

- **The norms sit before each sublayer** (the Llama block's).  ``assumed``:
  the config does not say; EXAONE 4.0 norms each sublayer's OUTPUT instead
  (``x + RMSNorm(Attn(x))``), which would be the two marked lines of
  ``attention`` and ``mlp`` with the norm moved past the sublayer.
- **Rotary embedding on the sliding layers only**, no position embedding on
  the full layers.  ``assumed``: EXAONE 4.0's hybrid convention.
- **The query and key norms** are the family's (EXAONE 4.0); no key of the
  config states them.  ``assumed``.
- **The chip's share of the experts and of the vocabulary** (``reduced``):
  the router scores all ``router_n_experts``, normalises over all the
  chosen, and the sum runs over those of the chosen that are held
  (``first_routed_expert`` .. ``+ num_experts - 1``); nothing stands in for
  the absent ones.  Embedding and head are the chip's ``vocab_size`` rows.
- **No multi-token-prediction head** (``num_nextn_predict_layers`` 0): a
  draft head that changes no logit of the model.

``forward`` also hands out what a cache must hold of the sequence, in
float32, as ``k`` and ``v`` ``[layers, S, kv heads x head_dim]`` (keys normed,
and rotated where the layer rotates), which the check (``lib/correct.py``)
compares row by row, taking the median row of the worst layer:

- a full layer: the keys and values of every token, row ``t`` token ``t``;
- a sliding layer: of the LAST ``sliding_window`` TOKENS ONLY, in position
  order.  The check wants as many rows as the sequence has tokens, of which
  the last are those the decode steps wrote, so the window is laid over the
  ``S`` rows cyclically (``tile_window``): row ``t`` is the window's token at
  the one position congruent to ``t`` modulo the window, and the last
  ``sliding_window`` rows are the window's tokens themselves.  A cache that
  keeps another window (the band left out, a window a page wider) has other
  tokens in most rows.
- ``k_grain``, ``v_grain``: what those rows keep below the grain of a token's
  int8 grid (``reference/nemotron_h.py::below_int8``), where the file leaves
  the cache in the activations' type: the reading that tells int8 pages or
  an int8 ring from what the file states.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.decoder import (_rms_norm, _rope, _swiglu, embed,
                                          head, weight)
from benchmarks.reference.nemotron_h import below_int8

F32 = jnp.float32


def tile_window(last, n_rows: int) -> np.ndarray:
    """``last`` [w, width], the tokens at the last ``w`` of ``n_rows``
    positions in position order -> [n_rows, width]: row ``t`` is the token
    at the one position of the last ``w`` that is congruent to ``t`` modulo
    ``w`` (rows ``n_rows - w ..`` are ``last`` itself)."""
    last = np.asarray(last, np.float32)
    w = last.shape[0]
    return last[(np.arange(n_rows) - (n_rows - w)) % w]


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps",
                                   "theta", "window", "rope"))
def attention(x, p, *, n_heads, n_kv, head_dim, eps, theta, window, rope):
    """x [S, H] -> (x', keys, values [S, n_kv * head_dim])."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        h = _rms_norm(x, p["attn_norm"], eps)      # assumed: norm BEFORE
        q = (h @ weight(p["wq"])).reshape(s, n_heads, head_dim)
        k = (h @ weight(p["wk"])).reshape(s, n_kv, head_dim)
        v = (h @ weight(p["wv"])).reshape(s, n_kv, head_dim)
        q = _rms_norm(q, p["q_norm"], eps)         # assumed: the family's
        k = _rms_norm(k, p["k_norm"], eps)
        if rope:                                   # assumed: sliding only
            q, k = _rope(q, theta), _rope(k, theta)
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        seen = j <= i                              # the explicit [S, S] mask
        if window:
            seen &= i - j < window
        group = n_heads // n_kv

        def one_group(qkv):
            qg, kg, vg = qkv                       # [group, S, d], [S, d] x 2
            scores = jnp.einsum("hqd,kd->hqk", qg, kg) / jnp.sqrt(
                F32(head_dim))
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   -1)
            return jnp.einsum("hqk,kd->hqd", probs, vg)

        attn = jax.lax.map(one_group, (
            q.reshape(s, n_kv, group, head_dim).transpose(1, 2, 0, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))   # [kv, group, S, d]
        attn = attn.transpose(2, 0, 1, 3).reshape(s, -1)
        return (x + attn @ weight(p["wo"]), k.reshape(s, -1),
                v.reshape(s, -1))


@partial(jax.jit, static_argnames=("top_k", "scaling", "first", "eps"))
def mlp(x, p, *, top_k, scaling, first, eps):
    """x [S, H] -> x': the dense SwiGLU where the layer has no router, else
    the routed experts held here and the shared expert."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        h = _rms_norm(x, p["mlp_norm"], eps)       # assumed: norm BEFORE
        if "router" not in p:
            return x + _swiglu(h, weight(p["w_gate"]), weight(p["w_up"]),
                               weight(p["w_down"]))
        scores = jax.nn.sigmoid(h @ weight(p["router"]))   # [S, router width]
        _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(F32),
                                  top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        share = jnp.zeros_like(scores).at[
            jnp.arange(s)[:, None], chosen].set(
                scaling * picked / jnp.sum(picked, -1, keepdims=True))
        n_held = p["w_up"].shape[0] if not hasattr(p["w_up"], "q") \
            else p["w_up"].q.shape[0]

        def one_expert(acc, e):
            pick = lambda w: type(w)(*(a[e] for a in w)) \
                if hasattr(w, "q") else w[e]
            out = _swiglu(h, weight(pick(p["w_gate"])),
                          weight(pick(p["w_up"])), weight(pick(p["w_down"])))
            return acc + out * share[:, first + e][:, None], None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                                 jnp.arange(n_held))
        shared = _swiglu(h, weight(p["w_shared_gate"]),
                         weight(p["w_shared_up"]), weight(p["w_shared_down"]))
        return x + routed + shared


def forward(conf, params, tokens, positions):
    """One sequence ``tokens`` [S], from the configuration file's published
    keys: float32 logits [len(positions), V] at the given positions, and
    what a cache must hold of the sequence (the top of the file): ``k``,
    ``v`` [layers, S, width] and their grains."""
    eps = float(conf["rms_norm_eps"])
    x = embed(params["embedding"], jnp.asarray(tokens, jnp.int32))
    n = len(tokens)
    keys, values = [], []
    for kind, p in zip(conf["layer_types"], params["layers"]):
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer_types: no attention kind {kind!r} in "
                             f"this reference")
        window = int(conf["sliding_window"]) \
            if kind == "sliding_attention" else 0
        x, k, v = attention(
            x, p, n_heads=conf["num_attention_heads"],
            n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
            eps=eps, theta=float(conf["rope_parameters"]["rope_theta"]),
            window=window, rope=bool(window))
        x = mlp(x, p, top_k=conf["num_experts_per_tok"],
                scaling=float(conf["routed_scaling_factor"]),
                first=conf["first_routed_expert"], eps=eps)
        k, v = np.asarray(k), np.asarray(v)
        if window:                # a cache keeps the last window, no more
            k, v = (tile_window(a[-min(window, n):], n) for a in (k, v))
        keys.append(k)
        values.append(v)
    out = head(x[jnp.asarray(positions)], params["final_norm"],
               params["lm_head"], eps=eps)
    held = {"k": np.stack(keys), "v": np.stack(values)}
    if conf.get("kv_cache_dtype") is None:
        held["k_grain"] = below_int8(held["k"])
        held["v_grain"] = below_int8(held["v"])
    return out, held


def logits(conf, params, tokens, positions) -> jnp.ndarray:
    """The logits of ``forward`` alone."""
    return forward(conf, params, tokens, positions)[0]
