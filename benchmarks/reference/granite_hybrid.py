"""Plain reference of ``granitemoehybrid`` without routed experts
(ibm-granite/granite-4.0-h-micro): the full forward pass over one whole
sequence in float32 ``jax.numpy`` at ``highest`` matmul precision, with no
cache, no kernel, no batching, no chunked scan and none of the program's
model code.

``x = E[token] * embedding_multiplier``.  Every layer ``l`` is a block of two
sublayers, ``x = x + residual_multiplier * mix_l(RMSNorm(x))`` and then ``x =
x + residual_multiplier * MLP(RMSNorm(x))`` (eps ``rms_norm_eps``), with
``MLP(u) = (silu(u W_g) * (u W_u)) W_d`` at width
``shared_intermediate_size`` and ``mix_l`` by ``layer_types[l]``:

- ``mamba`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC = silu(causal
  depthwise conv1d(xBC) + bias)``; ``x, B, C = split(xBC)`` as ``[heads,
  head_dim]``, ``[groups, state]``, ``[groups, state]`` (head ``h`` uses group
  ``h // (heads / groups)``); ``dt = softplus(dt + dt_bias)``; ``A =
  -exp(A_log)``; per head ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = h_t C_t + D x_t``, ONE POSITION AFTER ANOTHER (``lax.scan`` over the
  sequence); ``y = RMSNorm over each group of (y * silu(z))``; ``out = y
  W_out``.
- ``attention``: grouped-query causal attention, no bias, no rotary
  embedding, ``softmax(q k^T * attention_multiplier)``: the factor as
  published, on the scores, never folded.

``logits = RMSNorm(x) E^T / logits_scaling`` over the tied embedding (or the
head's own matrix where ``tie_word_embeddings`` is false).

Departures from the published model, each also in the configuration file
under ``assumed``:

- ``head_dim`` = ``hidden_size / num_attention_heads`` (64): the config gives
  none.
- ``torch_dtype`` bfloat16 for the weights the engine serves; the reference
  reads them into float32.
- ``ssm_state_dtype`` float32: the recurrent state's precision, which the
  config does not state (``benchmarks/control_state.py`` lowers this key).
- **No clamp on dt** after the softplus.
- **The seeded weights**: ``dt_bias`` / ``A_log`` / ``D`` from the Mamba-2
  family's initialisation (a log-uniform step in 0.001-0.1, floor 1e-4),
  matmul weights scaled normal, the embedding at ``1 /
  embedding_multiplier`` (``models/nemotron_h.py::init_params``).
- ``intermediate_size`` is unread: with ``num_local_experts`` 0 no routed
  expert has that width, and the "shared" MLP is the whole feed-forward
  part.  The published checkpoint holds its input matrix as one ``[hidden, 2
  x width]`` whose first half is the gate; here the halves are ``w_gate``
  and ``w_up``.

``forward`` also hands out what a cache would hold of the sequence, under
the names ``checks/paged_kv_state.py`` reads and laid out by
``reference/nemotron_h.py``'s own helpers, whose top says what each entry is:
``k``, ``v`` (the attention layers', by token), ``conv_state`` (every Mamba
layer's convolution tail), ``ssm_state`` (the FIRST Mamba layer's recurrent
state; no discrete choice precedes any layer here, but the check driver reads
the first, and one layer's 0.5 M elements are sample enough), and the grains
``ssm_grain`` (every Mamba layer's state below bfloat16, where the file states
float32), ``k_grain``, ``v_grain`` (below a token's int8 grid, where the file
leaves the pages in the activations' type).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.decoder import _rms_norm, embed, weight
from benchmarks.reference.nemotron_h import (below_bfloat16, below_int8,
                                             state_rows, tail_rows)

F32 = jnp.float32


@partial(jax.jit, static_argnames=("eps", "residual"))
def mlp_sublayer(x, p, *, eps, residual):
    """x [S, H] -> x + residual * MLP(RMSNorm(x))."""
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, p["mlp_norm"], eps)
        hidden = jax.nn.silu(u @ weight(p["w_gate"])) * (u @ weight(p["w_up"]))
        return x + residual * (hidden @ weight(p["w_down"]))


@partial(jax.jit, static_argnames=("heads", "head_dim", "groups", "state",
                                   "eps", "residual"))
def mamba_sublayer(x, p, *, heads, head_dim, groups, state, eps, residual):
    """x [S, H] -> (x', ssm_state [heads, head_dim, state], conv_state
    [conv_dim, kernel - 1]: the last inputs of the convolution)."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        inner, gn = heads * head_dim, groups * state
        u = _rms_norm(x, p["norm"], eps)
        proj = u @ weight(p["w_in"])
        z = proj[:, :inner]
        xbc = proj[:, inner:2 * inner + 2 * gn]
        dt = proj[:, 2 * inner + 2 * gn:]
        conv_w = weight(p["conv_w"])                       # [K, conv_dim]
        taps = conv_w.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc])
        conv = sum(padded[j:j + s] * conv_w[j] for j in range(taps))
        act = jax.nn.silu(conv + weight(p["conv_b"]))
        xs = act[:, :inner].reshape(s, heads, head_dim)
        b = jnp.repeat(act[:, inner:inner + gn].reshape(s, groups, state),
                       heads // groups, axis=1)            # [S, heads, N]
        c = jnp.repeat(act[:, inner + gn:].reshape(s, groups, state),
                       heads // groups, axis=1)
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))  # [S, heads]
        a = -jnp.exp(p["A_log"].astype(F32))

        def step(h, inp):
            x_t, b_t, c_t, dt_t = inp
            h = (jnp.exp(dt_t * a)[:, None, None] * h
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return h, jnp.einsum("hpn,hn->hp", h, c_t)

        h, y = jax.lax.scan(step, jnp.zeros((heads, head_dim, state), F32),
                            (xs, b, c, dt))
        y = y + p["D"].astype(F32)[None, :, None] * xs
        gated = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, groups, -1)
        normed = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, -1, keepdims=True) + eps)
        out = (normed.reshape(s, inner) * weight(p["gate_norm"])) \
            @ weight(p["w_out"])
        return x + residual * out, h, padded[s:].T


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps",
                                   "residual", "softmax_scale"))
def attention_sublayer(x, p, *, n_heads, n_kv, head_dim, eps, residual,
                       softmax_scale):
    """x [S, H] -> (x', keys, values [S, n_kv * head_dim]).  No rotary
    embedding; the scores are scaled by ``softmax_scale`` as published."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        u = _rms_norm(x, p["attn_norm"], eps)
        q = (u @ weight(p["wq"])).reshape(s, n_heads, head_dim)
        k = (u @ weight(p["wk"])).reshape(s, n_kv, head_dim)
        v = (u @ weight(p["wv"])).reshape(s, n_kv, head_dim)
        held = k.reshape(s, -1), v.reshape(s, -1)
        group = n_heads // n_kv
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * F32(softmax_scale)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)
        return (x + residual * (attn @ weight(p["wo"])), *held)


@partial(jax.jit, static_argnames=("eps", "scaling"))
def head(x, final_norm, table, *, eps, scaling):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ weight(table).T / scaling


def forward(conf, params, tokens, positions):
    """One sequence ``tokens`` [S], from the configuration file's published
    keys: float32 logits [len(positions), V] at the given positions, and
    what a cache holds of the sequence, each float32 [layers, S, width]
    (the top of the file says what each is)."""
    eps = float(conf["rms_norm_eps"])
    residual = float(conf["residual_multiplier"])
    x = embed(params["embedding"], jnp.asarray(tokens, jnp.int32)) \
        * F32(conf["embedding_multiplier"])
    n = len(tokens)
    keys, values, states, tails = [], [], [], []
    for kind, p in zip(conf["layer_types"], params["layers"]):
        if kind == "mamba":
            x, state, tail = mamba_sublayer(
                x, p, heads=conf["mamba_n_heads"],
                head_dim=conf["mamba_d_head"], groups=conf["mamba_n_groups"],
                state=conf["mamba_d_state"], eps=eps, residual=residual)
            states.append(np.asarray(state))
            tails.append(np.asarray(tail))
        elif kind == "attention":
            x, k, v = attention_sublayer(
                x, p, n_heads=conf["num_attention_heads"],
                n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
                eps=eps, residual=residual,
                softmax_scale=float(conf["attention_multiplier"]))
            keys.append(np.asarray(k))
            values.append(np.asarray(v))
        else:
            raise ValueError(f"layer_types: no layer kind {kind!r} in this "
                             f"reference")
        x = mlp_sublayer(x, p, eps=eps, residual=residual)
    table = params["embedding"] if conf["tie_word_embeddings"] \
        else params["lm_head"]
    out = head(x[jnp.asarray(positions)], params["final_norm"], table,
               eps=eps, scaling=float(conf["logits_scaling"]))
    held = {"k": np.stack(keys), "v": np.stack(values),
            "ssm_state": state_rows(np.stack(states[:1]), n),
            "conv_state": tail_rows(np.stack(tails), n)}
    # the grain of the next precision down, of what the file states finer
    if conf.get("ssm_state_dtype", "float32") == "float32":
        held["ssm_grain"] = below_bfloat16(np.stack(states), n)
    if conf.get("kv_cache_dtype") is None:
        held["k_grain"] = below_int8(held["k"])
        held["v_grain"] = below_int8(held["v"])
    return out, held


def logits(conf, params, tokens, positions) -> jnp.ndarray:
    """The logits of ``forward`` alone."""
    return forward(conf, params, tokens, positions)[0]
