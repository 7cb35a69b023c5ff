"""Plain reference of ``nemotron_h`` (NVIDIA-Nemotron-3-Super-120B-A12B): the
full forward pass over one whole sequence in float32 ``jax.numpy`` at
``highest`` matmul precision, with no cache, no kernel, no batching, no
chunked scan and none of the program's model code.

Every layer ``l`` of ``hybrid_override_pattern`` is ``x = x + mix_l(
RMSNorm_l(x))`` (eps ``layer_norm_epsilon``); a final RMSNorm, then the untied
head.  ``mix_l`` by the pattern's letter:

- ``M`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC = silu(causal depthwise
  conv1d(xBC) + bias)``; ``x, B, C = split(xBC)`` as ``[heads, head_dim]``,
  ``[groups, state]``, ``[groups, state]`` (head ``h`` uses group ``h //
  (heads / groups)``); ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
  per head ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t
  + D x_t``, ONE POSITION AFTER ANOTHER (``lax.scan`` over the sequence);
  ``y = RMSNorm over each group of (y * silu(z))``; ``out = y W_out``.
- ``E`` (LatentMoE): ``s = sigmoid(u W_r)`` over all the router's experts;
  the chosen are the top ``num_experts_per_tok`` of ``s + b`` (``b`` moves
  the choice only); ``w = routed_scaling_factor * s[chosen] /
  sum(s[chosen])``; ``v = u W_down``; ``routed = sum over the chosen experts
  HELD HERE of w_e * relu(v W1_e)^2 W2_e``; ``out = routed W_up + relu(u
  S1)^2 S2``.  The experts run one at a time under ``lax.scan`` on every
  token, so a layer's float32 experts never sit in memory together.
- ``*`` (attention): grouped-query causal softmax attention, no bias.

Departures from the published model, each also in the configuration file:

- **No rotary embedding** in the attention layers: the ``nemotron_h`` family's
  attention applies none (``rope_theta`` and ``partial_rotary_factor`` are in
  the config and unread).  ``assumed``.
- **No clamp on dt** after the softplus (``time_step_min/max/floor`` shape the
  seeded ``dt_bias`` only).  ``assumed``.
- **The chip's share of the experts** (``reduced``): the router scores all
  ``router_n_experts``, normalises over all the chosen, and the sum runs over
  those of the chosen that are held (``first_routed_expert`` ..
  ``+ n_routed_experts - 1``); nothing stands in for the absent ones.
- **No multi-token-prediction head** (``num_nextn_predict_layers`` 0): a draft
  head that changes no logit of the model.

``forward`` also hands out what a cache would hold of the sequence, in
float32, as ``[layers, tokens, width]`` arrays that the check
(``lib/correct.py``) compares row by row, taking the median row of the worst
layer:

- ``k``, ``v``: the attention layers' keys and values by token.
- ``conv_state``: each Mamba layer's convolution tail AFTER THE LAST TOKEN
  (``tail_rows``: a row is of one position, so a position whose router tie
  fell the other way is some of the rows and not a part of every row).
- ``ssm_state``: the recurrent state after the last token OF THE FIRST MAMBA
  LAYER, which no router precedes (``state_rows``: flattened, cut into as
  many rows as the sequence has tokens).  A state has no token axis: in a
  deeper layer a router flip among the last ten tokens is in every row of
  the half of the heads that forget within ten tokens, and the median row
  swings between 1% and 5% by the seed (PERF.md section 6, PR 32).  A
  deeper layer's stale or misplaced state moves every logit; its precision
  is read by the next entry.
- ``ssm_grain``, ``k_grain``, ``v_grain``: WHAT THE CACHE KEEPS BELOW THE
  GRAIN OF THE NEXT PRECISION DOWN from the one the file states
  (``below_bfloat16`` of every Mamba layer's state where the file states
  float32; ``below_int8`` of keys and values where the file leaves the
  pages in the activations' type).  Rounding a value to the lower precision
  loses a part of it whose size does not depend on the value: some 0.14% of
  an element for bfloat16, a quarter of a step for a token's int8 grid.  The
  reference's own values lose that, an engine that stores what the file
  states loses the same to within the sampling of a mean (0.2-1.5%), and an
  engine that stores one precision down has nothing left to lose and reads
  100% off.  This is the reading that tells a bfloat16 state from a float32
  one: the state itself is 0.1-0.3% apart after eight steps, beside 0.5%
  from bfloat16 activations.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from benchmarks.reference.decoder import _rms_norm, embed, head, weight

F32 = jnp.float32


def state_rows(state, n_rows: int) -> np.ndarray:
    """``state`` [layers, ...] -> [layers, n_rows, width]: each layer's
    state flattened and cut into ``n_rows`` equal rows, the tail of the last
    filled up cyclically from the start (no row is all zeros)."""
    flat = np.asarray(state, np.float32).reshape(state.shape[0], -1)
    width = -(-flat.shape[1] // n_rows)
    return np.stack([np.resize(layer, n_rows * width).reshape(n_rows, width)
                     for layer in flat])


def tail_rows(tails, n_rows: int) -> np.ndarray:
    """``tails`` [layers, channels, kernel - 1] -> [layers, n_rows, width]:
    each row holds channels of ONE of the last positions (row ``i`` is of
    position ``i mod (kernel - 1)``, its channels cut into equal rows, the
    last filled up from the start), so that a position whose router tie
    fell the other way is some of the rows and not a part of every row
    (``lib/correct.py`` takes the median row, as it takes the median token
    of keys and values)."""
    tails = np.asarray(tails, np.float32)
    taps = tails.shape[2]
    chunks = -(-n_rows // taps)
    width = -(-tails.shape[1] // chunks)
    rows = np.stack([[np.resize(layer[:, t], chunks * width)
                      .reshape(chunks, width) for t in range(taps)]
                     for layer in tails])          # [L, taps, chunks, width]
    return rows.transpose(0, 2, 1, 3).reshape(
        tails.shape[0], chunks * taps, width)[:, :n_rows]


GRAIN_PART = 65536        # elements to a mean: steady to a few tenths of a %
GRAIN_TOKENS = 64         # tokens to a mean, counted from the last one


def below_bfloat16(state, n_rows: int) -> np.ndarray:
    """``state`` [layers, ...] float32 -> [layers, n_rows, parts]: of each
    layer's state cut into up to 8 equal parts of ``GRAIN_PART`` elements at
    least, the mean share of an element that rounding it to bfloat16 would
    lose (every row the same: a state has no token axis).  0.14% of values
    kept in float32, nothing of values that are bfloat16 already."""
    flat = np.asarray(state, np.float32).reshape(len(state), -1)
    parts = max(1, min(8, flat.shape[1] // GRAIN_PART))
    lost = np.abs(flat - flat.astype(ml_dtypes.bfloat16).astype(np.float32))
    share = np.divide(lost, np.abs(flat), out=np.zeros_like(lost),
                      where=flat != 0)
    means = np.stack([[part.mean() for part in np.array_split(layer, parts)]
                      for layer in share])
    return np.repeat(means[:, None], n_rows, axis=1)


def below_int8(values) -> np.ndarray:
    """``values`` [layers, tokens, width] -> [layers, tokens, 1]: what
    rounding each token to its own int8 grid (127 steps up to its largest
    element, as a quantized cache keeps it) would lose of an element, in
    steps, as the mean over the token's block of ``GRAIN_TOKENS`` tokens;
    the blocks are counted from the last token, so the fed tokens share one
    with the end of the prompt.  A quarter of a step for values kept finer
    than the grid, nothing for values that lie on it."""
    values = np.asarray(values, np.float32)
    n = values.shape[1]
    top = np.abs(values).max(-1, keepdims=True)
    step = np.where(top > 0, top / np.float32(127), np.float32(1))
    lost = np.abs(values / step - np.round(values / step)).mean(-1)
    out = np.empty_like(lost)
    for hi in range(n, 0, -GRAIN_TOKENS):
        lo = max(0, hi - GRAIN_TOKENS)
        out[:, lo:hi] = lost[:, lo:hi].mean(-1, keepdims=True)
    return out[..., None]


@partial(jax.jit, static_argnames=("heads", "head_dim", "groups", "state",
                                   "eps"))
def mamba_layer(x, p, *, heads, head_dim, groups, state, eps):
    """x [S, H] -> (x', ssm_state [heads, head_dim, state], conv_state
    [conv_dim, kernel - 1]: the last inputs of the convolution)."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        inner, gn = heads * head_dim, groups * state
        u = _rms_norm(x, p["norm"], eps)
        proj = u @ weight(p["w_in"])
        z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + inner + 2 * gn],
                      proj[:, 2 * inner + 2 * gn:])
        conv_w = weight(p["conv_w"])                       # [K, conv_dim]
        k = conv_w.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
        conv = sum(padded[j:j + s] * conv_w[j] for j in range(k))
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
        return x + out, h, padded[s:].T


@partial(jax.jit, static_argnames=("top_k", "scaling", "first", "eps"))
def expert_layer(x, p, *, top_k, scaling, first, eps):
    """x [S, H] -> x'.  The router scores every expert it has a column for;
    the experts held are the stacked weights' ``first`` .. ``first + E - 1``.
    """
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        u = _rms_norm(x, p["norm"], eps)
        scores = jax.nn.sigmoid(u @ weight(p["router"]))   # [S, router width]
        _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(F32),
                                  top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        share = jnp.zeros_like(scores).at[
            jnp.arange(s)[:, None], chosen].set(
                scaling * picked / jnp.sum(picked, -1, keepdims=True))
        v = u @ weight(p["w_latent_down"])                 # [S, latent]
        n_held = p["w_up"].shape[0] if not hasattr(p["w_up"], "q") \
            else p["w_up"].q.shape[0]

        def one_expert(acc, e):
            pick = lambda w: type(w)(*(a[e] for a in w)) \
                if hasattr(w, "q") else w[e]
            hid = jnp.square(jax.nn.relu(v @ weight(pick(p["w_up"]))))
            out = hid @ weight(pick(p["w_down"]))
            return acc + out * share[:, first + e][:, None], None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(v),
                                 jnp.arange(n_held))
        shared = jnp.square(jax.nn.relu(u @ weight(p["w_shared_up"]))) \
            @ weight(p["w_shared_down"])
        return x + routed @ weight(p["w_latent_up"]) + shared


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps"))
def attention_layer(x, p, *, n_heads, n_kv, head_dim, eps):
    """x [S, H] -> (x', keys, values [S, n_kv * head_dim]).  No rotary
    embedding (see the top of the file)."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        u = _rms_norm(x, p["attn_norm"], eps)
        q = (u @ weight(p["wq"])).reshape(s, n_heads, head_dim)
        k = (u @ weight(p["wk"])).reshape(s, n_kv, head_dim)
        v = (u @ weight(p["wv"])).reshape(s, n_kv, head_dim)
        held = k.reshape(s, -1), v.reshape(s, -1)
        group = n_heads // n_kv
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)
        return (x + attn @ weight(p["wo"]), *held)


def forward(conf, params, tokens, positions):
    """One sequence ``tokens`` [S], from the configuration file's published
    keys: float32 logits [len(positions), V] at the given positions, and
    what a cache holds of the sequence, each float32 [layers, S, width]
    (the top of the file says what each is): ``k``, ``v``, ``conv_state``,
    ``ssm_state`` and the grains."""
    eps = float(conf["layer_norm_epsilon"])
    x = embed(params["embedding"], jnp.asarray(tokens, jnp.int32))
    n = len(tokens)
    keys, values, states, tails = [], [], [], []
    for letter, p in zip(conf["hybrid_override_pattern"], params["layers"]):
        if letter == "M":
            x, state, tail = mamba_layer(
                x, p, heads=conf["mamba_num_heads"],
                head_dim=conf["mamba_head_dim"], groups=conf["n_groups"],
                state=conf["ssm_state_size"], eps=eps)
            states.append(np.asarray(state))
            tails.append(np.asarray(tail))
        elif letter == "E":
            x = expert_layer(
                x, p, top_k=conf["num_experts_per_tok"],
                scaling=float(conf["routed_scaling_factor"]),
                first=conf["first_routed_expert"], eps=eps)
        elif letter == "*":
            x, k, v = attention_layer(
                x, p, n_heads=conf["num_attention_heads"],
                n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
                eps=eps)
            keys.append(np.asarray(k))
            values.append(np.asarray(v))
        else:
            raise ValueError(f"hybrid_override_pattern: no layer kind "
                             f"{letter!r} in this reference")
    out = head(x[jnp.asarray(positions)], params["final_norm"],
               params["lm_head"], eps=eps)
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
