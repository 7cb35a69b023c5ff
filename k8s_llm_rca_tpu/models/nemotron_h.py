"""The layer table: a decoder whose layers are of three kinds, one a layer
(``nemotron_h``), or one and a gated MLP behind it (``granitemoehybrid``).

``cfg.layer_table`` gives each layer its letter, and every layer is
``x = x + r * mix(RMSNorm(x))`` with ``r = cfg.residual_multiplier`` (1
for ``nemotron_h``) and ``mix`` by the letter:

- ``M`` — a Mamba-2 mixer: ``[z | xBC | dt] = u W_in``; a depthwise causal
  convolution and SiLU over ``xBC``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the state-space recurrence of ops/ssm.py per head;
  an RMSNorm over each group of ``y * silu(z)``; ``W_out``.  What a
  sequence keeps of its past is the recurrent state ``[heads, head_dim,
  state]`` in float32 and the convolution's last ``kernel - 1`` inputs.
- ``E`` — a LatentMoE layer: a sigmoid router with a selection bias over
  ``cfg.n_router`` experts (models/llama._route), the chosen experts'
  non-gated squared-ReLU MLPs in a latent space between two shared
  projections (llama._experts, on the experts held here), and an
  always-on shared expert at the full width.
- ``*`` — grouped-query attention with NO rotary embedding
  (``cfg.use_rope`` False), the only kind that caches keys and values;
  its softmax scale is the model's where it states one (``cfg.q_fold``,
  folded into the query by ``llama._qkv``).

Where ``cfg.block_mlp_size`` is set (``granitemoehybrid``: the published
``layer_types`` names ``mamba`` / ``attention``, held as
``cfg.mixer_types``, are the letters ``M`` /
``*``), every layer is a block of TWO sublayers, the mixer and then ``x =
x + r * MLP(RMSNorm(x))`` with the gated MLP of ``llama._mlp``
(``block_mlp``); the embedded tokens are scaled by
``cfg.embedding_multiplier`` (``llama.embed``) and the logits divided by
``cfg.logits_scaling`` over a head that may be tied (``llama._logits``).
That second family has no module of its own: it calls ``init_params``,
``mamba_prefill`` / ``mamba_decode``, ``attention_prefill``,
``block_mlp``, ``prefill_rows`` and ``forward`` here, each of which reads
the factors from ``cfg`` and leaves a program of the first family as it
was where they are 1.

The engine's programs (engine/paged.py) walk this table; here are the
weights, the three mixers in their prefill and their decode form, and a
plain ``forward`` for tests.  A prefill runs one row of the batch after
another (a ``jax.lax.scan`` over rows): rows share nothing, a row of a
bucket keeps every matmul large, the temporaries (the in-projection's
18,560 columns, the routed rows of 22 picks a position) stay one row's,
and a padding row, which repeats the row before it, costs nothing.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from k8s_llm_rca_tpu.config import ModelConfig
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.ops import ssm
from k8s_llm_rca_tpu.ops.attention import causal_attention
from k8s_llm_rca_tpu.ops.norms import rms_norm

Params = Dict[str, Any]
F32 = jnp.float32


def init_params(cfg: ModelConfig, key: jax.Array,
                tensor_transform=None) -> Params:
    """Seeded weights (``tensor_transform`` as in llama.init_params: applied
    to every matmul weight as it is made).  ``dt_bias`` is the inverse
    softplus of a log-uniform step in ``[ssm_dt_min, ssm_dt_max]`` floored
    at ``ssm_dt_floor``, ``A_log = log U(1, 16)``: the family's own
    initialisation, so that the seeded recurrence has the decays a trained
    one has (from a step of memory to thousands).  Every layer's output
    projection is scaled by ``1 / sqrt(depth)`` (one residual a layer;
    the family's ``rescale_prenorm_residual``), ``depth`` the whole
    model's (``cfg.init_layers``) where these layers are a stage of a
    deeper one; a model with a ``residual_multiplier`` bounds its stream
    by that factor instead and its output projections are not rescaled.
    The embedding's rows have the variance that lets the embedded tokens
    enter the stream at 1 (``1 / embedding_multiplier``).  A block of two
    sublayers (``cfg.block_mlp_size``) has in every layer ``mlp_norm``,
    ``w_gate``, ``w_up`` and ``w_down`` beside the mixer's weights; a tied
    head (``cfg.tie_embeddings``) makes no ``lm_head``."""
    dtype = jnp.dtype(cfg.dtype)
    h = cfg.hidden_size
    keys = jax.random.split(key, cfg.n_layers + 2)
    scale = 1.0 / math.sqrt(h)
    out_scale = (1.0 / math.sqrt(cfg.init_layers or cfg.n_layers)
                 if cfg.residual_multiplier == 1.0 else 1.0)
    tt = tensor_transform or (lambda w, **_: w)

    def dense(k, shape, sc, **tt_kw):
        w = llama._dense(k, shape, sc, dtype)
        out = tt(w, **tt_kw)
        if out is not w:
            w.delete()
        return out

    layers = []
    for i, kind in enumerate(cfg.layer_table):
        lk = jax.random.split(keys[i], 8)
        if kind == "M":
            inner, heads = cfg.ssm_inner, cfg.ssm_heads
            conv_dim, kc = cfg.ssm_conv_dim, cfg.ssm_conv_kernel
            step = jnp.exp(
                jax.random.uniform(lk[2], (heads,), F32)
                * (math.log(cfg.ssm_dt_max) - math.log(cfg.ssm_dt_min))
                + math.log(cfg.ssm_dt_min))
            step = jnp.maximum(step, cfg.ssm_dt_floor)
            layer = {
                "norm": jnp.ones((h,), dtype),
                "w_in": dense(lk[0], (h, 2 * inner + 2 * cfg.ssm_groups
                                      * cfg.ssm_state_size + heads), scale),
                "conv_w": llama._dense(lk[1], (kc, conv_dim),
                                       1.0 / math.sqrt(kc), dtype),
                "conv_b": llama._dense(lk[4], (conv_dim,), 0.1, dtype),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(jax.random.uniform(
                    lk[3], (heads,), F32, 1.0, 16.0)),
                "D": jnp.ones((heads,), F32),
                "gate_norm": jnp.ones((inner,), dtype),
                "w_out": dense(lk[5], (inner, h),
                               out_scale / math.sqrt(inner)),
            }
        elif kind == "E":
            e, lat = cfg.n_experts, cfg.moe_latent_size
            inter, shared = cfg.expert_size, cfg.shared_expert_size
            layer = {
                "norm": jnp.ones((h,), dtype),
                "router": dense(lk[0], (h, cfg.n_router), scale),
                "router_bias": 0.1 * jax.random.normal(
                    lk[1], (cfg.n_router,), F32),
                "w_latent_down": dense(lk[2], (h, lat), scale),
                "w_up": dense(lk[3], (e, lat, inter), 1.0 / math.sqrt(lat),
                              axis=(0, -1)),
                "w_down": dense(lk[4], (e, inter, lat),
                                out_scale / math.sqrt(inter), axis=(0, -1)),
                "w_latent_up": dense(lk[5], (lat, h), 1.0 / math.sqrt(lat)),
                "w_shared_up": dense(lk[6], (h, shared), scale),
                "w_shared_down": dense(lk[7], (shared, h),
                                       out_scale / math.sqrt(shared)),
            }
        else:
            q, kv = cfg.q_dim, cfg.kv_dim
            layer = {
                "attn_norm": jnp.ones((h,), dtype),
                "wq": dense(lk[0], (h, q), scale),
                "wk": dense(lk[1], (h, kv), scale),
                "wv": dense(lk[2], (h, kv), scale),
                "wo": dense(lk[3], (q, h), out_scale / math.sqrt(q)),
            }
        if cfg.block_mlp_size:
            inter = cfg.block_mlp_size
            mk = jax.random.split(jax.random.fold_in(keys[i], 1), 3)
            layer.update({
                "mlp_norm": jnp.ones((h,), dtype),
                "w_gate": dense(mk[0], (h, inter), scale),
                "w_up": dense(mk[1], (h, inter), scale),
                "w_down": dense(mk[2], (inter, h),
                                out_scale / math.sqrt(inter)),
            })
        layers.append(layer)
    params = {
        "embedding": dense(keys[-2], (cfg.vocab_size, h),
                           1.0 / cfg.embedding_multiplier, axis=0),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[-1], (cfg.vocab_size, h), scale,
                                  axis=0)
    return params


# ---------------------------------------------------------------------------
# the three mixers
# ---------------------------------------------------------------------------


def _residual(cfg: ModelConfig, x: jnp.ndarray, out: jnp.ndarray
              ) -> jnp.ndarray:
    """``x + out``, ``out`` scaled by ``cfg.residual_multiplier`` where
    the model has one: what every sublayer of the table ends in."""
    if cfg.residual_multiplier != 1.0:
        out = out * jnp.asarray(cfg.residual_multiplier, out.dtype)
    return x + out


def block_mlp(cfg: ModelConfig, layer: Params, x: jnp.ndarray
              ) -> jnp.ndarray:
    """The second sublayer of a block of two (``cfg.block_mlp_size``), over
    x [..., H]: the gated MLP of ``llama._mlp`` under its own norm and
    residual."""
    u = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    return _residual(cfg, x, llama._mlp(cfg, layer, u))


def _mamba_split(cfg: ModelConfig, layer: Params, u: jnp.ndarray):
    """u [..., H] -> (z [..., inner], xBC [..., conv_dim], dt [..., heads])."""
    proj = llama._w_mm(cfg, u, layer["w_in"])
    inner, conv_dim = cfg.ssm_inner, cfg.ssm_conv_dim
    return (proj[..., :inner], proj[..., inner:inner + conv_dim],
            proj[..., inner + conv_dim:])


def _xbc_split(cfg: ModelConfig, xbc: jnp.ndarray):
    """[..., conv_dim] -> x [..., heads, head_dim], B and C [..., G, N]."""
    inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state_size
    lead = xbc.shape[:-1]
    return (xbc[..., :inner].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim),
            xbc[..., inner:inner + gn].reshape(
                *lead, cfg.ssm_groups, cfg.ssm_state_size),
            xbc[..., inner + gn:].reshape(
                *lead, cfg.ssm_groups, cfg.ssm_state_size))


def _mamba_out(cfg: ModelConfig, layer: Params, y: jnp.ndarray,
               z: jnp.ndarray) -> jnp.ndarray:
    """y [..., heads, head_dim] float32, z [..., inner] -> [..., H]: the
    gate, the RMSNorm over each group of channels, the out-projection."""
    lead = z.shape[:-1]
    gated = y.reshape(*lead, cfg.ssm_inner) * jax.nn.silu(z.astype(F32))
    grouped = gated.reshape(*lead, cfg.ssm_groups, -1)
    normed = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + cfg.rms_norm_eps)
    normed = normed.reshape(*lead, cfg.ssm_inner) \
        * layer["gate_norm"].astype(F32)
    return llama._w_mm(cfg, normed.astype(z.dtype), layer["w_out"])


def mamba_prefill(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
                  lengths: jnp.ndarray, scan_kernel: bool = False):
    """One Mamba-2 layer over fresh right-padded sequences x [B, S, H].
    Returns (x', ssm_state [B, heads, head_dim, N] float32, conv_state
    [B, kernel - 1, conv_dim]) with both states as they stand after each
    row's last TRUE position: a pad position's ``dt`` is 0, and the
    convolution's tail is cut at ``lengths``.  ``scan_kernel`` (static):
    the chunked scan as its Pallas kernel, where the prefill's kernels
    may stand (``_stack``'s ``use_flash``)."""
    u = rms_norm(x, layer["norm"], cfg.rms_norm_eps)
    z, xbc, dt = _mamba_split(cfg, layer, u)
    tail = ssm.conv_tail(xbc, lengths, cfg.ssm_conv_kernel)
    xs, b, c = _xbc_split(cfg, ssm.causal_conv(
        xbc, layer["conv_w"], layer["conv_b"]))
    dt = jax.nn.softplus(dt.astype(F32) + layer["dt_bias"])
    true = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
    dt = jnp.where(true[..., None], dt, 0.0)
    scan = ssm.ssm_chunk_scan if scan_kernel else ssm.ssm_chunk_scan_xla
    y, state = scan(xs, dt, -jnp.exp(layer["A_log"]), b, c, layer["D"],
                    cfg.ssm_chunk)
    return _residual(cfg, x, _mamba_out(cfg, layer, y, z)), state, tail


def mamba_decode(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
                 ssm_state: jnp.ndarray, conv_state: jnp.ndarray,
                 pool_layer=None, slots: Optional[ssm.LiveSlots] = None):
    """One Mamba-2 layer for one new position of every slot: x [B, 1, H],
    ssm_state [B, heads, head_dim, N], conv_state [B, kernel - 1,
    conv_dim].  Returns (x', ssm_state', conv_state').  With
    ``pool_layer`` the state is every Mamba layer's as the pool keeps it,
    [layers, B, heads, head_dim, N], of which the kernel moves layer
    ``pool_layer`` on in place, for the live ``slots`` alone
    (``ssm.ssm_state_update_in_place``); it comes back whole."""
    u = rms_norm(x[:, 0], layer["norm"], cfg.rms_norm_eps)
    z, xbc, dt = _mamba_split(cfg, layer, u)
    xbc, conv_state = ssm.conv_step(xbc, layer["conv_w"], layer["conv_b"],
                                    conv_state)
    xs, b, c = _xbc_split(cfg, xbc)
    dt = jax.nn.softplus(dt.astype(F32) + layer["dt_bias"])
    operands = (xs, dt, -jnp.exp(layer["A_log"]), b, c, layer["D"])
    if pool_layer is None:
        y, ssm_state = ssm.ssm_state_update(ssm_state, *operands)
    else:
        y, ssm_state = ssm.ssm_state_update_in_place(
            ssm_state, pool_layer, *operands, slots)
    return (_residual(cfg, x, _mamba_out(cfg, layer, y, z)[:, None]),
            ssm_state, conv_state)


def expert_layer(cfg: ModelConfig, layer: Params, x: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One LatentMoE layer over x [B, S, H].  Returns (x', the number of
    (position, expert) pairs whose expert is held here, an int32 scalar
    counted from the router's choices: ``engine.moe_local_pairs``)."""
    u = rms_norm(x, layer["norm"], cfg.rms_norm_eps)
    topi, weights = llama._route(cfg, layer, u)
    latent = llama._w_mm(cfg, u, layer["w_latent_down"])
    routed = llama._experts(cfg, layer, latent, topi, weights)
    shared = llama._shared_hidden(cfg, layer, u)
    out = (llama._w_mm(cfg, routed, layer["w_latent_up"])
           + llama._w_mm(cfg, shared, layer["w_shared_down"]))
    n_local = llama.n_local_pairs(cfg, topi)
    return _residual(cfg, x, out), n_local


def attention_prefill(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
                      lengths: jnp.ndarray, attention_fn=None):
    """One attention layer over x [B, S, H]: (x', k, v [B, S, kv_dim])."""
    b, s, _ = x.shape
    q, k, v = llama._decode_qkv(cfg, layer, x, None, None)
    attn = (causal_attention(q, k, v, lengths) if attention_fn is None
            else attention_fn(q, k, v))
    x = attention_out(cfg, layer, x, attn.reshape(b, s, cfg.q_dim))
    return x, k.reshape(b, s, cfg.kv_dim), v.reshape(b, s, cfg.kv_dim)


def attention_out(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
                  attn: jnp.ndarray) -> jnp.ndarray:
    """The attention sublayer's end, shared by its prefill and decode
    forms: attn [B, S, q_dim] through the output projection onto the
    residual stream."""
    return _residual(cfg, x, llama._w_mm(cfg, attn, layer["wo"]))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _stack(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
           lengths: jnp.ndarray, use_flash: bool = False):
    """The layers over right-padded rows tokens [N, S]: the residual
    stream [N, S, H] and what each row leaves behind (keys and values of
    the attention layers [La, N, S, kv_dim], the Mamba layers' states
    [Lm, N, ...], the local-pair count, and how many expert-layer calls
    ran over the compact form).  ``use_flash`` (static): the prefill's
    kernels may stand in this program, the flash call from
    ``llama.FLASH_MIN_POSITIONS`` positions, the Mamba layers' chunked
    scan at every length."""
    x = llama.embed(cfg, params, tokens)
    attention_fn = None
    if llama.prefill_uses_flash(use_flash, tokens.shape[1]):
        attention_fn = llama._flash_attention_fn(lengths, None)
    ks, vs, states, tails = [], [], [], []
    pairs = []
    for kind, layer in zip(cfg.layer_table, params["layers"]):
        if kind == "M":
            x, state, tail = mamba_prefill(cfg, layer, x, lengths, use_flash)
            states.append(state.astype(jnp.dtype(cfg.ssm_state_dtype)))
            tails.append(tail)
        elif kind == "E":
            x, n = expert_layer(cfg, layer, x)
            pairs.append(n)
        else:
            x, k, v = attention_prefill(cfg, layer, x, lengths,
                                        attention_fn)
            ks.append(k)
            vs.append(v)
        if cfg.block_mlp_size:
            x = block_mlp(cfg, layer, x)
    return x, (jnp.stack(ks), jnp.stack(vs), jnp.stack(states),
               jnp.stack(tails), sum(pairs, jnp.int32(0)),
               llama.n_compact_overflows(cfg, tokens.size, pairs))


def forward(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            seq_lens: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """tokens [B, S] -> logits [B, S, V] float32 (tests and scoring)."""
    b, s = tokens.shape
    if seq_lens is None:
        seq_lens = jnp.full((b,), s, jnp.int32)
    x, _ = _stack(cfg, params, tokens, seq_lens)
    return llama._logits(cfg, params, x)


def prefill_rows(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                 lengths: jnp.ndarray, use_flash: bool = False):
    """Batched prefill WITHOUT a cache write, one row after another (the
    temporaries stay one row's): tokens [N, S] right-padded, lengths [N] ->
    (k, v [La, N, S, kv_dim], ssm_state [Lm, N, heads, head_dim, N_state],
    conv_state [Lm, N, kernel - 1, conv_dim], logits [N, V] at each row's
    last true token, local pairs and calls that ran over the compact form,
    both int32).  The caller writes pages and the slots' state
    (engine/paged.paged_prefill_batch)."""

    def one(row):
        toks, n = row
        x, (k, v, state, tail, n_local, n_over) = _stack(
            cfg, params, toks[None], n[None], use_flash)
        last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)
        logits = llama._logits(cfg, params, last)[0, 0]
        return (k[:, 0], v[:, 0], state[:, 0], tail[:, 0], logits, n_local,
                n_over)

    k, v, state, tail, logits, n_local, n_over = jax.lax.map(
        one, (tokens, lengths.astype(jnp.int32)))
    return (jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1),
            jnp.moveaxis(state, 0, 1), jnp.moveaxis(tail, 0, 1), logits,
            jnp.sum(n_local), jnp.sum(n_over))
