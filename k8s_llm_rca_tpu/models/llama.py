"""Llama-family decoder LM, TPU-first.

Pure-functional JAX: params are a plain pytree (dict/list of arrays), the
config is static, and the three entry points — ``forward`` (training/scoring),
``prefill`` (fill a KV-cache slot), ``decode_step`` (one autoregressive step
for all slots) — are designed to be jitted once with static shapes and reused
for the whole serving lifetime.  ``n_experts > 0`` switches the MLP to a
Mixtral-style sparse-MoE block (models/mixtral.py re-exports the presets; the
expert-parallel all-to-all dispatch path lives in parallel/moe.py).

This stack replaces the reference's remote GPT-4 compute (the reference's
only "model code" is the HTTPS client at common/openai_generic_assistant.py);
architecture follows the public Llama/Mixtral papers, not the reference.

Sharding: weights carry NamedShardings from runtime/sharding.llama_param_specs
(TP over "model", EP over "expert"); under jit XLA inserts the all-gathers /
psums.  Batch dims shard over "data".
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from k8s_llm_rca_tpu.config import ModelConfig
from k8s_llm_rca_tpu.models.quant import (
    QuantTensor4, _pack_nibbles, _unpack_nibbles, dq, gather_rows,
)
from k8s_llm_rca_tpu.ops.attention import (
    causal_attention, decode_attention, decode_attention_multi,
)
from k8s_llm_rca_tpu.ops.mla_attention import absorb_query, unabsorb_values
from k8s_llm_rca_tpu.ops.norms import rms_norm
from k8s_llm_rca_tpu.ops.quant_matmul import qmm, qmm_head, qmm_swiglu_experts
from k8s_llm_rca_tpu.ops.rope import apply_rope, rope_frequencies

Params = Dict[str, Any]


class KVCache(NamedTuple):
    """Slot-based contiguous KV cache: k/v are [L, B, S_max, n_kv*d].

    A reference and a draft cache, not a serving path: ``prefill`` /
    ``decode_step`` / ``decode_multi`` over it are the plain model the
    tests and the benchmark's logits check hold the engine to, and
    engine/speculative.ModelDraft keeps its small draft model's context
    in one.  The engine serves from engine/paged.PagePool.

    The kv-head and head-dim axes are stored MERGED: TPU tiles the last two
    axes of an array to (sublane, 128-lane) tiles, so a [..., n_kv, 64]
    layout pads head_dim 64 -> 128 and silently doubles cache HBM and
    attention read bandwidth.  [..., n_kv*64] keeps the lane axis a
    multiple of 128; call sites reshape to per-head form next to the
    attention einsum, where XLA fuses the (free, row-major) split.

    Optional int8 mode (``init_cache(kv_dtype=jnp.int8)``): k/v are int8
    with one dynamic scale per written token (``k_scale``/``v_scale``
    [L, B, S_max], amax/127 over that token's merged kv vector) — halves
    cache HBM and attention read bandwidth at a small quantization cost.
    Scales are per-token scalars, not per-head, because a [..., S, n_kv]
    scale array would pad n_kv=4 -> 128 lanes and eat the savings.

    Optional int4 mode (``init_cache(kv_dtype="int4")``): same per-token
    scalar scales, but k/v are nibble-PACKED int8 of shape
    [L, B, S_max, kv_dim/2] — two signed 4-bit values per byte along the
    merged kv axis (``models.quant._pack_nibbles``), quartering bf16 cache
    bytes.  The halved last dim is the discriminator: ``_kv_packed(cfg,
    cache)`` is how read/write sites choose the unpack path.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None

    @property
    def max_seq_len(self) -> int:
        return self.k.shape[2]

    @property
    def n_slots(self) -> int:
        return self.k.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def _dense(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array,
                tensor_transform=None) -> Params:
    """Random init (scaled normal).  Real checkpoints load via models/loader.

    ``tensor_transform``: optional hook applied to every matmul weight AS
    IT IS CREATED (norm gains excluded).  Streaming quantization goes
    through this — e.g. ``models.quant.quantize`` per tensor keeps peak
    HBM near the int8 size instead of bf16 + int8 resident together,
    which is what lets an 8B model initialize quantized on a 16G chip.
    """
    dtype = jnp.dtype(cfg.dtype)
    h, q, kv, inter = cfg.hidden_size, cfg.q_dim, cfg.kv_dim, cfg.intermediate_size
    keys = jax.random.split(key, cfg.n_layers + 2)
    scale = 1.0 / math.sqrt(h)
    # a stage cut out of a deeper model carries the deeper model's weights
    depth = cfg.init_layers or cfg.n_layers

    tt = tensor_transform or (lambda w, **_: w)

    def _tdense(key, shape, scale, **tt_kw):
        w = _dense(key, shape, scale, dtype)
        out = tt(w, **tt_kw)
        if out is not w:
            w.delete()                   # free the full-precision original
        return out

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], 8)
        layer: Dict[str, Any] = {
            "attn_norm": jnp.ones((h,), dtype),
            "mlp_norm": jnp.ones((h,), dtype),
            "wo": _tdense(lk[3], (q, h), scale / math.sqrt(2 * depth)),
        }
        if cfg.kv_lora_rank:
            # latent attention: the query whole (no q_lora_rank), the
            # latent and the one rotated key side by side, the latent's own
            # norm, and every head's unrotated key and value out of it
            r, nh = cfg.kv_lora_rank, cfg.n_heads
            layer.update({
                "wq": _tdense(lk[0], (h, nh * cfg.qk_head_dim), scale),
                "w_kva": _tdense(lk[1], (h, cfg.latent_row), scale),
                "kv_norm": jnp.ones((r,), dtype),
                "w_kvb": _tdense(
                    lk[2], (r, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                    1.0 / math.sqrt(r)),
            })
        else:
            layer.update({
                "wq": _tdense(lk[0], (h, q), scale),
                "wk": _tdense(lk[1], (h, kv), scale),
                "wv": _tdense(lk[2], (h, kv), scale),
            })
        if cfg.qk_norm:
            layer.update({"q_norm": jnp.ones((cfg.head_dim,), dtype),
                          "k_norm": jnp.ones((cfg.head_dim,), dtype)})
        if cfg.layer_cfg(i).n_experts > 0:
            e, width = cfg.n_experts, cfg.expert_size
            layer.update(
                {
                    "router": _tdense(lk[4], (h, cfg.n_router), scale),
                    "w_gate": _tdense(lk[5], (e, h, width), scale,
                                      axis=(0, -1)),
                    "w_up": _tdense(lk[6], (e, h, width), scale,
                                    axis=(0, -1)),
                    "w_down": _tdense(
                        lk[7], (e, width, h),
                        scale / math.sqrt(2 * depth), axis=(0, -1)),
                }
            )
            if cfg.router_kind == "sigmoid":
                layer["router_bias"] = 0.1 * jax.random.normal(
                    jax.random.fold_in(lk[4], 1), (cfg.n_router,),
                    jnp.float32)
            if cfg.shared_expert_size:
                sk = jax.random.split(jax.random.fold_in(lk[4], 2), 3)
                shared = cfg.shared_expert_size
                layer.update(
                    {
                        "w_shared_gate": _tdense(sk[0], (h, shared), scale),
                        "w_shared_up": _tdense(sk[1], (h, shared), scale),
                        "w_shared_down": _tdense(
                            sk[2], (shared, h),
                            scale / math.sqrt(2 * depth)),
                    }
                )
        else:
            layer.update(
                {
                    "w_gate": _tdense(lk[5], (h, inter), scale),
                    "w_up": _tdense(lk[6], (h, inter), scale),
                    "w_down": _tdense(
                        lk[7], (inter, h),
                        scale / math.sqrt(2 * depth)),
                }
            )
        layers.append(layer)

    params: Params = {
        "embedding": _tdense(keys[-2], (cfg.vocab_size, h), 1.0, axis=0),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _tdense(keys[-1], (cfg.vocab_size, h), scale,
                                    axis=0)
    return params


def init_cache(cfg: ModelConfig, n_slots: int,
               max_seq_len: Optional[int] = None,
               kv_dtype: Optional[Any] = None) -> KVCache:
    s = max_seq_len or cfg.max_seq_len
    if s > cfg.max_seq_len:
        # positions past the RoPE table would silently clamp to its last row
        # (JAX out-of-bounds gather semantics) and corrupt rotations.
        raise ValueError(
            f"cache max_seq_len {s} exceeds model max_seq_len {cfg.max_seq_len}")
    shape = (cfg.n_layers, n_slots, s, cfg.kv_dim)
    if isinstance(kv_dtype, str) and kv_dtype == "int4":
        # nibble-packed: two 4-bit values per byte along kv_dim (quarter
        # the bf16 cache bytes); per-token scalar scales as in int8 mode
        assert cfg.kv_dim % 2 == 0
        pshape = (*shape[:3], cfg.kv_dim // 2)
        return KVCache(k=jnp.zeros(pshape, jnp.int8),
                       v=jnp.zeros(pshape, jnp.int8),
                       k_scale=jnp.zeros(shape[:3], jnp.dtype(cfg.dtype)),
                       v_scale=jnp.zeros(shape[:3], jnp.dtype(cfg.dtype)))
    if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
        # two DISTINCT buffers: aliasing one zeros array as both scales
        # would donate the same buffer twice under donate_argnums
        return KVCache(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       k_scale=jnp.zeros(shape[:3], jnp.dtype(cfg.dtype)),
                       v_scale=jnp.zeros(shape[:3], jnp.dtype(cfg.dtype)))
    dtype = jnp.dtype(kv_dtype or cfg.dtype)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _kv_packed(cfg: ModelConfig, cache: KVCache) -> bool:
    """True when the cache stores nibble-packed int4 KV (kv_dim halved)."""
    return cache.k.shape[-1] != cfg.kv_dim


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def embed(cfg: ModelConfig, params: Params, tokens: jnp.ndarray
          ) -> jnp.ndarray:
    """tokens [...] -> the residual stream's first value [..., H]: the
    embedding's rows, times ``cfg.embedding_multiplier`` where the model
    has one."""
    x = gather_rows(params["embedding"], tokens).astype(jnp.dtype(cfg.dtype))
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _w_mm(cfg: ModelConfig, x: jnp.ndarray, w) -> jnp.ndarray:
    """Every weight-matmul site funnels through here so
    ``cfg.fused_quant_matmul`` can swap the ``x @ dq(w)`` XLA expression
    for the fused Pallas kernel shim (ops/quant_matmul.qmm) in ONE
    place.  The shim's own fallback IS ``x @ dq(w)``, so the flag is
    numerically inert everywhere the kernel can't run (plain weights,
    non-TPU backends, GSPMD-sharded params)."""
    if cfg.fused_quant_matmul:
        return qmm(x, w)
    return x @ dq(w)


def _qkv(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
         angles: jnp.ndarray, positions: jnp.ndarray):
    """x [B, S, H] -> q [B, S, n_heads, d], k/v [B, S, n_kv, d] (roped q,k).

    Head counts derive from the projection widths (-1), not cfg, so the
    same code serves manual-TP shard bodies whose local weights carry
    n_heads/t heads (parallel/pipeline PP×TP)."""
    b, s, _ = x.shape
    q = _w_mm(cfg, x, layer["wq"]).reshape(b, s, -1, cfg.head_dim)
    k = _w_mm(cfg, x, layer["wk"]).reshape(b, s, -1, cfg.head_dim)
    v = _w_mm(cfg, x, layer["wv"]).reshape(b, s, -1, cfg.head_dim)
    if cfg.qk_norm:
        # one learned gain over the head's width, before the rotation
        q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, angles, positions)
        k = apply_rope(k, angles, positions)
    if cfg.attn_scale:
        # the model's own softmax scale: every attention form scales by
        # 1 / sqrt(head_dim), so the query carries the rest, a power of
        # two (``ModelConfig.q_fold``) and so exact in every dtype
        q = q * jnp.asarray(cfg.q_fold, q.dtype)
    return q, k, v


def _latent_project(cfg: ModelConfig, layer: Params, h: jnp.ndarray,
                    angles: jnp.ndarray, positions: jnp.ndarray):
    """Latent attention's projections of the normed stream ``h`` [B, S, H]:
    every head's unrotated and rotated query ([B, S, n_heads,
    qk_nope_head_dim] and [..., qk_rope_head_dim]) and the ROW the cache
    keeps of each token, [B, S, latent_row]: the latent under its own
    norm, then the one rotated key all heads share."""
    b, s, _ = h.shape
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = _w_mm(cfg, h, layer["wq"]).reshape(b, s, -1, cfg.qk_head_dim)
    kva = _w_mm(cfg, h, layer["w_kva"])
    latent = rms_norm(kva[..., :r], layer["kv_norm"], cfg.rms_norm_eps)
    q_rope = apply_rope(q[..., nope:], angles, positions,
                        cfg.rope_interleave)
    k_rope = apply_rope(kva[..., None, r:], angles, positions,
                        cfg.rope_interleave)[:, :, 0]
    return q[..., :nope], q_rope, jnp.concatenate([latent, k_rope], axis=-1)


def _latent_qkv(cfg: ModelConfig, layer: Params, h: jnp.ndarray, angles,
                positions):
    """Latent attention over whole sequences, in the published form: each
    head's keys (its own unrotated part out of the latent, the shared
    rotated part behind it) and values are written out, to be attended as
    any head's are, at a key width of ``qk_head_dim`` and a value width
    of ``v_head_dim``; every attention form scales by ``1 /
    sqrt(q.shape[-1])``, which is the model's.  Returns q, k [B, S,
    n_heads, qk_head_dim], v [B, S, n_heads, v_head_dim] and the rows to
    cache."""
    q_nope, q_rope, row = _latent_project(cfg, layer, h, angles, positions)
    b, s, nh, nope = q_nope.shape
    r = cfg.kv_lora_rank
    kv = _w_mm(cfg, row[..., :r], layer["w_kvb"]).reshape(b, s, nh, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(row[:, :, None, r:], (b, s, nh, row.shape[-1] - r))],
        axis=-1)
    return jnp.concatenate([q_nope, q_rope], axis=-1), k, kv[..., nope:], row


def latent_decode_query(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
                        angles, positions):
    """The decode block's front half under latent attention, ABSORBED: x
    [B, 1, H] -> the query against the cached rows, [B, n_heads,
    latent_row] (each head's unrotated query carried into the latent's
    space by its own key up-projection, its rotated query behind it), and
    this token's row [B, latent_row]."""
    h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    q_nope, q_rope, row = _latent_project(cfg, layer, h, angles, positions)
    q_abs = absorb_query(q_nope[:, 0], _latent_up(cfg, layer),
                         cfg.qk_nope_head_dim)
    return jnp.concatenate([q_abs, q_rope[:, 0]], axis=-1), row[:, 0]


def _latent_up(cfg: ModelConfig, layer: Params) -> jnp.ndarray:
    """``w_kvb`` by head: [kv_lora_rank, n_heads, qk_nope_head_dim +
    v_head_dim]."""
    return dq(layer["w_kvb"]).reshape(
        cfg.kv_lora_rank, -1, cfg.qk_nope_head_dim + cfg.v_head_dim)


def latent_decode_values(cfg: ModelConfig, layer: Params,
                         o_latent: jnp.ndarray) -> jnp.ndarray:
    """What the absorbed walk returns, the probabilities over the cached
    latents [B, n_heads, kv_lora_rank], through each head's value
    up-projection: [B, 1, q_dim], ``_decode_finish``'s ``attn``."""
    out = unabsorb_values(o_latent, _latent_up(cfg, layer),
                          cfg.qk_nope_head_dim)
    return out.reshape(out.shape[0], 1, cfg.q_dim)


def _mlp(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
         ep_mesh=None, ep_token_axis: str = "data",
         expert_kernel: bool = False,
         local_pairs: Optional[list] = None) -> jnp.ndarray:
    """``ep_mesh``: optional Mesh with an "expert" axis — the MoE block then
    dispatches through the all-to-all expert-parallel path
    (parallel/moe.expert_parallel_moe) instead of ``_moe_mlp`` (token-
    grouped for a large call; for a small one fused from the packed int4
    experts where ``expert_kernel`` allows it, else dense soft dispatch).
    Lossless capacity (capacity_factor = n_experts) so serving under EP
    computes the same function as ``_moe_mlp``; engines bind this at
    construction (BASELINE configs[3]: Mixtral expert-parallel serving).
    ``ep_token_axis``: mesh axis the flattened token dim shards over
    alongside "expert" — "data" for batch prefill/decode, the CP seq axis
    under context-parallel prefill (the sequence stays put; dispatch rides
    the expert axis only).  ``local_pairs``: see ``_moe_mlp``."""
    if cfg.n_experts > 0:
        if ep_mesh is not None:
            from k8s_llm_rca_tpu.parallel.moe import expert_parallel_moe

            return expert_parallel_moe(
                x, layer, ep_mesh, top_k=cfg.n_experts_per_tok,
                capacity_factor=float(cfg.n_experts),
                data_axis=ep_token_axis)
        return _moe_mlp(cfg, layer, x, expert_kernel, local_pairs)
    gate = jax.nn.silu(_w_mm(cfg, x, layer["w_gate"]))
    up = _w_mm(cfg, x, layer["w_up"])
    return _w_mm(cfg, gate * up, layer["w_down"])


# Rows per expert (T * k / E) from which _moe_mlp routes each token's rows to
# its experts instead of running every expert on every token.  Set from one
# layer's MLP at Mixtral-8x7B widths with int4 experts on one TPU v5e (my
# chip runs, PR 30; ms a call, dense form | grouped form at XLA's own tiles,
# by T = B * S; _GROUPED_MATMUL_TILES below takes 10-18% more off the grouped
# form from 2048):
#      T    rows/expert    dense   grouped
#     32          8         5.19    14.12      (a decode call)
#    128         32         5.86    15.63
#    512        128        12.00    20.11
#   1024        256        19.98    22.81
#   1536        384        28.15    25.35      <- the grouped form wins from here
#   2048        512        36.68    28.41
#   4096       1024        69.72    38.47
#  16384       4096       290.45   109.85
# The grouped kernel reads its weights from HBM, so every call first writes
# each expert dequantized whole (0.94 GB a weight): ~14 ms a layer before the
# first row, where the dense form's fusion pays ~5.  From there it costs
# 5.9 us a token against 17.4.
MOE_GROUPED_MIN_ROWS_PER_EXPERT = 384

# The same threshold for small bf16 experts in a latent space
# (``cfg.moe_latent_size > 0``).  Set from one expert layer (router, latent
# projections, shared expert and all) at NVIDIA-Nemotron-3-Super's widths on
# one TPU v5e: 1024 -> 2688 -> 1024 squared-ReLU experts in bf16, 128 of the
# router's 512 held, 22 picks a position (my chip run, PR 32; ms a call,
# dense | grouped, by T = B * S; "all local" is the grouped form when every
# pick is held, the same rows with none behind the last group):
#      T    rows/expert    dense   grouped   (all local)
#     32         1.4        3.15     3.10       5.26       (a decode call)
#     64         2.75       3.03     3.92       6.03       (the cell's decode call)
#    256        11          3.11     6.18       8.48
#   1024        44          9.76     9.10      12.71      <- grouped won from here
#   2048        88         18.06    14.37      21.26
#   4096       176         35.12    20.72      32.49
#  16384       704           -      60.53     101.68      (4 x 4096)
#  32768      1408           -     113.20     192.13      (8 x 4096)
# Nothing is dequantized, so the grouped form has no fixed cost to win back;
# up to 256 positions the dense form is flat at 3 ms (it streams the 0.7 GB
# of held experts through the MXU whatever the rows) and the grouped form
# pays its sort and two row gathers.  That grouped form cost 0.6 of its
# all-local call for a quarter of the rows, and PR 38 found why: XLA's own
# tiles for 2,688 columns (``_grouped_matmul_tiles``), not the rows behind
# the last group, which the kernel skips whole.  The routed experts alone
# (``_experts``; my chip run, PR 38; ms a call: dense | grouped as it was |
# the whole form at fitted tiles | the compact form before it,
# ``moe_compact_rows`` at a slack of 2):
#      T    rows/expert    dense    was     whole   compact
#    256        11          2.08    7.05     2.28     2.29
#    512        22          4.00    7.55     2.57     2.46   <- grouped wins from here
#   1024        44          7.63    8.83     3.23     2.98
#   2048        88           -     10.51     4.55     4.22
#   3072       132           -     15.74     7.13     5.79
#   4096       176           -     17.93     8.58     7.51
# (every pick local, 3,072: 23.67 as it was, 10.42 now; a call that runs
# over the compact form: 10.46).  No bucket of a cell lies between the old
# threshold and the new.
MOE_GROUPED_MIN_ROWS_PER_EXPERT_LATENT = 22


# The same threshold for fine-grained bf16 SwiGLU experts (their own width,
# ``cfg.moe_intermediate_size``, not in a latent space).  Set from one sparse
# MLP (router, routed experts, shared expert) at K-EXAONE-236B-A23B's widths
# on one TPU v5e: 6144 -> 2048 -> 6144 SwiGLU experts in bf16, 16 of the
# router's 128 held, 8 picks a position (my chip run, PR 36; ms a call,
# dense | grouped, by T = B * S; in brackets the routed experts alone, without
# router and shared expert):
#      T    rows/expert    dense            grouped
#     64         4          2.06 (1.76)      2.97 (2.90)    (the cell's decode call)
#    256        16          2.09 (1.95)      3.19 (3.06)
#    512        32          4.10 (3.83)      3.96 (3.61)    <- grouped wins from here
#   1024        64          7.54 (6.92)      5.19 (4.16)
#   2048       128         14.94 (13.94)     7.33 (6.37)
#   4096       256         31.60 (27.79)    11.40 (9.51)
#   6144       384         47.26 (44.28)    15.56 (12.67)
# Nothing is dequantized, so the grouped form has no fixed cost to win back:
# up to 256 positions the dense form is flat at 2 ms (it streams the 1.2 GB
# of held experts whatever the rows) and the grouped form pays its sort and
# its gathers of T x 8 rows; from 512 the dense form computes 16 experts on
# every position for the one pick in eight that is local.  Int4 experts of
# this kind would need a row of their own (the dequantization's fixed cost,
# as in the first table).  The routed experts alone with the compact form
# (my chip run, PR 38; ms a call: dense | the whole form | the compact
# form before it, ``moe_compact_rows`` at a slack of 2):
#      T    rows/expert    dense    whole   compact
#    512        32          3.85     4.00     4.12   (the threshold stays)
#   2048       128           -       6.67     5.96
#   3072       192           -       8.54     8.02
#   4096       256           -      10.34     9.43
#   6144       384           -      14.01    13.21
# Here the tiles divide and were the measured ones already; what the
# compact form saves is the gather of 6,144-wide rows for picks that are
# nobody's here (4.4 ms of the 14 at 6,144), and what it pays is a
# scatter-add of float32 rows as wide (4.5 ms where the whole form's
# gather back and weighted sum take 2.1).
MOE_GROUPED_MIN_ROWS_PER_EXPERT_FINE = 32


def _grouped_min_rows(cfg: ModelConfig) -> int:
    """The table that holds for the experts ``cfg`` describes."""
    if cfg.moe_latent_size:
        return MOE_GROUPED_MIN_ROWS_PER_EXPERT_LATENT
    if cfg.moe_intermediate_size:
        return MOE_GROUPED_MIN_ROWS_PER_EXPERT_FINE
    return MOE_GROUPED_MIN_ROWS_PER_EXPERT


def moe_grouped(cfg: ModelConfig, n_tokens: int) -> bool:
    """Whether the expert layer takes the token-grouped path for a call of
    ``n_tokens`` positions (``B * S``, pad positions included).  The one
    place that decides: a prefill of 2048 positions gives a top-2-of-8
    router 512 rows an expert, a decode call of 32 slots gives 8, where
    grouping saves no arithmetic that matters, could skip no expert's
    dequantization (dead slots route too) and would put a sort into every
    step of a scan.  The rows an expert sees are the router's to say
    (``T * k`` over the experts it scores, held here or not).  Below the
    threshold ``moe_fused`` chooses between the two small-call forms."""
    if cfg.n_experts <= 0:
        return False
    rows_per_expert = n_tokens * cfg.n_experts_per_tok / cfg.n_router
    return rows_per_expert >= _grouped_min_rows(cfg)


# Positions (T = B * S) up to which a call that is not token-grouped reads
# its stacked int4 SwiGLU experts PACKED (ops/quant_matmul.py's
# ``quant_matmul_ekn4_swiglu`` and ``quant_matmul_ekn4``: nibbles unpacked in
# VMEM on the way to the MXU) instead of ``einsum(x, dq(w))``, which unpacks
# all eight experts whole in HBM first.  Set from one layer's expert MLP at
# Mixtral-8x7B widths with int4 experts on one TPU v5e (my chip run, PR 35;
# ms a call, XLA dense form | fused form; the grouped form for comparison
# from the table above, at XLA's tiles | _GROUPED_MATMUL_TILES):
#      T     dense    fused
#      8      5.24     1.10
#     32      5.25     1.16      (a decode call: 705 MB packed in 1.16 ms)
#     64      5.60     1.33
#    128      5.91     2.20
#    256      7.80     4.27
#    512     12.06     8.40
#   1024     20.06    16.64
#   1536     28.23    24.91      (grouped: 25.35 | 23.14, and it is taken)
#   2048     36.72    33.36      (grouped: 28.41 | 25.20)
# The fused form is 4.5 times faster at a decode call (three quarters of the
# HBM bandwidth on the packed bytes) and never slower than the dense form at
# any size measured: from 256 positions both are bound by the MXU and the
# fused form saves the unpack's pass over HBM, 3.4-3.7 ms.  So below the
# grouped threshold the XLA dense form stays only for what is not int4
# SwiGLU; the constant is the largest size measured, and matters to a router
# whose grouped form starts later than Mixtral's 1,536 positions.
MOE_FUSED_MAX_POSITIONS = 2048


def moe_fused(cfg: ModelConfig, layer: Params, n_tokens: int) -> bool:
    """Whether a call of ``n_tokens`` positions that ``moe_grouped`` leaves
    to the small-call forms reads its experts packed: stacked int4 weights
    (what the kernels read), a SwiGLU MLP (what they compute), and a call
    no larger than the chip's table allows.  Seen from the weights' storage
    type and the call's shape; whether a Pallas kernel may be called at all
    (no mesh bound, the weights whole on one device) is the engine's to
    say, where it binds its programs (``expert_kernel``)."""
    return (cfg.n_experts > 0 and cfg.mlp_act == "swiglu"
            and not cfg.moe_latent_size
            and all(isinstance(layer.get(name), QuantTensor4)
                    for name in ("w_gate", "w_up", "w_down"))
            and not moe_grouped(cfg, n_tokens)
            and n_tokens <= MOE_FUSED_MAX_POSITIONS)


def _route(cfg: ModelConfig, layer: Params, x: jnp.ndarray):
    """The router: x [B, S, H] -> (experts [B, S, k] int32 in the router's
    own numbering, weights [B, S, k] float32).

    - ``softmax`` (Mixtral): the top k logits, softmax over the kept ones.
    - ``sigmoid`` (nemotron_h): scores ``s = sigmoid(x W_r)`` in float32
      over every expert the router scores; the k chosen are the top of
      ``s + bias`` (the selection bias moves the CHOICE only); weights
      ``routed_scaling * s / sum(s)`` over the k chosen, held here or not.
    """
    k = cfg.n_experts_per_tok
    if cfg.router_kind == "sigmoid":
        scores = jax.nn.sigmoid(jnp.einsum(
            "bsh,he->bse", x, dq(layer["router"]),
            preferred_element_type=jnp.float32))
        _, topi = jax.lax.top_k(
            scores + layer["router_bias"].astype(jnp.float32), k)
        chosen = jnp.take_along_axis(scores, topi, axis=-1)
        return topi, cfg.routed_scaling * chosen / jnp.sum(
            chosen, axis=-1, keepdims=True)
    if cfg.router_kind != "softmax":
        raise ValueError(f"unknown router_kind {cfg.router_kind!r}")
    router_logits = _w_mm(cfg, x, layer["router"]).astype(jnp.float32)  # [B,S,E]
    topv, topi = jax.lax.top_k(router_logits, k)                   # [B,S,k]
    return topi, jax.nn.softmax(topv, axis=-1)                     # [B,S,k]


def _held(cfg: ModelConfig, topi: jnp.ndarray):
    """The router's choices in the numbering of the experts HELD here:
    (local ids [B, S, k], with ``cfg.n_experts`` for an expert that lives
    elsewhere; which pairs are held, or None where every expert is)."""
    if cfg.n_router == cfg.n_experts:
        return topi, None
    local = topi - cfg.expert_first
    held = (local >= 0) & (local < cfg.n_experts)
    return jnp.where(held, local, cfg.n_experts), held


def n_local_pairs(cfg: ModelConfig, topi: jnp.ndarray) -> jnp.ndarray:
    """How many of the router's (position, expert) choices name an expert
    held here, an int32 scalar: ``engine.moe_local_pairs``."""
    _, held = _held(cfg, topi)
    return (jnp.sum(held, dtype=jnp.int32) if held is not None
            else jnp.int32(topi.size))


def _shared_hidden(cfg: ModelConfig, layer: Params, x: jnp.ndarray
                   ) -> jnp.ndarray:
    """The always-on shared expert's hidden activation on x [..., H]
    (``w_shared_down`` takes it back): SwiGLU, or the non-gated squared
    ReLU of a ``relu2`` model."""
    up = _w_mm(cfg, x, layer["w_shared_up"])
    if cfg.mlp_act == "relu2":
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(_w_mm(cfg, x, layer["w_shared_gate"])) * up


def _moe_mlp(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
             expert_kernel: bool = False,
             local_pairs: Optional[list] = None) -> jnp.ndarray:
    """Sparse-expert MLP: the router's k experts a token (``_route``),
    their MLPs, the weighted sum (``_experts``), and beside it the shared
    expert where the model has one.  ``local_pairs``: a list the caller
    sums; this layer's ``n_local_pairs`` is appended to it."""
    topi, weights = _route(cfg, layer, x)
    if local_pairs is not None:
        local_pairs.append(n_local_pairs(cfg, topi))
    out = _experts(cfg, layer, x, topi, weights, expert_kernel)
    if cfg.shared_expert_size:
        out = out + _w_mm(cfg, _shared_hidden(cfg, layer, x),
                          layer["w_shared_down"])
    return out


def _experts(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
             topi: jnp.ndarray, weights: jnp.ndarray,
             expert_kernel: bool = False) -> jnp.ndarray:
    """The chosen experts' MLPs on x [B, S, W] and their weighted sum, for
    every model that has experts: SwiGLU (``w_gate``, ``w_up``, ``w_down``)
    or the non-gated squared ReLU (``w_up``, ``w_down``), all experts or
    the share held here (``cfg.expert_first``, ``cfg.n_experts`` of
    ``cfg.n_router``: a pair whose expert lives elsewhere adds nothing, and
    nothing stands in for it).  Three forms of one function, chosen here
    from what the call shows (``moe_grouped``, ``moe_fused``):

    - a large call (prefill, training) is **token-grouped**
      (``_moe_experts_grouped``): each token's row goes to its experts
      only, so the expert arithmetic is k/E of the dense form's, and where
      a share of the router's experts is held, to those held here only
      (the compact form, ``moe_compact_rows``);
    - a small call (decode) is **dense soft dispatch**: every expert held
      runs on every token and the router's weights zero out the rest — one
      einsum per projection, no sort, exactly equal to hard routing;
    - a small call over stacked int4 SwiGLU experts is the dense form
      **fused from the packed weights** (``qmm_swiglu_experts``): the same
      sums, with the nibbles unpacked in VMEM on the way to the MXU and the
      scale applied to the float32 output tile, where ``einsum(x, dq(w))``
      unpacks and scales every expert whole in HBM first.  Only where
      ``expert_kernel`` says a Pallas call may stand in the program: the
      engine's word, given where it binds its programs (no mesh, the
      weights whole on one device); every other caller (training, the
      reference loops, the sharded paths) leaves it False.

    All are lossless: no pair of an expert held here is dropped.  The one
    capacity there is, the compact form's row count, sizes a buffer and
    never drops: a call whose local pairs run over it takes the whole
    grouped form behind it and computes the same sum
    (``n_compact_overflows`` counts such calls).  The bandwidth-optimal EP
    dispatch (all_to_all over the "expert" axis) lives in parallel/moe.py
    and is used by the sharded engine path.
    """
    b, s, h = x.shape
    e = cfg.n_experts
    topi, _ = _held(cfg, topi)
    if moe_grouped(cfg, b * s):
        return _moe_experts_grouped(cfg, layer, x, topi, weights)
    # scatter the top-k weights back to a dense [B,S,E] map (an expert
    # held elsewhere is past the last column and lands nowhere)
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)            # [B,S,k,E]
    dense_w = jnp.einsum("bske,bsk->bse", onehot, weights)         # [B,S,E]

    if cfg.mlp_act == "relu2":
        hid = jnp.square(jax.nn.relu(
            jnp.einsum("bsh,ehi->bsei", x, dq(layer["w_up"]))))
        # weight, then contract experts and width in one matmul
        return jnp.einsum("bsei,eih->bsh",
                          hid * dense_w.astype(x.dtype)[..., None],
                          dq(layer["w_down"]))
    if expert_kernel and moe_fused(cfg, layer, b * s):
        per_expert = qmm_swiglu_experts(x, layer["w_gate"], layer["w_up"],
                                        layer["w_down"])
    else:
        gate = jax.nn.silu(jnp.einsum("bsh,ehi->bsei", x, dq(layer["w_gate"])))
        up = jnp.einsum("bsh,ehi->bsei", x, dq(layer["w_up"]))
        per_expert = jnp.einsum("bsei,eih->bseh", gate * up,
                                dq(layer["w_down"]))
    return jnp.einsum("bseh,bse->bsh", per_expert,
                      dense_w.astype(x.dtype))


# Row, contraction and column tile of XLA's grouped-matmul kernel where the
# three dimensions divide by them; left to itself XLA takes 512 x 512 x 512.
# One layer's MLP as above, ms a call (my chip runs, PR 30), XLA's tiles |
# these: T = 2048: 28.41 | 25.20; 4096: 38.48 | 34.35; 8192: 63.10 | 52.57;
# 16384: 109.84 | 90.27 (megablox gmm at the same tiles: 90.43).  Of the
# seven other tilings the kernel's VMEM admits none is better at 8192 and
# over; 256 x 2048 x 1024 is 4-5% better at 2048 and 4096 and worse above.
_GROUPED_MATMUL_TILES = (512, 1024, 1024)

# Where they do not divide (NVIDIA-Nemotron-3-Super's experts are 1024 ->
# 2688 -> 1024, and 2688 is 21 x 128) XLA's own choice falls to a 128-wide
# tile of the dimension that 512 does not divide (512 x 512 x 128 up,
# 512 x 128 x 512 down).  The tiles taken then are the largest multiples
# of 128 that divide, within these bounds (the row tile, a side of the
# weight tile, its elements): what was best of what the kernel's VMEM
# admitted on one TPU v5e (my chip run, PR 38; 25,600 rows of which
# 16,064 in 128 groups, ms a call, up | down; down's tiles mirror up's):
#   XLA's own                   6.80 | 4.53
#   128 x 1024 x 896            1.86 | 2.14
#   256 x 1024 x 896            1.78 | 1.86
#   256 x 512 x 2688            1.79 | 1.62
#   256 x 1024 x 2688           1.64 | 1.55    <- taken
#   512 x 1024 x 896            2.45 | 2.49
#   512 x 1024 x 2688           refused: out of VMEM
# The same times at 67,584 rows with the same 16,064 in groups: the kernel
# skips the rows behind the last group whole, and what it costs is set by
# the groups it visits, a row tile each at the least.
_GROUPED_MATMUL_FITTED = (256, 2688, 1024 * 2688)


def _grouped_matmul_tiles(m: int, k: int, n: int) -> Optional[Tuple]:
    """The tiles ``_grouped_matmul`` asks of XLA's kernel for rows ``[m,
    k]`` against weights ``[E, k, n]``: the measured ones where they
    divide; for weights they do not divide the largest multiples of 128
    that do, within the bounds measured with them (row tile, a side of
    the weight tile, its elements); else None, XLA's own choice."""
    tile_m, tile_k, tile_n = _GROUPED_MATMUL_TILES
    if k % tile_k == 0 and n % tile_n == 0:
        return None if m % tile_m else _GROUPED_MATMUL_TILES

    def fit(dim, most):
        return max((t for t in range(128, min(dim, most) + 1, 128)
                    if dim % t == 0), default=0)

    rows, side, weight = _GROUPED_MATMUL_FITTED
    tile_m, tile_k = fit(m, rows), fit(k, side)
    tile_n = tile_k and fit(n, min(side, weight // tile_k))
    return (tile_m, tile_k, tile_n) if tile_m and tile_n else None


def _grouped_matmul(rows: jnp.ndarray, w: jnp.ndarray,
                    group_sizes: jnp.ndarray) -> jnp.ndarray:
    """``rows[g_e : g_e+1] @ w[e]`` for every expert ``e``: rows ``[M, K]``
    sorted by expert, ``w`` ``[E, K, N]``, ``group_sizes`` ``[E]``.  On a
    TPU ``jax.lax.ragged_dot`` is XLA's own grouped-matmul kernel, which
    reads its tiles from the operation's ``ragged_dot_tiling`` attribute
    (``_grouped_matmul_tiles``); a shape no multiple of 128 divides keeps
    XLA's choice (its row tile is the largest divisor of ``M`` up to
    512).  Elsewhere the attribute is ignored."""
    tiles = _grouped_matmul_tiles(rows.shape[0], rows.shape[1], w.shape[-1])
    if tiles is None:
        return jax.lax.ragged_dot(rows, w, group_sizes)
    with set_xla_metadata(ragged_dot_tiling=",".join(map(str, tiles))):
        return jax.lax.ragged_dot(rows, w, group_sizes)


# Rows of the compact grouped form over the local pairs a uniform router
# would send here (``pairs * n_experts / n_router``): room for a call whose
# router favours the experts held, before the whole form has to take it.
# Set from one call's local pairs over that number (one row of a bucket in
# one expert layer; the two held-share cells' own weights at three seeds,
# prompts as their mixes make them, pad positions and all; my chip run, PR
# 38; 180 and 144 calls):
#                                     least  median  9 in 10   most
#   128 of 512 held, 22 picks          0.75    0.97    1.28    1.47
#   16 of 128 held, 8 picks            0.42    1.37    1.73    1.98
# A layer's share is its seeded selection bias's and hardly a prompt's (a
# layer's calls lie within 0.1 of each other), so a layer that runs over
# does so in every call: the slack is for the luckiest layer of a seed, and
# the fewer experts are held the further it lies (a layer's mean: 0.79-1.40
# and 0.50-1.75).  At 1.5 no call of the first kind and three in ten of
# the second run over; at 2 none of either.  What the room costs (the
# routed experts of one layer, ms a call, slack 1.5 | 2): 3,072 positions of
# the first kind 5.16 | 5.79; 6,144 of the second 11.81 | 13.21, and a call
# that runs over 34.7 where the whole form alone takes 31.3.  A ladder of
# sizes chosen from the count would win back that tenth and is not built:
# a third and fourth program a layer for 2% of a prefill.
MOE_COMPACT_SLACK = 2.0


def moe_compact_rows(cfg: ModelConfig, n_positions: int) -> Optional[int]:
    """Rows of the compact form of ``_moe_experts_grouped`` for a call of
    ``n_positions``, from what the call shows: its pairs, the share of the
    router's experts held here, ``MOE_COMPACT_SLACK``, up to a whole row
    tile of the grouped kernel.  None where such a call has no compact
    form: it is not token-grouped, every expert is held, or the rows would
    be all its pairs (a call so small that one tile covers it)."""
    if cfg.n_router == cfg.n_experts or not moe_grouped(cfg, n_positions):
        return None
    pairs = n_positions * cfg.n_experts_per_tok
    tile = _GROUPED_MATMUL_TILES[0]
    local = pairs * cfg.n_experts / cfg.n_router * MOE_COMPACT_SLACK
    rows = -(-math.ceil(local) // tile) * tile
    return rows if rows < pairs else None


def n_compact_overflows(cfg: ModelConfig, n_positions: int,
                        local_pairs) -> jnp.ndarray:
    """How many expert-layer calls of ``n_positions`` each, whose local
    pairs ``local_pairs`` lists (``n_local_pairs``, a call an entry), ran
    over the compact form of ``_moe_experts_grouped`` and took the whole
    one, an int32 scalar: ``engine.moe_compact_overflows``.  0 where such
    a call holds one form alone."""
    rows = moe_compact_rows(cfg, n_positions)
    if rows is None:
        return jnp.int32(0)
    return sum(((n > rows).astype(jnp.int32) for n in local_pairs),
               jnp.int32(0))


def _moe_experts_grouped(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
                         topi: jnp.ndarray, weights: jnp.ndarray
                         ) -> jnp.ndarray:
    """The expert MLPs of ``_experts`` on routed rows only.  The ``T * k``
    (token, expert) pairs are stable-sorted by expert, the tokens' rows
    gathered in that order, and the projections run as grouped matmuls
    over the stacked expert weights (``_grouped_matmul``: rows ``[g_e,
    g_e+1)`` meet expert ``e`` only).  ``group_sizes`` is counted from the
    data, so it is exact: every pair of an expert held here is computed,
    whatever the spread (an expert no token chose is an empty group).
    ``topi`` numbers the experts held (``_held``); a pair whose expert
    lives elsewhere carries the id one past the last, sorts behind every
    group, belongs to none and is given no weight.

    Two forms of the same sum.  The **whole** form gathers a row for every
    pair (``[T * k, H]``), and the pairs go back to their tokens by the
    inverse permutation and are summed under the router's weights, as the
    dense form sums them.  Where a share of the router's experts is held,
    the first ``n = sum(group_sizes)`` sorted pairs are exactly the local
    ones, and the **compact** form takes a static ``moe_compact_rows``
    prefix of the order instead: that many rows gathered, multiplied and
    added to their tokens' rows under the router's weights (in float32,
    cast once; a row at or past ``n`` is set to zero, never multiplied).
    It is chosen on the device (``lax.cond``) while ``n`` fits; a call
    whose router sends more here takes the whole form and costs what it
    cost before: the capacity sizes a buffer and drops nothing.
    ``ragged_dot``, the conditional and the scatter-add have JVP and
    transpose rules, so the path differentiates (engine/train.py)."""
    b, s, h = x.shape
    k = cfg.n_experts_per_tok
    pairs = b * s * k
    expert = topi.reshape(pairs)
    order = jnp.argsort(expert, stable=True)            # pair ids by expert
    if cfg.n_router == cfg.n_experts:
        group_sizes = jnp.bincount(
            expert, length=cfg.n_experts).astype(jnp.int32)
    else:
        # compared and summed: a bincount is a scatter-add of every pair
        group_sizes = jnp.sum(
            expert[:, None] == jnp.arange(cfg.n_experts, dtype=expert.dtype),
            axis=0, dtype=jnp.int32)
    x_rows = x.reshape(b * s, h)

    def mlp(rows):
        if cfg.mlp_act == "relu2":
            hid = jnp.square(jax.nn.relu(
                _grouped_matmul(rows, dq(layer["w_up"]), group_sizes)))
            return _grouped_matmul(hid, dq(layer["w_down"]), group_sizes)
        gate = jax.nn.silu(
            _grouped_matmul(rows, dq(layer["w_gate"]), group_sizes))
        up = _grouped_matmul(rows, dq(layer["w_up"]), group_sizes)
        return _grouped_matmul(gate * up, dq(layer["w_down"]), group_sizes)

    def whole():
        out = mlp(x_rows[order // k])                              # [T*k,H]
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(pairs, dtype=order.dtype))       # pair -> sorted row
        per_pair = out[inverse].reshape(b, s, k, -1)
        if cfg.n_router != cfg.n_experts:
            # rows behind the last group are no expert's: whatever the
            # grouped kernel left there is dropped, not multiplied by a
            # zero weight
            per_pair = jnp.where((topi < cfg.n_experts)[..., None],
                                 per_pair, 0)
        return jnp.einsum("bskh,bsk->bsh", per_pair, weights.astype(x.dtype))

    cap = moe_compact_rows(cfg, b * s)
    if cap is None:
        return whole()
    n_local = jnp.sum(group_sizes)

    def compact():
        picked = order[:cap]                # the local pairs first, by expert
        token = picked // k
        out = mlp(x_rows[token])                                   # [cap,H]
        weighted = jnp.where(
            (jnp.arange(cap) < n_local)[:, None],
            out.astype(jnp.float32)
            * weights.reshape(pairs)[picked][:, None], 0)
        summed = jnp.zeros((b * s, out.shape[-1]), jnp.float32).at[
            token].add(weighted)
        return summed.astype(jnp.result_type(out.dtype, x.dtype)).reshape(
            b, s, -1)

    return jax.lax.cond(n_local <= cap, compact, whole)


def _sp_constrain(x: jnp.ndarray, sp_mesh) -> jnp.ndarray:
    """Megatron-style sequence parallelism between TP regions: constrain
    the residual stream's SEQUENCE dim to shard over the TP axis
    ("model").  Norms/elementwise then run on 1/t of the tokens instead
    of replicating, and GSPMD lowers each TP all-reduce into the
    reduce-scatter + all-gather pair around the matmul regions — same
    communication volume, 1/t the activation memory and pointwise
    compute.  No-op when ``sp_mesh`` is None."""
    if sp_mesh is None:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(sp_mesh, P(None, "model", None)))


def _block_prefill(cfg, layer, x, angles, positions, seq_lens,
                   attention_fn=None, ep_mesh=None,
                   ep_token_axis: str = "data", sp_mesh=None,
                   expert_kernel: bool = False,
                   local_pairs: Optional[list] = None):
    """One transformer block over a full sequence.  ``attention_fn``
    defaults to masked causal attention (always safe: differentiable for
    training, GSPMD-partitionable for TP); inference prefill passes the
    Pallas flash kernel via ``prefill_kv(use_flash=True)`` and the
    context-parallel prefill passes ring attention (same (q, k, v) -> out
    contract).  ``sp_mesh``: Megatron-style SP — the residual stream
    seq-shards over "model" at both norm points (_sp_constrain)."""
    x = _sp_constrain(x, sp_mesh)
    h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    if cfg.kv_lora_rank:
        q, k, v, row = _latent_qkv(cfg, layer, h, angles, positions)
    else:
        q, k, v = _qkv(cfg, layer, h, angles, positions)
    if attention_fn is None:
        attn = causal_attention(q, k, v, seq_lens)
    else:
        attn = attention_fn(q, k, v)
    if cfg.kv_lora_rank:
        # what is cached is the token's latent row, and no value beside it
        k, v = row, None
    b, s, _, _ = attn.shape
    x = x + _w_mm(cfg, attn.reshape(b, s, cfg.q_dim), layer["wo"])
    x = _sp_constrain(x, sp_mesh)
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    x = x + _mlp(cfg, layer, h, ep_mesh, ep_token_axis, expert_kernel,
                 local_pairs)
    return x, k, v


def _decode_qkv(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
                angles: jnp.ndarray, positions: jnp.ndarray):
    """Decode-block front half: pre-attention norm + roped q/k/v.  Shared
    by the reference, paged and pipeline-parallel decode paths so the
    block semantics cannot drift apart."""
    h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    return _qkv(cfg, layer, h, angles, positions)


def _decode_finish(cfg: ModelConfig, layer: Params, x: jnp.ndarray,
                   attn: jnp.ndarray, ep_mesh=None,
                   expert_kernel: bool = False,
                   local_pairs: Optional[list] = None) -> jnp.ndarray:
    """Decode-block back half: attention output projection + residual +
    MLP (shared across decode paths, see _decode_qkv).  ``attn`` must
    already be flattened to [B, T, q_dim] — kernel outputs vary in rank,
    so call sites own the reshape."""
    x = x + _w_mm(cfg, attn, layer["wo"])
    hm = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    return x + _mlp(cfg, layer, hm, ep_mesh, expert_kernel=expert_kernel,
                    local_pairs=local_pairs)


def _quantize_kv(kv: jnp.ndarray, packed: bool = False,
                 axis_name: Optional[str] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token int8 (or nibble-packed int4 when ``packed``): kv
    [..., kv_dim] -> (int8 [..., kv_dim] | packed int8 [..., kv_dim/2],
    scale [...]).  The scale stays a per-token SCALAR in both modes: any
    trailing group axis would lane-pad to 128 on TPU and eat the savings
    (see KVCache docstring).

    ``axis_name``: inside a manual-TP shard_map body (parallel/pipeline
    PP×TP) each shard holds only its slice of the kv row; pmax-ing the
    local amax over the TP axis reproduces the FULL-row scale bit-for-bit,
    so shards quantize their slices exactly as the unsharded path
    quantizes the whole row — scale pools stay replicated across TP and
    quantized PP×TP matches the plain engines token-for-token."""
    qmax = 7.0 if packed else 127.0
    amax = jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=-1)
    if axis_name is not None:
        amax = jax.lax.pmax(amax, axis_name)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(kv.astype(jnp.float32) / scale[..., None]),
                 -qmax, qmax).astype(jnp.int8)
    if packed:
        q = _pack_nibbles(q)
    return q, scale.astype(kv.dtype)


def _dequant_layer(k_cache: jnp.ndarray, scale: Optional[jnp.ndarray],
                   dtype, packed: bool = False) -> jnp.ndarray:
    """[B, S, kv_dim] int8 (or [B, S, kv_dim/2] packed int4) + [B, S]
    scale -> dtype (identity when scale is None).  Expressed as
    convert*scale (plus shift/mask unpack for int4) at the read site for
    XLA to fuse into the attention einsum."""
    if scale is None:
        return k_cache
    if packed:
        k_cache = _unpack_nibbles(k_cache)
    return k_cache.astype(dtype) * scale[..., None].astype(dtype)


def _write_prefill_kv(cfg: ModelConfig, cache: KVCache, new_k, new_v,
                      slot) -> KVCache:
    """Write one sequence's full-depth prefill KV into cache slot ``slot``
    at sequence offset 0 (shared by the plain and CP prefill paths)."""
    L, s_pad = new_k.shape[0], new_k.shape[1]
    new_k = new_k.reshape(L, 1, s_pad, cfg.kv_dim)
    new_v = new_v.reshape(L, 1, s_pad, cfg.kv_dim)
    if cache.quantized:
        packed = _kv_packed(cfg, cache)
        new_k, ks = _quantize_kv(new_k, packed)
        new_v, vs = _quantize_kv(new_v, packed)
        k_scale = jax.lax.dynamic_update_slice(cache.k_scale, ks,
                                               (0, slot, 0))
        v_scale = jax.lax.dynamic_update_slice(cache.v_scale, vs,
                                               (0, slot, 0))
    else:
        k_scale, v_scale = cache.k_scale, cache.v_scale
    k_cache = jax.lax.dynamic_update_slice(cache.k, new_k, (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache.v, new_v, (0, slot, 0, 0))
    return KVCache(k_cache, v_cache, k_scale, v_scale)


def _logits(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    if cfg.fused_quant_matmul:
        logits = qmm_head(x, head).astype(jnp.float32)
    else:
        logits = jnp.einsum("bsh,vh->bsv", x, dq(head)).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def forward(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            seq_lens: Optional[jnp.ndarray] = None,
            ep_mesh=None, sp_mesh=None) -> jnp.ndarray:
    """Training/scoring forward: tokens [B, S] -> logits [B, S, V] (fp32).

    ``sp_mesh``: Megatron-style sequence parallelism — under TP, the
    residual stream between matmul regions seq-shards over "model"
    (_sp_constrain); pass the TP mesh."""
    b, s = tokens.shape
    if seq_lens is None:
        seq_lens = jnp.full((b,), s, jnp.int32)
    angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = gather_rows(params["embedding"], tokens).astype(jnp.dtype(cfg.dtype))
    for li, layer in enumerate(params["layers"]):
        x, _, _ = _block_prefill(
            cfg.layer_cfg(li), layer, x, angles, positions, seq_lens,
            _layer_attention_fn(cfg, li, seq_lens, False, s),
            ep_mesh=ep_mesh, sp_mesh=sp_mesh)
    return _logits(cfg, params, x)


# padded positions from which a prefill that may use the Pallas flash kernel
# does: below it the masked XLA form is as fast and compiles everywhere
FLASH_MIN_POSITIONS = 1024


def prefill_uses_flash(use_flash: bool, s_pad: int) -> bool:
    """Whether a prefill call over ``s_pad`` padded positions runs the
    Pallas flash kernel (a band for a window layer): the one rule of every
    prefill loop here, and of whoever counts what those calls covered
    (``engine.attn_window_prefill_tokens``)."""
    return bool(use_flash) and s_pad >= FLASH_MIN_POSITIONS


def _refuse_window_layers(cfg: ModelConfig, what: str) -> None:
    """The loops that hand back or keep EVERY position's keys and values
    of every layer (the contiguous ``KVCache``, the whole-depth prefills)
    have no place for what a sliding-window layer keeps, the last window in
    a ring of pages per decode slot: such a model is served by
    ``prefill_rows`` and the paged decode step, and scored by ``forward``.
    Layers that differ in anything else (rotary embedding by kind, a
    leading dense MLP) these loops read from ``cfg.layer_cfg``."""
    if cfg.n_window_layers:
        raise ValueError(
            f"{what} is not built for {cfg.name!r}: its "
            f"{cfg.n_window_layers} sliding-window layers keep the last "
            f"{cfg.attn_window} positions in a ring of pages per decode "
            f"slot, and this loop keeps every position of every layer")


def _refuse_latent(cfg: ModelConfig, what: str) -> None:
    """The loops over the contiguous ``KVCache`` and the whole-depth
    prefills hand back keys and values per head; a model with latent
    attention caches one latent row a token and is served by
    ``prefill_latent_row`` and the paged decode step, and scored by
    ``forward``."""
    if cfg.kv_lora_rank:
        raise ValueError(
            f"{what} is not built for {cfg.name!r}: latent attention "
            f"(kv_lora_rank={cfg.kv_lora_rank}) caches one row of "
            f"{cfg.latent_row} values a token, and this loop keeps keys "
            f"and values per head")


# q and key block of the lean flash call (ops/flash_attention.py) that the
# full layers of a model with window layers make: one 6144-position row of
# 64 heads takes 93.3 ms plain, 13.0 lean in 512-blocks, 7.0 in 1024-blocks
# (my chip run, PR 36, PERF.md section 6)
FLASH_LEAN_BLOCK = 1024


def _layer_attention_fn(cfg: ModelConfig, li: int, seq_lens,
                        use_flash: bool, s_pad: int):
    """The prefill attention of layer ``li`` over fresh sequences: the
    flash kernel where ``prefill_uses_flash`` (a window layer's band as
    it is, its time being grid steps; a full layer's call lean and in
    large blocks), else the masked XLA form (None: ``_block_prefill``'s
    default, the full causal one)."""
    window = cfg.attn_windows[li]
    if prefill_uses_flash(use_flash, s_pad):
        from k8s_llm_rca_tpu.ops.flash_attention import flash_attention

        if window:
            return lambda q, k, v: flash_attention(
                q, k, v, seq_lens, interpret=False, window=window)
        return lambda q, k, v: flash_attention(
            q, k, v, seq_lens, interpret=False, lean=True,
            block_q=FLASH_LEAN_BLOCK, block_k=FLASH_LEAN_BLOCK)
    if window:
        return lambda q, k, v: causal_attention(q, k, v, seq_lens,
                                                window=window)
    return None


def _flash_attention_fn(seq_lens, flash_mesh):
    """attention_fn for the Pallas flash kernel: per-shard under a TP mesh
    (ops.flash_attention_sharded — heads sharded over "model"), plain
    kernel otherwise."""
    if flash_mesh is not None:
        from k8s_llm_rca_tpu.ops.flash_attention import (
            flash_attention_sharded,
        )

        return lambda q, k, v: flash_attention_sharded(
            q, k, v, seq_lens, flash_mesh, interpret=None)
    from k8s_llm_rca_tpu.ops.flash_attention import flash_attention

    return lambda q, k, v: flash_attention(q, k, v, seq_lens,
                                           interpret=False)


def prefill_kv(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
               length: jnp.ndarray, use_flash: bool = False,
               ep_mesh=None, flash_mesh=None, sp_mesh=None,
               expert_kernel: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared prefill compute (the reference's slot write below, the
    engine's page scatter in engine/paged.py): run the stack over ONE
    right-padded sequence and return its full-depth KV plus the last valid
    token's logits.

    ``use_flash`` (static) routes attention through the Pallas flash
    kernel for S_pad >= 1024: the XLA path materializes the [H, S, S]
    fp32 score matrix and stops compiling around S=8k, flash streams it.
    Leave False for differentiation (pallas_call has no VJP) or
    TP-sharded params (no SPMD partitioning rule — it would replicate);
    the engine enables it automatically when safe.

    tokens [1, S_pad], ``length`` scalar valid length.  Returns
    (new_k [L, S_pad, n_kv, d], new_v likewise, logits [1, V]).
    """
    _refuse_window_layers(cfg, "llama.prefill_kv")
    _refuse_latent(cfg, "llama.prefill_kv")
    _, s_pad = tokens.shape
    angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.arange(s_pad)[None, :]
    seq_lens = jnp.asarray(length).reshape(1)
    x = gather_rows(params["embedding"], tokens).astype(jnp.dtype(cfg.dtype))

    attention_fn = None
    if prefill_uses_flash(use_flash, s_pad):
        attention_fn = _flash_attention_fn(seq_lens, flash_mesh)

    ks, vs = [], []
    for li, layer in enumerate(params["layers"]):
        x, k, v = _block_prefill(cfg.layer_cfg(li), layer, x, angles,
                                 positions, seq_lens, attention_fn, ep_mesh,
                                 sp_mesh=sp_mesh,
                                 expert_kernel=expert_kernel)
        ks.append(k[0])  # [S_pad, n_kv, d]
        vs.append(v[0])

    last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)  # [1,1,H]
    logits = _logits(cfg, params, last)[:, 0]                       # [1, V]
    return jnp.stack(ks), jnp.stack(vs), logits


def prefill(cfg: ModelConfig, params: Params, cache: KVCache,
            tokens: jnp.ndarray, length: jnp.ndarray, slot: jnp.ndarray,
            use_flash: bool = False, ep_mesh=None, flash_mesh=None,
            sp_mesh=None
            ) -> Tuple[KVCache, jnp.ndarray]:
    """Prefill ONE sequence into cache slot ``slot``.

    tokens [1, S_pad] right-padded; ``length`` scalar valid length; returns
    (cache', last-token logits [1, V]).  One compile per padded bucket length
    (the callers bucket prompt lengths to keep recompiles bounded).
    ``use_flash``: see prefill_kv.  ``flash_mesh``: run the kernel
    per-head-shard under this TP mesh (ops.flash_attention_sharded).
    """
    new_k, new_v, logits = prefill_kv(cfg, params, tokens, length, use_flash,
                                      ep_mesh, flash_mesh, sp_mesh)
    return _write_prefill_kv(cfg, cache, new_k, new_v, slot), logits


def _write_token_kv(cache_layer: jnp.ndarray, kv_new: jnp.ndarray,
                    lengths: jnp.ndarray) -> jnp.ndarray:
    """Scatter one token's k/v per slot: cache [B, S, kv_dim], kv_new
    [B, kv_dim], written at per-slot index lengths[b]."""
    def write_one(c, kv, pos):
        return jax.lax.dynamic_update_slice(c, kv[None], (pos, 0))

    return jax.vmap(write_one)(cache_layer, kv_new, lengths)


def _write_token_scale(scale_layer: jnp.ndarray, s_new: jnp.ndarray,
                       lengths: jnp.ndarray) -> jnp.ndarray:
    """Scatter one token's quant scale per slot: scales [B, S], s_new [B]."""
    def write_one(sl, s, pos):
        return jax.lax.dynamic_update_slice(sl, s[None], (pos,))

    return jax.vmap(write_one)(scale_layer, s_new, lengths)


def _store_layer_kv(cache: KVCache, li: int, k_new: jnp.ndarray,
                    v_new: jnp.ndarray, lengths: jnp.ndarray):
    """Write one layer's new-token k/v ([B, kv_dim] or [B, T, kv_dim])
    into the cache at per-slot offsets, quantizing when the cache is int8.
    Returns (k_layer, v_layer, k_scale_layer, v_scale_layer) — the scale
    layers are None for full-precision caches."""
    multi = k_new.ndim == 3
    write_kv = _write_tokens_kv if multi else _write_token_kv
    write_s = _write_tokens_scale if multi else _write_token_scale
    if cache.quantized:
        packed = cache.k.shape[-1] != k_new.shape[-1]
        k_q, k_s = _quantize_kv(k_new, packed)
        v_q, v_s = _quantize_kv(v_new, packed)
        return (write_kv(cache.k[li], k_q, lengths),
                write_kv(cache.v[li], v_q, lengths),
                write_s(cache.k_scale[li], k_s, lengths),
                write_s(cache.v_scale[li], v_s, lengths))
    return (write_kv(cache.k[li], k_new, lengths),
            write_kv(cache.v[li], v_new, lengths), None, None)


def decode_step(cfg: ModelConfig, params: Params, cache: KVCache,
                tokens: jnp.ndarray, lengths: jnp.ndarray, ep_mesh=None
                ) -> Tuple[KVCache, jnp.ndarray]:
    """One decode step for ALL slots (continuous batching inner loop).

    tokens [B] current token per slot; lengths [B] tokens already in the
    cache (the new token is written at index lengths[b] and attends to
    lengths[b]+1 positions).  Returns (cache', logits [B, V]).
    """
    _refuse_window_layers(cfg, "llama.decode_step")
    _refuse_latent(cfg, "llama.decode_step")
    b = tokens.shape[0]
    angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = lengths[:, None]                       # [B, 1]
    x = gather_rows(params["embedding"], tokens[:, None]).astype(jnp.dtype(cfg.dtype))

    s_max = cache.max_seq_len
    dtype = jnp.dtype(cfg.dtype)
    packed = _kv_packed(cfg, cache)
    new_ks, new_vs, new_kss, new_vss = [], [], [], []
    for li, layer in enumerate(params["layers"]):
        lcfg = cfg.layer_cfg(li)
        q, k, v = _decode_qkv(lcfg, layer, x, angles, positions)  # [B,1,h,d]
        k_cache, v_cache, k_s, v_s = _store_layer_kv(
            cache, li, k[:, 0].reshape(b, cfg.kv_dim),
            v[:, 0].reshape(b, cfg.kv_dim), lengths)
        new_ks.append(k_cache)
        new_vs.append(v_cache)
        new_kss.append(k_s)
        new_vss.append(v_s)
        attn = decode_attention(
            q,
            _dequant_layer(k_cache, k_s, dtype, packed).reshape(
                b, s_max, cfg.n_kv_heads, cfg.head_dim),
            _dequant_layer(v_cache, v_s, dtype, packed).reshape(
                b, s_max, cfg.n_kv_heads, cfg.head_dim),
            lengths + 1)
        x = _decode_finish(lcfg, layer, x,
                           attn.reshape(b, 1, cfg.q_dim), ep_mesh)

    cache = KVCache(
        jnp.stack(new_ks), jnp.stack(new_vs),
        jnp.stack(new_kss) if cache.quantized else None,
        jnp.stack(new_vss) if cache.quantized else None)
    logits = _logits(cfg, params, x)[:, 0]             # [B, V]
    return cache, logits


def _write_tokens_kv(cache_layer: jnp.ndarray, kv_new: jnp.ndarray,
                     lengths: jnp.ndarray) -> jnp.ndarray:
    """Scatter T tokens' k/v per slot: cache [B, S, kv_dim], kv_new
    [B, T, kv_dim], written at per-slot offsets lengths[b]..lengths[b]+T-1."""
    def write_one(c, kv, pos):
        return jax.lax.dynamic_update_slice(c, kv, (pos, 0))

    return jax.vmap(write_one)(cache_layer, kv_new, lengths)


def _write_tokens_scale(scale_layer: jnp.ndarray, s_new: jnp.ndarray,
                        lengths: jnp.ndarray) -> jnp.ndarray:
    """Scatter T tokens' quant scales per slot: scales [B, S], s_new [B, T]."""
    def write_one(sl, s, pos):
        return jax.lax.dynamic_update_slice(sl, s, (pos,))

    return jax.vmap(write_one)(scale_layer, s_new, lengths)


def decode_multi(cfg: ModelConfig, params: Params, cache: KVCache,
                 tokens: jnp.ndarray, lengths: jnp.ndarray, ep_mesh=None
                 ) -> Tuple[KVCache, jnp.ndarray]:
    """Multi-token decode step (speculative verification).

    tokens [B, T]: tokens[b, 0] is slot b's current token (as in
    decode_step) and tokens[b, 1:] are draft tokens to verify; lengths [B]
    tokens already in the cache.  Writes all T tokens' KV at
    lengths[b]..lengths[b]+T-1 and returns (cache', logits [B, T, V]) where
    logits[b, i] scores the token AFTER tokens[b, i].

    Rejected drafts need no cache rollback: attention masks by length, so
    KV written past the accepted position is invisible until overwritten
    by a later decode at that position.
    """
    _refuse_window_layers(cfg, "llama.decode_multi")
    _refuse_latent(cfg, "llama.decode_multi")
    b, t = tokens.shape
    s_max = cache.max_seq_len
    angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = lengths[:, None] + jnp.arange(t)[None, :]       # [B, T]
    x = gather_rows(params["embedding"], tokens).astype(jnp.dtype(cfg.dtype))

    dtype = jnp.dtype(cfg.dtype)
    packed = _kv_packed(cfg, cache)
    new_ks, new_vs, new_kss, new_vss = [], [], [], []
    for li, layer in enumerate(params["layers"]):
        lcfg = cfg.layer_cfg(li)
        q, k, v = _decode_qkv(lcfg, layer, x, angles, positions)  # [B,T,·,d]
        k_cache, v_cache, k_s, v_s = _store_layer_kv(
            cache, li, k.reshape(b, t, cfg.kv_dim),
            v.reshape(b, t, cfg.kv_dim), lengths)
        new_ks.append(k_cache)
        new_vs.append(v_cache)
        new_kss.append(k_s)
        new_vss.append(v_s)
        attn = decode_attention_multi(
            q,
            _dequant_layer(k_cache, k_s, dtype, packed).reshape(
                b, s_max, cfg.n_kv_heads, cfg.head_dim),
            _dequant_layer(v_cache, v_s, dtype, packed).reshape(
                b, s_max, cfg.n_kv_heads, cfg.head_dim),
            lengths + 1)
        x = _decode_finish(lcfg, layer, x,
                           attn.reshape(b, t, cfg.q_dim), ep_mesh)

    cache = KVCache(
        jnp.stack(new_ks), jnp.stack(new_vs),
        jnp.stack(new_kss) if cache.quantized else None,
        jnp.stack(new_vss) if cache.quantized else None)
    logits = _logits(cfg, params, x)                            # [B, T, V]
    return cache, logits


def prefill_kv_cp(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                  length: jnp.ndarray, mesh, seq_axis: str = "seq",
                  cp_mode: str = "ring", head_axis: Optional[str] = None,
                  ep_mesh=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Context-parallel prefill: ``prefill_kv`` with the sequence sharded
    over ``mesh[seq_axis]``.

    ``cp_mode``: "ring" — KV blocks rotate over the ICI ring
    (parallel/ring_attention.py; the [S, S] score matrix never
    materializes on one device) — or "ulysses" — head<->sequence
    all-to-all (parallel/ulysses.py; two collectives per attention,
    better when n_heads >= axis size and S fits one device).

    The engine's long-context mode: prompts larger than one device's
    activation budget prefill across the ring; the returned full-depth KV
    is written into the cache exactly like the single-device path.  Right
    padding is safe under pure causal masking (padded keys sit at
    positions >= length, which no valid query attends to).

    tokens [1, S_pad] with S_pad divisible by the axis size.  Returns
    (new_k [L, S_pad, n_kv, d], new_v, logits [1, V]).

    ``head_axis``: optional mesh axis sharding attention heads — the
    CP×TP composition (TP-sharded params produce head-sharded q/k/v;
    naming the axis keeps the ring/all-to-all per head shard instead of
    all-gathering heads at the shard_map boundary).

    ``ep_mesh``: the CP×EP composition — MoE MLPs dispatch through the
    all-to-all expert path with the flattened sequence as the token dim,
    sharded over (seq_axis, "expert"): each seq shard's tokens subdivide
    over the expert group, so the sequence never moves and the dispatch
    all-to-all rides the expert axis only.  Must be the SAME composed
    mesh as ``mesh`` (engine-validated).
    """
    from jax.sharding import PartitionSpec as P

    from k8s_llm_rca_tpu.parallel.ring_attention import ring_attention
    from k8s_llm_rca_tpu.parallel.ulysses import ulysses_attention

    _refuse_window_layers(cfg, "llama.prefill_kv_cp")
    _refuse_latent(cfg, "llama.prefill_kv_cp")
    if cp_mode not in ("ring", "ulysses"):
        raise ValueError(f"unknown cp_mode {cp_mode!r}")
    cp_attn = ring_attention if cp_mode == "ring" else ulysses_attention

    _, s_pad = tokens.shape
    angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.arange(s_pad)[None, :]
    x = gather_rows(params["embedding"], tokens).astype(jnp.dtype(cfg.dtype))
    x = jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, P(None, seq_axis, None)))

    attn = lambda q, k, v: cp_attn(q, k, v, mesh, seq_axis=seq_axis,
                                   head_axis=head_axis)
    ks, vs = [], []
    for li, layer in enumerate(params["layers"]):
        x, k, v = _block_prefill(cfg.layer_cfg(li), layer, x, angles,
                                 positions, seq_lens=None, attention_fn=attn,
                                 ep_mesh=ep_mesh, ep_token_axis=seq_axis)
        ks.append(k[0])
        vs.append(v[0])

    last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = _logits(cfg, params, last)[:, 0]
    return jnp.stack(ks), jnp.stack(vs), logits


def _prefill_batch_kv(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                      lengths: jnp.ndarray, use_flash: bool = False,
                      ep_mesh=None, flash_mesh=None, sp_mesh=None,
                      expert_kernel: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched prefill forward WITHOUT a cache write: tokens [N, S_pad]
    right-padded, lengths [N] -> (new_k [L, N, S_pad, kv_dim], new_v,
    logits [N, V] at each row's last valid token); the caller scatters
    the KV into pool pages (engine/paged.paged_prefill_batch)."""
    _refuse_window_layers(cfg, "llama._prefill_batch_kv")
    _refuse_latent(cfg, "llama._prefill_batch_kv")
    n, s_pad = tokens.shape
    angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(s_pad)[None, :], (n, s_pad))
    x = gather_rows(params["embedding"], tokens).astype(jnp.dtype(cfg.dtype))

    attention_fn = None
    if prefill_uses_flash(use_flash, s_pad):
        attention_fn = _flash_attention_fn(lengths, flash_mesh)

    ks, vs = [], []
    for li, layer in enumerate(params["layers"]):
        x, k, v = _block_prefill(cfg.layer_cfg(li), layer, x, angles,
                                 positions, lengths, attention_fn, ep_mesh,
                                 sp_mesh=sp_mesh,
                                 expert_kernel=expert_kernel)
        ks.append(k.reshape(n, s_pad, cfg.kv_dim))   # [N, S_pad, kv]
        vs.append(v.reshape(n, s_pad, cfg.kv_dim))

    idx = jnp.arange(n)
    last = x[idx, lengths - 1][:, None]              # [N, 1, H]
    logits = _logits(cfg, params, last)[:, 0]        # [N, V]
    return jnp.stack(ks), jnp.stack(vs), logits      # [L, N, S_pad, kv]


def prefill_rows(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                 lengths: jnp.ndarray, tail_starts: jnp.ndarray, tail: int,
                 use_flash: bool = False, expert_kernel: bool = False):
    """Batched prefill WITHOUT a cache write for a model with
    sliding-window layers, whose rows each leave a ring's tail behind: one
    row after another (``jax.lax.map``): rows share nothing, one row of a
    bucket keeps every matmul large, and the temporaries (the routed rows
    of the picks a position, a 6144-position row's band) stay one row's.

    tokens [N, S] right-padded, lengths [N].  A full layer's keys and
    values leave the map whole; a window layer's only as the ``tail``
    positions from ``tail_starts[row]`` (what its slot's ring keeps:
    engine/paged.py::_ring_tail says which).  Returns (k, v [Lf, N, S,
    kv_dim], window k, v [Lw, N, tail, kv_dim], logits [N, V] at each
    row's last true token, the rows' local expert pairs, and how many of
    their expert-layer calls ran over the compact form, both int32).
    """
    s_pad = tokens.shape[1]
    angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.arange(s_pad)[None, :]
    windows = cfg.attn_windows

    def one(row):
        toks, n, start = row
        seq_lens = n[None]
        x = gather_rows(params["embedding"], toks[None]).astype(
            jnp.dtype(cfg.dtype))
        full, ring, pairs = [], [], []
        for li, layer in enumerate(params["layers"]):
            x, k, v = _block_prefill(
                cfg.layer_cfg(li), layer, x, angles, positions, seq_lens,
                _layer_attention_fn(cfg, li, seq_lens, use_flash, s_pad),
                expert_kernel=expert_kernel, local_pairs=pairs)
            kv = (k.reshape(s_pad, cfg.kv_dim), v.reshape(s_pad, cfg.kv_dim))
            if windows[li]:
                ring.append(tuple(jax.lax.dynamic_slice_in_dim(
                    a, start, tail, axis=0) for a in kv))
            else:
                full.append(kv)
        last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)

        def stacked(kvs, i, rows):
            return (jnp.stack([kv[i] for kv in kvs]) if kvs
                    else jnp.zeros((0, rows, cfg.kv_dim), x.dtype))

        return (stacked(full, 0, s_pad), stacked(full, 1, s_pad),
                stacked(ring, 0, tail), stacked(ring, 1, tail),
                _logits(cfg, params, last)[0, 0], sum(pairs, jnp.int32(0)),
                n_compact_overflows(cfg, s_pad, pairs))

    k, v, wk, wv, logits, n_local, n_over = jax.lax.map(
        one, (tokens, lengths.astype(jnp.int32),
              tail_starts.astype(jnp.int32)))
    return (jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1),
            jnp.moveaxis(wk, 0, 1), jnp.moveaxis(wv, 0, 1), logits,
            jnp.sum(n_local), jnp.sum(n_over))


def prefill_latent_row(cfg: ModelConfig, params: Params,
                       tokens: jnp.ndarray, length: jnp.ndarray,
                       use_flash: bool = False,
                       expert_kernel: bool = False):
    """Prefill WITHOUT a cache write of ONE right-padded row of a model
    with latent attention: tokens [S], ``length`` its true tokens.  The
    engine runs a batch's rows one after another and writes each into its
    pages before the next (engine/paged.py::_prefill_latent_rows): rows
    share nothing, and a 12k-position row's keys and values per head,
    which only the prefill writes out, stay one row's.  Returns (the rows
    to cache [L, S, latent_row], logits [V] at the last true token, the
    row's local expert pairs, and how many of its expert-layer calls ran
    over the compact form, both int32)."""
    s_pad = tokens.shape[0]
    angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.arange(s_pad)[None, :]
    seq_lens = length[None]
    x = embed(cfg, params, tokens[None])
    rows, pairs = [], []
    for li, layer in enumerate(params["layers"]):
        x, latent, _ = _block_prefill(
            cfg.layer_cfg(li), layer, x, angles, positions, seq_lens,
            _layer_attention_fn(cfg, li, seq_lens, use_flash, s_pad),
            expert_kernel=expert_kernel, local_pairs=pairs)
        rows.append(latent[0])
    last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    return (jnp.stack(rows), _logits(cfg, params, last)[0, 0],
            sum(pairs, jnp.int32(0)), n_compact_overflows(cfg, s_pad, pairs))
