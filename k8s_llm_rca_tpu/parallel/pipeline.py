"""Pipeline parallelism: layer stages over the ``stage`` mesh axis.

GPipe-style microbatched forward under ``shard_map``: each device holds the
stacked params of ONE stage; activations flow device-to-device with
``ppermute`` over the schedule's M + P - 1 ticks (the P-1 bubble).  On real
pods the ``stage`` axis is laid out over DCN while TP stays on ICI
(SURVEY §2.2 PP row).

The stage function is arbitrary (a run of transformer blocks in practice);
``pipeline_apply`` is deliberately generic so tests can validate the
schedule with small closures.

Serving entry points (``paged_pp_prefill`` / ``paged_pp_decode_step`` /
``paged_pp_decode_multi`` / ``paged_pp_prefill_chunk`` over the page pool)
share ONE schedule implementation (``_gpipe_loop``); what varies per entry
point is only the per-stage compute + KV write.  All support quantized KV
(int8 / nibble-packed int4, the per-token scalar scales of
engine/paged.PagePool).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _gpipe_loop(stage_apply: Callable, x_mb: jnp.ndarray, kv: Tuple,
                m: int, n_st, my, perm, stage_axis: str):
    """The GPipe schedule, shared by every pipelined entry point.

    Runs M + P - 1 ticks; at tick t, this stage processes microbatch
    t - stage_index (clipped; ``valid`` is False on the warmup/drain
    garbage ticks).  ``stage_apply(h_in, mb_idx, valid, kv) -> (h_out,
    kv)`` owns the per-stage compute and any KV-cache writes (which must
    self-mask with ``valid``).  Returns (out [M, ...] = the last stage's
    per-microbatch outputs broadcast to every device, kv).
    """
    ticks = m + n_st - 1
    out_buf = jnp.zeros_like(x_mb)
    cur = jnp.zeros_like(x_mb[0])

    def tick(t, carry):
        cur, out_buf, kv = carry
        mb = jnp.clip(t - my, 0, m - 1)
        valid = jnp.logical_and(t - my >= 0, t - my < m)
        # stage 0 ingests microbatch t (when in range); others use received
        feed = x_mb[jnp.minimum(t, m - 1)]
        h_in = jnp.where(my == 0, feed, cur)
        h_out, kv = stage_apply(h_in, mb, valid, kv)
        # the last stage records its result for the microbatch finishing here
        mb_done = t - (n_st - 1)
        write = jnp.logical_and(my == n_st - 1, mb_done >= 0)
        out_buf = jax.lax.cond(
            write,
            lambda b: jax.lax.dynamic_update_index_in_dim(
                b, h_out, jnp.maximum(mb_done, 0), 0),
            lambda b: b, out_buf)
        cur = jax.lax.ppermute(h_out, stage_axis, perm)
        return cur, out_buf, kv

    cur, out_buf, kv = jax.lax.fori_loop(0, ticks, tick, (cur, out_buf, kv))
    # broadcast the last stage's buffer to every device so the out_spec can
    # be replicated (psum of one-hot contribution)
    contrib = jnp.where(my == n_st - 1, out_buf, jnp.zeros_like(out_buf))
    return jax.lax.psum(contrib, stage_axis), kv


def _stage_local_params(tree):
    """Unwrap grouped-repacked int4 leaves at the shard_map boundary.

    Inside a stage body each ``QuantTensor4Grouped`` leaf is this TP
    shard's contiguous block of the grouped packing — by construction a
    self-contained split-half buffer of its own columns (quant.
    repack_nibbles_grouped, "shard first, pack second") — so the local
    view IS a plain ``QuantTensor4`` and the stage code's ``dq()`` stays
    correct.  Globally the same leaves refuse ``dq()`` loudly; this
    unwrap is the one sanctioned crossing."""
    from k8s_llm_rca_tpu.models.quant import (
        QuantTensor4, QuantTensor4Grouped,
    )

    return jax.tree.map(
        lambda v: (QuantTensor4(q=v.q, scale=v.scale)
                   if isinstance(v, QuantTensor4Grouped) else v),
        tree, is_leaf=lambda v: isinstance(v, QuantTensor4Grouped))


def _stage_local_init(stage_layers, axis_name: str):
    n_stages = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    params = jax.tree.map(lambda a: a[0], stage_layers)   # strip stage dim
    params = _stage_local_params(params)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    return n_stages, my, params, perm


def stack_llama_stages(params: Any, n_stages: int) -> Any:
    """Regroup a llama param tree's layer list into a [P, L/P, ...] stacked
    pytree for ``pipeline_apply``: stage i holds layers [i*L/P, (i+1)*L/P).
    """
    layers = params["layers"]
    assert len(layers) % n_stages == 0, (
        f"{len(layers)} layers do not divide into {n_stages} stages")
    per = len(layers) // n_stages
    stages = [
        jax.tree.map(lambda *xs: jnp.stack(xs), *layers[i * per:(i + 1) * per])
        for i in range(n_stages)
    ]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stages)


def stacked_layer_specs(cfg, stage_axis: str = "stage",
                        tp_axis: str = None, ep_axis: str = None) -> Any:
    """PartitionSpec tree for a ``stack_llama_stages`` tree: stage axis
    leading; with ``tp_axis`` (PP×TP) each leaf additionally takes its TP
    dim from runtime.sharding.llama_param_specs shifted past the two
    stacking dims (stage over DCN, heads/hidden over ICI); with
    ``ep_axis`` (PP×EP) the stacked expert leaves keep their leading
    expert dim sharded (stage over DCN, experts over ICI).  Composed
    axes not being used map to None (replicated)."""
    from k8s_llm_rca_tpu.runtime.sharding import llama_param_specs

    layer = llama_param_specs(cfg)["layers"][0]
    if tp_axis is None and ep_axis is None:
        return {k: P(stage_axis) for k in layer}
    rename = {"model": tp_axis, "expert": ep_axis}
    return {k: P(stage_axis, None,
                 *(rename.get(a, a) if a in rename else a for a in spec))
            for k, spec in layer.items()}


def shard_stacked_layers(stacked: Any, mesh: Mesh,
                         stage_axis: str = "stage", cfg=None,
                         tp_axis: str = None, ep_axis: str = None) -> Any:
    """Place a ``stack_llama_stages`` tree with its leading stage axis
    sharded over ``mesh[stage_axis]`` — each device then holds ONLY its
    stage's layer weights, which is the HBM win that makes PP serve models
    whose weights exceed one chip.  Serving engines hoist this once.
    With ``tp_axis``/``ep_axis`` (requires ``cfg``), leaves also shard
    their TP/expert dims (stacked_layer_specs) for PP×TP / PP×EP serving;
    int8-quantized leaves (``QuantTensor``) shard their payload on the
    weight spec and their per-channel scales with reduced (size-1) dims
    replicated — runtime.sharding.shard_pytree's placement rule.

    int4 leaves (``QuantTensor4``) whose LAST axis shards over
    ``tp_axis`` are first RE-PACKED per shard
    (quant.repack_nibbles_grouped, "shard first, pack second"): each TP
    shard of the packed axis becomes a self-contained split-half buffer
    of its own columns, so the stage bodies' shard-local ``dq()`` is
    correct by construction.  Row-sharded int4 leaves (wo/w_down) keep
    the plain layout — packing is per-row independent.
    """
    if tp_axis is not None or ep_axis is not None:
        from k8s_llm_rca_tpu.runtime.sharding import shard_pytree

        specs = stacked_layer_specs(cfg, stage_axis, tp_axis, ep_axis)
        if tp_axis is not None:
            from k8s_llm_rca_tpu.models.quant import (
                QuantTensor4, repack_nibbles_grouped,
            )

            n_tp = mesh.shape[tp_axis]
            stacked = {
                k: (repack_nibbles_grouped(v, n_tp)
                    if isinstance(v, QuantTensor4) and tuple(specs[k])
                    and tuple(specs[k])[-1] == tp_axis else v)
                for k, v in stacked.items()
            }
        return shard_pytree(stacked, specs, mesh)

    def _put(x):
        spec = P(stage_axis, *(None,) * (x.ndim - 1))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(_put, stacked)


def _stacked_in_specs(stacked: Any, cfg, stage_axis: str,
                      tp_axis: str = None, ep_axis: str = None):
    """shard_map in_specs for a stacked layer tree.

    PP-only: the single prefix spec P(stage_axis) broadcasts over every
    leaf (including QuantTensor sub-leaves, whose q and scale both carry
    the leading stage dim).  Composed PP×TP / PP×EP: per-key specs, with
    quantized leaves (``QuantTensor``/``QuantTensor4``) expanded to
    (q spec, scale spec) — the scale takes the weight spec with its
    size-1 (reduced) dims replicated, mirroring
    runtime.sharding.shard_pytree's placement so the shard_map view
    matches where the bytes already live.  For int4 the q spec applies
    to the PACKED axis, which shard_stacked_layers re-packed per shard
    (``QuantTensor4Grouped``) so the local blocks are self-contained."""
    from k8s_llm_rca_tpu.models.quant import (
        QuantTensor, QuantTensor4, QuantTensor4Grouped,
    )

    if tp_axis is None and ep_axis is None:
        return P(stage_axis)
    base = stacked_layer_specs(cfg, stage_axis, tp_axis, ep_axis)
    out = {}
    for k, v in stacked.items():
        spec = base[k]
        if isinstance(v, (QuantTensor, QuantTensor4, QuantTensor4Grouped)):
            full = tuple(spec) + (None,) * (v.q.ndim - len(spec))
            scale_spec = P(*(s if d > 1 else None
                             for s, d in zip(full, v.scale.shape)))
            out[k] = type(v)(q=P(*full), scale=scale_spec)
        else:
            out[k] = spec
    return out


def llama_pipeline_forward(cfg, params: Any, tokens: jnp.ndarray, mesh: Mesh,
                           microbatches: int,
                           stage_axis: str = "stage",
                           stacked_layers: Any = None) -> jnp.ndarray:
    """Pipeline-parallel llama scoring forward: the transformer blocks are
    split into ``mesh.shape[stage_axis]`` stages and microbatched through
    ``pipeline_apply``; embedding lookup and the LM head run replicated
    outside the pipeline (they are <5% of FLOPs and keep the stage function
    uniform).  Matches ``models.llama.forward`` exactly on full-length
    sequences.  Reference has no model parallelism of any kind (SURVEY §2.2
    PP row); this is the DCN-friendly layer-stage axis for multi-host pods.

    Restacking the layer weights is O(model size); repeated callers should
    hoist it once via ``stack_llama_stages`` and pass ``stacked_layers``.
    """
    from k8s_llm_rca_tpu.models import llama as L

    b, s = tokens.shape
    assert b % microbatches == 0, (
        f"batch {b} must divide into {microbatches} microbatches")
    n_stages = mesh.shape[stage_axis]
    stacked = (stacked_layers if stacked_layers is not None
               else stack_llama_stages(params, n_stages))

    x = L.gather_rows(params["embedding"], tokens).astype(jnp.dtype(cfg.dtype))
    x_mb = x.reshape(microbatches, b // microbatches, s, x.shape[-1])

    def stage_fn(stage_layers, h):
        mb, s_, _ = h.shape
        angles = L.rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
        positions = jnp.broadcast_to(jnp.arange(s_)[None, :], (mb, s_))
        seq_lens = jnp.full((mb,), s_, jnp.int32)

        def body(carry, layer):
            carry, _, _ = L._block_prefill(cfg, layer, carry, angles,
                                           positions, seq_lens)
            return carry, None

        h, _ = jax.lax.scan(body, h, stage_layers)
        return h

    out = pipeline_apply(stage_fn, stacked, x_mb, mesh, stage_axis)
    return L._logits(cfg, params, out.reshape(b, s, -1))


def pipeline_apply(fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stacked_params: Any, x_mb: jnp.ndarray, mesh: Mesh,
                   stage_axis: str = "stage") -> jnp.ndarray:
    """Apply ``fn`` through P pipeline stages.

    stacked_params: pytree with a leading stage axis of size P (stage i's
    params at index i).  x_mb: [M, ...] microbatches.  Returns [M, ...] =
    stage_{P-1}(... stage_0(x) ...) per microbatch.
    """

    def body(stage_params, x_mb):
        n_st, my, params, perm = _stage_local_init(stage_params, stage_axis)

        def stage_apply(h, mb_idx, valid, kv):
            return fn(params, h), kv

        out, _ = _gpipe_loop(stage_apply, x_mb, (), x_mb.shape[0], n_st, my,
                             perm, stage_axis)
        return out

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(stage_axis), P(*(None,) * x_mb.ndim)),
        out_specs=P(*(None,) * x_mb.ndim),
        check_vma=False,
    )(stacked_params, x_mb)


# ---------------------------------------------------------------------------
# PP serving: pipelined prefill + per-stage KV decode
# ---------------------------------------------------------------------------
#
# What makes PP serve-capable is the CACHE split, not just the weights:
# stage i holds only its layers' weights AND its layers' KV (the cache/pool
# LAYER axis shards over "stage"), so a model whose weights+cache exceed
# one device serves across the stage axis — the DCN-friendly scale-out the
# reference cannot express at all (SURVEY §2.2 PP row).  All entry points
# run the GPipe microbatch schedule of ``_gpipe_loop``: at tick t, stage s
# processes microbatch t-s; activations hop stages via ppermute; cache
# writes are masked to valid (stage, tick) pairs.  Decode pipelines the
# BATCH (slot groups are the microbatches), so all stages stay busy in
# steady state after the P-1 bubble.
#
# Quantized KV (int8 / packed int4) uses the same per-token scalar scales
# as the plain paths: quantization happens at the per-stage write, dequant
# at the per-stage attention read, so PP serving composes with the cache
# compression that carries the big single-chip configs.


def kv_cache_stage_specs(tp_axis: str = None,
                         stage_axis: str = "stage") -> P:
    """PagePool k/v [L, pages, page, kv]: the LAYER axis shards over
    ``stage_axis``; under PP×TP the kv axis additionally shards over
    ``tp_axis``.  The ONE definition of the PP pool layout — the
    engine places the pool with it and the shard_map in/out specs
    reuse it, so the two cannot drift (a mismatch would silently
    reshard the full pool every decode tick)."""
    return P(stage_axis, None, None, tp_axis)


def kv_scale_stage_specs(stage_axis: str = "stage") -> P:
    """PagePool scales [L, pages, page]: layer axis over ``stage_axis``,
    like the payload they scale."""
    return P(stage_axis, None, None)


def _kv_tuple(cache) -> Tuple:
    """Pool -> flat array tuple for shard_map (scales only when
    quantized, so full-precision paths don't ship None through specs)."""
    if cache.k_scale is not None:
        return (cache.k, cache.v, cache.k_scale, cache.v_scale)
    return (cache.k, cache.v)


def _kv_specs(quant: bool, tp_axis: str = None,
              stage_axis: str = "stage") -> Tuple:
    kv = kv_cache_stage_specs(tp_axis, stage_axis)
    specs = (kv, kv)
    if quant:
        specs += (kv_scale_stage_specs(stage_axis),
                  kv_scale_stage_specs(stage_axis))
    return specs


def _rebuild(cache, kv_out: Tuple):
    if len(kv_out) == 4:
        return type(cache)(*kv_out)
    return type(cache)(kv_out[0], kv_out[1], None, None)


def _block_prefill_tp(cfg, layer, x, angles, positions, seq_lens,
                      tp_axis: str):
    """Manual-TP transformer block for use INSIDE a shard_map stage body
    (the PP×TP composition): column-parallel qkv / gate / up consume the
    replicated residual stream and produce LOCAL head / hidden shards,
    row-parallel wo / w_down produce partial sums combined with ``psum``
    over ``tp_axis``.  Numerically matches ``llama._block_prefill`` (the
    psum realizes the same contraction XLA's GSPMD inserts on the jitted
    path); returns (x, k_local, v_local) with k/v carrying this shard's
    kv heads only — the stage cache's kv axis is sharded to match."""
    from k8s_llm_rca_tpu.models.llama import _qkv, dq, rms_norm
    from k8s_llm_rca_tpu.ops.attention import causal_attention

    b, s, _ = x.shape
    h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    q, k, v = _qkv(cfg, layer, h, angles, positions)   # local head shards
    attn = causal_attention(q, k, v, seq_lens)
    out = attn.reshape(b, s, -1) @ dq(layer["wo"])
    x = x + jax.lax.psum(out, tp_axis)
    hm = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu(hm @ dq(layer["w_gate"]))
    up = hm @ dq(layer["w_up"])
    x = x + jax.lax.psum((gate * up) @ dq(layer["w_down"]), tp_axis)
    return x, k, v


def _decode_finish_tp(cfg, layer, x, attn_flat, tp_axis: str):
    """Decode-block back half under manual TP: row-parallel wo / w_down
    partial sums psum-combined (mirrors ``llama._decode_finish``)."""
    from k8s_llm_rca_tpu.models.llama import dq, rms_norm

    x = x + jax.lax.psum(attn_flat @ dq(layer["wo"]), tp_axis)
    hm = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu(hm @ dq(layer["w_gate"]))
    up = hm @ dq(layer["w_up"])
    return x + jax.lax.psum((gate * up) @ dq(layer["w_down"]), tp_axis)


def _moe_mlp_ep(cfg, layer, x, ep_axis: str):
    """EP MoE MLP for use INSIDE a shard_map stage body (the PP×EP
    composition): the residual stream ``x`` [b, s, H] is replicated
    across ``ep_axis``; each expert peer routes ITS token slice through
    the shared all-to-all dispatch (parallel.moe._moe_local — expert
    weights arrive pre-sliced by the stacked specs, leading dim E/P),
    then the outputs all_gather back to the full token set so the next
    stage-layer's attention sees every token.  Lossless capacity
    (capacity = tokens_local * top_k), matching the serving engines'
    expert_parallel_moe, so PP×EP is exactly the dense MoE function."""
    from k8s_llm_rca_tpu.models.llama import dq
    from k8s_llm_rca_tpu.parallel.moe import _moe_local

    b, s, h = x.shape
    p = jax.lax.axis_size(ep_axis)
    my = jax.lax.axis_index(ep_axis)
    t = b * s
    tl = t // p                     # validated: bm % n_ep == 0
    flat = x.reshape(t, h)
    x_local = jax.lax.dynamic_slice(flat, (my * tl, 0), (tl, h))
    out_local = _moe_local(
        x_local, dq(layer["router"]), dq(layer["w_gate"]),
        dq(layer["w_up"]), dq(layer["w_down"]), axis_name=ep_axis,
        n_experts=cfg.n_experts, top_k=cfg.n_experts_per_tok,
        capacity=max(1, tl * cfg.n_experts_per_tok))
    gathered = jax.lax.all_gather(out_local, ep_axis, axis=0, tiled=True)
    return gathered.reshape(b, s, h)


def _block_prefill_ep(cfg, layer, x, angles, positions, seq_lens,
                      ep_axis: str):
    """MoE transformer block for use inside a shard_map stage body
    (PP×EP): dense attention on the replicated stream, MoE MLP through
    the expert all-to-all (_moe_mlp_ep)."""
    from k8s_llm_rca_tpu.models.llama import _qkv, dq, rms_norm
    from k8s_llm_rca_tpu.ops.attention import causal_attention

    b, s, _ = x.shape
    h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    q, k, v = _qkv(cfg, layer, h, angles, positions)
    attn = causal_attention(q, k, v, seq_lens)
    x = x + attn.reshape(b, s, -1) @ dq(layer["wo"])
    hm = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    x = x + _moe_mlp_ep(cfg, layer, hm, ep_axis)
    return x, k, v


def _decode_finish_ep(cfg, layer, x, attn_flat, ep_axis: str):
    """Decode-block back half under PP×EP: dense output projection, MoE
    MLP through the expert all-to-all."""
    from k8s_llm_rca_tpu.models.llama import dq, rms_norm

    x = x + attn_flat @ dq(layer["wo"])
    hm = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    return x + _moe_mlp_ep(cfg, layer, hm, ep_axis)


# ---------------------------------------------------------------------------
# paged-pool PP serving
# ---------------------------------------------------------------------------


def paged_pp_prefill(cfg, params, pool, tokens, lengths, page_maps,
                     mesh: Mesh, microbatches: int = None,
                     stage_axis: str = "stage", stacked_layers=None,
                     tp_axis: str = None, ep_axis: str = None):
    """Pipeline-parallel paged prefill: N sequences' KV scattered into
    their pool pages, the pool's LAYER axis sharded over "stage".

    tokens [N, S_pad] right-padded with S_pad a page multiple; lengths
    [N]; page_maps [N, S_pad // page_size] page ids (same contract as
    engine/paged.paged_prefill_batch, incl. idempotent duplicate padding
    rows).  N must divide into ``microbatches``.  Returns (pool', logits
    [N, V] at each row's last valid token).  Supports quantized pools.

    ``tp_axis``: paged PP×TP — stage bodies run the manual-TP block and
    the pool's merged kv axis additionally shards over ``tp_axis`` (each
    device holds its stage's layers × its TP shard of every page).
    Quantized pools compose via the pmax full-row scale
    (llama._quantize_kv axis_name); scale pools replicate across TP.
    """
    from k8s_llm_rca_tpu.models import llama as L
    from k8s_llm_rca_tpu.engine.paged import PagePool, _pool_packed

    n_stages = mesh.shape[stage_axis]
    m = microbatches or n_stages
    b, s_pad = tokens.shape
    assert b % m == 0, (b, m)
    bm = b // m
    assert cfg.n_layers % n_stages == 0
    page_size = pool.page_size
    assert s_pad % page_size == 0, (s_pad, page_size)
    n_seq_pages = s_pad // page_size
    stacked = (stacked_layers if stacked_layers is not None
               else stack_llama_stages(params, n_stages))
    quant = pool.quantized
    packed = quant and _pool_packed(cfg, pool)

    x = L.gather_rows(params["embedding"], tokens).astype(jnp.dtype(cfg.dtype))
    h_dim = x.shape[-1]
    x_mb = x.reshape(m, bm, s_pad, h_dim)
    lengths_mb = lengths.reshape(m, bm)
    maps_mb = page_maps.reshape(m, bm, n_seq_pages)
    angles = L.rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

    def local(stage_layers, kv, x_mb, lengths_mb, maps_mb):
        n_st, my, layers, perm = _stage_local_init(stage_layers, stage_axis)
        positions = jnp.broadcast_to(jnp.arange(s_pad)[None, :], (bm, s_pad))

        def stage_apply(h, mb_idx, valid, kv):
            seq_lens = lengths_mb[mb_idx]
            pages = maps_mb[mb_idx]               # [bm, n_seq_pages]

            def body(carry, xs):
                layer, k_li, v_li = xs[0], xs[1], xs[2]
                if tp_axis is not None:
                    h2, k, v = _block_prefill_tp(cfg, layer, carry, angles,
                                                 positions, seq_lens,
                                                 tp_axis)
                elif ep_axis is not None:
                    h2, k, v = _block_prefill_ep(cfg, layer, carry, angles,
                                                 positions, seq_lens,
                                                 ep_axis)
                else:
                    h2, k, v = L._block_prefill(cfg, layer, carry, angles,
                                                positions, seq_lens)
                # kv_dim, or the local TP shard of it
                k_new = k.reshape(bm, s_pad, -1)
                v_new = v.reshape(bm, s_pad, -1)
                if quant:
                    ks_li, vs_li = xs[3], xs[4]
                    k_new, ks = L._quantize_kv(k_new, packed, tp_axis)
                    v_new, vs = L._quantize_kv(v_new, packed, tp_axis)
                    ks = ks.reshape(bm, n_seq_pages, page_size)
                    vs = vs.reshape(bm, n_seq_pages, page_size)
                    ks_li = ks_li.at[pages].set(
                        jnp.where(valid, ks, ks_li[pages]))
                    vs_li = vs_li.at[pages].set(
                        jnp.where(valid, vs, vs_li[pages]))
                k_new = k_new.reshape(bm, n_seq_pages, page_size, -1)
                v_new = v_new.reshape(bm, n_seq_pages, page_size, -1)
                k_li = k_li.at[pages].set(
                    jnp.where(valid, k_new.astype(k_li.dtype), k_li[pages]))
                v_li = v_li.at[pages].set(
                    jnp.where(valid, v_new.astype(v_li.dtype), v_li[pages]))
                return h2, ((k_li, v_li, ks_li, vs_li) if quant
                            else (k_li, v_li))

            h, kv = jax.lax.scan(body, h, (layers, *kv))
            return h, kv

        return _gpipe_loop(stage_apply, x_mb, kv, m, n_st, my, perm,
                           stage_axis)

    stacked_spec = _stacked_in_specs(stacked, cfg, stage_axis, tp_axis,
                                     ep_axis)
    out, kv_out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(stacked_spec, _kv_specs(quant, tp_axis, stage_axis), P(*(None,) * 4),
                  P(None, None), P(None, None, None)),
        out_specs=(P(*(None,) * 4), _kv_specs(quant, tp_axis, stage_axis)),
        check_vma=False,
    )(stacked, _kv_tuple(pool), x_mb, lengths_mb, maps_mb)

    x_final = out.reshape(b, s_pad, h_dim)
    last = x_final[jnp.arange(b), lengths - 1][:, None]
    logits = L._logits(cfg, params, last)[:, 0]
    return _rebuild(pool, kv_out), logits


def paged_pp_decode_step(cfg, params, pool, tokens, lengths, block_tables,
                         mesh: Mesh, microbatches: int = None,
                         stage_axis: str = "stage", stacked_layers=None,
                         tp_axis: str = None, ep_axis: str = None):
    """One pipeline-parallel paged decode step for ALL slots.

    tokens [B]; lengths [B]; block_tables [B, pages_per_seq].  The new
    token's KV scatters into each slot's current page on the LOCAL layer
    slice; attention reads the gathered dense view (the XLA paged path —
    pallas_call has no SPMD rule, and per-stage grids are small).  Returns
    (pool', logits [B, V]) matching ``paged.paged_decode_step``, incl.
    quantized pools and the PP×TP / PP×EP compositions.

    This IS the T=1 case of ``paged_pp_decode_multi`` — one shard_map
    body serves both the regular tick and speculative verification, so
    the masking/quantize-at-write/finish logic cannot drift between
    them.  Hot paths must pass a hoisted ``stacked_layers``.
    """
    pool, _, logits = paged_pp_decode_multi(
        cfg, params, pool, tokens[:, None], lengths, block_tables, mesh,
        microbatches, stage_axis, stacked_layers, tp_axis, ep_axis)
    return pool, logits[:, 0]

def paged_pp_decode_multi(cfg, params, pool, tokens, lengths, block_tables,
                          mesh: Mesh, microbatches: int = None,
                          stage_axis: str = "stage", stacked_layers=None,
                          tp_axis: str = None, ep_axis: str = None):
    """Pipeline-parallel paged MULTI-token decode (speculative
    verification): all T writes for a slot land in ONE page (the engine
    bounds T by each slot's in-page room, paged._spec_room_ok), so the
    page id is computed once per slot; attention reads the gathered
    dense view of the LOCAL layer slice.  Returns (pool', greedy [B, T],
    logits [B, T, V]) matching ``paged.paged_decode_multi``, composing
    with PP×TP (pmax quant scales) and PP×EP like the single-token
    pipelined step."""
    from k8s_llm_rca_tpu.models import llama as L
    from k8s_llm_rca_tpu.engine.paged import _pool_packed
    from k8s_llm_rca_tpu.ops.attention import decode_attention_multi

    n_stages = mesh.shape[stage_axis]
    m = microbatches or n_stages
    b, t = tokens.shape
    assert b % m == 0, (b, m)
    bm = b // m
    assert cfg.n_layers % n_stages == 0
    page_size = pool.page_size
    stacked = (stacked_layers if stacked_layers is not None
               else stack_llama_stages(params, n_stages))
    quant = pool.quantized
    packed = quant and _pool_packed(cfg, pool)
    pages_per_seq = block_tables.shape[1]
    s_max = pages_per_seq * page_size

    x = L.gather_rows(params["embedding"],
                      tokens).astype(jnp.dtype(cfg.dtype))      # [B, T, H]
    h_dim = x.shape[-1]
    x_mb = x.reshape(m, bm, t, h_dim)
    lengths_mb = lengths.reshape(m, bm)
    bt_mb = block_tables.reshape(m, bm, pages_per_seq)
    angles = L.rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    dtype = jnp.dtype(cfg.dtype)

    def local(stage_layers, kv, x_mb, lengths_mb, bt_mb):
        n_st, my, layers, perm = _stage_local_init(stage_layers, stage_axis)

        def stage_apply(h, mb_idx, valid, kv):
            lens = lengths_mb[mb_idx]                     # [bm]
            bt = bt_mb[mb_idx]                            # [bm, pages_per_seq]
            positions = lens[:, None] + jnp.arange(t)[None, :]
            page_idx = lens // page_size
            page_ids = jnp.take_along_axis(
                bt, page_idx[:, None], axis=1)            # [bm, 1]
            pages2d = jnp.broadcast_to(page_ids, (bm, t))
            offsets = (lens % page_size)[:, None] + jnp.arange(t)[None, :]

            def body(carry, xs):
                layer, k_li, v_li = xs[0], xs[1], xs[2]
                q, k, v = L._decode_qkv(cfg, layer, carry, angles, positions)
                k_tok = k.reshape(bm, t, -1)   # kv_dim (or TP shard)
                v_tok = v.reshape(bm, t, -1)
                if quant:
                    ks_li, vs_li = xs[3], xs[4]
                    k_tok, ks1 = L._quantize_kv(k_tok, packed, tp_axis)
                    v_tok, vs1 = L._quantize_kv(v_tok, packed, tp_axis)
                    ks_li = ks_li.at[pages2d, offsets].set(
                        jnp.where(valid, ks1, ks_li[pages2d, offsets]))
                    vs_li = vs_li.at[pages2d, offsets].set(
                        jnp.where(valid, vs1, vs_li[pages2d, offsets]))
                k_li = k_li.at[pages2d, offsets].set(
                    jnp.where(valid, k_tok.astype(k_li.dtype),
                              k_li[pages2d, offsets]))
                v_li = v_li.at[pages2d, offsets].set(
                    jnp.where(valid, v_tok.astype(v_li.dtype),
                              v_li[pages2d, offsets]))
                k_all = L._dequant_layer(
                    jnp.take(k_li, bt, axis=0),
                    jnp.take(ks_li, bt, axis=0) if quant else None,
                    dtype, packed).reshape(bm, s_max, -1, cfg.head_dim)
                v_all = L._dequant_layer(
                    jnp.take(v_li, bt, axis=0),
                    jnp.take(vs_li, bt, axis=0) if quant else None,
                    dtype, packed).reshape(bm, s_max, -1, cfg.head_dim)
                attn = decode_attention_multi(q, k_all, v_all, lens + 1)
                attn_flat = attn.reshape(bm, t, -1)
                if tp_axis is not None:
                    hx = _decode_finish_tp(cfg, layer, carry, attn_flat,
                                           tp_axis)
                elif ep_axis is not None:
                    hx = _decode_finish_ep(cfg, layer, carry, attn_flat,
                                           ep_axis)
                else:
                    hx = L._decode_finish(cfg, layer, carry, attn_flat)
                return hx, ((k_li, v_li, ks_li, vs_li) if quant
                            else (k_li, v_li))

            h, kv = jax.lax.scan(body, h, (layers, *kv))
            return h, kv

        return _gpipe_loop(stage_apply, x_mb, kv, m, n_st, my, perm,
                           stage_axis)

    stacked_spec = _stacked_in_specs(stacked, cfg, stage_axis, tp_axis,
                                     ep_axis)
    out, kv_out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(stacked_spec, _kv_specs(quant, tp_axis, stage_axis),
                  P(*(None,) * 4), P(None, None), P(None, None, None)),
        out_specs=(P(*(None,) * 4), _kv_specs(quant, tp_axis, stage_axis)),
        check_vma=False,
    )(stacked, _kv_tuple(pool), x_mb, lengths_mb, bt_mb)

    logits = L._logits(cfg, params, out.reshape(b, t, h_dim))   # [B, T, V]
    return (_rebuild(pool, kv_out), jnp.argmax(logits, axis=-1), logits)


def paged_pp_prefill_chunk(cfg, params, pool, tokens, chunk_len,
                           prefix_len, prefix_table, page_map, mesh: Mesh,
                           stage_axis: str = "stage", stacked_layers=None,
                           tp_axis: str = None):
    """Pipeline-parallel CHUNKED prefix prefill: the prefix-cache hit
    path under PP serving.  Prefills the non-cached SUFFIX of one prompt
    whose first ``prefix_len`` tokens' KV already sit in pool pages —
    same contract as ``paged.paged_prefill_chunk`` — with each stage
    gathering its OWN layers' cached prefix pages from its local pool
    slice and scattering its chunk KV back (the pool's layer axis is
    stage-sharded).  One sequence, so the GPipe schedule degenerates to
    m=1 (sequential stages, no overlap) — the win here is the prefix KV
    REUSE, not pipelining.

    ``tp_axis``: the PP×TP composition — stage bodies run the manual-TP
    chunk layer (``engine/paged._chunk_layer(tp_axis=)``: local head
    shards, psum combines)
    over the pool's kv-lane shard, so the agent-thread reuse the cache
    was built for survives in the production stage×model mesh.  EP is
    not composed (the chunk layer has no expert dispatch; the engine
    rejects prefix_cache under PP×EP)."""
    from k8s_llm_rca_tpu.engine.paged import _chunk_layer, _pool_packed
    from k8s_llm_rca_tpu.models import llama as L

    n_stages = mesh.shape[stage_axis]
    _, c_pad = tokens.shape
    page_size = pool.page_size
    assert c_pad % page_size == 0, (c_pad, page_size)
    n_chunk_pages = c_pad // page_size
    assert cfg.n_layers % n_stages == 0
    stacked = (stacked_layers if stacked_layers is not None
               else stack_llama_stages(params, n_stages))
    quant = pool.quantized
    packed = quant and _pool_packed(cfg, pool)
    s_prefix = prefix_table.shape[0] * page_size
    dtype = jnp.dtype(cfg.dtype)

    angles = L.rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = prefix_len + jnp.arange(c_pad)[None, :]          # [1, C]
    # causal + validity mask in absolute positions (paged_prefill_chunk)
    q_pos = prefix_len + jnp.arange(c_pad)                       # [C]
    k_abs = jnp.concatenate([jnp.arange(s_prefix), q_pos])       # [S]
    k_valid = jnp.concatenate([
        jnp.arange(s_prefix) < prefix_len,
        jnp.arange(c_pad) < chunk_len,
    ])
    mask = (q_pos[:, None] >= k_abs[None, :]) & k_valid[None, :]  # [C, S]
    x = L.gather_rows(params["embedding"], tokens).astype(dtype)  # [1, C, H]
    h_dim = x.shape[-1]
    x_mb = x.reshape(1, 1, c_pad, h_dim)
    pages = page_map.reshape(1, n_chunk_pages)

    def local(stage_layers, kv, x_mb, mask, positions, prefix_tbl, pages):
        n_st, my, layers, perm = _stage_local_init(stage_layers, stage_axis)
        pages1 = pages[0]                                 # [n_chunk_pages]

        def stage_apply(h, mb_idx, valid, kv):
            def body(carry, xs):
                layer, k_li, v_li = xs[0], xs[1], xs[2]
                ks_li = vs_li = None
                if quant:
                    ks_li, vs_li = xs[3], xs[4]
                # shared per-layer chunk block (engine/paged._chunk_layer
                # or its manual-TP twin): gather cached prefix, attend,
                # finish — only the page WRITE below is PP-specific
                x2, k, v = _chunk_layer(cfg, layer, carry, angles,
                                        positions, mask, k_li, v_li,
                                        ks_li, vs_li, prefix_tbl, dtype,
                                        packed, tp_axis=tp_axis)
                # scatter the chunk's KV into its new pages (valid-masked)
                k_new = k[0].reshape(c_pad, -1)    # kv_dim or its TP shard
                v_new = v[0].reshape(c_pad, -1)
                if quant:
                    k_new, ks = L._quantize_kv(k_new, packed, tp_axis)
                    v_new, vs = L._quantize_kv(v_new, packed, tp_axis)
                    ks = ks.reshape(n_chunk_pages, page_size)
                    vs = vs.reshape(n_chunk_pages, page_size)
                    ks_li = ks_li.at[pages1].set(
                        jnp.where(valid, ks, ks_li[pages1]))
                    vs_li = vs_li.at[pages1].set(
                        jnp.where(valid, vs, vs_li[pages1]))
                k_new = k_new.reshape(n_chunk_pages, page_size, -1)
                v_new = v_new.reshape(n_chunk_pages, page_size, -1)
                k_li = k_li.at[pages1].set(
                    jnp.where(valid, k_new.astype(k_li.dtype),
                              k_li[pages1]))
                v_li = v_li.at[pages1].set(
                    jnp.where(valid, v_new.astype(v_li.dtype),
                              v_li[pages1]))
                return x2, ((k_li, v_li, ks_li, vs_li) if quant
                            else (k_li, v_li))

            h, kv = jax.lax.scan(body, h, (layers, *kv))
            return h, kv

        return _gpipe_loop(stage_apply, x_mb, kv, 1, n_st, my, perm,
                           stage_axis)

    stacked_spec = _stacked_in_specs(stacked, cfg, stage_axis, tp_axis,
                                     None)
    out, kv_out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(stacked_spec, _kv_specs(quant, tp_axis, stage_axis),
                  P(*(None,) * 4), P(None, None), P(None, None), P(None),
                  P(None, None)),
        out_specs=(P(*(None,) * 4), _kv_specs(quant, tp_axis, stage_axis)),
        check_vma=False,
    )(stacked, _kv_tuple(pool), x_mb, mask, positions, prefix_table, pages)

    x_final = out.reshape(1, c_pad, h_dim)
    last = jax.lax.dynamic_slice_in_dim(x_final, chunk_len - 1, 1, axis=1)
    logits = L._logits(cfg, params, last)[:, 0]                  # [1, V]
    return _rebuild(pool, kv_out), logits
