"""Expert parallelism: hard top-k dispatch with all-to-all token exchange.

The bandwidth-real MoE path (SURVEY §2.2 EP row): experts are sharded over
the ``expert`` mesh axis, tokens are batch-sharded over ``data``; each
device routes its local tokens, packs them into per-expert capacity slots
(Switch/Mesh-TF dispatch-combine formulation — one-hot einsums, fully
static shapes for XLA), exchanges them with ``jax.lax.all_to_all`` so every
device receives exactly the tokens destined for ITS experts, applies its
expert MLPs, and reverses the exchange.

With sufficient capacity this computes exactly the same function as
models/llama._moe_mlp (tests assert parity), whose two one-chip forms
(token-grouped matmuls for a large call, dense soft dispatch for a small
one) drop nothing (the grouped form's one capacity, the rows of its
compact form, falls back to every pick's row); under pressure this path
drops overflow tokens like production MoE stacks do.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from k8s_llm_rca_tpu.models.quant import dq


def _route_exact(x, router_w, n_experts: int, top_k: int, capacity: int):
    """Dispatch/combine with a SINGLE shared cumsum across the k lanes so
    capacity slots never collide."""
    logits = (x @ router_w).astype(jnp.float32)
    topv, topi = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(topv, axis=-1)                  # [T, K]
    t = x.shape[0]
    # flatten (k, t) so lane 0 routes first (priority), then lane 1, ...
    flat_idx = topi.T.reshape(-1)                            # [K*T]
    flat_w = weights.T.reshape(-1)
    onehot = jax.nn.one_hot(flat_idx, n_experts)             # [K*T, E]
    pos = jnp.cumsum(onehot, axis=0) - 1.0
    in_cap = pos < capacity
    sel = onehot * in_cap
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity)  # [K*T, E, C]
    disp_flat = sel[..., None] * pos_oh                       # [K*T, E, C]
    comb_flat = (sel * flat_w[:, None])[..., None] * pos_oh
    dispatch = disp_flat.reshape(top_k, t, n_experts, capacity).sum(0)
    combine = comb_flat.reshape(top_k, t, n_experts, capacity).sum(0)
    return dispatch, combine


def _expert_mlp(x, w_gate, w_up, w_down):
    """x [E_local, C', H] through per-expert SwiGLU MLPs."""
    gate = jax.nn.silu(jnp.einsum("ech,ehi->eci", x, w_gate))
    up = jnp.einsum("ech,ehi->eci", x, w_up)
    return jnp.einsum("eci,eih->ech", gate * up, w_down)


def _moe_local(x, router_w, w_gate, w_up, w_down, *, axis_name: str,
               n_experts: int, top_k: int, capacity: int):
    """Under shard_map: x [T_local, H] (sharded over 'data'); expert weights
    sharded over ``axis_name`` (leading dim E/P)."""
    dispatch, combine = _route_exact(x, router_w, n_experts, top_k, capacity)

    # pack: [T, E, C] x [T, H] -> [E, C, H]
    expert_inputs = jnp.einsum("tec,th->ech", dispatch,
                               x.astype(jnp.float32))
    # exchange: split experts across devices, gather every device's slots
    # [E, C, H] -> [E/P, P*C, H]
    expert_inputs = jax.lax.all_to_all(
        expert_inputs, axis_name, split_axis=0, concat_axis=1, tiled=True)
    expert_outputs = _expert_mlp(expert_inputs.astype(x.dtype),
                                 w_gate, w_up, w_down)
    # reverse exchange: [E/P, P*C, H] -> [E, C, H]
    expert_outputs = jax.lax.all_to_all(
        expert_outputs, axis_name, split_axis=1, concat_axis=0, tiled=True)
    # unpack: [T, E, C] x [E, C, H] -> [T, H]
    out = jnp.einsum("tec,ech->th", combine,
                     expert_outputs.astype(jnp.float32))
    return out.astype(x.dtype)


def expert_parallel_moe(x: jnp.ndarray, layer: Dict, mesh: Mesh,
                        top_k: int, capacity_factor: float = 2.0,
                        expert_axis: str = "expert",
                        data_axis: str = "data") -> jnp.ndarray:
    """MoE forward with experts sharded over ``expert_axis`` and tokens over
    ``data_axis``.

    x [B, S, H]; layer holds 'router' [H, E] (replicated) and stacked expert
    weights 'w_gate'/'w_up' [E, H, I], 'w_down' [E, I, H] sharded on their
    leading expert dim.  Returns [B, S, H].
    """
    b, s, h = x.shape
    e = layer["router"].shape[-1]
    # tokens shard over BOTH axes so each expert-axis peer routes a distinct
    # token shard (otherwise the exchange carries P identical slot copies)
    n_tok_shards = mesh.shape[data_axis] * mesh.shape[expert_axis]
    if (b * s) % n_tok_shards:
        raise ValueError(
            f"tokens {b * s} not divisible by data*expert={n_tok_shards}")
    tokens_local = (b * s) // n_tok_shards
    capacity = max(1, int(capacity_factor * tokens_local * top_k / e))

    body = functools.partial(
        _moe_local, axis_name=expert_axis, n_experts=e, top_k=top_k,
        capacity=capacity)

    flat = x.reshape(b * s, h)
    tok_spec = P((data_axis, expert_axis), None)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None),
                  P(expert_axis, None, None), P(expert_axis, None, None),
                  P(expert_axis, None, None)),
        out_specs=tok_spec,
        check_vma=False,
    )(flat, dq(layer["router"]), dq(layer["w_gate"]), dq(layer["w_up"]),
      dq(layer["w_down"]))
    return out.reshape(b, s, h)
