"""Mixtral-family sparse-MoE decoder LM + expert-parallel serving assembly.

Architecturally this is the Llama stack with the MLP swapped for a
top-k-routed expert block, so the block implementation lives in
models/llama.py (``n_experts > 0`` switches it; ``llama._moe_mlp`` is the
one-chip form: token-grouped matmuls on the routed rows for a prefill-sized
call, dense soft dispatch for a decode-sized one, chosen by
``llama.moe_grouped``; parallel/moe.py is the all-to-all EP dispatch).
What lives HERE is what is Mixtral-specific: the presets and the
**expert-parallel serving assembly** — building the (data, expert) mesh,
sharding the stacked expert weights over it, and constructing an engine
whose every MoE MLP (prefill and decode) dispatches through the
all-to-all path.

Replaces the reference's remote GPT-4 (its only model access is the HTTPS
client, reference common/openai_generic_assistant.py:45-51) with the MoE
assistant of BASELINE config[3] (Mixtral-8x7B expert-parallel on v5e-16).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax

from k8s_llm_rca_tpu.config import (  # noqa: F401
    MIXTRAL_8X7B, TINY_MOE, EngineConfig, MeshConfig, ModelConfig,
)
from k8s_llm_rca_tpu.models.llama import (  # noqa: F401
    KVCache,
    decode_step,
    forward,
    init_cache,
    init_params,
    prefill,
)


def build_ep_mesh(n_expert_shards: int, n_data: int = 1, n_seq: int = 1,
                  devices: Optional[Sequence] = None):
    """(data, expert[, seq]) mesh for EP serving; ``n_expert_shards``
    devices hold disjoint expert subsets, ``n_data`` replicas shard the
    token batch, ``n_seq`` > 1 adds the context-parallel axis for the
    CP×EP composition (pass the mesh as BOTH ep_mesh and cp_mesh)."""
    from k8s_llm_rca_tpu.runtime.mesh import build_mesh

    return build_mesh(MeshConfig(data=n_data, expert=n_expert_shards,
                                 seq=n_seq),
                      devices=devices)


def shard_params_ep(cfg: ModelConfig, params, mesh):
    """Stacked expert weights [E, ...] over the "expert" axis, everything
    else replicated/TP per runtime.sharding.llama_param_specs."""
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )

    return shard_pytree(params, llama_param_specs(cfg), mesh)


def make_ep_engine(cfg: ModelConfig, engine_cfg: EngineConfig, params,
                   tokenizer, n_expert_shards: Optional[int] = None,
                   n_data: int = 1, devices: Optional[Sequence] = None,
                   mesh=None, **engine_kw):
    """Expert-parallel serving engine (BASELINE configs[3]).

    Builds the (data, expert) mesh (or takes ``mesh``), shards ``params``
    over it, and returns an engine whose MoE MLPs run the all-to-all
    dispatch on every prefill and decode step.
    ``n_expert_shards`` defaults to all local devices.
    """
    from k8s_llm_rca_tpu.engine import make_engine

    if cfg.n_experts <= 0:
        raise ValueError(f"{cfg.name} is not an MoE config")
    if mesh is None:
        if n_expert_shards is None:
            n_expert_shards = len(devices or jax.devices()) // n_data
        mesh = build_ep_mesh(n_expert_shards, n_data, devices=devices)
    sharded = shard_params_ep(cfg, params, mesh)
    return make_engine(cfg, engine_cfg, sharded, tokenizer, ep_mesh=mesh,
                       **engine_kw)
