"""Sharding specs: how params/activations map onto the mesh.

GSPMD-style tensor parallelism: we annotate weights and a few activation
boundaries with ``NamedSharding``/``with_sharding_constraint`` and let XLA
insert the collectives (all-gather on column-parallel inputs, psum on
row-parallel outputs) — the idiomatic TPU replacement for hand-written NCCL.

The specs themselves are no longer hand-rolled dicts: they are derived by
matching the ordered regex rule tables in ``runtime/rules.py`` against a
shape-only template of each model's param pytree (first match wins,
scalars replicate, no match is a loud ValueError naming the param).  The
``layout`` argument (a ``rules.SpecLayout``) picks which mesh axes the
logical data/fsdp/tp/ep axes land on; the default reproduces the
historical layout exactly:

- wq/wk/wv  [H, heads*d]  -> P(None, "model")   (column parallel: heads sharded)
- wo        [heads*d, H]  -> P("model", None)   (row parallel: psum output)
- w_gate/w_up [H, I]      -> P(None, "model")
- w_down    [I, H]        -> P("model", None)
- embedding [V, H]        -> P(None, "model")   (hidden sharded; lm_head tied)
- MoE experts get a leading "expert" axis on the stacked expert weights.
A layout with ``fsdp`` set additionally shards the non-TP matmul dim
(hidden; vocab for the embeddings) along the fsdp axis.  Batch dims of
activations shard on "data"; sequence on "seq" for SP/CP.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from k8s_llm_rca_tpu.config import ModelConfig
from k8s_llm_rca_tpu.runtime.rules import (  # noqa: F401  (re-exports)
    FSDP_LAYOUT,
    SpecLayout,
    TP_LAYOUT,
    encoder_param_template,
    encoder_rules,
    is_param_leaf,
    llama_param_template,
    llama_rules,
    match_partition_rules,
    paged_pool_specs,
    validate_layout,
)

PyTree = Any


def llama_param_specs(cfg: ModelConfig,
                      layout: Optional[SpecLayout] = None) -> Dict[str, Any]:
    """PartitionSpec pytree matching models/llama.init_params structure,
    derived from ``rules.llama_rules`` (dense + MoE) under ``layout``."""
    return match_partition_rules(
        llama_rules(cfg, layout), llama_param_template(cfg), table="llama")


def encoder_param_specs(cfg,
                        layout: Optional[SpecLayout] = None) -> Dict[str, Any]:
    """PartitionSpec pytree matching models/encoder.init_params structure,
    derived from ``rules.encoder_rules`` under ``layout``."""
    return match_partition_rules(
        encoder_rules(cfg, layout), encoder_param_template(cfg),
        table="encoder")


def shard_pytree(tree: PyTree, specs: PyTree, mesh: Mesh) -> PyTree:
    """Device-put a pytree with NamedShardings built from a spec pytree.

    ``None`` leaves (optional fields, e.g. KVCache scale arrays of a
    full-precision cache) pass through unsharded.  Quantized weights
    (``QuantTensor``/``QuantTensor4``) are treated as single leaves whose
    spec is the underlying weight's: the int payload takes it verbatim and
    the per-channel scale takes it with every size-1 (reduced) dim
    replicated — so TP composes with int8/int4 params.
    """
    from k8s_llm_rca_tpu.models.quant import (
        QuantTensor, QuantTensor4, QuantTensor4Grouped,
    )

    quant_types = (QuantTensor, QuantTensor4, QuantTensor4Grouped)

    def _put(x, spec):
        if x is None:
            return None
        if isinstance(x, quant_types):
            scale_spec = P(*(s if dim > 1 else None
                             for s, dim in zip(spec, x.scale.shape)))
            return type(x)(
                q=jax.device_put(x.q, NamedSharding(mesh, spec)),
                scale=jax.device_put(x.scale, NamedSharding(mesh, scale_spec)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(
        _put, tree, specs,
        is_leaf=lambda x: x is None or isinstance(x, quant_types))


def shard_with_rules(rules, tree: PyTree, mesh: Mesh, *,
                     table: str = "") -> PyTree:
    """Match ``rules`` against ``tree`` and device-put the result: the one
    call checkpoint ingestion routes through — an unseen param name fails
    with the matcher's named-param ValueError BEFORE any weight moves."""
    return shard_pytree(tree, match_partition_rules(rules, tree, table=table),
                        mesh)


def constrain(x, mesh: Mesh, spec: P):
    """with_sharding_constraint with an explicit mesh.  Invalid specs (wrong
    rank, non-divisible axis) must fail loudly — never silently drop the
    intended layout."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
