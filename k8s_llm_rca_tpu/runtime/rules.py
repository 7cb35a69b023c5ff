"""Partition rules: regex over '/'-joined param names → PartitionSpec.

The declarative replacement for hand-rolled per-model spec dicts
(ROADMAP item 1).  Three pieces:

- ``SpecLayout`` — a frozen mapping of LOGICAL parallel axes
  (data/fsdp/tp/pp/cp/ep) to mesh axis NAMES.  Rules are written against
  the logical axes; the layout decides which mesh axis (if any) each one
  lands on, so the same rule table serves a TP-only tier, an fsdp×tp
  mesh, or a replicated single chip just by swapping the layout.
- ``match_partition_rules(rules, tree)`` — flatten the pytree with
  key paths, join each path with '/' ("layers/0/wq"), and take the FIRST
  rule whose regex ``re.search``-matches.  Scalars (ndim 0 or a single
  element) replicate without consulting the table.  A param no rule
  matches is a **loud ValueError naming the param** — never a silent
  replicate: a silently replicated 8B weight is an HBM OOM three hours
  into a soak, not a test failure.
- per-model rule tables (``llama_rules`` covers dense + MoE/mixtral,
  ``encoder_rules`` the e5 tower) plus shape-only templates
  (``jax.ShapeDtypeStruct`` pytrees mirroring models/*.init_params) so
  two-way coverage — every param matched, every rule used — is provable
  without touching a device.

Serving-state derivation (``paged_pool_specs``) lives here too: the
engine's pool placement reads the same layout the weights were placed
with.
"""

from __future__ import annotations

import dataclasses
import math
import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any
RuleTable = List[Tuple[str, P]]

_LOGICAL_AXES = ("data", "fsdp", "tp", "ep", "cp", "pp")


@dataclass(frozen=True)
class SpecLayout:
    """Logical parallel axis → mesh axis name (None = that mode unused).

    Defaults reproduce the historical layout exactly: TP over "model",
    DP over "data", EP over "expert", CP over "seq", PP over "stage",
    and NO fsdp axis.  ``SpecLayout(fsdp="fsdp")`` turns on parameter
    sharding along the mesh's "fsdp" axis (all-gather-on-use via GSPMD).
    """

    data: Optional[str] = "data"
    fsdp: Optional[str] = None
    tp: Optional[str] = "model"
    ep: Optional[str] = "expert"
    cp: Optional[str] = "seq"
    pp: Optional[str] = "stage"

    def to_dict(self) -> Dict[str, Optional[str]]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Optional[str]]) -> "SpecLayout":
        """Inverse of to_dict — the proc-worker wire format.  Unknown keys
        are a loud error (a typo'd axis must not silently replicate)."""
        unknown = set(d) - set(_LOGICAL_AXES)
        if unknown:
            raise ValueError(
                f"SpecLayout.from_dict: unknown logical axes {sorted(unknown)}; "
                f"valid axes are {_LOGICAL_AXES}")
        base = cls()
        return cls(**{k: d.get(k, getattr(base, k)) for k in _LOGICAL_AXES})


TP_LAYOUT = SpecLayout()                     # the historical default
FSDP_LAYOUT = SpecLayout(fsdp="fsdp")        # fsdp (×tp when model > 1)


def _leaf_shape(x) -> Optional[Tuple[int, ...]]:
    """Shape used for the scalar-replicate check.  Quantized leaves
    (QuantTensor*) report the payload's shape — the rule that matched the
    bf16 weight governs its int form too."""
    if x is None:
        return None
    q = getattr(x, "q", None)
    if q is not None and hasattr(x, "scale"):
        return tuple(q.shape)
    shape = getattr(x, "shape", None)
    return tuple(shape) if shape is not None else ()


def _path_name(path) -> str:
    parts = []
    for entry in path:
        if isinstance(entry, jax.tree_util.DictKey):
            parts.append(str(entry.key))
        elif isinstance(entry, jax.tree_util.SequenceKey):
            parts.append(str(entry.idx))
        elif isinstance(entry, jax.tree_util.GetAttrKey):
            parts.append(str(entry.name))
        else:  # FlattenedIndexKey and friends
            parts.append(str(getattr(entry, "key", entry)))
    return "/".join(parts)


def _quant_leaf_types():
    from k8s_llm_rca_tpu.models.quant import (
        QuantTensor, QuantTensor4, QuantTensor4Grouped,
    )
    return (QuantTensor, QuantTensor4, QuantTensor4Grouped)


def is_param_leaf(x) -> bool:
    """is_leaf for param pytrees: None passes through as a leaf (optional
    fields) and quantized tensors stay whole (payload+scale share a rule)."""
    return x is None or isinstance(x, _quant_leaf_types())


def match_partition_rules(rules: RuleTable, tree: PyTree, *,
                          table: str = "") -> PyTree:
    """PartitionSpec pytree for ``tree``: first rule whose regex matches the
    '/'-joined param path wins; scalars replicate; no match is a ValueError
    naming the param (no silent replicate default)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=is_param_leaf)
    specs = []
    for path, leaf in flat:
        name = _path_name(path)
        shape = _leaf_shape(leaf)
        if shape is None:                     # optional/absent field
            specs.append(P())
            continue
        if len(shape) == 0 or math.prod(shape) == 1:
            specs.append(P())                 # scalars replicate
            continue
        for pattern, spec in rules:
            if re.search(pattern, name):
                specs.append(spec)
                break
        else:
            where = f" in rule table '{table}'" if table else ""
            raise ValueError(
                f"no partition rule matches param '{name}'{where}; add an "
                f"explicit rule — params are never silently replicated")
    return jax.tree_util.tree_unflatten(treedef, specs)


def unused_rules(rules: RuleTable, tree: PyTree) -> List[str]:
    """Patterns in ``rules`` that match NO param in ``tree`` — the other
    direction of two-way coverage (a dead rule is a typo'd regex waiting
    to replicate the param it was meant to shard)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_param_leaf)
    names = []
    for path, leaf in flat:
        shape = _leaf_shape(leaf)
        if shape is None or len(shape) == 0 or math.prod(shape) == 1:
            continue
        names.append(_path_name(path))
    dead = []
    for pattern, _ in rules:
        if not any(re.search(pattern, n) for n in names):
            dead.append(pattern)
    return dead


# ---------------------------------------------------------------------------
# Per-model rule tables.  Ordered: first match wins, so the MoE stacked-expert
# rules precede the dense MLP rules that would otherwise catch w_gate/w_up.
# ---------------------------------------------------------------------------

def refuse_per_layer_kinds(cfg, what: str) -> None:
    """The rule table and the template below are the UNIFORM Llama
    block's.  A model whose layers differ in kind, or that has the leaves
    that come with them (query/key norms, a router's selection bias, a
    shared expert), has no rule here, and no mesh serves it (the engine
    asks here before it takes one): the window layers' ring of pages per
    slot has no sharding rule either (engine/paged.py refuses a mesh for
    it by name)."""
    if (getattr(cfg, "mixed_layers", False) or getattr(cfg, "qk_norm", False)
            or (cfg.n_experts > 0 and (cfg.shared_expert_size
                                       or cfg.router_kind != "softmax"))):
        raise ValueError(
            f"{what} (runtime/rules.py) is not built for {cfg.name!r}: its "
            f"Llama block has per-layer kinds or leaves (attn_layer_types, "
            f"n_dense_layers, qk_norm, a sigmoid router's bias, a shared "
            f"expert) that no sharding rule covers, and a mesh over the "
            f"window layers' ring is not built")


def llama_rules(cfg, layout: Optional[SpecLayout] = None) -> RuleTable:
    """Rule table for models/llama.init_params (dense AND MoE/mixtral —
    ``cfg.n_experts > 0`` prepends the stacked-expert rules).

    With the default layout this reproduces the historical hand-rolled
    specs verbatim: wq/wk/wv/w_gate/w_up column-parallel P(None, "model"),
    wo/w_down row-parallel P("model", None), embedding/lm_head hidden-
    sharded, norms replicated.  A layout with ``fsdp`` set additionally
    shards the non-TP dim of every matmul weight (hidden for the blocks,
    vocab for embedding/lm_head) along the fsdp axis — GSPMD all-gathers
    on use, which is what makes greedy parity hold byte-identically.
    """
    refuse_per_layer_kinds(cfg, "the Llama rule table (llama_rules)")
    lo = layout or TP_LAYOUT
    f, t, e = lo.fsdp, lo.tp, lo.ep
    rules: RuleTable = []
    if cfg.n_experts > 0:
        rules += [
            (r"layers/\d+/router$", P(None, None)),
            # stacked experts [E, H, I] / [E, I, H]: experts over the ep
            # axis, hidden over fsdp, the other matmul dim over tp —
            # EP × TP (× fsdp) composes.
            (r"layers/\d+/(w_gate|w_up)$", P(e, f, t)),
            (r"layers/\d+/w_down$", P(e, t, f)),
        ]
    rules += [
        (r"layers/\d+/(attn_norm|mlp_norm)$", P(None)),
        (r"layers/\d+/(wq|wk|wv)$", P(f, t)),   # [H, heads*d] column-parallel
        (r"layers/\d+/wo$", P(t, f)),           # [heads*d, H] row-parallel
        (r"layers/\d+/(w_gate|w_up)$", P(f, t)),
        (r"layers/\d+/w_down$", P(t, f)),
        (r"^(embedding|lm_head)$", P(f, t)),    # [V, H]: vocab on fsdp
        (r"^final_norm$", P(None)),
    ]
    return rules


def encoder_rules(cfg=None, layout: Optional[SpecLayout] = None) -> RuleTable:
    """Rule table for models/encoder.init_params (e5 tower).  Same TP
    layout as the decoder; biases of sharded columns shard on the same
    axis; LayerNorms replicate.  Only word_embedding takes the fsdp axis
    (position/type tables are small and not generally divisible)."""
    lo = layout or TP_LAYOUT
    f, t = lo.fsdp, lo.tp
    return [
        (r"layers/\d+/(wq|wk|wv|w_in)$", P(f, t)),
        (r"layers/\d+/(bq|bk|bv|b_in)$", P(t)),
        (r"layers/\d+/(wo|w_out)$", P(t, f)),
        (r"layers/\d+/(bo|b_out)$", P(None)),
        (r"layers/\d+/(attn_ln_w|attn_ln_b|mlp_ln_w|mlp_ln_b)$", P(None)),
        (r"^word_embedding$", P(f, t)),
        (r"^(position_embedding|type_embedding)$", P(None, t)),
        (r"^(embed_ln_w|embed_ln_b)$", P(None)),
    ]


# ---------------------------------------------------------------------------
# Shape-only templates mirroring models/*.init_params — matching a rule
# table against the template derives the full spec pytree (and proves
# every-param coverage) without any device work.
# ---------------------------------------------------------------------------

def llama_param_template(cfg) -> Dict[str, Any]:
    """ShapeDtypeStruct pytree with the exact structure/shapes of
    models/llama.init_params (models/llama.py:89-158)."""
    refuse_per_layer_kinds(cfg, "the Llama parameter template "
                            "(llama_param_template)")
    dt = jnp.dtype(cfg.dtype)
    h, q, kv, inter = (cfg.hidden_size, cfg.q_dim, cfg.kv_dim,
                       cfg.intermediate_size)
    S = jax.ShapeDtypeStruct
    layer: Dict[str, Any] = {
        "attn_norm": S((h,), dt),
        "mlp_norm": S((h,), dt),
        "wq": S((h, q), dt),
        "wk": S((h, kv), dt),
        "wv": S((h, kv), dt),
        "wo": S((q, h), dt),
    }
    if cfg.n_experts > 0:
        e = cfg.n_experts
        layer.update({
            "router": S((h, e), dt),
            "w_gate": S((e, h, inter), dt),
            "w_up": S((e, h, inter), dt),
            "w_down": S((e, inter, h), dt),
        })
    else:
        layer.update({
            "w_gate": S((h, inter), dt),
            "w_up": S((h, inter), dt),
            "w_down": S((inter, h), dt),
        })
    tmpl: Dict[str, Any] = {
        "embedding": S((cfg.vocab_size, h), dt),
        "final_norm": S((h,), dt),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }
    if not cfg.tie_embeddings:
        tmpl["lm_head"] = S((cfg.vocab_size, h), dt)
    return tmpl


def encoder_param_template(cfg) -> Dict[str, Any]:
    """ShapeDtypeStruct pytree mirroring models/encoder.init_params
    (models/encoder.py:40-79)."""
    dt = jnp.dtype(cfg.dtype)
    h, inter = cfg.hidden_size, cfg.intermediate_size
    S = jax.ShapeDtypeStruct
    layer = {
        "wq": S((h, h), dt), "bq": S((h,), dt),
        "wk": S((h, h), dt), "bk": S((h,), dt),
        "wv": S((h, h), dt), "bv": S((h,), dt),
        "wo": S((h, h), dt), "bo": S((h,), dt),
        "attn_ln_w": S((h,), dt), "attn_ln_b": S((h,), dt),
        "w_in": S((h, inter), dt), "b_in": S((inter,), dt),
        "w_out": S((inter, h), dt), "b_out": S((h,), dt),
        "mlp_ln_w": S((h,), dt), "mlp_ln_b": S((h,), dt),
    }
    return {
        "word_embedding": S((cfg.vocab_size, h), dt),
        "position_embedding": S((cfg.max_seq_len, h), dt),
        "type_embedding": S((2, h), dt),
        "embed_ln_w": S((h,), dt),
        "embed_ln_b": S((h,), dt),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


# ---------------------------------------------------------------------------
# Serving-state derivation (optimizer-free: the paged KV pool).
# ---------------------------------------------------------------------------

def paged_pool_specs(layout: Optional[SpecLayout] = None) -> Tuple[P, P]:
    """Paged KV pool [L, n_pages, page, kv]: the merged kv axis over tp,
    pages replicated (page indices are host state).  Returns
    (pool_spec, scale_spec) — scales [L, n_pages, page] replicate their
    reduced dim.  fsdp never shards the pool."""
    lo = layout or TP_LAYOUT
    return (P(None, None, None, lo.tp), P(None, None, None))


# ---------------------------------------------------------------------------
# Layout pre-flight.
# ---------------------------------------------------------------------------

def validate_layout(layout: SpecLayout, mesh: Mesh,
                    peers: Sequence[Mesh] = ()) -> SpecLayout:
    """Cross-check a SpecLayout against the mesh BEFORE any weight is
    placed, so a misconfigured fleet dies at build time, not mid-sweep:

    - a logical axis mapped to a mesh axis name the mesh doesn't define
      → named ValueError;
    - a NON-DEFAULT mapping (fsdp, or any axis remapped away from its
      canonical name) onto a size-1 mesh axis → named ValueError: the
      layout requests sharding that silently wouldn't happen.  Default
      mappings tolerate size-1 axes — "tp over 'model'" on a model=1
      mesh is the pervasive single-chip degenerate case;
    - ``peers`` (other tiers' meshes) sharing any device with ``mesh``
      → ValueError listing the overlapping device ids.

    Returns the layout so call sites can validate-and-use in one line.
    """
    if layout is None:
        layout = TP_LAYOUT
    names = tuple(mesh.axis_names)
    default = SpecLayout()
    for logical, axis in layout.to_dict().items():
        if axis is None:
            continue
        if axis not in names:
            raise ValueError(
                f"SpecLayout.{logical} maps to mesh axis '{axis}' which is "
                f"undefined on a mesh with axes {names}")
        if axis != getattr(default, logical) and int(mesh.shape[axis]) <= 1:
            raise ValueError(
                f"SpecLayout.{logical} maps to mesh axis '{axis}' of size 1: "
                f"the layout requests sharding that cannot happen — widen "
                f"the axis or drop it from the layout")
    mine = {d.id for d in mesh.devices.flat}
    for peer in peers:
        if peer is mesh:
            continue
        overlap = mine & {d.id for d in peer.devices.flat}
        if overlap:
            raise ValueError(
                f"tier submeshes overlap on device ids {sorted(overlap)}: "
                f"per-tier layouts require disjoint device sets")
    return layout
