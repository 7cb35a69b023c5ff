"""Configuration layer.

The reference has no config system at all — Neo4j URIs, model names, polling
constants, retry counts and file paths are hardcoded in every driver
(reference: test_all.py:21-22, find_metapath/find_srckind_metapath_neo4j.py:50,
common/openai_generic_assistant.py:94-95).  Here every knob is an explicit
frozen dataclass so drivers, tests and benches share one source of truth.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

# the published names of ``ModelConfig.mixer_types`` and the layer table's
# letter for each
LAYER_TYPE_LETTERS = {"mamba": "M", "attention": "*"}


@dataclass(frozen=True)
class ModelConfig:
    """Decoder-LM architecture config.

    Two families share it.  With ``layer_pattern`` empty every layer is
    the Llama block (attention then MLP, two norms, two residuals;
    Mixtral via ``n_experts > 0``), computed by models/llama.py.  The
    block's layers need not be alike: ``attn_layer_types`` gives each
    layer's attention its kind, ``full_attention`` (position ``i`` sees
    ``0 .. i``; keys and values of every token, in pages) or
    ``sliding_attention`` (``i`` sees the last ``attn_window``
    positions up to itself; the last window of keys and values, in a ring
    of pages per decode slot); ``n_dense_layers`` leading layers have the
    dense MLP at ``intermediate_size`` and the rest the experts;
    ``qk_norm`` puts an RMSNorm with a learned gain over every query and
    key head before the rotary embedding, and ``rope_full_layers`` False
    leaves the rotary embedding to the window layers (``layer_cfg`` is
    the block's view of one layer).  With a layer table
    (models/nemotron_h.py walks it; ``layer_table`` is its one form, a
    letter a layer) a layer is ONE mixer or ONE feed-forward part under
    one norm and one residual, its kind the letter: ``M`` a Mamba-2 mixer
    (the ``ssm_*`` fields), ``E`` an expert layer (router kind, latent and
    shared widths, the experts held here), ``*`` attention.  The table is
    stated as ``layer_pattern``, the letters themselves (``nemotron_h``),
    or as ``mixer_types``, the published names ``mamba`` / ``attention``
    (``granitemoehybrid``).  ``block_mlp_size`` makes every layer of the
    table a block of TWO sublayers: behind the mixer, under a norm and a
    residual of its own, a gated MLP of that width.  Four scale factors,
    each 1 (or 0 for the softmax scale) where a model has none:
    ``embedding_multiplier`` on the embedded tokens,
    ``residual_multiplier`` on what every sublayer adds to the residual
    stream, ``logits_scaling`` dividing the logits, and ``attn_scale``,
    the model's own softmax scale in place of ``1 / sqrt(head_dim)``.
    The engine derives what it holds per slot (pages for the attention
    layers, a ring for the window layers, a recurrent state for the Mamba
    layers) from these tables and from nothing else.

    ``kv_lora_rank`` > 0 makes every layer's attention LATENT
    (multi-head latent attention, ``deepseek_v3``): a token's keys and
    values are up-projections of ONE vector of that width, and the cache
    keeps that vector (normalized) and one rotated key of
    ``qk_rope_head_dim`` shared by all heads, ``latent_row`` values a
    token and layer, in place of keys and values per head.  A head's
    query and key are ``qk_nope_head_dim`` unrotated and
    ``qk_rope_head_dim`` rotated values, its value ``v_head_dim``;
    ``head_dim`` is the rotary table's width (the published ``head_dim``
    of such a model is its ``qk_rope_head_dim``).
    """

    name: str = "tiny"
    vocab_size: int = 512
    hidden_size: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    intermediate_size: int = 256
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 1024
    dtype: str = "float32"          # compute/weight dtype ("bfloat16" on TPU)
    tie_embeddings: bool = True
    # MoE (0 experts == dense Llama MLP)
    n_experts: int = 0
    n_experts_per_tok: int = 2
    # route the 2-D weight-dequant GEMMs (wq/wk/wv/wo, the dense MLP, the
    # router, the lm head) through the Pallas kernels (ops/quant_matmul.py)
    # that stream PACKED int8/int4 tiles and dequantize in-register — on a
    # real TPU backend with quantized unsharded-or-shard-local weights;
    # every other case (plain arrays, CPU/interpret hosts, GSPMD-sharded
    # consumption) falls back to the identical x @ dq(w) XLA path.  Never
    # timed on a chip and off in every benchmark configuration.  Since PR 35
    # it selects nothing in the expert layer: the stacked experts' kernels
    # are chosen from the call's shape (llama.moe_fused)
    fused_quant_matmul: bool = False
    # --- the Llama block's layers by kind (empty / 0 = every layer alike:
    # full attention, rotary embedding, the one MLP) ---
    attn_layer_types: Tuple[str, ...] = ()   # a layer: "full_attention" |
                                             # "sliding_attention"
    attn_window: int = 0               # positions a sliding layer sees,
                                       # the query's own included
    n_dense_layers: int = 0            # leading layers with the dense MLP
                                       # (the rest route to the experts)
    qk_norm: bool = False              # RMSNorm + gain over each q, k head
    rope_full_layers: bool = True      # False: rotary embedding on the
                                       # sliding layers only
    # --- latent attention (0 = keys and values per head, the Llama
    # block's): the four widths, and how the rotated pairs lie ---
    kv_lora_rank: int = 0              # the latent a token is cached as
    qk_nope_head_dim: int = 0          # a head's unrotated q/k width
    qk_rope_head_dim: int = 0          # the rotated width, ONE key a token
    v_head_dim: int = 0                # a head's value width
    rope_interleave: bool = False      # rotated pairs are (2i, 2i + 1):
                                       # de-interleaved, then rotate-half
    # --- the layer table (empty = the Llama block in every layer),
    # stated one way or the other; ``layer_table`` is what is walked ---
    layer_pattern: str = ""            # a letter a layer: M | E | *
    mixer_types: Tuple[str, ...] = ()  # a name a layer: "mamba" |
                                       # "attention"
    block_mlp_size: int = 0            # >0: a gated MLP of this width
                                       # behind every mixer of the table
    use_rope: bool = True              # a table's attention applies none
    # --- scale factors (granitemoehybrid's four) ---
    embedding_multiplier: float = 1.0  # x = E[token] * this
    residual_multiplier: float = 1.0   # x = x + this * sublayer(norm(x))
    logits_scaling: float = 1.0        # logits = head(x) / this
    attn_scale: float = 0.0            # softmax(q k^T * this); 0 =
                                       # 1 / sqrt(head_dim).  Folded into
                                       # the query (``q_fold``)
    # Mamba-2 mixer: heads x head_dim inner width, B and C shared by the
    # heads of a group, a [heads, head_dim, state] recurrent state per
    # sequence kept in ``ssm_state_dtype`` plus the depthwise
    # convolution's last ``ssm_conv_kernel - 1`` inputs
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state_size: int = 0
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    ssm_state_dtype: str = "float32"
    # shape the seeded dt_bias (init_params reads them; the forward
    # applies no clamp to dt)
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # expert layer.  ``n_experts`` is how many experts are HELD here
    # (their weights exist); ``router_width`` is how many the router
    # scores (0 = n_experts, every expert held) and ``expert_first`` the
    # global index of the first one held: one chip's share of an
    # expert-parallel deployment routes over all of them and computes its
    # own experts' part (models/llama._moe_mlp)
    router_width: int = 0
    expert_first: int = 0
    router_kind: str = "softmax"       # "softmax" over the kept logits |
                                       # "sigmoid" scores + selection bias
    routed_scaling: float = 1.0        # sigmoid router: weights sum to this
    moe_latent_size: int = 0           # >0: experts live between two shared
                                       # projections hidden <-> latent
    moe_intermediate_size: int = 0     # expert width (0 = intermediate_size)
    shared_expert_size: int = 0        # >0: an always-on expert of this width
    mlp_act: str = "swiglu"            # "swiglu" | "relu2" (non-gated)
    # depth of the WHOLE model these layers are a stage of (0 = n_layers):
    # the family rescales every output projection by 1 / sqrt(depth) at
    # initialisation (rescale_prenorm_residual), and a stage cut out of
    # a deeper model carries the deeper model's weights
    init_layers: int = 0

    def __post_init__(self):
        if self.layer_pattern:
            if self.mixer_types:
                raise ValueError(
                    "layer_pattern and mixer_types both state the layer "
                    "table: give one")
            if len(self.layer_pattern) != self.n_layers:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r} has "
                    f"{len(self.layer_pattern)} letters for n_layers="
                    f"{self.n_layers}")
            unknown = set(self.layer_pattern) - set("ME*")
            if unknown:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r}: unknown layer "
                    f"kind {sorted(unknown)[0]!r} (M: a Mamba-2 mixer, "
                    f"which keeps a recurrent state per sequence; E: "
                    f"experts, which keep nothing; *: attention, which "
                    f"keeps keys and values in pages)")
        if self.mixer_types:
            if len(self.mixer_types) != self.n_layers:
                raise ValueError(
                    f"mixer_types has {len(self.mixer_types)} entries for "
                    f"n_layers={self.n_layers}")
            unknown = set(self.mixer_types) - set(LAYER_TYPE_LETTERS)
            if unknown:
                raise ValueError(
                    f"mixer_types: unknown layer kind "
                    f"{sorted(unknown)[0]!r} (mamba: a Mamba-2 mixer, which "
                    f"keeps a recurrent state per sequence; attention: "
                    f"which keeps keys and values in pages)")
        if self.block_mlp_size and (not self.layer_table or self.n_experts):
            raise ValueError(
                f"block_mlp_size={self.block_mlp_size} is a dense gated "
                f"MLP behind every mixer of a layer table: the Llama block "
                f"has its own MLP (intermediate_size), and beside experts "
                f"(n_experts={self.n_experts}) it is not built")
        if not self.layer_table and (self.embedding_multiplier != 1.0
                                     or self.residual_multiplier != 1.0):
            raise ValueError(
                f"embedding_multiplier={self.embedding_multiplier} and "
                f"residual_multiplier={self.residual_multiplier} are "
                f"applied by the layer table's programs "
                f"(models/nemotron_h.py); the Llama block's loops apply "
                f"neither (logits_scaling and attn_scale they do)")
        if self.attn_scale and math.frexp(self.q_fold)[0] != 0.5:
            raise ValueError(
                f"attn_scale={self.attn_scale} with head_dim="
                f"{self.head_dim}: the softmax scale is folded into the "
                f"query as attn_scale * sqrt(head_dim) = {self.q_fold}, "
                f"which is exact in every dtype only for a power of two; "
                f"another value needs the scale as an argument of the "
                f"attention forms, which is not built")
        if self.kv_lora_rank:
            self._check_latent()
        elif (self.qk_nope_head_dim or self.qk_rope_head_dim
              or self.v_head_dim or self.rope_interleave):
            raise ValueError(
                f"qk_nope_head_dim={self.qk_nope_head_dim}, "
                f"qk_rope_head_dim={self.qk_rope_head_dim}, v_head_dim="
                f"{self.v_head_dim} and rope_interleave="
                f"{self.rope_interleave} are latent attention's: they "
                f"need kv_lora_rank > 0")
        if self.attn_layer_types:
            if self.layer_table:
                raise ValueError(
                    "attn_layer_types is the Llama block's: a model with "
                    "a layer table has its attention kind in the table")
            if len(self.attn_layer_types) != self.n_layers:
                raise ValueError(
                    f"attn_layer_types has {len(self.attn_layer_types)} "
                    f"entries for n_layers={self.n_layers}")
            unknown = set(self.attn_layer_types) - {"full_attention",
                                                    "sliding_attention"}
            if unknown:
                raise ValueError(
                    f"attn_layer_types: unknown attention kind "
                    f"{sorted(unknown)[0]!r} (full_attention, "
                    f"sliding_attention)")
            if self.n_window_layers and self.attn_window <= 0:
                raise ValueError(
                    f"{self.n_window_layers} sliding_attention layers and "
                    f"attn_window={self.attn_window}")
        if not 0 <= self.n_dense_layers <= self.n_layers or (
                self.n_dense_layers and self.layer_table):
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} of the Llama block's "
                f"{self.n_layers} layers")
        if self.n_experts and not (
                0 <= self.expert_first
                and self.expert_first + self.n_experts <= self.n_router):
            last = self.expert_first + self.n_experts - 1
            raise ValueError(
                f"experts {self.expert_first}..{last} held of a router "
                f"over {self.n_router}")

    def _check_latent(self) -> None:
        """Latent attention is built for the Llama block with every layer
        full and rotated; what it is not built beside is refused by
        name."""
        what = f"latent attention (kv_lora_rank={self.kv_lora_rank})"
        if not (self.qk_nope_head_dim > 0 and self.qk_rope_head_dim > 0
                and self.v_head_dim > 0):
            raise ValueError(
                f"{what} needs its three head widths: qk_nope_head_dim="
                f"{self.qk_nope_head_dim}, qk_rope_head_dim="
                f"{self.qk_rope_head_dim}, v_head_dim={self.v_head_dim}")
        if self.head_dim != self.qk_rope_head_dim:
            raise ValueError(
                f"{what}: head_dim={self.head_dim} is the rotary table's "
                f"width and has to be qk_rope_head_dim="
                f"{self.qk_rope_head_dim}")
        if self.layer_pattern or self.mixer_types:
            raise ValueError(
                f"{what} in a layer table is not built: the table's "
                f"attention (models/nemotron_h.py) caches keys and values "
                f"per head")
        if "sliding_attention" in self.attn_layer_types:
            raise ValueError(
                f"{what} beside sliding-window layers is not built: a "
                f"ring holds keys and values per head")
        if self.qk_norm or self.attn_scale or not self.use_rope \
                or not self.rope_full_layers:
            raise ValueError(
                f"{what} is built with its own norm (on the latent), its "
                f"own softmax scale (1 / sqrt(qk_nope_head_dim + "
                f"qk_rope_head_dim), an argument of its attention forms) "
                f"and the rotary embedding in every layer: qk_norm="
                f"{self.qk_norm}, attn_scale={self.attn_scale}, use_rope="
                f"{self.use_rope}, rope_full_layers={self.rope_full_layers}")

    @property
    def layer_table(self) -> str:
        """The layer table, a letter a layer (``M`` | ``E`` | ``*``),
        however it was stated; empty for the Llama block in every layer.
        The one table every program walks."""
        if self.mixer_types:
            return "".join(LAYER_TYPE_LETTERS[t] for t in self.mixer_types)
        return self.layer_pattern

    @property
    def q_fold(self) -> float:
        """What a query is multiplied by ahead of every attention form,
        all of which scale by ``1 / sqrt(head_dim)``: the model's own
        softmax scale over that one (a power of two, ``__post_init__``
        holds it to that), 1 where the model has none."""
        if not self.attn_scale:
            return 1.0
        return self.attn_scale * math.sqrt(self.head_dim)

    @property
    def n_router(self) -> int:
        """Experts the router scores (the published count)."""
        return self.router_width or self.n_experts

    @property
    def expert_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def attn_windows(self) -> Tuple[int, ...]:
        """Each Llama-block layer's window: 0 where position ``i`` sees
        ``0 .. i``, else how many positions up to ``i`` it sees."""
        if not self.attn_layer_types:
            return (0,) * self.n_layers
        return tuple(self.attn_window if t == "sliding_attention" else 0
                     for t in self.attn_layer_types)

    @property
    def n_window_layers(self) -> int:
        """Layers that keep the last window of keys and values per slot."""
        return self.attn_layer_types.count("sliding_attention")

    @property
    def n_kv_layers(self) -> int:
        """Layers that cache every token's keys and values in pages: the
        pool's layer axis."""
        return (self.layer_table.count("*") if self.layer_table
                else self.n_layers - self.n_window_layers)

    @property
    def mixed_layers(self) -> bool:
        """The Llama block's layers differ in kind (window beside full
        attention, rotary embedding by kind, a leading dense MLP): its
        programs read ``layer_cfg`` layer by layer.  What needs more than
        that is the window alone (``n_window_layers``: a ring per slot, a
        prefill whose rows run one after another, ``llama.prefill_rows``)."""
        return bool(self.attn_layer_types or self.n_dense_layers)

    def ring_pages(self, page_size: int) -> int:
        """Pages of a slot's ring in a window layer: the pages the last
        ``attn_window`` positions can lie across."""
        return -(-self.attn_window // page_size) + 1

    def layer_cfg(self, li: int) -> "ModelConfig":
        """The Llama block's view of layer ``li``: this config where the
        layers are alike, else with that layer's rotary embedding (none
        on a full layer where ``rope_full_layers`` is False) and MLP (no
        experts in a leading dense layer)."""
        if not self.mixed_layers:
            return self
        return self.replace(
            use_rope=self.use_rope and (self.rope_full_layers
                                        or self.attn_windows[li] > 0),
            n_experts=0 if li < self.n_dense_layers else self.n_experts)

    @property
    def n_ssm_layers(self) -> int:
        """Layers that keep a recurrent state per sequence."""
        return self.layer_table.count("M")

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the depthwise convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size

    @property
    def qk_head_dim(self) -> int:
        """A latent head's query and key width, unrotated then rotated."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """What latent attention caches a token and layer: the latent and
        the one rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def q_dim(self) -> int:
        """The width attention hands to ``wo``."""
        if self.kv_lora_rank:
            return self.n_heads * self.v_head_dim
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        """The width of a cached row: a token's keys (and as many values)
        of every kv head, or its latent row."""
        if self.kv_lora_rank:
            return self.latent_row
        return self.n_kv_heads * self.head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Named architecture presets.  TINY/_MOE are for hermetic CPU tests; the
# 1B/8B/8x7B presets mirror the public architectures of the target models in
# BASELINE.md (TinyLlama-1.1B-Chat, Llama-3-8B-Instruct, Mixtral-8x7B).
# ---------------------------------------------------------------------------

TINY = ModelConfig(name="tiny")

TINY_MOE = ModelConfig(name="tiny_moe", n_experts=4, n_experts_per_tok=2)

# every layer kind of nemotron_h at toy widths: two Mamba-2 mixers, two
# expert layers (sigmoid router over 16, 8 held from the 4th, top-4, latent
# experts, a shared expert, squared ReLU) and one attention layer without
# rotary embedding
TINY_NEMOTRON_H = ModelConfig(
    name="tiny_nemotron_h", n_layers=5, layer_pattern="ME*ME",
    n_heads=4, n_kv_heads=2, head_dim=32, use_rope=False,
    tie_embeddings=False,
    ssm_heads=8, ssm_head_dim=16, ssm_groups=2, ssm_state_size=16,
    ssm_conv_kernel=4, ssm_chunk=16,
    n_experts=8, router_width=16, expert_first=4, n_experts_per_tok=4,
    router_kind="sigmoid", routed_scaling=2.5, moe_latent_size=64,
    moe_intermediate_size=96, shared_expert_size=192, mlp_act="relu2")

# every per-layer kind of the Llama block at toy widths (exaone_moe): a
# leading dense layer, three window layers to one full (window 8, rotary
# embedding on the window layers only), query/key norms, a sigmoid router
# over 16 experts of which 8 are held from the 4th, top-4, a SwiGLU shared
# expert
TINY_EXAONE_MOE = ModelConfig(
    name="tiny_exaone_moe", n_layers=5,
    attn_layer_types=("sliding_attention",) * 3 + ("full_attention",
                                                   "sliding_attention"),
    attn_window=8, n_dense_layers=1, qk_norm=True,
    rope_full_layers=False, tie_embeddings=False,
    n_experts=8, router_width=16, expert_first=4, n_experts_per_tok=4,
    router_kind="sigmoid", routed_scaling=2.5, moe_intermediate_size=96,
    shared_expert_size=96)

# deepseek_v3 (kanana-2) at toy widths: latent attention in every layer (a
# latent of 32 and one rotated key of 16 a token where keys and values per
# head would be 4 x (24 + 16) + 4 x 16 = 224), pairs interleaved, a leading
# dense layer, a sigmoid router over 16 experts of which 8 are held from the
# 4th, top-4, a SwiGLU shared expert
TINY_KANANA_MOE = ModelConfig(
    name="tiny_kanana_moe", n_layers=3, n_heads=4, n_kv_heads=4,
    head_dim=16, kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=16,
    v_head_dim=16, rope_interleave=True, n_dense_layers=1,
    tie_embeddings=False, rms_norm_eps=1e-6,
    n_experts=8, router_width=16, expert_first=4, n_experts_per_tok=4,
    router_kind="sigmoid", routed_scaling=2.448, moe_intermediate_size=96,
    shared_expert_size=192)

# granitemoehybrid at toy widths: every kind of layer by its published name,
# two sublayers a layer (a gated MLP behind every mixer), one group of B and
# C, all four scale factors at values that are not 1, a softmax scale of
# 1 / head_dim (a query fold of 1/4), attention without rotary embedding,
# tied head
TINY_GRANITE_HYBRID = ModelConfig(
    name="tiny_granite_hybrid", n_layers=4,
    mixer_types=("mamba", "attention", "mamba", "mamba"),
    block_mlp_size=192, n_heads=4, n_kv_heads=2, head_dim=16,
    use_rope=False, tie_embeddings=True,
    ssm_heads=8, ssm_head_dim=16, ssm_groups=1, ssm_state_size=16,
    ssm_conv_kernel=4, ssm_chunk=16,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attn_scale=1.0 / 16)

TINYLLAMA_1B = ModelConfig(
    name="tinyllama-1.1b",
    vocab_size=32000,
    hidden_size=2048,
    n_layers=22,
    n_heads=32,
    n_kv_heads=4,
    head_dim=64,
    intermediate_size=5632,
    rope_theta=10000.0,
    max_seq_len=2048,
    dtype="bfloat16",
    tie_embeddings=False,
)

LLAMA3_8B = ModelConfig(
    name="llama3-8b",
    vocab_size=128256,
    hidden_size=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    intermediate_size=14336,
    rope_theta=500000.0,
    max_seq_len=8192,
    dtype="bfloat16",
    tie_embeddings=False,
)

MIXTRAL_8X7B = ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32000,
    hidden_size=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    intermediate_size=14336,
    rope_theta=1000000.0,
    max_seq_len=8192,
    dtype="bfloat16",
    tie_embeddings=False,
    n_experts=8,
    n_experts_per_tok=2,
)

MODEL_REGISTRY = {
    c.name: c for c in (TINY, TINY_MOE, TINY_NEMOTRON_H, TINY_EXAONE_MOE,
                        TINY_KANANA_MOE, TINY_GRANITE_HYBRID, TINYLLAMA_1B,
                        LLAMA3_8B, MIXTRAL_8X7B)
}


@dataclass(frozen=True)
class EncoderConfig:
    """Bidirectional encoder config (e5 family) for embedding/rerank."""

    name: str = "tiny-encoder"
    vocab_size: int = 512
    hidden_size: int = 128
    n_layers: int = 2
    n_heads: int = 4
    intermediate_size: int = 256
    max_seq_len: int = 512
    layer_norm_eps: float = 1e-12
    dtype: str = "float32"


TINY_ENCODER = EncoderConfig()

E5_LARGE = EncoderConfig(
    name="e5-large",
    vocab_size=30522,
    hidden_size=1024,
    n_layers=24,
    n_heads=16,
    intermediate_size=4096,
    max_seq_len=512,
    dtype="bfloat16",
)


@dataclass(frozen=True)
class MeshConfig:
    """Logical device-mesh shape.  Axis names are load-bearing throughout:

    - ``data``   — DP: batch sharding
    - ``fsdp``   — FSDP: parameter sharding with all-gather-on-use (weights
                   split along their non-TP dim; runtime/rules.py SpecLayout
                   decides which params land on it)
    - ``model``  — TP: attention heads / MLP hidden dim over ICI
    - ``expert`` — EP: MoE experts (all-to-all token dispatch)
    - ``seq``    — SP/CP: sequence sharding (ring attention / Ulysses)
    - ``stage``  — PP: pipeline stages over DCN
    """

    data: int = 1
    fsdp: int = 1
    model: int = 1
    expert: int = 1
    seq: int = 1
    stage: int = 1

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("data", "fsdp", "model", "expert", "seq", "stage")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.data, self.fsdp, self.model, self.expert, self.seq,
                self.stage)

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclass(frozen=True)
class EngineConfig:
    """Inference-engine config: batching, KV cache, sampling, limits."""

    max_batch: int = 8                 # decode slots (continuous batching width)
    max_seq_len: int = 1024            # per-slot KV capacity
    prefill_buckets: Tuple[int, ...] = (64, 128, 256, 512, 1024)
    max_new_tokens: int = 256
    # KV pool storage: None = model dtype; "int8" / "int4" = per-token
    # quantized KV (half / a quarter of the pool's HBM and bandwidth,
    # small quality cost)
    kv_cache_dtype: Optional[str] = None
    # the paged KV pool.  ``paged`` selects nothing: the contiguous-slot
    # engine is gone and make_engine refuses False.  The field is kept
    # only until a benchmark PR drops the key from the ``engine`` group of
    # benchmarks/configs/*.json (ROADMAP B0.8), which reaches
    # EngineConfig(**group) as is.
    paged: bool = True
    page_size: int = 16
    num_pages: int = 1024
    # share page-aligned prompt-prefix KV between sequences
    # (engine/prefix.py) — the RCA agent threads grow monotonically,
    # so consecutive runs re-submit almost identical prompts
    prefix_cache: bool = True
    # sampling defaults
    temperature: float = 0.0           # 0 == greedy
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    # decode loop
    decode_chunk: int = 16             # device steps per host sync in scan mode
    # tick stepwise while requests are queued, so a freed slot is noticed
    # within ONE decode step (prompt admission, lower TTFT under load)
    # instead of after the current chunk.
    prompt_admission: bool = False
    # n-gram speculative decoding (greedy only; engine/speculative.py):
    # k drafts verified per tick by one multi-token decode.  0 = off.
    speculative_k: int = 0
    speculative_ngram: int = 3
    # host-side runtime: use the C++ components (page allocator, grammar
    # mask engine) when a toolchain can build them; pure-Python fallback
    # is behavior-identical
    native: bool = True
    # overlapped serving hot loop (docs/performance.md): device-resident
    # decode state (cur_tokens/lengths/block_tables stay on device between
    # ticks), coalesced device->host syncs (one packed fetch per flush),
    # deferred admission first-token fetches, and — for decode_chunk == 1
    # engines without speculation or live grammars — a one-tick-lagged
    # commit so host bookkeeping overlaps the in-flight device step.
    # Greedy byte-parity with host_overlap=False is guaranteed for every
    # supported composition; cp_mesh is excluded (loud ValueError).
    host_overlap: bool = False
    # per-tick prefill token budget (0 = off): a prompt whose
    # post-prefix-hit suffix exceeds the budget admits
    # through the existing jitted chunk-prefill path spread across ticks
    # — one <=budget page-aligned chunk per tick, the sequence's own
    # already-written pages as the growing prefix — instead of stalling
    # one tick on a monolithic prefill.  Must be a page_size multiple
    # (chunks scatter whole pages); greedy byte-parity with budget=0 is
    # guaranteed; cp_mesh/pp_mesh are excluded (loud ValueErrors).
    prefill_chunk_budget: int = 0
    # overload survival (docs/serving.md "overload & priorities"): when
    # > 0, a preempted sequence spills its written KV
    # pages to host buffers (one coalesced d2h fetch) and resumes by h2d
    # page restore instead of re-prefill — byte-identical greedy output,
    # no re-burned prefill FLOPs.  The value caps the TOTAL host-resident
    # spilled pages; a preemption that would exceed it falls back to the
    # free-and-re-prefill path.  0 = off (today's behavior).  Excluded
    # (loud ValueError) on cp_mesh (page axis sequence-sharded) and
    # pp_mesh (pool layer axis stage-sharded).
    max_spilled_pages: int = 0
    # tiered prefix cache (engine/prefix.py ``PrefixStore``,
    # docs/performance.md "tiered prefix cache"): when
    # any knob is set, prefix-cache eviction DEMOTES page KV to a
    # host-RAM store (one coalesced d2h gather, the same page-record
    # layout as KV spill) instead of discarding it, and tier-aware
    # ``match`` PROMOTES store hits back by h2d page writes — a warm
    # miss costs a page copy, not a re-prefill.  ``prefix_host_pages``
    # caps the host-RAM tier (L1).  ``prefix_disk_dir`` persists
    # demoted pages to disk (L2) with the utils/wal.py atomic
    # temp+fsync+replace recipe and CRC-verified load: a torn/corrupt
    # entry is a silent cold miss, never a crash.  ``prefix_disk_pages``
    # caps the disk tier (0 with a dir set = unbounded).  The store's
    # budget is its OWN — spilled-run pages (``max_spilled_pages``) and
    # cached prefix pages never share a cap.  Greedy byte-parity across
    # cold-miss / L0 / L1 / L2 hits is guaranteed; excluded (loud
    # ValueError) on cp_mesh (page axis sequence-sharded) and pp_mesh
    # (pool layer axis stage-sharded), mirroring the spill exclusions.
    prefix_host_pages: int = 0
    prefix_disk_dir: Optional[str] = None
    prefix_disk_pages: int = 0
    # pressure-driven demotion (docs/performance.md "cache fabric"):
    # when > 0, an HBM high-water mark in PAGES — at
    # every tick boundary where the allocator's free-page count dips
    # below it, refcount-0 prefix pages demote autonomously through the
    # same coalesced ``_demote`` gather explicit eviction uses, oldest
    # first, until the watermark is restored (or the evictable set runs
    # dry).  Engines keep hot pages resident under production load with
    # no router intervention; with a store attached the demoted pages
    # stay promotable, without one this is plain pressure eviction.
    # Requires ``prefix_cache=True``; excluded (loud ValueError) for
    # negative / over-capacity (>= num_pages) values.  0 = off (explicit
    # evict only, today's behavior).
    prefix_hbm_watermark: int = 0
    # store-backed instant recovery (requires a tiered/remote store;
    # docs/durability.md "store-backed restore"):
    # when True, every tick that grew the prefix cache also publishes
    # the newly-resident full-page chains to the store WITHOUT freeing
    # them (``PrefixCache.flush_to_store``), so a crash-restart, drain
    # migration or disagg prefill-death fallback on ANOTHER engine
    # re-prefills against a warm fabric — near-instant, promote-then-
    # adopt, spill-identical bucket math.  Excluded (loud ValueError)
    # without a store: write-through with nowhere to write is a config
    # bug, not a degraded mode.
    prefix_store_writethrough: bool = False


@dataclass(frozen=True)
class RCAConfig:
    """Agent-pipeline config (retry budgets mirror the reference's:
    test_all.py:63,99; polling limits common/openai_generic_assistant.py:94-95)."""

    locator_max_attempts: int = 3
    cypher_max_attempts: int = 3
    metapath_max_hops: int = 3
    # per-stage decode budgets (tokens); the locator's must exceed its
    # structured-output schema's minimal document (constrain.SchemaGrammar
    # .min_budget — EngineBackend.start rejects budgets below it)
    locator_max_new_tokens: int = 768
    cypher_max_new_tokens: int = 512
    analyzer_max_new_tokens: int = 512
    srckind_limit: int = 5
    state_limit: int = 10
    # submit all per-entity audit runs before awaiting any (SURVEY §3.4:
    # they are independent until the summary barrier), so the engine
    # decodes them in one continuous batch; False = reference-serial order
    concurrent_audits: bool = True
    run_timeout_s: float = 600.0
    model: str = "tiny"                # serve-side model name
    rerank_top_k: int = 0              # cap audited records when reranking (0 = all)
    # cap the STATE fields entering each audit prompt to the k most
    # relevant by embedding (0 = all 12 reference fields); requires a
    # pipeline reranker — the rerank result then shapes prompt CONTENT,
    # not just record order (BASELINE configs[4])
    rerank_fields_top_k: int = 0
    # start every incident on FRESH stage threads (templates/rules
    # re-seeded).  The reference reuses one monotonically growing thread
    # per assistant across a whole sweep (test_with_file.py loops over
    # setup-once assistants) — viable only against a remote model with
    # effectively unbounded context; with an in-tree engine whose
    # max_seq_len is a real KV budget, long sweeps need re-anchoring.
    # Retry-with-feedback WITHIN an incident still accumulates.
    fresh_threads: bool = False
    # grammar-constrained decode for the three structured stages (plan
    # schema, cypher skeleton, report schema).  False = raw free decode:
    # output validity then rests entirely on the MODEL — the content-
    # validation mode for distilled checkpoints (rca/distill.py), and the
    # reference's own hope-and-retry regime (test_all.py:63-83)
    constrained: bool = True


@dataclass(frozen=True)
class SweepConfig:
    """Batch-driver config (reference: test_with_file.py:42-43,177-198)."""

    input_csv: str = "data/incidents.csv"
    output_json: str = "output/rca-results.json"
    locator_usage_limit: int = 10
    cypher_usage_limit: int = 20
    analyzer_usage_limit: int = 30


@dataclass(frozen=True)
class FrameworkConfig:
    model: ModelConfig = field(default_factory=lambda: TINY)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    engine: EngineConfig = field(default_factory=EngineConfig)
    rca: RCAConfig = field(default_factory=RCAConfig)
    sweep: SweepConfig = field(default_factory=SweepConfig)
