"""Continuous-batching inference engine: the surface and the host loop.

The reference's serving model is one blocking OpenAI run at a time with an
escalating 5 s poll (common/openai_generic_assistant.py:92-115) — strictly
serial.  The engine replaces it with slot-based continuous batching
(Orca/vLLM-style, re-designed for XLA's static shapes):

- admission = prefill into a free slot, padded to a static bucket length
  (one compile per bucket, cached for the process lifetime);
- every tick runs ONE jitted decode dispatch for ALL active slots (a scan
  of up to ``decode_chunk`` steps); sequences join and leave the batch at
  token granularity;
- completed slots are freed immediately and re-admitted from the pending
  queue the same tick.

This module holds what the agent layer sees and what the tick shares
(``EngineBase``: submit/generate, deadlines, snapshot/restore, grammar
application, the overlapped hot loop, the scan-chunk rule, termination,
draft verification), the mesh validators and the on-device DFA steps.
The engine itself, ``PagedInferenceEngine`` with its page pool,
allocator and model entry points, is engine/paged.py.

Slot bookkeeping lives on the host; it is the only writer of slot
indices and page ids (see .claude/skills/verify/SKILL.md on what JAX's
index clamping would otherwise hide).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from k8s_llm_rca_tpu.config import EngineConfig, ModelConfig
from k8s_llm_rca_tpu.engine.sampling import (
    SamplingParams, sample_tokens_masked,
)
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.obs import trace as obs_trace
from k8s_llm_rca_tpu.runtime import profiling
from k8s_llm_rca_tpu.utils.logging import METRICS, get_logger
from k8s_llm_rca_tpu.utils.tokenizer import Tokenizer

log = get_logger(__name__)


def host_np(x) -> np.ndarray:
    """Device->host fetch that also works on arrays spanning
    NON-ADDRESSABLE devices (multi-process serving: the global mesh
    covers other processes' devices, so plain ``np.asarray`` raises).
    Fully-addressable values (incl. plain host arrays) fetch directly;
    otherwise every process participates in a ``process_allgather`` —
    safe because the engine's host driver runs SPMD-identically in all
    processes (same prompts, same deterministic schedule), so the
    collective lines up across the cluster.  ONE definition for the
    engine and the speculative path: every per-tick sync routes
    through here."""
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def flash_prefill_safe(params) -> bool:
    """Whether inference prefill may use the Pallas flash kernel: TPU
    backend and no multi-device (TP/EP) param sharding — pallas_call has
    no SPMD partitioning rule, so a sharded run would silently replicate
    attention on every device (and it has no VJP, but prefill is
    inference-only here)."""
    if jax.default_backend() != "tpu":
        return False
    for leaf in jax.tree.leaves(params):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and getattr(sharding, "num_devices", 1) > 1:
            return False
    return True


def flash_prefill_plan(params, tp_mesh, model_cfg,
                       ep_mesh=None) -> Tuple[bool, object]:
    """(use_flash, flash_mesh) for the prefill jits: the plain Pallas
    kernel when params are unsharded on TPU (flash_prefill_safe), the
    PER-SHARD kernel (ops.flash_attention_sharded under ``tp_mesh``) when
    TP-sharded with head counts divisible by the model axis — sharded
    prefill no longer concedes the kernel to XLA.  (False, None)
    otherwise (CPU, indivisible heads, or EP: MoE prefill shards TOKENS
    over data×expert, a layout the head-sharded shard_map wrapper would
    replicate every layer)."""
    if flash_prefill_safe(params):
        return True, None
    if ep_mesh is not None:
        return False, None
    if (tp_mesh is not None and jax.default_backend() == "tpu"
            and model_cfg.n_heads % tp_mesh.shape["model"] == 0
            and model_cfg.n_kv_heads % tp_mesh.shape["model"] == 0):
        return True, tp_mesh
    return False, None


def params_multi_device(params) -> bool:
    """True when any param leaf carries a >1-device sharding (TP/EP)."""
    for leaf in jax.tree.leaves(params):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and getattr(sharding, "num_devices", 1) > 1:
            return True
    return False


def validate_tp_mesh(tp_mesh, model_cfg, engine_cfg, cp_mesh=None,
                     cp_seq_axis: str = "seq") -> None:
    """TP pool-sharding preconditions: the merged kv axis splits over
    "model" head-aligned (see runtime.sharding.paged_pool_specs) and the
    slot batch over "data".

    CP composes with TP only on ONE mesh carrying both axes (the pool
    takes the composed page-over-seq × kv-over-model layout and the
    ring/Ulysses prefill runs per head shard — SURVEY §7 hard part 6);
    two DIFFERENT mesh objects cannot both own the pool."""
    if tp_mesh is None:
        return
    for axis in ("data", "model"):
        if axis not in tp_mesh.shape:
            raise ValueError(f"tp_mesh needs a '{axis}' axis, has "
                             f"{dict(tp_mesh.shape)}")
    if cp_mesh is not None:
        if cp_mesh is not tp_mesh:
            raise ValueError(
                "cp_mesh and tp_mesh must be the SAME composed mesh "
                "(one Mesh carrying 'data', 'model' and the seq axis); "
                "two distinct meshes cannot both lay out the pool")
        if cp_seq_axis not in tp_mesh.shape:
            raise ValueError(f"composed mesh lacks the '{cp_seq_axis}' axis")
        n_tp = tp_mesh.shape["model"]
        if model_cfg.n_heads % n_tp or model_cfg.n_kv_heads % n_tp:
            # the CP attention shards HEADS over "model" (unexpanded GQA
            # KV rides the ring), so both head counts must split evenly
            raise ValueError(
                f"n_heads={model_cfg.n_heads}/n_kv_heads="
                f"{model_cfg.n_kv_heads} not divisible by model axis "
                f"{n_tp} (required for CP×TP prefill)")
    if model_cfg.kv_dim % (2 * tp_mesh.shape["model"]):
        # the factor 2 keeps the nibble-packed int4 layout shardable too
        raise ValueError(
            f"kv_dim={model_cfg.kv_dim} not shardable over model axis "
            f"{tp_mesh.shape['model']}")
    if engine_cfg.max_batch % tp_mesh.shape["data"]:
        raise ValueError(
            f"max_batch={engine_cfg.max_batch} not divisible by data axis "
            f"{tp_mesh.shape['data']}")


def validate_fsdp_mesh(fsdp_mesh, model_cfg, engine_cfg, tp_mesh=None,
                       cp_mesh=None, ep_mesh=None, pp_mesh=None,
                       sp: bool = False) -> None:
    """FSDP serving preconditions: parameters shard along the "fsdp"
    axis (runtime/rules.py FSDP_LAYOUT — the non-TP matmul dim: hidden
    for the blocks, vocab for the embeddings)
    and GSPMD all-gathers each weight on use, so prefill and decode run
    unchanged and greedy parity is byte-identical.

    Composes with TP on ONE mesh carrying both "fsdp" and "model"
    (fsdp×tp — the 8-virtual-device parity row).  PP/CP/EP and SP are
    refused loudly until their greedy-parity matrix lands: each of those
    modes hand-places weights or activations (stage bodies, ring
    attention, all-to-all dispatch) and would silently gather the full
    weight per device without a proven composition rule.  The KV pool
    never shards on fsdp (paged_pool_specs) — only the weights do."""
    if fsdp_mesh is None:
        return
    for axis in ("data", "fsdp", "model"):
        if axis not in fsdp_mesh.shape:
            raise ValueError(f"fsdp_mesh needs a '{axis}' axis, has "
                             f"{dict(fsdp_mesh.shape)}")
    if tp_mesh is not None and tp_mesh is not fsdp_mesh:
        raise ValueError(
            "fsdp_mesh and tp_mesh must be the SAME composed mesh (one "
            "Mesh carrying 'fsdp' and 'model'); two distinct meshes "
            "cannot both lay out the weights")
    for other, what in ((cp_mesh, "CP"), (ep_mesh, "EP"), (pp_mesh, "PP")):
        if other is not None:
            raise ValueError(
                f"fsdp×{what} is unsupported until its greedy-parity "
                f"matrix lands (tests/test_sharding_rules.py): compose "
                f"fsdp with TP only")
    if sp:
        raise ValueError(
            "fsdp×SP is unsupported until its greedy-parity matrix lands: "
            "compose fsdp with TP only")
    n_f = fsdp_mesh.shape["fsdp"]
    for dim, what in ((model_cfg.hidden_size, "hidden_size"),
                      (model_cfg.vocab_size, "vocab_size")):
        if dim % n_f:
            raise ValueError(
                f"{what}={dim} not divisible by fsdp axis {n_f} "
                f"(fsdp shards the hidden/vocab dim of every weight)")
    if engine_cfg.max_batch % fsdp_mesh.shape["data"]:
        raise ValueError(
            f"max_batch={engine_cfg.max_batch} not divisible by data axis "
            f"{fsdp_mesh.shape['data']}")


def validate_replica_mesh(mesh, model_cfg, engine_cfg) -> None:
    """Cluster-replica preconditions (cluster/submesh.py): a replica
    submesh is a plain dp×tp carve of the global device list.  The
    replica axis already multiplies throughput by running N independent
    engines, so any composition whose collectives would have to span
    replicas — CP sequence sharding, PP stages, EP dispatch — is excluded
    loudly at construction rather than silently computing on a submesh
    that cannot see the other replicas' devices."""
    if mesh is None:
        return
    for axis, what in (("seq", "CP"), ("stage", "PP"), ("expert", "EP")):
        if mesh.shape.get(axis, 1) > 1:
            raise ValueError(
                f"{what}×replica is unsupported: a cluster replica owns a "
                f"DISJOINT submesh and its collectives cannot span "
                f"replicas (axis '{axis}'={mesh.shape[axis]}); replica "
                f"submeshes carve dp×tp only (cluster/submesh.py) — run "
                f"{what} inside ONE engine on the full mesh instead")
    validate_tp_mesh(mesh, model_cfg, engine_cfg)
    if mesh.shape.get("fsdp", 1) > 1:
        # dp×fsdp×tp carve (cluster/submesh.py fsdp=): same-mesh compose
        validate_fsdp_mesh(mesh, model_cfg, engine_cfg, tp_mesh=mesh)


def validate_disjoint_submeshes(meshes) -> None:
    """Replica submeshes must not share a single device: two engines
    dispatching onto one chip would serialize (and on TPU fight over the
    chip grant), silently destroying the throughput the cluster layer
    exists to multiply.  Loud ValueError names the overlapping device."""
    seen: Dict[int, int] = {}
    for i, mesh in enumerate(meshes):
        if mesh is None:
            continue
        for d in mesh.devices.flat:
            if d.id in seen:
                raise ValueError(
                    f"replica submeshes overlap: device {d.id} belongs to "
                    f"both replica {seen[d.id]} and replica {i}; carve "
                    f"disjoint contiguous device groups "
                    f"(cluster.carve_replica_meshes)")
            seen[d.id] = i


def validate_ep_mesh(ep_mesh, model_cfg, engine_cfg, cp_mesh,
                     cp_seq_axis: str = "seq") -> None:
    """EP serving preconditions: MoE model; mesh carries "data" and
    "expert" axes; decode batch and prefill buckets divide by the token
    sharding (tokens shard over data*expert, parallel/moe.py).

    CP composes with EP on ONE mesh carrying "data", "expert" and the
    seq axis: CP prefill then shards MoE tokens over (seq, expert) — the
    sequence stays put, dispatch rides the expert axis (models/llama.py
    prefill_kv_cp) — and decode tokens shard over (data, expert) as in
    plain EP, over the seq-sharded pool."""
    if ep_mesh is None:
        return
    if model_cfg.n_experts <= 0:
        raise ValueError("ep_mesh requires an MoE model (n_experts > 0)")
    if cp_mesh is not None and cp_mesh is not ep_mesh:
        raise ValueError(
            "cp_mesh and ep_mesh must be the SAME composed mesh (one "
            "Mesh carrying 'data', 'expert' and the seq axis); two "
            "distinct meshes cannot both lay out the token sharding")
    for axis in ("data", "expert"):
        if axis not in ep_mesh.shape:
            raise ValueError(f"ep_mesh needs a '{axis}' axis, has "
                             f"{dict(ep_mesh.shape)}")
    p_tok = ep_mesh.shape["data"] * ep_mesh.shape["expert"]
    if model_cfg.n_experts % ep_mesh.shape["expert"]:
        raise ValueError(
            f"n_experts={model_cfg.n_experts} not divisible by expert "
            f"axis {ep_mesh.shape['expert']}")
    if engine_cfg.max_batch % p_tok:
        raise ValueError(
            f"max_batch={engine_cfg.max_batch} not divisible by "
            f"data*expert={p_tok} (decode tokens shard over both)")
    if cp_mesh is not None:
        # CP prefill is per-sequence (b=1): its MoE token dim is the
        # padded sequence itself, sharded over (seq, expert)
        p_pref = ep_mesh.shape[cp_seq_axis] * ep_mesh.shape["expert"]
    else:
        p_pref = p_tok
    for b in tuple(engine_cfg.prefill_buckets) + (engine_cfg.max_seq_len,):
        if b % p_pref:
            raise ValueError(
                f"prefill bucket {b} not divisible by the prefill token "
                f"sharding {p_pref}")
    if engine_cfg.prefix_cache and engine_cfg.page_size % p_tok:
        # the prefix-cache chunked prefill runs at ANY page-multiple width
        # (capped by remaining pages), so every width is divisible only if
        # one page already is — fail at construction, not mid-serve
        raise ValueError(
            f"page_size={engine_cfg.page_size} not divisible by "
            f"data*expert={p_tok}: the prefix-cache chunked prefill can "
            f"emit any page-multiple width; use a larger page_size or "
            f"prefix_cache=False")


def validate_pp_mesh(pp_mesh, model_cfg, engine_cfg, cp_mesh, ep_mesh,
                     tp_mesh, microbatches: Optional[int],
                     stage_axis: str = "stage",
                     params=None) -> Optional[int]:
    """PP serving preconditions.  Returns the resolved microbatch count
    (None when pp_mesh is None).

    PP composes with TP on ONE mesh carrying "stage" and "model" (the
    multi-host pod topology: stages over DCN, heads/hidden over ICI; the
    stage bodies run the manual-TP block with psum combines —
    parallel/pipeline.py).  Quantized KV composes with PP×TP: the
    per-token scale is the full-row scale recovered by pmax over the TP
    group (llama._quantize_kv axis_name), so scale pools replicate
    across TP and numerics match the plain quantized paths exactly.
    Quantized WEIGHTS compose too: int8 payloads shard on the weight
    spec with per-channel scales replicating their reduced dims,
    and int4 payloads are re-packed per shard at the sharding boundary
    ("shard first, pack second") so the stage bodies' shard-local
    dequant is exact — see pipeline.shard_stacked_layers.

    PP composes with EP on ONE mesh carrying "stage" and "expert"
    (Mixtral across pods: stages over DCN, expert dispatch over ICI
    within each stage).  Stage bodies run dense attention on the
    replicated stream and route each expert peer's token slice through
    the shared all-to-all dispatch (parallel/pipeline._moe_mlp_ep);
    PP×TP×EP is not composed (the manual-TP stage block computes a
    dense MLP).  Speculative decoding composes: the verify step runs the
    pipelined multi-token decode (parallel/pipeline.paged_pp_decode_multi),
    so n-gram and draft-model speculation work under PP, PP×TP and PP×EP.
    CP remains exclusive."""
    if pp_mesh is None:
        return None
    if cp_mesh is not None:
        raise ValueError(
            "pp_mesh and cp_mesh are mutually exclusive by design: "
            "stage-local CP replicates the matmul FLOPs and weight "
            "streaming that stage-local TP divides (1.5-3.6x the FLOPs, "
            "1.2-2.9x the HBM bytes per device at 4k-128k contexts — "
            "runtime.profiling.stage_local_cp_vs_tp and "
            "docs/parallelism.md 'PP×CP: a quantified no'); use PP×TP, "
            "or CP×TP for GQA-limited long contexts")
    if ep_mesh is not None:
        if ep_mesh is not pp_mesh:
            raise ValueError(
                "pp_mesh and ep_mesh must be the SAME composed mesh "
                "(one Mesh carrying 'stage' and 'expert'); two distinct "
                "meshes cannot both lay out the weights")
        if tp_mesh is not None:
            raise ValueError(
                "PP×TP×EP is unsupported (the manual-TP stage block "
                "computes a dense MLP; compose PP×EP or PP×TP)")
        n_ep = ep_mesh.shape["expert"]
        m_ep = microbatches or pp_mesh.shape[stage_axis]
        if (engine_cfg.max_batch // max(1, m_ep)) % n_ep:
            raise ValueError(
                f"PP×EP needs the microbatch size "
                f"{engine_cfg.max_batch}//{m_ep} divisible by the expert "
                f"axis {n_ep} (each expert peer routes a distinct token "
                f"slice of the microbatch)")
    if tp_mesh is not None:
        if tp_mesh is not pp_mesh:
            raise ValueError(
                "pp_mesh and tp_mesh must be the SAME composed mesh "
                "(one Mesh carrying 'stage' and 'model'); two distinct "
                "meshes cannot both lay out the weights and pool")
        n_tp = tp_mesh.shape["model"]
        if (model_cfg.n_heads % n_tp or model_cfg.n_kv_heads % n_tp):
            raise ValueError(
                f"n_heads={model_cfg.n_heads}/n_kv_heads="
                f"{model_cfg.n_kv_heads} not divisible by model axis "
                f"{n_tp} (required for PP×TP stage bodies)")
        if params is not None:
            from k8s_llm_rca_tpu.models.quant import QuantTensor4

            # int8 (QuantTensor) composes: the stacked spec tree expands
            # per-leaf so payloads shard on the weight spec and
            # per-channel scales replicate their reduced dims
            # (pipeline._stacked_in_specs).  int4 composes by PER-SHARD
            # packing: shard_stacked_layers re-packs every column-sharded
            # QuantTensor4 so each TP shard is a self-contained
            # split-half buffer ("shard first, pack second",
            # quant.repack_nibbles_grouped) — which needs every sharded
            # channel dim divisible by 2*n_tp.
            if any(isinstance(leaf, QuantTensor4)
                   for leaf in jax.tree.leaves(
                       params, is_leaf=lambda x: isinstance(
                           x, QuantTensor4))):
                for dim, what in ((model_cfg.q_dim, "q_dim"),
                                  (model_cfg.kv_dim, "kv_dim"),
                                  (model_cfg.intermediate_size,
                                   "intermediate_size")):
                    if dim % (2 * n_tp):
                        raise ValueError(
                            f"PP×TP with int4 weights needs {what}={dim} "
                            f"divisible by 2*model axis={2 * n_tp} "
                            f"(per-shard split-half nibble packing)")
        if model_cfg.n_experts > 0:
            raise ValueError(
                "PP×TP does not support MoE models (the manual-TP stage "
                "block computes a dense MLP; expert-stacked weights need "
                "the EP dispatch, which PP excludes)")
    if stage_axis not in pp_mesh.shape:
        raise ValueError(f"pp_mesh needs a '{stage_axis}' axis, has "
                         f"{dict(pp_mesh.shape)}")
    n_stages = pp_mesh.shape[stage_axis]
    if model_cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={model_cfg.n_layers} not divisible into "
            f"{n_stages} pipeline stages")
    m = microbatches or n_stages
    if engine_cfg.max_batch % m:
        raise ValueError(
            f"max_batch={engine_cfg.max_batch} not divisible into "
            f"{m} PP microbatches")
    return m


def setup_draft(draft_model, model_cfg, engine_cfg):
    """Validate + build the ModelDraft for ``draft_model=(cfg, params)``;
    None passes through."""
    if draft_model is None:
        return None
    if engine_cfg.speculative_k <= 0:
        raise ValueError("draft_model requires speculative_k > 0 "
                         "(the draft only exists to fill draft slots)")
    dcfg, dparams = draft_model
    if dcfg.vocab_size != model_cfg.vocab_size:
        raise ValueError(
            f"draft vocab {dcfg.vocab_size} != target vocab "
            f"{model_cfg.vocab_size} (draft tokens must be target tokens)")
    from k8s_llm_rca_tpu.engine.speculative import ModelDraft

    return ModelDraft(dcfg, dparams, engine_cfg)


def validate_cp_divisibility(cp_seq_axis: str, n_cp: int, sizes) -> None:
    """CP prefill shards the padded sequence over the mesh axis; every
    prefill bucket (and max_seq_len — the caller passes page-rounded
    sizes) must split evenly across it."""
    bad = [s for s in sizes if s % n_cp]
    if bad:
        raise ValueError(
            f"cp mesh axis '{cp_seq_axis}' size {n_cp} must divide "
            f"every prefill bucket and max_seq_len; offending sizes: {bad}")


@dataclass(frozen=True)
class SequenceTiming:
    """One request's lifecycle, stamped on the engine's clock
    (``EngineBase._now``): arrival at ``submit``, the first slot grant,
    the host commit of its first and of its newest token.  A stamp the
    sequence never reached is None.  ``stall_s`` is the time it stood,
    holding a first token, behind the tick's prefill phases (other
    callers' prefills, and its own again after a preemption), on the same
    clock; None where it got no first token in this engine.  ``seq_id``
    is the key its ``engine.request`` span carries."""

    seq_id: int
    t_arrival: float
    t_admitted: Optional[float] = None
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    preemptions: int = 0
    stall_s: Optional[float] = None

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.t_admitted is None:
            return None
        return self.t_admitted - self.t_arrival

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.t_first is None else self.t_first - self.t_arrival

    @property
    def decode_s(self) -> Optional[float]:
        return None if self.t_first is None else self.t_last - self.t_first


@dataclass
class _Life:
    """The mutable form of ``SequenceTiming`` (the same fields in the
    same order, less the id): ONE record per sequence, handed from its
    ``_Pending`` to its ``_Active`` and back through a preemption's
    requeue, so the stamps survive every resume.  Its ``stall_s`` is
    the engine's running total of prefill-phase seconds
    (``EngineBase._stall_total``) as it stood at the first token;
    ``_settle_timing`` turns it into the seconds since."""

    t_arrival: float
    t_admitted: Optional[float] = None
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    preemptions: int = 0
    stall_s: Optional[float] = None

    def admitted(self, now: float) -> None:
        if self.t_admitted is None:
            self.t_admitted = now

    def committed(self, now: float) -> None:
        if self.t_first is None:
            self.t_first = now
        self.t_last = now


@dataclass
class SequenceResult:
    seq_id: int
    token_ids: List[int]
    text: str
    finish_reason: str          # "stop" | "eos" | "length" | "expired"
    prompt_tokens: int
    completion_tokens: int
    timing: Optional[SequenceTiming] = None


@dataclass
class _Active:
    seq_id: int
    slot: int
    prompt_tokens: int
    generated: List[int] = field(default_factory=list)
    max_new_tokens: int = 256
    stop_strings: Tuple[str, ...] = ()
    grammar: Optional[object] = None    # engine/constrain.py FSM (stateful)
    n_shared: int = 0   # leading block-table pages owned by the prefix cache
    # scheduling class (serve.backend.Priority; lower = more urgent):
    # orders admission and preemption-victim selection.  Deadlines live in
    # the engine's _deadlines registry, not on the sequence records.
    priority: int = 1
    life: Optional[_Life] = None        # always set by the engine


@dataclass
class _Pending:
    seq_id: int
    prompt_ids: List[int]
    max_new_tokens: int
    stop_strings: Tuple[str, ...]
    grammar: Optional[object] = None
    priority: int = 1
    life: Optional[_Life] = None        # always set by the engine


class EngineBase:
    """The continuous-batching engine's surface and host loop.

    ``paged.PagedInferenceEngine``, the one engine, implements the tick
    body (``_tick``), admission, retirement (``_retire``) and the pool's
    bookkeeping, including the per-slot rules this class calls and does
    not define (``_chunk_bound``, ``_spec_room_ok``, ``_drop_spill``,
    ``_expire_extra``, the resident-state edits of the overlapped loop).
    Everything the agent layer sees — submit/generate semantics, prompt
    clamping, finish reasons, stop-string trimming — lives here.
    """

    model_cfg: ModelConfig
    engine_cfg: EngineConfig
    tokenizer: Tokenizer
    # pipeline-parallel serving (pp_mesh=): admissions route through the
    # batched pipelined prefill, padded to _pp_m microbatch multiples
    _pp: bool = False
    _pp_m: Optional[int] = None
    # draft-model speculation (speculative.ModelDraft); None = n-gram drafts
    _draft = None
    # overlapped hot loop (engine_cfg.host_overlap; docs/performance.md).
    # _inflight: dispatched-but-uncommitted fast-path ticks, oldest first;
    # each entry is {"slots": [(slot, seq_id)...], "toks": device [B],
    # "admits": deferred first-token records}.  _admit_pending: sequences
    # activated this tick whose sampled first token has not crossed to
    # host yet.  _flushed_out: results produced by an out-of-tick flush
    # (cancel/snapshot/fault barrier), surfaced by the next _tick so
    # step() callers never lose them.  All three are re-bound to real
    # lists by the engine's constructor.
    _overlap: bool = False
    _overlap_lag: int = 2
    _inflight: Optional[List[dict]] = None
    _admit_pending: Optional[list] = None
    _flushed_out: Optional[list] = None
    # per-sequence absolute deadlines (seq_id -> time on ``_now``'s
    # clock), lazily created like ``_counts`` so engines without
    # deadlines pay one falsy check per tick.  ``clock``: injectable
    # time() source; None = the armed fault plan's VirtualClock when
    # present, else wall time — the same discipline as faults/ and
    # serve/api.py
    clock = None
    _deadlines: Optional[Dict[int, float]] = None
    # liveness heartbeat (cluster/health.py): ``heartbeat`` is the
    # monotonic tick serial every ``step`` bumps — probe-count liveness
    # stays deterministic under a frozen VirtualClock.  ``heartbeat_t``
    # is the clock stamp of the latest tick, taken only when a watchdog
    # registered this engine (``_hb_stamp``), keeping the unwatched hot
    # path to one falsy check.
    heartbeat: int = 0
    heartbeat_t: float = 0.0
    _hb_stamp: bool = False

    def _now(self) -> float:
        if self.clock is not None:
            return self.clock.time()
        if inject._ARMED is not None:
            return inject._ARMED.clock.time()
        return time.time()

    # -------------------------------------------------------- shared api

    def _clamp_prompt(self, prompt_ids: Sequence[int],
                      max_new_tokens: Optional[int]) -> Tuple[List[int], int]:
        """Fit prompt + generation into the per-sequence cache budget.

        First shrink max_new to what the cache can hold after the prompt;
        if the prompt alone overflows, keep its TAIL (the task statement
        sits at the end of RCA prompts) while reserving at least cap//4
        tokens of generation room.  (Long-context CP/ring-attention
        prefill lifts this limit later.)
        """
        max_new = (self.engine_cfg.max_new_tokens
                   if max_new_tokens is None else max_new_tokens)
        prompt_ids = list(prompt_ids)
        cap = self.engine_cfg.max_seq_len
        if len(prompt_ids) + max_new + 1 > cap:
            reserve = min(max_new, max(1, cap // 4))
            budget = cap - reserve - 1
            if len(prompt_ids) > budget:
                log.warning(
                    "truncating prompt %d -> %d tokens (cache cap %d)",
                    len(prompt_ids), budget, cap)
                had_bos = prompt_ids[0] == self.tokenizer.bos_id
                prompt_ids = prompt_ids[-budget:]
                if had_bos:   # keep BOS conditioning after tail-truncation
                    prompt_ids[0] = self.tokenizer.bos_id
            max_new = min(max_new, cap - len(prompt_ids) - 1)
        return prompt_ids, max_new

    @property
    def has_work(self) -> bool:
        return bool(self._active or self._pending)

    def submit(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: Optional[int] = None,
        stop_strings: Sequence[str] = (),
        grammar: Optional[object] = None,
        priority: int = 1,
        deadline_s: Optional[float] = None,
    ) -> int:
        """Queue a sequence; returns its seq_id.  Non-blocking.

        ``grammar``: optional constrain.py FSM owned by this sequence; the
        engine consults it every tick (forced tokens / logit masks).
        ``priority``: scheduling class (serve.backend.Priority; lower =
        more urgent) ordering admission and victim selection.
        ``deadline_s``: seconds from now on the injectable clock; past it
        the tick loop reaps the sequence (finish_reason "expired", pages
        freed the same tick — never held until the client polls)."""
        seq_id = next(self._seq_counter)
        prompt_ids, max_new = self._clamp_prompt(prompt_ids, max_new_tokens)
        self._register(seq_id, prompt_ids)
        self._enqueue(
            _Pending(seq_id, prompt_ids, max_new, tuple(stop_strings),
                     grammar, priority=int(priority),
                     life=_Life(self._now())))
        if deadline_s is not None:
            self._deadline_set(seq_id, self._now() + float(deadline_s))
        return seq_id

    def _deadline_set(self, seq_id: int, deadline: float) -> None:
        if self._deadlines is None:
            self._deadlines = {}
        self._deadlines[seq_id] = float(deadline)

    def _enqueue(self, req: "_Pending", front: bool = False) -> None:
        """Deterministic priority insert into the pending queue: stable
        FIFO within a class (submission order is the tiebreak), lower
        ``priority`` ints ahead.  ``front=True`` (preemption requeue)
        puts the request ahead of its OWN class — a preempted sequence
        resumes before un-admitted peers, preserving the engine's
        always-makes-progress invariant.  All-NORMAL traffic degenerates
        to exactly the old append / insert(0) behavior."""
        pri = req.priority
        for i, r in enumerate(self._pending):
            if (r.priority > pri) if not front else (r.priority >= pri):
                self._pending.insert(i, req)
                return
        self._pending.append(req)

    def _reap_deadlines(self) -> List["SequenceResult"]:
        """Retire every sequence whose deadline has passed — called at
        the top of each tick, BEFORE the flush drain, so the expired
        results surface from the same ``step()``.  Pages/slots free NOW
        (the eager half of the serve-layer timeout: an expired run must
        not hold pool pages until its client polls).  Disarmed path cost:
        one falsy-dict check."""
        if not self._deadlines:
            return []
        now = self._now()
        expired = [sid for sid, dl in self._deadlines.items() if now >= dl]
        if not expired:
            return []
        self._overlap_barrier()   # commit in-flight tokens before retiring
        out: List[SequenceResult] = []
        for seq_id in expired:
            self._deadlines.pop(seq_id, None)
            done = False
            for i, req in enumerate(self._pending):
                if req.seq_id == seq_id:
                    del self._pending[i]
                    out.append(self._expired_result(seq_id, req))
                    self._drop_spill(seq_id)
                    self._prompts.pop(seq_id, None)
                    resumed = getattr(self, "_resumed", None)
                    if resumed is not None:
                        resumed.pop(seq_id, None)
                    done = True
                    break
            if not done:
                for slot, st in list(self._active.items()):
                    if st.seq_id == seq_id:
                        out.append(self._retire(slot, "expired"))
                        done = True
                        break
            if not done:
                res = self._expire_extra(seq_id)
                if res is not None:
                    out.append(res)
                    done = True
            if done:
                self._count("engine.deadline_expirations")
        return out

    def _expired_result(self, seq_id: int,
                        req: "_Pending") -> "SequenceResult":
        """Terminal result for a sequence that expired while QUEUED: its
        record is whatever it had generated before preemption (possibly
        nothing) — mirroring what snapshot_sequences exports for pending
        entries."""
        resumed = getattr(self, "_resumed", None) or {}
        gen = list(resumed.get(seq_id, ()))
        prompt = list(self._prompts.get(seq_id, req.prompt_ids))
        return SequenceResult(
            seq_id=seq_id, token_ids=list(gen),
            text=self._final_text(gen, "expired", req.stop_strings),
            finish_reason="expired", prompt_tokens=len(prompt),
            completion_tokens=len(gen),
            timing=self._settle_timing(req, len(gen)))

    def _register(self, seq_id: int, prompt_ids: List[int]) -> None:
        """Keep a submitted (or restored) sequence's ORIGINAL prompt:
        results report against it, and drafts, snapshots and resumes
        rebuild their context from it."""
        self._prompts[seq_id] = list(prompt_ids)

    def cancel_seq(self, seq_id: int) -> bool:
        """Abort a sequence NOW: a queued request leaves the pending list,
        an active one retires its slot immediately (its pages are freed
        through the normal ``_retire`` path, so an abandoned run cannot
        leak allocator blocks).  No result is produced — callers
        that already dropped the handle simply never see one.  Returns
        whether the sequence was still live."""
        self._overlap_barrier()   # commit in-flight tokens before retiring
        for i, req in enumerate(self._pending):
            if req.seq_id == seq_id:
                del self._pending[i]
                self._drop_spill(seq_id)
                if self._deadlines:
                    self._deadlines.pop(seq_id, None)
                self._prompts.pop(seq_id, None)
                resumed = getattr(self, "_resumed", None)
                if resumed is not None:
                    resumed.pop(seq_id, None)
                return True
        for slot, st in list(self._active.items()):
            if st.seq_id == seq_id:
                self._retire(slot, "cancelled")
                return True
        return False

    # ------------------------------------------------- snapshot / restore

    def snapshot_sequences(self) -> Dict[str, object]:
        """Export every live sequence's durable state for crash recovery
        (serve/recover.py, docs/durability.md).

        Raw KV is deliberately NOT dumped: pages are device memory laid
        out per-engine, worthless across a restart.  What IS durable —
        original prompt ids, every generated token (pre-preemption prefix
        included), remaining budget, stop strings, and the engine RNG key
        — is exactly what ``restore_sequences`` needs to re-admit the
        sequence through a normal prefill; with the prefix cache enabled
        the re-prefill of already-seen tokens is a mostly-HIT path.

        Grammar FSM state is exported as a bool marker only (compiled
        FSMs are stateful host objects); restore rebuilds it by advancing
        a freshly compiled FSM over the generated tokens.

        Ordering is the scheduler's own priority: active sequences (in
        admission order) first, then the pending queue front-to-back —
        restoring preserves relative progress order deterministically.
        """
        # the overlapped hot loop may hold 1-2 dispatched-but-uncommitted
        # tokens per slot; commit them first so st.generated is complete
        # (the single invalidation point durability rides through)
        self._overlap_barrier()
        resumed = getattr(self, "_resumed", None) or {}
        seqs = []
        for st in sorted(self._active.values(), key=lambda s: s.seq_id):
            gen = list(resumed.get(st.seq_id, [])) + list(st.generated)
            seqs.append({
                "seq_id": st.seq_id,
                "prompt_ids": list(self._prompts.get(st.seq_id, [])),
                "generated": gen,
                # an _Active at its budget retires within the same tick,
                # so between ticks remaining >= 1 always holds; the max()
                # mirrors _preempt_slot's defensive clamp
                "remaining_new_tokens": max(
                    1, st.max_new_tokens - len(st.generated)),
                "stop_strings": list(st.stop_strings),
                "grammar": st.grammar is not None,
                "priority": st.priority,
                "deadline": (self._deadlines or {}).get(st.seq_id),
            })
        for req in self._pending:
            gen = list(resumed.get(req.seq_id, ()))
            # a preempted request's prompt_ids already carry its generated
            # prefix; recover the ORIGINAL prompt from _prompts.  A
            # KV-spilled sequence sits in this queue too,
            # so it snapshots as exactly its token record — the spill
            # buffers themselves are process-local device-layout memory
            # and are never serialized
            prompt = list(self._prompts.get(req.seq_id, req.prompt_ids))
            seqs.append({
                "seq_id": req.seq_id,
                "prompt_ids": prompt,
                "generated": gen,
                "remaining_new_tokens": req.max_new_tokens,
                "stop_strings": list(req.stop_strings),
                "grammar": req.grammar is not None,
                "priority": req.priority,
                "deadline": (self._deadlines or {}).get(req.seq_id),
            })
        key = jax.device_get(self._key)
        return {"rng_key": [int(x) for x in key], "sequences": seqs}

    def restore_sequences(self, snap: Dict[str, object],
                          grammars: Optional[Dict[int, object]] = None
                          ) -> List[int]:
        """Re-admit sequences exported by ``snapshot_sequences`` — into
        this engine or a fresh same-model one.  Each sequence is queued
        for a normal prefill of prompt + generated-so-far (the
        preemption/resume path, ``_preempt_slot``), so the engine's
        greedy-parity guarantees carry over: a restored sequence finishes
        with exactly the tokens a never-interrupted run produces.

        ``grammars``: freshly compiled FSMs keyed by seq_id for sequences
        snapshotted with ``grammar: true``; each is advanced over the
        generated tokens so its state matches the resume point.  Missing
        a required FSM raises (loud exclusion) rather than silently
        dropping the constraint.  Returns the restored seq_ids.
        """
        self._overlap_barrier()
        resumed = getattr(self, "_resumed", None)
        if resumed is None:
            raise ValueError(
                f"{type(self).__name__} has no resume bookkeeping "
                f"(_resumed); restore_sequences requires an engine built "
                f"with preemption/resume support")
        cap = self.engine_cfg.max_seq_len
        restored: List[int] = []
        max_seen = -1
        for s in snap["sequences"]:
            seq_id = int(s["seq_id"])
            if (seq_id in self._prompts
                    or any(r.seq_id == seq_id for r in self._pending)):
                raise ValueError(
                    f"restore collision: seq {seq_id} is already live in "
                    f"this engine")
            prompt = [int(t) for t in s["prompt_ids"]]
            gen = [int(t) for t in s["generated"]]
            remaining = int(s["remaining_new_tokens"])
            room = cap - len(prompt) - len(gen) - 1
            if room < 1:
                raise ValueError(
                    f"seq {seq_id} needs {len(prompt) + len(gen) + 2} "
                    f"cache positions but this engine caps at {cap}; "
                    f"restore into an engine with max_seq_len >= the "
                    f"snapshotting engine's")
            remaining = min(remaining, room)
            g = (grammars or {}).get(seq_id)
            if s.get("grammar") and g is None:
                raise ValueError(
                    f"seq {seq_id} was grammar-constrained; pass a "
                    f"freshly compiled FSM via grammars={{{seq_id}: fsm}} "
                    f"(FSM state is rebuilt by advancing over the "
                    f"generated tokens, never serialized)")
            if g is not None:
                for t in gen:
                    g.advance(t)
            self._register(seq_id, prompt)
            if gen:
                resumed[seq_id] = list(gen)
            self._enqueue(_Pending(
                seq_id, prompt + gen, remaining,
                tuple(s["stop_strings"]), g,
                priority=int(s.get("priority", 1)),
                life=_Life(self._now())))
            if s.get("deadline") is not None:
                self._deadline_set(seq_id, float(s["deadline"]))
            restored.append(seq_id)
            max_seen = max(max_seen, seq_id)
        # later submits must not reuse a restored id
        nxt = next(self._seq_counter)
        self._seq_counter = itertools.count(max(nxt, max_seen + 1))
        key = snap.get("rng_key")
        if key is not None:
            self._key = jnp.asarray(key, dtype=jnp.uint32)
        return restored

    # ------------------------------------------- per-run export / adopt

    def _export_entry(self, req: "_Pending",
                      resumed: Dict[int, List[int]]) -> Dict[str, object]:
        """One pending sequence as a ``snapshot_sequences``-shaped entry
        (the handoff frame's durable half, cluster/disagg.py)."""
        return {
            "seq_id": req.seq_id,
            "prompt_ids": list(self._prompts.get(req.seq_id,
                                                 req.prompt_ids)),
            "generated": list(resumed.get(req.seq_id, ())),
            "remaining_new_tokens": req.max_new_tokens,
            "stop_strings": list(req.stop_strings),
            "grammar": req.grammar is not None,
            "priority": req.priority,
            "deadline": (self._deadlines or {}).get(req.seq_id),
        }

    def adopt_run(self, entry: Dict[str, object], kv=None,
                  grammar=None) -> int:
        """Per-run ADOPT half of the disaggregated handoff: re-admit ONE
        exported entry through ``restore_sequences``, which re-prefills
        byte-identically (the engine stages the KV page record on top, so
        that the resume restores instead).  Returns the seq_id adopted."""
        sid = int(entry["seq_id"])
        self.restore_sequences(
            {"rng_key": None, "sequences": [entry]},
            grammars={sid: grammar} if grammar is not None else None)
        return sid

    # -------------------------------------------------- fault injection

    FAULT_SITE = inject.SITE_ENGINE_TICK

    def _tick_fault(self) -> None:
        """Apply this tick's scheduled fault (faults/plan.py).  Only ever
        called behind ``inject._ARMED is not None`` at the top of
        ``step()`` — the disarmed hot path pays exactly that one check."""
        plan = inject._ARMED
        if plan is None:
            return
        fault = plan.poll(self.FAULT_SITE)
        if fault is not None:
            # fault kinds that preempt/crash slots must see committed
            # host state, not a 1-2 token stale mirror
            self._overlap_barrier()
            self._apply_tick_fault(fault, plan)

    def _apply_tick_fault(self, fault, plan) -> None:
        """Host-stall tick faults (virtual-clock delay).  The engine
        extends this with allocator exhaustion, forced preemption waves
        and device KV loss."""
        if fault.kind in ("stall", "slow"):
            plan.clock.sleep(fault.delay_s or 0.05)
        else:
            log.warning("tick fault %r not applicable to engine ticks",
                        fault.kind)

    # ------------------------------------------------ grammar application

    def _grammar_first_token(self, grammar, logits, sampled: int,
                             remaining: int) -> int:
        """Constrain the first post-prefill token.  Resampling goes through
        the same device sampler as every later token (identical
        temperature/top-k/top-p semantics); admission is per-sequence, so
        the extra [1, V] sample costs one small dispatch once per
        sequence."""
        c = grammar.constraint(remaining)
        if c.force is not None:
            return c.force
        if c.allow is not None and not bool(c.allow[sampled]):
            self._key, sub = jax.random.split(self._key)
            masked = self._sample_masked(
                logits, sub, self.sampling, jnp.asarray(c.allow[None]))
            return int(self._fetch(masked)[0][0])
        return sampled

    def _budget_remaining(self, st: _Active) -> int:
        """Tokens this sequence can still emit: min of its max_new budget
        and the cache capacity left (both can trigger 'length').  Pure host
        arithmetic — prompt_tokens + generated tracks the device length
        (one behind mid-tick, which only closes the grammar one token
        early)."""
        cache_room = (self.engine_cfg.max_seq_len
                      - (st.prompt_tokens + len(st.generated)) - 1)
        return min(st.max_new_tokens - len(st.generated), cache_room)

    def _tick_constraints(self, active_slots, n_slots: int, vocab: int):
        """Collect per-slot constraints for this tick.  Returns
        (forced {slot: token}, allow [B, V] bool or None)."""
        forced = {}
        allow = None
        constrained = [s for s in active_slots
                       if self._active[s].grammar is not None]
        if not constrained:
            return forced, allow
        with profiling.annotate("engine.grammar_mask"):
            for slot in constrained:
                st = self._active[slot]
                c = st.grammar.constraint(self._budget_remaining(st))
                if c.force is not None:
                    forced[slot] = c.force
                elif c.allow is not None:
                    if allow is None:
                        allow = np.ones((n_slots, vocab), bool)
                    allow[slot] = c.allow
        return forced, allow

    # -------------------------------------------------- tick + observability

    # per-engine mirror of the engine.* METRICS counters (lazily created):
    # the tick timeline reads THIS, not the process-global METRICS, so a
    # traced run's gauges are a pure function of the engine's own activity
    # even when METRICS carries other engines'/tests' history
    _counts: Optional[Dict[str, float]] = None

    # cluster attribution (cluster/replica.py): the replica id this engine
    # serves under, None outside a cluster.  When set, engine.tick spans
    # carry a ``replica`` arg and TickSample.engine_id routes the Chrome
    # counter tracks onto a per-replica tid — attribution rides existing
    # span names, so the SITES registry stays closed.
    obs_replica: Optional[int] = None
    # router-written gauges (cluster/router.py writes queue_depth /
    # occupancy before pumping this replica); mirrored into TickSample so
    # the router's view rides the same per-tick recorder as pool pressure
    _cluster_gauges: Optional[Dict[str, float]] = None

    def _count(self, name: str, value: float = 1.0) -> None:
        """Increment a counter in METRICS and this engine's private
        mirror (both cheap; the mirror is a plain dict add)."""
        METRICS.inc(name, value)
        c = self._counts
        if c is None:
            c = self._counts = {}
        c[name] = c.get(name, 0.0) + value

    def _count_decode(self, steps: int) -> None:
        """One decode dispatch of ``steps`` model steps (a scan's chunk,
        1 for the stepwise and overlapped programs, the verified length
        under speculation): ``engine.decode_step.count`` counts
        dispatches, this counts the steps they ran."""
        self._count("engine.decode_steps", steps)

    def _settle_timing(self, st: Union[_Active, _Pending],
                       tokens: int) -> SequenceTiming:
        """Close a retiring sequence's lifecycle (``st`` active, or
        queued when its deadline passed): the frozen record for
        its ``SequenceResult``, the three durations into METRICS
        (``engine.queue_wait`` / ``engine.ttft`` / ``engine.tpot``, read
        as ``.total_s`` / ``.count``) and, under an active tracer, one
        ``engine.request`` span from arrival to the newest token."""
        timing = SequenceTiming(st.seq_id, *dataclasses.astuple(st.life))
        if timing.stall_s is not None:
            timing = dataclasses.replace(
                timing, stall_s=self._stall_total() - timing.stall_s)
        if timing.t_admitted is not None:
            METRICS.observe("engine.queue_wait", timing.queue_wait_s)
        if timing.t_first is None:
            return timing
        METRICS.observe("engine.ttft", timing.ttft_s)
        if tokens >= 2:
            METRICS.observe("engine.tpot", timing.decode_s / (tokens - 1))
        tr = obs_trace._ACTIVE
        if tr is not None:
            tr.add_span(
                "engine.request", timing.t_arrival, timing.t_last,
                cat="engine",
                args={"seq": st.seq_id,
                      "queue_wait_s": timing.queue_wait_s,
                      "prefill_s": timing.t_first - timing.t_admitted,
                      "decode_s": timing.decode_s,
                      "stall_s": timing.stall_s,
                      "tokens": tokens,
                      "preemptions": timing.preemptions})
        return timing

    # the stall a live sequence suffers from the tick's prefill phases
    # (``_prefill_phases``): a running total of their seconds on
    # ``_now``'s clock, and the start of the one that is open
    _stall_s: float = 0.0
    _stall_t0: Optional[float] = None

    def _stall_total(self) -> float:
        """Seconds this engine's ticks have spent in their prefill
        phases, up to this instant where one is open.  A sequence's
        ``stall_s`` is the difference between its first token and its
        settling, so it costs nothing per tick."""
        if self._stall_t0 is not None:
            now = self._now()
            self._stall_s += now - self._stall_t0
            self._stall_t0 = now
        return self._stall_s

    @contextlib.contextmanager
    def _prefill_phases(self):
        """Around a tick's prefill phases (chunks, admission, the first
        tokens' fetch and commit): their seconds times the sequences
        that hold a first token, and so wait for the decode behind them,
        go to ``engine.prefill_stall_seq_s`` (over
        ``engine.decode_tokens``: the stall a decoded token carries);
        present at 0 where nobody waited."""
        live = sum(1 for st in self._active.values()
                   if st.life.t_first is not None)
        before = self._stall_s
        self._stall_t0 = self._now()
        try:
            yield
        finally:
            seconds = self._stall_total() - before
            self._stall_t0 = None
            self._count("engine.prefill_stall_seq_s", live * seconds)

    # ---------------------------------------- overlapped hot loop (shared)
    #
    # docs/performance.md is the design note.  Invariants enforced here:
    #  - host commit order per sequence is exactly the plain engine's
    #    (admission first token, then decode tokens in dispatch order);
    #  - _inflight entries only exist across fast-path ticks; every other
    #    path (grammar, speculation, chunked scan, cancel, snapshot,
    #    restore, faults) flushes FIRST, so it observes committed state;
    #  - a slot retired/preempted while its tokens were in flight simply
    #    drops them at flush (the seq_id guard below) — greedy re-prefill
    #    regenerates identical tokens, so parity is preserved.

    def _fetch(self, *arrays) -> Tuple[np.ndarray, ...]:
        """ONE coalesced device->host sync: start async copies for every
        device array, then materialize all of them.  Counted as a single
        ``engine.d2h_syncs`` when any input actually lives on device —
        the counter measures sync POINTS (each is one blocking round
        trip to the device whatever the payload count), not arrays
        moved — and timed as ONE ``engine.fetch`` span then; host arrays
        pass through with neither."""
        if all(isinstance(a, np.ndarray) for a in arrays):
            return tuple(host_np(a) for a in arrays)
        self._count("engine.d2h_syncs")
        # the time the host is blocked on the chip (JAX's own
        # ``np.asarray(jax.Array)`` trace event nests inside)
        with profiling.annotate("engine.fetch"):
            for a in arrays:
                start = getattr(a, "copy_to_host_async", None)
                if start is not None:
                    start()
            return tuple(host_np(a) for a in arrays)

    def _overlap_fast(self) -> bool:
        """Whether THIS tick may dispatch without waiting to commit (the
        one-tick-lagged fast path).  Chunked-scan engines amortize host
        work in-scan already; speculation and live/queued grammar slots
        need host tokens (drafts, FSM masks) before the next dispatch, so
        they take the flush-first synchronous path — per the tentpole
        contract, grammar forces sync per-batch composition, never by
        disabling overlap globally."""
        if not self._overlap:
            return False
        cfg = self.engine_cfg
        if cfg.decode_chunk > 1 or cfg.speculative_k > 0:
            return False
        if any(st.grammar is not None for st in self._active.values()):
            return False
        if any(r.grammar is not None for r in self._pending):
            return False
        return True

    def _defer_first(self, st: _Active, first_dev, idx: int) -> None:
        """Queue an admitted sequence's on-device first token; the host
        value lands at the next drain/flush (one coalesced fetch for ALL
        admissions instead of one blocking fetch per admission group)."""
        self._admit_pending.append((st, first_dev, idx))

    def _take_admit_pending(self) -> list:
        pend, self._admit_pending = self._admit_pending, []
        return pend

    def _commit_first(self, st: _Active, token: int,
                      update_dev: bool = True) -> Optional[SequenceResult]:
        """Host-side commit of an admission's first token (the deferred
        half of _activate).  ``update_dev=False`` at a lagged flush: the
        device token array has advanced past the first token, so only
        host mirrors may move.  The liveness guard drops the token when
        the slot was preempted before the fetch landed: the requeued
        prompt then re-prefills and greedily re-samples the SAME token,
        so nothing is lost (docs/performance.md)."""
        live = self._active.get(st.slot) is st
        if not live:
            return None
        st.generated.append(token)
        if st.life.t_first is None:
            st.life.stall_s = self._stall_total()
        st.life.committed(self._now())
        self._note_first_token(st.slot, token, update_dev=update_dev)
        reason = self._finish_reason(st, token, st.prompt_tokens)
        if reason is not None:
            return self._retire(st.slot, reason)
        return None

    def _drain_admission_commits(self) -> List[SequenceResult]:
        """Fetch every deferred admission first token in ONE sync and
        commit them in admission order."""
        pend = self._take_admit_pending()
        if not pend:
            return []
        uniq: Dict[int, int] = {}
        order = []
        for _, a, _ in pend:
            if id(a) not in uniq:
                uniq[id(a)] = len(order)
                order.append(a)
        hosts = self._fetch(*order)
        out: List[SequenceResult] = []
        with profiling.annotate("engine.commit"):
            for st, a, i in pend:
                r = self._commit_first(st, int(hosts[uniq[id(a)]][i]))
                if r is not None:
                    out.append(r)
        return out

    def _overlap_flush(self) -> List[SequenceResult]:
        """Commit every in-flight fast-path tick: one coalesced fetch for
        all entries' token vectors + deferred admission firsts, then the
        plain commit loop per entry in dispatch order.  Safe to call any
        time; a no-op when nothing is in flight."""
        entries, self._inflight = self._inflight, []
        finished: List[SequenceResult] = []
        if entries:
            uniq: Dict[int, int] = {}
            order = []
            for e in entries:
                for a in [e["toks"]] + [rec[1] for rec in e["admits"]]:
                    if id(a) not in uniq:
                        uniq[id(a)] = len(order)
                        order.append(a)
            hosts = self._fetch(*order)
            for e in entries:
                self._note_flush_entry(e)
                for st, a, i in e["admits"]:
                    r = self._commit_first(st, int(hosts[uniq[id(a)]][i]),
                                           update_dev=False)
                    if r is not None:
                        finished.append(r)
                toks_host = hosts[uniq[id(e["toks"])]]
                # only slots still owned by the sequence that was active
                # at dispatch time commit; retired/preempted slots' tokens
                # are dropped (see class invariants above)
                slots = [s for s, sid in e["slots"]
                         if s in self._active
                         and self._active[s].seq_id == sid]
                finished.extend(self._commit_scanned(
                    slots, toks_host[None, :], 1,
                    self._overlap_post_commit))
        finished.extend(self._drain_admission_commits())
        return finished

    def _overlap_barrier(self) -> None:
        """Flush outside a tick (cancel/snapshot/restore/fault).  Results
        finished by the flush are stashed and surfaced by the NEXT tick,
        so step() callers never lose them."""
        if self._inflight or self._admit_pending:
            out = self._overlap_flush()
            if out:
                self._flushed_out.extend(out)
            self._invalidate_device_state()

    def step(self) -> List[SequenceResult]:
        """One engine tick (the public pump surface): apply this tick's
        scheduled fault, run the tick body (``_tick``) inside
        the ``engine.tick`` span (``profiling.annotate``: profiler
        annotation, always-on timer, obs span) and, only when a tracer
        is active, record a TickSample of the scheduler/pool gauges."""
        self.heartbeat += 1                    # liveness tick serial
        if self._hb_stamp:                     # unwatched cost: this check
            self.heartbeat_t = self._now()
        if inject._ARMED is not None:          # disarmed cost: this check
            self._tick_fault()
        targs = ({} if self.obs_replica is None
                 else {"replica": self.obs_replica})
        with profiling.annotate("engine.tick", **targs):
            finished = self._tick()
        tr = obs_trace._ACTIVE
        if tr is not None:                     # untraced cost: this check
            self._record_tick(tr)
        return finished

    def _tick(self) -> List[SequenceResult]:
        raise NotImplementedError

    def _tick_gauges(self) -> Dict[str, Optional[int]]:
        """Scheduler gauges for the tick timeline; the engine adds pool
        pressure (free/evictable pages)."""
        crit = norm = batch = 0
        for r in self._pending:
            if r.priority <= 0:
                crit += 1
            elif r.priority == 1:
                norm += 1
            else:
                batch += 1
        return {"running": len(self._active),
                "queued": len(self._pending),
                "queued_critical": crit, "queued_normal": norm,
                "queued_batch": batch,
                "free_pages": None, "evictable_pages": None}

    def _record_tick(self, tr) -> None:
        from k8s_llm_rca_tpu.obs.timeline import TickSample

        g = self._tick_gauges()
        c = self._counts or {}
        tl = tr.timeline
        tl.record(TickSample(
            tick=tl.total, ts=tr.now(),
            running=g["running"], queued=g["queued"],
            free_pages=g["free_pages"],
            evictable_pages=g["evictable_pages"],
            prefill_tokens=c.get("engine.prefill_tokens", 0.0),
            decode_tokens=c.get("engine.decode_tokens", 0.0),
            prefix_hit_tokens=c.get("engine.prefix_hit_tokens", 0.0),
            preemptions=c.get("engine.preemptions", 0.0),
            admission_rejections=c.get("engine.admission_rejections",
                                       0.0),
            h2d_uploads=c.get("engine.h2d_uploads", 0.0),
            d2h_syncs=c.get("engine.d2h_syncs", 0.0),
            dispatches=c.get("engine.dispatches", 0.0),
            prefill_chunks=c.get("engine.prefill_chunks", 0.0),
            spilled_pages=c.get("engine.spilled_pages", 0.0),
            restored_pages=c.get("engine.restored_pages", 0.0),
            deadline_expirations=c.get("engine.deadline_expirations", 0.0),
            prefix_hits_l0=c.get("engine.prefix_hits_l0", 0.0),
            prefix_hits_l1=c.get("engine.prefix_hits_l1", 0.0),
            prefix_hits_l2=c.get("engine.prefix_hits_l2", 0.0),
            prefix_demotions=c.get("engine.prefix_demotions", 0.0),
            prefix_promoted_pages=c.get("engine.prefix_promoted_pages",
                                        0.0),
            prefix_bytes_restored=c.get("engine.prefix_bytes_restored",
                                        0.0),
            prefix_store_misses_remote=c.get(
                "engine.prefix_store_misses_remote", 0.0),
            prefix_watermark_demotions=c.get(
                "engine.prefix_watermark_demotions", 0.0),
            idle_ticks=c.get("engine.idle_ticks", 0.0),
            queued_critical=g.get("queued_critical", 0),
            queued_normal=g.get("queued_normal", 0),
            queued_batch=g.get("queued_batch", 0),
            engine_id=self.obs_replica or 0,
            cluster_queue_depth=(self._cluster_gauges or {}).get(
                "queue_depth", 0.0),
            cluster_occupancy=(self._cluster_gauges or {}).get(
                "occupancy", 0.0)))

    # ---------------------------------------- chunked scan tick (shared)

    def _dfa_device_tables(self, tables):
        """Upload one grammar's DFA tables once; reuse across scans."""
        dev_cache = getattr(self, "_dfa_dev", None)
        if dev_cache is None:
            dev_cache = self._dfa_dev = {}
        dev = dev_cache.get(id(tables))
        if dev is None:
            dev = (jnp.asarray(tables.allow), jnp.asarray(tables.token_next),
                   jnp.asarray(tables.dist), jnp.asarray(tables.close_tok),
                   jnp.asarray(tables.complete), tables)
            # bound device-table residency (the tuple keeps `tables` alive,
            # so id() cannot be reused while an entry lives)
            while len(dev_cache) >= 4:
                dev_cache.pop(next(iter(dev_cache)))
            dev_cache[id(tables)] = dev
        return dev

    def _dfa_scan_vectors(self, tables):
        """[B] DFA state + remaining-budget vectors for a scan batch:
        grammar slots carry their state, free slots the FREE row."""
        b = self.engine_cfg.max_batch
        states = np.full((b,), tables.free_state, np.int32)
        remaining = np.full((b,), np.int32(1 << 30), np.int32)
        for slot, st in self._active.items():
            if st.grammar is not None:
                states[slot] = st.grammar.state
                remaining[slot] = self._budget_remaining(st)
        return states, remaining

    _DFA_FUSE_BUCKET = 1024   # fused state-count rounding (compile reuse)

    def _scan_dfa_setup(self):
        """Fused DFA tables + per-slot state/budget vectors for this tick.

        DISTINCT compiled grammars fuse into ONE scan state space: each
        table's states are relabeled by a fixed offset (token_next entries
        are in-table state ids, so adding the offset keeps every
        transition inside its own region), the [S_i, V] tables stack along
        the state axis, and each slot's scan state carries its table's
        offset.  A mixed batch — e.g. planner, Cypher-skeleton and
        reporter schemas in flight at once from different sweep workers —
        then decodes inside one jitted scan instead of degrading to
        per-token host ticks.  The stacked size rounds up to
        ``_DFA_FUSE_BUCKET`` with dead rows (never indexed) so distinct
        grammar combinations share scan compilations.

        Returns None when no grammar slot is active, else
        ((allow, next, dist, close, complete) device arrays,
        states [B] int32, remaining [B] int32)."""
        tabs, seen = [], set()
        for st in self._active.values():
            if st.grammar is not None:
                t = st.grammar.tables
                if id(t) not in seen:
                    seen.add(id(t))
                    tabs.append(t)
        if not tabs:
            return None
        with profiling.annotate("engine.grammar_mask"):
            return self._fuse_dfa_tables(tabs)

    def _fuse_dfa_tables(self, tabs):
        """``_scan_dfa_setup``'s work once a grammar slot is known to be
        active: ``tabs`` are the distinct DFA table sets in flight."""
        tabs.sort(key=id)
        key = tuple(id(t) for t in tabs)
        cache = getattr(self, "_dfa_fused", None)
        if cache is None:
            cache = self._dfa_fused = {}
        entry = cache.get(key)
        if entry is not None:
            cache[key] = cache.pop(key)   # LRU refresh: the hot combo must
            # survive one-shot per-incident skeleton combos churning by
        if entry is None:
            offsets, off = {}, 0
            allow, nxt, dist, close, complete = [], [], [], [], []
            for t in tabs:
                offsets[id(t)] = off
                allow.append(t.allow)
                nxt.append(t.token_next.astype(np.int32) + np.int32(off))
                dist.append(t.dist)
                close.append(t.close_tok)
                complete.append(t.complete)
                off += t.n_states
            v = allow[0].shape[1]
            pad = -(-off // self._DFA_FUSE_BUCKET) * self._DFA_FUSE_BUCKET \
                - off
            if pad:
                allow.append(np.zeros((pad, v), bool))
                nxt.append(np.zeros((pad, v), np.int32))
                dist.append(np.zeros((pad,), np.int32))
                close.append(np.zeros((pad,), np.int32))
                complete.append(np.zeros((pad,), bool))
            entry = ((jnp.asarray(np.concatenate(allow)),
                      jnp.asarray(np.concatenate(nxt)),
                      jnp.asarray(np.concatenate(dist)),
                      jnp.asarray(np.concatenate(close)),
                      jnp.asarray(np.concatenate(complete))),
                     offsets, tabs[0].free_state, tuple(tabs))
            # bound device residency; the kept tabs tuple pins id()s
            while len(cache) >= 4:
                cache.pop(next(iter(cache)))
            cache[key] = entry
        dev, offsets, free, _pin = entry
        b = self.engine_cfg.max_batch
        states = np.full((b,), free, np.int32)
        remaining = np.full((b,), np.int32(1 << 30), np.int32)
        for slot, st in self._active.items():
            if st.grammar is not None:
                states[slot] = (offsets[id(st.grammar.tables)]
                                + st.grammar.state)
                remaining[slot] = self._budget_remaining(st)
        return dev, states, remaining

    def _grammar_post_commit(self, slot: int, token: int) -> None:
        """Keep host grammar FSMs in lockstep with scan-emitted tokens."""
        st = self._active.get(slot)
        if st is not None and st.grammar is not None:
            st.grammar.advance(token)

    def _scan_chunk(self) -> int:
        """Device decode steps to run in ONE dispatch this tick.

        The scan path amortizes per-dispatch host latency over many
        steps; only an interpreted (non-DFA) grammar forces stepwise
        ticks (it needs per-token host masks).  Mixed DFA grammars fuse
        into one scan state space (_scan_dfa_setup).  Queued admissions
        force stepwise ticks only when ``prompt_admission`` is set:
        admission happens at the next step() either way, and the knob
        trades one dispatch per token for up to decode_chunk-1 steps of
        TTFT.  The chunk is the largest power of two <=
        decode_chunk that fits every slot's CACHE headroom and its
        allocated pages (``_chunk_bound``); per-slot token budgets
        deliberately do NOT bound it (DFA slots force-close in-scan,
        plain slots' over-decoded tokens are never committed — see the inline comment), and stop strings/EOS
        inside a chunk are trimmed after the fact, same text semantics
        as the stepwise path."""
        # which bound set the chunk: ONE ``engine.scan_limit.<reason>``
        # increment per decode tick ("full" = nothing cut it)
        limit = full = self.engine_cfg.decode_chunk
        reason = "full"
        if limit <= 1:
            self._count("engine.scan_limit.full")
            return 1
        if self.engine_cfg.prompt_admission and self._pending:
            # admit promptly: a retirement frees a slot within one step
            # instead of up to decode_chunk-1 steps
            self._count("engine.scan_limit.admission")
            return 1
        for slot, st in self._active.items():
            if st.grammar is not None:
                t = getattr(st.grammar, "tables", None)
                if t is None:
                    # interpreted FSM: per-token host work
                    self._count("engine.scan_limit.grammar")
                    return 1
            # bound by CACHE headroom (never write past max_seq_len), NOT
            # by the slot's token budget: DFA slots enforce budgets
            # in-scan (the `remaining` vector force-closes), and a plain
            # slot's tokens past its budget are simply never committed
            # (_commit_scanned stops at the finish reason).  Min-ing the
            # budget here let any near-finished straggler collapse the
            # whole batch's chunk to 1 — with B staggered short-budget
            # runs, SOME slot is almost always in its tail, so the scan
            # degenerated to per-token dispatches exactly when the batch
            # was busiest (observed on the shared-engine sweep).
            headroom = max(1, self.engine_cfg.max_seq_len - (
                st.prompt_tokens + len(st.generated)))
            if headroom < limit:
                limit, reason = headroom, "headroom"
            bound = self._chunk_bound(slot)
            if bound < limit:
                limit, reason = bound, "pages"
        chunk = 1
        while chunk * 2 <= limit:
            chunk *= 2
        # a bound that still leaves the largest power of two under
        # decode_chunk cut nothing
        self._count("engine.scan_limit."
                    + ("full" if chunk * 2 > full else reason))
        return chunk

    def _commit_scanned(self, active_slots, toks_host, chunk: int,
                        post_commit=None) -> List[SequenceResult]:
        """Shared commit loop for scanned tokens: append, per-token finish
        check at the stepwise-equivalent device length (prompt +
        len(generated) - 1), metrics, mid-chunk retirement.  ``post_commit``
        lets the tick update its host-side length/token arrays per
        commit."""
        finished: List[SequenceResult] = []
        with profiling.annotate("engine.commit"):
            now = self._now()       # one stamp for every slot of the tick
            for slot in active_slots:
                st = self._active[slot]
                base_len = st.prompt_tokens + len(st.generated)
                committed = 0
                reason = None
                for j in range(chunk):
                    token = int(toks_host[j, slot])
                    st.generated.append(token)
                    committed += 1
                    if post_commit is not None:
                        post_commit(slot, token)
                    reason = self._finish_reason(st, token, base_len + j)
                    if reason is not None:
                        break
                st.life.committed(now)
                self._count("engine.decode_tokens", committed)
                if reason is not None:
                    finished.append(self._retire(slot, reason))
        return finished

    def run_to_completion(self) -> List[SequenceResult]:
        """Pump until queue and slots drain; returns all finished sequences."""
        out: List[SequenceResult] = []
        while self.has_work:
            out.extend(self.step())
        return out

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: Optional[int] = None,
        stop_strings: Sequence[str] = (),
    ) -> List[SequenceResult]:
        """Batch convenience: submit all, pump, return in submit order."""
        ids = [self.submit(p, max_new_tokens, stop_strings) for p in prompts]
        results = {r.seq_id: r for r in self.run_to_completion()}
        return [results[i] for i in ids]

    # ------------------------------------------------- shared termination

    def _finish_reason(self, st: _Active, token: int,
                       length: int) -> Optional[str]:
        if token == self.tokenizer.eos_id:
            return "eos"
        if len(st.generated) >= st.max_new_tokens:
            return "length"
        if length + 1 >= self.engine_cfg.max_seq_len:
            return "length"
        if st.stop_strings:
            # decode only a bounded tail window: a token covers >= 1 char,
            # so a window of max_stop_chars + 8 tokens always contains any
            # stop string that just completed (avoids O(n^2) re-decoding).
            # _stop_context (not st.generated directly) so a stop string
            # spanning a preemption/resume boundary is still seen.
            window = max(len(s) for s in st.stop_strings) + 8
            text = self.tokenizer.decode(self._stop_context(st)[-window:])
            for s in st.stop_strings:
                if s in text:
                    return "stop"
        return None

    def _stop_context(self, st: _Active) -> List[int]:
        """Tokens eligible for stop-string matching, with any
        pre-preemption/pre-restore generation prepended so matches can
        span a resume boundary."""
        resumed = getattr(self, "_resumed", None)
        if resumed:
            prefix = resumed.get(st.seq_id)
            if prefix:
                return prefix + st.generated
        return st.generated

    def _final_text(self, generated: List[int], reason: str,
                    stop_strings: Tuple[str, ...]) -> str:
        text = self.tokenizer.decode(generated)
        if reason == "eos":
            text = self.tokenizer.decode(generated[:-1])
        elif reason == "stop":
            for s in stop_strings:
                idx = text.find(s)
                if idx >= 0:
                    text = text[:idx]
                    break
        return text

    # --------------------------------------------- speculative decoding

    def _speculation_applies(self) -> bool:
        """Speculate only when exact-equivalence is guaranteed and every
        slot has cache room for the full T = k+1 token write."""
        k = self.engine_cfg.speculative_k
        if k <= 0 or self.engine_cfg.temperature != 0.0:
            return False
        # the lengths mirror is host numpy, which _fetch passes through
        # uncounted
        (lengths_host,) = self._fetch(self.lengths)
        return all(self._spec_room_ok(s, k + 1, lengths_host)
                   for s in self._active)

    def _greedy_with_grammar(self, st: _Active, greedy_token: int,
                             logits_row) -> int:
        """The token a plain greedy tick would commit: grammar force /
        allow-mask applied to argmax, identically to the regular path.
        ``logits_row`` is fetched lazily — only grammar slots pay for it."""
        if st.grammar is None:
            return greedy_token
        c = st.grammar.constraint(self._budget_remaining(st))
        if c.force is not None:
            return c.force
        if c.allow is not None:
            masked = np.where(np.asarray(c.allow), host_np(logits_row),
                              -np.inf)
            return int(np.argmax(masked))
        return greedy_token

    def _build_drafts(self, active_slots, cur_host
                      ) -> Tuple[np.ndarray, Dict[int, List[int]]]:
        """Per-slot draft proposals: (tokens_in [B, k+1], drafts {slot:
        draft}).  Drafts come from the draft MODEL when one is attached
        (constructor ``draft_model=``), else n-gram prompt lookup."""
        from k8s_llm_rca_tpu.engine.speculative import ngram_draft

        k_spec = self.engine_cfg.speculative_k
        tokens_in = np.zeros((self.engine_cfg.max_batch, k_spec + 1),
                             np.int32)
        drafts: Dict[int, List[int]] = {}
        if self._draft is not None:
            for slot in active_slots:
                st = self._active[slot]
                ctx = (self._prompts.get(st.seq_id, [])
                       + self._stop_context(st))
                self._draft.sync(slot, st.seq_id, ctx)
            drafts = self._draft.draft(active_slots, k_spec,
                                       self.tokenizer.eos_id)
            for slot in active_slots:
                tokens_in[slot, 0] = cur_host[slot]
                d = drafts[slot]
                tokens_in[slot, 1:1 + len(d)] = d
            return tokens_in, drafts
        for slot in active_slots:
            st = self._active[slot]
            # _stop_context (not st.generated) so a resumed sequence's
            # pre-preemption tokens keep the lookup context contiguous
            ctx = self._prompts.get(st.seq_id, []) + self._stop_context(st)
            d = ngram_draft(ctx, self.engine_cfg.speculative_ngram, k_spec)
            drafts[slot] = d
            tokens_in[slot, 0] = cur_host[slot]
            tokens_in[slot, 1:1 + len(d)] = d
        return tokens_in, drafts

    def _uniform_dfa_tables(self):
        """The single DFA table set shared by ALL grammar slots, or None
        (no grammar slots, an interpreted FSM, or mixed tables).  When
        non-None, grammar work can run fully on device — the scan tick
        and the speculative verify both key off this."""
        tables = None
        for st in self._active.values():
            if st.grammar is None:
                continue
            t = getattr(st.grammar, "tables", None)
            if t is None:
                return None
            if tables is None:
                tables = t
            elif t is not tables:
                return None
        return tables

    def _verify_and_commit(self, active_slots, drafts, greedy_host,
                           logits_host, post_commit=None,
                           constrained: bool = False
                           ) -> List[SequenceResult]:
        """Shared draft verification: commit the longest prefix of each
        slot's draft that agrees with the model's own greedy (grammar-
        constrained) choice, plus one bonus token from the first
        disagreeing position.  Greedy-exact by construction.

        ``constrained``: the greedy choices were already grammar-
        constrained ON DEVICE (dfa_greedy_multi) — skip the host-side
        re-application (the FSM still advances per commit, which also
        validates the device transition)."""
        finished: List[SequenceResult] = []
        with profiling.annotate("engine.commit"):
            now = self._now()
            for slot in active_slots:
                st = self._active[slot]
                draft = drafts[slot]
                base_len = st.prompt_tokens + len(st.generated)
                committed: List[int] = []
                reason = None
                for j in range(len(draft) + 1):
                    if constrained:
                        token = int(greedy_host[slot, j])
                    else:
                        token = self._greedy_with_grammar(
                            st, int(greedy_host[slot, j]),
                            logits_host[slot, j]
                            if logits_host is not None else None)
                    st.generated.append(token)
                    if st.grammar is not None:
                        st.grammar.advance(token)
                    committed.append(token)
                    if post_commit is not None:
                        post_commit(slot, token)
                    # cache now holds j+1 more tokens than before this
                    # commit: tokens_in[0..j] are written; token itself is
                    # written on a LATER tick (same as the regular path's
                    # current token)
                    reason = self._finish_reason(st, token, base_len + j)
                    accepted = (reason is None and j < len(draft)
                                and token == draft[j])
                    if not accepted:
                        break
                st.life.committed(now)
                self._count("engine.decode_tokens", len(committed))
                self._count("engine.spec_drafted", len(draft))
                self._count("engine.spec_accepted",
                            max(0, len(committed) - 1))
                if reason is not None:
                    finished.append(self._retire(slot, reason))
                elif self._draft is not None:
                    self._draft.advance(slot, st.seq_id, committed)
        return finished

    def _need_spec_logits(self, active_slots) -> bool:
        # full logits cross the host boundary only when a grammar slot
        # needs a masked argmax (32000x smaller transfer otherwise)
        return any(self._active[s].grammar is not None
                   for s in active_slots)

    def _spec_constrained_greedy(self, greedy, logits, active_slots):
        """Shared verify-tick grammar handling: when every grammar slot
        shares one compiled DFA, re-derive the greedy choices CONSTRAINED
        on device (dfa_greedy_multi — spec×grammar keeps multi-token
        verify with no [B, T, V] transfer); otherwise fall back to the
        host path (ship logits, _greedy_with_grammar per position).
        Returns (greedy_host [B, T], logits_host or None, constrained)."""
        if not self._need_spec_logits(active_slots):
            return self._fetch(greedy)[0], None, False
        tables = self._uniform_dfa_tables()
        if tables is None:
            greedy_host, logits_host = self._fetch(greedy, logits)
            return greedy_host, logits_host, False
        (allow_t, next_t, dist_t, close_t, complete_t,
         _) = self._dfa_device_tables(tables)
        states, remaining = self._dfa_scan_vectors(tables)
        greedy = self._spec_dfa_greedy(
            logits, jnp.asarray(states), jnp.asarray(remaining),
            self.tokenizer.eos_id, allow_t, next_t, dist_t, close_t,
            complete_t)
        return self._fetch(greedy)[0], None, True


def dfa_scan_step(logits, cur, lens, done, states, remaining, key,
                  sampling: SamplingParams, eos_id: int,
                  allow_t, next_t, dist_t, close_t, complete_t):
    """One on-device DFA-constrained sampling step, the body of
    ``paged.paged_decode_scan_dfa``'s scan (single source for the
    budget-fits mask, force-close, complete->EOS, and state-transition
    logic).

    Returns (cur', lens', done', states', remaining', sub_key_consumed).
    """
    key, sub = jax.random.split(key)
    nxt_states = next_t[states]                       # [B, V]
    fits = dist_t[nxt_states] <= (remaining - 2)[:, None]
    rows = allow_t[states] & fits
    sampled = sample_tokens_masked(logits, sub, sampling, rows)
    # empty row (sub-minimal budget, guarded at submit): force close
    nxt = jnp.where(rows.any(axis=-1), sampled, close_t[states])
    nxt = jnp.where(complete_t[states], eos_id, nxt)
    newly_done = done | (nxt == eos_id)
    advance = jnp.logical_not(done)
    cur = jnp.where(advance, nxt, cur)
    lens = lens + advance.astype(lens.dtype)
    step_dfa = advance & (nxt != eos_id)
    states = jnp.where(step_dfa, next_t[states, nxt], states)
    remaining = remaining - advance.astype(jnp.int32)
    return cur, lens, newly_done, states, remaining, key


def dfa_greedy_multi(logits, states, remaining, eos_id: int,
                     allow_t, next_t, dist_t, close_t, complete_t):
    """Grammar-constrained greedy over a verification step's positions,
    entirely on device (the speculative analog of ``dfa_scan_step``).

    logits [B, T, V]; states/remaining [B] (FREE row for ungrammared
    slots, whose result is then the plain argmax).  The DFA advances along
    the CONSTRAINED choices: on the accepted draft prefix they equal the
    draft (that is what acceptance means), and positions after the first
    disagreement are never committed by the host.  Returns tokens [B, T],
    so speculative decoding keeps multi-token verify under a grammar
    without shipping [B, T, V] logits to the host."""

    def step(carry, lt):
        states, remaining = carry
        nxt_states = next_t[states]                       # [B, V]
        fits = dist_t[nxt_states] <= (remaining - 2)[:, None]
        rows = allow_t[states] & fits
        masked = jnp.where(rows, lt, -jnp.inf)
        tok = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        tok = jnp.where(rows.any(axis=-1), tok, close_t[states])
        tok = jnp.where(complete_t[states], eos_id, tok)
        states = jnp.where(tok != eos_id, next_t[states, tok], states)
        remaining = remaining - 1
        return (states, remaining), tok

    _, toks = jax.lax.scan(step, (states, remaining),
                           jnp.swapaxes(logits, 0, 1))
    return jnp.swapaxes(toks, 0, 1)                       # [B, T]
