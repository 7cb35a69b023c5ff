"""What the attention of a model with sliding-window layers beside full ones
has to do, from shapes and counters, and which device operations are its: the
cost side of the readers ``window_attn_decode_roofline``,
``full_attn_decode_roofline``, ``window_attn_prefill_roofline``,
``attn_busy_share``, ``routed_expert_busy_share`` and ``window_cache_share``.
Beside ``costs.py``, whose byte count assumes keys and values of every token
in every layer.

The work is counted from the mask, not from the implementation, so a later
kernel is judged on the same work:

- a decode step of a WINDOW layer has to read, for each live slot, the keys
  and values of the ``min(length, window)`` positions its query sees; of a
  FULL layer, of every live position.  The engine counts both where it
  dispatches (``engine.attn_window_tokens``, ``engine.attn_full_tokens``:
  steps x sum over live slots x layers of the kind, from the lengths at the
  dispatch, so a scan's later steps are counted a little short, never long).
  Bound by memory bandwidth: a decode query does two operations per cached
  byte.
- a prefill's BANDED call over one position of one window layer: that
  position's ``heads`` queries meet at most ``window`` keys, two products of
  ``head_dim`` each way (scores, and the weighted values).  The engine counts
  the positions the banded calls covered, pad included (the call runs on
  them): ``engine.attn_window_prefill_tokens``.  Bound by the MXU.

The device operations are the kernels' own names (``ops/paged_attention.py``
names a window layer's call ``window_paged_attention`` and a full layer's
``paged_attention``, ``ops/flash_attention.py`` the banded call
``flash_attention_window`` and the full one ``flash_attention``), and for the
routed experts the shapes in the operation's HLO text that only they have.
All take the built model (``engine.model_cfg``); the attention readers give
None for a model without window layers (``has_window``), the experts' for one
without experts of their own width.
"""

from __future__ import annotations

import re
from typing import Optional

from benchmarks.trace.ssm_costs import seconds_of  # noqa: F401  (re-export)

WINDOW_DECODE = re.compile(r"^%?window_paged_attention")
FULL_DECODE = re.compile(r"^%?paged_attention")
WINDOW_PREFILL = re.compile(r"^%?flash_attention_window")
FULL_PREFILL = re.compile(r"^%?flash_attention(?!_window)")
ATTENTION = (WINDOW_DECODE, FULL_DECODE, WINDOW_PREFILL, FULL_PREFILL)


def has_window(cfg) -> bool:
    return bool(getattr(cfg, "n_window_layers", 0))


def kv_token_bytes(model_cfg, engine_cfg) -> float:
    """Bytes of keys and values ONE token holds in ONE layer, scales
    included."""
    per_elem = {"int8": 1.0, "int4": 0.5}.get(engine_cfg.kv_cache_dtype, 2.0)
    scale = 4.0 if engine_cfg.kv_cache_dtype in ("int8", "int4") else 0.0
    return 2.0 * (model_cfg.kv_dim * per_elem + scale)


def decode_bytes(model_cfg, engine_cfg, layer_tokens: float) -> float:
    """Bytes a decode kernel has to read for ``layer_tokens`` (token, layer)
    pairs its queries see (``engine.attn_window_tokens`` or
    ``engine.attn_full_tokens``)."""
    return layer_tokens * kv_token_bytes(model_cfg, engine_cfg)


def band_ops(model_cfg, layer_positions: float) -> float:
    """Operations of the banded prefill calls over ``layer_positions``
    (position, window layer) pairs: every head's query against ``window``
    keys, and the weighted sum of as many values."""
    return (layer_positions * 4.0 * model_cfg.attn_window
            * model_cfg.n_heads * model_cfg.head_dim)


def routed_expert_pattern(cfg):
    """The routed experts of a Llama-block expert layer whose experts have
    their own width: the held experts' stacked weights (``[held, hidden,
    width]``, ``[held, width, hidden]``), a per-expert activation at that
    width (``[..., held, width]``) and XLA's grouped matmul kernel.  The
    router, the shared expert and the dense layer's MLP are not in it.  None
    for a model without such experts (none at all, experts of the MLP's own
    width, or latent ones, which ``ssm_costs.latent_moe_pattern`` finds)."""
    if not (cfg.n_experts and getattr(cfg, "moe_intermediate_size", 0)) \
            or getattr(cfg, "moe_latent_size", 0):
        return None
    e, h, w = cfg.n_experts, cfg.hidden_size, cfg.expert_size
    return re.compile("|".join(
        [re.escape(f"[{e},{h},{w}]"), re.escape(f"[{e},{w},{h}]"),
         re.escape(f",{e},{w}]"), r"^%?ragged-dot"]))


def all_full_bytes(model_cfg, full_bytes: float) -> Optional[float]:
    """What the live tokens' cache would hold were every layer a full one,
    from what the full layers hold of them (``engine.cache_bytes_full``)."""
    if not model_cfg.n_kv_layers:
        return None
    return full_bytes * model_cfg.n_layers / model_cfg.n_kv_layers
