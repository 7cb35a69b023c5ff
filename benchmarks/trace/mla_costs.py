"""What the attention of a model with LATENT attention (``deepseek_v3``) has
to do, from shapes and counters, and which device operations are its: the
cost side of the readers ``mla_decode_roofline``, ``mla_prefill_roofline``,
``mla_busy_share`` and ``latent_cache_bytes_per_token``.  Beside
``costs.py``, whose byte count assumes keys and values per head of every
token in every layer: such a model caches one row a token and layer.

The work is counted from the arithmetic, not from the implementation, so a
later kernel is judged on the same work:

- a decode step's ABSORBED walk has to read, for each live slot and layer,
  the row of every cached token once (``latent_row`` values in the model's
  type: what is cached, not the lanes a pool pads it to) and spends on it
  ``2 x heads x (latent_row + latent)`` operations (every head's score over
  the whole row, and its weighted sum of the latent, the row less its
  rotated key).  The engine counts
  the rows where it dispatches (``engine.mla_decode_row_reads``: live tokens
  summed over slots, steps and layers, a scan's later steps included).  At 32
  heads in bfloat16 that is 60 operations a byte where the chip's ridge is
  240, so the bound is the LARGER of the two times and both are computed.
- a prefill's attention in the published form: every (query, key) pair the
  causal mask lets through costs each head ``2 x qk_head_dim`` operations for
  its score and ``2 x v_head_dim`` for its value.  The engine counts the
  pairs of the rows it ran, a padding row's repeat included, x layers
  (``engine.mla_prefill_pairs``).  Bound by the MXU.

The device operations are the kernels' own names (``ops/mla_attention.py``
names the walk ``mla_paged_attention``; the prefill's call is
``ops/flash_attention.py``'s ``flash_attention``) and, for the two einsums
that carry a decode query into the latent's space and the attended latents
out of it, the shapes in the operation's HLO text that only they have:
``w_kvb`` by head, ``[latent, heads, qk_nope_head_dim + v_head_dim]``, and
its two halves.  All take the built model (``engine.model_cfg``) and give
None for a model without latent attention, and for a program whose
``ModelConfig`` has no such field (``has_latent``).
"""

from __future__ import annotations

import re

from benchmarks.trace.ssm_costs import _any, seconds_of  # noqa: F401

DECODE = re.compile(r"^%?mla_paged_attention")
PREFILL = re.compile(r"^%?flash_attention(?!_window)")
TYPE_BYTES = {"bfloat16": 2.0, "float32": 4.0}


def has_latent(cfg) -> bool:
    return bool(getattr(cfg, "latent_row", 0))


def latent_width(cfg) -> int:
    """The latent's own width: the cached row less its rotated key."""
    return cfg.latent_row - cfg.qk_rope_head_dim


def row_bytes(cfg) -> float:
    """Bytes of what ONE token caches in ONE layer: the latent and the one
    rotated key, in the model's type."""
    return cfg.latent_row * TYPE_BYTES[cfg.dtype]


def decode_bytes(cfg, row_reads: float) -> float:
    return row_reads * row_bytes(cfg)


def decode_ops(cfg, row_reads: float) -> float:
    return row_reads * 2.0 * cfg.n_heads * (cfg.latent_row
                                            + latent_width(cfg))


def prefill_ops(cfg, pairs: float) -> float:
    """Operations of the published form's attention over ``pairs`` (query,
    key, layer) triples (``engine.mla_prefill_pairs``)."""
    return pairs * 2.0 * cfg.n_heads * (cfg.qk_head_dim + cfg.v_head_dim)


def absorb_pattern(cfg):
    """The einsums beside the walk (``ops/mla_attention.py::absorb_query``,
    ``unabsorb_values``): operations whose text carries ``w_kvb`` by head or
    one of its halves."""
    if not has_latent(cfg):
        return None
    r, h = latent_width(cfg), cfg.n_heads
    return _any([re.escape(f"[{r},{h},{w}]") for w in (
        cfg.qk_nope_head_dim + cfg.v_head_dim, cfg.qk_nope_head_dim,
        cfg.v_head_dim)])
