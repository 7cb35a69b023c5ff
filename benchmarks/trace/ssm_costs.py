"""What the Mamba-2 and latent-expert layers have to do, from shapes and
counters, and which device operations are theirs: the cost side of the
readers ``ssm_decode_roofline``, ``ssm_prefill_roofline``, ``ssm_busy_share``
and ``latent_moe_busy_share``.  Beside ``costs.py``, whose byte count assumes
keys and values in every layer.

The work is counted from the recurrence, not from the implementation, so a
later kernel is judged on the same work:

- a decode step moves each slot's state of each Mamba layer on by one
  position: it has to read and write the state (``heads x head_dim x state``
  in the state's dtype) and read that position's ``x``, ``B``, ``C`` and
  ``dt``.  Bound by memory bandwidth (3 operations a state element).
- a prefill's chunked scan over one position of one layer: the products
  inside a chunk (``C.B`` over the chunk for each group, the masked product
  with ``x`` for each head), what the position adds to the chunk's state and
  what it reads of the state carried in; and it has to read ``x``, ``B``,
  ``C``, ``dt`` and write ``y``.  The share is of the larger of the two
  least times.

The device operations are found as ``costs.expert_mlp_pattern`` finds the
expert MLP's: by a kernel's own name where one has it (``ssm_state_update``,
``ssm_chunk_scan``: the names ops/ssm.py gives the two, kept by whatever
implements them), else by the shapes in the operation's HLO text that only
that computation has.  All take the built model (``engine.model_cfg``); a
model without such layers gives None.
"""

from __future__ import annotations

import re
from typing import Optional

_ITEM = {"float32": 4, "bfloat16": 2, "float16": 2}


def has_ssm(cfg) -> bool:
    return bool(getattr(cfg, "n_ssm_layers", 0))


def _any(parts):
    return re.compile("|".join(parts))


def _dims(*dims) -> str:
    """``,a,b,c]``: the trailing dimensions of a shape in HLO text."""
    return re.escape("," + ",".join(str(d) for d in dims) + "]")


def state_update_pattern(cfg, max_batch: int):
    """The decode step's state update: an operation named for it, or one
    whose text carries every slot's state of one layer or of all
    (``[slots, heads, head_dim, state]`` with or without the layer axis)."""
    if not has_ssm(cfg):
        return None
    state = (max_batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size)
    return _any([r"^%?ssm_state_update",
                 re.escape("[" + ",".join(map(str, state)) + "]"),
                 _dims(*state)])


def chunk_scan_pattern(cfg):
    """The prefill's chunked scan: an operation named for it, or one whose
    text carries a shape only the chunked form has: the decay between the
    positions of a chunk for each head (``chunk x chunk`` under heads or
    under groups x heads-a-group), a chunk's state for each head
    (``head_dim x state`` likewise), or the per-group ``C.B`` product."""
    if not has_ssm(cfg):
        return None
    h, g = cfg.ssm_heads, cfg.ssm_groups
    r, q = h // g, cfg.ssm_chunk
    p, n = cfg.ssm_head_dim, cfg.ssm_state_size
    return _any([r"^%?ssm_chunk_scan",
                 _dims(g, r, q, q), _dims(h, q, q), _dims(g, q, q),
                 _dims(g, r, p, n), _dims(q, g, r, p), _dims(g, r, q)])


def mamba_rest_pattern(cfg):
    """What else a Mamba layer runs: the in- and out-projection (their
    weight's shape) and the convolution (its channel count, which no other
    layer has)."""
    if not has_ssm(cfg):
        return None
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state_size
    proj = 2 * inner + 2 * cfg.ssm_groups * cfg.ssm_state_size + cfg.ssm_heads
    return _any([re.escape(f"[{cfg.hidden_size},{proj}]"),
                 re.escape(f"[{inner},{cfg.hidden_size}]"),
                 _dims(proj), _dims(conv)])


def latent_moe_pattern(cfg):
    """The routed experts of a latent expert layer: the held experts'
    stacked weights (``[held, latent, width]``, ``[held, width, latent]``),
    an activation at the experts' width (``[..., width]``: no other layer
    has it) and XLA's grouped matmul kernel."""
    lat = getattr(cfg, "moe_latent_size", 0)
    if not lat:
        return None
    e, w = cfg.n_experts, cfg.expert_size
    return _any([re.escape(f"[{e},{lat},{w}]"), re.escape(f"[{e},{w},{lat}]"),
                 _dims(w), r"^%?ragged-dot"])


def state_update_bytes(cfg, slot_steps: float) -> float:
    """Bytes ``slot_steps`` updates (slots x steps x Mamba layers) have to
    move: the state in and out, that position's x, B, C (activations'
    dtype) and dt."""
    item = _ITEM[cfg.ssm_state_dtype]
    act = _ITEM.get(cfg.dtype, 2)
    state = cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state_size
    conv = (cfg.ssm_heads * cfg.ssm_head_dim
            + 2 * cfg.ssm_groups * cfg.ssm_state_size)
    return slot_steps * (2 * state * item + (conv + cfg.ssm_heads) * act)


def chunk_scan_work(cfg, layer_tokens: float):
    """(operations, bytes) of the chunked scan over ``layer_tokens``
    positions x Mamba layers (pad positions included: the scan runs on
    them)."""
    h, g, q = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_chunk
    p, n = cfg.ssm_head_dim, cfg.ssm_state_size
    ops = 2 * q * g * n + 2 * q * h * p + 4 * h * p * n
    act = _ITEM.get(cfg.dtype, 2)
    nbytes = (2 * h * p + 2 * g * n + h) * act
    return layer_tokens * ops, layer_tokens * nbytes


def seconds_of(trace, *patterns) -> Optional[float]:
    """Self seconds of the traced operations whose name or HLO text one of
    the patterns finds (None without a trace or where a pattern is None:
    the model has no such layer)."""
    if trace is None or any(p is None for p in patterns):
        return None
    text = trace["op_text"]
    return sum(s for name, s in trace["op_seconds"].items()
               if any(p.search(name) or p.search(text[name])
                      for p in patterns))
