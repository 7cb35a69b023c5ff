"""Which device operations are the gated MLP that sits behind every mixer of
a layer table whose layers are blocks of two sublayers
(``ModelConfig.block_mlp_size``; ``granitemoehybrid``): the other half of
such a block beside what ``ssm_costs`` finds of its Mamba-2 mixers, for the
reader ``block_mlp_busy_share``.

Found as ``ssm_costs`` finds its operations, by the shapes in an operation's
HLO text that only this computation has, taken from the built model
(``engine.model_cfg``): the MLP's weights (``[hidden, width]`` for gate and
up, ``[width, hidden]`` for down) and an activation at the MLP's width
(``[..., width]``).  That holds where no other layer has a dimension of that
width: in granite-4.0-h-micro the MLP's 8192 is beside a mixer's 4096 inner
channels, 4352 convolution channels and 8512 projected columns, 2048 hidden
and 100352 vocabulary rows.  A model without such a sublayer, and a program
whose ``ModelConfig`` has no such field, give None.
"""

from __future__ import annotations

import re

from benchmarks.trace import ssm_costs


def block_mlp_pattern(cfg):
    width = getattr(cfg, "block_mlp_size", 0)
    if not width:
        return None
    h = cfg.hidden_size
    return ssm_costs._any([re.escape(f"[{h},{width}]"),
                           re.escape(f"[{width},{h}]"),
                           ssm_costs._dims(width)])
