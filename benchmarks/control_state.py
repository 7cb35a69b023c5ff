"""``benchmarks/control.py`` for a model that keeps a recurrent state per
slot: the control is the engine built with that STATE one precision below
the one the configuration's file states (``ssm_state_dtype``: bfloat16 for
float32), the pages as stated.  Everything else is ``control.py``'s: the
same seeds, the same check, the same summary and exit code.

    chiprun -- python benchmarks/control_state.py \
        --workload nemotron3-super-d11.audit-report --seeds 11 12 13
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LOWER = {"float32": "bfloat16"}


def lowered(conf):
    """The configuration with its recurrent state one precision down."""
    stated = conf.get("ssm_state_dtype")
    if stated not in LOWER:
        raise SystemExit(f"control: no precision below a {stated!r} state")
    return dict(conf, ssm_state_dtype=LOWER[stated])


def main(argv=None) -> int:
    from benchmarks import control

    control.lowered = lowered
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
