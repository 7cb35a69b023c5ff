"""Record the small trace kept in ``benchmarks/tests/data``: the ``tiny``
configuration on the chip, a handful of requests, the last ticks traced.

    chiprun -- python benchmarks/trace/record_sample.py

Writes ``chiprun_out/sample.xplane.pb`` and prints how its planes, lines and
events are named, with every stat of one event per device line.
"""

from __future__ import annotations

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    out = os.path.join(ROOT, "chiprun_out", "sample.xplane.pb")
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--benchmark", os.path.join(ROOT, "benchmarks", "tests",
                                     "rehearsal.json"),
         "--workload", "tiny.chat-open", "--seed", "1", "--seconds", "2",
         "--trace", "1", "--keep-trace", out])
    if rc:
        return rc
    import jax  # the child has exited: the chip is free again

    from benchmarks.trace import reduce

    pd = jax.profiler.ProfileData.from_file(out)
    print(reduce.describe(pd))
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                print(plane.name, line.name, ev.name[:80],
                      {k: str(v)[:80] for k, v in ev.stats})
                break
    return 0


if __name__ == "__main__":
    sys.exit(main())
