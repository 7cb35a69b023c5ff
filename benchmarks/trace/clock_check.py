"""Which clock the profiler stamps a ``TraceAnnotation`` on.

    python3 benchmarks/trace/clock_check.py [--allow-cpu]

Takes ``time.time_ns()`` before and just inside 50 annotations while the
profiler runs over a little device work, then reads the annotations' starts
back from the ``.xplane.pb``.  An event's ``start_ns`` there counts from the
capture's ``profile_start_time`` (a stat of the ``Task Environment`` plane,
Unix nanoseconds), so the absolute start is the two added.  If every start
lies between its two stamps, the profiler's clock is the wall clock: the
engine's ``_now()`` stamps, the obs ``Tracer``'s spans and the device trace
are then one timeline, and a Chrome export of the spans lies over an XProf
capture as it is.  Prints one JSON object; refuses a CPU unless told.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

NAME = "clock.probe"
PROBES = 50


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"clock_check: no TPU here ({dev.platform})", file=sys.stderr)
        return 2
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step = jax.jit(lambda a: a @ a)
    step(x).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="clock-check-")
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 2
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    stamps = []
    for _ in range(PROBES):
        before = time.time_ns()
        with jax.profiler.TraceAnnotation(NAME):
            inside = time.time_ns()
            step(x).block_until_ready()
        stamps.append((before, inside))
        time.sleep(0.005)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    start_ns, probes, device_first = None, [], None
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                start_ns = int(value)
        for line in plane.lines:
            for ev in line.events:
                if ev.name == NAME:
                    probes.append(ev.start_ns)
                elif (plane.name.startswith("/device:")
                      and line.name == "XLA Ops"):
                    device_first = (ev.start_ns if device_first is None
                                    else min(device_first, ev.start_ns))
    if start_ns is None or len(probes) != PROBES:
        print(f"clock_check: profile_start_time {start_ns}, "
              f"{len(probes)} of {PROBES} annotations found", file=sys.stderr)
        return 1
    probes.sort()
    offsets = [start_ns + p - inside
               for p, (_, inside) in zip(probes, stamps)]
    q1, _, q3 = statistics.quantiles(offsets, n=4)
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "probes": PROBES,
        "starts_between_their_stamps": sum(
            1 for p, (a, b) in zip(probes, stamps) if a <= start_ns + p <= b),
        "stamp_gap_ns_median": statistics.median(b - a for a, b in stamps),
        # annotation start less the stamp taken just inside it
        "offset_ns_median": statistics.median(offsets),
        "offset_ns_min": min(offsets), "offset_ns_max": max(offsets),
        "jitter_ns_iqr": q3 - q1,
        # the first device operation, on the same base: after the first
        # stamp if the device plane shares the host's clock
        "first_device_op_after_first_stamp_ns": (
            None if device_first is None
            else start_ns + device_first - stamps[0][0]),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
