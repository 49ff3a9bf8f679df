"""The program's own host spans, from the traced run's file.

The program puts its spans on the host plane of the profiler's trace
(paddle_tpu/observability/tracing.py: `trainer.step`, `serving.step`,
`serving.phase` with its `phase` stat, ...), on the same clock as the
device's operations. harness/trace.py load() keeps the benchmark's own
`bench.*` host events only, so this module opens the run's .xplane.pb
again, under the directory both runners write it to, and returns the host
events whose name matches. A program that has no such span (an older
commit) gives an empty list, never an error.
"""

from __future__ import annotations

import glob
import os
import re


def trace_dir(cell_name):
    """Where runners/train.py and harness/serving.py write a cell's trace."""
    return os.path.join(os.environ.get("TMPDIR", "/tmp"),
                        "bench_trace_" + cell_name)


def newest_xplane(directory):
    """The newest .xplane.pb under a trace directory, or None."""
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def host_events(directory, pattern):
    """[(name, start_s, duration_s, stats dict)] of the events on the host
    planes whose name matches `pattern` (re.search), in time order."""
    path = newest_xplane(directory)
    if path is None:
        return []
    import jax
    rx = re.compile(pattern)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if rx.search(e.name):
                    out.append((e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9, dict(e.stats)))
    return sorted(out, key=lambda ev: ev[1])


def seconds_by_stat(events, stat):
    """{value of `stat`: summed seconds} over events that carry it, e.g. the
    seconds of each engine phase from `serving.phase` events."""
    out = {}
    for _, _, d, stats in events:
        if stat in stats:
            out[stats[stat]] = out.get(stats[stat], 0.0) + d
    return out
