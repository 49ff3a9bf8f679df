"""From the profiler's trace to numbers. The only reduction there is.

`Capture` starts and stops jax.profiler around a short window; `load()` reads the
.xplane.pb with jax.profiler.ProfileData into plain lists; everything
below that works on plain lists of (name, start_s, duration_s) so that it
can be checked on a hand-built trace (tests/test_trace.py).

Device planes are named "/device:TPU:<n>". On each, the line "XLA Ops"
holds one event per executed HLO operation and "XLA Modules" one per
executed program. Host spans are the benchmark's own
jax.profiler.TraceAnnotation("bench.*") events on the host plane, which the
profiler puts on the same clock.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import time

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv)", re.I)


class Capture:
    """jax.profiler around a short window: start() and stop() between
    steps; t0 and t1 are the host clock at either end. The directory is
    emptied first."""

    def __init__(self, trace_dir):
        self.dir = trace_dir
        self.t0 = self.t1 = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        if self.t0 is None or self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()


def span(name):
    """A host span on the trace's clock (a no-op when nothing traces)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def load(trace_dir, device_prefix="/device:TPU:"):
    """{"devices": {n: {"ops": [...], "modules": [...]}}, "host": [...]}
    with events as (name, start_s, duration_s, stats dict)."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}

    def events(line, keep_stats):
        evs = []
        for e in line.events:
            stats = {}
            if keep_stats:
                for k, v in e.stats:
                    if k in keep_stats:
                        stats[k] = v
            evs.append((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                        stats))
        return evs

    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            try:
                n = int(plane.name[len(device_prefix):].split()[0])
            except ValueError:
                continue
            dev = out["devices"].setdefault(n, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] += events(line, ("long_name", "tf_op",
                                                "hlo_category"))
                elif line.name == MODULES_LINE:
                    dev["modules"] += events(line, ())
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        out["host"].append((e.name, e.start_ns * 1e-9,
                                            e.duration_ns * 1e-9, {}))
    return out


def describe(trace_dir, limit=12):
    """Planes, lines and a few events with their stats: what a builder
    reads by hand before trusting the names the readers match on. A traced
    run prints it when BENCH_DESCRIBE_TRACE is set (the trace itself does
    not come back from the chip)."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    lines = []
    if not paths:
        return ["no .xplane.pb under " + trace_dir]
    data = jax.profiler.ProfileData.from_file(paths[-1])
    for plane in data.planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name!r} events={len(evs)}")
            seen = set()
            for e in evs:
                if e.name in seen:
                    continue
                seen.add(e.name)
                if len(seen) > limit:
                    break
                lines.append(f"    {e.name!r} start={e.start_ns} "
                             f"dur={e.duration_ns} stats={list(e.stats)[:8]}")
    return lines


# -- reductions on plain lists ------------------------------------------------

def union_seconds(intervals):
    """Total length of the union of (start, duration) intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        if d <= 0:
            continue
        if end is None or s > end:
            total += d
            end = s + d
        elif s + d > end:
            total += s + d - end
            end = s + d
    return total


def merged(intervals):
    """The union as a sorted list of disjoint (start, end)."""
    out = []
    for s, d in sorted(intervals):
        if d <= 0:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def busy_and_window(trace):
    """(busy_s averaged over the device planes, window_s): busy is the
    union of the intervals in which an operation ran on a device; the
    window runs from the first to the last device event over all planes."""
    per_dev, starts, ends = [], [], []
    for dev in trace["devices"].values():
        iv = [(s, d) for _, s, d, _ in dev["ops"]]
        if iv:
            per_dev.append(union_seconds(iv))
            starts.append(min(s for s, _ in iv))
            ends.append(max(s + d for s, d in iv))
    if not per_dev:
        return 0.0, 0.0
    return sum(per_dev) / len(per_dev), max(ends) - min(starts)


_HLO = re.compile(r"^%?([\w\-]+?)[.\d]*\s*=\s*\(?([a-z0-9]+\[[^\]]*\])")


def op_label(name, stats):
    """A short label for a device operation: its name without the running
    number, with its (first) result shape. On this TPU the event's name is
    the whole HLO line ("%fusion.770 = (f32[4,2048]{...}, ...) fusion(...)");
    elsewhere the text is in the stat `long_name`. A Pallas kernel
    (custom_call_target tpu_custom_call) is marked as one."""
    text = name if " = " in name else str(stats.get("long_name", ""))
    m = _HLO.match(text)
    if not m:
        return re.sub(r"[.\d]+$", "", name)[:80]
    kind = "pallas:" if "tpu_custom_call" in text else ""
    return f"{kind}{m.group(1)} {m.group(2)}"


def top_ops(trace, n=10):
    """[(label, seconds)]: device operations by total time, summed over the
    device planes and divided by their number."""
    totals = {}
    ndev = max(1, len(trace["devices"]))
    for dev in trace["devices"].values():
        for name, _, d, stats in dev["ops"]:
            key = op_label(name, stats)
            totals[key] = totals.get(key, 0.0) + d / ndev
    return sorted(totals.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps_by_span(trace, n=10):
    """[(label, seconds)]: idle time of the first device plane, attributed
    to the host span (bench.*) that covers the start of each gap."""
    if not trace["devices"]:
        return []
    dev = trace["devices"][min(trace["devices"])]
    busy = merged([(s, d) for _, s, d, _ in dev["ops"]])
    spans = sorted((s, s + d, name) for name, s, d, _ in trace["host"])
    agg = {}
    for (a0, a1), (b0, _) in zip(busy, busy[1:]):
        gap = b0 - a1
        if gap <= 0:
            continue
        owner = "(no span)"
        for s0, s1, name in spans:
            if s0 <= a1 < s1:
                owner = name
            if s0 > a1:
                break
        tot, cnt, longest = agg.get(owner, (0.0, 0, 0.0))
        agg[owner] = (tot + gap, cnt + 1, max(longest, gap))
    rows = [(f"{k} (gaps {c}, longest {m * 1e3:.3f} ms)", t)
            for k, (t, c, m) in agg.items()]
    return sorted(rows, key=lambda kv: -kv[1])[:n]


def exposed_collective_seconds(ops):
    """Of one device's op events, the seconds in which a collective ran and
    no other operation did: collective time not hidden behind compute."""
    coll = [(s, d) for name, s, d, _ in ops if COLLECTIVE.match(name)]
    comp = merged([(s, d) for name, s, d, _ in ops
                   if not COLLECTIVE.match(name)])
    exposed = 0.0
    for c0, c1 in merged(coll):
        hidden = sum(max(0.0, min(c1, b1) - max(c0, b0)) for b0, b1 in comp
                     if b1 > c0 and b0 < c1)
        exposed += (c1 - c0) - hidden
    return exposed


def module_durations(trace, pattern):
    """Durations (s) of executed programs whose name matches `pattern`, on
    the first device plane."""
    if not trace["devices"]:
        return []
    dev = trace["devices"][min(trace["devices"])]
    rx = re.compile(pattern)
    return [d for name, _, d, _ in dev["modules"] if rx.search(name)]


def op_seconds(trace, pattern, field="name"):
    """Seconds of device operations whose name (or the stat `field`)
    matches, averaged over the device planes."""
    rx = re.compile(pattern)
    ndev = max(1, len(trace["devices"]))
    total = 0.0
    for dev in trace["devices"].values():
        for name, _, d, stats in dev["ops"]:
            text = name if field == "name" else str(stats.get(field, ""))
            if rx.search(text):
                total += d
    return total / ndev
