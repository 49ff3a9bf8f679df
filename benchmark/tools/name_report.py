"""What the program calls the work in the last traced run of a cell.

    python3 benchmark/tools/name_report.py --workload <cell> [--dir DIR]

Reads the trace a `--trace 1` run left under $TMPDIR/bench_trace_<cell>
(or DIR) and prints one JSON object:

- `scopes`: the device's busy time split by the scopes in each operation's
  op_name (harness/op_names.py) — component scopes "pt.*", effect scopes
  "kv.*" and kernel names "fa_*", joined outermost first ("pt.attn/fa_fwd",
  "pt.attn/pt.serve.gather"); "(unnamed)" is what the names miss. Each
  operation counts its own time (a `while` less its body), so the groups
  are disjoint and sum to the busy time. XLA gives a fusion the
  metadata of one of its members: exact for kernels, approximate at fusion
  boundaries.
- `modules`: the same split inside each executed program, by its name
  without the run's id.
- `host_spans`: count, median and total milliseconds of each host span of
  the program and of the benchmark (trainer.*, serving.*, bench.*).
- `phases`: seconds of each engine phase, from the `serving.phase` events.

It must run in a process that does not hold the chip (JAX_PLATFORMS=cpu is
fine): only files are read.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import op_names, program_spans, stats, trace as tr  # noqa: E402

SCOPE = re.compile(r"(?<![\w.])(?:pt|kv)\.[a-z_.]+|(?<![\w.])fa_[a-z_]+")
SPANS = r"^(trainer|serving|bench)\."


def scope_key(op_name):
    found = []
    for s in SCOPE.findall(op_name):
        if s not in found:
            found.append(s)
    return "/".join(found) or "(unnamed)"


def split_by_scope(trace, module_names):
    """({scope key: seconds}, {module: {scope key: seconds}}) over the
    first device plane, from each operation's own time."""
    total, per_module = {}, {}
    if not trace["devices"]:
        return total, per_module
    dev = trace["devices"][min(trace["devices"])]
    for op_name, own, module in op_names.named_ops(dev, module_names):
        key = scope_key(op_name)
        module = re.sub(r"\(\d+\)$", "", module) or "(no module)"
        total[key] = total.get(key, 0.0) + own
        per_module.setdefault(module, {})
        per_module[module][key] = per_module[module].get(key, 0.0) + own
    return total, per_module


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--dir", default=None)
    args = ap.parse_args(argv)
    directory = args.dir or program_spans.trace_dir(args.workload)
    trace = tr.load(directory)
    if trace is None:
        raise SystemExit(f"no .xplane.pb under {directory}")
    busy, window = tr.busy_and_window(trace)
    total, per_module = split_by_scope(trace, op_names.modules(directory))

    def table(seconds):
        return {k: {"seconds": v, "share_of_busy_pct": 100.0 * v / busy}
                for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])}

    spans = {}
    events = program_spans.host_events(directory, SPANS)
    for name, _, d, _ in events:
        spans.setdefault(name, []).append(d)
    out = {
        "busy_s": busy, "window_s": window,
        "scopes": table(total) if busy else {},
        "modules": {m: table(v) for m, v in per_module.items()} if busy
        else {},
        "host_spans": {n: {"count": len(v),
                           "median_ms": 1e3 * stats.percentile(v, 50),
                           "total_ms": 1e3 * sum(v)}
                       for n, v in sorted(spans.items())},
        "phases": program_spans.seconds_by_stat(
            [e for e in events if e[0] == "serving.phase"], "phase"),
    }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
