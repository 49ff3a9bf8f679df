"""Where a traced train step's device time goes, by scope (a mixer's parts
included) and by pass, in seconds a step.

    python3 benchmark/tools/pass_report.py --workload <cell> [--dir DIR]
        [--markdown]

Reads the trace a `--trace 1` run left under $TMPDIR/bench_trace_<cell> (or
DIR) and prints one JSON object (with --markdown, the same as tables):

- `passes`: the busy time split by the program's rule for the pass of an
  operation (`paddle_tpu.observability.catalog.trace_pass`: forward,
  recompute, xla_remat, backward, update), seconds a step and % of busy.
- `scopes`: the busy time by scope path (component scopes "pt.*" and kernel
  names, outermost first: "pt.attn/pt.attn.sliding/pt.attn.pos",
  "pt.ssm/pt.ssm.scan", "pt.attn/fa_fwd"; "(unnamed)" is what the names
  miss) x pass.
- `mixers`: for every mixer scope (pt.attn, pt.attn.sliding, pt.attn.full,
  pt.ssm, pt.retn) its share of busy, its parts' (the next name down the
  path: a part scope, a scan, a kernel) and the remainder: what sits
  directly under the mixer's scope, which should stay under 2% of busy.
- `checks`: the passes sum to 100 +/- 0.5% of busy; every mixer's remainder
  is under 2% of busy; the recomputation's and XLA's rematerialisation's
  shares, and `mfu_scale`: 1 / (1 - (recompute + xla_remat) / 100), what
  `train_mfu` is to be multiplied by to credit recomputation.

Every instant goes to the innermost operation running then (a `while` less
its body; `readers/pass_time_share.py innermost_seconds`), so every split is
an exact partition of the busy time, and a scope's share here can read
under the accepted scope metric's where its operations sit in loops
(`op_names.self_seconds` counts some of a loop's body twice: ROADMAP B5).
XLA gives a fusion the metadata of one of its members: exact for kernels,
approximate at fusion boundaries. A step loaded from a compile cache that
another tree wrote carries that tree's names (jax's cache key leaves
metadata out).

It must run in a process that does not hold the chip (JAX_PLATFORMS=cpu is
fine): only files are read. The tree it runs from supplies the rule; without
one (an older commit) every operation's pass reads "(no rule)".
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
from harness import op_names, program_spans, trace as tr  # noqa: E402
from readers import pass_time_share  # noqa: E402

NAME = re.compile(r"(?<![\w.])(?:pt|kv)\.[a-z_.]+"
                  r"|(?<![\w.])(?:fa|faw|retn)_[a-z_]+")
MIXERS = ("pt.attn", "pt.attn.sliding", "pt.attn.full", "pt.ssm", "pt.retn")
STEP_MODULE = r"train_step"
REMAINDER = "(remainder)"


def scope_path(op_name):
    """The scopes and kernel names of an op_name, outermost first, each
    once (a backward rule re-enters its scan's scope)."""
    found = []
    for s in NAME.findall(op_name):
        if s not in found:
            found.append(s)
    return found


def mixer_and_part(path):
    """(mixer key, part) of a scope path that starts inside a mixer: the
    leading mixer scopes joined ("pt.attn/pt.attn.sliding"), and the next
    name down, REMAINDER where there is none. (None, None) outside one."""
    n = 0
    while n < len(path) and path[n] in MIXERS:
        n += 1
    if not n:
        return None, None
    return "/".join(path[:n]), path[n] if n < len(path) else REMAINDER


def report(trace, module_names, rule):
    """The object this tool prints, from a loaded trace."""
    if not trace["devices"]:
        return {}
    dev = trace["devices"][min(trace["devices"])]
    busy = tr.union_seconds([(s, d) for _, s, d, _ in dev["ops"]])
    steps = max(1, len(tr.module_durations(trace, STEP_MODULE)))
    rule = rule or (lambda op_name, inst: "(no rule)")
    passes, scopes, mixers = {}, {}, {}
    for op_name, _, which, own in pass_time_share.passed_ops(
            dev, module_names, rule):
        path = scope_path(op_name)
        passes[which] = passes.get(which, 0.0) + own
        row = scopes.setdefault("/".join(path) or "(unnamed)", {})
        row[which] = row.get(which, 0.0) + own
        mixer, part = mixer_and_part(path)
        if mixer:
            parts = mixers.setdefault(mixer, {})
            parts[part] = parts.get(part, 0.0) + own

    def pct(seconds):
        return 100.0 * seconds / busy if busy else 0.0

    def cell(seconds):
        return {"seconds_a_step": seconds / steps,
                "share_of_busy_pct": pct(seconds)}

    out_scopes = {}
    for key, row in sorted(scopes.items(), key=lambda kv: -sum(kv[1].values())):
        out_scopes[key] = dict(
            cell(sum(row.values())),
            by_pass={p: v / steps for p, v in sorted(row.items())})
    out_mixers = {}
    for key, parts in sorted(mixers.items()):
        out_mixers[key] = dict(
            cell(sum(parts.values())),
            parts={p: cell(v) for p, v in
                   sorted(parts.items(), key=lambda kv: -kv[1])
                   if p != REMAINDER},
            remainder=cell(parts.get(REMAINDER, 0.0)))
    total = sum(passes.values())
    again = pct(passes.get("recompute", 0.0) + passes.get("xla_remat", 0.0))
    return {
        "busy_s": busy, "steps": steps,
        "passes": {p: cell(v) for p, v in sorted(passes.items())},
        "scopes": out_scopes,
        "mixers": out_mixers,
        "checks": {
            "passes_sum_pct_of_busy": pct(total),
            "passes_partition_the_busy_time": abs(pct(total) - 100.0) <= 0.5,
            "largest_mixer_remainder_pct": max(
                [m["remainder"]["share_of_busy_pct"]
                 for m in out_mixers.values()], default=0.0),
            "every_mixer_remainder_under_2pct": all(
                m["remainder"]["share_of_busy_pct"] < 2.0
                for m in out_mixers.values()),
            "recompute_pct": pct(passes.get("recompute", 0.0)),
            "xla_remat_pct": pct(passes.get("xla_remat", 0.0)),
            "mfu_scale": 1.0 / (1.0 - again / 100.0) if again < 100 else None,
        },
    }


def markdown(rep):
    """The report as the tables PERF.md section 5 holds."""
    order = [p for p in ("forward", "recompute", "xla_remat", "backward",
                         "update", "(no rule)") if p in rep["passes"]]
    lines = [f"busy {rep['busy_s']:.4f} s over {rep['steps']} steps; "
             "seconds a step", "",
             "| scope | " + " | ".join(order) + " | all | % of busy |",
             "|---|" + "---|" * (len(order) + 2)]
    for key, row in rep["scopes"].items():
        if row["share_of_busy_pct"] < 0.05:
            continue
        lines.append(
            f"| `{key}` | "
            + " | ".join(f"{row['by_pass'].get(p, 0.0):.4f}" for p in order)
            + f" | {row['seconds_a_step']:.4f} "
            f"| {row['share_of_busy_pct']:.2f} |")
    lines.append(
        "| **all** | "
        + " | ".join(f"{rep['passes'][p]['seconds_a_step']:.4f}"
                     for p in order)
        + f" | {sum(v['seconds_a_step'] for v in rep['passes'].values()):.4f}"
        f" | {rep['checks']['passes_sum_pct_of_busy']:.2f} |")
    lines.append(
        "| % of busy | "
        + " | ".join(f"{rep['passes'][p]['share_of_busy_pct']:.2f}"
                     for p in order) + " | | |")
    lines += ["", "| mixer | % of busy | parts, % of busy | remainder |",
              "|---|---|---|---|"]
    for key, m in rep["mixers"].items():
        parts = ", ".join(f"`{p}` {v['share_of_busy_pct']:.2f}"
                          for p, v in m["parts"].items())
        lines.append(f"| `{key}` | {m['share_of_busy_pct']:.2f} | {parts} "
                     f"| {m['remainder']['share_of_busy_pct']:.2f} |")
    lines += ["", "checks: " + json.dumps(rep["checks"])]
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--dir", default=None)
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args(argv)
    directory = args.dir or program_spans.trace_dir(args.workload)
    trace = tr.load(directory)
    if trace is None:
        raise SystemExit(f"no .xplane.pb under {directory}")
    rep = report(trace, op_names.modules(directory), pass_time_share._rule())
    print(markdown(rep) if args.markdown else json.dumps(rep, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
