"""Find an open-loop cell's knee once, on the chip: one engine, several
fixed rates, one ramp + window each, drained in between.

    python3 benchmark/tools/sweep_rates.py --workload mistral-7b-v0.3-d8.chat \
        --rates 2,2.5,3,3.5,4,4.5 --seconds 30 --seed 11

Prints one JSON line per rate: TTFT and TPOT p50/p90 from the due time,
the share of sampled requests inside both limits of the traffic file, and
the backlog (requests issued and still without a first token) at the
window's start and end. The knee is the highest rate at which the backlog
does not grow and 90% of requests meet both limits; the cell runs at 0.8 x
that. The limits are set from the lowest rate's medians (about 2.5 x), so
run the sweep once with loose limits to read those first.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--benchmark-json", default=None)
    ap.add_argument("--allow-cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    from harness import cells, device, schedule as sched, serving, stats
    from harness.runners import open_loop

    cell = cells.load_cell(args.workload, args.benchmark_json)
    if device.require_chips(cell.chips, args.allow_cpu_rehearsal) is None:
        return 3
    device.setup_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    traffic = cell.traffic
    sess = serving.Session(cell, args.seed, trace=False)
    traffic["arrivals"]["rate_per_s"] = max(rates)
    sess.warm(sched.build(traffic, args.seed, args.seconds))
    for rate in rates:
        traffic["arrivals"]["rate_per_s"] = rate
        schedule = sched.build(traffic, args.seed, args.seconds)
        starts = sched.segment_starts(traffic, args.seconds)
        items, t_w0, t_w1 = open_loop.measure(sess, schedule, starts,
                                               args.seconds)
        red = open_loop.reduce_window(items, t_w0, t_w1, traffic)

        def backlog(at):
            return sum(1 for t in items if t.t_issue is not None
                       and t.t_issue <= at
                       and (t.req.t_first is None or t.req.t_first > at))
        lanes = [n for t, n in sess.lane_samples if t_w0 <= t <= t_w1]
        print("sweep " + json.dumps({
            "rate_per_s": rate, "sample": red["sample"],
            "failed": red["failed"],
            "ttft_p50_s": stats.percentile(red["ttft"], 50),
            "ttft_p90_s": stats.percentile(red["ttft"], 90),
            "tpot_p50_s": stats.percentile(red["tpot"], 50),
            "tpot_p90_s": stats.percentile(red["tpot"], 90),
            "attainment": red["met_both_limits"] / max(1, red["sample"]),
            "backlog_at_start": backlog(t_w0), "backlog_at_end": backlog(t_w1),
            "lanes_mean": sum(lanes) / max(1, len(lanes)),
            "lanes_max": max(lanes or [0]),
            "lateness_p90_s": stats.percentile(red["lateness"], 90),
            "limits": [traffic["ttft_limit_s"], traffic["tpot_limit_s"]],
        }), flush=True)
        sess.drive([], float("inf"), until_idle=True)      # drain
        sess.engine.finished.clear()
        sess.lane_samples.clear()
        sess.live_context.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
