"""Traffic kind `open_loop`: requests arrive on a schedule, whatever the
engine does; the tails over the window's requests are what is judged.

Segments "ramp" (inside set-up, served, not sampled) and "window". The
sample is the requests due in the window less its last `ttft_limit_s`
seconds (those are served too; no run waits on a drain). See
traffic/chat.json for the parameters and schedule.py for the generator.
"""

from __future__ import annotations

import time

from harness import schedule as sched, serving, stats


def measure(sess, schedule, starts, seconds):
    """Drive `schedule` through the session's engine, ramp then window;
    returns (tracked requests, window start, window end). Everything before
    the window's start is set-up."""
    t_zero = time.perf_counter()
    items = serving.absolute(schedule, t_zero)
    t_w0 = t_zero + starts["window"]
    t_w1 = t_w0 + seconds
    hooks = [(t_w0, lambda: sess.mark("window_start"))]
    sess.drive(items, t_w1, hooks + sess.trace_hooks(t_w0, seconds))
    sess.mark("window_end")
    return items, t_w0, t_w1


def reduce_window(items, t_w0, t_w1, traffic):
    """Sample, TTFT and TPOT values, lateness: plain arithmetic on the
    tracked requests (tests feed it hand-made ones)."""
    ttft_limit = float(traffic["ttft_limit_s"])
    tpot_limit = float(traffic["tpot_limit_s"])
    in_window = [t for t in items if t_w0 <= t.t_due < t_w1]
    sample = [t for t in in_window if t.t_due < t_w1 - ttft_limit]
    ttft_ok, ttft_failed_floor, tpot, met = [], [], [], 0
    for t in sample:
        req = t.req
        if (req is None or req.t_first is None or req.t_first > t_w1
                or req.finish_reason in ("shed", "rejected", "timeout")):
            ttft_failed_floor.append(t_w1 - t.t_due)
            continue
        ttft = req.t_first - t.t_due
        ttft_ok.append(ttft)
        inside = [(ts, n) for ts, n in t.stamps if ts <= t_w1]
        gap = None
        if inside and inside[-1][1] >= 16 and inside[-1][1] > inside[0][1]:
            gap = ((inside[-1][0] - inside[0][0])
                   / (inside[-1][1] - inside[0][1]))
            tpot.append(gap)
        if ttft <= ttft_limit and (gap is None or gap <= tpot_limit):
            met += 1
    return {"sample": len(sample), "in_window": len(in_window),
            "failed": len(ttft_failed_floor),
            "ttft": stats.with_failures_as_largest(ttft_ok,
                                                   ttft_failed_floor),
            "tpot": tpot, "met_both_limits": met,
            "lateness": [t.t_issue - t.t_due for t in in_window
                         if t.t_issue is not None]}


def run(cell, seed, seconds, trace, t_start, device_info):
    traffic = cell.traffic
    schedule = sched.build(traffic, seed, seconds)
    starts = sched.segment_starts(traffic, seconds)
    sess = serving.Session(cell, seed, trace)
    t_built = time.perf_counter()
    widths = sess.warm(schedule)
    t_warm = time.perf_counter()

    items, t_w0, t_w1 = measure(sess, schedule, starts, seconds)
    red = reduce_window(items, t_w0, t_w1, traffic)
    samples = sess.window_samples(t_w0, t_w1)
    done = [t for t in items if t.t_done is not None and t.t_done <= t_w1]
    checks, problems, memory_peak, loaded = sess.finish(done)

    e2e = {}
    if red["ttft"]:
        e2e["ttft_p90_s"] = stats.percentile(red["ttft"], 90)
    if red["tpot"]:
        e2e["tpot_p90_s"] = stats.percentile(red["tpot"], 90)
    info = {
        "kind": "open_loop", "params": sess.n_params,
        "schedule_digest": sched.digest(schedule),
        "requests_scheduled": len(schedule),
        "rate_per_s": traffic["arrivals"].get("rate_per_s"),
        "ttft_limit_s": traffic["ttft_limit_s"],
        "tpot_limit_s": traffic["tpot_limit_s"],
        "sample": red["sample"], "in_window": red["in_window"],
        "tpot_sample": len(red["tpot"]),
        "ttft_p50_s": stats.percentile(red["ttft"], 50),
        "tpot_p50_s": stats.percentile(red["tpot"], 50),
        "completed_in_window": sum(1 for t in done if t.t_done >= t_w0),
        "without_first_token_at_window_end": sum(
            1 for t in items if t.t_issue is not None
            and t.req.t_first is None),
        "engine_steps_in_window": (sess.marks["window_end"]["steps"]
                                   - sess.marks["window_start"]["steps"]),
        "prefill_widths_warmed": widths, "checks": checks,
        "setup_breakup_s": {"build": t_built - t_start,
                            "warm": t_warm - t_built,
                            "ramp": t_w0 - t_warm},
        "compile": sess.meter.report(), "problems": problems,
        "compiles_in_window": samples["compiles_in_window"],
        "counters_at_window_end": sess.marks["window_end"]["counters"],
    }
    return {
        "correct": not problems, "attempted": red["sample"],
        "failed": red["failed"], "setup_s": t_w0 - t_start, "e2e": e2e,
        "samples": {**red, **samples, "items": items,
                    "window_s": t_w1 - t_w0},
        "trace": loaded, "info": info, "memory_peak_bytes": memory_peak,
    }
