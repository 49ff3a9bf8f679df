"""Traffic kind `closed_batch`: an offline flush. Every request is handed
to the engine at the window's start (more than complete in --seconds, so
the engine never runs dry); the throughput is the prompt plus output tokens
of the requests completed in the window over the MEASURED time from the
window's start to the last such completion.
"""

from __future__ import annotations

import time

from harness import schedule as sched, serving, stats


def reduce_window(items, t_w0, t_w1):
    """(rate, tokens, seconds, completed): arithmetic on tracked requests."""
    done = [t for t in items if t.t_done is not None and t.t_done <= t_w1
            and t.req.finish_reason == "length"]
    rate, tokens, secs = stats.completion_rate(
        [(t.row["prompt_len"] + len(t.req.generated), t.t_done)
         for t in done], t_w0)
    return rate, tokens, secs, done


def run(cell, seed, seconds, trace, t_start, device_info):
    schedule = sched.build(cell.traffic, seed, seconds)
    sess = serving.Session(cell, seed, trace)
    t_built = time.perf_counter()
    widths = sess.warm(schedule)
    t_warm = time.perf_counter()

    sess.mark("window_start")
    t_w0 = time.perf_counter()
    items = serving.absolute(schedule, t_w0)
    t_w1 = t_w0 + seconds
    sess.drive(items, t_w1, sess.trace_hooks(t_w0, seconds), until_idle=True)
    t_end = time.perf_counter()
    sess.mark("window_end")

    rate, tokens, secs, done = reduce_window(items, t_w0, t_w1)
    failed = sum(1 for t in items if t.req is not None and t.req.finish_reason
                 in ("shed", "rejected", "timeout"))
    samples = sess.window_samples(t_w0, t_w1)
    checks, problems, memory_peak, loaded = sess.finish(done)
    if failed:
        problems.append(f"{failed} requests were shed, rejected or timed out")
    info = {
        "kind": "closed_batch", "params": sess.n_params,
        "schedule_digest": sched.digest(schedule),
        "requests_handed_over": len(items), "completed": len(done),
        "tokens_completed": tokens, "seconds_to_last_completion": secs,
        "ran_dry": bool(t_end < t_w1),
        "prefill_widths_warmed": widths, "checks": checks,
        "setup_breakup_s": {"build": t_built - t_start,
                            "warm": t_warm - t_built},
        "compile": sess.meter.report(), "problems": problems,
        "compiles_in_window": samples["compiles_in_window"],
        "counters_at_window_end": sess.marks["window_end"]["counters"],
    }
    return {
        "correct": not problems, "attempted": len(done) + failed,
        "failed": failed, "setup_s": t_w0 - t_start,
        "e2e": {} if rate is None else {"serve_tokens_per_s": rate},
        "samples": {**samples, "items": items,
                    "window_s": min(t_end, t_w1) - t_w0},
        "trace": loaded, "info": info, "memory_peak_bytes": memory_peak,
    }
