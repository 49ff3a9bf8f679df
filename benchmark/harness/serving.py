"""What the two serving runners share: build and warm the engine, drive a
schedule through it on the host clock, stamp token times, judge a sample
of served requests against the float32 reference.

Clock: time.perf_counter(), the clock the engine stamps Request.t_first
with. A request's latency is anchored at the time it was DUE by the
schedule, not at Request.t_arrival (stamped inside add_request, after the
generator's own lateness behind a running decode tile).
"""

from __future__ import annotations

import gc
import importlib
import os
import time

import numpy as np

from harness import device, schedule as sched, trace as tr
from harness.compile_meter import CompileMeter


def chunk_plan(prompt_len, buckets, chunk):
    """Widths of the prefill programs a prompt runs through, by the
    engine's rule (full chunks, then the tail padded to the smallest bucket
    that fits), from its public options `buckets` and `chunk`."""
    widths_all = sorted({b for b in buckets if b <= chunk} | {chunk})
    plan, rest = [], prompt_len
    while rest > chunk:
        plan.append(chunk)
        rest -= chunk
    plan.append(next(w for w in widths_all if w >= rest))
    return plan


class Tracked:
    """One scheduled request and what the benchmark saw of it."""
    __slots__ = ("row", "req", "t_due", "t_issue", "stamps", "t_done")

    def __init__(self, row, t_due):
        self.row = row
        self.req = None          # the engine's Request once issued
        self.t_due = t_due
        self.t_issue = None
        self.stamps = []         # (t, tokens emitted so far), as they grow
        self.t_done = None


class Session:
    """An engine with its bookkeeping, from set-up to the judged sample."""

    def __init__(self, cell, seed, trace):
        self.cell, self.seed, self.trace = cell, seed, trace
        self.meter = CompileMeter()
        self.family = importlib.import_module(
            f"families.{cell.config['family']}")
        if trace:
            # counters and the engine's phase accountant, traced run only:
            # the end-to-end run pays for no instrumentation
            from paddle_tpu.observability.metrics import get_registry
            from paddle_tpu.profiler.phases import get_phase_accountant
            get_registry().enable()
            self.phases = get_phase_accountant()
            self.phases.enable()
        else:
            self.phases = None
        (self.engine, self.cfg, self.n_params,
         self.weights) = self.family.build_engine(cell.config, seed)
        self.vocab = int(self.cfg.vocab_size)
        self.live = []           # Tracked requests not finished yet
        self.lane_samples = []   # (t, busy lanes) after each step
        self.live_context = []   # (t, context tokens of decoding requests)
        self.steps = 0
        self.marks = {}          # name -> counters and clocks at that time
        self.capture = None      # the profiler's short window, traced run

    # -- set-up ---------------------------------------------------------------
    def warm(self, schedule):
        """Run one request through every prefill width the schedule uses,
        and the decode program; returns the widths."""
        eng = self.engine
        widths = set()
        for row in schedule:
            widths |= set(chunk_plan(row["prompt_len"], eng.buckets,
                                     eng.chunk))
        longest = max(r["prompt_len"] for r in schedule)
        for w in sorted(widths):
            eng.add_request(sched.prompt_tokens(w, w, self.vocab),
                            max_new_tokens=eng.decode_steps + 2)
        if longest > eng.chunk:      # the chunked path, start > 0
            eng.add_request(sched.prompt_tokens(1, eng.chunk + 1, self.vocab),
                            max_new_tokens=2)
        while eng.has_work():
            eng.step()
        eng.finished.clear()
        return sorted(widths)

    # -- driving ----------------------------------------------------------------
    def issue(self, tracked, now):
        row = tracked.row
        eng = self.engine
        eng.add_request(
            sched.prompt_tokens(row["token_seed"], row["prompt_len"],
                                self.vocab),
            max_new_tokens=row["output_len"], eos_token_id=None)
        tracked.req = eng.queue[-1]
        tracked.t_issue = now
        self.live.append(tracked)

    def step(self):
        """One engine step, then the token stamps and the lane sample."""
        with tr.span("bench.engine_step"):
            self.engine.step()
        self.steps += 1
        now = time.perf_counter()
        still, context = [], 0
        for t in self.live:
            n = len(t.req.generated)
            if n and (not t.stamps or n > t.stamps[-1][1]):
                t.stamps.append((now, n))
            if t.req.done:
                t.t_done = now
            else:
                still.append(t)
                if n:
                    context += t.row["prompt_len"] + n
        self.live = still
        self.live_context.append((now, context))
        self.lane_samples.append(
            (now, sum(r is not None for r in self.engine.lanes)))
        return now

    def drive(self, items, t_stop, hooks=(), until_idle=False):
        """Open loop: issue every request whose due time has come, step the
        engine, until `t_stop` (or, with `until_idle`, until nothing is left
        to do). `items`: Tracked in due order, due times absolute. `hooks`:
        (time, callable) pairs run between steps once their time has come
        (window marks, the profiler's start and stop). Returns the index of
        the first request not issued."""
        eng, i, n = self.engine, 0, len(items)
        hooks = sorted(hooks, key=lambda h: h[0])
        h = 0
        while True:
            now = time.perf_counter()
            while h < len(hooks) and hooks[h][0] <= now:
                hooks[h][1]()
                h += 1
            if now >= t_stop or (until_idle and i >= n
                                 and not eng.has_work()):
                return i
            if i < n and items[i].t_due <= now:
                with tr.span("bench.issue"):
                    while i < n and items[i].t_due <= now:
                        self.issue(items[i], now)
                        i += 1
            if eng.has_work():
                self.step()
            else:
                nxt = min(items[i].t_due if i < n else t_stop, t_stop)
                time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.002)))

    def mark(self, name):
        """Clock, phase seconds, compile events and counters, now."""
        self.marks[name] = {"t": time.perf_counter(),
                            "phases": self.phase_seconds(),
                            "compile_events": self.meter.events,
                            "counters": self.counters(), "steps": self.steps}

    def trace_hooks(self, t_w0, seconds):
        """drive() hooks that trace `trace_seconds` of the window from
        `trace_offset_share` of its length on (traced run only)."""
        if not self.trace:
            return []
        traffic = self.cell.traffic
        self.capture = tr.Capture(os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "bench_trace_" + self.cell.name))
        t0 = t_w0 + float(traffic["trace_offset_share"]) * seconds
        return [(t0, self.capture.start),
                (t0 + float(traffic["trace_seconds"]), self.capture.stop)]

    # -- after the window -----------------------------------------------------
    def window_samples(self, t_w0, t_w1):
        """Lane and context samples of the window for the readers; the
        context is taken over the traced part where there is one."""
        cap = self.capture
        lo, hi = (cap.t0, cap.t1) if cap and cap.t1 else (t_w0, t_w1)
        return {
            "lanes": [(t, n) for t, n in self.lane_samples
                      if t_w0 <= t <= t_w1],
            "live_context": [(t, n) for t, n in self.live_context
                             if lo <= t <= hi],
            "max_batch": self.engine.max_batch,
            "shapes": self.family.shapes(self.cfg), "marks": self.marks,
            "t_w0": t_w0, "t_w1": t_w1,
            "compiles_in_window": (
                self.marks["window_end"]["compile_events"]
                - self.marks["window_start"]["compile_events"])}

    def finish(self, candidates):
        """Stop the trace, judge served requests, load the trace. Returns
        (checks, problems, memory peak, loaded trace)."""
        traffic = self.cell.traffic
        if self.capture:
            self.capture.stop()
        checks, fallbacks, memory_peak = self.judge(
            candidates, int(traffic["check_requests"]),
            float(traffic["logit_tolerance"]),
            int(traffic["check_max_tokens"]))
        loaded = None
        if self.capture and self.capture.t1:
            loaded = tr.load(self.capture.dir)
            if os.environ.get("BENCH_DESCRIBE_TRACE"):
                print("\n".join(tr.describe(self.capture.dir)), flush=True)
        problems = [f"request check failed: {c}" for c in checks
                    if not c["ok"]]
        if not checks:
            problems.append("no completed request to check")
        problems += [f"program {k} fell back at PIR stage {v!r}"
                     for k, v in fallbacks.items() if v is not None]
        return checks, problems, memory_peak, loaded

    def phase_seconds(self):
        if self.phases is None:
            return None
        rep = self.phases.report()
        return {"wall_s": rep["wall_s"], "coverage": rep["coverage"],
                **{p: v["seconds"] for p, v in rep["phases"].items()}}

    def counters(self):
        """Program counters (traced run): sheds, deferrals, rejections,
        fallbacks, retraces."""
        if not self.trace:
            return None
        from paddle_tpu.observability import snapshot
        names = ("serving_shed_total", "serving_deferred_total",
                 "serving_rejected_total", "pir_fallback_total",
                 "jit_retrace_total", "attention_backend_failures_total")
        snap = snapshot()
        out = {n: 0.0 for n in names}
        for m in snap.get("metrics", []):
            if m.get("name") in out:
                out[m["name"]] += sum(float(s.get("value", 0.0))
                                      for s in m.get("samples", []))
        return out

    def judge(self, candidates, how_many, tol, max_tokens):
        """A seeded sample of completed requests against the reference. The
        engine is dropped first: the reference needs its memory. Prefers one
        single-chunk and one chunked prompt."""
        memory_peak = device.memory_peak_bytes(self.cell.chips)
        chunk = self.engine.chunk
        fallbacks = {k: getattr(r, "fallback", None)
                     for k, r in self.engine.compile_reports.items()}
        done = [t for t in candidates
                if t.req is not None and t.req.finish_reason == "length"
                and t.row["prompt_len"] + t.row["output_len"] <= max_tokens]
        rs = np.random.RandomState(self.seed % (2 ** 32))
        rs.shuffle(done)
        short = [t for t in done if t.row["prompt_len"] <= chunk]
        long_ = [t for t in done if t.row["prompt_len"] > chunk]
        picked = (short[:1] + long_[:1] + short[1:] + long_[1:])[:how_many]
        material = [(sched.prompt_tokens(t.row["token_seed"],
                                         t.row["prompt_len"], self.vocab),
                     list(t.req.generated)) for t in picked]
        self.engine = None
        self.live = []
        gc.collect()
        state = self.weights()
        checks = [self.family.check_request(state, self.cfg, p, g, tol)
                  for p, g in material]
        return checks, fallbacks, memory_peak


def absolute(schedule, t_zero):
    return [Tracked(row, t_zero + row["due_s"]) for row in schedule]
