"""Programs built inside the measured window: jax's backend-compile events
(a persistent-cache load counts too: a new program was still built) plus
the program's own jit_retrace_total. Expect 0."""


def read(ctx, params):
    n = ctx.samples.get("compiles_in_window")
    if n is None:
        return None
    marks = ctx.samples.get("marks") or {}
    c0 = (marks.get("window_start") or {}).get("counters")
    c1 = (marks.get("window_end") or {}).get("counters")
    if c0 and c1:
        n += c1["jit_retrace_total"] - c0["jit_retrace_total"]
    return float(n)
