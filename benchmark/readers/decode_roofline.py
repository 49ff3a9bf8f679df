"""A decode step's share (%) of its memory roofline: the bytes one step has
to move (the matmul weights once, plus the cached keys and values of every
live context token once; harness/flops.py decode_step_bytes) over the
chip's HBM bandwidth, divided by the traced time of one decode step (the
decode program's median duration over its fused steps). Memory-bound: a
decode step does ~2 x lanes operations per weight byte.
params: {"module_regex"}"""

from harness import flops, stats, trace as tr


def read(ctx, params):
    if ctx.trace is None:
        return None
    durs = tr.module_durations(ctx.trace, params["module_regex"])
    live = ctx.samples.get("live_context")
    if not durs or not live:
        return None
    steps = int(ctx.cell.config["engine"]["decode_steps"])
    per_step = stats.percentile(durs, 50) / steps
    tokens = sum(n for _, n in live) / len(live)
    least = (flops.decode_step_bytes(ctx.samples["shapes"], tokens)
             / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / per_step
