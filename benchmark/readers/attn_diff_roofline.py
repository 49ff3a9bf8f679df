"""The differential attention layers' flash kernels' share (%) of their
compute roofline: the query-key pairs the masks leave in every
differential layer over the traced steps (harness/diff_attn_flops.py:
scores at the query heads' qk lanes, values at their pairs' v lanes,
the backward twice the forward, recomputation and padded lanes not
credited), each chip doing its share, over the peak bf16 rate, divided by
the traced time of the events whose name matches (averaged over the
chips). `shapes` is what families/phi4flash.py shapes() returns. None
where there is no trace, no such kernel or no such shape.
params: {"regex", "pass": "fwd" | "bwd"}"""

from harness import diff_attn_flops, trace as tr


def read(ctx, params):
    if ctx.trace is None:
        return None
    secs = tr.op_seconds(ctx.trace, params["regex"])
    steps = ctx.cell.traffic.get("trace_steps")
    s = ctx.samples
    if not secs or not steps or "diff_windows" not in s["shapes"]:
        return None
    count = {"fwd": diff_attn_flops.diff_fwd_flops,
             "bwd": diff_attn_flops.diff_bwd_flops}[params["pass"]]
    ops = steps * s["batch"] * count(s["shapes"], s["seq"])
    return 100.0 * ops / s["chips"] / ctx.peaks["bf16_flops"] / secs
