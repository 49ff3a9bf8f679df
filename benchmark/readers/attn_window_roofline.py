"""The windowed flash kernels' share (%) of their compute roofline: the
banded query-key pairs of every window layer over the traced steps
(harness/window_flops.py: 4 x heads x head_dim a pair forward, twice that
backward, recomputation not credited; the count is the band's, whatever
the kernel visits), each chip doing its share, over the peak bf16 rate,
divided by the traced time of the events whose name matches (averaged
over the chips). `shapes` is what families/mellum.py shapes() returns:
window_layers, window, window_heads, head_dim. None where there is no
trace, no such kernel (an older program) or no such shape.
params: {"regex", "field", "pass": "fwd" | "bwd"}"""

from harness import trace as tr, window_flops


def read(ctx, params):
    if ctx.trace is None:
        return None
    secs = tr.op_seconds(ctx.trace, params["regex"],
                         params.get("field", "name"))
    steps = ctx.cell.traffic.get("trace_steps")
    s = ctx.samples
    if not secs or not steps or "window_layers" not in s["shapes"]:
        return None
    shapes = s["shapes"]
    count = {"fwd": window_flops.window_fwd_flops,
             "bwd": window_flops.window_bwd_flops}[params["pass"]]
    ops = (steps * shapes["window_layers"] * s["batch"]
           * count(shapes["window_heads"], shapes["head_dim"], s["seq"],
                   shapes["window"]))
    least = ops / s["chips"] / ctx.peaks["bf16_flops"]
    return 100.0 * least / secs
