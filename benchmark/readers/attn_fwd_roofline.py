"""The flash forward kernel's share (%) of its compute roofline: causal
forward operations of every layer's attention over the traced steps
(harness/flops.py attention_fwd_flops), each chip doing its share, over the
peak bf16 rate, divided by the traced time of the forward kernel's events
(averaged over the chips). Compute-bound at seq 2048, d 128.
params: {"regex", "field", "steps_key"}"""

from harness import flops, trace as tr


def read(ctx, params):
    if ctx.trace is None:
        return None
    secs = tr.op_seconds(ctx.trace, params["regex"],
                         params.get("field", "name"))
    steps = ctx.cell.traffic.get("trace_steps")
    if not secs or not steps:
        return None
    s = ctx.samples
    ops = (steps * s["shapes"]["layers"] * s["batch"]
           * flops.attention_fwd_flops(s["shapes"], s["seq"]))
    least = ops / s["chips"] / ctx.peaks["bf16_flops"]
    return 100.0 * least / secs
