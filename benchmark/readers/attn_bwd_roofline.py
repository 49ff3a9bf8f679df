"""The flash backward kernels' share (%) of their compute roofline: twice
the causal forward operations of every layer's attention over the traced
steps (harness/flops.py attention_fwd_flops: dQ, dK and dV from the same
query-key pairs; the recomputed scores are not credited, as in train_mfu),
each chip doing its share, over the peak bf16 rate, divided by the traced
time of both backward kernels' events (averaged over the chips).
params: {"regex", "field"}"""

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
    ops = (2.0 * steps * s["shapes"]["layers"] * s["batch"]
           * flops.attention_fwd_flops(s["shapes"], s["seq"]))
    least = ops / s["chips"] / ctx.peaks["bf16_flops"]
    return 100.0 * least / secs
