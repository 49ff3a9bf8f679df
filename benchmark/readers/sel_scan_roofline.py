"""The selective scan's kernels' share (%) of the memory system's
roofline: the least bytes they must move over the traced steps
(harness/selective_scan_bytes.py: every Mamba layer's forward and
backward, each operand once, recomputation not credited), each chip
doing its share, over the peak HBM bandwidth, divided by the traced time
of the events whose name matches (averaged over the chips). None where
there is no trace, no such kernel (an older program, or the jax.numpy
scan) or no such shape.
params: {"regex"}"""

from harness import selective_scan_bytes, trace as tr


def read(ctx, params):
    if ctx.trace is None:
        return None
    secs = tr.op_seconds(ctx.trace, params["regex"])
    steps = ctx.cell.traffic.get("trace_steps")
    s = ctx.samples
    if not secs or not steps or "sel_layers" not in s["shapes"]:
        return None
    moved = (steps * s["batch"] * s["shapes"]["sel_layers"]
             * selective_scan_bytes.train_bytes(s["shapes"], s["seq"]))
    return 100.0 * moved / s["chips"] / ctx.peaks["hbm_bytes_per_s"] / secs
