"""The power-retention scan's share (%) of the chip's peak bf16 rate over
the traced steps: layers x sequences x the operations one layer's forward
and backward need (harness/retention_flops.py: the smaller of the
quadratic and the recurrent form, recomputation not credited) over the
peak, divided by the own time of the operations under the program's scope
`pt.retn.scan` (ops/power_retention.py; by op_name, harness/op_names.py).
Compute bounds it: a layer's retention moves megabytes for 5e12
operations. None where there is no trace, no HLO metadata, no such scope
(an older program) or no retention in the family's shapes.
params: {"regex"}"""

from harness import op_names, program_spans, retention_flops


def read(ctx, params):
    if ctx.trace is None:
        return None
    s = ctx.samples
    steps = ctx.cell.traffic.get("trace_steps")
    if not steps or "retention_heads" not in s["shapes"]:
        return None
    names = op_names.modules(program_spans.trace_dir(ctx.cell.name))
    if not names:
        return None
    secs = op_names.op_name_seconds(ctx.trace, names, params["regex"])
    if not secs:
        return None
    ops = (steps * s["batch"] * s["shapes"]["layers"]
           * retention_flops.retention_train_flops(s["shapes"], s["seq"]))
    return 100.0 * ops / ctx.peaks["bf16_flops"] / s["chips"] / secs
