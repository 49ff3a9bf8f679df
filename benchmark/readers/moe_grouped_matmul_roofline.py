"""The routed experts' grouped-matmul kernels' share (%) of their roofline
over the traced steps: the larger of operations over the peak bf16 rate and
bytes over the memory's rate (harness/moe_flops.py, every layer, forward
and backward, recomputation not credited), divided by the own time of the
kernels' events. The rows are what routing sent to the held experts: the
program's gauge `moe_held_assignment_share` (taken at set-up on the first
sequence) x tokens x top-k. The kernels are found by op_name: XLA's
grouped-matmul kernel carries its own name in place of the program's name
stack (tests/test_flash_mosaic_compile.py pins it). None where there is no
trace, no such kernel or no gauge. params: {"regex", "gauge"}"""

from harness import moe_flops, op_names, program_spans
from readers import registry_gauge


def read(ctx, params):
    if ctx.trace is None:
        return None
    names = op_names.modules(program_spans.trace_dir(ctx.cell.name))
    share = registry_gauge.read(ctx, {"gauge": params["gauge"]})
    steps = ctx.cell.traffic.get("trace_steps")
    s = ctx.samples
    if not names or not share or not steps or "expert_ffn" not in s["shapes"]:
        return None
    secs = op_names.op_name_seconds(ctx.trace, names, params["regex"])
    if not secs:
        return None
    shapes = s["shapes"]
    rows = share * s["batch"] * s["seq"] * shapes["top_k"]
    layers = steps * shapes["layers"]
    least = max(
        layers * moe_flops.grouped_matmul_train_flops(shapes, rows)
        / ctx.peaks["bf16_flops"],
        layers * moe_flops.grouped_matmul_train_bytes(shapes, rows)
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / s["chips"] / secs
