"""Time of the device operations whose op_name (the program's name stack
for the operation, harness/op_names.py: component scopes such as "pt.mlp"
and kernel names) matches, as a share (%) of the device's busy time in the
traced window. XLA gives a fusion the metadata of one of its members: exact
for kernels, approximate at fusion boundaries. None where the trace holds
no HLO metadata or nothing matches. params: {"regex"}"""

from harness import op_names, program_spans, trace as tr


def read(ctx, params):
    if ctx.trace is None:
        return None
    names = op_names.modules(program_spans.trace_dir(ctx.cell.name))
    if not names:
        return None
    secs = op_names.op_name_seconds(ctx.trace, names, params["regex"])
    busy, _ = tr.busy_and_window(ctx.trace)
    if not secs or not busy:
        return None
    return 100.0 * secs / busy
