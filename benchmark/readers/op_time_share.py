"""Time of the device operations whose name matches, as a share (%) of the
device's busy time in the traced window. params: {"regex", "field"}"""

from harness import trace as tr


def read(ctx, params):
    if ctx.trace is None:
        return None
    secs = tr.op_seconds(ctx.trace, params["regex"],
                         params.get("field", "name"))
    busy, _ = tr.busy_and_window(ctx.trace)
    if not secs or not busy:
        return None
    return 100.0 * secs / busy
