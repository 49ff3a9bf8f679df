"""Collective time not hidden behind compute, as a share (%) of the traced
window: per chip, the seconds in which a collective operation ran and no
other operation did; averaged over the chips."""

from harness import trace as tr


def read(ctx, params):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    _, window = tr.busy_and_window(ctx.trace)
    if not window:
        return None
    per = [tr.exposed_collective_seconds(d["ops"])
           for d in ctx.trace["devices"].values()]
    return 100.0 * (sum(per) / len(per)) / window
