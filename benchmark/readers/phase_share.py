"""Share (%) of the window's wall time the engine's PhaseAccountant gave to
the named phases. params: {"phases": ["hostsync"]}"""


def read(ctx, params):
    marks = ctx.samples.get("marks") or {}
    a = (marks.get("window_start") or {}).get("phases")
    b = (marks.get("window_end") or {}).get("phases")
    if not a or not b:
        return None
    wall = marks["window_end"]["t"] - marks["window_start"]["t"]
    if wall <= 0:
        return None
    secs = sum(b.get(p, 0.0) - a.get(p, 0.0) for p in params["phases"])
    return 100.0 * secs / wall
