"""A statistic of the traced durations of one program (an "XLA Modules"
event). params: {"module_regex", "stat": "p50" | "mean", "scale"}"""

from harness import stats, trace as tr


def read(ctx, params):
    if ctx.trace is None:
        return None
    durs = tr.module_durations(ctx.trace, params["module_regex"])
    if not durs:
        return None
    value = (stats.percentile(durs, 50) if params.get("stat", "p50") == "p50"
             else sum(durs) / len(durs))
    return value * params.get("scale", 1.0)
