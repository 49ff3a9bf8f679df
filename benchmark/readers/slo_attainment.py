"""Share (%) of the sampled requests inside both limits of the traffic
file; a failed request misses."""


def read(ctx, params):
    n = ctx.samples.get("sample")
    if not n:
        return None
    return 100.0 * ctx.samples["met_both_limits"] / n
