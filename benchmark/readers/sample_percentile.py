"""A percentile of one of the runner's samples.
params: {"sample": "lateness", "q": 90, "scale": 1.0}"""

from harness import stats


def read(ctx, params):
    values = ctx.samples.get(params["sample"])
    if not values:
        return None
    return stats.percentile(values, params["q"]) * params.get("scale", 1.0)
