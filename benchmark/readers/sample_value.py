"""One number the runner already holds. params: {"key", "scale"}"""


def read(ctx, params):
    value = ctx.samples.get(params["key"])
    if value is None:
        return None
    return value * params.get("scale", 1.0)
