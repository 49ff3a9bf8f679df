"""A gauge of the program's own metric registry (paddle_tpu/observability/
catalog.py), as it stands in this process after the run: what the program
counted about itself, not a time. None where the program has no such gauge
(an older program) or nothing ever set it. params: {"gauge", "scale"}"""


def read(ctx, params):
    try:
        from paddle_tpu.observability import metrics
    except ImportError:
        return None
    family = metrics.get_registry().get(params["gauge"])
    if family is None or family.type != "gauge" or family.labelnames:
        return None
    value = family.value
    if not value:               # a gauge nobody set reads 0.0
        return None
    return value * params.get("scale", 1.0)
