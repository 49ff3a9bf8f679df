"""Decode-lane state sampled by the benchmark after each engine.step().
params: {"what": "occupancy" | "full_share"}; each sample is weighted by
the time until the next one."""


def read(ctx, params):
    lanes = ctx.samples.get("lanes")
    cap = ctx.samples.get("max_batch")
    if not lanes or len(lanes) < 2 or not cap:
        return None
    total = lanes[-1][0] - lanes[0][0]
    if total <= 0:
        return None
    acc = 0.0
    for (t0, n), (t1, _) in zip(lanes, lanes[1:]):
        w = t1 - t0
        acc += w * (n / cap if params["what"] == "occupancy"
                    else float(n >= cap))
    return 100.0 * acc / total
