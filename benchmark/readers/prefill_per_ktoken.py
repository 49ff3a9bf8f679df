"""Traced prefill program time per thousand real prompt tokens (ms).
params: {"module_regex", "width_regex"}"""

from readers import _prefill


def read(ctx, params):
    got = _prefill.traced(ctx, params)
    per = _prefill.per_padded_token(ctx) if got else None
    if not got or not per:
        return None
    secs, padded = got
    return 1e3 * secs / (padded * per[0] / 1e3)
