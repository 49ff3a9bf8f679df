"""Shared by the prefill readers: what the trace holds of prefill programs
and what the schedule says a padded prefill token stands for.

The trace gives each prefill program's width (from its name) and time, so
padded tokens in the traced window are exact. How many REAL prompt tokens
and how many operations a padded token stands for is a property of the
traffic's fixed multiset of prompts (every seed has the same), computed
from the schedule with the engine's chunking rule."""

import re

from harness import flops
from harness.serving import chunk_plan


def traced(ctx, params):
    """(seconds, padded tokens) of prefill programs on the first device."""
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    dev = ctx.trace["devices"][min(ctx.trace["devices"])]
    rx = re.compile(params["module_regex"])
    wx = re.compile(params["width_regex"])
    secs, padded = 0.0, 0
    for name, _, d, _ in dev["modules"]:
        if rx.search(name):
            m = wx.search(name)
            if m:
                secs += d
                padded += int(m.group(1))
    return (secs, padded) if padded else None


def per_padded_token(ctx):
    """(real tokens, operations) per padded token over the window's
    requests, with the engine options of the configuration file."""
    eng = ctx.cell.config["engine"]
    buckets = eng["prefill_buckets"]
    chunk = eng.get("prefill_chunk") or max(buckets)
    real = padded = ops = 0.0
    for t in ctx.samples["items"]:
        if t.row["segment"] != "window":
            continue
        s = t.row["prompt_len"]
        padded += sum(chunk_plan(s, buckets, chunk))
        real += s
        ops += flops.prefill_flops(ctx.samples["shapes"], s)
    return (real / padded, ops / padded) if padded else None
