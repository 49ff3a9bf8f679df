"""The one traffic generator: a schedule of requests from a data file.

A traffic file gives `segments` ("ramp" inside set-up, "window" measured),
each a number of requests (a rate times a length, or a count) whose prompt
lengths, output lengths and inter-arrival gaps are STRATIFIED: the n evenly
spaced quantiles of their distributions, not n draws. --seed only permutes
each list (within blocks of `stratify_block` requests, so that every prefix
of the schedule has the same mix too) and draws the token ids. Every seed
therefore offers the same multiset of work and the same gaps in another
order.

Distributions: {"dist": "lognormal", "median", "sigma", "min", "max"},
{"dist": "uniform", "min", "max"}, {"dist": "fixed", "value"}.
Arrivals: {"process": "poisson", "rate_per_s"} (exponential gaps),
{"process": "at_once"} (all due at the segment's start).
"""

from __future__ import annotations

import hashlib
import json
import math
from statistics import NormalDist

import numpy as np


def _quantiles(spec, n):
    """n evenly spaced quantiles (at (i + 0.5) / n) of a length
    distribution, as whole numbers inside [min, max]."""
    us = [(i + 0.5) / n for i in range(n)]
    kind = spec["dist"]
    if kind == "fixed":
        return [int(spec["value"])] * n
    if kind == "uniform":
        vals = [spec["min"] + u * (spec["max"] - spec["min"]) for u in us]
    elif kind == "lognormal":
        nd = NormalDist()
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(u))
                for u in us]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return [int(min(max(round(v), spec["min"]), spec["max"])) for v in vals]


def _gaps(arrivals, n):
    if arrivals["process"] == "at_once":
        return [0.0] * n
    if arrivals["process"] == "poisson":
        rate = float(arrivals["rate_per_s"])
        return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


def _permute_in_blocks(values, block, rng):
    """Strata interleaved into blocks: block b takes every (n/block)-th
    quantile, so each block spans the whole distribution; the seed permutes
    the block's order."""
    n = len(values)
    if not block or block >= n:
        return [values[i] for i in rng.permutation(n)]
    n_blocks = math.ceil(n / block)
    out = []
    for b in range(n_blocks):
        members = values[b::n_blocks]
        out += [members[i] for i in rng.permutation(len(members))]
    return out


def segment_count(seg, arrivals, seconds):
    if "count" in seg:
        return int(seg["count"])
    length = seconds if seg["length_s"] == "seconds" else float(seg["length_s"])
    per_s = seg.get("requests_per_s", arrivals.get("rate_per_s"))
    return max(1, int(round(float(per_s) * length)))


def build(traffic, seed, seconds):
    """The schedule as a list of dicts, in due order:
    {"i", "segment", "due_s" (from the first segment's start), "prompt_len",
    "output_len", "token_seed"}. Segment k starts where segment k-1's
    nominal length ends."""
    rng = np.random.RandomState(seed % (2 ** 32))
    starts = segment_starts(traffic, seconds)
    out = []
    for seg in traffic["segments"]:
        t_seg = starts[seg["name"]]
        n = segment_count(seg, traffic["arrivals"], seconds)
        block = traffic.get("stratify_block")
        prompts = _permute_in_blocks(_quantiles(traffic["prompt_len"], n),
                                     block, rng)
        outputs = _permute_in_blocks(_quantiles(traffic["output_len"], n),
                                     block, rng)
        gaps = _permute_in_blocks(_gaps(traffic["arrivals"], n), block, rng)
        # a request is due at the middle of its gap, so the first is not
        # always at the segment's very start and the sum stays the length
        due = np.cumsum(gaps) - 0.5 * np.asarray(gaps)
        for j in range(n):
            out.append({"i": len(out), "segment": seg["name"],
                        "due_s": t_seg + float(due[j]),
                        "prompt_len": prompts[j], "output_len": outputs[j],
                        "token_seed": int(rng.randint(0, 2 ** 31 - 1))})
    out.sort(key=lambda r: (r["due_s"], r["i"]))
    return out


def segment_starts(traffic, seconds):
    """{segment name: start, in seconds from the first segment's start}."""
    starts, t = {}, 0.0
    for seg in traffic["segments"]:
        starts[seg["name"]] = t
        t += float(seconds) if seg["length_s"] == "seconds" \
            else float(seg["length_s"])
    return starts


def prompt_tokens(token_seed, length, vocab):
    """Token ids of one request; 0 is left out (pad id in most vocabs)."""
    return np.random.RandomState(token_seed).randint(
        1, vocab, (length,)).astype(np.int32)


def digest(schedule):
    """sha256 over the schedule's canonical JSON: one seed, one digest."""
    blob = json.dumps(schedule, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]
