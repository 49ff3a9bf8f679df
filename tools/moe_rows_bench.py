"""XLA's grouped-matmul kernel (`lax.ragged_dot`) on the chip, isolated, at
the routed experts' published widths: how a call's time depends on the
length of the sorted buffer and on the rows in each group.

    chiprun -- python tools/moe_rows_bench.py [--out chiprun_out/moe_rows.json]

One layer's two products (x W_in: 9 x 4096 x 1536; act W_out: 9 x 768 x
4096), forward and the backward's two products each (the rows' gradient,
the weights' gradient), for buffers of [rows] of which `live` hold an
assignment, in nine groups drawn as routing draws them (top-10 of 72
seeded logits a token, experts 0-8 held): 2,048 tokens' groups in the
worst-case buffer of a block and in one a quarter as long, 8,192 tokens'
groups in two lengths. Then the pieces of `parallel/moe.py dropless_moe`
that are no expert work, for one block's tokens and for all four blocks'
at once: the row gathers are the device's time, the small operations'
milliseconds are mostly the host's launches (in a traced step a top-k or
a sort is 0.02 ms). Prints one JSON object; ms are medians of `--repeat`
timings of `--calls` calls each, back to back.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.parallel import moe  # noqa: E402

D, INTER, HELD, EXPERTS, TOP_K = 4096, 768, 9, 72, 10


def timed(fn, args, calls, repeat):
    """Median milliseconds of one call of the jitted `fn`."""
    out = fn(*args)
    jax.block_until_ready(out)
    took = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        took.append((time.perf_counter() - t0) / calls * 1e3)
    return float(np.median(took))


def group_sizes(tokens, seed):
    """Rows of each held expert when `tokens` tokens are routed by seeded
    logits: what the benchmark's cell sees at seeded weights."""
    logits = jax.random.normal(jax.random.key(seed), (tokens, EXPERTS))
    _, ids = jax.lax.top_k(logits, TOP_K)
    return moe.sorted_assignments(ids, (0, HELD))[2]


def products(rows, sizes, calls, repeat, key):
    """ms and TFLOP/s on live rows of the six grouped products."""
    live = int(jnp.sum(sizes))
    k1, k2, k3, k4 = jax.random.split(key, 4)
    bf = jnp.bfloat16
    x = jax.random.normal(k1, (rows, D), bf)
    act = jax.random.normal(k2, (rows, INTER), bf)
    w_in = jax.random.normal(k3, (HELD, D, 2 * INTER), bf) * 0.02
    w_out = jax.random.normal(k4, (HELD, INTER, D), bf) * 0.02
    out = {"rows": rows, "live": live,
           "group_rows": [int(s) for s in sizes]}
    for name, lhs, w in (("x_w_in", x, w_in), ("act_w_out", act, w_out)):
        fwd = jax.jit(lambda a, b: jax.lax.ragged_dot(a, b, sizes))
        g = jnp.ones((rows, w.shape[2]), bf)
        d_lhs = jax.jit(lambda a, b, c: jax.vjp(
            lambda t: jax.lax.ragged_dot(t, b, sizes), a)[1](c)[0])
        d_w = jax.jit(lambda a, b, c: jax.vjp(
            lambda t: jax.lax.ragged_dot(a, t, sizes), b)[1](c)[0])
        flops = 2.0 * live * w.shape[1] * w.shape[2]
        for what, fn, args in (("fwd", fwd, (lhs, w)),
                               ("d_rows", d_lhs, (lhs, w, g)),
                               ("d_weights", d_w, (lhs, w, g))):
            ms = timed(fn, args, calls, repeat)
            out[f"{name}.{what}"] = {
                "ms": round(ms, 4),
                "tflops_live": round(flops / ms / 1e9, 2)}
    return out


def route_pieces(tokens, calls, repeat, key):
    """ms of what `pt.moe.route` holds, forward, one call each."""
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (tokens, D), jnp.bfloat16)
    router = jax.random.normal(k2, (D, EXPERTS), jnp.bfloat16) * 0.02
    held = (0, HELD)
    ids, _ = jax.jit(lambda a, b: moe.route_top_k(a, b, TOP_K))(x, router)
    source, slot, sizes = jax.jit(
        lambda i: moe.sorted_assignments(i, held))(ids)
    token = source // TOP_K
    y = jax.random.normal(k1, (source.shape[0], D), jnp.bfloat16)
    out = {"tokens": tokens, "rows": int(source.shape[0]),
           "live": int(jnp.sum(sizes))}
    for name, fn, args in (
            ("route_top_k", jax.jit(
                lambda a, b: moe.route_top_k(a, b, TOP_K)), (x, router)),
            ("sorted_assignments", jax.jit(
                lambda i: moe.sorted_assignments(i, held)), (ids,)),
            ("dispatch_gather", jax.jit(moe._dispatch), (x, token, slot)),
            ("combine_gathers", jax.jit(moe._combine), (y, token, slot))):
        out[name] = round(timed(fn, args, calls, repeat), 4)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    key = jax.random.key(args.seed)
    block_sizes = group_sizes(2048, args.seed)
    whole_sizes = group_sizes(8192, args.seed)
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "products": [
            products(2048 * HELD, block_sizes, args.calls, args.repeat, key),
            products(4096, block_sizes, args.calls, args.repeat, key),
            products(16384, whole_sizes, args.calls, args.repeat, key),
            products(12288, whole_sizes, args.calls, args.repeat, key)],
        "route": [route_pieces(2048, args.calls, args.repeat, key),
                  route_pieces(8192, args.calls, args.repeat, key)],
    }
    text = json.dumps(result, indent=1)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
