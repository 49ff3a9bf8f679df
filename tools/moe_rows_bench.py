"""XLA's grouped-matmul kernel (`lax.ragged_dot`) on the chip, isolated, at
the routed experts' published widths: how a call's time depends on the
length of the sorted buffer and on the rows in each group.

    chiprun -- python tools/moe_rows_bench.py [--out chiprun_out/moe_rows.json]
    chiprun -- python tools/moe_rows_bench.py --hidden 2304 --inter 896 \
        --held 16 --experts 64 --top-k 8 --tokens 4096 8192 16384 32768

One layer's two products (x W_in: held x hidden x 2 inter; act W_out: held
x inter x hidden), forward and the backward's two products each (the rows'
gradient, the weights' gradient), for buffers of [rows] of which `live`
hold an assignment, in `--held` groups drawn as routing draws them
(top-k of `--experts` seeded logits a token, the first `--held` held).
`--tokens` names the calls: `tokens` routes that many tokens into the
buffer `dropless_moe` gives them (tokens x min(top-k, held) rows),
`tokens:rows` into one of `rows`. The defaults are granite's widths and
PR 29's table (9 x 4096 x 1536 and 9 x 768 x 4096, top-10 of 72: 2,048
tokens' groups in the worst-case buffer of a block and in one a quarter
as long, 8,192 tokens' groups in two lengths). Then, for each count of
tokens, the pieces of `parallel/moe.py dropless_moe` that are no expert
work (the row gathers are the device's time, the small operations'
milliseconds are mostly the host's launches: in a traced step a top-k or
a sort is 0.02 ms), and the whole layer, forward and backward, over the
largest count in rematerialised blocks of each count, as a model's step
runs it (`models/sub_block.py over_token_blocks`). Each product is timed
through `lax.ragged_dot` and, where `moe.grouped_matmul` chooses jax's
Pallas grouped matmul for the widths, through that (`chosen`); `--sweep`
adds every candidate tiling of that kernel, which is what `_GMM_TILES`
in parallel/moe.py was read off. Prints one JSON object; ms are medians
of `--repeat` timings of `--calls` calls each, back to back.
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

from paddle_tpu.models.sub_block import over_token_blocks  # noqa: E402
from paddle_tpu.parallel import moe  # noqa: E402


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


def group_sizes(a, tokens):
    """Rows of each held expert when `tokens` tokens are routed by seeded
    logits: what the benchmark's cell sees at seeded weights."""
    logits = jax.random.normal(jax.random.key(a.seed), (tokens, a.experts))
    _, ids = jax.lax.top_k(logits, a.top_k)
    return moe.sorted_assignments(ids, (0, a.held))[2]


def weights(a, key):
    """(w_in, w_out) of the held experts, bf16, N(0, 0.02)."""
    k1, k2 = jax.random.split(key)
    bf = jnp.bfloat16
    return (jax.random.normal(k1, (a.held, a.hidden, 2 * a.inter), bf) * 0.02,
            jax.random.normal(k2, (a.held, a.inter, a.hidden), bf) * 0.02)


def operands(a, tokens, rows, key):
    """(sizes, live rows, [(name, lhs, w, cotangent)] of the two products)
    for `tokens` routed tokens in a buffer of `rows`."""
    sizes = group_sizes(a, tokens)
    k1, k2, k3 = jax.random.split(key, 3)
    bf = jnp.bfloat16
    w_in, w_out = weights(a, k3)
    return sizes, int(jnp.sum(sizes)), [
        ("x_w_in", jax.random.normal(k1, (rows, a.hidden), bf), w_in,
         jnp.ones((rows, 2 * a.inter), bf)),
        ("act_w_out", jax.random.normal(k2, (rows, a.inter), bf), w_out,
         jnp.ones((rows, a.hidden), bf))]


def three_products(product, sizes):
    """{forward, rows' gradient, weights' gradient} of `product(lhs, w,
    sizes)`, each jitted, each of (lhs, w, cotangent)."""
    return {
        "fwd": jax.jit(lambda l, r, c: product(l, r, sizes)),
        "d_rows": jax.jit(lambda l, r, c: jax.vjp(
            lambda t: product(t, r, sizes), l)[1](c)[0]),
        "d_weights": jax.jit(lambda l, r, c: jax.vjp(
            lambda t: product(l, t, sizes), r)[1](c)[0])}


def products(a, tokens, rows, key):
    """ms and TFLOP/s on live rows of the six grouped products through
    `lax.ragged_dot` (XLA's kernel) and, where it chooses another
    (`chosen`), through what `dropless_moe` calls, `moe.grouped_matmul`."""
    sizes, live, both = operands(a, tokens, rows, key)
    out = {"tokens": tokens, "rows": rows, "live": live,
           "group_rows": [int(s) for s in sizes]}
    paths = {"ragged_dot": jax.lax.ragged_dot}
    if moe._gmm_tiles(*both[0][1:3]) or moe._gmm_tiles(*both[1][1:3]):
        paths["chosen"] = moe.grouped_matmul
    for path, product in paths.items():
        total_ms = total_flops = 0.0
        for name, lhs, w, g in both:
            flops = 2.0 * live * w.shape[1] * w.shape[2]
            for what, fn in three_products(product, sizes).items():
                ms = timed(fn, (lhs, w, g), a.calls, a.repeat)
                total_ms, total_flops = total_ms + ms, total_flops + flops
                out.setdefault(f"{name}.{what}", {})[path] = {
                    "ms": round(ms, 4),
                    "tflops_live": round(flops / ms / 1e9, 2)}
        out.setdefault("all_six", {})[path] = {
            "ms": round(total_ms, 4),
            "tflops_live": round(total_flops / total_ms / 1e9, 2)}
    return out


def sweep(a, tokens, rows, key):
    """ms of each of the six products through jax's Pallas grouped matmul
    (megablox `gmm`, `tgmm`) at every candidate tiling: rows of 128, 256,
    512 (and 1,024 for the weights' gradient), k and n whole or halved
    where the half is whole 128-lane tiles. `_GMM_TILES` in
    parallel/moe.py holds each product's fastest; a tiling that Mosaic
    refuses (VMEM) reads as its error."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    sizes, live, both = operands(a, tokens, rows, key)
    bf = jnp.bfloat16
    out = {"tokens": tokens, "rows": rows, "live": live}
    for name, lhs, w, g in both:
        k, n = w.shape[1:]
        flops = 2.0 * live * k * n
        calls = {
            "fwd": (k, n, lambda t: jax.jit(
                lambda l, r, c: gmm(l, r, sizes, bf, t))),
            "d_rows": (n, k, lambda t: jax.jit(
                lambda l, r, c: gmm(c, r, sizes, bf, t, transpose_rhs=True))),
            "d_weights": (k, n, lambda t: jax.jit(
                lambda l, r, c: tgmm(l.swapaxes(0, 1), c, sizes, bf, t)))}
        for what, (tk_of, tn_of, make) in calls.items():
            found = out[f"{name}.{what}"] = {}
            row_tiles = (256, 512, 1024) if what == "d_weights" \
                else (128, 256, 512)
            for tiles in [(tm, tk, tn) for tm in row_tiles
                          for tk in (tk_of, tk_of // 2) if tk % 128 == 0
                          for tn in (tn_of, tn_of // 2) if tn % 128 == 0]:
                try:
                    ms = timed(make(tiles), (lhs, w, g), a.calls, a.repeat)
                    found[str(tiles)] = {
                        "ms": round(ms, 4),
                        "tflops_live": round(flops / ms / 1e9, 2)}
                except Exception as e:  # noqa: BLE001 — Mosaic's refusal
                    found[str(tiles)] = {
                        "error": (str(e).splitlines() or [repr(e)])[0][:120]}
    return out


def route_pieces(a, tokens, key):
    """ms of what `pt.moe.route` holds, forward, one call each."""
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (tokens, a.hidden), jnp.bfloat16)
    router = jax.random.normal(
        k2, (a.hidden, a.experts), jnp.bfloat16) * 0.02
    held, top_k = (0, a.held), a.top_k
    ids, _ = jax.jit(lambda t, w: moe.route_top_k(t, w, top_k))(x, router)
    source, slot, sizes = jax.jit(
        lambda i: moe.sorted_assignments(i, held))(ids)
    token = source // top_k
    y = jax.random.normal(k1, (source.shape[0], a.hidden), jnp.bfloat16)
    out = {"tokens": tokens, "rows": int(source.shape[0]),
           "live": int(jnp.sum(sizes))}
    for name, fn, args in (
            ("route_top_k", jax.jit(
                lambda t, w: moe.route_top_k(t, w, top_k)), (x, router)),
            ("sorted_assignments", jax.jit(
                lambda i: moe.sorted_assignments(i, held)), (ids,)),
            ("dispatch_gather", jax.jit(moe._dispatch), (x, token, slot)),
            ("combine_gathers", jax.jit(moe._combine), (y, token, slot))):
        out[name] = round(timed(fn, args, a.calls, a.repeat), 4)
    return out


def layer(a, tokens, block, key):
    """ms of `dropless_moe` forward and backward over `tokens` tokens in
    rematerialised blocks of `block`, the routing not differentiated, and
    the TFLOP/s that would be if the seven grouped products on the live
    rows (x W_in forward and recomputed, act W_out forward, the two
    backward products of each) were all of it."""
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (1, tokens, a.hidden), jnp.bfloat16)
    router = jax.random.normal(
        k2, (a.hidden, a.experts), jnp.bfloat16) * 0.02
    w_in, w_out = weights(a, k3)

    def loss(x, w_in, w_out):
        one = jax.checkpoint(lambda h: moe.dropless_moe(
            h.reshape(-1, a.hidden), router, w_in, w_out, a.top_k,
            (0, a.held), False).reshape(h.shape))
        return jnp.sum(over_token_blocks(one, x, block)
                       .astype(jnp.float32))

    ms = timed(jax.jit(jax.grad(loss, (0, 1, 2))), (x, w_in, w_out),
               max(1, a.calls // 8), a.repeat)
    live = int(jnp.sum(group_sizes(a, tokens)))
    flops = 2.0 * live * a.hidden * a.inter * (4 * 2 + 3)
    return {"tokens": tokens, "block": block, "ms": round(ms, 3),
            "tflops_live": round(flops / ms / 1e9, 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=4096)
    ap.add_argument("--inter", type=int, default=768)
    ap.add_argument("--held", type=int, default=9)
    ap.add_argument("--experts", type=int, default=72)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--tokens", nargs="+", default=[
        "2048", "2048:4096", "8192:16384", "8192:12288"],
        help="tokens, or tokens:rows of the buffer, of each call")
    ap.add_argument("--sweep", action="store_true",
                    help="also time the Pallas grouped matmul's tilings")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    key = jax.random.key(a.seed)
    shapes = []
    for entry in a.tokens:
        tokens, _, rows = entry.partition(":")
        shapes.append((int(tokens), int(rows) if rows
                       else int(tokens) * min(a.top_k, a.held)))
    counts = sorted({t for t, _ in shapes})
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "widths": {k: getattr(a, k) for k in
                   ("hidden", "inter", "held", "experts", "top_k")},
        "products": [products(a, t, rows, key) for t, rows in shapes],
        "route": [route_pieces(a, t, key) for t in counts],
        "layer": [layer(a, counts[-1], t, key) for t in counts],
    }
    if a.sweep:
        result["sweep"] = [sweep(a, t, rows, key) for t, rows in shapes]
    text = json.dumps(result, indent=1)
    print(text)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
