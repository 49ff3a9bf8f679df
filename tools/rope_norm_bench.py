"""The per-head q/k RMSNorm + RoPE of models/rope.py `norm_rope` on the chip,
isolated, at a cell's projections: the `jax.numpy` composition
(`apply_rope(_rms(...))`, what every call was before PR 37 and what other
shapes and backends still run), the Pallas kernel pair
(ops/pallas/rope_norm.py) and, for the choice between them, the same
mathematics as `jax.numpy` with `jnp.roll` and the signed sine table.

    chiprun -- python tools/rope_norm_bench.py [--sweep] \
        [--out chiprun_out/rope_norm.json]
    chiprun -- python tools/rope_norm_bench.py --shapes 2:16384:32 2:16384:4

`--shapes` are batch:positions:heads of a projection at `--head-dim`; the
defaults are the Mellum cell's q and k (2 x 16,384 x 32 and x 4 heads) and
the Brumby cell's (1 x 16,384 x 40 and x 8). For each: milliseconds of the
forward and of the gradients (of x and of the weight, from a seeded
cotangent; the forward they need is inside), the share of the memory
system's peak that is (a forward reads and writes the projection once, a
backward reads it and the cotangent and writes one gradient), how many
entries of each forward differ from the composition's and by how many bf16
steps at most (from the composition compiled without XLA's excess
precision, which makes every rounding it writes, and from the one a step
compiles, which may keep float32 between two), and each path's gradients
against the composition's evaluated in float32. `--sweep` times every row
tile x heads a block the kernels can be built at (a refusal of Mosaic's
reads as its error): `_ROWS` and `_HEADS` in ops/pallas/rope_norm.py were
read off it. A projection enters as the models hand it over, (batch, T,
heads, d): the times of the kernels' path hold the relayout to and from
(tokens, heads x d) that a step's neighbours may absorb (the forward
kernel alone, tables made before, reads and writes the Mellum q in 0.97
ms where this tool reads 2.59).
Prints one JSON object; ms are medians of `--repeat` timings of `--calls`
calls each, back to back.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(TOOLS), TOOLS]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from moe_rows_bench import timed  # noqa: E402  (the tools' one timer)
from paddle_tpu.generation import _rms  # noqa: E402
from paddle_tpu.models import rope  # noqa: E402
from paddle_tpu.ops.pallas import rope_norm  # noqa: E402

HBM_BYTES_PER_S = 819e9         # TPU v5e (benchmark/harness/peaks.json)
_F32 = jnp.float32


def jnp_forward(x, weight, cos, sin, eps):
    """ops/pallas/rope_norm.py `forward` as jax.numpy: the rotation a
    `jnp.roll` of each head's lanes."""
    d = weight.shape[0]
    u = x.reshape(x.shape[0] // cos.shape[0], cos.shape[0], -1, d)
    y = _rms(u, weight, eps).astype(_F32)
    out = y * cos[:, None] + jnp.roll(y, d // 2, axis=-1) * sin[:, None]
    return out.astype(x.dtype).reshape(x.shape)


def jnp_backward(x, weight, cos, sin, g, eps):
    """ops/pallas/rope_norm.py `backward` as jax.numpy, float32 throughout."""
    d = weight.shape[0]
    shape = (x.shape[0] // cos.shape[0], cos.shape[0], -1, d)
    u, g = x.reshape(shape).astype(_F32), g.reshape(shape).astype(_F32)
    r = jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
    n = u * r
    dy = g * cos[:, None] + jnp.roll(g * sin[:, None], d // 2, axis=-1)
    dn = dy * weight.astype(_F32)
    du = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
    return du.astype(x.dtype).reshape(x.shape), jnp.sum(dy * n, (0, 1, 2))


def under_the_vjp(forward, backward):
    """norm_rope(x, weight, eps, inv_freq, factor) through a `custom_vjp`
    wired as models/rope.py wires the kernels', with these two in their
    place."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 4))
    def call(x, weight, eps, inv_freq, factor):
        return fwd(x, weight, eps, inv_freq, factor)[0]

    def fwd(x, weight, eps, inv_freq, factor):
        tables = rope._rotation_tables(x.shape[1], inv_freq, factor)
        out = forward(rope._as_rows(x), weight, *tables, eps)
        return out.reshape(x.shape), (x, weight, inv_freq)

    def bwd(eps, factor, saved, g):
        x, weight, inv_freq = saved
        tables = rope._rotation_tables(x.shape[1], inv_freq, factor)
        dx, dw = backward(rope._as_rows(x), weight, *tables,
                          rope._as_rows(g), eps)
        return (dx.reshape(x.shape), dw.astype(weight.dtype),
                jnp.zeros_like(inv_freq))

    call.defvjp(fwd, bwd)
    return call


def paths(tile=None, heads=None):
    """{name: norm_rope(x, weight, eps, inv_freq, factor)} of the three; the
    kernels at the tiles they choose are the models' own call."""
    if tile is None and heads is None:
        def kernels(x, w, eps, inv, f):
            return rope._norm_rope_in_vmem(x, w, inv, eps, f)
    else:
        kernels = under_the_vjp(
            functools.partial(rope_norm.forward, tile=tile, heads=heads),
            functools.partial(rope_norm.backward, tile=tile, heads=heads))
    return {
        "composition": lambda x, w, eps, inv, f: rope.apply_rope(
            _rms(x, w, eps), inv, f),
        "kernels": kernels,
        "jnp_roll": under_the_vjp(jnp_forward, jnp_backward)}


def two_calls(fn, a, inv, factor):
    """(forward, gradients) of one path, jitted, of (x, weight, cotangent)."""
    def f(x, w):
        return fn(x, w, a.eps, inv, factor)
    return (jax.jit(lambda x, w, g: f(x, w)),
            jax.jit(lambda x, w, g: jax.vjp(f, x, w)[1](g)))


@jax.jit
def bf16_steps(got, ref):
    """The largest difference in bf16 steps at the size of the rotated pair
    an entry belongs to (a rotation keeps a pair's norm, and an entry that
    the pair's terms nearly cancel in is small beside what was rounded),
    and how many entries differ at all."""
    halves = ref.shape[:-1] + (2, ref.shape[-1] // 2)
    got = got.astype(_F32).reshape(halves)
    ref = ref.astype(_F32).reshape(halves)
    pair = jnp.sqrt(jnp.sum(ref ** 2, axis=-2, keepdims=True))
    step = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(pair, 1e-30))) - 7)
    return jnp.max(jnp.abs(got - ref) / step), jnp.sum(got != ref)


def relative(got, ref):
    got, ref = jnp.asarray(got, _F32), jnp.asarray(ref, _F32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


def shape_report(a, batch, positions, heads, key, inv, factor):
    kx, kw, kg = jax.random.split(key, 3)
    bf = jnp.bfloat16
    x = jax.random.normal(kx, (batch, positions, heads, a.head_dim), bf)
    w = (1 + 0.1 * jax.random.normal(kw, (a.head_dim,))).astype(bf)
    g = jax.random.normal(kg, x.shape, bf)
    moved = {"fwd": 2 * x.nbytes, "grad": 3 * x.nbytes}
    out = {"batch": batch, "positions": positions, "heads": heads,
           "projection_mib": round(x.nbytes / 2**20, 1)}
    ref_fwd, ref_grad = two_calls(paths()["composition"], a, inv, factor)
    exact = ref_grad(x.astype(_F32), w.astype(_F32), g.astype(_F32))
    # every rounding the composition writes is made, where XLA may by
    # default keep float32 between two of them
    written = ref_fwd.lower(x, w, g).compile(
        compiler_options={"xla_allow_excess_precision": False})(x, w, g)
    compiled = ref_fwd(x, w, g)
    for name, fn in paths().items():
        fwd, grad = two_calls(fn, a, inv, factor)
        got = out[name] = {}
        for what, call in (("fwd", fwd), ("grad", grad)):
            ms = timed(call, (x, w, g), a.calls, a.repeat)
            got[what + "_ms"] = round(ms, 4)
            got[what + "_share_of_hbm_peak"] = round(
                moved[what] / HBM_BYTES_PER_S / (ms / 1e3), 3)
        for against, ref in (("as_written", written),
                             ("compiled", compiled)):
            most, differ = bf16_steps(fwd(x, w, g), ref)
            got[f"fwd_bf16_steps_from_composition_{against}"] = float(most)
            got[f"fwd_entries_that_differ_{against}"] = int(differ)
        dx, dw = grad(x, w, g)
        got["dx_against_float32"] = relative(dx, exact[0])
        got["dw_against_float32"] = relative(dw, exact[1])
    if a.sweep:
        found = out["sweep"] = {}
        for tile in (128, 256, 512, 1024, 2048):
            for block in (1, 2, 4, 8, 16):
                if heads % block or positions % tile:
                    continue
                try:
                    fwd, grad = two_calls(paths(tile, block)["kernels"], a,
                                          inv, factor)
                    found[f"{tile}x{block}"] = {
                        "fwd_ms": round(timed(fwd, (x, w, g), a.calls,
                                              a.repeat), 4),
                        "grad_ms": round(timed(grad, (x, w, g), a.calls,
                                               a.repeat), 4)}
                except Exception as e:  # noqa: BLE001 — Mosaic's refusal
                    found[f"{tile}x{block}"] = {
                        "error": (str(e).splitlines() or [repr(e)])[0][:120]}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=[
        "2:16384:32", "2:16384:4", "1:16384:40", "1:16384:8"],
        help="batch:positions:heads of each projection")
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--theta", type=float, default=1e6)
    ap.add_argument("--yarn-factor", type=float, default=0.0,
                    help="above 1: YaRN's frequencies and attention factor "
                         "over 8,192 original positions")
    ap.add_argument("--eps", type=float, default=1e-6)
    ap.add_argument("--sweep", action="store_true",
                    help="also time every row tile x heads a block")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="a smoke run off the chip: the kernels interpreted, "
                         "the times worth nothing")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.cpu:
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    params = {"rope_type": "default", "rope_theta": a.theta}
    if a.yarn_factor > 1:
        params.update(rope_type="yarn", factor=a.yarn_factor,
                      original_max_position_embeddings=8192)
    inv, factor = rope.rope_frequencies(params, a.head_dim)
    key = jax.random.key(a.seed)
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "head_dim": a.head_dim, "rope": params,
        "kernels_built_at": {"rows": rope_norm._ROWS,
                             "heads": rope_norm._HEADS},
        "shapes": [shape_report(a, *map(int, s.split(":")), key, inv, factor)
                   for s in a.shapes]}
    text = json.dumps(result, indent=1)
    print(text)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
