"""Per-head RMSNorm, its weight and rotate-half RoPE as one pass over a
projection, forward and backward, for models/rope.py `norm_rope`.

A projection x is (tokens, heads x d), head h in lanes h d .. (h + 1) d - 1,
and token t of a sequence stands at position t mod positions. For one head's
row u, with r = rsqrt(mean(u^2) + eps):

    y   = round(round(u r) w)                  the norm, rounded to x's type
                                               where generation._rms rounds
    out = round(y cos + roll(y, d / 2) sin)    sin with the first half's sign

At a head_dim that is a multiple of 128 the rotate-half pairing is a
rotation of whole lane registers (ops/pallas/power_retention.py rotates
lanes the same way), so nothing is split, concatenated or kept in float32
outside VMEM: `normrope_fwd` reads a tile once and writes it once,
`normrope_bwd` reads the cotangent and the RAW projection and writes one
gradient, with the weight's gradient as float32 partial sums a row tile.
The backward is float32 throughout and rounds once:

    dy = g cos + roll(g sin, d / 2)            the rotation's transpose
    dw = sum over rows and heads of dy (u r)
    du = r (dy w - (u r) mean(dy w (u r)))     RMSNorm's chain rule

The tables (positions, d) are float32 operands made by the caller; their
block follows the row tile alone and the heads are the grid's inner axis, so
a tile of them is fetched once for all heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default

__all__ = ["forward", "backward", "row_tile", "ROW_MULTIPLE"]

_F32 = jnp.float32
# rows a grid step at most, and heads a block at most. Measured on the chip
# (tools/rope_norm_bench.py --sweep; PERF.md section 6, PR 37): from 512 x 4
# up every tiling is within 3% of the best, and at 512 x 8 both kernels fit
# Mosaic's default VMEM limit at every shape (1,024 x 8 does not: the
# backward holds three blocks of the projection's size twice)
_ROWS = 512
_HEADS = 8
# bf16's sublane tile: a row tile is a multiple of it
ROW_MULTIPLE = 16


def row_tile(positions, most=_ROWS):
    """Rows a grid step: the largest multiple of `ROW_MULTIPLE` up to `most`
    that divides the positions of a sequence (a tile of the tables is then a
    block of them), or None where there is none."""
    for tile in range(min(most, positions) // ROW_MULTIPLE * ROW_MULTIPLE, 0,
                      -ROW_MULTIPLE):
        if positions % tile == 0:
            return tile
    return None


def _heads_a_block(heads, most=_HEADS):
    return max(n for n in range(1, min(most, heads) + 1) if heads % n == 0)


def _head_lanes(ref, j, d):
    return ref[:, j * d:(j + 1) * d].astype(_F32)


def _fwd_kernel(x_ref, w_ref, cos_ref, sin_ref, out_ref, *, eps):
    d = w_ref.shape[1]
    dtype = out_ref.dtype
    w, cos, sin = w_ref[...].astype(_F32), cos_ref[...], sin_ref[...]
    for j in range(x_ref.shape[1] // d):
        u = _head_lanes(x_ref, j, d)
        r = jax.lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + eps)
        y = (u * r).astype(dtype).astype(_F32)
        y = (y * w).astype(dtype).astype(_F32)
        out_ref[:, j * d:(j + 1) * d] = (
            y * cos + pltpu.roll(y, d // 2, axis=1) * sin).astype(dtype)


def _bwd_kernel(x_ref, w_ref, cos_ref, sin_ref, g_ref, dx_ref, dw_ref, *,
                eps):
    d = w_ref.shape[1]
    w, cos, sin = w_ref[...].astype(_F32), cos_ref[...], sin_ref[...]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw = jnp.zeros_like(dw_ref)
    for j in range(x_ref.shape[1] // d):
        u = _head_lanes(x_ref, j, d)
        r = jax.lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + eps)
        n = u * r
        g = _head_lanes(g_ref, j, d)
        dy = g * cos + pltpu.roll(g * sin, d // 2, axis=1)
        dw = dw + jnp.sum(dy * n, axis=0, keepdims=True)
        dn = dy * w
        du = r * (dn - n * jnp.mean(dn * n, axis=1, keepdims=True))
        dx_ref[:, j * d:(j + 1) * d] = du.astype(dx_ref.dtype)
    dw_ref[...] += dw


def _check(x, weight, cos, sin):
    d = weight.shape[0]
    if d % 128 or x.shape[1] % d:
        raise ValueError(f"projection {x.shape}, head_dim {d}: a rotation "
                         "is a lane rotation only at a multiple of 128")
    if cos.shape != sin.shape or cos.shape[1] != d \
            or x.shape[0] % cos.shape[0]:
        raise ValueError(f"tables {cos.shape}, {sin.shape} against "
                         f"{x.shape} at head_dim {d}")


# Each call is jitted on everything its kernel is built from (every default
# is resolved before), so that a model's layers, and a layer's forward,
# recomputation and backward, trace and lower each kernel once a signature
# (sixteen forward calls a step in the Mellum cell, two signatures).
_STATIC = ("eps", "tile", "heads", "interpret")


def _built_from(x, weight, cos, eps, tile, heads, interpret):
    tile = row_tile(cos.shape[0]) if tile is None else tile
    if tile is None or cos.shape[0] % tile:
        raise ValueError(f"no row tile divides {cos.shape[0]} positions")
    return dict(
        eps=float(eps), tile=tile,
        heads=_heads_a_block(x.shape[1] // weight.shape[0])
        if heads is None else heads,
        interpret=_interpret_default() if interpret is None else interpret)


def _specs(x, d, positions, tile, heads):
    """The grid (row tiles, head blocks), and the blocks of a projection,
    of the weight and of a table."""
    grid = (x.shape[0] // tile, x.shape[1] // (heads * d))
    tiles_a_sequence = positions // tile
    return grid, (
        pl.BlockSpec((tile, heads * d), lambda i, h: (i, h)),
        pl.BlockSpec((1, d), lambda i, h: (0, 0)),
        pl.BlockSpec((tile, d), lambda i, h: (i % tiles_a_sequence, 0)))


def forward(x, weight, cos, sin, eps, *, tile=None, heads=None,
            interpret=None):
    """x (tokens, heads x d) and weight (d,) of one type, cos and sin
    (positions, d) float32 with tokens a multiple of positions -> the
    normalised, weighted and rotated projection, in x's type."""
    _check(x, weight, cos, sin)
    return _fwd_call(x, weight, cos, sin,
                     **_built_from(x, weight, cos, eps, tile, heads,
                                   interpret))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(x, weight, cos, sin, *, eps, tile, heads, interpret):
    d = weight.shape[0]
    grid, (rows, one, table) = _specs(x, d, cos.shape[0], tile, heads)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[rows, one, table, table],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="normrope_fwd",
    )(x, weight.reshape(1, d), cos, sin)


def backward(x, weight, cos, sin, g, eps, *, tile=None, heads=None,
             interpret=None):
    """The cotangents (of x, in its type; of the weight, float32 (d,)) from
    the cotangent g of `forward`'s output and the projection itself."""
    _check(x, weight, cos, sin)
    if g.shape != x.shape:
        raise ValueError(f"cotangent {g.shape} of a projection {x.shape}")
    return _bwd_call(x, weight, cos, sin, g,
                     **_built_from(x, weight, cos, eps, tile, heads,
                                   interpret))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(x, weight, cos, sin, g, *, eps, tile, heads, interpret):
    d = weight.shape[0]
    grid, (rows, one, table) = _specs(x, d, cos.shape[0], tile, heads)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=grid,
        in_specs=[rows, one, table, table, rows],
        out_specs=[rows, pl.BlockSpec((None, 1, d), lambda i, h: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((grid[0], 1, d), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="normrope_bwd",
    )(x, weight.reshape(1, d), cos, sin, g)
    return dx, jnp.sum(dw, axis=(0, 1))
