"""Power retention's products with an expansion, as Pallas kernels that make
the expansion a rotation at a time in VMEM and never write it out.

ops/power_retention.py lays phi(u) out as d / 2 + 1 rotations of d lanes,
phi(u)[r, i] = w_r u_i u_{(i + r) mod d}. At a head_dim that is a multiple
of 128 a rotation is a lane rotation of whole vector registers, so the
three products that have an expansion as an operand need no (rows,
features) array at all:

    read   phi(u) @ M          (n, d), (features, e) -> (n, e)
    write  phi(u)^T @ W        (n, d), (n, e)        -> (features, e)
    back   the chain rule through phi back to u: with G_r = dY @ M_r^T,
           du = sum_r w_r [G_r * roll(u, -r) + roll(G_r * u, +r)]

Each loops over the rotations, `_GROUP` of them a step: a step makes each
`u * roll(u, -r)` in float32 on the VPU, rounds it once to the operands'
type (where `_expand` rounds), and the group side by side is one operand
of an MXU product accumulated in float32: (rows, g d) x (g d, e) in
`read`, its transpose against (rows, e) in `write`, and in `back` one
(rows, e) x (e, g d) product gives the group's G_r. The normaliser stays
a column beside v and beside the state (e = 2 d): the numerator and the
normaliser see the same rounded expansion, and summing it on the VPU
instead was measured slower (PERF.md section 6, PR 33).

`phi_dot` and `phi_t_dot` are the two differentiable operations over them:
each one's backward is `back` for u and the other one's kernel for the
second operand, so every kernel serves the query side (`weighted`: the
rotations' weights 1/d, 2/d .. 2/d, 1/d) and the key side (bare).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default, _pad_to

__all__ = ["phi_dot", "phi_t_dot", "read", "write", "back"]

_F32 = jnp.float32
# rows of u resident a grid step, at most: their float32 copy, one group of
# rotations and the (rows, e) accumulator stay inside VMEM beside a whole
# state; measured on the chip (PERF.md section 6, PR 33), every kernel
# gains up to here
_ROWS = 2560
# rotations an MXU product takes at once (65 = 5 x 13 at d 128): side by
# side they are one operand (rows, _GROUP * d), so the MXU sums over them
# (`read`), streams them past one weight tile (`write`) or makes their
# cotangents in one pass (`back`)
_GROUP = 13
# Mosaic's limit for one call: the kernels need 24-28 MiB at the Brumby
# cell's shapes (a whole state, a tile of rows, a group's temporaries).
# No more than that: what a kernel may take XLA keeps free of the arrays it
# would hold in VMEM across the scan's other operations (PERF.md section
# 6, PR 33)
_VMEM_LIMIT = 32 << 20


def _rotations(d):
    return d // 2 + 1


def _weight(r, d):
    """The query side's weight of rotation r, with the 1 / d scale: the
    full square is rotation 0, twice each of 1 .. d/2 - 1, and d / 2."""
    return jnp.where((r == 0) | (r == d // 2), 1.0 / d, 2.0 / d)


def _ahead(u, r):
    """u_{(i + r) mod d} at lane i."""
    d = u.shape[1]
    return pltpu.roll(u, jax.lax.rem(d - r, d), axis=1)


def _rotation(u, r, weighted, dtype):
    """Rotation r of phi(u): u (rows, d) float32 -> (rows, d) in `dtype`,
    the product (and its weight) in float32, rounded once."""
    p = u * _ahead(u, r)
    if weighted:
        p = p * _weight(r, u.shape[1])
    return p.astype(dtype)


def _rows(first, size, d):
    """The rows of a (features, e) array that rotations first .. first +
    size - 1 stand against."""
    return pl.ds(pl.multiple_of(first * d, d), size * d)


def _group(u, first, size, weighted, dtype):
    """Rotations first .. first + size - 1 of phi(u) side by side:
    (rows, size * d) in `dtype`."""
    return jnp.concatenate([_rotation(u, first + j, weighted, dtype)
                            for j in range(size)], axis=1)


def _for_groups(d, group, body, carry=None):
    """body(first, size, carry) -> carry over all d / 2 + 1 rotations,
    `group` at a time in a loop, what is left over after it."""
    rot = _rotations(d)
    carry = jax.lax.fori_loop(
        0, rot // group, lambda i, c: body(i * group, group, c), carry)
    if rot % group:
        carry = body(rot - rot % group, rot % group, carry)
    return carry


def _read_kernel(u_ref, m_ref, out_ref, *, weighted, group):
    u = u_ref[...].astype(_F32)
    out_ref[...] = jnp.zeros_like(out_ref)

    def body(first, size, carry):
        p = _group(u, first, size, weighted, m_ref.dtype)
        out_ref[...] += jnp.dot(p, m_ref[_rows(first, size, u.shape[1]), :],
                                preferred_element_type=_F32)
        return carry

    _for_groups(u.shape[1], group, body)


def _write_kernel(u_ref, w_ref, out_ref, *, weighted, group):
    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...].astype(_F32)
    w = w_ref[...]

    def body(first, size, carry):
        p = _group(u, first, size, weighted, w.dtype)
        out_ref[_rows(first, size, u.shape[1]), :] += jax.lax.dot_general(
            p, w, (((0,), (0,)), ((), ())),            # p^T @ w
            preferred_element_type=_F32)
        return carry

    _for_groups(u.shape[1], group, body)


def _back_kernel(u_ref, dy_ref, m_ref, du_ref, *, weighted, group):
    d = u_ref.shape[1]
    u = u_ref[...].astype(_F32)
    dy = dy_ref[...]

    def body(first, size, du):
        g_all = jax.lax.dot_general(                 # dy @ m_rows^T
            dy, m_ref[_rows(first, size, d), :], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)
        for j in range(size):
            r = first + j
            g = g_all[:, j * d:(j + 1) * d]
            if weighted:
                g = g * _weight(r, d)
            # d / du_j of sum_i g_i u_i u_{i + r}: lane j as the first
            # factor, and as the second (i = j - r)
            du = du + g * _ahead(u, r) + pltpu.roll(g * u, r, axis=1)
        return du

    du = _for_groups(d, group, body, jnp.zeros_like(u))
    du_ref[...] = du.astype(du_ref.dtype)


def _row_tile(n):
    """Rows a grid step: the fewest steps of at most `_ROWS` rows, the
    rows spread evenly over them (a multiple of 16, bf16's sublane tile)."""
    steps = -(-n // _ROWS)
    return -(-n // (16 * steps)) * 16


# Each call is jitted on everything its kernel is built from (every default
# is resolved before), so that a model's layers, and a layer's forward,
# recomputation and backward, which call with a handful of signatures,
# trace and lower each kernel once a signature and not once a call (36
# calls a step in the Brumby cell, 6 signatures); XLA inlines them.
_STATIC = ("weighted", "interpret", "tile", "group")


def _built_from(u, weighted, interpret):
    return dict(weighted=weighted, tile=_row_tile(u.shape[0]), group=_GROUP,
                interpret=_interpret_default() if interpret is None
                else interpret)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _check(u, second, rows_of_second):
    n, d = u.shape
    if d % 128:
        raise ValueError(f"head_dim {d}: a rotation is a lane rotation only "
                         "at a multiple of 128")
    if second.shape[0] != rows_of_second or second.shape[1] % 128:
        raise ValueError(f"u {u.shape} against {second.shape}")


def read(u, m, weighted, interpret=None):
    """phi(u) @ m: u (n, d), m (features, e) in u's type -> (n, e) float32."""
    _check(u, m, _rotations(u.shape[1]) * u.shape[1])
    return _read_call(u, m, **_built_from(u, weighted, interpret))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _read_call(u, m, *, weighted, interpret, tile, group):
    n, d = u.shape
    e = m.shape[1]
    u_p = _pad_to(u, 0, tile)
    out = pl.pallas_call(
        functools.partial(_read_kernel, weighted=weighted, group=group),
        grid=(u_p.shape[0] // tile,),
        in_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0)),
                  pl.BlockSpec(m.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((tile, e), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((u_p.shape[0], e), _F32),
        compiler_params=_params("parallel"),
        interpret=interpret,
        name="retn_read",
    )(u_p, m)
    return out[:n]


def write(u, w, weighted, interpret=None):
    """phi(u)^T @ w: u (n, d), w (n, e) in u's type -> (features, e)
    float32, accumulated over the row grid. Rows padded to the tile are
    zero in u, so their expansion adds nothing."""
    _check(u, w, u.shape[0])
    return _write_call(u, w, **_built_from(u, weighted, interpret))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _write_call(u, w, *, weighted, interpret, tile, group):
    d, e = u.shape[1], w.shape[1]
    u_p, w_p = _pad_to(u, 0, tile), _pad_to(w, 0, tile)
    features = _rotations(d) * d
    return pl.pallas_call(
        functools.partial(_write_kernel, weighted=weighted, group=group),
        grid=(u_p.shape[0] // tile,),
        in_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0)),
                  pl.BlockSpec((tile, e), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((features, e), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((features, e), _F32),
        compiler_params=_params("arbitrary"),
        interpret=interpret,
        name="retn_write",
    )(u_p, w_p)


def back(u, dy, m, weighted, interpret=None):
    """The cotangent of u through y = phi(u) @ m: u (n, d), dy (n, e), m
    (features, e), one type -> (n, d) in that type. phi's own cotangent
    (n, features) never exists."""
    _check(u, m, _rotations(u.shape[1]) * u.shape[1])
    _check(u, dy, u.shape[0])
    return _back_call(u, dy, m, **_built_from(u, weighted, interpret))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _back_call(u, dy, m, *, weighted, interpret, tile, group):
    n, d = u.shape
    e = m.shape[1]
    u_p, dy_p = _pad_to(u, 0, tile), _pad_to(dy, 0, tile)
    du = pl.pallas_call(
        functools.partial(_back_kernel, weighted=weighted, group=group),
        grid=(u_p.shape[0] // tile,),
        in_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0)),
                  pl.BlockSpec((tile, e), lambda i: (i, 0)),
                  pl.BlockSpec(m.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((tile, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((u_p.shape[0], d), u.dtype),
        compiler_params=_params("parallel"),
        interpret=interpret,
        name="retn_back",
    )(u_p, dy_p, m)
    return du[:n]


# The backward's calls are traced when the surrounding scan is transposed,
# outside whatever scope the forward was called in: they enter the scan's
# scope themselves, so that the kernels' time on a trace stays with it.


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def phi_dot(u, m, weighted):
    """phi(u) @ m in float32; differentiable in u and m."""
    return read(u, m, weighted)


def _phi_dot_fwd(u, m, weighted):
    return read(u, m, weighted), (u, m)


def _phi_dot_bwd(weighted, saved, dy):
    u, m = saved
    dy = dy.astype(u.dtype)
    with jax.named_scope("pt.retn.scan"):
        return (back(u, dy, m, weighted),
                write(u, dy, weighted).astype(m.dtype))


phi_dot.defvjp(_phi_dot_fwd, _phi_dot_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def phi_t_dot(u, w, weighted):
    """phi(u)^T @ w in float32; differentiable in u and w."""
    return write(u, w, weighted)


def _phi_t_dot_fwd(u, w, weighted):
    return write(u, w, weighted), (u, w)


def _phi_t_dot_bwd(weighted, saved, dout):
    u, w = saved
    dout = dout.astype(u.dtype)
    with jax.named_scope("pt.retn.scan"):
        return (back(u, w, dout, weighted),
                read(u, dout, weighted).astype(w.dtype))


phi_t_dot.defvjp(_phi_t_dot_fwd, _phi_t_dot_bwd)
