"""Mamba-1's selective scan as two Pallas kernels that keep the state in VMEM
and walk along time.

For channels c and states n, with Delta = softplus(delta + delta_bias):

    h_t[n, c] = exp(Delta_t[c] A[c, n]) h_{t-1}[n, c] + Delta_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] h_t[n, c] + D[c] u_t[c]
    g_t[c]    = y_t[c] silu(z_t[c])

Every (channel, state) pair decays at its own rate, so the recurrence is
elementwise work along the sequence and no chunk of it is a matmul (as
ops/mamba2.py's SSD is). A grid step holds a tile of `_CHANNELS` channels'
state, (N, channels) float32 with the channels on the lanes, and walks its
rows of time one at a time, eight rows (a sublane tile) a loop step.

    selscan_fwd  g, and the state before every `_LANES` steps (the chunk
                 states, (batch, T / _LANES, N, E) float32)
    selscan_bwd  one chunk of `_LANES` steps a grid step, the chunks in
                 reverse: the chunk's states made again from its saved
                 start into VMEM, then the adjoint walked backwards through
                 them; du, d delta, dz, and dA, dD, d delta_bias summed
                 over time, dB and dC a channel tile's share

B and C come in time on the lanes, (batch, T / 128, N, 128): a step takes
its column by a one-hot lane select and a lane sum, which the VPU and the
cross-lane unit do without a relayout. Inputs are cast to float32 a block
at a time in VMEM; the arithmetic is float32 throughout.

`selective_scan` is the differentiable operation over the pair;
ops/selective_scan.py calls it where the shape allows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default

__all__ = ["selective_scan", "forward", "backward", "supported"]

_F32 = jnp.float32
# steps of time a chunk state stands before, and a backward grid step
# walks: one lane tile of B and C
_LANES = 128
# rows of time a forward grid step walks (whole lane tiles of B and C)
_FWD_ROWS = 512
# channels a grid step holds: the state (N, _CHANNELS) float32 and the
# backward's states of a chunk, (_LANES + 1, N, _CHANNELS), stay in VMEM
_CHANNELS = 512
# Mosaic's limit for one call: the backward needs about 8 MiB at N 16
_VMEM_LIMIT = 32 << 20


def supported(u, a):
    """Whether these operands run the kernels: on a TPU, whole lane tiles
    of channels and whole sublane tiles of states, bf16 or float32."""
    return (jax.default_backend() == "tpu"
            and u.shape[-1] % 128 == 0 and a.shape[-1] % 8 == 0
            and u.dtype in (jnp.bfloat16, jnp.float32))


def _channel_tile(e):
    return next(t for t in (_CHANNELS, 256, 128) if e % t == 0)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(x)))


def _column(block, lane):
    """Lane `lane` of an (N, 128) block as an (N, 1) column."""
    hot = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) == lane
    return jnp.sum(jnp.where(hot, block, 0.0), axis=1, keepdims=True)


def _fwd_kernel(u_ref, dl_ref, z_ref, at_ref, d_ref, bias_ref, bt_ref, ct_ref,
                g_ref, st_ref, h_ref, dt_ref, du_ref, y_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    u = u_ref[...].astype(_F32)
    dt = _softplus(dl_ref[...].astype(_F32) + bias_ref[...])
    dt_ref[...] = dt
    du_ref[...] = dt * u
    at = at_ref[...]

    def lane_tile(s, h):
        st_ref[s] = h                       # the state before step s * 128
        bt, ct = bt_ref[s], ct_ref[s]

        def rows(i, h):
            r0 = pl.multiple_of(s * _LANES + i * 8, 8)
            dts = dt_ref[pl.ds(r0, 8), :]
            dus = du_ref[pl.ds(r0, 8), :]
            ys = []
            for j in range(8):
                lane = i * 8 + j
                h = (jnp.exp(dts[j:j + 1] * at) * h
                     + _column(bt, lane) * dus[j:j + 1])
                ys.append(jnp.sum(_column(ct, lane) * h, axis=0,
                                  keepdims=True))
            y_ref[pl.ds(r0, 8), :] = jnp.concatenate(ys, axis=0)
            return h

        return jax.lax.fori_loop(0, _LANES // 8, rows, h)

    h_ref[...] = jax.lax.fori_loop(0, u.shape[0] // _LANES, lane_tile,
                                   h_ref[...])
    y = y_ref[...] + d_ref[...] * u
    z = z_ref[...].astype(_F32)
    g_ref[...] = (y * z * jax.nn.sigmoid(z)).astype(g_ref.dtype)


def _bwd_kernel(u_ref, dl_ref, z_ref, dg_ref, at_ref, d_ref, bias_ref,
                bt_ref, ct_ref, st_ref,
                du_ref, ddl_ref, dz_ref, dat_ref, dd_ref, dbias_ref,
                dbt_ref, dct_ref,
                hs_ref, gc_ref, x_ref, dt_ref, uf_ref, zf_ref, dgf_ref,
                duf_ref, ddlf_ref, dzf_ref, dyf_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        gc_ref[...] = jnp.zeros_like(gc_ref)
        dat_ref[...] = jnp.zeros_like(dat_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    x = dl_ref[...].astype(_F32) + bias_ref[...]
    x_ref[...] = x
    dt_ref[...] = _softplus(x)
    uf_ref[...] = u_ref[...].astype(_F32)
    zf_ref[...] = z_ref[...].astype(_F32)
    dgf_ref[...] = dg_ref[...].astype(_F32)
    at, dcoef = at_ref[...], d_ref[...]
    bt, ct = bt_ref[0], ct_ref[0]
    groups = _LANES // 8

    # the chunk's states again, from the one the forward saved: hs[t + 1]
    # is the state after step t, hs[0] the one before the chunk
    hs_ref[0] = st_ref[0]

    def forward_rows(i, h):
        r0 = pl.multiple_of(i * 8, 8)
        dts = dt_ref[pl.ds(r0, 8), :]
        us = uf_ref[pl.ds(r0, 8), :]
        for j in range(8):
            h = (jnp.exp(dts[j:j + 1] * at) * h
                 + _column(bt, i * 8 + j) * (dts[j:j + 1] * us[j:j + 1]))
            hs_ref[i * 8 + j + 1] = h
        return h

    jax.lax.fori_loop(0, groups, forward_rows, hs_ref[0])

    # the adjoint backwards: gc is exp(Delta_{t+1} A) g_{t+1}, the part of
    # the state's cotangent that comes from later steps
    def backward_rows(k, carry):
        gc, dat, dbt, dct = carry
        i = groups - 1 - k
        r0 = pl.multiple_of(i * 8, 8)
        dts, us = dt_ref[pl.ds(r0, 8), :], uf_ref[pl.ds(r0, 8), :]
        zs, dgs = zf_ref[pl.ds(r0, 8), :], dgf_ref[pl.ds(r0, 8), :]
        sig_x = jax.nn.sigmoid(x_ref[pl.ds(r0, 8), :])
        rows = {name: [None] * 8 for name in ("du", "ddl", "dz", "dy")}
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        for j in reversed(range(8)):
            lane = i * 8 + j
            hot = lanes == lane
            b, c = _column(bt, lane), _column(ct, lane)
            h, h_prev = hs_ref[i * 8 + j + 1], hs_ref[i * 8 + j]
            dt, u = dts[j:j + 1], us[j:j + 1]
            z, dg = zs[j:j + 1], dgs[j:j + 1]
            y = jnp.sum(c * h, axis=0, keepdims=True) + dcoef * u
            sz = jax.nn.sigmoid(z)
            dy = dg * z * sz
            g = gc + c * dy
            dct = dct + jnp.where(hot, jnp.sum(h * dy, axis=1,
                                               keepdims=True), 0.0)
            dbt = dbt + jnp.where(hot, jnp.sum(g * (dt * u), axis=1,
                                               keepdims=True), 0.0)
            gb = jnp.sum(g * b, axis=0, keepdims=True)
            a = jnp.exp(dt * at)
            ga = g * a * h_prev
            ddt = u * gb + jnp.sum(ga * at, axis=0, keepdims=True)
            dat = dat + ga * dt
            gc = a * g
            rows["du"][j] = dt * gb + dcoef * dy
            rows["ddl"][j] = ddt * sig_x[j:j + 1]
            rows["dz"][j] = dg * y * sz * (1.0 + z * (1.0 - sz))
            rows["dy"][j] = dy
        for name, ref in (("du", duf_ref), ("ddl", ddlf_ref),
                          ("dz", dzf_ref), ("dy", dyf_ref)):
            ref[pl.ds(r0, 8), :] = jnp.concatenate(rows[name], axis=0)
        return gc, dat, dbt, dct

    zero = jnp.zeros(bt.shape, _F32)
    gc, dat, dbt, dct = jax.lax.fori_loop(
        0, groups, backward_rows, (gc_ref[...], dat_ref[...], zero, zero))
    gc_ref[...] = gc
    dat_ref[...] = dat
    dbt_ref[0] = dbt
    dct_ref[0] = dct
    du_ref[...] = duf_ref[...].astype(du_ref.dtype)
    ddl_ref[...] = ddlf_ref[...].astype(ddl_ref.dtype)
    dz_ref[...] = dzf_ref[...].astype(dz_ref.dtype)
    dd_ref[...] += jnp.sum(dyf_ref[...] * uf_ref[...], axis=0, keepdims=True)
    dbias_ref[...] += jnp.sum(ddlf_ref[...], axis=0, keepdims=True)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _time_on_lanes(x, steps):
    """(batch, T, N) -> (batch, T / 128, N, 128) float32, T padded to
    `steps` with zeros."""
    b, t, n = x.shape
    x = jnp.pad(x.astype(_F32), ((0, 0), (0, steps - t), (0, 0)))
    return jnp.swapaxes(x.reshape(b, steps // _LANES, _LANES, n), 2, 3)


def _time_off_lanes(x, t):
    b, c, n, _ = x.shape
    return jnp.swapaxes(x, 2, 3).reshape(b, c * _LANES, n)[:, :t]


def _padded(x, steps):
    return jnp.pad(x, ((0, 0), (0, steps - x.shape[1]), (0, 0)))


def _steps(t):
    return -(-t // _FWD_ROWS) * _FWD_ROWS


def forward(u, delta, z, a, b, c, d, delta_bias, interpret=None):
    """(g, chunk states): u, delta, z (batch, T, E); a (E, N); b, c (batch,
    T, N); d, delta_bias (E,). g in u's type, the states (batch, T' / 128,
    N, E) float32 for T padded to T' (a multiple of `_FWD_ROWS`)."""
    return _fwd_call(u, delta, z, a, b, c, d, delta_bias,
                     interpret=_interpret_default() if interpret is None
                     else interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_call(u, delta, z, a, b, c, d, delta_bias, *, interpret):
    bsz, t, e = u.shape
    n = a.shape[1]
    steps, tc = _steps(t), _channel_tile(e)
    rows = _FWD_ROWS
    seq = pl.BlockSpec((None, rows, tc), lambda i, j, k: (i, k, j))
    chan = pl.BlockSpec((1, tc), lambda i, j, k: (0, j))
    lanes = pl.BlockSpec((None, rows // _LANES, n, _LANES),
                         lambda i, j, k: (i, k, 0, 0))
    g, states = pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, e // tc, steps // rows),
        in_specs=[seq, seq, seq,
                  pl.BlockSpec((n, tc), lambda i, j, k: (0, j)),
                  chan, chan, lanes, lanes],
        out_specs=[seq, pl.BlockSpec((None, rows // _LANES, n, tc),
                                     lambda i, j, k: (i, k, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, steps, e), u.dtype),
                   jax.ShapeDtypeStruct((bsz, steps // _LANES, n, e), _F32)],
        scratch_shapes=[pltpu.VMEM((n, tc), _F32)]
        + [pltpu.VMEM((rows, tc), _F32)] * 3,
        compiler_params=_params(),
        interpret=interpret,
        name="selscan_fwd",
    )(_padded(u, steps), _padded(delta, steps), _padded(z, steps),
      a.astype(_F32).T, d.astype(_F32)[None], delta_bias.astype(_F32)[None],
      _time_on_lanes(b, steps), _time_on_lanes(c, steps))
    return g[:, :t], states


def backward(u, delta, z, a, b, c, d, delta_bias, states, dg,
             interpret=None):
    """The cotangents (du, d delta, dz, da, db, dc, dd, d delta_bias) of
    g = forward(...)[0] against dg, each in its operand's type, from the
    forward's chunk states."""
    return _bwd_call(u, delta, z, a, b, c, d, delta_bias, states, dg,
                     interpret=_interpret_default() if interpret is None
                     else interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_call(u, delta, z, a, b, c, d, delta_bias, states, dg, *, interpret):
    bsz, t, e = u.shape
    n = a.shape[1]
    steps, tc = _steps(t), _channel_tile(e)
    chunks, tiles = steps // _LANES, e // tc

    def back(i, j, k):              # the chunks from the last to the first
        return chunks - 1 - k

    seq = pl.BlockSpec((None, _LANES, tc), lambda i, j, k: (i, back(i, j, k),
                                                            j))
    chan = pl.BlockSpec((1, tc), lambda i, j, k: (0, j))
    lanes = pl.BlockSpec((None, 1, n, _LANES),
                         lambda i, j, k: (i, back(i, j, k), 0, 0))
    summed = pl.BlockSpec((None, 1, tc), lambda i, j, k: (i, 0, j))
    share = pl.BlockSpec((None, None, 1, n, _LANES),
                         lambda i, j, k: (i, j, back(i, j, k), 0, 0))
    grads = pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, tiles, chunks),
        in_specs=[seq, seq, seq, seq,
                  pl.BlockSpec((n, tc), lambda i, j, k: (0, j)),
                  chan, chan, lanes, lanes,
                  pl.BlockSpec((None, 1, n, tc),
                               lambda i, j, k: (i, back(i, j, k), 0, j))],
        out_specs=[seq, seq, seq,
                   pl.BlockSpec((None, n, tc), lambda i, j, k: (i, 0, j)),
                   summed, summed, share, share],
        out_shape=[jax.ShapeDtypeStruct((bsz, steps, e), u.dtype),
                   jax.ShapeDtypeStruct((bsz, steps, e), delta.dtype),
                   jax.ShapeDtypeStruct((bsz, steps, e), z.dtype),
                   jax.ShapeDtypeStruct((bsz, n, e), _F32),
                   jax.ShapeDtypeStruct((bsz, 1, e), _F32),
                   jax.ShapeDtypeStruct((bsz, 1, e), _F32),
                   jax.ShapeDtypeStruct((bsz, tiles, chunks, n, _LANES),
                                        _F32),
                   jax.ShapeDtypeStruct((bsz, tiles, chunks, n, _LANES),
                                        _F32)],
        scratch_shapes=[pltpu.VMEM((_LANES + 1, n, tc), _F32),
                        pltpu.VMEM((n, tc), _F32)]
        + [pltpu.VMEM((_LANES, tc), _F32)] * 9,
        compiler_params=_params(),
        interpret=interpret,
        name="selscan_bwd",
    )(_padded(u, steps), _padded(delta, steps), _padded(z, steps),
      _padded(dg, steps), a.astype(_F32).T, d.astype(_F32)[None],
      delta_bias.astype(_F32)[None], _time_on_lanes(b, steps),
      _time_on_lanes(c, steps), states)
    du, ddl, dz, dat, dd, dbias, dbt, dct = grads
    return (du[:, :t], ddl[:, :t], dz[:, :t],
            jnp.sum(dat, axis=0).T.astype(a.dtype),
            _time_off_lanes(jnp.sum(dbt, axis=1), t).astype(b.dtype),
            _time_off_lanes(jnp.sum(dct, axis=1), t).astype(c.dtype),
            jnp.sum(dd, axis=(0, 1)).astype(d.dtype),
            jnp.sum(dbias, axis=(0, 1)).astype(delta_bias.dtype))


@jax.custom_vjp
def selective_scan(u, delta, a, b, c, d, z, delta_bias):
    """g = y silu(z) of the selective scan (module docstring), through the
    kernels; differentiable in every operand."""
    return forward(u, delta, z, a, b, c, d, delta_bias)[0]


def _scan_fwd(u, delta, a, b, c, d, z, delta_bias):
    g, states = forward(u, delta, z, a, b, c, d, delta_bias)
    return g, (u, delta, a, b, c, d, z, delta_bias, states)


def _scan_bwd(saved, dg):
    u, delta, a, b, c, d, z, delta_bias, states = saved
    # traced where the step is transposed: the kernel enters the scan's
    # scope itself, so that its time on a trace stays with it
    with jax.named_scope("pt.ssm.sel"):
        du, ddl, dz, da, db, dc, dd, dbias = backward(
            u, delta, z, a, b, c, d, delta_bias, states, dg.astype(u.dtype))
    return du, ddl, da, db, dc, dd, dz, dbias


selective_scan.defvjp(_scan_fwd, _scan_bwd)
