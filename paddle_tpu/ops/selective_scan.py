"""Mamba-1's selective scan (Gu and Dao 2023, arXiv:2312.00752, Algorithm 2)
with its output gate, as `selective_scan_fn(u, delta, A, B, C, D, z,
delta_bias, delta_softplus=True)` of the reference implementation computes
it:

    Delta = softplus(delta + delta_bias)                     (batch, T, E)
    h_t   = exp(Delta_t[:, None] A) h_{t-1} + (Delta_t u_t)[:, None] B_t
    y_t   = h_t C_t + D u_t
    out   = y silu(z)

for u, delta, z (batch, T, E), A (E, N) (negative), B, C (batch, T, N), D
and delta_bias (E,). A channel's N states each decay at their own rate, so
no part of the recurrence is a matmul.

Where the operands allow (a TPU, channels a multiple of 128, states of 8)
the two Pallas kernels of ops/pallas/selective_scan.py run it, the state
in VMEM (no flag: the shape decides, as ops/power_retention.py's does).
Everywhere else, and in the tests as their oracle, `scan_jnp`: a
`lax.scan` over chunks of `chunk` steps, each chunk a rematerialised
`lax.scan` over its steps, float32 throughout. Both run under the scope
`pt.ssm.sel`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas import selective_scan as _kernels

__all__ = ["selective_scan", "scan_jnp"]

_F32 = jnp.float32
CHUNK = 256


def selective_scan(u, delta, A, B, C, D, z, delta_bias):
    """out = y silu(z) in u's type (module docstring)."""
    with jax.named_scope("pt.ssm.sel"):
        if _kernels.supported(u, A):
            return _kernels.selective_scan(u, delta, A, B, C, D, z,
                                           delta_bias)
        return scan_jnp(u, delta, A, B, C, D, z, delta_bias)


def scan_jnp(u, delta, A, B, C, D, z, delta_bias, chunk=CHUNK):
    """The same in jax.numpy: the state (batch, E, N) float32 carried over
    chunks of `chunk` steps, each chunk's steps a rematerialised scan (the
    backward holds one chunk's states at a time). T need not be a multiple
    of `chunk`: the tail is padded with steps that add nothing."""
    b, t, e = u.shape
    steps = -(-t // chunk) * chunk
    a = A.astype(_F32)
    dt = jax.nn.softplus(delta.astype(_F32) + delta_bias.astype(_F32))
    u32 = u.astype(_F32)

    def time_major(x):
        x = jnp.pad(x, ((0, 0), (0, steps - t), (0, 0)))
        return jnp.moveaxis(x, 1, 0).reshape((steps // chunk, chunk, b)
                                             + x.shape[2:])

    def step(h, xs):
        dt_t, du_t, b_t, c_t = xs
        h = jnp.exp(dt_t[..., None] * a) * h + du_t[..., None] * b_t[:, None]
        return h, jnp.einsum("ben,bn->be", h, c_t)

    @jax.checkpoint
    def run_chunk(h, xs):
        return jax.lax.scan(step, h, xs)

    # pad first: the padded steps' Delta u is 0, whatever softplus gives
    xs = tuple(time_major(x) for x in (dt, dt * u32, B.astype(_F32),
                                       C.astype(_F32)))
    h0 = jnp.zeros((b, e, a.shape[1]), _F32)
    _, ys = jax.lax.scan(run_chunk, h0, xs)
    y = jnp.moveaxis(ys.reshape(steps, b, e), 0, 1)[:, :t]
    y = y + D.astype(_F32) * u32
    z32 = z.astype(_F32)
    return (y * z32 * jax.nn.sigmoid(z32)).astype(u.dtype)
