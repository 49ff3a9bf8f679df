"""Mamba-1's selective scan (ops/selective_scan.py) at test sizes on the CPU.

The jax.numpy scan, values and every gradient, against the recurrence
written out one position at a time in float32 (this file's `_sequential`,
which shares nothing with either implementation), across chunk edges and
at a length that is no multiple of the chunk; the Pallas kernels
(ops/pallas/selective_scan.py `selscan_fwd` / `selscan_bwd`, in Pallas's
interpreter here) against the jax.numpy scan, and the rule that picks
between them. tests/test_flash_mosaic_compile.py compiles the kernels for a
described v5e at the Phi-4-mini-flash cell's shape.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import selective_scan as ss
from paddle_tpu.ops.pallas import selective_scan as kernels

NAMES = ("u", "delta", "A", "B", "C", "D", "z", "delta_bias")


def _operands(b, t, e, n, seed=0):
    """Operands as a Mamba layer makes them: A = -(1..N) scaled a little
    by channel, delta's bias the published log-uniform step's inverse
    softplus, everything else N(0, 1) (delta N(0, 0.5))."""
    ks = jax.random.split(jax.random.key(seed), 8)
    u, z = (jax.random.normal(k, (b, t, e)) for k in ks[:2])
    delta = 0.5 * jax.random.normal(ks[2], (b, t, e))
    a = -jnp.arange(1, n + 1, dtype=jnp.float32)[None] \
        * jax.random.uniform(ks[3], (e, 1), minval=0.5, maxval=1.5)
    bm, cm = (jax.random.normal(k, (b, t, n)) for k in ks[4:6])
    d = jax.random.normal(ks[6], (e,))
    dt = jnp.exp(jax.random.uniform(ks[7], (e,), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    return u, delta, a, bm, cm, d, z, dt + jnp.log(-jnp.expm1(-dt))


def _sequential(u, delta, a, bm, cm, d, z, delta_bias):
    """The recurrence one position at a time, a Python loop over t."""
    dt = jax.nn.softplus(delta + delta_bias)
    h = jnp.zeros((u.shape[0],) + a.shape)
    ys = []
    for t in range(u.shape[1]):
        h = jnp.exp(dt[:, t, :, None] * a) * h \
            + (dt[:, t] * u[:, t])[..., None] * bm[:, t, None, :]
        ys.append(jnp.sum(h * cm[:, t, None, :], -1) + d * u[:, t])
    return jnp.stack(ys, 1) * jax.nn.silu(z)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _value_and_grads(fn, args, cot):
    out, vjp = jax.vjp(fn, *args)
    return out, vjp(cot)


@pytest.mark.parametrize("t, chunk", [(37, 8), (24, 8), (5, 16)])
def test_jnp_scan_values_and_every_gradient_match_the_recurrence(t, chunk):
    """Float32 both sides: what separates them is the order of the sums
    (1e-5 relative is a few dozen roundings); a state not carried across a
    chunk edge or a tail step that adds something is of order 0.1-1."""
    args = _operands(2, t, 16, 4)
    cot = jax.random.normal(jax.random.key(9), (2, t, 16))
    got, grads = _value_and_grads(
        lambda *x: ss.scan_jnp(*x, chunk=chunk), args, cot)
    want, wgrads = _value_and_grads(_sequential, args, cot)
    assert _rel(got, want) < 1e-5
    for name, g, w in zip(NAMES, grads, wgrads):
        assert g.shape == w.shape, name
        assert float(jnp.abs(w).max()) > 0, name       # every one reached
        assert _rel(g, w) < 1e-5, name


def test_jnp_scan_keeps_the_operands_type():
    args = [x.astype(jnp.bfloat16) if x.ndim == 3 else x
            for x in _operands(1, 12, 8, 4)]
    out = ss.scan_jnp(*args, chunk=4)
    assert out.dtype == jnp.bfloat16 and out.shape == (1, 12, 8)


@pytest.mark.parametrize("b, t", [(1, 300), (2, 128)])
def test_kernels_match_the_jnp_scan(b, t):
    """The kernels, interpreted: the forward's chunk states carried over
    the time grid and the backward's chunks in reverse (300 positions pad
    to one grid step of 512 with 2.3 chunks of 128 in it; two sequences of
    one chunk each), values and all eight cotangents. Float32 arithmetic
    both sides."""
    args = _operands(b, t, 256, 16, seed=b)
    cot = jax.random.normal(jax.random.key(3), (b, t, 256))
    got, grads = _value_and_grads(kernels.selective_scan, args, cot)
    want, wgrads = _value_and_grads(
        lambda *x: ss.scan_jnp(*x, chunk=64), args, cot)
    assert _rel(got, want) < 1e-5
    for name, g, w in zip(NAMES, grads, wgrads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _rel(g, w) < 1e-5, name


def test_kernels_take_bf16_operands_and_give_their_types_back():
    """bf16 u, delta, z and B, C (as the model passes them): outputs and
    cotangents in each operand's type, within bf16's rounding of the
    float32 scan on the same rounded operands."""
    args = list(_operands(1, 128, 128, 16, seed=4))
    for i in (0, 1, 3, 4, 6):
        args[i] = args[i].astype(jnp.bfloat16)
    cot = jax.random.normal(jax.random.key(5), (1, 128, 128),
                            jnp.bfloat16)
    got, grads = _value_and_grads(kernels.selective_scan, args, cot)
    want, wgrads = _value_and_grads(
        lambda *x: ss.scan_jnp(*x, chunk=32),
        [x.astype(jnp.float32) for x in args], cot.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16
    # bf16 keeps 8 bits: a rounding of the output is 0.2-0.4% of it
    assert _rel(got.astype(jnp.float32), want) < 0.01
    for name, arg, g, w in zip(NAMES, args, grads, wgrads):
        assert g.dtype == arg.dtype, name
        assert _rel(g.astype(jnp.float32), w) < 0.01, name


def test_the_rule_picks_the_kernels_on_a_tpu_only(monkeypatch):
    u, a = jnp.zeros((1, 8, 256), jnp.bfloat16), jnp.zeros((256, 16))
    assert not kernels.supported(u, a)            # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.supported(u, a)
    assert not kernels.supported(jnp.zeros((1, 8, 200), jnp.bfloat16),
                                 jnp.zeros((200, 16)))   # not whole lanes
    assert not kernels.supported(u, jnp.zeros((256, 12)))    # states


def test_the_op_runs_under_its_scope():
    args = _operands(1, 8, 8, 4)
    text = jax.jit(lambda *x: ss.selective_scan(*x).sum()).lower(
        *args).as_text(debug_info=True)
    assert "pt.ssm.sel" in text
