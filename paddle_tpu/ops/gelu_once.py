"""Exact GELU evaluated once for a layer that sits between two matmuls.

Differentiating `matmul(gelu(matmul(x)))` with plain `jax.nn.gelu` keeps
the pre-activation alone, and XLA re-evaluates the activation wherever it is
read: in the input of the second matmul, in the input of that matmul's
weight-gradient product, and its derivative in the epilogue of the
input-gradient product. The exact form there is a float32 `erfc` (a rational
polynomial and an exponential), so each re-evaluation slows a matmul fusion
by about half (PERF.md section 6, PR 31).

`gelu_once` evaluates value and derivative together where the
pre-activation is made, keeps both in the input's type behind an
`optimization_barrier` (which XLA may not duplicate or move work across),
and multiplies the cotangent by the kept derivative. It costs one more
activation-sized array a call, so it is for a model whose author knows the
activation sits between two matmuls with memory to spare (`models/gpt.py`).
Everything else keeps `F.gelu`.

Differentiated or not it returns the same value: `x * 0.5 (1 + erf(x /
sqrt 2))` in float32 (wider if x is), rounded to x's type once. That is not
`jax.nn.gelu`'s arithmetic: `jax.nn.gelu` takes `erfc`, three times erf's
work on a TPU, and on a bf16 input rounds `x / sqrt 2`, erfc's result and
the product, so a quarter of its values lie one bf16 ulp from these, further
from the float32 function. 0.5 (1 + erf) gives up only erfc's relative
accuracy far out in the negative tail, 6e-8 |x| absolute. Not differentiated
(`generate`, a serving forward) no derivative is computed or held.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["gelu_once"]


def _wide(x):
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


def _cdf(xf):
    return 0.5 * (1.0 + jax.lax.erf(xf * np.sqrt(0.5).astype(xf.dtype)))


@jax.custom_vjp
def gelu_once(x):
    """Exact (erf) GELU of x, with the backward described above."""
    xf = _wide(x)
    return (xf * _cdf(xf)).astype(x.dtype)


def _fwd(x):
    xf = _wide(x)
    cdf = _cdf(xf)
    density = np.sqrt(0.5 / np.pi).astype(xf.dtype) * jnp.exp(-0.5 * xf * xf)
    # (value, derivative), each rounded once; the second is the residual
    return jax.lax.optimization_barrier(
        ((xf * cdf).astype(x.dtype), (cdf + xf * density).astype(x.dtype)))


def _bwd(g, dh):
    return (dh * g,)


gelu_once.defvjp(_fwd, _bwd)
