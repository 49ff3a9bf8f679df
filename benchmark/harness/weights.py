"""Seeded weights, made on the device in one jitted call.

The model object is built by the program's own constructor (that is the
normal path, and it fixes names and shapes); its initial values are then
replaced, all leaves at once, by values drawn from --seed in the type the
cell runs in. Matrices and embeddings are N(0, 0.02) (the GPT-2/GPT-3
scheme both families' recipes use), vectors named *norm*/ln_* `weight` are
ones, biases zeros.
"""

from __future__ import annotations

import re

_ONES = re.compile(r"(norm|ln_\w+)\.weight$")


def _kind(name, shape):
    if len(shape) >= 2:
        return "normal"
    return "ones" if _ONES.search(name) else "zeros"


def make(shapes, seed, dtype, std=0.02):
    """{name: array} for {name: shape}: one jitted call, one PRNG key folded
    with the leaf's index. The `rbg` generator is the one that is fast on a
    TPU; the same seed gives the same weights on the same installation."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)
    dt = jnp.dtype(dtype)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape = tuple(shapes[name])
            kind = _kind(name, shape)
            if kind == "normal":
                k = jax.random.fold_in(key, i)
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * std).astype(dt)
            elif kind == "ones":
                out[name] = jnp.ones(shape, dt)
            else:
                out[name] = jnp.zeros(shape, dt)
        return out

    key = jax.random.key(seed % (2 ** 31 - 1), impl="rbg")
    return jax.jit(build)(key)


def install(model, seed, dtype):
    """Replace every floating leaf of `model` by seeded values of `dtype`;
    returns the number of parameters."""
    import jax.numpy as jnp
    import numpy as np

    state = model.state_dict()
    floating = {k: tuple(t.shape) for k, t in state.items()
                if jnp.issubdtype(t._data.dtype, jnp.floating)}
    new = make(floating, seed, dtype)
    for k, arr in new.items():
        state[k]._data = arr
    return sum(int(np.prod(s)) for s in floating.values())
