"""Residual sub-blocks that are rematerialised in the backward: what
models/granite_moe_hybrid.py, models/brumby.py, models/mellum.py and
models/phi4flash.py build their layers from.

A step then holds the sub-blocks' inputs and one sub-block's internals.
The layers are not stacked and scanned: a scan's backward returns the
stacked weight gradients whole, and the trainer's fused update (6 bytes a
parameter) rests on each gradient dying where it is made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import execute
from ..framework.param_attr import ParamAttr
from ..generation import _rms

__all__ = ["Params", "SubBlock", "over_token_blocks", "blocked_lm_loss",
           "layer_norm"]


class Params(nn.Layer):
    """Named parameters of one sub-module, made in the model's dtype."""

    def __init__(self, dtype, **specs):
        super().__init__()
        for name, (shape, init) in specs.items():
            setattr(self, name, self.create_parameter(
                shape, attr=ParamAttr(initializer=init), dtype=dtype))


class SubBlock(nn.Layer):
    """A residual sub-block computed by one pure function of (hidden,
    parameters), rematerialised in the backward. On a device trace its
    operations therefore run in three passes under the scopes `_pure`
    enters: forward, recompute (jax names the second forward
    `rematted_computation`) and backward; observability/catalog.py
    trace_pass tells them apart."""

    def _pure(self, h, *extra, **params):
        raise NotImplementedError

    def _over(self, block, h, *extra):
        """`block` (the rematerialised `_pure`) over the hidden states."""
        return block(h, *extra)

    def forward(self, hidden, *extra):
        """`extra`: arrays a sub-block reads beside the residual stream (an
        earlier layer's keys and values, a memory), passed to `_pure` after
        it and rematerialised with it. `_pure` may return a tuple: the
        residual stream first, then what later sub-blocks read."""
        names, tensors = zip(*self.named_parameters())
        n = len(extra)

        def pure(h, *arrays):
            params = {k.replace(".", "_"): a
                      for k, a in zip(names, arrays[n:])}
            return self._over(jax.checkpoint(
                lambda hb, *xb: self._pure(hb, *xb, **params)), h,
                *arrays[:n])

        return execute(pure, hidden, *extra, *tensors,
                       _name=type(self).__name__)


def layer_norm(x, weight, bias, eps):
    """LayerNorm over the last axis, statistics in float32, in x's type."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def over_token_blocks(block, h, size):
    """`block` over (batch, seq, hidden) in blocks of `size` tokens one
    after the other, each rematerialised on its own, where the tokens are
    a multiple of `size` and more than one block. A loop, not an unrolled
    list: independent blocks would be scheduled side by side and hold all
    their rows at once."""
    b, s, d = h.shape
    n = b * s // size
    if n < 2 or b * s % size:
        return block(h)
    blocks = h.reshape(n, 1, size, d)
    return jax.lax.map(block, blocks).reshape(b, s, d)


def blocked_lm_loss(h, norm_w, head_w, labels, eps, block, norm_b=None):
    """Mean next-token cross entropy of (batch, T, hidden) against labels
    (batch, T): final norm (RMSNorm; LayerNorm where `norm_b` is given),
    head and log-softmax over `block` tokens at a time, each block
    rematerialised in the backward. Every position is a row, so that the
    blocks are even; a sequence's last position, which predicts nothing,
    carries weight 0."""
    b, s, d = h.shape
    rows = h.reshape(b * s, d)
    targets = jnp.roll(labels, -1, axis=1).reshape(b * s)
    counted = (jnp.arange(b * s) % s != s - 1)

    @jax.checkpoint
    def block_sum(x, tgt, on):
        with jax.named_scope("pt.head"):
            x = _rms(x, norm_w, eps) if norm_b is None else \
                layer_norm(x, norm_w, norm_b, eps)
            logits = jnp.dot(x, head_w, preferred_element_type=jnp.float32)
        with jax.named_scope("pt.loss"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            own = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
            return jnp.sum(jnp.where(on, lse - own, 0.0))

    n = b * s // block
    if n < 2 or b * s % block:
        total = block_sum(rows, targets, counted)
    else:
        total = jnp.sum(jax.lax.map(
            lambda t: block_sum(*t),
            (rows.reshape(n, block, d), targets.reshape(n, block),
             counted.reshape(n, block))))
    return total / (b * (s - 1))
