"""Functionalize an imperative Layer: params/buffers → pure-function inputs.

The same substitution trick as jit.to_static's trace (one mechanism, two
consumers): temporarily rebind every Parameter/buffer's ._data to the traced
array, run the Layer's Python forward once, restore. The resulting pure
function is what jax.jit / jax.value_and_grad / pjit consume.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..framework import core as _core
from ..framework import random as _random
from ..framework.core import Tensor


def functional_call(model, params: dict, *args, rng_key=None, training=True,
                    **kwargs):
    """Run model(*args, **kwargs) with parameter arrays taken from `params`
    (name -> jax array, matching model.state_dict() keys). Returns raw
    arrays. Safe to call under jit tracing."""
    state = model.state_dict()
    saved = []
    # honor training=False: dropout/BN branch on layer.training at trace
    # time. Save EVERY sublayer's flag so restore can't clobber submodules
    # the user deliberately kept in eval (e.g. frozen BatchNorm).
    mode_saved = None
    if not training and getattr(model, "training", False):
        if hasattr(model, "named_sublayers"):
            mode_saved = [(m, m.training)
                          for _, m in model.named_sublayers(include_self=True)]
        else:
            mode_saved = [(model, model.training)]
        model.eval()

    def wrap(a):
        # stop_gradient=False is load-bearing: Tensor's default (True) would
        # make execute() place a lax.stop_gradient barrier on this input
        # inside the trace (core.py TraceContext branch), silently severing
        # the chain rule at every functional_call boundary — per-layer
        # compositions (scanned llama, pipeline stage_fn) would train only
        # their last block. Inputs to a functional jax-facing API are
        # differentiable by definition; integer/bool inputs are excluded
        # from diff by dtype anyway.
        if isinstance(a, Tensor):
            # preserve the caller's flag: an EXPLICIT detach() must keep its
            # barrier; only raw arrays get the differentiable default
            return Tensor(a._data, stop_gradient=a.stop_gradient)
        if isinstance(a, jax.Array) or hasattr(a, "dtype"):
            return Tensor(a, stop_gradient=False)
        return a

    try:
        for name, t in state.items():
            if name in params:
                saved.append((t, t._data, t._node))
                t._data = params[name]
                t._node = None
        wrapped = [wrap(a) for a in args]
        wrapped_kw = {k: wrap(v) for k, v in kwargs.items()}
        ctx = _core.TraceContext()
        if rng_key is not None:
            with ctx, _random._global_rng.trace_scope(rng_key):
                out = model(*wrapped, **wrapped_kw)
        else:
            with ctx:
                out = model(*wrapped, **wrapped_kw)
        return jax.tree_util.tree_map(
            lambda o: o._data if isinstance(o, Tensor) else o, out,
            is_leaf=lambda v: isinstance(v, Tensor))
    finally:
        for t, data, node in saved:
            t._data = data
            t._node = node
        if mode_saved:
            for m, was in mode_saved:
                m.training = was


def make_loss_fn(model, loss_fn: Callable | None = None, training=True):
    """Build pure loss(params, batch, rng_key) -> scalar.

    If the model returns (loss, logits) when given labels (LM convention),
    loss_fn may be None. training=False traces the model in eval mode
    (dropout off, BN running stats).
    """

    def pure_loss(params, batch, rng_key):
        if isinstance(batch, (tuple, list)) and len(batch) == 2:
            x, y = batch
        else:
            x, y = batch, None
        if loss_fn is None:
            out = functional_call(model, params, x, labels=y, rng_key=rng_key,
                                  training=training)
            loss = out[0] if isinstance(out, (tuple, list)) else out
        else:
            out = functional_call(model, params, x, rng_key=rng_key,
                                  training=training)
            logits = out[0] if isinstance(out, (tuple, list)) else out
            loss = loss_fn(Tensor(logits), Tensor(y))
            loss = loss._data if isinstance(loss, Tensor) else loss
        return loss.astype(jnp.float32) if hasattr(loss, "astype") else loss

    return pure_loss


def split_stacked_layer_params(state: dict,
                               pattern: str = r"^llama\.layers\.(\d+)\.(.+)$"):
    """Split a name->array state dict into (stacked, other): parameters whose
    names match `pattern` are grouped by suffix and stacked on a new leading
    layer dim (L, ...); everything else passes through. Shared by the
    pipeline runner (which reshapes to (pp, L/pp, ...)) and the
    scan-over-layers model."""
    import re as _re
    rx = _re.compile(pattern)
    per_layer: dict = {}
    other: dict = {}
    for k, v in state.items():
        m = rx.match(k)
        if m:
            per_layer.setdefault(m.group(2), []).append((int(m.group(1)), v))
        else:
            other[k] = v
    stacked = {}
    for name, items in per_layer.items():
        items.sort()
        stacked[name] = jnp.stack([v for _, v in items])
    return stacked, other


def rmsnorm_lm_loss(norm_w, proj_w_t, h, labels, eps):
    """Final RMSNorm -> projection -> next-token cross-entropy, fp32 softmax.
    proj_w_t: (hidden, vocab) — pass embed_weight.T for tied embeddings."""
    h32 = h.astype(jnp.float32)
    ms = jnp.mean(h32 * h32, axis=-1, keepdims=True)
    h = (h32 * jax.lax.rsqrt(ms + eps)).astype(h.dtype) * norm_w
    logits = h @ proj_w_t
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
    tgt = labels[:, 1:]
    picked = jnp.take_along_axis(lp, tgt[..., None], -1)[..., 0]
    return -jnp.mean(picked)


def rmsnorm_lm_loss_chunked(norm_w, proj_w_t, h, labels, eps,
                            chunk: int = 256):
    """Sequence-chunked flavor of rmsnorm_lm_loss: the full (b, s, vocab)
    fp32 logits/log-softmax buffer dominates single-chip HBM at LM scale
    (b8 s2048 v32k fp32 = 2.1GB live into the backward, which is what
    pushes the >=780M train steps past the v5e's 16GB). A lax.scan over
    sequence chunks with jax.checkpoint keeps ONE chunk's logits live
    (b*chunk*vocab) and recomputes per chunk in the backward. Same math as
    rmsnorm_lm_loss (log-softmax picked = picked - logsumexp) up to fp
    reassociation of the mean."""
    h32 = h.astype(jnp.float32)
    ms = jnp.mean(h32 * h32, axis=-1, keepdims=True)
    hn = (h32 * jax.lax.rsqrt(ms + eps)).astype(h.dtype) * norm_w
    x = hn[:, :-1]
    y = labels[:, 1:]
    b, sm1, d = x.shape
    pad = (-sm1) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        y = jnp.pad(y, ((0, 0), (0, pad)))
    mask = (jnp.arange(sm1 + pad) < sm1).astype(jnp.float32)
    nch = (sm1 + pad) // chunk
    xc = jnp.moveaxis(x.reshape(b, nch, chunk, d), 1, 0)
    yc = jnp.moveaxis(y.reshape(b, nch, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(nch, chunk)[None].repeat(b, 0), 1, 0)

    def chunk_nll(total, xym):
        xcb, ycb, mcb = xym
        logits = (xcb @ proj_w_t).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, ycb[..., None], -1)[..., 0]
        return total + jnp.sum((lse - picked) * mcb), None

    total, _ = jax.lax.scan(jax.checkpoint(chunk_nll), jnp.float32(0.0),
                            (xc, yc, mc))
    return total / (b * sm1)
