"""Mamba-2 mixer pieces in plain jax.numpy: the causal depthwise conv, the
chunked state-space scan (SSD, Dao & Gu 2024, "Transformers are SSMs",
listing 1) and the gated RMSNorm.

The recurrence, per head with state S (head_dim x d_state):

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T
    y_t = S_t C_t + D * x_t

`ssd_chunked_scan` computes it a chunk at a time: inside a chunk the
outputs are one masked (chunk x chunk) product per head, between chunks
one state is carried. The chunks are a `lax.scan` whose body is
rematerialised, so a layer's backward holds one chunk's (heads, chunk,
chunk) decay matrix and never all of them (at 128 heads and 8192 tokens
they are 1 GiB in float32). Decays are accumulated and exponentiated in
float32 whatever the operands' type; the matmuls take the operands' type
(bf16 on the chip) and accumulate in float32; the carried state is float32.

No Pallas kernel here: every product is an einsum XLA lowers to the MXU.
The scan is the first candidate for one (PERF.md section 7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv1d_silu", "ssd_chunked_scan", "gated_rms_norm"]


def _conv_taps(x, weight, bias):
    """bias + sum_i weight[i] * x[t - (width - 1) + i], float32."""
    width, seq = weight.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = bias.astype(jnp.float32)
    for i in range(width):
        y = y + xp[:, i:i + seq].astype(jnp.float32) * w[i]
    return y


@jax.custom_vjp
def causal_conv1d_silu(x, weight, bias):
    """silu of the depthwise causal convolution over the sequence, in x's
    type. x (batch, seq, channels); weight (width, channels), tap
    `width - 1` multiplying the current position; bias (channels,). Sums in
    float32. The backward is written out (shifted slices the other way):
    autodiff's keeps one float32 (batch, seq, channels) array a tap alive,
    1.3 GiB at 8192 x 8448."""
    return jax.nn.silu(_conv_taps(x, weight, bias)).astype(x.dtype)


def _conv_fwd(x, weight, bias):
    return causal_conv1d_silu(x, weight, bias), (x, weight, bias)


def _conv_bwd(res, g):
    x, weight, bias = res
    width, seq = weight.shape[0], x.shape[1]
    # the forward's taps made again by hand: on a trace the second forward
    # of a rule, not its backward (catalog.py trace_pass)
    with jax.named_scope("pt.recompute"):
        u = _conv_taps(x, weight, bias)
    sig = jax.nn.sigmoid(u)
    du = g.astype(jnp.float32) * sig * (1.0 + u * (1.0 - sig))
    dup = jnp.pad(du, ((0, 0), (0, width - 1), (0, 0)))
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    dx = sum(dup[:, width - 1 - i:width - 1 - i + seq] * w[i]
             for i in range(width))
    dw = jnp.stack([jnp.sum(du * xp[:, i:i + seq].astype(jnp.float32),
                            axis=(0, 1)) for i in range(width)])
    return (dx.astype(x.dtype), dw.astype(weight.dtype),
            jnp.sum(du, axis=(0, 1)).astype(bias.dtype))


causal_conv1d_silu.defvjp(_conv_fwd, _conv_bwd)


def gated_rms_norm(y, z, weight, eps):
    """RMSNorm(y * silu(z)) * weight over the last axis (Mamba-2's gated
    norm with one group: the statistic runs over every channel)."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(g * g, axis=-1, keepdims=True)
    return (g * jax.lax.rsqrt(ms + eps)).astype(y.dtype) * weight


def _chunk_step(state, inputs, a_head, d_head):
    """One chunk. state (b, h, p, n) float32; x (b, l, h * p); dt (b, l, h)
    float32; bm, cm (b, l, n). Returns the state after the chunk and the
    chunk's outputs (b, l, h * p) in x's type. Heads and head_dim are one
    axis outside this function: a trailing axis of 64 is padded to the
    128 lanes in every array that crosses the scan's boundary."""
    x, dt, bm, cm = inputs
    dtype = x.dtype
    f32 = jnp.float32
    batch, length, heads = dt.shape
    x = x.reshape(batch, length, heads, -1)
    a = jnp.moveaxis(dt * a_head, 1, 2)                  # (b, h, l), <= 0
    cum = jnp.cumsum(a, axis=-1)                         # through position i
    # inside the chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) dt_j (C_i.B_j) x_j
    seg = cum[..., :, None] - cum[..., None, :]          # (b, h, l, l)
    causal = jnp.tril(jnp.ones((length, length), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))    # masked BEFORE exp
    scores = jnp.einsum("bin,bjn->bij", cm, bm, preferred_element_type=f32)
    dt_h = jnp.moveaxis(dt, 1, 2)                        # (b, h, l)
    mix = (scores[:, None] * decay * dt_h[..., None, :]).astype(dtype)
    y = jnp.einsum("bhij,bjhp->bihp", mix, x, preferred_element_type=f32)
    # what the state carried into the chunk still gives position i
    carried = jnp.einsum("bin,bhpn->bihp", cm, state.astype(dtype),
                         preferred_element_type=f32)
    y = y + carried * jnp.moveaxis(jnp.exp(cum), 1, 2)[..., None]
    y = y + x.astype(f32) * d_head[:, None]
    # the state after the chunk
    to_end = jnp.exp(cum[..., -1:] - cum) * dt_h         # (b, h, l)
    xw = (x.astype(f32) * jnp.moveaxis(to_end, 1, 2)[..., None]).astype(dtype)
    state = state * jnp.exp(cum[..., -1])[..., None, None] + jnp.einsum(
        "bjhp,bjn->bhpn", xw, bm, preferred_element_type=f32)
    return state, y.astype(dtype).reshape(batch, length, -1)


def ssd_chunked_scan(x, dt, a_head, bm, cm, d_head, chunk):
    """The Mamba-2 recurrence over a whole sequence, `chunk` positions at a
    time. x (batch, seq, heads, head_dim); dt (batch, seq, heads), after
    its softplus; a_head (heads,), negative; bm, cm (batch, seq, d_state),
    one group shared by every head; d_head (heads,). Returns y like x.

    A sequence that is no multiple of `chunk` is padded with dt = 0: a
    padded position neither decays the state nor adds to it."""
    with jax.named_scope("pt.ssm.scan"):
        batch, seq, heads, head_dim = x.shape
        pad = (-seq) % chunk
        dt = dt.astype(jnp.float32)
        x = x.reshape(batch, seq, heads * head_dim)
        if pad:
            x, dt, bm, cm = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                             for t in (x, dt, bm, cm))
        n_chunks = (seq + pad) // chunk

        def chunked(t):            # (b, s, ...) -> (chunks, b, chunk, ...)
            return jnp.moveaxis(
                t.reshape((batch, n_chunks, chunk) + t.shape[2:]), 1, 0)

        a32 = a_head.astype(jnp.float32)
        d32 = d_head.astype(jnp.float32)
        step = jax.checkpoint(
            lambda state, inputs: _chunk_step(state, inputs, a32, d32))
        state0 = jnp.zeros((batch, heads, head_dim, bm.shape[-1]),
                           jnp.float32)
        _, y = jax.lax.scan(step, state0, tuple(map(chunked,
                                                    (x, dt, bm, cm))))
        y = jnp.moveaxis(y, 0, 1).reshape(batch, seq + pad, heads, head_dim)
        return y[:, :seq] if pad else y
