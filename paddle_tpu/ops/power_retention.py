"""Power retention (degree 2) in plain jax.numpy: gated linear attention
whose feature map makes phi(q) . phi(k) = (q . k / sqrt(d))^2 exactly
("Scaling Context Requires Rethinking Attention", arXiv:2507.04239).

Per state (key/value) head, with g_t = exp(log_g_t) in (0, 1]:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T       z_t = g_t z_{t-1} + phi(k_t)
    y_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps)

for every query head of the state head's group (GQA: the group's query
heads read one state; k, v and the state are never repeated).

`power_retention` computes it `chunk` positions at a time. Inside a chunk
the outputs are the masked (chunk x chunk) product of the quadratic form,
a_ts = exp(L_t - L_s) (q_t . k_s)^2 / d with L the running sum of the
log-decays; what earlier chunks left is one carried state S (features x d)
and one normaliser z (features) per state head, both float32, and the two
parts share one denominator. Numerator and denominator come out of the
same products (a column of ones beside v, `_beside`), so they see the
same rounded weights and no expansion is read a second time for its
normaliser. The chunks are a `lax.scan` whose body is rematerialised, and
the state heads a `lax.map` around it, so a layer's backward holds one
carried state a chunk and one state head's chunk, never all of them
(phi(Q) of 40 heads x 1,024 rows would be 0.68 GB in bf16). Decays
are summed and exponentiated in float32 whatever the operands' type; the
matmuls take the operands' type (bf16 on the chip) and accumulate in
float32.

**The expansion's layout.** Not the d (d + 1) / 2 minimal entries u_a u_b
(a <= b) but d / 2 + 1 rotations of d lanes each:

    phi(u)[r, i] = w_r u_i u_{(i + r) mod d},      r = 0 .. d / 2

Rotation r and rotation d - r hold the same unordered pairs, so the full
square sum_{a,b} q_a q_b k_a k_b is rotation 0, twice each of 1 .. d/2 - 1,
and rotation d / 2 once (it holds every one of its pairs twice already).
That is 65 x 128 = 8,320 features at d = 128 for the 8,256 minimal ones
(64 duplicates), and the product is the same. The weights and the 1 / d
scale sit on the QUERY side (w = 1/d, 2/d .. 2/d, 1/d) and the key side is
bare u_i u_{i+r}: at a d that is a power of two neither costs a rounding,
and the carried state is sqrt(d) x the symmetric convention's, which no
output sees.

**Who makes the expansion.** The input's shape decides, nothing else
(`_in_vmem`). At a head_dim that is a multiple of 128 a rotation is a
rotation of whole lane registers, and the two products that have an
expansion as an operand, the state read by the queries and the state's
update from the keys, are the Pallas kernels of ops/pallas/
power_retention.py (compiled on a TPU, interpreted elsewhere): they make
each rotation in VMEM beside the MXU, round it where `_expand` rounds, and
no (rows, features) array reaches memory, forward or backward. At any
other head_dim (the tiny models') `expand_queries` / `expand_keys` build
the expansion in jax.numpy, both factors of every feature by a product
with a 0/1 matrix (`_selectors`), and the products are einsums. The two
paths round at the same points: they differ by the order of float32 sums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .pallas import power_retention as _kernels

__all__ = ["power_retention", "expand_queries", "expand_keys",
           "retention_features"]

_F32 = jnp.float32


def retention_features(head_dim):
    """Entries of one expanded vector in this module's layout."""
    return (head_dim // 2 + 1) * head_dim


@functools.lru_cache(maxsize=None)
def _selectors(d):
    """Two (d, features) matrices of 0 and 1: column r * d + i of the first
    picks entry i, of the second entry (i + r) mod d, so u @ tile and
    u @ rotate are the two factors of every feature side by side, and the
    expansion is their elementwise product with no reshape between lanes
    and rows. Matmuls, because that is what the chip rotates lanes fastest
    with (XLA lowers 65 shifted slices to as many copies, and a (rows, 65,
    128) view of the features to layouts with the rows in the lanes); a
    0/1 matrix selects exactly."""
    if d % 2:
        raise ValueError(f"head_dim {d} is odd: the layout pairs rotations "
                         "r and d - r")
    features = retention_features(d)
    r, i = np.divmod(np.arange(features), d)
    tile = np.zeros((d, features), np.float32)
    rotate = np.zeros((d, features), np.float32)
    tile[i, np.arange(features)] = 1.0
    rotate[(i + r) % d, np.arange(features)] = 1.0
    return tile, rotate


def _expand(u, weights=None):
    """(..., d) -> (..., features) in u's type: w_f u_i u_{(i + r) mod d}
    at f = r d + i, the product in float32 and rounded once."""
    first, second = (
        jnp.einsum("...d,df->...f", u, jnp.asarray(m, u.dtype),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=u.dtype).astype(_F32)
        for m in _selectors(u.shape[-1]))
    out = first * second
    if weights is not None:
        out = out * weights
    return out.astype(u.dtype)


def expand_keys(k):
    """phi on the key side: (..., d) -> (..., features), in k's type."""
    return _expand(k)


def expand_queries(q):
    """phi on the query side, with the rotations' weights and the 1 / d
    scale: expand_queries(q) . expand_keys(k) = (q . k)^2 / d."""
    d = q.shape[-1]
    w = np.full((d // 2 + 1,), 2.0 / d, np.float32)
    w[0] = w[d // 2] = 1.0 / d
    return _expand(q, np.repeat(w, d))


def _beside(a, column):
    """(n, d), (n,) -> (n, 2 d): a, then `column`, then zeros. A product
    with v beside a column of ones gives a sum's numerator and its
    normaliser from one pass over the other operand, and the state beside
    its normaliser takes both back out of one expansion (the MXU has the
    room; a second pass over an expansion does not come free)."""
    return jnp.concatenate(
        [a, jnp.zeros_like(a).at[:, 0].set(column.astype(a.dtype))], axis=-1)


def _in_vmem(d):
    """Whether the expansions are made in the kernels of
    ops/pallas/power_retention.py: a rotation is a rotation of whole lane
    registers there, which it is at a multiple of 128 and at no other d."""
    return d % 128 == 0


def _read_state(rows, carried):
    """phi_q(rows) @ carried: (n, d), (features, e) -> (n, e) float32."""
    if _in_vmem(rows.shape[-1]):
        return _kernels.phi_dot(rows, carried, True)
    return jnp.einsum("nf,fe->ne", expand_queries(rows), carried,
                      preferred_element_type=_F32)


def _write_state(k, vw):
    """phi_k(k)^T @ vw: (c, d), (c, e) -> (features, e) float32."""
    if _in_vmem(k.shape[-1]):
        return _kernels.phi_t_dot(k, vw, False)
    return jnp.einsum("sf,se->fe", expand_keys(k), vw,
                      preferred_element_type=_F32)


def _chunk_step(carry, inputs, eps):
    """One chunk of one state head. carry: S (features, d), z (features,),
    float32. inputs: q (r, c, d) for the group's r query heads, k, v
    (c, d), log_g (c,) float32. Returns the carry after the chunk and the
    chunk's outputs (r, c, d) in q's type."""
    state, norm = carry
    q, k, v, log_g = inputs
    dtype = q.dtype
    rep, length, d = q.shape
    rows = q.reshape(rep * length, d)                    # the group's heads
    va = _beside(v, jnp.ones((length,), dtype))          # are rows of one v
    cum = jnp.cumsum(log_g)                              # through position t
    # inside the chunk: a_ts = exp(L_t - L_s) (q_t . k_s)^2 / d for s <= t
    seg = cum[:, None] - cum[None, :]
    causal = jnp.tril(jnp.ones((length, length), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))    # masked BEFORE exp
    scores = jnp.einsum("nd,sd->ns", rows, k, preferred_element_type=_F32)
    scores = scores.reshape(rep, length, length)
    weights = (scores * scores * (decay * (1.0 / d))).astype(dtype)
    both = jnp.einsum("ns,se->ne", weights.reshape(rep * length, length), va,
                      preferred_element_type=_F32)       # numerator | sum
    # what the state carried into the chunk still gives position t
    carried = _beside(state, norm)
    into = jnp.tile(jnp.exp(cum), rep)[:, None]
    both = both + into * _read_state(rows, carried.astype(dtype))
    y = (both[:, :d] / (both[:, d:d + 1] + eps)).astype(dtype)
    # the state after the chunk
    to_end = jnp.exp(cum[-1] - cum)                      # (c,)
    vw = (va.astype(_F32) * to_end[:, None]).astype(dtype)
    added = _write_state(k, vw)
    last = jnp.exp(cum[-1])
    return ((state * last + added[:, :d], norm * last + added[:, d]),
            y.reshape(rep, length, d))


def power_retention(q, k, v, log_g, chunk=1024, eps=1e-6):
    """Degree-2 power retention over whole sequences, `chunk` positions at
    a time. q (batch, seq, heads, d); k, v (batch, seq, state_heads, d);
    log_g (batch, seq, state_heads), the log of each position's decay
    (<= 0). Query head i reads state head i // (heads / state_heads).
    Returns y like q. Differentiable in all four.

    A sequence that is no multiple of `chunk` is padded with zero keys,
    values and log-decays: a padded position neither decays the state nor
    adds to it."""
    with jax.named_scope("pt.retn.scan"):
        batch, seq, heads, d = q.shape
        groups = k.shape[2]
        if heads % groups:
            raise ValueError(f"{heads} query heads over {groups} state heads")
        rep = heads // groups
        chunk = min(chunk, seq)
        pad = (-seq) % chunk
        n_chunks = (seq + pad) // chunk
        log_g = log_g.astype(_F32)

        def per_head(t):
            # (b, s, groups, ...) -> (b * groups, chunks, chunk, ...)
            if pad:
                t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            t = jnp.moveaxis(t, 2, 1)
            return t.reshape((batch * groups, n_chunks, chunk) + t.shape[3:])

        # queries: (b * groups, chunks, rep, chunk, d)
        qs = jnp.moveaxis(per_head(q.reshape(batch, seq, groups, rep, d)),
                          3, 2)
        xs = (qs, per_head(k), per_head(v), per_head(log_g))
        step = jax.checkpoint(lambda c, i: _chunk_step(c, i, eps))
        features = retention_features(d)

        def one_head(inputs):
            zero = (jnp.zeros((features, d), _F32),
                    jnp.zeros((features,), _F32))
            return jax.lax.scan(step, zero, inputs)[1]

        y = jax.lax.map(one_head, xs)        # (b * g, chunks, r, chunk, d)
        y = jnp.moveaxis(y, 2, 3).reshape(batch, groups, seq + pad, rep, d)
        y = jnp.moveaxis(y, 1, 2).reshape(batch, seq + pad, heads, d)
        return y[:, :seq] if pad else y
