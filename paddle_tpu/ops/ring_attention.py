"""Ring attention: exact attention over sequence shards (context parallelism).

reference capability: the SEP/"segment parallel" axis
(python/paddle/distributed/fleet/meta_parallel/segment_parallel.py:26,
fleet/base/topology.py:199). The reference splits sequences across ranks but
ships NO ring-attention kernel (SURVEY.md §5) — attention there requires
gathering the sequence. This module fills that gap TPU-natively:

- K/V shards rotate around the ring with jax.lax.ppermute over the mesh
  axis (ICI neighbor exchange — the optimal topology for a TPU torus).
- Each step computes a partial attention of the local Q block against the
  visiting K/V block; partials merge with the numerically-stable
  log-sum-exp recurrence (same math as flash attention's online softmax).
- Communication overlaps compute: XLA schedules the ppermute DMA of step
  i+1 concurrently with the matmuls of step i.

Use inside shard_map with sequences sharded on `axis_name`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _partial_attention(q, k, v, scale, mask=None):
    """Returns unnormalized (acc, m, l) for merging. q/k/v: (B, S, H, D)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # (B,H,Q,1)
    # guard all-masked rows
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return acc, m_safe, l


def _ring_flash(q, k, v, axis_name: str, causal: bool, scale: float):
    """Flash-kernel ring: each visiting K/V block runs through the Pallas
    streaming kernel (no O(S_local^2) score materialization) and partials
    merge by the (out, lse) recurrence. Kernel roles stay STATIC — the
    first block is always this shard's own (causal diagonal), and in the
    scan every block runs the non-causal kernel with skipped blocks killed
    by masking their lse to -inf before the merge (no runtime branch
    around a pallas call)."""
    from .pallas.flash_attention import _flash_fwd_bhsd, _interpret_default
    b, s_local, h, d = q.shape
    interp = _interpret_default()
    qf = jnp.swapaxes(q, 1, 2).reshape(b * h, s_local, d)
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    def flash(k_cur, v_cur, block_causal):
        kf = jnp.swapaxes(k_cur, 1, 2).reshape(b * h, s_local, d)
        vf = jnp.swapaxes(v_cur, 1, 2).reshape(b * h, s_local, d)
        if interp:
            # the pallas INTERPRETER can't evaluate under shard_map's
            # varying-manual-axes tracking (dynamic_slice vma mismatch,
            # jax-ml/jax check_vma limitation) — on non-TPU backends run a
            # dense block computation with the kernel's exact (out, lse)
            # contract so the ring merge/masking logic is still tested
            s = jnp.einsum("bqd,bkd->bqk", qf, kf,
                           preferred_element_type=jnp.float32) * scale
            if block_causal:
                rows = jnp.arange(s_local)[:, None]
                s = jnp.where(rows >= jnp.arange(s_local)[None, :], s,
                              NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
            out = jnp.einsum("bqk,bkd->bqd", p / l, vf.astype(jnp.float32))
            return out, (m + jnp.log(l))[..., 0]
        out, lse = _flash_fwd_bhsd(qf, kf, vf, block_causal, scale,
                                   interpret=False)
        return out.astype(jnp.float32), lse

    def merge(carry, part):
        out, lse = carry
        out_i, lse_i = part
        lse_new = jnp.logaddexp(lse, lse_i)
        w = jnp.exp(lse - lse_new)[..., None]
        w_i = jnp.exp(lse_i - lse_new)[..., None]
        return out * w + out_i * w_i, lse_new

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # the first visiting block is ALWAYS this shard's own (the causal
    # diagonal) — its kernel role is static, no runtime branch around the
    # pallas call (lax.switch over pallas bodies trips XLA lowering)
    out, lse = flash(k, v, causal)
    k_cur = jax.lax.ppermute(k, axis_name, perm)
    v_cur = jax.lax.ppermute(v, axis_name, perm)

    def step(carry, i):
        ol, k_cur, v_cur = carry
        out_i, lse_i = flash(k_cur, v_cur, False)
        if causal:
            # visiting block index = (my_idx - 1 - i) mod size; under
            # causal attention only blocks strictly BEFORE mine contribute
            # (masking the lse kills skipped blocks in the merge — the
            # kernel role stays static)
            kv_idx = jnp.mod(my_idx - 1 - i, axis_size)
            valid = kv_idx < my_idx
            lse_i = jnp.where(valid, lse_i, NEG_INF)
        ol = merge(ol, (out_i, lse_i))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (ol, k_nxt, v_nxt), None

    ((out, lse), _, _), _ = jax.lax.scan(
        step, ((out, lse), k_cur, v_cur), jnp.arange(axis_size - 1))
    out = out.reshape(b, h, s_local, d)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash_diff(q, k, v, axis_name, causal, scale):
    return _ring_flash(q, k, v, axis_name, causal, scale)


def _ring_flash_fwd(q, k, v, axis_name, causal, scale):
    return _ring_flash(q, k, v, axis_name, causal, scale), (q, k, v)


def _ring_flash_bwd(axis_name, causal, scale, res, g):
    # pallas_call has no AD rule; the backward recomputes through the dense
    # ring (numerically identical forward) and differentiates that —
    # rematerialization, same contract as flash attention's own bwd split
    q, k, v = res
    _, pull = jax.vjp(
        lambda q_, k_, v_: _ring_dense(q_, k_, v_, axis_name, causal, scale),
        q, k, v)
    return pull(g)


_ring_flash_diff.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: float | None = None, use_flash: bool | None = None):
    """Exact attention where q/k/v are sharded on the sequence dim over
    `axis_name`. Layout: (batch, local_seq, heads, head_dim).

    Must be called inside shard_map/pjit with `axis_name` in scope.
    use_flash: route each visiting block through the Pallas streaming
    kernel (default: on TPU) instead of the dense einsum partial — the
    local block never materializes an S_local x S_local score matrix, so
    per-shard sequence length is HBM-bound, not VMEM/score-bound. The
    flash forward is paired (custom_vjp) with the dense ring as its
    backward, so jax.grad works identically on both paths.
    """
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if use_flash:
        return _ring_flash_diff(q, k, v, axis_name, causal, scale)
    return _ring_dense(q, k, v, axis_name, causal, scale)


def _ring_dense(q, k, v, axis_name, causal, scale):
    b, s_local, h, d = q.shape
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    q_global = my_idx * s_local + jnp.arange(s_local)

    def mask_for(kv_idx):
        if not causal:
            return None
        k_global = kv_idx * s_local + jnp.arange(s_local)
        return (q_global[:, None] >= k_global[None, :])[None, None]  # (1,1,Q,K)

    acc = jnp.zeros((b, h, s_local, d), jnp.float32)
    m = jnp.full((b, h, s_local, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_local, 1), jnp.float32)
    # shard_map tracks varying-manual-axes; mark the carries as varying
    # over the ring axis so the scan carry types match
    acc, m, l = (jax.lax.pcast(x, (axis_name,), to="varying")
                 for x in (acc, m, l))

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def merge(carry, k_cur, v_cur, kv_idx):
        acc, m, l = carry
        acc_i, m_i, l_i = _partial_attention(q, k_cur, v_cur, scale,
                                             mask_for(kv_idx))
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_i - m_new)
        return (acc * alpha + acc_i * beta, m_new, l * alpha + l_i * beta)

    def step(carry, _):
        acc_m_l, k_cur, v_cur, kv_idx = carry
        acc_m_l = merge(acc_m_l, k_cur, v_cur, kv_idx)
        # rotate k/v to the next ring position (ICI neighbor exchange)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        kv_idx = jnp.asarray((kv_idx - 1) % axis_size, jnp.int32)
        return (acc_m_l, k_nxt, v_nxt, kv_idx), None

    # first axis_size-1 steps rotate; the final block is merged without a
    # wasted trailing ppermute
    ((acc, m, l), k_last, v_last, kv_last), _ = jax.lax.scan(
        step, ((acc, m, l), k, v, jnp.asarray(my_idx, jnp.int32)), None,
        length=axis_size - 1)
    acc, m, l = merge((acc, m, l), k_last, v_last, kv_last)

    out = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # back to (B, S, H, D)
