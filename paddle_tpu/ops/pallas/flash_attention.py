"""Flash attention (forward + backward) as Pallas TPU kernels.

reference capability: paddle/phi/kernels/gpu/flash_attn_kernel.cu and
flash_attn_grad_kernel.cu (FlashAttention-2 via dynload) +
python/paddle/nn/functional/flash_attention.py.

TPU-native design (not a CUDA port):
- Forward: grid (batch*heads, q_blocks, k_blocks). Q/K/V blocks are DMA'd
  per grid step by BlockSpec — no whole-K/V-in-VMEM residency, so sequence
  length is bounded by HBM, not VMEM. The online-softmax running
  (m, l, acc) state lives in VMEM scratch that persists across the
  (sequential, innermost) k-block grid dimension. The forward also emits
  the per-row logsumexp for the backward.
- Backward: the FlashAttention-2 split. delta = rowsum(dO * O) is a cheap
  XLA elementwise reduce. dQ kernel: grid (bh, q_blocks, k_blocks),
  accumulates scale * dS @ K into VMEM scratch. dK/dV kernel: grid
  (bh, k_blocks, q_blocks), accumulates dS^T @ Q and P^T @ dO. P is
  rematerialized per block from (Q, K, lse) — nothing O(S^2) is ever
  stored.
- MXU does the matmuls with fp32 accumulation (preferred_element_type);
  VPU does the softmax pieces. Causal: blocks strictly above the diagonal
  skip compute via @pl.when; the diagonal block is masked with
  broadcasted_iota. Cross-length causal uses the bottom-right-aligned
  convention (offset = seq_k - seq_q), matching the dense reference.

On non-TPU backends the kernels run under the Pallas interpreter (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework import flags as _flags

NEG_INF = -1e30
_LANES = 128  # store per-row scalars broadcast across one lane tile


def _causal_mask(s, qi, kj, block_q, block_k, offset):
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(rows + offset >= cols, s, NEG_INF)


def _ktail_mask(s, kj, block_q, block_k, seq_k):
    cols = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(cols < seq_k, s, NEG_INF)


def _block_needed(qi, kj, block_q, block_k, causal, offset):
    if not causal:
        return True
    # any (row, col) with row + offset >= col in this block pair?
    return (qi * block_q + block_q - 1 + offset) >= (kj * block_k)


_flags.define_flag(
    "flash_packed_grid", "auto",
    "causal flash kernels iterate only the lower-triangle (q,k) block "
    "pairs instead of a rectangular grid with half the steps masked off "
    "(saves the skipped steps' k/v DMAs and grid overhead). 'auto' (the "
    "default since the bf16 finalization): ON under the Pallas "
    "interpreter (numerically exact, pinned by tier-1) and on real TPUs "
    "only when the baked attention ledger marks packed_grid_validated "
    "for the device — the sqrt-based index maps are non-affine, so the "
    "ledger flips this per-device once a chip run (chip_smoke.py's "
    "kernel phase reports it) shows Mosaic lowers them and they match. "
    "on/off force it either way. NOTE: read at TRACE time — set the env "
    "var before process start (or clear jit caches); set_flags after a "
    "shape compiled does not retrace it.")


def _packing_on():
    from .attention_router import packed_grid_enabled
    return packed_grid_enabled()


def _tri_decode(p):
    """Linear triangle index -> (qi, kj) with kj <= qi (row-major packing:
    p = qi*(qi+1)/2 + kj). The causal-packed grid iterates ONLY the lower
    triangle of (q block, k block) pairs — a full rectangular grid spends
    half its steps (and their k/v block DMAs) on pairs the causal mask
    fully discards. f32 sqrt is exact for the sizes involved (p < 2^23);
    the +-1 correction guards the perfect-square boundary cases."""
    pf = p.astype(jnp.float32)
    qi = jnp.floor((jnp.sqrt(8.0 * pf + 1.0) - 1.0) * 0.5).astype(jnp.int32)
    tri = qi * (qi + 1) // 2
    qi = jnp.where(p < tri, qi - 1, qi)
    qi = jnp.where(p >= (qi + 1) * (qi + 2) // 2, qi + 1, qi)
    kj = p - qi * (qi + 1) // 2
    return qi, kj


def _tri_maps(g):
    """(qmap, kmap) BlockSpec index maps for the packed (bh, tri) grid —
    shared by the fwd and dQ kernels (the dKV kernel's reversed-row
    staircase variant lives at its call site)."""
    def qmap(b, p):
        qi, _ = _tri_decode(p)
        return (b, qi, 0)

    def kmap(b, p):
        _, kj = _tri_decode(p)
        return (b // g, kj, 0)
    return qmap, kmap


def _fa_fwd_kernel(q_ref, k_ref, v_ref, *refs,
                   causal: bool, scale: float, seq_k: int, block_q: int,
                   block_k: int, offset: int, mask_k_tail: bool,
                   packed: bool = False, epilogue: bool = False,
                   rms_eps: float = 1e-6, rms_d: int = 0):
    # optional fused epilogue (FlashFuser-style widened fusion): two extra
    # inputs — residual block + lane-broadcast RMSNorm gamma — and the
    # flush writes rmsnorm(attn + residual) * gamma instead of attn,
    # saving one full HBM round-trip of the attention output. The norm
    # axis is the head dim (rms_d = TRUE d, so zero-pad columns don't
    # skew the mean).
    if epilogue:
        res_ref, w_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    else:
        o_ref, lse_ref, m_s, l_s, acc_s = refs
    if packed:   # causal lower-triangle grid: (bh, tri(nq))
        qi, kj = _tri_decode(pl.program_id(1))
        is_last = kj == qi   # kj_max(qi) == qi when block_q == block_k
    else:
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        is_last = kj == pl.num_programs(2) - 1

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def _compute():
        # dots run on NATIVE (bf16) operands with f32 accumulation — the
        # MXU's full-rate mode and exactly the dense XLA path's precision
        # (einsum + preferred_element_type=f32). Upcasting operands to
        # f32 first quarters MXU throughput; r5 measured the f32-operand
        # flavor of this kernel at 0.86x dense fwd / 0.52x dense bwd.
        q = q_ref[0]                              # (block_q, d)
        k = k_ref[0]                              # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if mask_k_tail:
            s = _ktail_mask(s, kj, block_q, block_k, seq_k)
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        m_prev = m_s[...][:, :1]
        l_prev = l_s[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    if causal and not packed:
        pl.when(_block_needed(qi, kj, block_q, block_k, causal, offset))(
            _compute)
    else:
        _compute()   # packed grid contains only needed blocks

    @pl.when(is_last)
    def _flush():
        l = jnp.maximum(l_s[...][:, :1], 1e-30)
        out = acc_s[...] / l
        if epilogue:
            h = out + res_ref[0].astype(jnp.float32)
            # mean over the TRUE head dim (pad columns are zero in both
            # attn out and residual, so the sum is exact)
            ms = jnp.sum(h * h, axis=-1, keepdims=True) / rms_d
            out = h * jax.lax.rsqrt(ms + rms_eps) * \
                w_ref[...][:1, :].astype(jnp.float32)
        o_ref[0] = out.astype(o_ref.dtype)
        # lane-expanded (block_q, _LANES) write: TPU block shapes need the
        # last two dims tiled (8, 128); a (1, block_q) row per grid step is
        # unlowerable. m_s/l_s already hold the row value in every lane.
        # (Same layout as jax's official TPU flash kernel's l/m outputs.)
        lse_ref[0] = m_s[...] + jnp.log(jnp.maximum(l_s[...], 1e-30))


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  dq_s, *, causal: bool, scale: float, seq_k: int,
                  block_q: int, block_k: int, offset: int,
                  mask_k_tail: bool, packed: bool = False):
    if packed:   # causal lower-triangle grid: (bh, tri(nq))
        qi, kj = _tri_decode(pl.program_id(1))
        is_last = kj == qi
    else:
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        is_last = kj == pl.num_programs(2) - 1

    @pl.when(kj == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def _compute():
        # bf16 operands + f32 accumulation on every dot (see fwd kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                   # (block_q, 1) of lanes
        delta = delta_ref[0][:, :1]
        s = scale * jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if mask_k_tail:
            s = _ktail_mask(s, kj, block_q, block_k, seq_k)
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        p = jnp.exp(s - lse)                      # (block_q, block_k)
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_s[...] += scale * jax.lax.dot_general(
            ds, k, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal and not packed:
        pl.when(_block_needed(qi, kj, block_q, block_k, causal, offset))(
            _compute)
    else:
        _compute()   # packed grid contains only needed blocks

    @pl.when(is_last)
    def _flush():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_s, dv_s, *, causal: bool, scale: float,
                   seq_k: int, block_q: int, block_k: int, offset: int,
                   mask_k_tail: bool, n_rep: int = 1, packed_nq: int = 0):
    # grid (bh_kv, k blocks, q-head group reps, q blocks): the scratch
    # accumulates over BOTH the group axis and the q blocks, flushing once
    # per kv block — this is how GQA's dK/dV reduction happens in-kernel.
    # Packed (causal, square blocks): grid (bh_kv, tri(nq), reps) where the
    # triangle index runs (kj, qi >= kj) pairs via u = nq-1-kj, w = qi-kj
    # (so per-kj pairs are consecutive and the scratch flushes per kv block)
    if packed_nq:
        u, w = _tri_decode(pl.program_id(1))
        kj = packed_nq - 1 - u
        qi = kj + w
        rr = pl.program_id(2)
        first = (w == 0) & (rr == 0)
        last = (w == u) & (rr == n_rep - 1)
    else:
        kj = pl.program_id(1)
        rr = pl.program_id(2)
        qi = pl.program_id(3)
        first = (qi == 0) & (rr == 0)
        last = (qi == pl.num_programs(3) - 1) & (rr == n_rep - 1)

    @pl.when(first)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def _compute():
        # bf16 operands + f32 accumulation on every dot (see fwd kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = scale * jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if mask_k_tail:
            s = _ktail_mask(s, kj, block_q, block_k, seq_k)
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        p = jnp.exp(s - lse)
        p_lo = p.astype(do.dtype)
        dv_s[...] += jax.lax.dot_general(
            p_lo, do, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # (block_k, d)
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_s[...] += scale * jax.lax.dot_general(
            ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal and not packed_nq:
        pl.when(_block_needed(qi, kj, block_q, block_k, causal, offset))(
            _compute)
    else:
        _compute()   # packed grid contains only needed blocks

    @pl.when(last)
    def _flush():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _pad_to(x, axis, multiple):
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def _interpret_default():
    return jax.default_backend() != "tpu"


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying `like`'s varying-manual-axes: inside a
    shard_map (check_vma), pallas_call outputs must declare how they
    vary over the mesh (e.g. the ring-attention 'sep' axis)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _block_sizes(sq, sk, block_q, block_k):
    return min(block_q, sq), min(block_k, sk)


# candidate (block_q, block_k) VMEM tilings for the autotuner — the TPU
# analog of the reference's per-algorithm candidate list (auto_tune_base.h).
# Large tiles are cheap in VMEM (512x512: ~1.3MB of block buffers vs the
# ~128MB budget) and cut grid-iteration overhead 8-16x vs 128x128.
_BLOCK_CANDIDATES = ((128, 128), (256, 128), (128, 256), (256, 256),
                     (512, 128), (128, 512), (256, 512), (512, 256),
                     (512, 512))


# Shipped block-size table keyed by (kind, seq bucket, head_dim),
# consulted when autotune is off so production gets measured tiles
# without paying a tuning pass. Populate from a hardware autotune run:
# tools/flash_vs_xla.py (on the chip) then tools/bake_flash_blocks.py
# prints the literal. Empty or missing entries fall back to (128, 128).
_SHIPPED_BLOCKS = {}


def _shipped_blocks(kind, sq, d, device_kind):
    if "v5 lite" not in device_kind:
        return None
    bucket = 1024 if sq <= 1024 else (2048 if sq <= 2048 else 4096)
    return _SHIPPED_BLOCKS.get((kind, bucket, d))


def _tuned_blocks(kind, bh, sq, sk, d, dtype, causal, interpret):
    """Resolve (block_q, block_k): the baked attention ledger (versioned,
    device-tagged — tools/bake_flash_blocks.py --ledger), the legacy
    _SHIPPED_BLOCKS literal, the runtime-timed winner when
    FLAGS_use_autotune is on, else (128, 128). Timing runs on synthetic
    zeros, so this works even while the caller is being traced."""
    from .autotune import autotune, autotune_enabled
    if not autotune_enabled():
        if not interpret:
            from .attention_router import ledger_blocks
            hit = ledger_blocks(kind, bh, sq, sk, d, dtype, causal)
            if hit:
                return hit
        if _SHIPPED_BLOCKS and not interpret:
            hit = _shipped_blocks(kind, sq, d,
                                  getattr(jax.devices()[0], "device_kind", ""))
            if hit and hit[0] <= sq and hit[1] <= sk:
                return hit
        return 128, 128
    dev = jax.devices()[0]
    # tb (the clamped tuning batch*heads) is part of the key: block ranking
    # depends on grid parallelism, so a winner timed at 2 heads must not be
    # served to a 64-head caller
    tb = min(bh, 64)
    key = (kind, tb, sq, sk, d, str(dtype), bool(causal), dev.device_kind)

    def make_runner(cfg):
        bq, bk = cfg
        if bq > sq or bk > sk:
            raise ValueError("block larger than sequence")
        # tune at (close to) the caller's real batch*heads: block choice
        # interacts with grid parallelism, and a 2-head proxy ranked
        # candidates differently from the bh=64 train shape on v5e
        q = jnp.zeros((tb, sq, d), dtype)
        k = jnp.zeros((tb, sk, d), dtype)
        v = jnp.zeros((tb, sk, d), dtype)
        # each candidate runs 8 iterations inside ONE compiled scan so
        # per-dispatch launch overhead does not rank the candidates. The
        # carry feeds q so the body can't be hoisted.
        if kind == "fwd":
            def step(qq):
                o, _ = _flash_fwd_bhsd(qq, k, v, causal, 1.0, block_q=bq,
                                       block_k=bk, interpret=interpret)
                return jnp.sum(o.astype(jnp.float32))
        else:
            # o / lse only need the forward's shapes: timing is on zeros
            lse = jnp.zeros((tb, sq), jnp.float32)

            def step(qq):
                outs = _flash_bwd_bhsd(qq, k, v, q, lse, q, causal, 1.0,
                                       block_q=bq, block_k=bk,
                                       interpret=interpret)
                return sum(jnp.sum(x.astype(jnp.float32)) for x in outs)

        @jax.jit
        def loop():
            def body(c, _):
                s = step(q + c)
                return (s * 0).astype(q.dtype), None
            c, _ = jax.lax.scan(body, jnp.zeros((), q.dtype), None, length=8)
            return c

        def run():
            jax.block_until_ready(loop())
        return run

    return autotune(key, _BLOCK_CANDIDATES, make_runner, default=(128, 128))


def _flash_fwd_bhsd(q, k, v, causal, scale, block_q=128, block_k=128,
                    interpret=None, q_per_kv=1, residual=None,
                    rms_weight=None, rms_eps=1e-6, rms_d=None):
    """q: (BH, Sq, D), k/v: (BH // q_per_kv, Sk, D) -> (out, lse).

    residual/rms_weight (both given or neither): fuse the
    rmsnorm(attn + residual) * weight epilogue into the kernel's flush —
    the attention output never round-trips HBM unnormalized. residual:
    (BH, Sq, D); rms_weight: (D,). rms_d = the TRUE head dim when D is
    zero-padded (the mean divisor). Forward-only (no VJP).

    Ragged sequence lengths are padded to block multiples; padded K columns
    are masked in-kernel, padded Q rows sliced off on return (so results
    are exact for any length).

    GQA (q_per_kv > 1): kv stays UNEXPANDED — the k/v BlockSpec index map
    folds the head grouping (q index b -> kv index b // q_per_kv), so no
    (B, S, H, D) broadcast of KV ever materializes in HBM. With batch-major
    bh layout (bi*h + hq), b // q_per_kv == bi*kvh + hq // rep exactly."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q, block_k = _block_sizes(sq, sk, block_q, block_k)
    q_p = _pad_to(q, 1, block_q)
    k_p = _pad_to(k, 1, block_k)
    v_p = _pad_to(v, 1, block_k)
    sq_p, sk_p = q_p.shape[1], k_p.shape[1]
    mask_k_tail = sk_p != sk
    if interpret is None:
        interpret = _interpret_default()
    g = q_per_kv
    nq, nk = sq_p // block_q, sk_p // block_k
    # causal + square blocks + equal (padded) lengths: pack the grid to
    # the lower triangle of (q block, k block) pairs — the rectangular
    # grid spends half its steps and k/v DMAs on fully-masked pairs
    packed = (causal and sk == sq and sq_p == sk_p
              and block_q == block_k and _packing_on())
    epilogue = residual is not None
    kernel = functools.partial(
        _fa_fwd_kernel, causal=causal, scale=scale, seq_k=sk,
        block_q=block_q, block_k=block_k, offset=sk - sq,
        mask_k_tail=mask_k_tail, packed=packed, epilogue=epilogue,
        rms_eps=rms_eps, rms_d=(rms_d or d))
    if packed:
        grid = (bh, nq * (nq + 1) // 2)
        qmap, kmap = _tri_maps(g)
        in_maps = [qmap, kmap, kmap]
        out_maps = [qmap, qmap]
        wmap = lambda b, p: (0, 0)   # noqa: E731
    else:
        grid = (bh, nq, nk)
        in_maps = [lambda b, i, j: (b, i, 0),
                   lambda b, i, j: (b // g, j, 0),
                   lambda b, i, j: (b // g, j, 0)]
        out_maps = [lambda b, i, j: (b, i, 0), lambda b, i, j: (b, i, 0)]
        wmap = lambda b, i, j: (0, 0)   # noqa: E731
    in_specs = [
        pl.BlockSpec((1, block_q, d), in_maps[0]),
        pl.BlockSpec((1, block_k, d), in_maps[1]),
        pl.BlockSpec((1, block_k, d), in_maps[2]),
    ]
    operands = [q_p, k_p, v_p]
    if epilogue:
        # residual rides the q index map; gamma is one (8, d) sublane-
        # tiled block (a bare (1, d) block is unlowerable on TPU), f32 so
        # bf16 gammas don't hit the (16, 128) bf16 tile minimum
        in_specs.append(pl.BlockSpec((1, block_q, d), in_maps[0]))
        in_specs.append(pl.BlockSpec((8, d), wmap))
        operands.append(_pad_to(residual, 1, block_q))
        operands.append(jnp.broadcast_to(
            rms_weight.astype(jnp.float32)[None, :], (8, d)))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), out_maps[0]),
            pl.BlockSpec((1, block_q, _LANES), out_maps[1]),
        ],
        out_shape=[
            _sds((bh, sq_p, d), q.dtype, q),
            _sds((bh, sq_p, _LANES), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="fa_fwd",
    )(*operands)
    # collapse the lane-expanded lse back to (bh, sq_p) right away so the
    # autodiff residual is O(S), not O(S * 128)
    return out[:, :sq], lse[..., 0]


def _flash_bwd_bhsd(q, k, v, o, lse, g, causal, scale, block_q=128,
                    block_k=128, interpret=None, q_per_kv=1):
    """FlashAttention-2 backward: returns (dq, dk, dv), all in input dtype.
    GQA: k/v carry BH // q_per_kv heads; dk/dv come back already reduced
    over the query-head group (the rep axis rides the grid, accumulating
    into the same VMEM scratch — no XLA-side segment-sum needed)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q, block_k = _block_sizes(sq, sk, block_q, block_k)
    if interpret is None:
        interpret = _interpret_default()

    # delta = rowsum(dO * O): cheap XLA elementwise+reduce, fp32
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    q_p = _pad_to(q, 1, block_q)
    do_p = _pad_to(g, 1, block_q)
    delta_p = _pad_to(delta, 1, block_q)
    k_p = _pad_to(k, 1, block_k)
    v_p = _pad_to(v, 1, block_k)
    sq_p, sk_p = q_p.shape[1], k_p.shape[1]
    # lse from the forward is already padded to a block_q multiple of the
    # forward's padding; re-pad defensively (values for pad rows are finite,
    # and pad-row contributions vanish because dO pad rows are zero).
    lse_p = _pad_to(lse, 1, block_q)[:, :sq_p]
    mask_k_tail = sk_p != sk
    offset = sk - sq
    common = dict(causal=causal, scale=scale, seq_k=sk, block_q=block_q,
                  block_k=block_k, offset=offset, mask_k_tail=mask_k_tail)

    nq, nk = sq_p // block_q, sk_p // block_k

    # lane-expand the per-row scalars: a (1, block_q) block is unlowerable
    # on TPU (last-two-dims tiling), so feed (1, block_q, _LANES) blocks
    lse3 = jnp.broadcast_to(lse_p[..., None], (bh, sq_p, _LANES))
    delta3 = jnp.broadcast_to(delta_p[..., None], (bh, sq_p, _LANES))

    grp = q_per_kv
    bh_kv = bh // grp
    # same lower-triangle packing as the forward (see _flash_fwd_bhsd):
    # dq accumulates over kj <= qi only, so the rectangular grid's upper
    # half is pure skipped-step overhead for causal self-attention
    packed = (causal and sk == sq and sq_p == sk_p
              and block_q == block_k and _packing_on())
    if packed:
        dq_grid = (bh, nq * (nq + 1) // 2)
        dq_qmap, dq_kmap = _tri_maps(grp)
        dq_in = [dq_qmap, dq_kmap, dq_kmap, dq_qmap, dq_qmap, dq_qmap]
        dq_out = dq_qmap
    else:
        dq_grid = (bh, nq, nk)
        dq_qm = lambda b, i, j: (b, i, 0)   # noqa: E731
        dq_km = lambda b, i, j: (b // grp, j, 0)   # noqa: E731
        dq_in = [dq_qm, dq_km, dq_km, dq_qm, dq_qm, dq_qm]
        dq_out = dq_qm
    dq = pl.pallas_call(
        functools.partial(_fa_dq_kernel, packed=packed, **common),
        grid=dq_grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), dq_in[0]),
            pl.BlockSpec((1, block_k, d), dq_in[1]),
            pl.BlockSpec((1, block_k, d), dq_in[2]),
            pl.BlockSpec((1, block_q, d), dq_in[3]),
            pl.BlockSpec((1, block_q, _LANES), dq_in[4]),
            pl.BlockSpec((1, block_q, _LANES), dq_in[5]),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), dq_out),
        out_shape=_sds((bh, sq_p, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="fa_bwd_dq",
    )(q_p, k_p, v_p, do_p, lse3, delta3)

    # dkv grid: (kv heads, kv blocks, group reps, q blocks) — i innermost,
    # then r, so for a fixed kv block the scratch accumulates over the
    # whole query-head group before flushing (n_rep=grp in the kernel).
    # Packed: (kv heads, tri(nq), reps) — see _fa_dkv_kernel
    if packed:
        def dkv_qmap(b, p, r):
            u, w = _tri_decode(p)
            return (b * grp + r, (nq - 1 - u) + w, 0)

        def dkv_kmap(b, p, r):
            u, _ = _tri_decode(p)
            return (b, nq - 1 - u, 0)
        dkv_grid = (bh_kv, nq * (nq + 1) // 2, grp)
        dkv_in = [dkv_qmap, dkv_kmap, dkv_kmap, dkv_qmap, dkv_qmap,
                  dkv_qmap]
        dkv_out = dkv_kmap
        dkv_extra = {"packed_nq": nq}
    else:
        dkv_qm = lambda b, j, r, i: (b * grp + r, i, 0)   # noqa: E731
        dkv_km = lambda b, j, r, i: (b, j, 0)   # noqa: E731
        dkv_grid = (bh_kv, nk, grp, nq)
        dkv_in = [dkv_qm, dkv_km, dkv_km, dkv_qm, dkv_qm, dkv_qm]
        dkv_out = dkv_km
        dkv_extra = {}
    dk, dv = pl.pallas_call(
        functools.partial(_fa_dkv_kernel, n_rep=grp, **dkv_extra, **common),
        grid=dkv_grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), dkv_in[0]),
            pl.BlockSpec((1, block_k, d), dkv_in[1]),
            pl.BlockSpec((1, block_k, d), dkv_in[2]),
            pl.BlockSpec((1, block_q, d), dkv_in[3]),
            pl.BlockSpec((1, block_q, _LANES), dkv_in[4]),
            pl.BlockSpec((1, block_q, _LANES), dkv_in[5]),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), dkv_out),
            pl.BlockSpec((1, block_k, d), dkv_out),
        ],
        out_shape=[
            _sds((bh_kv, sk_p, d), k.dtype, k),
            _sds((bh_kv, sk_p, d), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="fa_bwd_dkv",
    )(q_p, k_p, v_p, do_p, lse3, delta3)

    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


def _xla_attention_bhsd(q, k, v, causal, scale):
    """Dense reference (O(S^2) memory). Used by tests and tiny shapes."""
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _fwd_blocks(q, k, causal):
    bh, sq, d = q.shape
    return _tuned_blocks("fwd", bh, sq, k.shape[1], d, q.dtype, causal,
                         _interpret_default())


def _bwd_blocks(q, k, causal):
    bh, sq, d = q.shape
    return _tuned_blocks("bwd", bh, sq, k.shape[1], d, q.dtype, causal,
                         _interpret_default())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention_bhsd(q, k, v, causal, scale, q_per_kv=1):
    bq, bk = _fwd_blocks(q, k, causal)
    out, _ = _flash_fwd_bhsd(q, k, v, causal, scale, block_q=bq, block_k=bk,
                             q_per_kv=q_per_kv)
    return out


def _fa_fwd(q, k, v, causal, scale, q_per_kv=1):
    bq, bk = _fwd_blocks(q, k, causal)
    out, lse = _flash_fwd_bhsd(q, k, v, causal, scale, block_q=bq, block_k=bk,
                               q_per_kv=q_per_kv)
    return out, (q, k, v, out, lse)


def _dense_remat_bwd(q, k, v, causal, scale, q_per_kv, g):
    """Backward via XLA-dense rematerialization (GQA-grouped).

    Measured on TPU v5e (r5): ISOLATED-kernel timing favors this hybrid
    over the Pallas dQ/dKV split (9.0ms vs 12.9ms fwd+bwd at s2048 d128
    with the f32-operand kernels), but END-TO-END the 535m train step
    measured the opposite — 0.406 MFU hybrid vs 0.426 full-pallas — the
    transient (bh, sq, sk) fp32 buffer's HBM pressure costs the scheduled
    step more than the kernel gap saves. It remains the better backward
    for zero-padded head dims (d96: 6.7ms vs 13.8ms per-kernel, the pad
    taxes the Pallas bwd twice) and is selectable via
    FLAGS_flash_attention_bwd=xla."""
    def f(q_, k_, v_):
        if q_per_kv == 1:
            return _xla_attention_bhsd(q_, k_, v_, causal, scale)
        bh, sq, d = q_.shape
        bkv = k_.shape[0]
        qg = q_.reshape(bkv, q_per_kv, sq, d)
        s = jnp.einsum("bgqd,bkd->bgqk", qg, k_,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            sk = k_.shape[1]
            mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
            s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v_.dtype)
        o = jnp.einsum("bgqk,bkd->bgqd", p, v_)
        return o.reshape(bh, sq, d)

    _, pull = jax.vjp(f, q, k, v)
    return pull(g)


_flags.define_flag(
    "flash_attention_bwd", "auto",
    "flash-attention backward: 'pallas' (FA-2 dQ/dKV kernels), 'xla' "
    "(dense rematerialization, XLA-differentiated), or 'auto' (routed "
    "per shape by ops/pallas/attention_router from the baked hardware "
    "ledger: the r5 end-to-end A/B on v5e measured the full-pallas bwd "
    "at 0.426 MFU vs 0.406 for the xla-remat hybrid on the 535m train "
    "step even though isolated-kernel timing favors the hybrid — the "
    "dense remat's O(S^2) buffer costs more in HBM pressure than it "
    "saves in kernel time once the whole step is scheduled — while the "
    "zero-padded d96 shapes measured the hybrid winning both ways)")


def _fa_bwd(causal, scale, q_per_kv, res, g):
    q, k, v, o, lse = res
    mode = _flags.flag_value("flash_attention_bwd")
    if mode == "auto":
        # per-shape routed choice with provenance (ledger -> measurement
        # -> heuristic); a router failure propagates
        from .attention_router import route
        mode = route(q.shape[0], q.shape[1], k.shape[1], q.shape[2],
                     q.dtype, causal).bwd
    if mode == "xla":
        return _dense_remat_bwd(q, k, v, causal, scale, q_per_kv, g)
    bq, bk = _bwd_blocks(q, k, causal)
    return _flash_bwd_bhsd(q, k, v, o, lse, g, causal, scale,
                           block_q=bq, block_k=bk, q_per_kv=q_per_kv)


_flash_attention_bhsd.defvjp(_fa_fwd, _fa_bwd)


# mesh axes (the names parallel/spmd.py create_mesh fixes) the kernel may be
# split over: batch rows over the data-parallel axes, heads over the
# tensor-parallel axis. Both are independent rows of the kernel's grid.
_BATCH_AXES = ("dp", "sharding")
_HEAD_AXIS = "mp"


def _mesh_spec(b, h, kvh):
    """PartitionSpec of the (batch, seq, heads, head_dim) operands under
    the ambient mesh (jax.set_mesh — SpmdTrainer sets it around its step),
    or None outside a multi-device mesh.

    GSPMD cannot partition a Mosaic kernel: on a TPU a pallas_call whose
    operands are sharded fails to lower with "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map." So
    under a mesh the kernel runs per shard inside a shard_map. An axis
    that does not divide its dimension, or is already manual (a caller's
    own shard_map), is left out and that dimension is replicated."""
    mesh = jax.sharding.get_abstract_mesh()
    free = {a: n for a, n in mesh.shape.items()
            if n > 1 and a not in mesh.manual_axes}
    if not free:
        return None
    batch, n = [], 1
    for a in _BATCH_AXES:
        if a in free and b % (n * free[a]) == 0:
            batch.append(a)
            n *= free[a]
    m = free.get(_HEAD_AXIS)
    heads = _HEAD_AXIS if m and h % m == 0 and kvh % m == 0 else None
    return jax.sharding.PartitionSpec(tuple(batch) or None, None, heads, None)


def flash_attention_bshd(q, k, v, causal=False, scale=None):
    """Paddle flash_attention layout: (batch, seq, heads, head_dim).

    GQA-native: k/v may carry FEWER heads than q (num_kv_heads divides
    num_heads); the kernel groups query heads onto shared KV blocks via
    the BlockSpec index map, so the (B, S, H, D) KV broadcast the
    reference materializes never exists, and dK/dV come back reduced.

    Under an ambient multi-device mesh the kernel runs per shard of batch
    and heads inside a shard_map (see _mesh_spec)."""
    b, _, h, d = q.shape
    kvh = k.shape[2]
    if h % kvh:
        raise ValueError(f"num_heads {h} not divisible by kv heads {kvh}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    local = functools.partial(_flash_attention_local, causal=causal,
                              scale=scale)
    spec = _mesh_spec(b, h, kvh)
    if spec is None:
        return local(q, k, v)
    return jax.shard_map(local, in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)


def _flash_attention_local(q, k, v, causal, scale):
    """flash_attention_bshd on the operands one device holds."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    # lane-align the head dim (e.g. 96 -> 128, the llama_780m shape): zero
    # pad columns change neither QK^T nor PV, their grads come back zero,
    # and `scale` is already fixed from the TRUE d above. Costs d_pad/d
    # extra MXU work — cheaper than losing the O(S^2) HBM win at long seq.
    d_pad = (-d) % _LANES
    if d_pad:
        padw = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        q = jnp.pad(q, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
    dp = d + d_pad
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, dp)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * kvh, sk, dp)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * kvh, sk, dp)
    out = _flash_attention_bhsd(qt, kt, vt, causal, scale, h // kvh)
    out = jnp.swapaxes(out.reshape(b, h, sq, dp), 1, 2)
    return out[..., :d] if d_pad else out


def flash_attention_rms_epilogue_bshd(q, k, v, residual, rms_weight,
                                      causal=True, scale=None, eps=1e-6):
    """Flash attention with the rmsnorm(attn + residual) * gamma epilogue
    FUSED into the kernel's flush step — the attention output is written
    to HBM exactly once, already normalized (the FlashFuser-style
    widened fusion the backend router can select where it wins).

    Layout matches flash_attention_bshd: q (b, sq, h, d), k/v GQA-native
    (b, sk, kvh, d); residual (b, sq, h, d); rms_weight (d,). The norm
    axis is the HEAD dim (per-head RMSNorm — use h=1 for a full-hidden
    norm). Forward-only: no VJP is defined (the training path routes
    through the unfused custom-vjp kernels); intended for inference /
    serving prefill.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    if h % kvh:
        raise ValueError(f"num_heads {h} not divisible by kv heads {kvh}")
    if residual.shape != q.shape:
        raise ValueError(f"residual shape {residual.shape} != q {q.shape}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    d_pad = (-d) % _LANES
    if d_pad:
        padw = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        q = jnp.pad(q, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        residual = jnp.pad(residual, padw)
        rms_weight = jnp.pad(rms_weight, ((0, d_pad),))
    dp = d + d_pad
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, dp)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * kvh, sk, dp)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * kvh, sk, dp)
    rt = jnp.swapaxes(residual, 1, 2).reshape(b * h, sq, dp)
    bq, bk = _fwd_blocks(qt, kt, causal)
    out, _ = _flash_fwd_bhsd(qt, kt, vt, causal, scale, block_q=bq,
                             block_k=bk, q_per_kv=h // kvh, residual=rt,
                             rms_weight=rms_weight, rms_eps=eps, rms_d=d)
    out = jnp.swapaxes(out.reshape(b, h, sq, dp), 1, 2)
    return out[..., :d] if d_pad else out
