"""Flash attention (forward + backward) as Pallas TPU kernels.

reference capability: paddle/phi/kernels/gpu/flash_attn_kernel.cu and
flash_attn_grad_kernel.cu (FlashAttention-2 via dynload) +
python/paddle/nn/functional/flash_attention.py.

TPU-native design (not a CUDA port). Three kernels, `fa_fwd`, `fa_bwd_dq`
and `fa_bwd_dkv`, share one geometry (`Tiles`, chosen from the shape by
`choose_tiles`): a RESIDENT tile of one side stays in VMEM while the
grid's innermost (sequential) axis streams large tiles of the other side
past it, and the kernel loops over SUB-blocks of the streamed tile, so the
score tile stays bounded while a call makes a few hundred grid steps. At
the sizes `choose_tiles` hands out the streamed tile is the whole padded
sequence whenever that fits VMEM: K and V (forward, dQ) or Q and dO (dKV)
are then read from HBM once per head.

- Forward: grid (batch*heads, q tiles, k tiles). The online-softmax
  running (m, l, acc) state lives in VMEM scratch across the k axis; the
  forward also emits the per-row logsumexp, as a (1, rows) row.
- Backward: the FlashAttention-2 split. delta = rowsum(dO * O) is a cheap
  XLA elementwise reduce. dQ: grid (bh, q tiles, k tiles), accumulates
  dS @ K. dK/dV: grid (kv heads, k tiles, group reps, q tiles), computes
  the TRANSPOSED score tile (keys on sublanes, queries on lanes) so that
  lse and delta enter as rows and dV, dK are plain P^T @ dO, dS^T @ Q
  products; GQA's reduction over the query-head group happens in its
  scratch. P is rematerialized per sub-block from (Q, K, lse) — nothing
  O(S^2) is ever stored, and no per-row scalar is broadcast in HBM.
- MXU does the matmuls on native (bf16) operands with fp32 accumulation;
  the softmax scale is folded into the resident tile once. VPU does the
  softmax pieces in fp32.
- Causal: ONE schedule. The rectangular grid's streamed index map is
  clamped to the last (first, for dKV) tile the resident tile needs, so a
  step above the diagonal repeats the previous block index and issues no
  DMA; inside a tile the sub-block loops' bounds skip what the diagonal
  hides, and the iota mask is built only on sub-blocks the diagonal (or
  the padded key tail) crosses. Cross-length causal uses the
  bottom-right-aligned convention (offset = seq_k - seq_q), matching the
  dense reference.
- Window (with causal): query i sees key j iff 0 <= i + offset - j <
  window. The band enters where the diagonal does: the sub-block loops get
  a lower bound as well, the sub-blocks the band's far edge crosses the
  iota mask, and the streamed grid axis runs over the tiles of each
  resident tile's band, first to last, so no grid step is made for a tile
  the band never reaches. The same three kernel functions under the names
  `faw_fwd`, `faw_bwd_dq`, `faw_bwd_dkv`; a window that hides nothing
  (`effective_window`) is the causal call, program and names.

On non-TPU backends the kernels run under the Pallas interpreter (tests).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
_LANES = 128  # scratch holds per-row scalars broadcast across one lane tile
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b


# --------------------------------------------------------------------------
# tile geometry
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Tiles:
    """Tile geometry of the three kernels at one attention shape. Each
    triple is (resident, streamed, sub) rows: the resident side's tile,
    the DMA'd tile of the side streamed past it, and the sub-block of the
    streamed tile that one iteration of the in-kernel loop handles (sub
    divides streamed). fwd and dq: resident = query rows, streamed = key
    rows. dkv: resident = key rows, streamed = query rows."""

    fwd: tuple
    dq: tuple
    dkv: tuple

    def _sweeps(self, seq_q, seq_k):
        """(kernel's name less its prefix, tile, resident side, resident
        and streamed sequence lengths) of the three kernels."""
        return (("fwd", self.fwd, "q", seq_q, seq_k),
                ("bwd_dq", self.dq, "q", seq_q, seq_k),
                ("bwd_dkv", self.dkv, "k", seq_k, seq_q))

    def grid_steps(self, batch_heads: int, seq_q: int, seq_k: int,
                   window=None) -> dict:
        """Grid steps of one call of each kernel, by the kernel's name
        (`faw_*` under a window that hides something: their streamed axis
        runs over the tiles one resident tile's band can touch)."""
        window = effective_window(window, True, seq_k)
        out = {}
        for name, tile, side, seq_res, seq_str in self._sweeps(seq_q, seq_k):
            if window is None:
                streamed = -(-seq_str // tile[1])
            else:
                streamed = _band_tiles(side, tile, seq_res, seq_str,
                                       seq_k - seq_q, window)
            out[("fa_" if window is None else "faw_") + name] = (
                batch_heads * -(-seq_res // tile[0]) * streamed)
        return out

    def visited_pairs(self, seq_q: int, seq_k: int, window=None) -> dict:
        """Query-key pairs one (batch, head) of each kernel's sweep visits
        under the causal mask, by the kernel's name: (visited sub-blocks)
        x (resident rows x sub rows), from the bounds the kernels' own
        loops take (`_key_bounds`, `_query_bounds`), counted on the host.
        `band_pairs` is what the mask needs."""
        window = effective_window(window, True, seq_k)
        offset = seq_k - seq_q
        out = {}
        for name, tile, side, seq_res, seq_str in self._sweeps(seq_q, seq_k):
            res, streamed, sub = tile
            bounds = _key_bounds if side == "q" else _query_bounds
            blocks = 0
            for i in range(-(-seq_res // res)):
                for j in range(-(-seq_str // streamed)):
                    lo, _, _, hi = bounds(i * res, res, j * streamed, sub,
                                          streamed // sub, True, offset,
                                          seq_str, window)
                    blocks += hi - lo
            out[("fa_" if window is None else "faw_") + name] = (
                blocks * res * sub)
        return out


def effective_window(window, causal, seq_k):
    """`window` as the kernels take it: None where it hides nothing (no
    window, or one that reaches past the first key), so that such a call
    is the causal call, program and names. A window needs `causal`: query
    i (bottom-right aligned) sees key j iff 0 <= i + offset - j < window."""
    if window is None:
        return None
    window = int(window)
    if window < 1:
        raise ValueError(f"window {window}: at least the query's own key")
    if not causal:
        raise ValueError("a window is a band under the causal diagonal: "
                         "causal=True")
    return None if window >= seq_k else window


def band_pairs(seq_q, seq_k, window=None):
    """Query-key pairs the causal mask (bottom-right aligned), cut to
    `window` keys a query where one is given, leaves of one head."""
    import numpy as np
    reach = np.arange(seq_q, dtype=np.int64) + (seq_k - seq_q)
    last = np.minimum(reach, seq_k - 1)
    first = 0 if window is None else np.maximum(reach - window + 1, 0)
    return int(np.maximum(last - first + 1, 0).sum())


# (resident, sub) rows a kernel takes when the sequence is long enough:
# measured on a TPU v5e at bh 64, seq 2048, d 128, bf16, causal (PERF.md
# section 6, PR 27). The forward's time follows the number of sub-blocks
# it visits, not their size — every visit reduces each row across lanes
# twice, for the running max and sum — so it takes the largest; the
# backward kernels reduce nothing and lose beyond 512, to the diagonal's
# waste. FLAGS_use_autotune times _ROW_CANDIDATES instead.
_TILE_ROWS = {"fwd": (1024, 1024), "dq": (512, 512), "dkv": (512, 512)}
_ROW_CANDIDATES = ((256, 256), (512, 256), (512, 512), (1024, 512),
                   (1024, 1024))

# Under a window that hides something the same rows won the sweep (TPU v5e,
# batch x heads 64 over 8 key-value heads, seq 16384, window 1024, d 128,
# bf16: tools/window_attention.py; PERF.md section 6, PR 34): the forward
# 10.1 ms a call at 1024 x 1024, 15.1 at 512 x 512, 25 at 256. The one
# difference is dKV's streamed tile: it streams Q and dO, which change with
# every query head of the group, so whole they would be 8 MiB a grid step
# for a band of 1024 + 512 rows (11.3 ms at 1024 rows, 24.4 whole). For the
# forward and dQ the streamed tile's size moves nothing (K and V of a head
# are fetched once either way), so they keep the whole sequence.
_WINDOW_STREAMED_ROWS = {"dkv": 1024}

# what a kernel's buffers may take when the streamed tile is sized, and the
# most Mosaic is ever asked for (a v5e core has 128 MiB of VMEM; the
# compiler's default scoped limit is 16 MiB)
_VMEM_BUDGET = 40 << 20
_VMEM_LIMIT_MAX = 100 << 20


def _round_up(n, m):
    return -(-n // m) * m


def _fit(seq, rows):
    """The largest of rows, rows/2, ... (never under one lane tile) whose
    padding of `seq` wastes at most an eighth of it."""
    while rows > _LANES and (_round_up(seq, rows) - seq) * 8 > seq:
        rows //= 2
    return rows


def vmem_bytes(kind, tile, head_dim, itemsize):
    """Bytes of VMEM one grid step of kernel `kind` holds at `tile`:
    double-buffered blocks, scratch, and the score-sized temporaries of one
    sub-block. The limit handed to Mosaic is computed from this."""
    res, streamed, sub = tile
    f32 = 4
    row = head_dim * itemsize
    temps = 4 * res * sub * f32             # s, p, dp, ds of one sub-block
    if kind == "fwd":
        blocks = 2 * (2 * res * row         # q, out
                      + 2 * streamed * row  # k, v
                      + 8 * res * f32)      # lse row, one sublane tile
        scratch = res * row + 2 * res * _LANES * f32 + res * head_dim * f32
    elif kind == "dq":
        blocks = 2 * (3 * res * row         # q, dO, dq
                      + 2 * streamed * row  # k, v
                      + 2 * 8 * res * f32)  # lse, delta rows
        scratch = res * row + 2 * res * _LANES * f32 + res * head_dim * f32
    else:
        blocks = 2 * (4 * res * row         # k, v, dk, dv
                      + 2 * streamed * row  # q, dO
                      + 2 * 8 * streamed * f32)   # lse, delta rows
        scratch = res * row + 2 * res * head_dim * f32
    return blocks + scratch + temps


def choose_tiles(seq_q, seq_k, head_dim, itemsize,
                 vmem_budget=_VMEM_BUDGET, rows=None, window=None) -> Tiles:
    """The one place tiles are chosen, from the shape alone. Resident and
    sub tiles are the kernel's `_TILE_ROWS`, halved while padding a short
    or ragged sequence to them would waste more than an eighth of it; the
    streamed tile is the whole padded sequence, halved while the kernel's
    buffers exceed `vmem_budget`. `rows` overrides `_TILE_ROWS` for some
    kernels (the autotuner's candidates).

    Under a `window` that hides something (`effective_window`) a kernel
    named in `_WINDOW_STREAMED_ROWS` streams tiles as near to that many
    rows as its sub tile allows."""
    banded = effective_window(window, True, seq_k) is not None
    rows = {**_TILE_ROWS, **(rows or {})}
    cap = _WINDOW_STREAMED_ROWS if banded else {}

    def one(kind, seq_res, seq_str):
        res = _fit(seq_res, rows[kind][0])
        sub = _fit(seq_str, rows[kind][1])
        streamed = _round_up(seq_str, sub)
        if kind in cap:
            streamed = min(streamed, _round_up(cap[kind], sub))
        while streamed > sub and vmem_bytes(
                kind, (res, streamed, sub), head_dim, itemsize) > vmem_budget:
            streamed = _round_up(streamed // 2, sub)
        return (res, streamed, sub)

    return Tiles(fwd=one("fwd", seq_q, seq_k), dq=one("dq", seq_q, seq_k),
                 dkv=one("dkv", seq_k, seq_q))


# --------------------------------------------------------------------------
# the sweep: which sub-blocks a resident tile visits, and which need a mask
# --------------------------------------------------------------------------

def _fdiv(x, n):
    """floor(max(x, 0) / n): on traced int32 scalars in a kernel or an
    index map, on Python ints where the host counts the same sweep."""
    if isinstance(x, int):
        return max(x, 0) // n
    return jax.lax.div(jnp.maximum(x, 0), jnp.int32(n))


def _cdiv(x, n):
    """ceil(max(x, 0) / n), as `_fdiv`."""
    return _fdiv(x + n - 1, n)


def _min(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


def _max(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return max(a, b)
    return jnp.maximum(a, b)


def _key_bounds(row0, block_q, col0, block_k, n_sub, causal, offset, seq_k,
                window=None):
    """Query rows [row0, row0 + block_q) against key sub-blocks s = 0..n_sub
    of block_k columns from col0: -> (lo, lo_full, n_full, hi). Sub-blocks
    [lo_full, n_full) are attended whole; [lo, lo_full) need the iota mask
    (the band's lower edge crosses them), as do [n_full, hi) (the diagonal
    or the padded key tail); below lo every key is behind the window of
    every row, from hi on they hold nothing. Without a window lo = lo_full
    = 0."""
    n_full = hi = n_sub
    lo = lo_full = 0
    if causal:
        hi = _min(hi, _fdiv(row0 + block_q - 1 + offset - col0
                            + block_k, block_k))
        n_full = _min(n_full, _fdiv(row0 + offset - col0 + 1, block_k))
    if seq_k is not None:
        hi = _min(hi, _fdiv(seq_k - col0 + block_k - 1, block_k))
        n_full = _min(n_full, _fdiv(seq_k - col0, block_k))
    if window is not None:
        # row r's first key is r + offset - window + 1
        first = row0 + offset - window + 1 - col0
        lo = _min(hi, _fdiv(first, block_k))
        lo_full = _min(hi, _cdiv(first + block_q - 1, block_k))
        n_full = _max(n_full, lo_full)
    return lo, lo_full, n_full, hi


def _query_bounds(col0, block_k, row0, block_q, n_sub, causal, offset,
                  seq_q, window=None):
    """Key rows [col0, col0 + block_k) against query sub-blocks s = 0..n_sub
    of block_q rows from row0: -> (lo, first_full, last_full, hi). [lo,
    first_full) cross the diagonal, [first_full, last_full) are attended
    whole, [last_full, hi) cross the band's far edge; below lo the keys are
    in the future of every query, from hi on the rows are pad or past the
    window of every key. Without a window last_full = hi."""
    lo = first_full = 0
    hi = n_sub
    if seq_q is not None:
        hi = _min(hi, _fdiv(seq_q - row0 + block_q - 1, block_q))
    if window is not None:
        # key c's last query is c - offset + window - 1
        hi = _min(hi, _cdiv(col0 + block_k - offset + window - 1 - row0,
                            block_q))
    if causal:
        lo = _min(hi, _fdiv(col0 - offset - row0, block_q))
        first_full = _min(hi, _fdiv(
            col0 + block_k - 1 - offset - row0 + block_q - 1, block_q))
    last_full = hi
    if window is not None:
        last_full = _min(hi, _max(first_full, _fdiv(
            col0 - offset + window - row0, block_q)))
    return lo, first_full, last_full, hi


def _keep(shape, q_axis, row0, col0, causal, offset, seq_k, window=None):
    """Attended pairs of one score tile whose queries run along `q_axis`
    from row0 and whose keys run along the other axis from col0."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    keep = None
    if causal:
        keep = rows + offset >= cols
    if window is not None:
        keep = keep & (rows + offset - cols < window)
    if seq_k is not None:
        tail = cols < seq_k
        keep = tail if keep is None else keep & tail
    return keep


# The streamed tiles a resident tile needs, first and last. The index maps
# clamp to them, so that a grid step outside repeats a block index and
# issues no DMA. Under a window the grid's streamed axis is RELATIVE to the
# first (step j loads tile first + j, at most the last) and only as long as
# the widest resident tile's band (`_band_tiles`): the steps of a
# rectangular grid that the band never reaches are not made at all.

def _last_key_tile(i, block_q, block_km, n_km, offset):
    """The last streamed key tile query tile i attends under the causal
    mask: the index map clamps to it, so the steps beyond repeat its block
    index and fetch nothing."""
    return _min(_fdiv(i * block_q + block_q - 1 + offset, block_km),
                n_km - 1)


def _first_key_tile(i, block_q, block_km, n_km, offset, window):
    """The first streamed key tile query tile i's window reaches."""
    return _min(_fdiv(i * block_q + offset - window + 1, block_km), n_km - 1)


def _first_q_tile(j, block_k, block_qm, n_qm, offset):
    """The first streamed query tile key tile j is visible to."""
    return _min(_fdiv(j * block_k - offset, block_qm), n_qm - 1)


def _last_q_tile(j, block_k, block_qm, n_qm, offset, window):
    """The last streamed query tile whose window reaches key tile j."""
    return _min(_fdiv(j * block_k + block_k - 1 - offset + window - 1,
                      block_qm), n_qm - 1)


def _band_tiles(side, tile, seq_res, seq_str, offset, window):
    """Steps of a windowed call's streamed grid axis: the most streamed
    tiles any resident tile's band touches. side 'q': resident query
    tiles (fwd, dQ); 'k': resident key tiles (dKV)."""
    res, streamed, _ = tile
    n_res, n_str = -(-seq_res // res), -(-seq_str // streamed)
    if side == "q":
        spans = (_last_key_tile(i, res, streamed, n_str, offset)
                 - _first_key_tile(i, res, streamed, n_str, offset, window)
                 for i in range(n_res))
    else:
        spans = (_last_q_tile(j, res, streamed, n_str, offset, window)
                 - _first_q_tile(j, res, streamed, n_str, offset)
                 for j in range(n_res))
    return max(1, max(spans) + 1)


def _loop(start, stop, body):
    """body(s) for s in [start, stop); the bounds may be traced."""
    def step(s, carry):
        body(s)
        return carry
    jax.lax.fori_loop(start, stop, step, 0)


def _scaled(ref, scale):
    """The resident tile with the softmax scale folded in, once, in the
    operand dtype (the MXU takes it as it is). Where the scale is no power
    of two (d 128: 2**-3.5) a bf16 tile takes one more rounding than
    scaling the f32 scores would give it: 2**-9 relative at most on each
    element of q (of k in dKV), the size of the rounding q and k already
    carry."""
    x = ref[0]
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _column(row_ref, rows):
    """A (1, rows) block of per-row scalars as a lane-broadcast
    (rows, _LANES) column tile."""
    return jnp.transpose(jnp.broadcast_to(row_ref[0], (_LANES, rows)))


def _streamed_origin(qi, kj, tile, offset, window, n_streamed):
    """First key column of the streamed tile grid step (qi, kj) of the
    forward and dQ kernels holds: tile kj, or under a window tile kj of
    query tile qi's band (a step past the band's last tile repeats that
    tile's block index; its columns, reckoned here unclamped, lie above
    the diagonal or past the keys and the sweep is empty)."""
    block_q, block_km, _ = tile
    if window is None:
        return kj * block_km
    return (_first_key_tile(qi, block_q, block_km, n_streamed, offset,
                            window) + kj) * block_km


def _sweep_keys(visit, row0, col0, tile, causal, offset, seq_k, window):
    """visit(s, masked) over the key sub-blocks of one streamed tile that
    the resident query tile attends (`_key_bounds`)."""
    block_q, block_km, block_k = tile
    lo, lo_full, n_full, hi = _key_bounds(
        row0, block_q, col0, block_k, block_km // block_k, causal, offset,
        seq_k, window)
    if window is not None:
        _loop(lo, lo_full, functools.partial(visit, masked=True))
    _loop(lo_full, n_full, functools.partial(visit, masked=False))
    if causal or seq_k is not None:
        _loop(n_full, hi, functools.partial(visit, masked=True))


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def _fa_fwd_kernel(q_ref, k_ref, v_ref, *refs, causal: bool, scale: float,
                   seq_k, tile: tuple, offset: int, epilogue: bool = False,
                   rms_eps: float = 1e-6, rms_d: int = 0, window=None,
                   n_streamed: int = 0):
    # optional fused epilogue (FlashFuser-style widened fusion): two extra
    # inputs — residual block + lane-broadcast RMSNorm gamma — and the
    # flush writes rmsnorm(attn + residual) * gamma instead of attn,
    # saving one full HBM round-trip of the attention output. The norm
    # axis is the head dim (rms_d = TRUE d, so zero-pad columns don't
    # skew the mean).
    if epilogue:
        res_ref, w_ref, o_ref, lse_ref, qs_s, m_s, l_s, acc_s = refs
    else:
        o_ref, lse_ref, qs_s, m_s, l_s, acc_s = refs
    block_q, block_km, block_k = tile
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    row0 = qi * block_q
    col0 = _streamed_origin(qi, kj, tile, offset, window, n_streamed)

    @pl.when(kj == 0)
    def _init():
        qs_s[...] = _scaled(q_ref, scale)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def visit(s, masked):
        # dots run on NATIVE (bf16) operands with f32 accumulation — the
        # MXU's full-rate mode and exactly the dense XLA path's precision
        # (einsum + preferred_element_type=f32)
        c = pl.multiple_of(s * block_k, block_k)
        k = k_ref[0, pl.ds(c, block_k), :]
        v = v_ref[0, pl.ds(c, block_k), :]
        sc = jax.lax.dot_general(qs_s[...], k, _NT,
                                 preferred_element_type=jnp.float32)
        if masked:
            sc = jnp.where(_keep(sc.shape, 0, row0, col0 + c, causal, offset,
                                 seq_k, window), sc, NEG_INF)
        m_prev = m_s[...][:, :1]
        l_prev = l_s[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    _sweep_keys(visit, row0, col0, tile, causal, offset, seq_k, window)

    @pl.when(kj == pl.num_programs(2) - 1)
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
        # m_s/l_s hold the row's value in every lane: one transpose turns
        # the column into the (1, block_q) row the backward reads
        lse = m_s[...] + jnp.log(jnp.maximum(l_s[...], 1e-30))
        lse_ref[0] = jnp.transpose(lse)[:1, :]


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  qs_s, lse_s, delta_s, dq_s, *, causal: bool, scale: float,
                  seq_k, tile: tuple, offset: int, window=None,
                  n_streamed: int = 0):
    block_q, block_km, block_k = tile
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    row0 = qi * block_q
    col0 = _streamed_origin(qi, kj, tile, offset, window, n_streamed)

    @pl.when(kj == 0)
    def _init():
        qs_s[...] = _scaled(q_ref, scale)
        # the row scalars change only with the resident q tile: turned
        # into columns once here, not per sub-block
        lse_s[...] = _column(lse_ref, block_q)
        delta_s[...] = _column(delta_ref, block_q)
        dq_s[...] = jnp.zeros_like(dq_s)

    def visit(s, masked):
        # bf16 operands + f32 accumulation on every dot (see fwd kernel)
        c = pl.multiple_of(s * block_k, block_k)
        k = k_ref[0, pl.ds(c, block_k), :]
        v = v_ref[0, pl.ds(c, block_k), :]
        do = do_ref[0]
        sc = jax.lax.dot_general(qs_s[...], k, _NT,
                                 preferred_element_type=jnp.float32)
        if masked:
            sc = jnp.where(_keep(sc.shape, 0, row0, col0 + c, causal, offset,
                                 seq_k, window), sc, NEG_INF)
        p = jnp.exp(sc - lse_s[...][:, :1])       # (block_q, block_k)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_s[...][:, :1])).astype(k.dtype)
        dq_s[...] += jax.lax.dot_general(ds, k, _NN,
                                         preferred_element_type=jnp.float32)

    _sweep_keys(visit, row0, col0, tile, causal, offset, seq_k, window)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _flush():
        dq_ref[0] = (dq_s[...] * scale).astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, ks_s, dk_s, dv_s, *, causal: bool,
                   scale: float, seq_q, tile: tuple, offset: int,
                   n_rep: int = 1, window=None, n_streamed: int = 0):
    # grid (bh_kv, k tiles, q-head group reps, q tiles): the scratch
    # accumulates over BOTH the group axis and the q tiles, flushing once
    # per kv tile — this is how GQA's dK/dV reduction happens in-kernel.
    # The score tile is TRANSPOSED (keys on sublanes, queries on lanes):
    # lse and delta broadcast down sublanes as the (1, block_q) rows they
    # arrive as, and dV, dK are plain (block_k, block_q) @ (block_q, d).
    # Padded key rows need no mask here: a key row's dK and dV depend on
    # that row alone, and the pad rows are sliced off.
    block_k, block_qm, block_q = tile
    kj = pl.program_id(1)
    rr = pl.program_id(2)
    qi = pl.program_id(3)
    col0 = kj * block_k
    if window is None:
        row0 = qi * block_qm
    else:
        row0 = (_first_q_tile(kj, block_k, block_qm, n_streamed, offset)
                + qi) * block_qm

    @pl.when((qi == 0) & (rr == 0))
    def _init():
        ks_s[...] = _scaled(k_ref, scale)
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def visit(s, masked):
        # bf16 operands + f32 accumulation on every dot (see fwd kernel)
        r = pl.multiple_of(s * block_q, block_q)
        q = q_ref[0, pl.ds(r, block_q), :]
        do = do_ref[0, pl.ds(r, block_q), :]
        st = jax.lax.dot_general(ks_s[...], q, _NT,
                                 preferred_element_type=jnp.float32)
        if masked:
            st = jnp.where(_keep(st.shape, 1, row0 + r, col0, causal, offset,
                                 None, window), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0, s])          # (block_k, block_q)
        dv_s[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[0, s])).astype(q.dtype)
        dk_s[...] += jax.lax.dot_general(dst, q, _NN,
                                         preferred_element_type=jnp.float32)

    lo, first_full, last_full, hi = _query_bounds(
        col0, block_k, row0, block_q, block_qm // block_q, causal, offset,
        seq_q, window)
    if causal:
        _loop(lo, first_full, functools.partial(visit, masked=True))
    _loop(first_full, last_full, functools.partial(visit, masked=False))
    if window is not None:
        _loop(last_full, hi, functools.partial(visit, masked=True))

    @pl.when((qi == pl.num_programs(3) - 1) & (rr == n_rep - 1))
    def _flush():
        dk_ref[0] = (dk_s[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# calls
# --------------------------------------------------------------------------

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


def _params(kind, tile, head_dim, itemsize, semantics):
    """Mosaic's parameters of one call: the grid axes' semantics, and a
    VMEM limit of twice the buffers' arithmetic (the compiler's own
    temporaries are not in it), never under 32 MiB."""
    est = vmem_bytes(kind, tile, head_dim, itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=min(max(2 * est, 32 << 20), _VMEM_LIMIT_MAX))


def _key_tile_map(tile, n_km, offset, causal, window, q_per_kv):
    """Index map of the forward's and dQ's K and V blocks over grid (b, i,
    j): kv head b // q_per_kv (GQA), streamed tile j clamped to the last
    tile query tile i attends; under a window, tile j of i's band."""
    block_q, block_km, _ = tile

    def kmap(b, i, j):
        if window is not None:
            j = jnp.minimum(
                _first_key_tile(i, block_q, block_km, n_km, offset, window)
                + j, _last_key_tile(i, block_q, block_km, n_km, offset))
        elif causal:
            j = jnp.minimum(j, _last_key_tile(i, block_q, block_km, n_km,
                                              offset))
        return (b // q_per_kv, j, 0)

    return kmap


def _flash_fwd_bhsd(q, k, v, causal, scale, tiles=None, interpret=None,
                    q_per_kv=1, residual=None, rms_weight=None,
                    rms_eps=1e-6, rms_d=None, window=None):
    """q: (BH, Sq, D), k/v: (BH // q_per_kv, Sk, D) -> (out, lse), lse
    (BH, Sq) float32. tiles: a `Tiles` (None = `choose_tiles` of the shape).

    residual/rms_weight (both given or neither): fuse the
    rmsnorm(attn + residual) * weight epilogue into the kernel's flush —
    the attention output never round-trips HBM unnormalized. residual:
    (BH, Sq, D); rms_weight: (D,). rms_d = the TRUE head dim when D is
    zero-padded (the mean divisor). Forward-only (no VJP).

    Ragged sequence lengths are padded to tile multiples; padded K columns
    are masked in-kernel, padded Q rows sliced off on return (so results
    are exact for any length).

    GQA (q_per_kv > 1): kv stays UNEXPANDED — the k/v BlockSpec index map
    folds the head grouping (q index b -> kv index b // q_per_kv), so no
    (B, S, H, D) broadcast of KV ever materializes in HBM. With batch-major
    bh layout (bi*h + hq), b // q_per_kv == bi*kvh + hq // rep exactly."""
    window = effective_window(window, causal, k.shape[1])
    if tiles is None:
        tiles = choose_tiles(q.shape[1], k.shape[1], q.shape[2],
                             q.dtype.itemsize, window=window)
    if interpret is None:
        interpret = _interpret_default()
    return _fwd_call(q, k, v, residual, rms_weight, causal=causal,
                     scale=scale, tiles=tiles, interpret=interpret,
                     q_per_kv=q_per_kv, rms_eps=rms_eps, rms_d=rms_d,
                     window=window)


# the calls are jitted so that a model's layers, which call with one
# signature, trace and lower each kernel once instead of once a layer (a
# third of a second of set-up a layer at the trainer's shape); XLA inlines
# them like any call. Every default is resolved before, so what the jit
# caches on is what the kernel is built from.
@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "tiles", "interpret", "q_per_kv", "rms_eps", "rms_d",
    "window"))
def _fwd_call(q, k, v, residual, rms_weight, *, causal, scale, tiles,
              interpret, q_per_kv, rms_eps, rms_d, window=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    tile = block_q, block_km, block_k = tiles.fwd
    q_p = _pad_to(q, 1, block_q)
    k_p = _pad_to(k, 1, block_km)
    v_p = _pad_to(v, 1, block_km)
    sq_p, sk_p = q_p.shape[1], k_p.shape[1]
    g = q_per_kv
    nq, nk = sq_p // block_q, sk_p // block_km
    offset = sk - sq
    epilogue = residual is not None
    banded = window is not None
    kernel = functools.partial(
        _fa_fwd_kernel, causal=causal, scale=scale,
        seq_k=sk if sk_p != sk or banded else None, tile=tile, offset=offset,
        epilogue=epilogue, rms_eps=rms_eps, rms_d=(rms_d or d),
        window=window, n_streamed=nk)
    kmap = _key_tile_map(tile, nk, offset, causal, window, g)

    def qmap(b, i, j):
        return (b, i, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), qmap),
        pl.BlockSpec((1, block_km, d), kmap),
        pl.BlockSpec((1, block_km, d), kmap),
    ]
    operands = [q_p, k_p, v_p]
    if epilogue:
        # residual rides the q index map; gamma is one (8, d) sublane-
        # tiled block (a bare (1, d) block is unlowerable on TPU), f32 so
        # bf16 gammas don't hit the (16, 128) bf16 tile minimum
        in_specs.append(pl.BlockSpec((1, block_q, d), qmap))
        in_specs.append(pl.BlockSpec((8, d), lambda b, i, j: (0, 0)))
        operands.append(_pad_to(residual, 1, block_q))
        operands.append(jnp.broadcast_to(
            rms_weight.astype(jnp.float32)[None, :], (8, d)))
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, _band_tiles("q", tile, sq, sk, offset, window)
              if banded else nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), qmap),
            # (bh, 1, sq_p) in (1, 1, block_q) blocks: a row per q tile,
            # the second-minor block dim being the array's own
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            _sds((bh, sq_p, d), q.dtype, q),
            _sds((bh, 1, sq_p), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), q.dtype),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_params("fwd", tile, d, q.dtype.itemsize,
                                ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="faw_fwd" if banded else "fa_fwd",
    )(*operands)
    return out[:, :sq], lse[:, 0, :sq]


def _flash_bwd_bhsd(q, k, v, o, lse, g, causal, scale, tiles=None,
                    interpret=None, q_per_kv=1, window=None):
    """FlashAttention-2 backward: returns (dq, dk, dv), all in input dtype.
    lse: (BH, Sq) from the forward. GQA: k/v carry BH // q_per_kv heads;
    dk/dv come back already reduced over the query-head group (the rep
    axis rides the grid, accumulating into the same VMEM scratch — no
    XLA-side segment-sum needed)."""
    window = effective_window(window, causal, k.shape[1])
    if tiles is None:
        tiles = choose_tiles(q.shape[1], k.shape[1], q.shape[2],
                             q.dtype.itemsize, window=window)
    if interpret is None:
        interpret = _interpret_default()
    return _bwd_call(q, k, v, o, lse, g, causal=causal, scale=scale,
                     tiles=tiles, interpret=interpret, q_per_kv=q_per_kv,
                     window=window)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "tiles", "interpret", "q_per_kv", "window"))
def _bwd_call(q, k, v, o, lse, g, *, causal, scale, tiles, interpret,
              q_per_kv, window=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    itemsize = q.dtype.itemsize
    offset = sk - sq
    grp = q_per_kv
    bh_kv = bh // grp
    banded = window is not None

    # delta = rowsum(dO * O): cheap XLA elementwise+reduce, fp32
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    # ---- dQ: resident q tile, K/V streamed -------------------------------
    tile = block_q, block_km, block_k = tiles.dq
    q_p = _pad_to(q, 1, block_q)
    do_p = _pad_to(g, 1, block_q)
    k_p = _pad_to(k, 1, block_km)
    v_p = _pad_to(v, 1, block_km)
    sq_p, sk_p = q_p.shape[1], k_p.shape[1]
    nq, nk = sq_p // block_q, sk_p // block_km
    # per-row scalars travel as (bh, 1, sq_p) rows; pad rows are finite
    # and their contributions vanish because dO's pad rows are zero
    rows = [_pad_to(x, 1, block_q)[:, None, :] for x in (lse, delta)]

    dq_kmap = _key_tile_map(tile, nk, offset, causal, window, grp)

    def dq_qmap(b, i, j):
        return (b, i, 0)

    def dq_rmap(b, i, j):
        return (b, 0, i)

    dq = pl.pallas_call(
        functools.partial(_fa_dq_kernel, causal=causal, scale=scale,
                          seq_k=sk if sk_p != sk or banded else None,
                          tile=tile, offset=offset, window=window,
                          n_streamed=nk),
        grid=(bh, nq, _band_tiles("q", tile, sq, sk, offset, window)
              if banded else nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), dq_qmap),
            pl.BlockSpec((1, block_km, d), dq_kmap),
            pl.BlockSpec((1, block_km, d), dq_kmap),
            pl.BlockSpec((1, block_q, d), dq_qmap),
            pl.BlockSpec((1, 1, block_q), dq_rmap),
            pl.BlockSpec((1, 1, block_q), dq_rmap),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), dq_qmap),
        out_shape=_sds((bh, sq_p, d), q.dtype, q),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), q.dtype),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_params("dq", tile, d, itemsize,
                                ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="faw_bwd_dq" if banded else "fa_bwd_dq",
    )(q_p, k_p, v_p, do_p, *rows)

    # ---- dK/dV: resident k tile, Q/dO streamed ---------------------------
    # grid (kv heads, k tiles, group reps, q tiles) — q tiles innermost,
    # then reps, so for a fixed kv tile the scratch accumulates over the
    # whole query-head group before flushing (n_rep=grp in the kernel)
    tile = block_k, block_qm, block_q = tiles.dkv
    k_p = _pad_to(k, 1, block_k)
    v_p = _pad_to(v, 1, block_k)
    q_p = _pad_to(q, 1, block_qm)
    do_p = _pad_to(g, 1, block_qm)
    sq_p, sk_p = q_p.shape[1], k_p.shape[1]
    nqm, nk = sq_p // block_qm, sk_p // block_k
    # a (1, block_q) row per q sub-block, picked by its leading index
    rows = [_pad_to(x, 1, block_qm).reshape(bh, sq_p // block_q, 1, block_q)
            for x in (lse, delta)]

    def q_tile(j, i):
        # step i's streamed query tile: never before the first one key
        # tile j is visible to; under a window, step i of j's band
        if banded:
            return jnp.minimum(
                _first_q_tile(j, block_k, block_qm, nqm, offset) + i,
                _last_q_tile(j, block_k, block_qm, nqm, offset, window))
        if causal:
            i = jnp.maximum(i, _first_q_tile(j, block_k, block_qm, nqm,
                                             offset))
        return i

    def dkv_qmap(b, j, r, i):
        return (b * grp + r, q_tile(j, i), 0)

    def dkv_rmap(b, j, r, i):
        return (b * grp + r, q_tile(j, i), 0, 0)

    def dkv_kmap(b, j, r, i):
        return (b, j, 0)

    n_sub = block_qm // block_q
    dk, dv = pl.pallas_call(
        functools.partial(_fa_dkv_kernel, causal=causal, scale=scale,
                          seq_q=sq if sq_p != sq or banded else None,
                          tile=tile, offset=offset, n_rep=grp, window=window,
                          n_streamed=nqm),
        grid=(bh_kv, nk, grp, _band_tiles("k", tile, sk, sq, offset, window)
              if banded else nqm),
        in_specs=[
            pl.BlockSpec((1, block_qm, d), dkv_qmap),
            pl.BlockSpec((1, block_k, d), dkv_kmap),
            pl.BlockSpec((1, block_k, d), dkv_kmap),
            pl.BlockSpec((1, block_qm, d), dkv_qmap),
            pl.BlockSpec((1, n_sub, 1, block_q), dkv_rmap),
            pl.BlockSpec((1, n_sub, 1, block_q), dkv_rmap),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), dkv_kmap),
            pl.BlockSpec((1, block_k, d), dkv_kmap),
        ],
        out_shape=[
            _sds((bh_kv, sk_p, d), k.dtype, k),
            _sds((bh_kv, sk_p, d), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), k.dtype),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_params(
            "dkv", tile, d, itemsize,
            ("parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="faw_bwd_dkv" if banded else "fa_bwd_dkv",
    )(q_p, k_p, v_p, do_p, *rows)

    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


def band_mask(seq_q, seq_k, window=None):
    """(seq_q, seq_k) bool: the causal mask, bottom-right aligned, cut to
    `window` keys a query where one is given. What the dense paths apply."""
    back = (jnp.arange(seq_q)[:, None] + (seq_k - seq_q)
            - jnp.arange(seq_k)[None, :])
    mask = back >= 0
    return mask if window is None else mask & (back < window)


def _xla_attention_bhsd(q, k, v, causal, scale, window=None):
    """Dense reference (O(S^2) memory). Used by tests and tiny shapes."""
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s = jnp.where(band_mask(q.shape[1], k.shape[1], window), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _tiles_for(kind, bh, sq, sk, d, dtype, causal, interpret, window=None):
    """`choose_tiles` of the shape; with FLAGS_use_autotune on, the
    (resident, sub) rows of kernel(s) `kind` ('fwd' | 'bwd') are the timed
    winner of `_ROW_CANDIDATES` instead of `_TILE_ROWS`. Timing runs on
    synthetic zeros, so this works even while the caller is being traced."""
    from .autotune import autotune, autotune_enabled
    itemsize = jnp.dtype(dtype).itemsize
    if not autotune_enabled():
        return choose_tiles(sq, sk, d, itemsize, window=window)
    dev = jax.devices()[0]
    # tb (the clamped tuning batch*heads) is part of the key: tile ranking
    # depends on grid parallelism, so a winner timed at 2 heads must not be
    # served to a 64-head caller
    tb = min(bh, 64)
    key = (kind, tb, sq, sk, d, str(dtype), bool(causal), dev.device_kind,
           window)
    kernels = ("fwd",) if kind == "fwd" else ("dq", "dkv")

    def tiles_of(rows):
        return choose_tiles(sq, sk, d, itemsize, window=window,
                            rows={name: rows for name in kernels})

    def make_runner(rows):
        if rows[0] > _round_up(sq, _LANES) or rows[1] > _round_up(sk, _LANES):
            raise ValueError("tile larger than the sequence")
        tiles = tiles_of(rows)
        q = jnp.zeros((tb, sq, d), dtype)
        k = jnp.zeros((tb, sk, d), dtype)
        v = jnp.zeros((tb, sk, d), dtype)
        # each candidate runs 8 iterations inside ONE compiled scan so
        # per-dispatch launch overhead does not rank the candidates. The
        # carry feeds q so the body can't be hoisted.
        if kind == "fwd":
            def step(qq):
                o, _ = _flash_fwd_bhsd(qq, k, v, causal, 1.0, tiles=tiles,
                                       interpret=interpret, window=window)
                return jnp.sum(o.astype(jnp.float32))
        else:
            # o / lse only need the forward's shapes: timing is on zeros
            lse = jnp.zeros((tb, sq), jnp.float32)

            def step(qq):
                outs = _flash_bwd_bhsd(qq, k, v, q, lse, q, causal, 1.0,
                                       tiles=tiles, interpret=interpret,
                                       window=window)
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

    return tiles_of(autotune(key, _ROW_CANDIDATES, make_runner,
                             default=_TILE_ROWS[kernels[0]]))


def _tiles(kind, q, k, causal, window=None):
    bh, sq, d = q.shape
    return _tiles_for(kind, bh, sq, k.shape[1], d, q.dtype, causal,
                      _interpret_default(),
                      effective_window(window, causal, k.shape[1]))


def tiles_for_shape(batch_heads, seq_q, seq_k, head_dim, dtype,
                    causal, window=None) -> Tiles:
    """The tiles the entry points of this module hand the three kernels
    for attention of this shape, resolved the way they resolve them
    (`_tiles_for`: the head dim padded to the lane width, and
    FLAGS_use_autotune's timed winners where it is on). The router's
    Decision records these."""
    d = _round_up(head_dim, _LANES)
    window = effective_window(window, causal, seq_k)
    fwd, bwd = (_tiles_for(kind, batch_heads, seq_q, seq_k, d,
                           jnp.dtype(dtype), causal, _interpret_default(),
                           window)
                for kind in ("fwd", "bwd"))
    return Tiles(fwd=fwd.fwd, dq=bwd.dq, dkv=bwd.dkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_bhsd(q, k, v, causal, scale, q_per_kv=1, window=None):
    return _fa_fwd(q, k, v, causal, scale, q_per_kv, window)[0]


def _fa_fwd(q, k, v, causal, scale, q_per_kv=1, window=None):
    out, lse = _flash_fwd_bhsd(q, k, v, causal, scale,
                               tiles=_tiles("fwd", q, k, causal, window),
                               q_per_kv=q_per_kv, window=window)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, q_per_kv, window, res, g):
    q, k, v, o, lse = res
    return _flash_bwd_bhsd(q, k, v, o, lse, g, causal, scale,
                           tiles=_tiles("bwd", q, k, causal, window),
                           q_per_kv=q_per_kv, window=window)


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


def flash_attention_bshd(q, k, v, causal=False, scale=None, window=None):
    """Paddle flash_attention layout: (batch, seq, heads, head_dim).

    window (with causal): query i, bottom-right aligned, sees key j iff
    0 <= i + offset - j < window: `window` keys with its own (the Hugging
    Face sliding-window mask). The kernels (`faw_*`) visit the band's
    sub-blocks and no others. A window that hides nothing at this shape is
    the causal call.

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
    local = functools.partial(
        _flash_attention_local, causal=causal, scale=scale,
        window=effective_window(window, causal, k.shape[1]))
    spec = _mesh_spec(b, h, kvh)
    if spec is None:
        return local(q, k, v)
    return jax.shard_map(local, in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)


def _flash_attention_local(q, k, v, causal, scale, window=None):
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
    out = _flash_attention_bhsd(qt, kt, vt, causal, scale, h // kvh, window)
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
    out, _ = _flash_fwd_bhsd(qt, kt, vt, causal, scale,
                             tiles=_tiles("fwd", qt, kt, causal),
                             q_per_kv=h // kvh, residual=rt,
                             rms_weight=rms_weight, rms_eps=eps, rms_d=d)
    out = jnp.swapaxes(out.reshape(b, h, sq, dp), 1, 2)
    return out[..., :d] if d_pad else out
