"""Paged KV-cache attention (block attention) for inference serving.

reference: paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
+ python surface incubate/nn/functional/block_multihead_attention.py —
vLLM-style paged KV cache: the cache is a pool of fixed-size blocks; each
sequence owns a list of block ids (block_tables), so memory is allocated in
block_size granules with no per-sequence max-length reservation.

TPU-native: gathers over the block pool are XLA dynamic-gathers that Mosaic
handles well at decode shapes; the full attention runs as one batched einsum
over the gathered pages (decode q length is 1, so the MXU work is a skinny
matmul — bandwidth-bound, which the gather layout serves).

Cache layout: [num_blocks, block_size, num_kv_heads, head_dim].

Quantized block format (round 11): the pool may store blocks as int8 or
fp8 instead of the native compute dtype. Scales live ALONGSIDE the
blocks in a parallel [num_blocks, block_size, num_kv_heads] array — one
scale per cached (token, head), bfloat16 — so a block and its scales
are gathered by the same table lookup and dequantization fuses into the
attention read (no separate dequant pass, no bf16 copy of the pool ever
materializes in HBM). int8 uses the same symmetric [-qmax, qmax] grid
as nn/quant/format.py; fp8 rounds through the real ml_dtypes storage
types with the same absmax->fmax scaling as fake_fp8_quant, so KV
blocks reproduce exactly what serialized fp8 tensors would.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["paged_attention_decode", "paged_attention_decode_inner",
           "paged_attention_prefill_chunk", "paged_attention_verify",
           "write_to_cache", "write_chunk_to_cache", "KVBlockFormat",
           "kv_write_token", "kv_write_chunk", "kv_write_tokens",
           "kv_rollback_tokens", "BlockKVCacheManager"]


class KVBlockFormat:
    """Storage format of the paged KV pool: how K/V bytes sit in HBM.

    name:
      "native"/"bf16" -> passthrough: blocks hold `native_dtype`, no
                         scales (the pre-round-11 pool, byte-identical).
      "int8"          -> symmetric absmax int8 per (token, head):
                         q = round(x / s), s = absmax/127 — the same
                         [-qmax, qmax] grid nn/quant/format.py emits.
      "fp8_e4m3"/"fp8_e5m2" -> real ml_dtypes float8 storage (framework/
                         dtypes.py registry), absmax scaled onto the fp8
                         grid exactly like fake_fp8_quant: q = x/s*fmax
                         rounded through the fp8 dtype, x' = q/fmax*s.

    Scales are bfloat16, one per (token, head) — 2 bytes next to D
    payload bytes, so int8 halves the pool's bytes/token at head_dim 64+
    (the ">=1.9x lanes" capacity contract is test-pinned). Encode uses
    the ROUNDED stored scale so decode is its exact inverse modulo the
    payload grid.
    """

    NAMES = ("native", "bf16", "int8", "fp8_e4m3", "fp8_e5m2")

    def __init__(self, name="native", native_dtype=jnp.bfloat16):
        if name not in self.NAMES:
            raise ValueError(
                f"unknown kv cache format {name!r}; one of {self.NAMES}")
        self.name = name
        self.native_dtype = native_dtype
        self.scale_dtype = jnp.bfloat16
        self.quantized = name not in ("native", "bf16")
        if name == "int8":
            self.store_dtype = jnp.int8
            self._qmax = 127.0          # symmetric grid (format.py contract)
            self._fmax = None
        elif self.quantized:
            # fp8: grid limits + storage dtype from THE shared registries
            from ..nn.quant.format import fp8_limits
            from ..framework import dtypes as _dtypes
            fmax, dtype_name = fp8_limits(name.split("_", 1)[1])
            self.store_dtype = _dtypes.NAME2DTYPE[dtype_name]
            self._qmax = None
            self._fmax = fmax
        else:
            self.store_dtype = native_dtype
            self._qmax = self._fmax = None

    def encode(self, x):
        """x [..., D] native -> (payload [..., D] store_dtype,
        scale [...] scale_dtype). Passthrough formats return (x, None)."""
        if not self.quantized:
            return x, None
        x32 = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(x32), axis=-1)
        if self._qmax is not None:                       # int8
            scale = (amax / self._qmax).astype(self.scale_dtype)
            safe = jnp.where(scale > 0, scale, 1.0).astype(jnp.float32)
            q = jnp.clip(jnp.round(x32 / safe[..., None]),
                         -self._qmax, self._qmax).astype(self.store_dtype)
        else:                                            # fp8
            scale = amax.astype(self.scale_dtype)
            safe = jnp.where(scale > 0, scale, 1.0).astype(jnp.float32)
            q = jnp.clip(x32 * self._fmax / safe[..., None],
                         -self._fmax, self._fmax).astype(self.store_dtype)
        return q, scale

    def decode(self, q, scale):
        """Inverse of encode, in the native compute dtype."""
        if not self.quantized:
            return q
        q32 = q.astype(jnp.float32)
        s32 = scale.astype(jnp.float32)[..., None]
        if self._qmax is not None:
            return (q32 * s32).astype(self.native_dtype)
        return (q32 / self._fmax * s32).astype(self.native_dtype)

    def bytes_per_token(self, kv_heads, head_dim):
        """HBM bytes one cached token costs in ONE of the k/v arrays
        (payload + its scales); double for k and v."""
        payload = kv_heads * head_dim * jnp.dtype(self.store_dtype).itemsize
        if not self.quantized:
            return payload
        return payload + kv_heads * jnp.dtype(self.scale_dtype).itemsize


def write_to_cache(k_cache, v_cache, k_new, v_new, block_tables, write_pos,
                   active=None, scratch_block=None):
    """Scatter new K/V (one token per sequence) into the paged cache.

    k_new/v_new: [B, KVH, D]; block_tables: [B, max_blocks] int32;
    write_pos: [B] absolute position of the new token per sequence.
    When `active` ([B] bool) is given, inactive rows write to
    `scratch_block` instead of their table entry — the fused K-step
    decode keeps dead lanes scribbling somewhere no live sequence owns
    without data-dependent control flow. Returns (k_cache, v_cache).
    """
    # kv.write scope: marks the pool scatters as stateful for the PIR
    # verifier's effect-order rule (COMPILER.md "Verifier & dataflow
    # analysis") — a pass may drop a dead write, never reorder live ones
    with jax.named_scope("kv.write"):
        block_size = k_cache.shape[1]
        block_idx = write_pos // block_size                   # [B]
        in_block = write_pos % block_size                     # [B]
        block_ids = jnp.take_along_axis(block_tables, block_idx[:, None],
                                        axis=1)[:, 0]         # [B]
        if active is not None:
            block_ids = jnp.where(active, block_ids, scratch_block)
        k_cache = k_cache.at[block_ids, in_block].set(k_new)
        v_cache = v_cache.at[block_ids, in_block].set(v_new)
        return k_cache, v_cache


def write_chunk_to_cache(k_cache, v_cache, k_new, v_new, table_row, start):
    """Scatter a prompt CHUNK's K/V (one sequence, C contiguous tokens)
    into the paged cache.

    k_new/v_new: [C, KVH, D]; table_row: [max_blocks] int32 block table of
    the owning sequence; start: absolute position of the chunk's first
    token. Positions past the row's allocated entries land in whatever
    the row is padded with (the engine pads with its scratch block).
    """
    with jax.named_scope("kv.write"):
        block_size = k_cache.shape[1]
        pos = start + jnp.arange(k_new.shape[0])
        block_ids = jnp.take(table_row, pos // block_size)
        in_block = pos % block_size
        k_cache = k_cache.at[block_ids, in_block].set(k_new)
        v_cache = v_cache.at[block_ids, in_block].set(v_new)
        return k_cache, v_cache


def _token_slots(block_tables, start_pos, count, block_size,
                 active=None, scratch_block=None):
    """(block_ids [B, C], in_block [B, C]) for `count` contiguous tokens
    per lane starting at start_pos[b]. Dead lanes are routed whole to
    `scratch_block`; positions past a lane's table row clamp to the
    row's last entry (the engine pads rows with its scratch block, so
    overshoot lands in scratch — same contract as write_chunk_to_cache)."""
    pos = start_pos[:, None] + jnp.arange(count)[None, :]      # [B, C]
    block_idx = jnp.clip(pos // block_size, 0, block_tables.shape[1] - 1)
    block_ids = jnp.take_along_axis(block_tables, block_idx, axis=1)
    if active is not None:
        block_ids = jnp.where(active[:, None], block_ids, scratch_block)
    return block_ids, pos % block_size


def kv_write_tokens(fmt, k_cache, v_cache, k_scale, v_scale,
                    k_new, v_new, block_tables, start_pos,
                    active=None, scratch_block=None):
    """Write C contiguous tokens PER LANE (the speculative verify write:
    k_new/v_new [B, C, KVH, D] at positions start_pos[b]..start_pos[b]+C-1),
    saving the pre-write contents of every touched slot for rollback.

    Returns (k_cache, v_cache, k_scale, v_scale, saved) where `saved` is
    a tuple of the old payloads (and old scales when `fmt` quantizes)
    shaped like the writes — feed it to kv_rollback_tokens to restore
    rejected draft positions byte-exactly. Scale caches are [NB, BS, KVH]
    (None for passthrough formats, passed through unchanged).
    """
    with jax.named_scope("kv.write"):
        block_size = k_cache.shape[1]
        bids, inb = _token_slots(block_tables, start_pos, k_new.shape[1],
                                 block_size, active, scratch_block)
        saved_k = k_cache[bids, inb]                           # [B, C, KVH, D]
        saved_v = v_cache[bids, inb]
        if fmt is not None and fmt.quantized:
            qk, sk = fmt.encode(k_new)
            qv, sv = fmt.encode(v_new)
            saved = (saved_k, saved_v, k_scale[bids, inb], v_scale[bids, inb])
            k_scale = k_scale.at[bids, inb].set(sk)
            v_scale = v_scale.at[bids, inb].set(sv)
        else:
            qk, qv = k_new, v_new
            saved = (saved_k, saved_v)
        k_cache = k_cache.at[bids, inb].set(qk.astype(k_cache.dtype))
        v_cache = v_cache.at[bids, inb].set(qv.astype(v_cache.dtype))
        return k_cache, v_cache, k_scale, v_scale, saved


def kv_rollback_tokens(fmt, k_cache, v_cache, k_scale, v_scale, saved,
                       block_tables, start_pos, keep,
                       active=None, scratch_block=None):
    """Restore the slots a kv_write_tokens call touched wherever
    keep[b, i] is False (rejected draft positions). Kept slots' restores
    are redirected to `scratch_block` instead of being masked out — the
    scatter stays dense and branch-free, and scratch contents are
    garbage by contract. Returns (k_cache, v_cache, k_scale, v_scale)."""
    # kv.rollback scope: same effect-order contract as kv.write — a
    # rollback must never migrate past the write it undoes
    with jax.named_scope("kv.rollback"):
        block_size = k_cache.shape[1]
        bids, inb = _token_slots(block_tables, start_pos, keep.shape[1],
                                 block_size, active, scratch_block)
        bids = jnp.where(keep, scratch_block, bids)
        if fmt is not None and fmt.quantized:
            saved_k, saved_v, saved_ks, saved_vs = saved
            k_scale = k_scale.at[bids, inb].set(saved_ks)
            v_scale = v_scale.at[bids, inb].set(saved_vs)
        else:
            saved_k, saved_v = saved
        k_cache = k_cache.at[bids, inb].set(saved_k)
        v_cache = v_cache.at[bids, inb].set(saved_v)
        return k_cache, v_cache, k_scale, v_scale


def kv_write_token(fmt, k_cache, v_cache, k_scale, v_scale, k_new, v_new,
                   block_tables, write_pos, active=None, scratch_block=None):
    """Format-aware single-token write (the non-speculative decode step).
    With a passthrough format this IS write_to_cache — same ops, same
    trace — so the bf16 pool keeps its pre-round-11 bytes. Returns
    (k_cache, v_cache, k_scale, v_scale)."""
    if fmt is None or not fmt.quantized:
        k_cache, v_cache = write_to_cache(k_cache, v_cache, k_new, v_new,
                                          block_tables, write_pos,
                                          active, scratch_block)
        return k_cache, v_cache, k_scale, v_scale
    qk, sk = fmt.encode(k_new)
    qv, sv = fmt.encode(v_new)
    k_cache, v_cache = write_to_cache(k_cache, v_cache, qk, qv,
                                      block_tables, write_pos,
                                      active, scratch_block)
    with jax.named_scope("kv.write"):
        bids, inb = _token_slots(block_tables, write_pos, 1,
                                 k_cache.shape[1], active, scratch_block)
        k_scale = k_scale.at[bids[:, 0], inb[:, 0]].set(sk)
        v_scale = v_scale.at[bids[:, 0], inb[:, 0]].set(sv)
    return k_cache, v_cache, k_scale, v_scale


def kv_write_chunk(fmt, k_cache, v_cache, k_scale, v_scale, k_new, v_new,
                   table_row, start):
    """Format-aware write_chunk_to_cache (one sequence, C contiguous
    prompt tokens [C, KVH, D]). Passthrough formats take the original
    code path untouched. Returns (k_cache, v_cache, k_scale, v_scale)."""
    if fmt is None or not fmt.quantized:
        k_cache, v_cache = write_chunk_to_cache(k_cache, v_cache, k_new,
                                                v_new, table_row, start)
        return k_cache, v_cache, k_scale, v_scale
    qk, sk = fmt.encode(k_new)
    qv, sv = fmt.encode(v_new)
    k_cache, v_cache = write_chunk_to_cache(k_cache, v_cache, qk, qv,
                                            table_row, start)
    with jax.named_scope("kv.write"):
        block_size = k_cache.shape[1]
        pos = start + jnp.arange(k_new.shape[0])
        block_ids = jnp.take(table_row, pos // block_size)
        in_block = pos % block_size
        k_scale = k_scale.at[block_ids, in_block].set(sk)
        v_scale = v_scale.at[block_ids, in_block].set(sv)
    return k_cache, v_cache, k_scale, v_scale


def paged_attention_decode_inner(q, k_cache, v_cache, block_tables,
                                 seq_lens, scale=None, fmt=None,
                                 k_scale_cache=None, v_scale_cache=None):
    """Unjitted body of paged_attention_decode — call this from inside an
    already-compiled program (e.g. the serving engine's fused K-step
    decode scan) so XLA sees one flat program instead of a nested pjit
    call per layer per step.

    With a quantized `fmt`, blocks are gathered in their storage dtype
    and dequantized against the per-(token, head) scale caches right at
    the read — XLA fuses the dequant into the gather, so no bf16 copy of
    the pool materializes. fmt=None keeps the original trace."""
    B, H, D = q.shape
    _, block_size, KVH, _ = k_cache.shape
    groups = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    max_blocks = block_tables.shape[1]
    L = max_blocks * block_size
    dequant = fmt is not None and fmt.quantized

    def one(qb, table, n):
        with jax.named_scope("pt.serve.gather"):
            k = k_cache[table]                               # [mb, bs, KVH, D]
            v = v_cache[table]
            if dequant:
                k = fmt.decode(k, k_scale_cache[table])
                v = fmt.decode(v, v_scale_cache[table])
            k = k.reshape(L, KVH, D)
            v = v.reshape(L, KVH, D)
        with jax.named_scope("pt.serve.attend"):
            qg = qb.reshape(KVH, groups, D)
            # scores[kvh, g, l]
            s = jnp.einsum("hgd,lhd->hgl", qg, k) * scale
            mask = jnp.arange(L) < n
            s = jnp.where(mask[None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hgl,lhd->hgd", p, v)
            return o.reshape(H, D)

    return jax.vmap(one)(q, block_tables, seq_lens)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attention_decode(q, k_cache, v_cache, block_tables, seq_lens,
                           scale=None):
    """One decode step over paged caches.

    q: [B, H, D] (single new token per sequence);
    k_cache/v_cache: [num_blocks, block_size, KVH, D];
    block_tables: [B, max_blocks_per_seq]; seq_lens: [B] (incl. new token).
    Supports GQA (H a multiple of KVH). Returns [B, H, D].
    """
    return paged_attention_decode_inner(q, k_cache, v_cache, block_tables,
                                        seq_lens, scale=scale)


def paged_attention_verify(q, k_cache, v_cache, block_tables, base_lens,
                           scale=None, fmt=None, k_scale_cache=None,
                           v_scale_cache=None):
    """Speculative-verify attention: C queries PER LANE (the step token
    plus D draft tokens, already written to the pool) attend causally
    over each lane's cache.

    q: [B, C, H, D]; base_lens: [B] — the lane length BEFORE this step's
    write, so query i sits at absolute position base_lens[b] + i and
    attends to every cached position `p <= base_lens[b] + i`. This is
    write_chunk/prefill-chunk masking batched over lanes; with C == 1 it
    computes exactly what paged_attention_decode_inner computes for
    seq_lens = base_lens + 1. Returns [B, C, H, D].
    """
    B, C, H, D = q.shape
    _, block_size, KVH, _ = k_cache.shape
    groups = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    L = block_tables.shape[1] * block_size
    dequant = fmt is not None and fmt.quantized

    def one(qb, table, n0):
        with jax.named_scope("pt.serve.gather"):
            k = k_cache[table]
            v = v_cache[table]
            if dequant:
                k = fmt.decode(k, k_scale_cache[table])
                v = fmt.decode(v, v_scale_cache[table])
            k = k.reshape(L, KVH, D)
            v = v.reshape(L, KVH, D)
        with jax.named_scope("pt.serve.attend"):
            qg = qb.reshape(C, KVH, groups, D)
            s = jnp.einsum("chgd,lhd->chgl", qg, k,
                           preferred_element_type=jnp.float32) * scale
            pos_q = n0 + jnp.arange(C)
            valid = jnp.arange(L)[None, :] <= pos_q[:, None]   # [C, L]
            s = jnp.where(valid[:, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            o = jnp.einsum("chgl,lhd->chgd", p, v)
            return o.reshape(C, H, D)

    return jax.vmap(one)(q, block_tables, base_lens)


def paged_attention_prefill_chunk(q, k_cache, v_cache, table_row, start,
                                  scale=None, fmt=None, k_scale_cache=None,
                                  v_scale_cache=None):
    """Chunked-prefill attention for ONE sequence: C chunk queries attend
    over every cached position `p <= start + qi` — earlier chunks already
    scattered into the paged pool plus the (just-written) chunk itself,
    causal within the chunk.

    q: [C, H, D] (rotated chunk queries); k_cache/v_cache:
    [num_blocks, block_size, KVH, D] AFTER write_chunk_to_cache for this
    chunk; table_row: [max_blocks] int32; start: absolute position of the
    chunk's first token. Returns [C, H, D].
    """
    C, H, D = q.shape
    _, block_size, KVH, _ = k_cache.shape
    groups = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    L = table_row.shape[0] * block_size
    with jax.named_scope("pt.serve.gather"):
        k = k_cache[table_row]
        v = v_cache[table_row]
        if fmt is not None and fmt.quantized:
            k = fmt.decode(k, k_scale_cache[table_row])
            v = fmt.decode(v, v_scale_cache[table_row])
        k = k.reshape(L, KVH, D)
        v = v.reshape(L, KVH, D)
    with jax.named_scope("pt.serve.attend"):
        qg = q.reshape(C, KVH, groups, D)
        s = jnp.einsum("chgd,lhd->chgl", qg, k,
                       preferred_element_type=jnp.float32) * scale
        pos_q = start + jnp.arange(C)
        valid = jnp.arange(L)[None, :] <= pos_q[:, None]      # [C, L]
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("chgl,lhd->chgd", p, v)
        return o.reshape(C, H, D)


class BlockKVCacheManager:
    """Host-side block allocator — the analog of the reference's block table
    management in block_multihead_attention (paged KV serving loop).

    Round 18: blocks are refcounted so sequences can SHARE a prompt
    prefix (`share`), with copy-on-write (`fork_cow`) before any write
    into a shared block. `free` decrements; a block returns to the free
    list only when its last holder lets go. Sequences that never share
    behave exactly as before."""

    def __init__(self, num_blocks, block_size, num_kv_heads, head_dim,
                 dtype=jnp.bfloat16):
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.k_cache = jnp.zeros((num_blocks, block_size, num_kv_heads,
                                  head_dim), dtype)
        self.v_cache = jnp.zeros_like(self.k_cache)
        self._free = list(range(num_blocks - 1, -1, -1))
        self._tables = {}   # seq_id -> [block ids]
        self._lens = {}     # seq_id -> length
        self._ref = {}      # block id -> refcount (absent == free)

    def allocate(self, seq_id, num_tokens):
        """Ensure capacity for `num_tokens` total tokens."""
        need = (num_tokens + self.block_size - 1) // self.block_size
        table = self._tables.setdefault(seq_id, [])
        while len(table) < need:
            if not self._free:
                raise MemoryError("KV cache pool exhausted")
            b = self._free.pop()
            self._ref[b] = 1
            table.append(b)
        self._lens[seq_id] = num_tokens
        return table

    def free(self, seq_id):
        for b in self._tables.pop(seq_id, []):
            n = self._ref.get(b, 1) - 1
            if n <= 0:
                self._ref.pop(b, None)
                self._free.append(b)
            else:
                self._ref[b] = n
        self._lens.pop(seq_id, None)

    def share(self, src_id, dst_id, num_blocks):
        """Start dst's table with src's first `num_blocks` blocks
        (refcount +1 each): a prompt-prefix hit. dst must be fresh; its
        tail grows through the usual allocate()."""
        if self._tables.get(dst_id):
            raise ValueError(f"share into non-empty sequence {dst_id!r}")
        src = self._tables[src_id][:num_blocks]
        table = self._tables.setdefault(dst_id, [])
        for b in src:
            self._ref[b] = self._ref.get(b, 0) + 1
            table.append(b)
        self._lens[dst_id] = len(table) * self.block_size
        return table

    def fork_cow(self, seq_id, idx):
        """Give seq_id a private copy of its idx-th block before a write
        lands in it (no-op when already private). Byte-exact device
        copy; the old block loses one reference."""
        old = self._tables[seq_id][idx]
        if self._ref.get(old, 1) <= 1:
            return old
        if not self._free:
            raise MemoryError("KV cache pool exhausted (COW fork)")
        new = self._free.pop()
        self._ref[new] = 1
        self.k_cache = self.k_cache.at[new].set(self.k_cache[old])
        self.v_cache = self.v_cache.at[new].set(self.v_cache[old])
        self._tables[seq_id][idx] = new
        n = self._ref.get(old, 1) - 1
        if n <= 0:
            self._ref.pop(old, None)
            self._free.append(old)
        else:
            self._ref[old] = n
        return new

    def prefill(self, seq_id, k, v):
        """Write a whole prompt's K/V ([L, KVH, D]) into fresh blocks."""
        L = k.shape[0]
        table = self.allocate(seq_id, L)
        bs = self.block_size
        pad = (len(table) * bs) - L
        kp = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
        ids = jnp.asarray(table)
        self.k_cache = self.k_cache.at[ids].set(
            kp.reshape(len(table), bs, *k.shape[1:]))
        self.v_cache = self.v_cache.at[ids].set(
            vp.reshape(len(table), bs, *v.shape[1:]))
        return table

    def append(self, seq_id, k_new, v_new):
        """Append one token's K/V ([KVH, D]); returns new length."""
        n = self._lens[seq_id]
        table = self.allocate(seq_id, n + 1)
        pos = jnp.asarray([n])
        tbl = jnp.asarray([table])
        self.k_cache, self.v_cache = write_to_cache(
            self.k_cache, self.v_cache, k_new[None], v_new[None],
            tbl, pos)
        return n + 1

    def batch_tables(self, seq_ids, pad_to=None):
        """Dense [B, max_blocks] table + [B] lengths for a decode batch."""
        import numpy as np
        mb = max(len(self._tables[s]) for s in seq_ids)
        if pad_to:
            mb = max(mb, pad_to)
        tables = np.zeros((len(seq_ids), mb), np.int32)
        lens = np.zeros((len(seq_ids),), np.int32)
        for i, s in enumerate(seq_ids):
            t = self._tables[s]
            tables[i, :len(t)] = t
            lens[i] = self._lens[s]
        return jnp.asarray(tables), jnp.asarray(lens)
