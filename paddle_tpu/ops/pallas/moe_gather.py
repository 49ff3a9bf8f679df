"""The routed experts' combine as one pass over the slot table: for every
token, the sum of the rows of y its slots name, reading only those rows.

    out[t] = sum over j of y[slot[t, j]], slots past y's rows left out,
             summed in float32 in column order, rounded once to y's type

which is `parallel/moe.py _gather_rows` bit for bit for finite rows: a
slot it leaves out adds +0.0 there, and a float32 sum that starts at +0.0
never becomes -0.0, so leaving the term out changes nothing.

A DMA moves whole sublane tiles: a row of y cannot be fetched alone, only
the eight rows of the tile it lies in (Mosaic refuses a slice of one row),
and a vector load of one row at a dynamic offset costs a rotation of every
register it touches. So a grid step takes a tile of tokens and fetches the
distinct 8-row blocks their live slots name, each once (`sorted_assignments`
puts a held expert's rows in token order, so a tile's rows of one expert
are one run and its blocks serve several tokens), and the MXU picks the
rows out of them:

    G[(j, t)] = onehot(slot[t, j] == row id of each fetched row) @ rows

Each (j, t) names at most one row, so every entry of G is one product with
1.0 and zeros: the row itself, exactly, in float32. The columns are then
added in order. Rows that no slot of the tile names are zeroed before the
product, a 32-bit word (two bf16 rows) at a time (they may hold anything,
NaN too, and 0 x NaN is NaN).

Before the call `jax.numpy` puts each token's live slots first (a stable
sort of the table's rows: the order of the sum is kept), lists each tile's
distinct blocks, ascending (two sorts), and the row id of each place of
the buffer. Where no token of a tile has more than half the columns live,
the product takes the first half alone. The blocks of the next tile are
fetched while this one is summed. The buffer holds `_CAPACITY` blocks: a
tile that names more (never at the cells' routing, which needs 13-27)
fetches the rest in rounds of as many, waiting for each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default

__all__ = ["gather", "usable"]

_F32 = jnp.float32
# rows a DMA moves: y's sublane tile in HBM
_BLOCK = 8
# fetched rows a product takes: the MXU's depth
_ROWS = 128
_BLOCKS = _ROWS // _BLOCK
# tokens a grid step, and blocks the buffer holds: at the Mellum and
# granite cells' routing a tile of 32 tokens names 13-27 blocks
_TILES = (32, 16, 8)
_CAPACITY = 32
# VMEM a call may take: the buffer twice, the float32 columns, the output
# block twice, under Mosaic's default limit
_VMEM_BYTES = 12 << 20
# the row id of a place in the buffer that holds no block
_NONE = -(1 << 30)


def _tile(tokens, k, width):
    """Tokens a grid step, or None: the largest of `_TILES` that divides
    the tokens and leaves the call's buffers within `_VMEM_BYTES`."""
    for tile in _TILES:
        need = (2 * _CAPACITY * _BLOCK * width * 2 + k * tile * width * 4
                + 2 * tile * width * 2)
        if tokens % tile == 0 and need <= _VMEM_BYTES:
            return tile
    return None


def usable(y, slot):
    """Whether `gather` takes these operands: on a TPU, y [rows, D] bf16
    with D a multiple of 128 and rows a multiple of 8, slot [tokens, k]
    int32 with a tile that divides the tokens."""
    return (jax.default_backend() == "tpu"
            and y.ndim == 2 and slot.ndim == 2
            and y.dtype == jnp.bfloat16 and slot.dtype == jnp.int32
            and y.shape[1] % 128 == 0 and y.shape[0] % _BLOCK == 0
            and _tile(slot.shape[0], slot.shape[1], y.shape[1]) is not None)


def _kernel(blocks, counts, widest, row, across, even, y_hbm, out_ref, buf,
            cols, sem, *, k, tile):
    i = pl.program_id(0)
    rows = y_hbm.shape[0]
    names = tile * k                  # (column, token) pairs of a tile

    def copy(at, b, n):
        """The n-th block of tile `at` into place n of buffer b."""
        block = blocks[at * names + n]
        return pltpu.make_async_copy(
            y_hbm.at[pl.ds(pl.multiple_of(block * _BLOCK, _BLOCK), _BLOCK)],
            buf.at[b, pl.ds(pl.multiple_of(n % _CAPACITY * _BLOCK, _BLOCK),
                            _BLOCK)],
            sem.at[b])

    def start(at, b, first, last):
        def one(n, c):
            copy(at, b, n).start()
            return c
        jax.lax.fori_loop(first, last, one, 0)

    def wait(at, b, first, last):
        def one(n, c):
            copy(at, b, n).wait()
            return c
        jax.lax.fori_loop(first, last, one, 0)

    @pl.when(i == 0)
    def _():
        start(0, 0, 0, jnp.minimum(counts[0], _CAPACITY))

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        start(i + 1, (i + 1) % 2, 0, jnp.minimum(counts[i + 1], _CAPACITY))

    b = i % 2
    count = counts[i]
    wait(i, b, 0, jnp.minimum(count, _CAPACITY))
    pairs = row[...]

    def down(x):
        """A (1, n) row of int32 as an (n, 1) column."""
        return jnp.transpose(jnp.broadcast_to(x, (8, x.shape[1])))[:, :1]
    # the pairs as a column, (column, token) in that order
    column = down(pairs)
    # the mask of a 32-bit word of the bf16 buffer, rows 2 p (low half) and
    # 2 p + 1 (high half): ones where a pair of the tile names the row
    evens = down(even[...])
    keep = (jnp.where(jnp.any(evens == pairs, axis=1, keepdims=True),
                      jnp.uint32(0xFFFF), jnp.uint32(0))
            | jnp.where(jnp.any(evens + 1 == pairs, axis=1, keepdims=True),
                        jnp.uint32(0xFFFF0000), jnp.uint32(0)))

    def picked(used, across, fetched):
        """The fetched rows the pairs of the first `used` columns name,
        float32: a one-hot product over rows whose ids are `across` (the
        rows no pair names already zeroed)."""
        pick = jnp.where(column[:used * tile] == across, 1.0, 0.0)
        return jnp.dot(pick.astype(y_hbm.dtype), fetched,
                       preferred_element_type=_F32)

    # a token's live slots come first: where no token of the tile has
    # more than `half`, the later columns hold nothing and are not made
    half = max(1, k // 2)
    few = (widest[i] <= half) & (count <= _CAPACITY)
    for used in sorted({half, k}):
        # the buffer's rows, in as many products of 128 rows as hold blocks
        for n in range(1, _CAPACITY // _BLOCKS + 1):
            held = n * _ROWS

            @pl.when(((count <= n * _BLOCKS) | (n == _CAPACITY // _BLOCKS))
                     & ((count > (n - 1) * _BLOCKS) | (n == 1))
                     & (few == (used == half)))
            def _():
                # two bf16 rows a 32-bit word: the mask of each half
                words = pltpu.bitcast(buf[b, :held], jnp.uint32) & keep[
                    :held // 2]
                cols[:used * tile] = picked(
                    used, across[:, :held], pltpu.bitcast(words, y_hbm.dtype))

    # blocks past the buffer's, a round of `_CAPACITY` at a time, their
    # row ids made here from the block list
    def extra(r, c):
        first = r * _CAPACITY
        start(i, b, first, jnp.minimum(count, first + _CAPACITY))
        wait(i, b, first, jnp.minimum(count, first + _CAPACITY))
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _ROWS), 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)
        for c in range(_CAPACITY // _BLOCKS):
            ids_across = jnp.full((1, _ROWS), _NONE, jnp.int32)
            ids_down = jnp.full((_ROWS, 1), _NONE, jnp.int32)
            for q in range(_BLOCKS):
                n = first + c * _BLOCKS + q
                base = jnp.where(n < count, blocks[
                    i * names + jnp.minimum(n, names - 1)] * _BLOCK, _NONE)
                ids_across = jnp.where(lane // _BLOCK == q,
                                       base + lane % _BLOCK, ids_across)
                ids_down = jnp.where(sub // _BLOCK == q,
                                     base + sub % _BLOCK, ids_down)
            named = jnp.any(pairs == ids_down, axis=1, keepdims=True)
            cols[...] += picked(k, ids_across, jnp.where(
                named, buf[b, c * _ROWS:(c + 1) * _ROWS], 0))
        return c
    jax.lax.fori_loop(1, (count + _CAPACITY - 1) // _CAPACITY, extra, 0)

    for used in sorted({half, k}):
        @pl.when(few == (used == half))
        def _():
            acc = jnp.zeros(out_ref.shape, _F32)
            for j in range(used):
                acc = acc + cols[j * tile:(j + 1) * tile]
            out_ref[...] = acc.astype(out_ref.dtype)


def _plan(slot, rows, tile):
    """What the kernel needs of the slot table, made by `jax.numpy`: the
    distinct 8-row blocks each tile's live slots name, ascending (a list
    `tile * k` long a tile), how many each tile names, and the row id of
    each place of a tile's buffer and of each even place."""
    tokens, k = slot.shape
    tiles = tokens // tile
    live = jnp.sort(jnp.where(slot < rows, slot, rows).reshape(
        tiles, tile * k), axis=1)
    block = live // _BLOCK
    first = jnp.concatenate(
        [live[:, :1] < rows, (block[:, 1:] != block[:, :-1])
         & (live[:, 1:] < rows)], axis=1)
    blocks = jnp.sort(jnp.where(first, block, rows // _BLOCK), axis=1)
    counts = jnp.sum(first, axis=1, dtype=jnp.int32)
    held = jnp.pad(blocks, ((0, 0), (0, max(0, _CAPACITY - tile * k))),
                   constant_values=rows // _BLOCK)[:, :_CAPACITY] * _BLOCK
    # a place past the tile's blocks reads rows and up: no slot names it
    ids = (jnp.repeat(held, _BLOCK, axis=1)
           + jnp.tile(jnp.arange(_BLOCK, dtype=jnp.int32), _CAPACITY))
    return blocks.reshape(-1), counts, ids, ids[:, ::2]


def gather(y, slot, *, tile=None, interpret=None):
    """y [rows, D] bf16, slot [tokens, k] int32 with every slot in
    [0, rows] -> [tokens, D] bf16: for each token the float32 sum, in
    column order, of the rows its slots below `rows` name."""
    tile = _tile(*slot.shape, y.shape[1]) if tile is None else tile
    if tile is None or slot.shape[0] % tile or y.shape[0] % _BLOCK:
        raise ValueError(f"y {y.shape}, slot {slot.shape}, tile {tile}")
    return _call(y, slot, tile=tile,
                 interpret=_interpret_default() if interpret is None
                 else interpret)


# jitted on everything the kernel is built from, so that a step's calls
# (forward, recomputation and backward of every layer) lower it once a
# signature
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _call(y, slot, *, tile, interpret):
    tokens, k = slot.shape
    rows, width = y.shape
    tiles = tokens // tile
    held = _CAPACITY * _BLOCK
    # a token's live slots first, in column order
    slot = jax.lax.sort((jnp.where(slot < rows, 0, 1), slot), dimension=1,
                        is_stable=True, num_keys=1)[1]
    blocks, counts, ids, even = _plan(slot, rows, tile)
    widest = jnp.max(jnp.sum(slot < rows, axis=1, dtype=jnp.int32).reshape(
        tiles, tile), axis=1)
    # a tile's slots as (column, token) pairs, dead ones naming no row,
    # padded to whole lane tiles
    pairs = jnp.where(slot < rows, slot, -1).reshape(tiles, tile, k)
    pairs = pairs.transpose(0, 2, 1).reshape(tiles, tile * k)
    pairs = jnp.pad(pairs, ((0, 0), (0, -tile * k % 128)),
                    constant_values=-1)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((None, 1, pairs.shape[1]),
                             lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((None, 1, held), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((None, 1, held // 2), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, width), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, held, width), y.dtype),
                pltpu.VMEM((tile * k, width), _F32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((tokens, width), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_gather",
    )(blocks, counts, widest, pairs[:, None, :], ids[:, None, :],
      even[:, None, :], y)
