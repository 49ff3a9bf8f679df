"""Measure the windowed flash kernels (faw_*) on real hardware.

What ops/pallas/flash_attention.py's `_TILE_ROWS` under a window and
`_WINDOW_STREAMED_ROWS` rest on, and the comparison ISSUE 34 asks for. At
batch 2 x 32 query heads over 4 key-value heads, head dim 128, bf16,
window 1,024, sequence 16,384, 8,192 and 2,048:

- each kernel alone (forward, dQ, dK/dV) under the window at the tiles
  `choose_tiles` hands the shape, against the same kernel with
  window=None (the whole causal triangle);
- forward and forward + backward through `flash_attention_bshd`, with and
  without the window, against dense XLA attention with the band mask
  (at as many heads as its float32 scores allow, reckoned a head);
- with --sweep (or --sweep=dkv,dq for some kernels; --sweep-only skips
  the comparison above), at 16,384: every (resident, sub) rows candidate
  x streamed-tile rows, each kernel alone (the tile table of PERF.md).

Timing as tools/flash_vs_xla.py: N iterations inside one compiled scan.

  chiprun -- python tools/window_attention.py --sweep
writes chiprun_out/window_attention.json. Without a TPU it exits 1 unless
--cpu asks for one tiny shape (which writes nothing).
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if "--cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")

from paddle_tpu.framework.compile_cache import setup_compile_cache

setup_compile_cache()

import jax.numpy as jnp
import numpy as np

from tools.flash_vs_xla import REPO, amortized, log, timeit

HEADS, KV_HEADS, HEAD_DIM, WINDOW = 32, 4, 128, 1024
ROWS = ((256, 256), (512, 256), (512, 512), (1024, 512), (1024, 1024))
STREAMED = (1024, 2048, 4096, None)         # None: the whole sequence
DENSE_SCORE_BYTES = 1 << 30                 # float32 scores a dense call


def _candidate(kind, rows, streamed, seq, d):
    """(resident, streamed, sub) rows of one row of the tile table: the
    streamed tile `streamed` rows (None: the whole sequence), halved while
    the kernel's buffers are over what `choose_tiles` allows them."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    res, sub = rows
    streamed = -(-min(streamed or seq, seq) // sub) * sub
    while streamed > sub and fa.vmem_bytes(
            kind, (res, streamed, sub), d, 2) > fa._VMEM_BUDGET:
        streamed = -(-(streamed // 2) // sub) * sub
    return (res, streamed, sub)


def _operands(bh, g, seq, d):
    rng = np.random.RandomState(1)
    q, do = (jnp.asarray(rng.randn(bh, seq, d), jnp.bfloat16)
             for _ in range(2))
    k, v = (jnp.asarray(rng.randn(bh // g, seq, d), jnp.bfloat16)
            for _ in range(2))
    return q, k, v, do


def _fsum(*xs):
    return sum(jnp.sum(x.astype(jnp.float32)) for x in xs)


def kernel_ms(bh, g, seq, d, window, tiles, kinds=("fwd", "dq", "dkv")):
    """{kind: ms a call} of each kernel alone at `tiles`: one kernel's
    output is all a timed program uses, so XLA drops the other calls."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    q, k, v, do = _operands(bh, g, seq, d)
    scale = d ** -0.5
    kw = dict(tiles=tiles, q_per_kv=g, window=window)
    out, lse = jax.jit(lambda a, b, c: fa._flash_fwd_bhsd(
        a, b, c, True, scale, **kw))(q, k, v)

    def bwd(pick):
        return lambda q_, k_, v_, o_, lse_, do_: _fsum(*pick(
            fa._flash_bwd_bhsd(q_, k_, v_, o_, lse_, do_, True, scale,
                               **kw)))

    steps = {"fwd": lambda q_, k_, v_, *_: _fsum(fa._flash_fwd_bhsd(
                 q_, k_, v_, True, scale, **kw)[0]),
             "dq": bwd(lambda o: o[:1]), "dkv": bwd(lambda o: o[1:])}
    return {kind: round(timeit(amortized(steps[kind]), q, k, v, out, lse,
                               do) * 1e3, 3)
            for kind in kinds}


def layer_ms(b, h, kvh, seq, d, window, dense=False):
    """(forward ms, forward + backward ms) of attention on (b, seq, h, d)
    operands: the kernels' entry point, or dense XLA with the band mask."""
    from paddle_tpu.nn.functional.attention import _expand_kv, _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16)
    k, v = (jnp.asarray(rng.randn(b, seq, kvh, d), jnp.bfloat16)
            for _ in range(2))

    def attn(q_, k_, v_):
        if dense:
            k_, v_ = _expand_kv(k_, v_, h)
            return _xla_attention(q_, k_, v_, causal=True, window=window)
        return flash_attention_bshd(q_, k_, v_, causal=True, window=window)

    fwd = lambda *a: _fsum(attn(*a))                      # noqa: E731
    both = lambda *a: _fsum(*jax.grad(                    # noqa: E731
        lambda *x: _fsum(attn(*x)), argnums=(0, 1, 2))(*a))
    return tuple(round(timeit(amortized(f), q, k, v) * 1e3, 3)
                 for f in (fwd, both))


def main():
    from paddle_tpu.ops.pallas import flash_attention as fa
    dev = jax.devices()[0]
    log(f"device: {dev} ({getattr(dev, 'device_kind', '?')})")
    cpu = "--cpu" in sys.argv
    if dev.platform != "tpu" and not cpu:
        sys.exit("window_attention: jax found no TPU; pass --cpu for the "
                 "smoke shape")
    d, g = HEAD_DIM, HEADS // KV_HEADS
    seqs, batch, window = ((256,), 1, 64) if cpu else (
        (16384, 8192, 2048), 2, WINDOW)
    heads, kvh = (8, 1) if cpu else (HEADS, KV_HEADS)
    bh = batch * heads
    rows = []
    for seq in () if "--sweep-only" in sys.argv else seqs:
        row = {"seq": seq, "batch": batch, "heads": heads, "kv_heads": kvh,
               "head_dim": d, "window": window}
        for name, w in (("window", window), ("causal", None)):
            tiles = fa.choose_tiles(seq, seq, d, 2, window=w)
            row[name] = {
                "tiles": {"fwd": tiles.fwd, "dq": tiles.dq,
                          "dkv": tiles.dkv},
                "grid_steps": tiles.grid_steps(bh, seq, seq, w),
                "visited_pairs": tiles.visited_pairs(seq, seq, w),
                "needed_pairs": fa.band_pairs(seq, seq, w),
                "kernel_ms": kernel_ms(bh, g, seq, d, w, tiles),
                "layer_ms_fwd_fwdbwd": layer_ms(batch, heads, kvh, seq, d, w)}
            log(f"seq {seq} {name}: {row[name]}")
        # dense: as many heads as DENSE_SCORE_BYTES of float32 scores hold
        dh = max(1, min(heads, DENSE_SCORE_BYTES // (4 * seq * seq)))
        fwd, both = layer_ms(1, dh, dh, seq, d, window, dense=True)
        row["dense_band_mask"] = {
            "heads_timed": dh, "ms_fwd_fwdbwd": (fwd, both),
            "ms_fwd_fwdbwd_at_all_heads": (
                round(fwd * bh / dh, 3), round(both * bh / dh, 3))}
        log(f"seq {seq} dense: {row['dense_band_mask']}")
        rows.append(row)
    result = {"device": getattr(dev, "device_kind", dev.platform),
              "rows": rows}
    sweep = [a for a in sys.argv if a.split("=")[0] == "--sweep"]
    if sweep:
        seq = seqs[0]
        table = []
        # --sweep: every kernel; --sweep=dkv,dq: those
        kinds = sweep[0].partition("=")[2].split(",") if "=" in sweep[0] \
            else ("fwd", "dq", "dkv")
        chosen = fa.choose_tiles(seq, seq, d, 2, window=window)
        for kind in kinds:
            for r in ROWS:
                for streamed in STREAMED:
                    tiles = dataclasses.replace(
                        chosen, **{kind: _candidate(kind, r, streamed, seq,
                                                    d)})
                    try:
                        ms = kernel_ms(bh, g, seq, d, window, tiles,
                                       kinds=(kind,))[kind]
                    except Exception as e:  # noqa: BLE001 — a tile the
                        # compiler refuses is a row of the table
                        ms = f"{type(e).__name__}: {str(e)[:120]}"
                    table.append({"kernel": kind,
                                  "tile": getattr(tiles, kind), "ms": ms})
                    log(f"sweep {table[-1]}")
        result["sweep_seq"] = seq
        result["sweep"] = table
    if cpu:
        return
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "window_attention.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
