"""Measure Pallas flash attention vs XLA dense attention on real hardware.

This tool times fwd and fwd+bwd for the dense path, the full-Pallas path
(ops/pallas/flash_attention.py: two-level tiles chosen from the shape by
`choose_tiles`, one causal sweep), and the hybrid (Pallas fwd + XLA-remat
bwd — the `flash_attention_bwd` modes) across seq 1024-4096 (causal,
bf16); times each of the three kernels alone (fa_fwd, fa_bwd_dq,
fa_bwd_dkv) at the tiles the chooser hands the shape, with their grid
steps; with --tune also times the autotuner's (resident, sub) row
candidates; and writes the table that tools/bake_flash_blocks.py bakes
into the attention ledger.

Timing method: each measurement runs N iterations INSIDE one compiled
lax.scan so per-dispatch launch overhead is amortized out of the kernel
time. The scan carry feeds each iteration so XLA cannot hoist the body.

Run it on the chip through the chip tool (one process holds the chip):
  chiprun -- python tools/flash_vs_xla.py
The table is printed as the last line and written to
chiprun_out/flash_vs_xla.json, which the tool copies back; move it to
.flash_vs_xla.json to re-bake. Without a TPU the tool exits 1 unless
--cpu asks for the tiny smoke shapes (which write nothing).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if "--cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")   # smoke-test mode

from paddle_tpu.framework.compile_cache import setup_compile_cache

setup_compile_cache()

import jax.numpy as jnp
import numpy as np

T0 = time.time()
N_ITERS = 16


def log(msg):
    print(f"[{time.time() - T0:7.1f}s] {msg}", flush=True)


def amortized(step_fn, n=N_ITERS):
    """n iterations inside ONE compiled program; the carry data-flows into
    each iteration so the body cannot be CSE'd/hoisted."""
    @jax.jit
    def run(q, k, v):
        def body(carry, _):
            s = step_fn(q + carry, k, v)
            return (s * 0).astype(q.dtype), None
        c, _ = jax.lax.scan(body, jnp.zeros((), q.dtype), None, length=n)
        return c
    return run


def timeit(run, *args, reps=3):
    jax.block_until_ready(run(*args))          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / N_ITERS


def kernel_ms(b, h, seq, d, q_per_kv=1):
    """Each flash kernel alone at the chooser's tiles: {kernel name: ms a
    call} and the tiles and grid steps behind them. One kernel's output is
    all a timed program uses, so XLA drops the other calls."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    bh = b * h
    rng = np.random.RandomState(1)
    q, g = (jnp.asarray(rng.randn(bh, seq, d), jnp.bfloat16)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(bh // q_per_kv, seq, d), jnp.bfloat16)
            for _ in range(2))
    scale = d ** -0.5
    tiles = fa.choose_tiles(seq, seq, d, 2)
    out, lse = jax.jit(lambda a, b_, c: fa._flash_fwd_bhsd(
        a, b_, c, True, scale, q_per_kv=q_per_kv))(q, k, v)

    def fsum(*xs):
        return sum(jnp.sum(x.astype(jnp.float32)) for x in xs)

    def bwd(pick):
        def step(q_, k_, v_):
            return fsum(*pick(fa._flash_bwd_bhsd(
                q_, k_, v_, out, lse, g, True, scale, q_per_kv=q_per_kv)))
        return step

    steps = {
        "fa_fwd": lambda q_, k_, v_: fsum(fa._flash_fwd_bhsd(
            q_, k_, v_, True, scale, q_per_kv=q_per_kv)[0]),
        "fa_bwd_dq": bwd(lambda o: o[:1]),
        "fa_bwd_dkv": bwd(lambda o: o[1:]),
    }
    return {"ms": {name: round(timeit(amortized(step), q, k, v) * 1e3, 3)
                   for name, step in steps.items()},
            "tiles": {"fwd": tiles.fwd, "dq": tiles.dq, "dkv": tiles.dkv},
            "grid_steps": tiles.grid_steps(bh, seq, seq)}


def attention_flops(b, h, sq, sk, d, causal, bwd=False):
    """Matmul FLOPs of attention (2*bhs^2*d for QK^T, same for PV);
    backward re-does ~2.5x the forward matmuls (dQ, dK, dV, P remat)."""
    f = 2 * 2 * b * h * sq * sk * d
    if causal:
        f /= 2
    return f * (2.5 if bwd else 1.0)


def main():
    dev = jax.devices()[0]
    log(f"device: {dev} ({getattr(dev, 'device_kind', '?')})")
    on_tpu = dev.platform == "tpu"
    if not on_tpu and "--cpu" not in sys.argv:
        sys.exit("flash_vs_xla: jax found no TPU; pass --cpu for the "
                 "smoke shapes")

    from paddle_tpu.framework import flags as _flags
    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    from paddle_tpu.ops.pallas import autotune as at

    # (seq, batch, heads, head_dim): keep the DENSE path's fp32 logits
    # <= ~512 MB. head_dim 96 rows measure the zero-pad path (llama_780m)
    shapes = [(1024, 8, 16, 128), (2048, 4, 8, 128), (4096, 1, 8, 128),
              (2048, 4, 8, 96)]
    # timed a kernel at a time (no dense A/B, so no logits-buffer cap):
    # (2048, 4, 16, 128) is the benchmark's training shape (gpt3-xl-d12
    # and llama_535m: batch 4, 16 heads, d 128) — `_TILE_ROWS` in
    # flash_attention.py was measured there
    tune_shapes = shapes + [(2048, 4, 16, 128)]
    if not on_tpu:
        shapes = [(256, 1, 2, 128), (256, 1, 2, 96)]
        tune_shapes = shapes
    causal = True
    rows = []

    def flash_sum(q, k, v):
        return jnp.sum(flash_attention_bshd(q, k, v, causal=True)
                       .astype(jnp.float32))

    def dense_sum(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    flash_grad = jax.grad(flash_sum, argnums=(0, 1, 2))
    dense_grad = jax.grad(dense_sum, argnums=(0, 1, 2))

    for seq, b, h, d in shapes:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16)

        # numeric gate first: flash must agree with dense before timing
        of = np.asarray(jax.jit(lambda a, b_, c: flash_attention_bshd(
            a, b_, c, causal=True))(q, k, v).astype(jnp.float32))
        od = np.asarray(jax.jit(lambda a, b_, c: _xla_attention(
            a, b_, c, causal=True))(q, k, v).astype(jnp.float32))
        err = float(np.max(np.abs(of - od)))
        log(f"seq={seq} b={b} h={h}: max|flash-dense| = {err:.4f}")
        row = {"seq": seq, "batch": b, "heads": h, "head_dim": d,
               "max_abs_err": err, "iters_per_timing": N_ITERS}
        if err > 0.1:  # bf16 inputs: ~1e-2 expected; 0.1 = clearly wrong
            row["error"] = "NUMERIC MISMATCH — timing skipped"
            rows.append(row)
            continue

        tf = timeit(amortized(flash_sum), q, k, v)
        td = timeit(amortized(dense_sum), q, k, v)
        tg = {}
        for name, mode, gfn in (("pallas", "pallas", flash_grad),
                                ("hybrid", "xla", flash_grad),
                                ("dense", "pallas", dense_grad)):
            _flags.set_flags({"FLAGS_flash_attention_bwd": mode})
            tg[name] = timeit(amortized(
                lambda q_, k_, v_, g=gfn: sum(
                    jnp.sum(x.astype(jnp.float32)) for x in g(q_, k_, v_))),
                q, k, v)
        _flags.set_flags({"FLAGS_flash_attention_bwd": "auto"})
        fl_f = attention_flops(b, h, seq, seq, d, causal)
        fl_b = fl_f + attention_flops(b, h, seq, seq, d, causal, bwd=True)
        row.update({
            "flash_fwd_ms": round(tf * 1e3, 3),
            "dense_fwd_ms": round(td * 1e3, 3),
            "fwd_speedup": round(td / tf, 3),
            "fwdbwd_ms_pallas": round(tg["pallas"] * 1e3, 3),
            "fwdbwd_ms_hybrid": round(tg["hybrid"] * 1e3, 3),
            "fwdbwd_ms_dense": round(tg["dense"] * 1e3, 3),
            "flash_fwd_tflops": round(fl_f / tf / 1e12, 2),
            "tflops_pallas_bwd": round(fl_b / tg["pallas"] / 1e12, 2),
            "tflops_hybrid_bwd": round(fl_b / tg["hybrid"] / 1e12, 2),
            "tflops_dense": round(fl_b / tg["dense"] / 1e12, 2),
        })
        rows.append(row)
        log(f"  fwd: flash {tf*1e3:.2f}ms vs dense {td*1e3:.2f}ms "
            f"({td/tf:.2f}x) | fwd+bwd ms: pallas {tg['pallas']*1e3:.2f} "
            f"hybrid {tg['hybrid']*1e3:.2f} dense {tg['dense']*1e3:.2f}")

    # each kernel alone, at the chooser's tiles
    kernels = {}
    for seq, b, h, d in tune_shapes:
        if d % 128:
            continue    # the wrapper pads such head dims; the A/B rows do
        res = kernel_ms(b, h, seq, d)
        kernels[f"s{seq}_d{d}_bh{b * h}"] = res
        log(f"kernels seq={seq} bh={b * h}: {res['ms']} ms, grid steps "
            f"{res['grid_steps']}, tiles {res['tiles']}")

    # --tune: the autotuner's (resident, sub) row candidates on hardware,
    # ~5 x fwd/bwd compiles a shape; the table alone needs none of it
    tuned = {}
    if "--tune" in sys.argv:
        from paddle_tpu.ops.pallas.flash_attention import _tiles_for
        at.enable_autotune()
        for seq, b, h, d in tune_shapes:
            for kind in ("fwd", "bwd"):
                win = _tiles_for(kind, b * h, seq, seq, d, jnp.bfloat16,
                                 True, not on_tpu)
                tuned[f"{kind}_s{seq}_d{d}_bh{b * h}"] = {
                    "fwd": win.fwd, "dq": win.dq, "dkv": win.dkv}
                log(f"autotune {kind} seq={seq} bh={b * h}: winner {win}")
        at.disable_autotune()
        tuned["candidate_ms"] = {str(k): v for k, v in at.timing_log.items()}

    out = {"device": str(dev),
           "device_kind": getattr(dev, "device_kind", "?"),
           "causal": causal, "dtype": "bfloat16",
           "rows": rows, "kernels": kernels, "autotuned_tiles": tuned}
    if on_tpu:
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        path = os.path.join(REPO, "chiprun_out", "flash_vs_xla.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        log(f"wrote {path}")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
