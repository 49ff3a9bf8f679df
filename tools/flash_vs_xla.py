"""Measure Pallas flash attention vs XLA dense attention on real hardware.

The one tool that re-checks the rule in ops/pallas/attention_router.py on
a chip. It times forward and forward + backward of the flash kernels
(ops/pallas/flash_attention.py: two-level tiles chosen from the shape by
`choose_tiles`, one causal sweep) against dense XLA attention over ROWS:
the rows PR 27 measured at the training cells' lengths (seq 1024-4096,
head dim 96 / 128, causal, bf16) and the sweep around them (seq 64-1024
x head dim 64 / 128 x causal and not; head dim 64 and non-causal at long
seq; float32; seq_q != seq_k). It also times each of the three kernels
alone (fa_fwd, fa_bwd_dq, fa_bwd_dkv) at the tiles the chooser hands the
shape, with their grid steps, and with --tune the autotuner's (resident,
sub) row candidates.

Timing method: each measurement runs N iterations INSIDE one compiled
lax.scan so per-dispatch launch overhead is amortized out of the kernel
time. The scan carry feeds each iteration so XLA cannot hoist the body.

Run it on the chip through the chip tool (one process holds the chip):
  chiprun -- python tools/flash_vs_xla.py
The table is printed at the end and written whole to
chiprun_out/flash_vs_xla.json, which the tool copies back. Without a TPU
the tool exits 1 unless --cpu asks for the tiny smoke shapes (which write
nothing).
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
    """n iterations of step_fn(*arrays) inside ONE compiled program; the
    carry data-flows into the first array of each iteration so the body
    cannot be CSE'd/hoisted. An array the step needs is best an ARGUMENT:
    one closed over is a constant of the executable (268 MB each at 64 x
    16,384 x 128, a minute of compile a program)."""
    @jax.jit
    def run(q, *rest):
        def body(carry, _):
            s = step_fn(q + carry, *rest)
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

    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    from paddle_tpu.ops.pallas import autotune as at

    bf16, f32 = jnp.bfloat16, jnp.float32
    # (seq_q, seq_k, batch, heads, head_dim, dtype, causal): batch x heads
    # keeps the DENSE path's fp32 scores <= 512 MB. The first four are
    # PR 27's rows (head_dim 96 rides zero-padded to 128)
    shapes = [(1024, 1024, 8, 16, 128, bf16, True),
              (2048, 2048, 4, 8, 128, bf16, True),
              (4096, 4096, 1, 8, 128, bf16, True),
              (2048, 2048, 4, 8, 96, bf16, True)]
    shapes += [(s, s, 8, 16, d, bf16, c)
               for s in (64, 128, 256, 512, 1024) for d in (64, 128)
               for c in (True, False) if (s, d, c) != (1024, 128, True)]
    shapes += [(2048, 2048, 4, 8, 64, bf16, True),
               (4096, 4096, 1, 8, 64, bf16, True),
               (2048, 2048, 4, 8, 128, bf16, False),
               (4096, 4096, 1, 8, 128, bf16, False),
               (512, 512, 8, 16, 128, f32, True),
               (2048, 2048, 4, 8, 128, f32, True),
               (128, 2048, 4, 8, 128, bf16, True),
               (512, 4096, 1, 8, 128, bf16, True),
               (1024, 4096, 1, 8, 128, bf16, True)]
    # timed a kernel at a time (no dense A/B, so no scores-buffer cap):
    # (2048, 4, 16, 128) is the benchmark's training shape (gpt3-xl-d12:
    # batch 4, 16 heads, d 128) — `_TILE_ROWS` in flash_attention.py was
    # measured there
    tune_shapes = [(1024, 8, 16, 128), (2048, 4, 8, 128), (4096, 1, 8, 128),
                   (2048, 4, 16, 128)]
    if not on_tpu:
        shapes = [(256, 256, 1, 2, 128, bf16, True),
                  (128, 256, 1, 2, 96, f32, False)]
        tune_shapes = [(256, 1, 2, 128)]
    rows = []

    for sq, sk, b, h, d, dtype, causal in shapes:
        def flash_out(q, k, v):
            return flash_attention_bshd(q, k, v, causal=causal)

        def dense_out(q, k, v):
            return _xla_attention(q, k, v, causal=causal)

        def fsum(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32))

        def gsum(fn):
            g = jax.grad(fsum(fn), argnums=(0, 1, 2))
            return lambda q, k, v: sum(
                jnp.sum(x.astype(jnp.float32)) for x in g(q, k, v))

        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, sq, h, d), dtype)
        k = jnp.asarray(rng.randn(b, sk, h, d), dtype)
        v = jnp.asarray(rng.randn(b, sk, h, d), dtype)

        # numeric gate first: flash must agree with dense before timing
        of, od = (np.asarray(jax.jit(fn)(q, k, v).astype(jnp.float32))
                  for fn in (flash_out, dense_out))
        err = float(np.max(np.abs(of - od)))
        row = {"seq_q": sq, "seq_k": sk, "batch": b, "heads": h,
               "head_dim": d, "dtype": jnp.dtype(dtype).name,
               "causal": causal, "max_abs_err": err,
               "iters_per_timing": N_ITERS}
        rows.append(row)
        if err > 0.1:  # bf16 inputs: ~1e-2 expected; 0.1 = clearly wrong
            row["error"] = "NUMERIC MISMATCH — timing skipped"
            log(f"{row}")
            continue

        try:
            ms = {name: timeit(amortized(step), q, k, v) * 1e3
                  for name, step in (("flash_fwd", fsum(flash_out)),
                                     ("dense_fwd", fsum(dense_out)),
                                     ("flash_fwdbwd", gsum(flash_out)),
                                     ("dense_fwdbwd", gsum(dense_out)))}
        except Exception as e:  # noqa: BLE001 — an arm the compiler or
            # the device's memory refuses is the row's finding
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            log(f"{row}")
            continue
        fl_f = attention_flops(b, h, sq, sk, d, causal)
        fl_b = fl_f + attention_flops(b, h, sq, sk, d, causal, bwd=True)
        row.update({f"{name}_ms": round(t, 4) for name, t in ms.items()})
        row.update({
            "fwd_speedup": round(ms["dense_fwd"] / ms["flash_fwd"], 3),
            "fwdbwd_speedup": round(
                ms["dense_fwdbwd"] / ms["flash_fwdbwd"], 3),
            "flash_fwd_tflops": round(fl_f / ms["flash_fwd"] / 1e9, 2),
            "flash_fwdbwd_tflops": round(fl_b / ms["flash_fwdbwd"] / 1e9, 2),
        })
        log(f"sq={sq} sk={sk} bh={b * h} d={d} {row['dtype']} "
            f"causal={causal} err={err:.4f} | fwd: flash "
            f"{ms['flash_fwd']:.3f} dense {ms['dense_fwd']:.3f} ms "
            f"({row['fwd_speedup']}x) | fwd+bwd: flash "
            f"{ms['flash_fwdbwd']:.3f} dense {ms['dense_fwdbwd']:.3f} ms "
            f"({row['fwdbwd_speedup']}x)")

    # each kernel alone, at the chooser's tiles
    kernels = {}
    for seq, b, h, d in tune_shapes:
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
           "rows": rows, "kernels": kernels, "autotuned_tiles": tuned}
    if on_tpu:
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        path = os.path.join(REPO, "chiprun_out", "flash_vs_xla.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        log(f"wrote {path}")
    for r in rows:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
