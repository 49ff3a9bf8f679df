"""Traffic kind `train`: a fixed job of whole optimizer steps.

    {"kind": "train", "batch": 4, "seq": 2048, "learning_rate": 1e-4,
     "warm_steps": 3, "distinct_batches": 8, "trace_steps": 3,
     "loss_tolerance": 0.05}

Set-up: model, seeded weights, trainer, the float32 reference's loss on the
first batch, then `warm_steps` steps on that batch (the first compiles; the
loss must agree with the reference and fall). Window: steps over seeded
random batches, one step kept in flight, until --seconds have passed; the
rate is the tokens of the whole steps completed over the measured time from
the first step's dispatch to block_until_ready of the last.
"""

from __future__ import annotations

import importlib
import math
import os
import time

import numpy as np

from harness import device, trace as tr
from harness.compile_meter import CompileMeter


def _batches(traffic, vocab, seed):
    rs = np.random.RandomState(seed % (2 ** 32))
    return [rs.randint(0, vocab, (int(traffic["batch"]), int(traffic["seq"]))
                       ).astype(np.int32)
            for _ in range(int(traffic["distinct_batches"]))]


def run(cell, seed, seconds, trace, t_start, device_info):
    traffic, config = cell.traffic, cell.config
    family = importlib.import_module(f"families.{config['family']}")
    meter = CompileMeter()
    info = {"kind": "train"}

    trainer, cfg, n_params = family.build_trainer(config, traffic, seed)
    batches = _batches(traffic, cfg.vocab_size, seed)
    tokens_per_step = int(traffic["batch"]) * int(traffic["seq"])
    t_built = time.perf_counter()

    # correct, part 1: the float32 reference on the same weights and batch,
    # before the first step donates them
    ref_loss = family.reference_loss(trainer, cfg, batches[0])
    t_ref = time.perf_counter()

    warm = []
    for _ in range(int(traffic["warm_steps"])):
        loss = trainer.step((batches[0], batches[0]))
        warm.append(float(loss))
    t_warm = time.perf_counter()
    compiled_setup = meter.events

    # -- the window ----------------------------------------------------------
    losses, n_done = [], 0
    with tr.span("bench.window"):
        t0 = time.perf_counter()
        prev = None
        k = 0
        while True:
            with tr.span("bench.train_step"):
                b = batches[k % len(batches)]
                cur = trainer.step((b, b))
            k += 1
            if prev is not None:
                prev.block_until_ready()
                losses.append(prev)
                n_done += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            prev = cur
        cur.block_until_ready()
        t1 = time.perf_counter()
    losses.append(cur)
    n_done += 1
    window_s = t1 - t0
    losses = [float(x) for x in losses]
    compiles_in_window = meter.events - compiled_setup
    rate = tokens_per_step * n_done / window_s

    # -- after the window: the traced steps, the step's memory ---------------
    loaded = None
    if trace:
        trace_dir = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                                 "bench_trace_" + cell.name)
        cap = tr.Capture(trace_dir)
        cap.start()
        for i in range(int(traffic["trace_steps"])):
            with tr.span("bench.train_step"):
                b = batches[i % len(batches)]
                loss = trainer.step((b, b))
        loss.block_until_ready()
        cap.stop()
        loaded = tr.load(trace_dir)
        if os.environ.get("BENCH_DESCRIBE_TRACE"):
            print("\n".join(tr.describe(trace_dir)), flush=True)
    b = batches[0]
    xla = trainer.step_memory((b, b))
    step_bytes = xla["argument"] + xla["output"] - xla["alias"] + xla["temp"]
    allocator_peak = device.memory_peak_bytes(cell.chips)

    tol = float(traffic["loss_tolerance"])
    problems = []
    if not abs(warm[0] - ref_loss) <= tol:
        problems.append(f"first-step loss {warm[0]:.5f} differs from the "
                        f"float32 reference {ref_loss:.5f} by more than {tol}")
    if not all(math.isfinite(x) for x in warm + losses):
        problems.append("non-finite loss")
    if len(warm) > 1 and not warm[-1] < warm[0]:
        problems.append(f"loss did not fall on the repeated batch: {warm}")

    info.update(
        params=n_params, tokens_per_step=tokens_per_step, steps=n_done,
        window_s=window_s, first_step_loss=warm[0], reference_loss=ref_loss,
        loss_diff=abs(warm[0] - ref_loss), warm_losses=warm,
        window_losses_first_last=[losses[0], losses[-1]],
        setup_breakup_s={"build": t_built - t_start,
                         "reference": t_ref - t_built,
                         "warm_steps": t_warm - t_ref},
        compile=meter.report(), problems=problems,
        compiles_in_window=compiles_in_window,
        xla_step_memory=xla, allocator_peak_bytes=allocator_peak,
        mesh={k: int(v) for k, v in trainer.mesh.shape.items() if v > 1})
    return {
        "correct": not problems, "attempted": n_done, "failed": 0,
        "setup_s": t0 - t_start,
        "e2e": {"train_tokens_per_s": rate},
        "samples": {"tokens_per_s": rate, "shapes": family.shapes(cfg),
                    "seq": int(traffic["seq"]), "batch": int(traffic["batch"]),
                    "chips": cell.chips, "step_bytes": step_bytes,
                    "compiles_in_window": compiles_in_window,
                    "window_s": window_s},
        "trace": loaded, "info": info,
        "memory_peak_bytes": max(step_bytes, allocator_peak or 0),
    }
