"""Benchmark arms: Llama pretraining step throughput on one TPU chip, the
BASELINE secondaries (ResNet / BERT / serving decode), the traffic-harness
leg and the simulated-mesh sharding sweep.

Each invocation runs ONE arm in the process that holds the chip and prints
ONE JSON line: {"metric": ..., "value": N, "unit": ..., "device":
{"platform", "kind", "count"}, "detail": {...}}.

  python bench.py [--config N]            Llama ladder (MFU of a compiled
                                          bf16 AdamW causal-LM train step)
  python bench.py --secondary resnet|bert|decode|both
  python bench.py --secondary multichip   needs >=4 devices (--cpu: 8 virtual)
  python bench.py --loadgen [scenario]

No chip is a failure, not a fallback: without an accelerator the process
exits 1 unless --cpu is given, and a --cpu row is named *_cpu_smoke — a CPU
number never appears under a device metric's name. An arm that raised makes
the exit code non-zero. A device whose peak is not in PEAK_BF16 is an error.
"""

from __future__ import annotations

import json
import os
import sys
import time

# peak dense bf16 FLOP/s per chip, keyed by jax's device_kind. Source: Google
# Cloud TPU documentation (system architecture pages for v4 / v5e / v5p / v6e).
PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e
    "TPU v6e": 918e12,
}


def detect_peak():
    """Peak bf16 FLOP/s of the device the process runs on. A device_kind
    that is not in the table is an error, never a default."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16:
        raise KeyError(
            f"no peak FLOP/s recorded for device_kind {kind!r}; add it to "
            "bench.PEAK_BF16 with its source")
    return PEAK_BF16[kind]


def _device_info():
    """The device as jax reports it — every printed row carries this."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _init_backend(force_cpu: bool):
    """First touch of jax for every arm. --cpu pins the CPU platform before
    the backend initializes; without it an accelerator must be present."""
    import jax
    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.framework.compile_cache import setup_compile_cache
    setup_compile_cache()
    on_tpu = jax.devices()[0].platform != "cpu"
    if not on_tpu and not force_cpu:
        print("bench.py: jax found no accelerator "
              f"(default backend {jax.default_backend()!r}); pass --cpu for "
              "an explicit CPU smoke run", file=sys.stderr)
        sys.exit(1)
    return on_tpu


def _row_name(name: str, on_tpu: bool) -> str:
    return name if on_tpu else name + "_cpu_smoke"


def _failed_arms(detail: dict) -> list:
    return sorted(k for k in detail if k.endswith("_error"))


def _llama_ladder():
    """Bench configs, biggest first; worker walks down on OOM.
    Sizes chosen for one v5e/v5p chip (~16 GB HBM) with AdamW state."""
    from paddle_tpu.models.llama import LlamaConfig
    gpt3_1p3b = dict(vocab_size=32000, hidden_size=2048, intermediate_size=8192,
                     num_hidden_layers=24, num_attention_heads=16,
                     max_position_embeddings=2048, dtype="bfloat16")
    llama_780m = dict(vocab_size=32000, hidden_size=1536, intermediate_size=6144,
                      num_hidden_layers=16, num_attention_heads=16,
                      max_position_embeddings=2048, dtype="bfloat16")
    llama_535m = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5504,
                      num_hidden_layers=8, num_attention_heads=16,
                      max_position_embeddings=2048, dtype="bfloat16")
    return [
        # (name, cfg, batch, seq, steps, remat). Remat is ON for >=780M:
        # every no-remat big config exceeds the v5e's 16GB once bf16 AdamW
        # moments + activations + the loss buffer stack up (r5; the chunked
        # LM loss and per-layer remat are what fit them). 535m keeps the
        # fused LM loss (its 1.05GB fp32 logits buffer fits with room —
        # the r2 0.5216-MFU run was fused; chunking it costs throughput),
        # selected via the worker's per-row loss_chunk_mb below.
        ("llama_1.3b", LlamaConfig(**gpt3_1p3b), 8, 2048, 8, True),
        ("llama_1.3b_small_batch", LlamaConfig(**gpt3_1p3b), 4, 2048, 8, True),
        ("llama_780m", LlamaConfig(**llama_780m), 8, 2048, 8, True),
        ("llama_535m", LlamaConfig(**llama_535m), 4, 2048, 8, False),
    ]


def _loss_chunk_mb_for(name):
    """Per-config fused-vs-chunked LM loss threshold (MB of fp32 logits)."""
    return 1100 if name == "llama_535m" else 256


def _pir_cache_stats():
    """PIR persistent compile-cache counters (hit/miss/write/corrupt/
    evict) — process-local, metrics-independent; rows record the delta
    per config so the compile-cost trajectory is tracked alongside MFU."""
    from paddle_tpu.pir import stats_snapshot
    return stats_snapshot()


def _pir_cache_delta(before, after):
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in after if after.get(k, 0) != before.get(k, 0)}


def _run_one(cfg, batch, seq, steps, remat, on_tpu, remat_policy=None,
             loss_chunk_mb=256, run_name="llama"):
    """One config: scan-over-layers train step (HLO size O(1) in depth, so
    XLA compiles one layer body instead of an unrolled stack)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.models.scanned import build_scanned_llama

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    n_params = model.num_params()
    params, loss_fn = build_scanned_llama(
        model, remat=remat, dtype="bfloat16" if on_tpu else None,
        remat_policy=remat_policy, loss_chunk_mb=loss_chunk_mb)
    opt = optimizer.AdamW(3e-4, parameters=model.parameters())
    opt_state = opt.tree_init(params)
    # the scanned params are fresh (stacked, cast) copies; free the
    # imperative model's originals so they don't pin HBM for the whole run
    # (functional_call substitutes every template param by name, so the
    # template's own arrays are never read)
    for t in model.state_dict().values():
        t._data = jnp.zeros((), t._data.dtype)

    def train_step(p, st, ids, labels, lr, stp):
        loss, grads = jax.value_and_grad(loss_fn)(p, ids, labels)
        new_p, new_st = opt.tree_update(p, grads, st, lr, stp)
        return loss, new_p, new_st

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    lr = jnp.float32(3e-4)

    # compile ONCE ahead of time; the AOT executable is used for every step
    # and also provides XLA's own FLOP count (an MFU cross-check that
    # doesn't depend on the 6N analytic formula)
    from paddle_tpu.framework import flags as _wflags
    bwd_mode_used = _wflags.flag_value("flash_attention_bwd")
    if bwd_mode_used == "auto":
        # 'auto' is routed per shape by the baked attention ledger —
        # resolve it for THIS config's attention shape so the bench row
        # records what actually ran
        from paddle_tpu.ops.pallas.attention_router import route
        hd_ = cfg.hidden_size // cfg.num_attention_heads
        bwd_mode_used = "auto:" + route(
            batch * cfg.num_attention_heads, seq, seq, hd_,
            "bfloat16" if on_tpu else "float32", True).bwd
    jstep = jax.jit(train_step, donate_argnums=(0, 1))
    cache_before = _pir_cache_stats()
    t_cold = time.perf_counter()
    run = jstep.lower(params, opt_state, ids, ids, lr,
                      jnp.int32(1)).compile()
    ca = run.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    xla_flops = float((ca or {}).get("flops", 0.0)) or None

    # warmup (settle allocator / first dispatch); the first call closes
    # the cold-compile window, the second is the warm reference — the
    # cold-vs-warm gap IS the compile cost this config pays at startup
    loss, params, opt_state = run(params, opt_state, ids, ids, lr,
                                  jnp.int32(1))
    _ = float(loss)
    compile_cold_s = time.perf_counter() - t_cold
    t_warm = time.perf_counter()
    loss, params, opt_state = run(params, opt_state, ids, ids, lr,
                                  jnp.int32(2))
    _ = float(loss)
    compile_warm_s = time.perf_counter() - t_warm

    t0 = time.perf_counter()
    for i in range(steps):
        loss, params, opt_state = run(params, opt_state, ids, ids, lr,
                                      jnp.int32(3 + i))
    final = float(loss)  # sync
    dt = time.perf_counter() - t0
    tokens = batch * seq * steps
    # feed the round's training telemetry through the observability layer
    # (train_step_seconds / tokens / MFU gauges) — the timed loop above is
    # untouched; record_run back-fills the aggregate so the bench row's
    # embedded snapshot is self-describing
    from paddle_tpu import observability as _obs
    fpt = (6.0 * n_params
           + 12.0 * cfg.num_hidden_layers * cfg.hidden_size * seq)
    _obs.StepWatch(tokens_per_step=batch * seq, flops_per_token=fpt,
                   peak_flops=detect_peak() if on_tpu else None,
                   run_name=run_name).record_run(
        steps, dt, tokens=tokens, loss=final)
    return {"tokens_per_s": tokens / dt, "n_params": n_params, "loss": final,
            "attention_bwd_used": bwd_mode_used,
            "lm_loss_path": loss_fn.lm_loss_path,  # set when traced
            "step_time_s": dt / steps, "xla_flops_per_step": xla_flops,
            "compile_cold_s": round(compile_cold_s, 3),
            "compile_warm_s": round(compile_warm_s, 3),
            "compile_cache": _pir_cache_delta(cache_before,
                                              _pir_cache_stats())}


def _functional_train_setup(model, opt, to_bf16):
    """state_dict -> pure param arrays (+ optional bf16 cast) + opt state.
    Frees the imperative model's own arrays (functional_call substitutes
    every param by name, so the templates are never read) — on a ~16 GB
    chip the f32 originals would otherwise pin HBM for the whole bench."""
    import jax.numpy as jnp
    params = {}
    for k, t in model.state_dict().items():
        a = t._data
        if to_bf16 and a.dtype == jnp.float32:
            a = a.astype(jnp.bfloat16)
        params[k] = a
        if to_bf16:
            t._data = jnp.zeros((), t._data.dtype)
    return params, opt.tree_init(params)


def _jit_train_step(opt, loss_fn):
    """Shared step builder: value_and_grad + optimizer update, params and
    opt state donated. loss_fn(params, *data) -> scalar."""
    import jax

    def train_step(p, st, *tail):
        *data, lr, stp = tail
        loss, grads = jax.value_and_grad(loss_fn)(p, *data)
        new_p, new_st = opt.tree_update(p, grads, st, lr, stp)
        return loss, new_p, new_st

    return jax.jit(train_step, donate_argnums=(0, 1))


def _time_train(jstep, params, opt_state, make_args, steps):
    """Shared bench loop: one compile+warmup step, then `steps` timed steps.
    Returns (final_loss, seconds). make_args(i) -> per-step tail args."""
    loss, params, opt_state = jstep(params, opt_state, *make_args(1))
    _ = float(loss)
    t0 = time.perf_counter()
    for i in range(steps):
        loss, params, opt_state = jstep(params, opt_state, *make_args(2 + i))
    final = float(loss)
    return final, time.perf_counter() - t0


def _bench_resnet(on_tpu):
    """BASELINE row 2: ResNet-50 ImageNet-shape train step, images/sec.
    reference perf unit: python/paddle/profiler/timer.py (ips)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.nn import functional as F
    from paddle_tpu.parallel.functional import make_loss_fn

    paddle.seed(0)
    if on_tpu:
        from paddle_tpu.vision.models import resnet50
        model, batch, hw, steps = resnet50(), 64, 224, 8
    else:
        from paddle_tpu.vision.models import resnet18
        model, batch, hw, steps = resnet18(num_classes=10), 2, 32, 2
    opt = optimizer.Momentum(0.1, momentum=0.9,
                             parameters=model.parameters())
    params, opt_state = _functional_train_setup(model, opt, to_bf16=on_tpu)
    loss_fn = make_loss_fn(
        model, lambda logits, y: F.cross_entropy(logits, y))
    jstep = _jit_train_step(opt, lambda p, x, y: loss_fn(p, (x, y), None))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, 3, hw, hw),
                    jnp.bfloat16 if on_tpu else jnp.float32)
    y = jnp.asarray(rng.randint(0, 1000 if on_tpu else 10, (batch,)),
                    jnp.int32)
    lr = jnp.float32(0.1)
    final, dt = _time_train(jstep, params, opt_state,
                            lambda i: (x, y, lr, jnp.int32(i)), steps)
    return {"resnet_images_per_s": round(batch * steps / dt, 1),
            "resnet_batch": batch, "resnet_loss": round(final, 4),
            "resnet_variant": "resnet50_224" if on_tpu else "resnet18_32_cpu"}


def _bench_bert(on_tpu):
    """BASELINE row 3: BERT-base pretraining-shape step, MFU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    paddle.seed(0)
    if on_tpu:
        cfg = BertConfig(dropout=0.0)  # bert-base: 12L/768/12H
        batch, seq, steps = 32, 512, 8
    else:
        cfg = BertConfig(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, intermediate_size=128,
                         max_position_embeddings=128, dropout=0.0)
        batch, seq, steps = 2, 64, 2
    model = BertForPretraining(cfg)
    n_params = sum(int(np.prod(t.shape))
                   for t in model.state_dict().values())
    opt = optimizer.AdamW(1e-4, parameters=model.parameters())
    params, opt_state = _functional_train_setup(model, opt, to_bf16=on_tpu)
    from paddle_tpu.parallel.functional import functional_call

    def loss_fn(p, ids, labels):
        out = functional_call(model, p, ids, masked_lm_labels=labels)
        loss = out[0] if isinstance(out, (tuple, list)) else out
        return loss.astype(jnp.float32)

    jstep = _jit_train_step(opt, loss_fn)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(
        np.where(rng.rand(batch, seq) < 0.15,
                 rng.randint(0, cfg.vocab_size, (batch, seq)), -100),
        jnp.int32)
    lr = jnp.float32(1e-4)
    final, dt = _time_train(jstep, params, opt_state,
                            lambda i: (ids, labels, lr, jnp.int32(i)), steps)
    tok_per_s = batch * seq * steps / dt
    out = {"bert_tokens_per_s": round(tok_per_s, 1),
           "bert_params": n_params, "bert_loss": round(final, 4),
           "bert_batch": batch, "bert_seq": seq}
    if on_tpu:
        flops_per_token = (6.0 * n_params +
                           12.0 * cfg.num_hidden_layers * cfg.hidden_size * seq)
        out["bert_base_mfu"] = round(
            flops_per_token * tok_per_s / detect_peak(), 4)
    return out


def _bench_decode(on_tpu):
    """Serving decode: compiled KV-cache generate() tokens/s, bf16 and
    weight-only int8 (reference capability:
    paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import generation
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=8,
                          num_attention_heads=16,
                          max_position_embeddings=2048, dtype="bfloat16")
        batch, prompt, new = 8, 128, 128
    else:
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4, max_position_embeddings=256)
        batch, prompt, new = 2, 16, 8
    model = LlamaForCausalLM(cfg)
    if on_tpu:  # serve in bf16
        for t in model.state_dict().values():
            if t._data.dtype == jnp.float32:
                t._data = t._data.astype(jnp.bfloat16)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, prompt)),
                      jnp.int32)
    out = {"decode_batch": batch, "decode_prompt": prompt,
           "decode_new_tokens": new,
           "decode_params": model.num_params()}

    # prefill-only program vs full program isolates per-token decode cost
    r1 = generation.generate(model, ids, max_new_tokens=1)   # compile
    rn = generation.generate(model, ids, max_new_tokens=new)  # compile
    _ = np.asarray(rn._data)
    t0 = time.perf_counter()
    for _i in range(3):
        _ = np.asarray(generation.generate(model, ids, max_new_tokens=1)._data)
    prefill_s = (time.perf_counter() - t0) / 3
    t0 = time.perf_counter()
    for _i in range(3):
        _ = np.asarray(
            generation.generate(model, ids, max_new_tokens=new)._data)
    full_s = (time.perf_counter() - t0) / 3
    per_tok = max(full_s - prefill_s, 1e-9) / (new - 1)
    out["decode_prefill_ms"] = round(prefill_s * 1e3, 2)
    out["decode_per_token_ms"] = round(per_tok * 1e3, 3)
    out["decode_tokens_per_s"] = round(batch / per_tok, 1)
    del r1, rn

    # weight-only int8 serving path (its OWN prefill baseline — the bf16
    # prefill time would make the subtraction noise on small configs)
    wog1 = generation.WeightOnlyGenerator(model, max_new_tokens=1)
    wog = generation.WeightOnlyGenerator(model, max_new_tokens=new,
                                         share_weights_from=wog1)
    _ = np.asarray(wog1.generate(ids)._data)  # compile
    _ = np.asarray(wog.generate(ids)._data)   # compile
    t0 = time.perf_counter()
    for _i in range(3):
        _ = np.asarray(wog1.generate(ids)._data)
    q_prefill_s = (time.perf_counter() - t0) / 3
    t0 = time.perf_counter()
    for _i in range(3):
        _ = np.asarray(wog.generate(ids)._data)
    q_full_s = (time.perf_counter() - t0) / 3
    q_per_tok = max(q_full_s - q_prefill_s, 1e-9) / (new - 1)
    out["decode_int8_per_token_ms"] = round(q_per_tok * 1e3, 3)
    out["decode_int8_tokens_per_s"] = round(batch / q_per_tok, 1)
    out["decode_int8_weight_mb"] = round(wog.quantized_bytes() / 2**20, 1)
    del wog, wog1

    # continuous-batching engine (paged KV cache, iteration-level
    # scheduling — inference/serving.py): end-to-end tokens/s for a mixed
    # batch of requests, the serving-loop analog of the reference's
    # block_multihead_attention deployment. Measured as an A/B so the
    # fused-decode win is recorded, not claimed: decode_steps=1
    # reproduces the old step-per-token engine; decode_steps=K is the
    # fused scan with device-resident lane state + dispatch overlap.
    try:
        fused_k = 8
        new_eng = max(new, 193)  # decode-dominant mix: 192 fused tokens/req
        # (long enough that the CPU-proxy streams settle into the cyclic
        # tail the prompt-lookup drafter feeds on — the head of each
        # stream is chaotic and accepts nothing, like real free text)
        spec_d = 3
        base = _bench_engine_config(model, cfg, prompt, new_eng, batch, 1,
                                    compat=True)
        modern1 = _bench_engine_config(model, cfg, prompt, new_eng, batch, 1)
        fused = _bench_engine_config(model, cfg, prompt, new_eng, batch,
                                     fused_k)
        specarm = _bench_engine_config(model, cfg, prompt, new_eng, batch,
                                       fused_k, spec=True,
                                       draft_depth=spec_d)
        # judge the speculative arm against the default serving SLOs the
        # moment it finishes (same estimator as tools/slo_report.py);
        # the verdict rides inside the arm's A/B entry
        from paddle_tpu import observability as _sobs
        from paddle_tpu.observability import slo as _slo
        _e = _slo.SLOEngine()
        _e.observe(_sobs.snapshot(), t=0.0)
        v = _e.evaluate(emit=False)
        spec_slo = {"ok": v["ok"],
                    "failing": [s["name"] for s in v["slos"]
                                if not s["ok"]]}
        spec_q = _bench_engine_config(model, cfg, prompt, new_eng, batch,
                                      fused_k, spec=True,
                                      draft_depth=spec_d, kv_dtype="int8")
        # tuned arm: backoff-ladder drafter + per-workload depth from
        # inference/drafting.py — the acceptance delta vs the flat arm
        # above is the evidence the per-scenario statistics earn their
        # keep (PERF.md records the current numbers)
        from paddle_tpu.inference import drafting as _drafting
        tuned_stats = _drafting.SCENARIO_DRAFT_STATS["offline_batch"]
        tuned_fn = _drafting.backoff_drafter(tuned_stats["ngrams"])
        spec_tuned = _bench_engine_config(
            model, cfg, prompt, new_eng, batch, fused_k, spec=True,
            draft_depth=tuned_stats["depth"], drafter=tuned_fn)
        # round 18: suffix-automaton drafter at EQUAL depth vs the tuned
        # ladder — longest-match lookup should convert the repetitive
        # motif tail at least as well as the fixed (3,2) rungs
        suffix_fn = _drafting.suffix_drafter()
        spec_suffix = _bench_engine_config(
            model, cfg, prompt, new_eng, batch, fused_k, spec=True,
            draft_depth=tuned_stats["depth"], drafter=suffix_fn)
        # headline row = the production config (fused); the A/B keeps the
        # baseline next to it plus the overlap evidence per config. Three
        # arms decompose the win: the pre-fused host loop (re-upload +
        # host sync every token), device-resident state + overlap alone
        # (decode_steps=1), and the full fused K-step tile.
        out["engine_requests"] = fused["requests"]
        out["engine_tokens"] = fused["tokens"]
        out["engine_tokens_per_s"] = fused["tokens_per_s"]
        out["engine_decode_steps"] = fused_k
        out["engine_compile_cold_s"] = fused["compile_cold_s"]
        out["engine_compile_cache"] = fused["compile_cache"]
        out["engine_compile"] = fused["compile"]
        speed = (fused["tokens_per_s"] / base["tokens_per_s"]
                 if base["tokens_per_s"] else float("nan"))
        spec_speed = (specarm["tokens_per_s"] / fused["tokens_per_s"]
                      if fused["tokens_per_s"] else float("nan"))
        keys = ("tokens_per_s", "tpot_ms", "uploads", "dispatches",
                "hostsync_ms")
        skeys = keys + ("draft_tokens", "accepted_tokens", "acceptance")
        out["engine_ab"] = {
            "decode_steps=1": {k: base[k] for k in keys},
            "decode_steps=1+resident_state+overlap":
                {k: modern1[k] for k in keys},
            f"decode_steps={fused_k}": {k: fused[k] for k in keys},
            f"decode_steps={fused_k}+spec(d={spec_d})":
                {**{k: specarm[k] for k in skeys}, "slo": spec_slo},
            f"decode_steps={fused_k}+spec+int8kv":
                {k: spec_q[k] for k in skeys},
            (f"decode_steps={fused_k}+spec_tuned({tuned_fn.label},"
             f"d={tuned_stats['depth']})"):
                {k: spec_tuned[k] for k in skeys},
            (f"decode_steps={fused_k}+spec_suffix({suffix_fn.label},"
             f"d={tuned_stats['depth']})"):
                {k: spec_suffix[k] for k in skeys},
            "speedup": round(speed, 2),
            "spec_speedup": round(spec_speed, 2),
            # speculation must be invisible in the committed streams; the
            # int8-KV arm is exact-dequant too but its attention reads
            # round through int8, so it parity-checks against itself only
            "greedy_parity": (base["outputs"] == fused["outputs"]
                              == modern1["outputs"] == specarm["outputs"]
                              == spec_tuned["outputs"]
                              == spec_suffix["outputs"]),
        }
        # round 18: cross-request prefix cache, cold (cache off: every
        # prompt fully prefilled) vs warm (index pre-populated: only the
        # per-request tail prefills). One warm engine REUSED across the
        # warm-up and timed runs — the index must persist to be a cache.
        out["engine_prefix_ab"] = _bench_engine_prefix(model, cfg, batch)
        # close the telemetry loop: judge this run's TTFT/TPOT/finish mix
        # against the default serving SLOs with the same estimator
        # tools/slo_report.py uses; the verdict rides the bench row
        eng_slo = _slo.SLOEngine()
        eng_slo.observe(_sobs.snapshot(), t=0.0)
        out["engine_slo"] = eng_slo.evaluate()
        obs_dir = os.environ.get("BENCH_OBS_DIR")
        if obs_dir:     # drop the request-grouped Chrome trace too
            os.makedirs(obs_dir, exist_ok=True)
            out["engine_trace"] = _sobs.get_tracer().export_chrome_trace(
                os.path.join(obs_dir, "engine_trace.json"))
    except Exception as e:  # noqa: BLE001 — the arms below still run; the
        # *_error key makes secondary_worker exit non-zero
        out["engine_error"] = f"{type(e).__name__}: {str(e)[:2000]}"
    # round 19: auto-fusion A/B (fuse pass on/off over a llama-block
    # train step + a fused-decode step proxy); its own guard so one arm's
    # failure does not skip the others (each *_error fails the run)
    try:
        out["fusion_ab"] = _bench_fusion_ab()
    except Exception as e:  # noqa: BLE001
        out["fusion_ab_error"] = f"{type(e).__name__}: {str(e)[:2000]}"
    # round 22: multi-adapter (LoRA) serving A/B — the slot-0 identity
    # contract, the mixed-adapter throughput tax, and the
    # recompile-free hot-swap gate; its own guard like fusion_ab
    try:
        out["adapters_ab"] = _bench_engine_adapters(model, cfg, batch)
    except Exception as e:  # noqa: BLE001
        out["adapters_ab_error"] = f"{type(e).__name__}: {str(e)[:2000]}"
    return out


def _bench_engine_prefix(model, cfg, batch):
    """Round-18 prefix-cache A/B: a shared-prefix request mix (96-token
    tenant-common head + 4 distinct tail tokens per request) run on a
    cache-off engine (cold: full prefill per request) and on ONE warm
    prefix-cache engine whose index was populated by an untimed pass of
    the same mix. Warm admissions resolve the head from the index and
    prefill only the tail — with buckets (16, 112) that is a 16-wide
    tail chunk instead of the 112-wide full chunk, so both the
    prefill-token count and the wall clock move. Records the
    prefill-token reduction, the warm speedup, and cold-vs-warm greedy
    parity (the byte-identity contract, measured not claimed)."""
    import numpy as np
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine

    def ctr(name):
        fam = obs.get_registry().get(name)
        return fam.value if fam is not None else 0.0

    head_len, tail_len, new = 96, 4, 8
    s = head_len + tail_len
    n_req = batch * 3
    rng = np.random.RandomState(18)
    head = rng.randint(1, cfg.vocab_size, (head_len,))
    prompts = [np.concatenate(
        [head, rng.randint(1, cfg.vocab_size, (tail_len,))])
        for _ in range(n_req)]
    blocks_per_seq = (s + new) // 16 + 2

    def build(prefix_cache):
        return ContinuousBatchingEngine(
            model,
            num_blocks=batch * blocks_per_seq + head_len // 16 + 2,
            block_size=16, max_batch=batch,
            max_blocks_per_seq=blocks_per_seq,
            prefill_buckets=(16, 112), decode_steps=8,
            prefix_cache=prefix_cache)

    def timed(eng):
        done0 = frozenset(eng.finished)  # run() returns ALL-time finished
        for p in prompts:
            eng.add_request(p, max_new_tokens=new)
        saved0 = ctr("serving_prefix_tokens_saved_total")
        t0 = time.perf_counter()
        res = eng.run()
        dt = time.perf_counter() - t0
        saved = int(ctr("serving_prefix_tokens_saved_total") - saved0)
        outs = [v for rid, v in res.items() if rid not in done0]
        toks = sum(len(v) for v in outs)
        return {"tokens_per_s": round(toks / dt, 1),
                "prefill_tokens": n_req * s - saved,
                "tokens_saved": saved,
                "outputs": sorted(map(tuple, outs))}

    cold_eng = build(False)
    cold_eng.add_request(prompts[0], max_new_tokens=new)
    cold_eng.run()                  # compile outside the timed region
    cold = timed(cold_eng)
    warm_eng = build(True)
    for p in prompts:               # untimed pass: compiles + warms the
        warm_eng.add_request(p, max_new_tokens=new)     # prefix index
    warm_eng.run()
    warm = timed(warm_eng)
    parity = cold.pop("outputs") == warm.pop("outputs")
    return {
        "requests": n_req, "prompt_tokens": n_req * s,
        "shared_head_tokens": head_len,
        "cold": cold, "warm": warm,
        "prefill_token_reduction": round(
            cold["prefill_tokens"] / max(1, warm["prefill_tokens"]), 2),
        "warm_speedup": round(
            warm["tokens_per_s"] / max(cold["tokens_per_s"], 1e-9), 2),
        "greedy_parity": parity,
    }


def _bench_engine_adapters(model, cfg, batch):
    """Round-22 multi-adapter (LoRA) A/B, three legs on one request mix:

    * identity — the same all-base request mix on a storeless engine
      and on a store-attached engine (every lane adapter_id=0, the
      all-zeros slot). Greedy streams must be byte-identical: attaching
      the store may not perturb base serving.
    * mixed — the mix re-run with every request under an adapter
      (round-robin over 4 names, all within the 4-slot pool). Records
      the throughput ratio vs the base run on the SAME engine (the
      per-token cost of the batched per-lane delta gathers) and that
      the adapter streams actually differ from base.
    * hot-swap — all 8 registered adapters driven serially through the
      4-slot store, so every acquire past the first four LRU-evicts and
      hot-loads. ``jit_retrace_total`` over everything after warmup
      must stay exactly flat: adapter identity is data (a pool slot
      index), never part of a compile key."""
    import numpy as np
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine, make_demo_store
    from paddle_tpu.inference.loadgen import _counter_total

    def ctr(name):
        fam = obs.get_registry().get(name)
        return fam.value if fam is not None else 0.0

    s, new = 16, 24
    n_req = batch * 3
    rng = np.random.RandomState(22)
    prompts = [rng.randint(1, cfg.vocab_size, (s,)) for _ in range(n_req)]
    blocks_per_seq = (s + new) // 16 + 2

    def build(store):
        return ContinuousBatchingEngine(
            model, num_blocks=batch * blocks_per_seq + 4, block_size=16,
            max_batch=batch, max_blocks_per_seq=blocks_per_seq,
            prefill_buckets=(16,), decode_steps=8, adapters=store)

    def timed(eng, adapter_of):
        done0 = frozenset(eng.finished)
        for i, p in enumerate(prompts):
            a = adapter_of(i)
            eng.add_request(p, max_new_tokens=new,
                            **({"adapter": a} if a else {}))
        t0 = time.perf_counter()
        res = eng.run()
        dt = time.perf_counter() - t0
        outs = [v for rid, v in res.items() if rid not in done0]
        return {"tokens_per_s": round(sum(len(v) for v in outs) / dt, 1),
                "outputs": sorted(map(tuple, outs))}

    plain_eng = build(None)
    plain_eng.add_request(prompts[0], max_new_tokens=new)
    plain_eng.run()                 # compile outside the timed region
    plain = timed(plain_eng, lambda i: None)

    names = ["lora%d" % i for i in range(8)]
    store_eng = build(make_demo_store(model, names, n_slots=4))
    store_eng.add_request(prompts[0], max_new_tokens=new)
    store_eng.run()                 # compile (the lora-tailed programs)
    retrace0 = ctr("jit_retrace_total")
    snap0 = obs.snapshot()
    base = timed(store_eng, lambda i: None)
    timed(store_eng, lambda i: names[i % 4])   # untimed: hot-loads the
    mixed = timed(store_eng, lambda i: names[i % 4])    # 4 working set
    for nm in names:                # hot-swap: every slot churns
        store_eng.add_request(prompts[0], max_new_tokens=8, adapter=nm)
        store_eng.run()
    snap1 = obs.snapshot()
    swap_retraces = int(ctr("jit_retrace_total") - retrace0)
    loads = int(_counter_total(snap1, "serving_adapter_loads_total")
                - _counter_total(snap0, "serving_adapter_loads_total"))
    evictions = int(
        _counter_total(snap1, "serving_adapter_evictions_total")
        - _counter_total(snap0, "serving_adapter_evictions_total"))
    identity = plain["outputs"] == base["outputs"]
    differs = mixed["outputs"] != base["outputs"]
    ratio = mixed["tokens_per_s"] / max(base["tokens_per_s"], 1e-9)
    return {
        "requests": n_req, "adapters": len(names), "slots": 4,
        "base_tokens_per_s": base["tokens_per_s"],
        "mixed_tokens_per_s": mixed["tokens_per_s"],
        "mixed_vs_base": round(ratio, 2),
        "identity_parity": identity,
        "adapter_streams_differ": differs,
        "hot_swap_loads": loads,
        "hot_swap_evictions": evictions,
        "swap_recompiles": swap_retraces,
        "gate_ok": bool(identity and differs and swap_retraces == 0
                        and loads >= len(names) and evictions >= 4),
    }


def _bench_fusion_ab():
    """Round-19/23 auto-fusion A/B: three programs — a llama-block
    train step (rmsnorm + attention + gelu-MLP + residuals, fwd +
    weight grads), a fused-decode step proxy (block fwd + final
    rmsnorm + logits matmul + softmax/argmax tail), and a
    matmul-epilogue shape (dot → bias → gelu → residual → rmsnorm with
    the residual escaping as a second output — the fusion-v2
    epilogue-absorption + output-promotion showcase) — compiled
    through the PIR pipeline with the fuse pass on and off. Records
    committed groups (total and by provenance kind), predicted bytes
    saved (with the delta vs the round-19 single-output-planner
    baseline where one exists), and the warm wall ratio. Gate (CPU
    proxy, where XLA already fuses aggressively so the win is mostly
    predicted, not walled): fused <= 1.05x unfused, >= 1 committed
    group per program with bytes saved > 0, the train step strictly
    above its round-19 bytes-saved baseline, and at least one
    committed group of each v2 kind (multi_output, epilogue) across
    the arms."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework import flags as _flags
    from paddle_tpu.pir.pipeline import compile_flat

    rng = np.random.RandomState(0)
    S, D, F, V = 64, 128, 256, 512
    scale = np.float32(1.0 / np.sqrt(D))   # float32: a python-float
    # closure would capture a float64 constant the verifier rejects

    def rms(x, g):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * g

    def block(x, wq, wk, wv, wo, w1, w2, g1, g2):
        h = rms(x, g1)
        q, k, v = h @ wq, h @ wk, h @ wv
        a = jax.nn.softmax((q @ k.T) * scale, axis=-1)
        x = x + (a @ v) @ wo
        h = rms(x, g2)
        return x + jax.nn.gelu(h @ w1, approximate=False) @ w2

    p = [jnp.asarray(rng.randn(D, D) * 0.05, jnp.float32)
         for _ in range(4)]
    p += [jnp.asarray(rng.randn(D, F) * 0.05, jnp.float32),
          jnp.asarray(rng.randn(F, D) * 0.05, jnp.float32),
          jnp.asarray(rng.rand(D), jnp.float32),
          jnp.asarray(rng.rand(D), jnp.float32)]
    x = jnp.asarray(rng.randn(S, D), jnp.float32)
    we = jnp.asarray(rng.randn(D, V) * 0.05, jnp.float32)
    gf = jnp.asarray(rng.rand(D), jnp.float32)

    def llama_step(x_, *params):
        def loss(ps):
            out = block(x_, *ps)
            return jnp.mean(out * out)
        l, gs = jax.value_and_grad(loss)(tuple(params))
        return (l, *gs)

    def fused_decode(x_, we_, gf_, *params):
        h = rms(block(x_, *params), gf_)
        logits = h[-1:] @ we_
        probs = jax.nn.softmax(logits, axis=-1)
        return (jnp.argmax(probs, axis=-1), jnp.max(probs, axis=-1))

    def matmul_epilogue(x_, w_, b_, ge_):
        h = x_ @ w_ + b_
        a = jax.nn.gelu(h, approximate=True)
        y = a + x_
        return (rms(y, ge_), y)     # y escapes: promoted group output

    # big enough that real work (not dispatch) dominates the warm wall
    SE, DE = 256, 512
    xe = jnp.asarray(rng.randn(SE, DE), jnp.float32)
    we2 = jnp.asarray(rng.randn(DE, DE) * 0.05, jnp.float32)
    be = jnp.asarray(rng.randn(DE) * 0.05, jnp.float32)
    ge = jnp.asarray(rng.rand(DE), jnp.float32)

    # round-19 bytes-saved baselines (the single-output v1 planner, PR
    # 16 — PERF.md round-19 table); v2 must beat them where they exist
    baseline_r19 = {"llama_step": 2123272, "fused_decode": 1533488}

    programs = {
        "llama_step": (llama_step, [x, *p]),
        "fused_decode": (fused_decode, [x, we, gf, *p]),
        "matmul_epilogue": (matmul_epilogue, [xe, we2, be, ge]),
    }
    prev = _flags.flag_value("pir_passes")
    no_fuse = ",".join(s for s in prev.split(",") if s.strip() != "fuse")
    out = {"programs": {}}
    try:
        for name, (fn, args) in programs.items():
            _flags.set_flags({"pir_passes": no_fuse})
            off_fn, off_rep = compile_flat(fn, args,
                                           name=f"fusion_{name}_off")
            _flags.set_flags({"pir_passes": prev})
            on_fn, on_rep = compile_flat(fn, args, name=f"fusion_{name}")
            t_off, t_on, want, got = _time_jitted_pair(off_fn, on_fn, args)
            ok = all(np.allclose(np.asarray(w), np.asarray(g),
                                 rtol=2e-5, atol=2e-6)
                     for w, g in zip(want, got))
            ratio = t_on / max(t_off, 1e-9)
            row = {
                "unfused_s": round(t_off, 6),
                "fused_s": round(t_on, 6),
                "wall_ratio": round(ratio, 3),
                "fusion_groups": on_rep.fusion_groups,
                "fusion_kinds": dict(on_rep.fusion_kinds),
                "predicted_bytes_saved": on_rep.fusion_bytes_saved,
                "fallback": on_rep.fallback or off_rep.fallback,
                "numerics_ok": bool(ok),
                "gate_ok": bool(ok and on_rep.fusion_groups >= 1
                                and on_rep.fusion_bytes_saved > 0
                                and ratio <= 1.05),
            }
            base = baseline_r19.get(name)
            if base is not None:
                row["r19_bytes_saved"] = base
                row["bytes_saved_delta_vs_r19"] = \
                    on_rep.fusion_bytes_saved - base
                row["gate_ok"] = bool(
                    row["gate_ok"] and on_rep.fusion_bytes_saved > base)
            out["programs"][name] = row
    finally:
        _flags.set_flags({"pir_passes": prev})
    rows = out["programs"].values()
    out["fusion_groups_total"] = sum(r["fusion_groups"] for r in rows)
    kinds_total = {}
    for r in rows:
        for k, n in r["fusion_kinds"].items():
            kinds_total[k] = kinds_total.get(k, 0) + n
    out["fusion_kinds_total"] = kinds_total
    out["multi_output_groups_total"] = kinds_total.get("multi_output", 0)
    out["epilogue_groups_total"] = kinds_total.get("epilogue", 0)
    out["predicted_bytes_saved_total"] = sum(
        r["predicted_bytes_saved"] for r in rows)
    out["max_wall_ratio"] = max(r["wall_ratio"] for r in rows)
    out["gate_ok"] = bool(all(r["gate_ok"] for r in rows)
                          and out["multi_output_groups_total"] >= 1
                          and out["epilogue_groups_total"] >= 1)
    return out


def _bench_engine_config(model, cfg, prompt, new, batch, decode_steps,
                         compat=False, spec=False, draft_depth=4,
                         kv_dtype="bf16", drafter=None):
    """One engine A/B arm: fresh engine at the given decode_steps, same
    request mix (seeded), compile outside the timed region. Returns
    tokens/s plus the TPOT/host-sync/upload deltas for this arm (and the
    draft/accept split when the arm speculates)."""
    import numpy as np
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine

    def hist(name):
        fam = obs.get_registry().get(name)
        return (fam.sum, fam.count) if fam is not None else (0.0, 0)

    def ctr(name):
        fam = obs.get_registry().get(name)
        return fam.value if fam is not None else 0.0

    blocks_per_seq = (prompt + new) // 16 + 2
    eng = ContinuousBatchingEngine(
        model, num_blocks=batch * blocks_per_seq + 1,  # full batch + scratch
        block_size=16, max_batch=batch, max_blocks_per_seq=blocks_per_seq,
        prefill_buckets=(prompt,), decode_steps=decode_steps,
        compat_step_loop=compat, speculative_decode=spec,
        draft_depth=draft_depth, kv_cache_dtype=kv_dtype, drafter=drafter)
    n_req = batch * 3  # oversubscribed: exercises admission/retirement
    req_rng = np.random.RandomState(7)  # same mix in every arm
    # drafter-friendly mix: every prompt tiles the same short random
    # motif, a repetitive workload (think extraction/fill-in traffic)
    # whose greedy continuation settles into a cycle the prompt-lookup
    # drafter can latch onto. Acceptance is measured, not assumed; the
    # non-speculative arms run the same mix for parity.
    motif = req_rng.randint(0, cfg.vocab_size, (5,))
    prompts = [np.tile(motif, prompt // 5 + 1)[:prompt]
               for _ in range(n_req)]
    for p in prompts:
        eng.add_request(p, max_new_tokens=new)
    cache_before = _pir_cache_stats()
    t_cold = time.perf_counter()
    eng.step()  # compile prefill + decode outside the timed region
    eng._drain_all()  # the compile-laden first tile must not skew TPOT
    compile_cold_s = time.perf_counter() - t_cold
    pre_tokens = sum(len(r.generated) for r in eng.finished.values())
    pre_tokens += sum(len(r.generated) for r in eng.lanes if r is not None)
    tpot0, up0, disp0 = hist("serving_tpot_seconds"), \
        ctr("serving_lane_state_uploads_total"), \
        ctr("serving_decode_dispatches_total")
    sync0 = hist("serving_hostsync_seconds")
    draft0 = ctr("serving_draft_tokens_total")
    acc0 = ctr("serving_accepted_tokens_total")
    t0 = time.perf_counter()
    res = eng.run()
    dt = time.perf_counter() - t0
    tpot1, sync1 = hist("serving_tpot_seconds"), hist("serving_hostsync_seconds")
    total = sum(len(v) for v in res.values()) - pre_tokens
    d_tpot = ((tpot1[0] - tpot0[0]) / max(tpot1[1] - tpot0[1], 1))
    d_sync = ((sync1[0] - sync0[0]) / max(sync1[1] - sync0[1], 1))
    drafted = int(ctr("serving_draft_tokens_total") - draft0)
    accepted = int(ctr("serving_accepted_tokens_total") - acc0)
    spec_stats = {}
    if spec:
        spec_stats = {
            "draft_tokens": drafted, "accepted_tokens": accepted,
            "acceptance": round(accepted / drafted, 3) if drafted else 0.0,
        }
    return {
        **spec_stats,
        "requests": n_req, "tokens": total,
        "tokens_per_s": round(total / dt, 1),
        "tpot_ms": round(d_tpot * 1e3, 3),
        "hostsync_ms": round(d_sync * 1e3, 3),
        "uploads": int(ctr("serving_lane_state_uploads_total") - up0),
        "dispatches": int(ctr("serving_decode_dispatches_total") - disp0),
        "compile_cold_s": round(compile_cold_s, 3),
        "compile_cache": _pir_cache_delta(cache_before, _pir_cache_stats()),
        "compile": {k: getattr(r, "cache", None)
                    for k, r in eng.compile_reports.items() if r is not None},
        "outputs": sorted(map(tuple, res.values())),
    }


def _time_jitted(fn, args, repeats=7):
    """Min-of-N warm wall time of a compiled callable (min, not mean:
    scheduler noise only ever adds time)."""
    import time as _time

    import jax
    out = fn(*args)
    jax.tree_util.tree_map(
        lambda a: a.block_until_ready() if hasattr(a, "block_until_ready")
        else a, out)
    best = float("inf")
    for _ in range(repeats):
        t0 = _time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_map(
            lambda a: a.block_until_ready() if hasattr(a, "block_until_ready")
            else a, out)
        best = min(best, _time.perf_counter() - t0)
    return best, out


def _time_jitted_pair(fa, fb, args, repeats=9):
    """Interleaved min-of-N A/B wall time of two compiled callables over
    the same args. Alternating samples instead of two back-to-back
    min-of-N blocks: clock-frequency drift between the blocks would
    alias straight into the A/B ratio."""
    import time as _time

    import jax

    def _sync(out):
        jax.tree_util.tree_map(
            lambda a: a.block_until_ready() if hasattr(a, "block_until_ready")
            else a, out)
        return out

    out_a, out_b = _sync(fa(*args)), _sync(fb(*args))
    best_a = best_b = float("inf")
    for _ in range(repeats):
        t0 = _time.perf_counter()
        _sync(fa(*args))
        best_a = min(best_a, _time.perf_counter() - t0)
        t0 = _time.perf_counter()
        _sync(fb(*args))
        best_b = min(best_b, _time.perf_counter() - t0)
    return best_a, best_b, out_a, out_b


def _bench_multichip_sharding():
    """Manual vs auto sharding on a simulated >=4-device host mesh
    (MULTICHIP row; also graft leg 6): two captured programs — a
    llama-block train-step proxy (fwd+bwd) and a fused K-step decode
    proxy (scan) — each run under every hand-written GSPMD strategy
    via jit in_shardings, then through the PIR pipeline's cost-driven
    search + propagation. Records per-strategy step times, the search
    decision, numerics parity with the hand-annotated baseline, and
    auto/best-manual time ratios."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.pir import shard_prop
    from paddle_tpu.pir.pipeline import compile_flat

    devs = jax.devices()
    if len(devs) < 4:
        return {"skipped": f"need >=4 devices, have {len(devs)}"}
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "mp"))

    def named(spec_list):
        return [NamedSharding(mesh, P(*s)) for s in spec_list]

    rng = np.random.RandomState(0)

    # program 1: llama-block train-step proxy — loss fwd + weight grads
    # through the Megatron-shaped two-matmul block
    def train_step(x, w1, w2):
        def loss(w1_, w2_):
            return jnp.sum((jnp.tanh(x @ w1_) @ w2_) ** 2)
        l, (g1, g2) = jax.value_and_grad(loss, argnums=(0, 1))(w1, w2)
        return (l, g1, g2)

    xs = jnp.asarray(rng.randn(256, 512).astype(np.float32))
    w1 = jnp.asarray(rng.randn(512, 1024).astype(np.float32)) * 0.02
    w2 = jnp.asarray(rng.randn(1024, 512).astype(np.float32)) * 0.02
    step_args = [xs, w1, w2]
    step_strategies = {
        "replicated": [(None, None), (None, None), (None, None)],
        "dp": [("dp", None), (None, None), (None, None)],
        "tp": [(None, None), (None, "mp"), ("mp", None)],
        "dp+tp": [("dp", None), (None, "mp"), ("mp", None)],
    }

    # program 2: fused K-step decode proxy — the serving engine's
    # decode_steps=K scan shape (carry @ weight, K times)
    K = 8

    def fused_decode(x, w):
        def body(carry, _):
            return jnp.tanh(carry @ w), ()
        out, _ = jax.lax.scan(body, x, None, length=K)
        return (out,)

    dx = jnp.asarray(rng.randn(256, 512).astype(np.float32))
    dw = jnp.asarray(rng.randn(512, 512).astype(np.float32)) * 0.02
    decode_args = [dx, dw]
    decode_strategies = {
        "replicated": [(None, None), (None, None)],
        "dp": [("dp", None), (None, None)],
        "tp": [(None, None), (None, "mp")],
    }

    out = {"devices": 4, "mesh": "dp=2,mp=2"}
    programs = {}
    for name, fn, args, strategies in (
            ("llama_step", train_step, step_args, step_strategies),
            ("fused_decode", fused_decode, decode_args, decode_strategies)):
        want = fn(*args)
        manual_s = {}
        for sname, specs in strategies.items():
            t, got = _time_jitted(
                jax.jit(fn, in_shardings=named(specs)), args)
            manual_s[sname] = round(t, 6)
            ok = all(np.allclose(w, g, rtol=2e-4, atol=2e-5)
                     for w, g in zip(want, got))
            if not ok:
                manual_s[sname + "_numerics"] = "MISMATCH"
        space = [(n, s) for n, s in strategies.items()
                 if n != "replicated"]
        with shard_prop.mesh_scope(mesh, search=space):
            auto_fn, report = compile_flat(fn, args, name=f"mc_{name}")
            auto_t, got = _time_jitted(auto_fn, args)
        numerics_ok = all(np.allclose(w, g, rtol=2e-4, atol=2e-5)
                          for w, g in zip(want, got))
        best_manual = min(manual_s.values())
        programs[name] = {
            "manual_s": manual_s,
            "auto_s": round(auto_t, 6),
            "auto_decision": report.shard_decision,
            "auto_fallback": report.fallback,
            "numerics_ok": bool(numerics_ok),
            "auto_vs_best_manual": round(auto_t / best_manual, 3),
        }
    out["programs"] = programs
    out["max_auto_vs_best_manual"] = max(
        p["auto_vs_best_manual"] for p in programs.values())
    out["numerics_ok"] = all(p["numerics_ok"] for p in programs.values())
    return out


def multichip_worker(force_cpu: bool):
    """--secondary multichip: manual-vs-auto sharding sweep on >=4 devices
    (--cpu: 8 simulated host devices — the XLA preset must land before jax
    wakes up, hence a dedicated arm instead of a secondary_worker row)."""
    if force_cpu:
        flags_env = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags_env:
            os.environ["XLA_FLAGS"] = (
                flags_env
                + " --xla_force_host_platform_device_count=8").strip()
    on_tpu = _init_backend(force_cpu)
    detail = {}
    try:
        detail.update(_bench_multichip_sharding())
    except Exception as e:  # noqa: BLE001 — reported in the row, exit 1
        detail["multichip_error"] = f"{type(e).__name__}: {str(e)[:2000]}"
    if "skipped" in detail:
        detail["multichip_error"] = detail.pop("skipped")
    ratio = detail.get("max_auto_vs_best_manual", 0.0)
    print(json.dumps({"metric": _row_name("multichip_sharding", on_tpu),
                      "value": ratio,
                      "unit": "auto/best-manual step-time ratio",
                      "vs_baseline": 1.0 if detail.get("numerics_ok")
                      else 0.0,
                      "device": _device_info(),
                      "detail": detail}))
    return 1 if _failed_arms(detail) else 0


def secondary_worker(force_cpu: bool, which: str):
    """ResNet/BERT/decode secondary metrics (BASELINE rows 2-3 + serving).
    Arms are isolated so one failure does not skip the others; any
    *_error in the row makes the exit code non-zero."""
    on_tpu = _init_backend(force_cpu)
    from paddle_tpu import observability as _obs
    _obs.enable()   # serving TTFT/TPOT/queue metrics ride the decode row
    detail = {}
    benches = [("resnet", _bench_resnet), ("bert", _bench_bert),
               ("decode", _bench_decode)]
    for name, fn in benches:
        if which not in (name, "both"):
            continue
        try:
            detail.update(fn(on_tpu))
        except Exception as e:  # noqa: BLE001 — reported in the row, exit 1
            detail[f"{name}_error"] = f"{type(e).__name__}: {str(e)[:2000]}"
    failed = _failed_arms(detail)
    detail["metrics_snapshot"] = _obs.snapshot(
        meta={"which": which, "round": _current_round()})
    print(json.dumps({"metric": _row_name("secondary_models", on_tpu),
                      "value": 0.0 if failed else 1.0,
                      "unit": "detail", "vs_baseline": 0.0,
                      "device": _device_info(),
                      "detail": detail}))
    return 1 if failed else 0


def _mesh_scaling_rows(paddle, cfg, eng_kw, n_requests=16, max_new=16):
    """CPU-proxy mesh scaling evidence for the loadgen row: drive the
    SAME deterministic request set through (a) a 1-replica mesh, (b) a
    2-replica data-parallel mesh, (c) a 2-replica disaggregated
    (prefill + decode) mesh, and compare aggregate tok/s over the
    simulated-parallel wall (per-round max of in-process replica step
    walls — labeled simulated; nproc=1 serializes the real clock).
    Greedy streams must be byte-identical across all three topologies."""
    import numpy as np
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.mesh import MeshRouter, ReplicaPool
    from paddle_tpu.models.llama import LlamaForCausalLM

    def factory():
        paddle.seed(0)   # identical weights on every replica
        return ContinuousBatchingEngine(LlamaForCausalLM(cfg), **eng_kw)

    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size,
                           size=int(rng.randint(6, 14))).tolist()
               for _ in range(n_requests)]

    def drive(n, disaggregate, port):
        pool = ReplicaPool(factory, n=n, disaggregate=disaggregate,
                           store_port=port)
        router = MeshRouter(pool)
        # warm every replica's compiled programs (prefill bucket,
        # decode tile, lane upload, handoff import) so the measured
        # wall is steady-state serving, not per-replica compile
        for p in prompts[: 2 * n]:
            router.add_request(list(p), max_new_tokens=max_new)
        router.run()
        w0 = router.sim_parallel_wall_s
        c0 = sum(len(r.generated) for r in router.finished.values())
        for p in prompts:
            router.add_request(list(p), max_new_tokens=max_new)
        streams = router.run()
        rep = router.mesh_report()
        rep["measured_tokens"] = rep["committed_tokens"] - c0
        rep["measured_wall_s"] = rep["sim_parallel_wall_s"] - w0
        # measured streams only (warmup rids excluded) for identity
        measured = {rid: toks for rid, toks in streams.items()
                    if rid >= 2 * n}
        return measured, rep

    s1, r1 = drive(1, False, 47101)
    s2, r2 = drive(2, False, 47102)
    sd, rd = drive(2, True, 47103)

    def agg(rep):
        w = rep["measured_wall_s"]
        return rep["measured_tokens"] / w if w > 0 else 0.0

    def streams_eq(a, b):
        # mesh rids differ by warmup count across topologies; identity
        # is positional — i-th measured request, same prompt each time
        return list(a.values()) == list(b.values())

    t1, t2, td = agg(r1), agg(r2), agg(rd)
    return {
        "sim_parallel": True,   # nproc=1: wall is the simulated clock
        "requests": n_requests,
        "tokens": r1["measured_tokens"],
        "tok_per_s_1replica": round(t1, 1),
        "tok_per_s_2replica": round(t2, 1),
        "tok_per_s_2replica_disagg": round(td, 1),
        "speedup_2replica": round(t2 / t1, 3) if t1 > 0 else None,
        "speedup_disagg": round(td / t1, 3) if t1 > 0 else None,
        "dp_byte_identical": streams_eq(s2, s1),
        "disagg_byte_identical": streams_eq(sd, s1),
        "disagg_handoffs": rd["handoffs"],
    }


def loadgen_worker(force_cpu: bool, scenario="chat", seed=0):
    """--loadgen leg: drive the serving engine with a seeded traffic
    scenario (inference/loadgen.py, same harness as tools/loadgen.py)
    and emit goodput, p95 TTFT, the SLO verdict, and the profiler's
    phase-attribution coverage as one bench JSON row."""
    on_tpu = _init_backend(force_cpu)
    from paddle_tpu import observability as _obs
    _obs.enable()
    from paddle_tpu.profiler.phases import get_phase_accountant
    get_phase_accountant().enabled = True
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine, loadgen
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=8,
                          num_attention_heads=16,
                          max_position_embeddings=2048, dtype="bfloat16")
        eng_kw = dict(num_blocks=1024, block_size=16, max_batch=8,
                      prefill_buckets=(32, 64, 128), max_queue=256)
    else:
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4,
                          max_position_embeddings=256)
        eng_kw = dict(num_blocks=128, block_size=8, max_batch=4,
                      prefill_buckets=(16, 32), max_queue=64)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    # scheduler=True: the bench leg runs the closed SLO loop, so
    # check_report additionally gates brownout-recovered-to-0 and
    # known-finish-reasons on every row
    eng = ContinuousBatchingEngine(model, scheduler=True, **eng_kw)
    rep = loadgen.run_scenario(eng, scenario, seed=seed)
    problems = loadgen.check_report(rep)
    mesh_row = None
    if not on_tpu:
        # disaggregated-mesh scaling row (CPU proxy): 1 vs 2 replicas,
        # byte-identity + >=1.6x aggregate tok/s gates (RESILIENCE.md
        # mesh runbook)
        mesh_row = _mesh_scaling_rows(paddle, cfg, eng_kw)
        if not mesh_row["dp_byte_identical"]:
            problems.append("2-replica mesh streams diverge from the "
                            "1-replica reference")
        if not mesh_row["disagg_byte_identical"]:
            problems.append("disaggregated mesh streams diverge from the "
                            "1-replica reference")
        sp = mesh_row["speedup_2replica"]
        if sp is None or sp < 1.6:
            problems.append(f"2-replica mesh aggregate tok/s speedup "
                            f"{sp} < 1.6x over 1 replica")
    detail = {
        "scenario": rep["scenario"], "seed": rep["seed"],
        "schedule_digest": rep["schedule"]["digest"],
        "issued": rep["issued"], "finished": rep["finished"],
        "goodput": rep["goodput"], "goodput_rps": rep["goodput_rps"],
        "ttft_p95_s": rep["ttft"]["p95"], "tpot_p95_s": rep["tpot"]["p95"],
        "slo_ok": rep["slo"].get("ok"),
        "slo": [{k: r.get(k) for k in ("name", "ok", "observed",
                                       "burn_rate")}
                for r in rep["slo"].get("slos", [])],
        "attribution_coverage": rep["coverage"],
        "cost_ratio": rep["cost"]["ratio"],
        "headroom_floor": rep["headroom_floor"],
        "classes": rep.get("classes"),
        "brownout_level_end": rep.get("brownout_level_end"),
        "brownout_transitions": rep.get("brownout_transitions"),
        "preemptions": rep.get("preemptions"),
        "mesh_scaling": mesh_row,
        "check_problems": problems,
    }
    detail["metrics_snapshot"] = _obs.snapshot(
        meta={"which": "loadgen", "round": _current_round()})
    print(json.dumps({"metric": _row_name("loadgen_goodput", on_tpu),
                      "unit": "req/s",
                      "value": rep["goodput_rps"],
                      "vs_baseline": 1.0 if rep["slo"].get("ok") else 0.0,
                      "device": _device_info(),
                      "detail": detail}))
    return 0 if not problems else 1


def worker(force_cpu: bool, only_config: int | None = None):
    """Llama ladder arm: biggest config first, walking down past a config
    that raised (each is reported in skipped_configs and fails the run)."""
    on_tpu = _init_backend(force_cpu)
    from paddle_tpu import observability as _obs
    from paddle_tpu.models.llama import LlamaConfig

    # bench workers always run with telemetry ON: a bench row should be
    # self-describing hardware evidence (the timed regions themselves are
    # instrumented only via the post-hoc record_run, never per-step)
    _obs.enable()
    if on_tpu:
        ladder = _llama_ladder()
        if only_config is not None:
            ladder = ladder[only_config:only_config + 1]
    else:
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4, max_position_embeddings=256)
        ladder = [("llama_tiny_cpu", cfg, 2, 128, 3, False)]

    remat_policy = None
    if "--remat-policy" in sys.argv:
        remat_policy = sys.argv[sys.argv.index("--remat-policy") + 1]
    remat_override = None   # experiment knobs
    if "--remat" in sys.argv:
        remat_override = sys.argv[sys.argv.index("--remat") + 1] == "on"
    batch_override = None
    if "--batch" in sys.argv:
        batch_override = int(sys.argv[sys.argv.index("--batch") + 1])
    chunk_override = None
    if "--loss-chunk-mb" in sys.argv:
        chunk_override = int(sys.argv[sys.argv.index("--loss-chunk-mb") + 1])
    errors = []      # configs that raised (walked past)
    for name, cfg, batch, seq, steps, remat in ladder:
        if remat_override is not None:
            remat = remat_override
        if batch_override is not None:
            batch = batch_override
        chunk_mb = chunk_override if chunk_override is not None \
            else _loss_chunk_mb_for(name)
        try:
            r = _run_one(cfg, batch, seq, steps, remat, on_tpu,
                         remat_policy=remat_policy,
                         loss_chunk_mb=chunk_mb, run_name=name)
        except Exception as e:  # noqa: BLE001 — walk down; fails the run
            errors.append(f"{name}: {type(e).__name__}: {str(e)[:2000]}")
            continue
        tok_per_s = r["tokens_per_s"]
        n_params = r["n_params"]
        # training FLOPs: 6N per token + attention 12*L*h*s per token
        flops_per_token = (6.0 * n_params +
                           12.0 * cfg.num_hidden_layers * cfg.hidden_size * seq)
        achieved = flops_per_token * tok_per_s
        # which attention implementation this config actually ran (weak #3
        # r4: the ladder conflated flash and dense rows without labeling) —
        # computed from the REAL selection predicate (which since r6 is
        # the per-shape backend router), plus the router's own provenance
        # so every bench row says WHY its backend was chosen
        from paddle_tpu.nn.functional.attention import _use_pallas
        hd = cfg.hidden_size // cfg.num_attention_heads
        run_dtype = "bfloat16" if on_tpu else "float32"
        attn_backend = ("pallas_flash" if _use_pallas(
            (batch, seq, cfg.num_attention_heads, hd), hd, False,
            dtype=run_dtype, causal=True)
            else "xla_dense")
        bwd_mode = r["attention_bwd_used"]
        from paddle_tpu.ops.pallas.attention_router import route
        dec = route(batch * cfg.num_attention_heads, seq, seq, hd,
                    run_dtype, True)
        router_info = {"fwd": dec.fwd, "bwd": dec.bwd,
                       "source": dec.source,
                       "provenance": dec.provenance}
        detail = {"config": name, "tokens_per_s": round(tok_per_s, 1),
                  "params": n_params, "loss": round(r["loss"], 4),
                  "batch": batch, "seq": seq, "remat": remat,
                  "attention_backend": attn_backend,
                  "attention_bwd": bwd_mode,
                  "attention_router": router_info,
                  "lm_loss": r.get("lm_loss_path"),
                  # the full registry snapshot rides in the row: train
                  # telemetry + router decision counters, self-describing
                  # and round-trippable via observability.load_snapshot
                  "metrics_snapshot": _obs.snapshot(
                      meta={"config": name, "round": _current_round()})}
        if errors:
            detail["skipped_configs"] = errors
        if on_tpu:
            peak = detect_peak()
            mfu = achieved / peak
            if r.get("xla_flops_per_step"):
                # cross-check: XLA's own HLO flop count / measured step time
                detail["mfu_xla_costmodel"] = round(
                    r["xla_flops_per_step"] / r["step_time_s"] / peak, 4)
            print(json.dumps({
                "metric": "llama_train_mfu_1chip",
                "value": round(mfu, 4),
                "unit": "mfu_fraction",
                "vs_baseline": round(mfu / 0.38, 4),
                "device": _device_info(),
                "detail": detail,
            }))
        else:
            print(json.dumps({
                "metric": "llama_train_tokens_per_s_cpu_smoke",
                "value": round(tok_per_s, 1),
                "unit": "tokens/s",
                "vs_baseline": 0.0,
                "device": _device_info(),
                "detail": detail,
            }))
        return 1 if errors else 0
    print(json.dumps({
        "metric": _row_name("llama_train_mfu_1chip", on_tpu), "value": 0.0,
        "unit": "mfu_fraction", "vs_baseline": 0.0,
        "device": _device_info(),
        "error": "all ladder configs failed", "detail": {"errors": errors}}))
    return 1


def _current_round():
    """Round number from the driver's PROGRESS.jsonl heartbeat (None if
    unavailable) — stamped into each row's metrics snapshot."""
    try:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "PROGRESS.jsonl")
        last = None
        with open(path) as f:
            for line in f:
                if line.strip():
                    last = line
        obj = json.loads(last)
        return obj.get("round") if isinstance(obj, dict) else None
    except (OSError, TypeError, ValueError):
        return None


def _arg_after(flag, default=None):
    """The token following `flag` in sys.argv unless it is another flag."""
    i = sys.argv.index(flag)
    if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("-"):
        return sys.argv[i + 1]
    return default


def main():
    force_cpu = "--cpu" in sys.argv
    if "--loadgen" in sys.argv:
        # traffic-harness row — goodput, p95 TTFT, SLO verdict, attribution
        # coverage (see OBSERVABILITY.md load-testing runbook)
        return loadgen_worker(force_cpu,
                              scenario=_arg_after("--loadgen", "chat"))
    if "--secondary" in sys.argv:
        which = _arg_after("--secondary", "both")
        if which == "multichip":
            return multichip_worker(force_cpu)
        return secondary_worker(force_cpu, which=which)
    cfg = None
    if "--config" in sys.argv:
        cfg = int(sys.argv[sys.argv.index("--config") + 1])
    return worker(force_cpu, only_config=cfg)


if __name__ == "__main__":
    sys.exit(main())
