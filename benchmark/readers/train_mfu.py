"""Model FLOP/s utilization (%) of the training window: the operations
forward and backward need per token (harness/flops.py
train_flops_per_token: 6 per matmul parameter with the output head,
attention counted causal, recomputation not credited) times the measured
tokens per second, over chips times the peak bf16 rate."""

from harness import flops


def read(ctx, params):
    rate = ctx.samples.get("tokens_per_s")
    if not rate:
        return None
    per_token = flops.train_flops_per_token(ctx.samples["shapes"],
                                            ctx.samples["seq"])
    return 100.0 * per_token * rate / (ctx.samples["chips"]
                                       * ctx.peaks["bf16_flops"])
