"""Prefill's share (%) of its compute roofline: the operations the traced
prefill programs' prompts need (harness/flops.py prefill_flops: matmuls,
causal attention, one head row) over the chip's peak bf16 rate, divided by
the traced prefill time. Compute-bound: at these widths a prefill chunk
does ~1000 operations per weight byte. params as prefill_per_ktoken."""

from readers import _prefill


def read(ctx, params):
    got = _prefill.traced(ctx, params)
    per = _prefill.per_padded_token(ctx) if got else None
    if not got or not per:
        return None
    secs, padded = got
    least = padded * per[1] / ctx.peaks["bf16_flops"]
    return 100.0 * least / secs
