"""Rotary position embedding by kind: what one `rope_parameters` entry of a
Hugging Face config.json gives, and the rotation.

`rope_frequencies` returns (inv_freq, attention_factor) of the kinds
`default` and `yarn` (transformers' modeling_rope_utils: the default kind
is theta^(-2i/d); `_compute_yarn_parameters` blends interpolated and
extrapolated frequencies over a linear ramp between two correction
dimensions and scales cos and sin by the attention factor). Tables and the
rotation are float32, rounded once to the operand's type: tables rounded
first (incubate's fused_rotary_position_embedding) are, at 16k positions
in bf16, a second rounding of every rotated entry (tests/test_brumby.py
holds both to a float64 rotation). The callers enter the scope the work
carries on a device trace, tables included: `pt.attn.pos` (models/mellum.py),
`pt.retn.pos` (models/brumby.py).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

__all__ = ["rope_frequencies", "apply_rope"]


def _yarn_correction_range(params, head_dim):
    """(low, high): the rotary dimensions between which YaRN's ramp runs,
    from the rotations a dimension makes over the original context:
    floor / ceil of d ln(L / (2 pi beta)) / (2 ln theta) at beta_fast /
    beta_slow, clamped to [0, d - 1] (the published entries' 18 and 35 at
    d 128 lie inside d / 2 - 1 too)."""
    theta = float(params["rope_theta"])
    original = float(params["original_max_position_embeddings"])

    def dim_of(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = math.floor(dim_of(float(params.get("beta_fast", 32))))
    high = math.ceil(dim_of(float(params.get("beta_slow", 1))))
    return max(low, 0), min(high, head_dim - 1)


def rope_frequencies(params, head_dim):
    """(inv_freq float32 (head_dim / 2,), attention_factor float) of one
    `rope_parameters` entry: {"rope_type": "default" | "yarn",
    "rope_theta", and for yarn "factor",
    "original_max_position_embeddings", "beta_fast", "beta_slow",
    "attention_factor" (default 0.1 ln(factor) + 1)}."""
    kind = params.get("rope_type", "default")
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    pos_freqs = float(params["rope_theta"]) ** exponents
    if kind == "default":
        return 1.0 / pos_freqs, 1.0
    if kind != "yarn":
        raise NotImplementedError(f"rope_type {kind!r}: default or yarn")
    factor = float(params["factor"])
    attention_factor = params.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    low, high = _yarn_correction_range(params, head_dim)
    if low == high:
        high += 0.001           # as published: no division by zero
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp           # 1: the dimension extrapolates untouched
    return (interpolation * (1.0 - keep) + extrapolation * keep,
            float(attention_factor))


def apply_rope(x, inv_freq, attention_factor=1.0):
    """Rotate-half RoPE at positions 0..T-1 of x (batch, T, heads, d): the
    tables and the rotation in float32, cos and sin scaled by
    `attention_factor`, one rounding to x's type."""
    freqs = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * cos + rot * sin).astype(x.dtype)
