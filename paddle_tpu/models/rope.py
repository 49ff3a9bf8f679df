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

`norm_rope` is what both models call for q and for k: the per-head RMSNorm
(generation._rms), its weight and the rotation. Rotate-half is a rotation
by d / 2 lanes times a sine table whose first half carries the minus sign;
where the lanes of a head can be rotated whole (`_rotates_whole_lanes`: the
input says, no flag) the three are one pass over the projection in VMEM,
forward and backward (ops/pallas/rope_norm.py, `normrope_fwd` and
`normrope_bwd`, under a `custom_vjp` whose residuals are the raw projection
and the weight); every other call is the `jax.numpy` composition.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..generation import _rms
from ..ops.pallas import rope_norm as _kernels

__all__ = ["rope_frequencies", "apply_rope", "norm_rope"]


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


def _rotation_tables(positions, inv_freq, attention_factor):
    """cos and sin at positions 0..positions-1, (positions, d) float32,
    both scaled by `attention_factor`; sin with the first half's sign, so
    that rotate-half is x cos + roll(x, d / 2) sin."""
    freqs = jnp.arange(positions, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def apply_rope(x, inv_freq, attention_factor=1.0):
    """Rotate-half RoPE at positions 0..T-1 of x (batch, T, heads, d): the
    tables and the rotation in float32, cos and sin scaled by
    `attention_factor`, one rounding to x's type."""
    cos, sin = _rotation_tables(x.shape[1], inv_freq, attention_factor)
    x32 = x.astype(jnp.float32)
    rolled = jnp.roll(x32, x.shape[-1] // 2, axis=-1)
    return (x32 * cos[:, None, :] + rolled * sin[:, None, :]).astype(x.dtype)


def _rotates_whole_lanes(x, norm_weight):
    """Whether `norm_rope` of these operands runs the kernels: on a TPU, a
    head's lanes whole vector registers (head_dim a multiple of 128),
    bf16 or float32 throughout, and a row tile that divides the positions."""
    return (jax.default_backend() == "tpu"
            and x.shape[-1] % 128 == 0
            and x.dtype == norm_weight.dtype
            and x.dtype in (jnp.bfloat16, jnp.float32)
            and _kernels.row_tile(x.shape[1]) is not None)


def norm_rope(x, norm_weight, eps, inv_freq, attention_factor=1.0):
    """apply_rope(_rms(x, norm_weight, eps), inv_freq, attention_factor) of
    a projection x (batch, T, heads, d): the RMSNorm over each head's d
    entries, its weight (d,), the rotation at positions 0..T-1; float32
    arithmetic, rounded to x's type after the norm, after the weight and
    after the rotation."""
    if _rotates_whole_lanes(x, norm_weight):
        return _norm_rope_in_vmem(x, norm_weight, inv_freq, float(eps),
                                  float(attention_factor))
    return apply_rope(_rms(x, norm_weight, eps), inv_freq, attention_factor)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm_rope_in_vmem(x, norm_weight, inv_freq, eps, attention_factor):
    return _norm_rope_fwd(x, norm_weight, inv_freq, eps, attention_factor)[0]


def _as_rows(x):
    """(batch, T, heads, d) as the kernels' (tokens, heads x d)."""
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2] * x.shape[3])


def _norm_rope_fwd(x, norm_weight, inv_freq, eps, attention_factor):
    tables = _rotation_tables(x.shape[1], inv_freq, attention_factor)
    out = _kernels.forward(_as_rows(x), norm_weight, *tables, eps)
    return out.reshape(x.shape), (x, norm_weight, inv_freq)


def _norm_rope_bwd(eps, attention_factor, saved, g):
    x, norm_weight, inv_freq = saved
    tables = _rotation_tables(x.shape[1], inv_freq, attention_factor)
    dx, dw = _kernels.backward(_as_rows(x), norm_weight, *tables,
                               _as_rows(g), eps)
    return (dx.reshape(x.shape), dw.astype(norm_weight.dtype),
            jnp.zeros_like(inv_freq))


_norm_rope_in_vmem.defvjp(_norm_rope_fwd, _norm_rope_bwd)
