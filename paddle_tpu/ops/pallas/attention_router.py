"""Which attention kernel a shape gets: the flash kernels or dense XLA.

reference capability: python/paddle/nn/functional/flash_attention.py's
sdp_kernel-style backend selection. One rule on the shape, in `route`;
every caller (nn.functional attention, generation and serving prefill, the
PIR sdpa pattern, chip_smoke.py) asks it and knows nothing else.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from ...framework import flags as _flags

__all__ = ["Decision", "route", "decision_log", "clear_routing_cache"]


@dataclasses.dataclass(frozen=True)
class Decision:
    """fwd/bwd: 'pallas' or 'xla', always equal (the backward of a flash
    forward is the flash backward). tiles: the flash_attention.Tiles the
    kernels take at this shape and grid_steps: each kernel's grid steps
    in one call, by kernel name, from flash_attention.tiles_for_shape,
    the resolver the kernels' own entry points use; there whichever way
    the choice went. why: the line of the rule that decided.
    visited_pair_share: query-key pairs the three kernels' sweeps visit at
    sub-block granularity (Tiles.visited_pairs, summed) over three times
    the pairs the mask leaves (flash_attention.band_pairs); 1.0 at best,
    None for a call that is not causal (nothing is skipped there)."""

    fwd: str
    bwd: str
    tiles: Any
    grid_steps: dict
    why: str
    visited_pair_share: Optional[float] = None


# The one constant of the rule, from tools/flash_vs_xla.py on a TPU v5e
# (PR 30, chip call 48; table in PERF.md section 6), forward and forward +
# backward against dense XLA attention: the kernels win every row with
# seq_q >= 512 but one (1.09-10x; head dim 64, 96 and 128, causal and not,
# bf16 and float32, batch*heads 8-128, seq_k up to 4096; the rows at seq
# 1024-4096 repeat PR 27's, and both benchmark cells and both end-to-end
# A/Bs lie there) and lose every row with seq_q <= 256 (dense 2.2-6x
# faster: the kernels' smallest tile is 128 rows and a grid step costs what
# these whole problems cost). The one: seq_q 512 against seq_k 4096 at
# batch*heads 8, forward a tie and forward + backward 1.4x to dense.
# Nothing else in the shape moved the winner, so nothing else is read.
_FLASH_MIN_SEQ_Q = 512

_decisions: dict[tuple, Decision] = {}


def clear_routing_cache():
    _decisions.clear()


def decision_log():
    """[((batch_heads, seq_q, seq_k, head_dim, dtype, causal, window),
    Decision)] for every distinct shape this process asked about; window
    is None where there is none or it hides nothing at the shape."""
    return [(key[:7], dec) for key, dec in _decisions.items()]


def route(batch_heads: int, seq_q: int, seq_k: int, head_dim: int, dtype,
          causal: bool, platform: Optional[str] = None,
          window: Optional[int] = None) -> Decision:
    """The attention backend for one shape. batch_heads = batch * query
    heads (the kernels' parallel grid axis). platform defaults to the
    live jax backend; tests and chip_smoke.py name one they are not on.
    window: keys a query sees under the causal mask, its own included
    (flash_attention.effective_window). It is in the key, the tiles and
    the counts, and not in the choice: a band is a shorter sweep of the
    same kernels, not an arbitrary mask."""
    import jax
    import jax.numpy as jnp
    from .autotune import autotune_enabled   # it changes Decision.tiles
    from .flash_attention import (band_pairs, effective_window,
                                  tiles_for_shape)
    dtype = jnp.dtype(dtype).name
    platform = platform or jax.default_backend()
    forced = _flags.flag_value("flash_attention_backend")
    window = effective_window(window, causal, seq_k)
    key = (batch_heads, seq_q, seq_k, head_dim, dtype, bool(causal), window,
           platform, forced, autotune_enabled())
    if key in _decisions:
        return _decisions[key]
    if forced == "xla":
        backend, why = "xla", "FLAGS_flash_attention_backend=xla"
    elif platform != "tpu":
        backend, why = "xla", f"the kernels are TPU programs: {platform}"
    elif forced == "pallas":
        backend, why = "pallas", "FLAGS_flash_attention_backend=pallas"
    elif seq_q >= _FLASH_MIN_SEQ_Q:
        backend, why = "pallas", f"seq_q >= {_FLASH_MIN_SEQ_Q}"
    else:
        backend, why = "xla", f"seq_q < {_FLASH_MIN_SEQ_Q}"
    tiles = tiles_for_shape(batch_heads, seq_q, seq_k, head_dim, dtype,
                            causal, window)
    share = None
    if causal:
        visited = tiles.visited_pairs(seq_q, seq_k, window)
        share = sum(visited.values()) / (
            3.0 * max(band_pairs(seq_q, seq_k, window), 1))
    dec = Decision(fwd=backend, bwd=backend, tiles=tiles,
                   grid_steps=tiles.grid_steps(batch_heads, seq_q, seq_k,
                                               window),
                   why=why, visited_pair_share=share)
    _decisions[key] = dec
    return dec
