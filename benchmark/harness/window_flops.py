"""Operations of causal attention under a window, from shapes. The
conventions are harness/flops.py's: a multiply-add is 2 operations, a
query-key pair costs 4 x head_dim operations a head in the forward (QK^T
and PV), the backward twice that (dQ, dK, dV from the same pairs;
recomputed scores are not credited), and only the pairs the mask LEAVES
are counted: a kernel that visits more of the triangle gets no credit for
it.

Query t of a sequence sees keys t - window + 1 .. t (`window` keys with
its own, the Hugging Face sliding-window mask); `window` None is the
causal triangle of harness/flops.py.
"""

from __future__ import annotations


def band_pairs(seq, window=None):
    """Query-key pairs of one head over one sequence of `seq` tokens."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    # the first `window` queries see 1, 2, ... window keys; the rest window
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def window_fwd_flops(heads, head_dim, seq, window=None):
    """One layer's forward over one sequence."""
    return 4.0 * heads * head_dim * band_pairs(seq, window)


def window_bwd_flops(heads, head_dim, seq, window=None):
    """One layer's backward over one sequence."""
    return 2.0 * window_fwd_flops(heads, head_dim, seq, window)
