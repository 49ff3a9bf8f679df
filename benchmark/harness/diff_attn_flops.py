"""Operations of the differential attention layers' flash calls, from
shapes. The conventions are harness/flops.py's and harness/
window_flops.py's: a multiply-add is 2 operations, the backward twice the
forward (dQ, dK, dV from the same pairs; recomputed scores are not
credited), and only the pairs the masks LEAVE are counted.

A differential layer (models/phi4flash.py) makes two softmax maps a
differential head: every query head's scores against its key head at
qk_dim lanes, and every query head's map times its value pair at v_dim
lanes. A pair of positions then costs 2 x query_heads x qk_dim operations
for the scores and 2 x query_heads x v_dim for the values, at 40 x 64 and
40 x 128 (2 x 20 differential heads x 128) in the published model. The
zero lanes a program pads q and k with to v's width are not credited.

`shapes` is what families/phi4flash.py shapes() returns: diff_windows (one
entry a differential layer, None for a causal one), diff_query_heads,
diff_qk_dim, diff_v_dim.
"""

from __future__ import annotations

from harness import window_flops


def diff_fwd_flops(shapes, seq):
    """Every differential layer's forward over one sequence."""
    per_pair = 2.0 * shapes["diff_query_heads"] * (shapes["diff_qk_dim"]
                                                   + shapes["diff_v_dim"])
    return per_pair * sum(window_flops.band_pairs(seq, w)
                          for w in shapes["diff_windows"])


def diff_bwd_flops(shapes, seq):
    """Every differential layer's backward over one sequence."""
    return 2.0 * diff_fwd_flops(shapes, seq)
