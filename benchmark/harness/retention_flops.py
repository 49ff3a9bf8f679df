"""Operations of one power-retention layer (degree 2, arXiv:2507.04239),
from shapes. The conventions are harness/flops.py's: a multiply-add is 2
operations, training is 3 x the forward (recomputation is not credited),
the quadratic form is counted CAUSAL.

The layer can be computed in two forms, and a program may mix them (a
chunked scan does): the count is THE SMALLER of the two, so that it is the
same work whatever the program does and a share of a peak taken from it
can only fall short.

`shapes` is what families/brumby.py shapes() returns: retention_heads,
retention_state_heads, head_dim, retention_features. The features counted
are the MINIMAL symmetric expansion's, head_dim (head_dim + 1) / 2 (8,256
at 128), whatever layout the program expands into (ops/power_retention.py
uses 8,320).
"""

from __future__ import annotations


def minimal_features(head_dim):
    return head_dim * (head_dim + 1) // 2


def quadratic_fwd_flops(shapes, seq):
    """q . k and a v for every query head and every causal pair: 2 x
    head_dim operations each."""
    return (4.0 * shapes["retention_heads"] * shapes["head_dim"]
            * seq * (seq + 1) / 2.0)


def recurrent_fwd_flops(shapes, seq):
    """Per token: phi(k) v^T into each state head's state and phi(q)
    against it for each query head, features x head_dim multiply-adds
    each. The expansion itself, the decay and the normaliser are
    elementwise or matrix-vector work and count nothing."""
    return (float(seq)
            * (shapes["retention_state_heads"] + shapes["retention_heads"])
            * 2.0 * minimal_features(shapes["head_dim"])
            * shapes["head_dim"])


def retention_fwd_flops(shapes, seq):
    """One layer's forward over one sequence of `seq` tokens."""
    return min(quadratic_fwd_flops(shapes, seq),
               recurrent_fwd_flops(shapes, seq))


def retention_train_flops(shapes, seq):
    """One layer, one sequence, forward and backward."""
    return 3.0 * retention_fwd_flops(shapes, seq)
