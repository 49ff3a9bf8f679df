"""Operations and bytes of the routed experts' grouped matmuls, from shapes
and the number of rows routing sent to the experts held here. The
conventions are harness/flops.py's: a multiply-add is 2 operations,
training is 3 x the forward (recomputation is not credited), and bytes are
the least the algorithm has to move, whatever blocks the program cuts its
tokens into.

`shapes` is what families/granite_hybrid.py shapes() returns: hidden,
expert_ffn (one routed expert's width), experts_held.
"""

from __future__ import annotations


def grouped_matmul_train_flops(shapes, rows):
    """One layer, one step: x W_in (hidden -> 2 x expert_ffn, gate and up)
    and act W_out (expert_ffn -> hidden) over `rows` assignments, forward
    and the backward's two products each."""
    per_row = 2.0 * shapes["hidden"] * 3 * shapes["expert_ffn"]
    return 3.0 * rows * per_row


def grouped_matmul_train_bytes(shapes, rows, bytes_per_value=2):
    """One layer, one step: the held experts' matrices read in the forward,
    read again for the rows' gradient and written once as their own
    gradient; the rows of x, of the gate-and-up product, of the activation
    and of the output, each moved once in each of the three passes."""
    weights = shapes["experts_held"] * 3 * shapes["hidden"] \
        * shapes["expert_ffn"]
    row = 2 * shapes["hidden"] + 3 * shapes["expert_ffn"]
    return 3.0 * bytes_per_value * (weights + rows * row)
