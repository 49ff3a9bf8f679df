"""Bytes the selective scan's kernels must move, from shapes. The scan
(ops/pallas/selective_scan.py: `selscan_fwd`, `selscan_bwd`) is
elementwise work along the sequence and the chip's published peaks give
no rate for its vector unit, so its roofline is the memory system's: the
least bytes over the peak HBM bandwidth.

Counted for one layer and one sequence of `seq` positions, each operand
read once and each result written once, in the type the kernels'
interface gives it (`itemsize` for the (seq, channels) arrays, float32 for
the rest), recomputation not credited:

    forward   reads u, delta, z (seq x channels), A (channels x state),
              B, C (seq x state), D and delta's bias (channels); writes
              the gated output (seq x channels) and the chunk states
              (seq / 128 x state x channels, float32)
    backward  reads u, delta, z, the output's cotangent, A, B, C, D, the
              bias and the chunk states; writes du, d delta, dz (seq x
              channels), dA, dB, dC, dD and d bias

`shapes` is what families/phi4flash.py shapes() returns: sel_layers,
sel_channels, sel_state, sel_itemsize.
"""

from __future__ import annotations

CHUNK = 128         # positions a chunk state stands before


def _parts(shapes, seq):
    e, n = shapes["sel_channels"], shapes["sel_state"]
    big = seq * e * shapes["sel_itemsize"]
    small = 4 * (e * n + 2 * seq * n + 2 * e)       # A, B, C, D, the bias
    states = 4 * (seq // CHUNK) * n * e
    return big, small, states


def forward_bytes(shapes, seq):
    big, small, states = _parts(shapes, seq)
    return 4 * big + small + states


def backward_bytes(shapes, seq):
    big, small, states = _parts(shapes, seq)
    return 7 * big + 2 * small + states


def train_bytes(shapes, seq):
    """One layer, one sequence, forward and backward."""
    return forward_bytes(shapes, seq) + backward_bytes(shapes, seq)
