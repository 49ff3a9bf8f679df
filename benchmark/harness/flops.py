"""Operations and bytes the algorithms need, from shapes. The yardstick's
arithmetic: no PR that claims a gain can change it.

Conventions, stated once:
- a multiply-add is 2 operations;
- training needs 3 x the forward's matmul operations (forward, and the
  backward's two products per matmul); recomputation is NOT credited;
- attention is counted CAUSAL: a query at position t needs t + 1 keys, so
  a sequence of s tokens needs s * (s + 1) / 2 query-key pairs, half of
  the full square. A kernel that computes the masked half does not get
  credit for it;
- `shapes` is what a family's loader returns: layers, hidden, heads,
  kv_heads, head_dim, ffn, vocab, matmul_params_per_layer, head_params.
  The embedding lookup is a gather and counts no operations; the output
  head is a matmul and counts (also where it is tied to the embedding).
"""

from __future__ import annotations


def attention_pairs_causal(seq):
    return seq * (seq + 1) / 2.0


def attention_fwd_flops(shapes, seq):
    """One layer's causal attention forward over one sequence: QK^T and PV,
    2 * head_dim operations each per query-key pair and head."""
    return (4.0 * shapes["heads"] * shapes["head_dim"]
            * attention_pairs_causal(seq))


def train_flops_per_token(shapes, seq):
    """Forward + backward operations per trained token at sequence length
    `seq`: 6 per matmul parameter (layers and head), plus 3 x the causal
    attention forward."""
    n = shapes["layers"] * shapes["matmul_params_per_layer"] \
        + shapes["head_params"]
    attn = 3.0 * shapes["layers"] * attention_fwd_flops(shapes, seq) / seq
    return 6.0 * n + attn


def prefill_flops(shapes, prompt_len):
    """Operations to prefill one prompt: every layer's matmuls for every
    token, causal attention over the prompt, and the head for the one
    position whose logits are needed."""
    return (2.0 * shapes["layers"] * shapes["matmul_params_per_layer"]
            * prompt_len
            + shapes["layers"] * attention_fwd_flops(shapes, prompt_len)
            + 2.0 * shapes["head_params"])


def weight_bytes(shapes, bytes_per_param=2):
    """Bytes of the matmul weights one decode step has to read once."""
    return bytes_per_param * (shapes["layers"]
                              * shapes["matmul_params_per_layer"]
                              + shapes["head_params"])


def kv_bytes_per_token(shapes, bytes_per_value=2):
    """Bytes of cached keys and values one context token holds."""
    return (2 * shapes["layers"] * shapes["kv_heads"] * shapes["head_dim"]
            * bytes_per_value)


def decode_step_bytes(shapes, live_context_tokens):
    """Least bytes one decode step moves: the weights once, and the keys
    and values of every live context token once."""
    return weight_bytes(shapes) + kv_bytes_per_token(shapes) \
        * live_context_tokens
