"""The yardstick of the looped-decoder cells, counted from shapes alone
as ``counts.py`` counts the others: what a step *has to* do.  The stack
of L layers is applied R times, so there are R * L layer applications
and R exits a step; operations that rematerialisation runs a second
time do not count.  Each function has a hand-worked value in
``tests/benchmark/test_looped_cell.py``.
"""

from __future__ import annotations


def causal_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                           applications: int = 1) -> float:
    """Forward and backward of causal scaled-dot-product attention
    proper (QK^T and PV, no projections): 2 matmuls forward, 4 backward,
    each 2*b*heads*s*s*d FLOPs over the whole square, of which a causal
    mask needs half: 6*b*heads*s*s*d per application."""
    return 6.0 * batch * heads * seq * seq * head_dim * applications


def looped_step_flops(batch: int, seq: int, hidden: int, layers: int,
                      heads: int, head_dim: int, ffn: int, vocab: int,
                      passes: int) -> float:
    """Matmul FLOPs of one training step, forward + backward (3x the
    forward, 6 per parameter and token): per layer application QKV
    (h x 3*heads*d), the output projection (heads*d x h) and the three
    feed-forward matrices (h x ffn); per pass the untied head (h x
    vocab) at every position; and causal attention proper.  The
    embedding look-up, the norms, the gated activation, the softmax and
    the exit gate (h x 1) are not counted."""
    tokens = batch * seq
    per_layer = 4 * hidden * heads * head_dim + 3 * hidden * ffn
    dense = 6.0 * tokens * passes * (layers * per_layer + hidden * vocab)
    return dense + causal_attention_flops(batch, heads, seq, head_dim,
                                          passes * layers)


def xent_bytes(tokens: int, vocab: int, passes: int,
               logit_bytes: int = 4) -> float:
    """Bytes the cross-entropies of one step have to move over the
    logits: per pass the forward reads them once, the backward reads
    them and writes their gradient.  Labels, losses and the row
    statistics are a vocabulary's width smaller and not counted."""
    return 3.0 * passes * tokens * vocab * logit_bytes
