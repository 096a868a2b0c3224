"""The yardstick of the hybrid Mamba-2 / expert-decoder cells, counted
from shapes alone as ``counts.py`` counts the others: what a step *has
to* do.  The SSD scan is counted as its chunked form at chunks of 128
computes it, whatever implements it; attention over its causal pairs;
the non-gated experts over the assignments the routing is expected to
send here; operations that rematerialisation runs a second time do not
count.  Each function has a hand-worked value in
``tests/benchmark/test_nemotron_cell.py``.
"""

from __future__ import annotations

from benchmarks.counts_kda import mla_attention_flops

CHUNK = 128


def scan_flops(batch: int, heads: int, groups: int, seq: int, head_dim: int,
               state: int, layers: int = 1, chunk: int = CHUNK) -> float:
    """Matmul FLOPs of the chunked SSD scan, forward and backward.  A
    chunk of Q positions, forward: C B^T once a group (2 Q^2 N), and a
    head's M x (2 Q^2 P), C S^T and the state update x^T B (2 Q N P
    each).  Backward, each product's two pullbacks: dM = dy x^T and
    M^T dy, dC from dy S and dS from (C e)^T dy, dB and d(x w) from the
    state's, dC and dB from dCB: twice the forward."""
    chunks = batch * (-(-seq // chunk)) * layers
    q, n, p = chunk, state, head_dim
    forward = groups * 2 * q * q * n + heads * (2 * q * q * p + 4 * q * n * p)
    return 3.0 * chunks * forward


def scan_bytes(batch: int, heads: int, groups: int, seq: int, head_dim: int,
               state: int, layers: int = 1, act_bytes: int = 2,
               step_bytes: int = 4) -> float:
    """Bytes the scan has to move: forward, read x, B and C (the model's
    activations) and dt and the running sum L (float32, a head) and
    write y; backward, read the same and dy and write the gradients of
    x, B, C, dt and L."""
    pos = batch * seq * layers
    inputs = pos * ((heads * head_dim + 2 * groups * state) * act_bytes
                    + 2 * heads * step_bytes)
    out = pos * heads * head_dim * act_bytes
    return 2.0 * inputs + 2.0 * out + inputs


def mamba_mixer_params(hidden: int, heads: int, head_dim: int, groups: int,
                       state: int) -> int:
    """Matmul parameters of a Mamba-2 mixer: in_proj to [z | xBC | dt]
    and out_proj."""
    inner = heads * head_dim
    return hidden * (2 * inner + 2 * groups * state + heads) + inner * hidden


def attention_params(hidden: int, heads: int, kv_heads: int,
                     head_dim: int) -> int:
    return hidden * head_dim * (2 * heads + 2 * kv_heads)


def expert_flops(tokens: int, hidden: int, width: int, top_k: int, held: int,
                 router_width: int, layers: int = 1) -> float:
    """Forward and backward of the non-gated held experts (up and down,
    2 hidden x width a token) over the assignments an unbiased router
    is expected to send here: tokens x top_k x held / router_width."""
    assignments = tokens * top_k * held / router_width
    return 6.0 * assignments * 2 * hidden * width * layers


def step_flops(batch: int, seq: int, hidden: int, kinds, *, heads: int,
               head_dim: int, groups: int, state: int, attn_heads: int,
               kv_heads: int, attn_head_dim: int, width: int,
               shared_width: int, top_k: int, held: int, router_width: int,
               vocab: int) -> float:
    """Matmul FLOPs of one training step, forward + backward (6 a
    parameter and token): the mixers' and attention's projections, the
    routers, the shared experts, the expected assignments' share of the
    held experts, the untied head, the scan and attention proper.
    ``kinds``: the pattern's letter of each layer.  Convolutions, norms,
    gates and softmaxes are not counted."""
    tokens = batch * seq
    n_m, n_a, n_e = (sum(1 for k in kinds if k == c) for c in "M*E")
    params = (hidden * vocab
              + n_m * mamba_mixer_params(hidden, heads, head_dim, groups,
                                         state)
              + n_a * attention_params(hidden, attn_heads, kv_heads,
                                       attn_head_dim)
              + n_e * (hidden * router_width + 2 * hidden * shared_width))
    return (6.0 * tokens * params
            + expert_flops(tokens, hidden, width, top_k, held, router_width,
                           n_e)
            + scan_flops(batch, heads, groups, seq, head_dim, state, n_m)
            + mla_attention_flops(batch, attn_heads, seq, attn_head_dim,
                                  attn_head_dim, n_a))
