"""The yardstick of the hybrid linear-attention expert-decoder cells,
counted from shapes alone as ``counts.py`` counts the others: what a
step *has to* do.  The delta-rule scan is counted as its chunked form at
chunks of 64 computes it, whatever implements it; latent attention over
its causal pairs at the score width and the value width; the experts
over the assignments the routing is expected to send here; operations
that rematerialisation runs a second time do not count.  Each function
has a hand-worked value in ``tests/benchmark/test_kimi_cell.py``.
"""

from __future__ import annotations

from benchmarks.counts_moe import causal_pairs, expert_flops

CHUNK = 64


def scan_flops(batch: int, heads: int, seq: int, dk: int, dv: int,
               layers: int = 1, chunk: int = CHUNK) -> float:
    """Matmul FLOPs of the chunked gated delta rule, forward and
    backward (twice the forward).  A chunk of C positions a head,
    forward: the two intra-chunk products (k k^T and q k^T, half a
    C x C each at dk: 2 C^2 dk), the forward solve for [W_k | W_v]
    (C^2 (dk + dv)), M U (C^2 dv) and three products with the dk x dv
    state (W_k S, q S and the update's k^T U: 6 C dk dv)."""
    chunks = batch * heads * (-(-seq // chunk)) * layers
    c = chunk
    forward = (2 * c * c * dk + c * c * (dk + dv) + c * c * dv
               + 6 * c * dk * dv)
    return 3.0 * chunks * forward


def scan_bytes(batch: int, heads: int, seq: int, dk: int, dv: int,
               layers: int = 1, act_bytes: int = 2,
               gate_bytes: int = 4) -> float:
    """Bytes the scan has to move: forward, read q, k, v (the model's
    activations), g (float32, a channel) and beta (float32, a head) and
    write o; backward, read the same and do and write the gradients of
    q, k, v, g and beta."""
    pos = batch * heads * seq * layers
    inputs = pos * ((2 * dk + dv) * act_bytes + dk * gate_bytes
                    + gate_bytes)
    out = pos * dv * act_bytes
    return 2.0 * inputs + 2.0 * out + inputs


def mla_attention_flops(batch: int, heads: int, seq: int, dk: int, dv: int,
                        layers: int = 1) -> float:
    """Forward and backward of causal attention proper with scores at
    dk and values at dv: QK^T and PV forward (2 dk + 2 dv a pair and
    head), twice that backward (dQ, dK; dP, dV)."""
    return 6.0 * batch * heads * (dk + dv) * causal_pairs(seq) * layers


def kda_mixer_params(hidden: int, heads: int, head_dim: int) -> int:
    """Matmul parameters of a KDA mixer: q, k, v, o, the decay's and the
    output gate's two low-rank factors (rank head_dim), beta's."""
    width = heads * head_dim
    return (4 * hidden * width + 2 * (hidden * head_dim + head_dim * width)
            + hidden * heads)


def mla_mixer_params(hidden: int, heads: int, kv_lora_rank: int,
                     nope: int, rope: int, dv: int) -> int:
    return (hidden * heads * (nope + rope) + hidden * (kv_lora_rank + rope)
            + kv_lora_rank * heads * (nope + dv) + heads * dv * hidden)


def step_flops(batch: int, seq: int, hidden: int, kinds, heads: int,
               kda_heads: int, kda_head_dim: int, kv_lora_rank: int,
               nope: int, rope: int, dv: int, dense_width: int, width: int,
               top_k: int, held: int, router_width: int, shared: int,
               vocab: int) -> float:
    """Matmul FLOPs of one training step, forward + backward (6 a
    parameter and token): the mixers' projections, the dense layer, the
    routers, the shared experts, the expected assignments' share of the
    held experts, the untied head, the scan and latent attention.
    ``kinds``: (mixer, dense) a layer.  Convolutions, norms, gates and
    softmaxes are not counted."""
    tokens = batch * seq
    params = hidden * vocab
    n_kda = sum(1 for m, _ in kinds if m == "kda")
    n_mla = len(kinds) - n_kda
    n_moe = sum(1 for _, dense in kinds if not dense)
    params += n_kda * kda_mixer_params(hidden, kda_heads, kda_head_dim)
    params += n_mla * mla_mixer_params(hidden, heads, kv_lora_rank, nope,
                                       rope, dv)
    params += (len(kinds) - n_moe) * 3 * hidden * dense_width
    params += n_moe * (hidden * router_width + 3 * hidden * width * shared)
    return (6.0 * tokens * params
            + expert_flops(tokens, hidden, width, top_k, held, router_width,
                           n_moe)
            + scan_flops(batch, kda_heads, seq, kda_head_dim, kda_head_dim,
                         n_kda)
            + mla_attention_flops(batch, heads, seq, nope + rope, dv, n_mla))
