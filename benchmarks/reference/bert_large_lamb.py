"""Plain reference for ``bert_large_lamb``: BERT's encoder with a tied
masked-LM head, its loss and gradients, and LAMB, in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision.  No kernels, no
loss scaling, no buckets, and nothing imported from the program.

It follows the published model (Devlin et al., arXiv:1810.04805:
post-LayerNorm residuals, learned positions, tanh-GELU) with the
departures of ``apex_tpu.models.bert``, which it has to match to be a
reference for it: the fused QKV projection is laid out per head
(``[q_0 k_0 v_0 q_1 ...]``), there are no token-type embeddings on this
path, LayerNorm's epsilon is 1e-5, and the MLM head is the tied
embedding alone (no transform layer, no output bias).  LAMB is
arXiv:1904.00962 as apex's ``FusedLAMB`` states it: global gradient-norm
clip to 1.0 first, bias-corrected moments, decoupled weight decay inside
the update, trust ratio ||p||/||update|| per tensor.

Memory: the step runs layer by layer (forward keeps each layer's input,
backward re-runs one layer under ``jax.vjp``), so that BERT-Large at b8
s512 fits beside float32 weights, gradients and two moments.

``precision`` other than ``"f32"`` is for the control: every matmul
operand is rounded to that type (with a per-tensor scale for fp8) before
a float32 product, which is what computing the model in that type does
to its numbers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import (HIGHEST, as_floats, diff_norms, norms,
                                         rounder, unzip)

LN_EPS = 1e-5


# ---- what the configuration's sizes mean ----------------------------------

def param_spec(sizes: dict) -> dict:
    h, ffn = sizes["hidden_size"], sizes["intermediate_size"]
    w = ("normal", sizes.get("initializer_range", 0.02))
    ones, zeros = ("ones",), ("zeros",)

    def ln():
        return {"bias": ((h,), zeros), "weight": ((h,), ones)}

    def lin(i, o):
        return {"bias": ((o,), zeros), "weight": ((i, o), w)}

    spec = {"embed": {"weight": ((sizes["padded_vocab_size"], h), w)},
            "embed_layernorm": ln(),
            "pos_embedding": ((sizes["max_position_embeddings"], h), w)}
    for i in range(sizes["num_hidden_layers"]):
        spec[f"layer_{i}"] = {
            "attn_layernorm": ln(), "attn_proj": lin(h, h),
            "attn_qkv": lin(h, 3 * h), "mlp_fc1": lin(h, ffn),
            "mlp_fc2": lin(ffn, h), "mlp_layernorm": ln()}
    return spec


# ---- arithmetic -------------------------------------------------------------

def _layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["weight"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _embed(p, tokens):
    """p: embed, embed_layernorm, pos_embedding -> (b, s, h)."""
    s = tokens.shape[1]
    x = p["embed"]["weight"][tokens] + p["pos_embedding"][:s][None]
    return _layer_norm(x, p["embed_layernorm"])


def _layer(p, x, *, heads: int, rnd):
    b, s, h = x.shape
    d = h // heads

    def lin(x, q):
        return jnp.matmul(rnd(x), rnd(q["weight"]),
                          precision=HIGHEST) + q["bias"]

    qkv = lin(x, p["attn_qkv"]).reshape(b, s, heads, 3 * d)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k),
                        precision=HIGHEST) / math.sqrt(d)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v),
                     precision=HIGHEST).reshape(b, s, h)
    x = _layer_norm(x + lin(ctx, p["attn_proj"]), p["attn_layernorm"])
    y = _gelu_tanh(lin(x, p["mlp_fc1"]))
    return _layer_norm(x + lin(y, p["mlp_fc2"]), p["mlp_layernorm"])


def _head_loss(table, x, labels, *, rnd):
    """Mean cross-entropy of the tied head over every position."""
    logits = jnp.matmul(rnd(x), rnd(table).T, precision=HIGHEST)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


class _Programs:
    """The few jitted pieces, compiled once per shape: every layer has
    the same shapes, so 24 layers run through two programs."""

    def __init__(self, heads: int, precision: str):
        rnd = rounder(precision)
        layer = functools.partial(_layer, heads=heads, rnd=rnd)
        head = functools.partial(_head_loss, rnd=rnd)
        self.embed = jax.jit(_embed)
        self.layer = jax.jit(layer)
        self.head = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))

        @jax.jit
        def layer_bwd(p, x, dy):
            return jax.vjp(layer, p, x)[1](dy)

        @jax.jit
        def embed_bwd(p, tokens, dx):
            return jax.vjp(lambda q: _embed(q, tokens), p)[1](dx)[0]

        self.layer_bwd, self.embed_bwd = layer_bwd, embed_bwd


def loss_and_grads(progs: _Programs, params: dict, tokens, labels,
                   n_layers: int):
    top = {k: params[k] for k in ("embed", "embed_layernorm",
                                  "pos_embedding")}
    xs = [progs.embed(top, tokens)]
    for i in range(n_layers):
        xs.append(progs.layer(params[f"layer_{i}"], xs[-1]))
    loss, (d_table, dx) = progs.head(params["embed"]["weight"], xs.pop(),
                                     labels)
    grads = {}
    for i in reversed(range(n_layers)):
        grads[f"layer_{i}"], dx = progs.layer_bwd(
            params[f"layer_{i}"], xs.pop(), dx)
    g_top = progs.embed_bwd(top, tokens, dx)
    g_top["embed"]["weight"] = g_top["embed"]["weight"] + d_table
    grads.update(g_top)
    return loss, grads


# ---- LAMB -------------------------------------------------------------------

@jax.jit
def _sumsq(tree):
    return sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(tree))


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _lamb(p, g, m, v, t, clip, hyper):
    b1, b2 = hyper["beta1"], hyper["beta2"]

    def leaf(p, g, m, v):
        g = g * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                   + hyper["eps"])
        u = u + hyper["weight_decay"] * p
        pn, un = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(u * u))
        trust = jnp.where((pn > 0) & (un > 0), pn / un, 1.0)
        trust = jnp.where(hyper["weight_decay"] == 0.0, 1.0, trust)
        return p - hyper["lr"] * trust * u, m, v

    ps, treedef = jax.tree_util.tree_flatten(p)
    outs = [leaf(*x) for x in zip(ps, *(jax.tree_util.tree_leaves(t)
                                        for t in (g, m, v)))]
    return unzip(treedef, outs, 3)


def follow(params: dict, batches, sizes: dict, optimizer: dict,
           precision: str = "f32") -> dict:
    """Train from ``params`` (float32, consumed) over ``batches``
    (``(tokens, labels)`` each) and return what the comparison reads:
    each step's loss, the first gradient as the optimizer got it
    (clipped; the first moment over 1 - beta1) and the parameters'
    change after the last step, both as norms by leaf."""
    hyper = {k: jnp.float32(optimizer[k]) for k in
             ("lr", "beta1", "beta2", "eps", "weight_decay")}
    max_norm = float(optimizer["max_grad_norm"])
    n_layers = sizes["num_hidden_layers"]
    progs = _Programs(sizes["num_attention_heads"], precision)
    groups = list(params)                 # update group by group
    start = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": []}
    for t, (tokens, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grads(progs, params, tokens, labels,
                                     n_layers)
        out["losses"].append(float(loss))
        gnorm = float(jnp.sqrt(sum(_sumsq(grads[k]) for k in groups)))
        clip = jnp.float32(max_norm / gnorm
                           if max_norm > 0 and gnorm > max_norm else 1.0)
        for k in groups:
            params[k], m[k], v[k] = _lamb(params[k], grads.pop(k), m[k],
                                          v[k], jnp.float32(t), clip, hyper)
        if t == 1:
            out["grad1"] = as_floats(norms(m),
                                     1.0 / (1.0 - optimizer["beta1"]))
    out["change"] = as_floats(diff_norms(params, start))
    return out
