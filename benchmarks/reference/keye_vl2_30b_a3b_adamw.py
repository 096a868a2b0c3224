"""Plain reference for ``keye_vl2_30b_a3b_adamw``: the language decoder
of a sparse-attention expert model (grouped-query attention over the
keys a learned indexer selects, then one holder's share of a dropless
top-k expert layer), its loss and gradients, and AdamW, in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision.
No kernels, no grouped products, no loss scaling, no buckets, and
nothing imported from the program.

The equations (the configuration's ``assumed`` says where each comes
from):

    h' = h  + Attn(RMSNorm(h)),   h'' = h' + MoE(RMSNorm(h'))
    loss = mean cross-entropy(W_head RMSNorm_f(h_L)) + weight * sum_l L_I

    x = RMSNorm(h);  [q | k | v] = x W_qkv  (H, HK, HK heads of d)
    q, k <- RMSNorm per head, rotary over the whole head
    x~ = stop_gradient(x);  [q^I | k^I | w] = x~ W_I  (Hi heads of Di, one, Hi)
    k^I <- LayerNorm(k^I);  rotary on q^I, k^I
    I[t, s] = sum_j w[t, j] Hi^-1/2 Di^-1/2 relu(q^I[t, j] . k^I[s])
    S_t = lax.top_k's min(topk, t + 1) best keys s <= t
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s] / sqrt(d)) v[s]
    p[t, s] = stop_gradient(mean_h softmax_{S_t}(...)[s])
    L_I = mean_t sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(I[t])[s])

    r = softmax(x W_r) over all E experts;  E_t = top-k;  g = r / sum_{E_t} r
    y[t] = sum_{e in E_t, e held} g[t, e] W_down^e(silu(W_gate^e x) * W_up^e x)

Only the held experts ``expert_offset .. expert_offset + held`` exist
here (``held`` is the leading size of the expert matrices); what the
others would add is left out, as in the program, and that partial
result goes on to the next layer.  AdamW as apex's ``FusedAdam`` states
it (``adam_w_mode``): global gradient-norm clip over all leaves first,
bias-corrected moments, decoupled weight decay on every leaf.

Memory: the step runs layer by layer (forward keeps each layer's input,
backward re-runs one under ``jax.vjp``), attention with its indexer in
blocks of query rows and the logits in blocks of token rows.

``precision`` other than ``"f32"`` is for the control: every matmul
operand is rounded to that type before a float32 product.
``sizes["indexer_topk"]``, ``sizes["num_experts_per_tok"]`` and
``sizes["index_loss_weight"]`` are read here, so this model's own
faults (selection left out, top-7 for top-8, the indexer's objective
left out) are this reference with one of them changed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import (HIGHEST, as_floats, diff_norms, norms,
                                         rounder, unzip)

BLOCK = 512           # query rows of attention / token rows of the head


# ---- what the configuration's sizes mean ----------------------------------

def param_spec(sizes: dict) -> dict:
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hi, di = sizes["indexer_num_heads"], sizes["indexer_head_dim"]
    held, f = sizes["num_experts"], sizes["moe_intermediate_size"]
    w = ("normal", sizes.get("initializer_range", 0.02))
    ones, zeros = ("ones",), ("zeros",)

    def norm(n=h):
        return {"weight": ((n,), ones)}

    table = ("normal", sizes.get("embedding_initializer_range", 1.0))
    spec = {"embed": {"weight": ((sizes["vocab_size"], h), table)},
            "final_norm": norm(),
            "head": ((h, sizes["vocab_size"]), w)}
    for i in range(sizes["num_hidden_layers"]):
        spec[f"layer_{i}"] = {
            "attn_norm": norm(),
            "attn_qkv": {"weight": ((h, (heads + 2 * kv) * d), w)},
            "q_norm": norm(d), "k_norm": norm(d),
            "index_proj": {"weight": ((h, hi * di + di + hi), w)},
            "index_k_norm": {"weight": ((di,), ones), "bias": ((di,), zeros)},
            "attn_proj": {"weight": ((heads * d, h), w)},
            "mlp_norm": norm(),
            "moe": {"router": ((h, sizes["router_num_experts"]), w),
                    "gate_up": ((held, h, 2 * f), w),
                    "down": ((held, f, h), w)}}
    return spec


# ---- arithmetic -------------------------------------------------------------

def _rms_norm(x, p, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * p["weight"]


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["weight"] + p["bias"]


def _rotary(x, theta):
    """x (b, s, heads, d): rotate-half rotary positions over all d."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _blocks(n):
    blk = min(BLOCK, n)
    if n % blk:
        raise ValueError(f"{n} rows do not divide into blocks of {blk}")
    return blk


def index_scores(qi, ki, w):
    """I (b, t, s) for a block of queries: qi (b, t, Hi, Di), ki
    (b, s, Di), w (b, t, Hi) with the scale in it."""
    sc = jnp.einsum("btjd,bsd->btjs", qi, ki, precision=HIGHEST)
    return jnp.einsum("btjs,btj->bts", jax.nn.relu(sc), w,
                      precision=HIGHEST)


def select(scores, seen, topk):
    """``lax.top_k``'s set among the causal keys as a boolean mask:
    scores (b, t, s), seen (t, s) the causal pairs."""
    b, t, s = scores.shape
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), min(topk, s))
    mask = jnp.zeros((b, t, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None],
        idx].set(True)
    return mask & seen


def _sparse_attention(q, k, v, qi, ki, w, *, topk, rnd):
    """(b, s, heads, d) q, (b, s, kv, d) k and v, the indexer's qi
    (b, s, Hi, Di), ki (b, s, Di), w (b, s, Hi) -> (context (b, s,
    heads * d), L_I), a block of query rows at a time (each block
    rematerialised in the backward pass)."""
    b, s, heads, d = q.shape
    rep = heads // k.shape[2]
    k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
    blk = _blocks(s)
    cols = jnp.arange(s)

    @jax.checkpoint
    def rows(args):
        qb, qib, wb, first = args
        seen = cols[None, :] <= (first + jnp.arange(blk))[:, None]
        scores = index_scores(rnd(qib), rnd(ki), wb)
        chosen = jax.lax.stop_gradient(select(scores, seen, topk))
        main = jnp.einsum("bqhd,bkhd->bhqk", rnd(qb), rnd(k),
                          precision=HIGHEST) / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(chosen[:, None], main, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v),
                         precision=HIGHEST)
        p = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
        logq = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf),
                                  axis=-1)
        some = chosen & (p > 0)
        kl = jnp.where(some, p * (jnp.log(jnp.where(some, p, 1.0))
                                  - jnp.where(some, logq, 0.0)), 0.0)
        return out, jnp.sum(kl, axis=-1)

    def split(x):
        return x.reshape((b, s // blk, blk) + x.shape[2:]).swapaxes(0, 1)

    out, kl = jax.lax.map(rows, (split(q), split(qi), split(w),
                                 jnp.arange(0, s, blk)))
    out = out.swapaxes(0, 1).reshape(b, s, heads * d)
    return out, jnp.mean(kl.swapaxes(0, 1).reshape(b, s))


def route(x, router, top_k, norm_topk_prob, rnd):
    """x (T, h) -> (gates (T, k), experts (T, k)) over ALL experts."""
    r = jax.nn.softmax(jnp.matmul(rnd(x), rnd(router), precision=HIGHEST),
                       axis=-1)
    gates, experts = jax.lax.top_k(r, top_k)
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts


def moe(p, x, *, top_k, offset=0, norm_topk_prob=True, rnd=lambda x: x):
    """The held experts' part of the expert layer, x (T, h): every held
    expert applied to every token under a dense mask."""
    gates, experts = route(x, p["router"], top_k, norm_topk_prob, rnd)
    y = jnp.zeros_like(x)
    for e in range(p["gate_up"].shape[0]):
        g = jnp.sum(jnp.where(experts == offset + e, gates, 0.0), axis=-1)
        gate, up = jnp.split(jnp.matmul(rnd(x), rnd(p["gate_up"][e]),
                                        precision=HIGHEST), 2, axis=-1)
        y = y + g[:, None] * jnp.matmul(rnd(jax.nn.silu(gate) * up),
                                        rnd(p["down"][e]), precision=HIGHEST)
    return y


def _layer(p, x, *, sizes, rnd):
    b, s, h = x.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    hi, di = sizes["indexer_num_heads"], sizes["indexer_head_dim"]
    theta = float(sizes["rope_theta"])

    def lin(x, q):
        return jnp.matmul(rnd(x), rnd(q["weight"]), precision=HIGHEST)

    xn = _rms_norm(x, p["attn_norm"], eps)
    q, k, v = jnp.split(lin(xn, p["attn_qkv"]),
                        [heads * d, (heads + kv) * d], axis=-1)
    q = _rotary(_rms_norm(q.reshape(b, s, heads, d), p["q_norm"], eps), theta)
    k = _rotary(_rms_norm(k.reshape(b, s, kv, d), p["k_norm"], eps), theta)
    qi, ki, w = jnp.split(lin(jax.lax.stop_gradient(xn), p["index_proj"]),
                          [hi * di, hi * di + di], axis=-1)
    qi = _rotary(qi.reshape(b, s, hi, di), theta)
    ki = _rotary(_layer_norm(ki, p["index_k_norm"], eps)[:, :, None], theta)
    ctx, l_index = _sparse_attention(
        q, k, v.reshape(b, s, kv, d), qi, ki[:, :, 0],
        w * (hi ** -0.5 * di ** -0.5), topk=sizes["indexer_topk"], rnd=rnd)
    x = x + lin(ctx, p["attn_proj"])
    y = moe(p["moe"], _rms_norm(x, p["mlp_norm"], eps).reshape(b * s, h),
            top_k=sizes["num_experts_per_tok"],
            offset=sizes.get("expert_offset", 0),
            norm_topk_prob=sizes.get("norm_topk_prob", True), rnd=rnd)
    return x + y.reshape(b, s, h), l_index


def _head(p, x, labels, *, eps, rnd):
    """Mean cross-entropy of the untied head over the final norm's
    output, the logits a block of token rows at a time."""
    flat = _rms_norm(x, p["final_norm"], eps).reshape(-1, x.shape[-1])
    blk = _blocks(flat.shape[0])

    @jax.checkpoint
    def rows(args):
        xb, yb = args
        logits = jnp.matmul(rnd(xb), rnd(p["head"]), precision=HIGHEST)
        picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    losses = jax.lax.map(rows, (flat.reshape(-1, blk, flat.shape[-1]),
                                labels.reshape(-1, blk)))
    return jnp.mean(losses)


class _Programs:
    """The few jitted pieces, compiled once per shape: every layer has
    the same shapes, so all of them run through two programs."""

    def __init__(self, sizes: dict, precision: str):
        rnd = rounder(precision)
        layer = functools.partial(_layer, sizes=sizes, rnd=rnd)
        head = functools.partial(_head, eps=sizes["rms_norm_eps"], rnd=rnd)
        self.layer = jax.jit(layer)
        self.head = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))

        @jax.jit
        def layer_bwd(p, x, dy, dl):
            return jax.vjp(layer, p, x)[1]((dy, dl))

        @jax.jit
        def embed_bwd(table, tokens, dx):
            return jnp.zeros_like(table).at[tokens].add(dx)

        self.layer_bwd, self.embed_bwd = layer_bwd, embed_bwd


def loss_and_grads(progs: _Programs, params: dict, tokens, labels,
                   sizes: dict):
    """-> (loss, language loss, sum of L_I, gradients)."""
    weight = jnp.float32(sizes.get("index_loss_weight", 1.0))
    n_layers = sizes["num_hidden_layers"]
    h = params["embed"]["weight"][tokens]
    inputs, l_index = [], jnp.float32(0.0)
    for i in range(n_layers):
        inputs.append(h)
        h, l_i = progs.layer(params[f"layer_{i}"], h)
        l_index = l_index + l_i
    top = {"final_norm": params["final_norm"], "head": params["head"]}
    lm, (g_top, dh) = progs.head(top, h, labels)
    grads = dict(g_top)
    for i in reversed(range(n_layers)):
        grads[f"layer_{i}"], dh = progs.layer_bwd(
            params[f"layer_{i}"], inputs.pop(), dh, weight)
    grads["embed"] = {"weight": progs.embed_bwd(
        params["embed"]["weight"], tokens, dh)}
    return lm + weight * l_index, lm, l_index, grads


# ---- AdamW ------------------------------------------------------------------

@jax.jit
def _sumsq(tree):
    return sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(tree))


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(p, g, m, v, t, clip, hyper):
    b1, b2 = hyper["beta1"], hyper["beta2"]

    def leaf(p, g, m, v):
        g = g * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                   + hyper["eps"])
        return p - hyper["lr"] * (u + hyper["weight_decay"] * p), m, v

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
    change after the last step, both as norms by leaf.  ``index_losses``
    beside them is each step's sum of L_I (printed, not compared)."""
    hyper = {k: jnp.float32(optimizer[k]) for k in
             ("lr", "beta1", "beta2", "eps", "weight_decay")}
    max_norm = float(optimizer["max_grad_norm"])
    progs = _Programs(sizes, precision)
    start = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": [], "index_losses": []}
    for t, (tokens, labels) in enumerate(batches, start=1):
        loss, _, l_index, grads = loss_and_grads(progs, params, tokens,
                                                 labels, sizes)
        out["losses"].append(float(loss))
        out["index_losses"].append(float(l_index))
        gnorm = float(jnp.sqrt(sum(_sumsq(grads[k]) for k in grads)))
        clip = jnp.float32(min(1.0, max_norm / (gnorm + 1e-6))
                           if max_norm > 0 else 1.0)
        for k in list(params):                            # group by group
            params[k], m[k], v[k] = _adamw(params[k], grads.pop(k), m[k],
                                           v[k], jnp.float32(t), clip, hyper)
        if t == 1:
            out["grad1"] = as_floats(norms(m),
                                     1.0 / (1.0 - optimizer["beta1"]))
    out["change"] = as_floats(diff_norms(params, start))
    return out
