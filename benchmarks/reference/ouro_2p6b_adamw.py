"""Plain reference for ``ouro_2p6b_adamw``: a looped decoder (one stack
of layers applied R times to its own output with the same weights, an
exit after each pass), its loss and gradients, and AdamW, in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision.
No kernels, no loss scaling, no buckets, no scan over passes, and
nothing imported from the program.

The equations (arXiv:2510.25741 and the model's published code; what
the configuration's ``assumed`` lists is marked *):

    h = E[tokens]
    for t in 1..R:                                  # the same weights
        for l in 1..L:
            a = h + RMSNorm( Attn( RMSNorm(h) ) )   # * sandwich norms
            h = a + RMSNorm( W_down( silu(W_gate u) * (W_up u) ) ),
                                                    #   u = RMSNorm(a)
        x_t = RMSNorm_f(h);  h = x_t                # * closes each pass
        l_t = cross_entropy(W_head x_t, labels)     # per token
        lam_t = sigmoid(w_g . x_t + b_g)            # * the exit gate
    p_t = lam_t prod_{j<t}(1 - lam_j) (t < R);  p_R = prod_{j<R}(1 - lam_j)
    loss = mean_tokens( sum_t p_t l_t - beta * H(p) )        # * beta

Attention is causal, 16 heads of 128, rotary positions (rotate-half
over the whole head, theta 1e6), scale d^-0.5, no bias anywhere.  To
match the program's parameters the fused projections are laid out as it
lays them out: ``attn_qkv`` per head (``[q_0 k_0 v_0 q_1 ...]``),
``mlp_gate_up`` as ``[gate | up]``.  AdamW as apex's ``FusedAdam``
states it (``adam_w_mode``): global gradient-norm clip first,
bias-corrected moments, decoupled weight decay on every leaf.

Memory: the step runs application by application (forward keeps each
application's input, backward re-runs one under ``jax.vjp``),
attention in blocks of query rows and the exits' logits in blocks of
token rows, so that the cell's size fits beside float32 weights,
gradients and two moments.

``precision`` other than ``"f32"`` is for the control: every matmul
operand is rounded to that type before a float32 product.
``sizes["total_ut_steps"]`` and ``sizes["exit_entropy_weight"]`` are
read here, so the two faults of this model's own (a pass left out, the
entropy term left out) are this reference with one of them changed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import (HIGHEST, as_floats, diff_norms, norms,
                                         rounder, unzip)

BLOCK = 512           # query rows of attention / token rows of an exit


# ---- what the configuration's sizes mean ----------------------------------

def param_spec(sizes: dict) -> dict:
    h, ffn = sizes["hidden_size"], sizes["intermediate_size"]
    w = ("normal", sizes.get("initializer_range", 0.02))
    ones = ("ones",)

    def norm():
        return {"weight": ((h,), ones)}

    stack = {"exit": {"final_norm": norm(), "gate_bias": ((1,), ("zeros",)),
                      "gate_weight": ((h,), w),
                      "head": ((h, sizes["vocab_size"]), w)}}
    for i in range(sizes["num_hidden_layers"]):
        stack[f"layer_{i}"] = {
            "attn_norm": norm(), "attn_out_norm": norm(),
            "attn_proj": {"weight": ((h, h), w)},
            "attn_qkv": {"weight": ((h, 3 * h), w)},
            "mlp_down": {"weight": ((ffn, h), w)},
            "mlp_gate_up": {"weight": ((h, 2 * ffn), w)},
            "mlp_norm": norm(), "mlp_out_norm": norm()}
    return {"embed": {"weight": ((sizes["vocab_size"], h), w)},
            "stack": stack}


# ---- arithmetic -------------------------------------------------------------

def _rms_norm(x, p, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * p["weight"]


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


def _attention(q, k, v, *, rnd):
    """Causal softmax attention, (b, s, heads, d), a block of query
    rows at a time (each block rematerialised in the backward pass)."""
    b, s, heads, d = q.shape
    blk = _blocks(s)
    cols = jnp.arange(s)

    @jax.checkpoint
    def rows(args):
        qb, first = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", rnd(qb), rnd(k),
                            precision=HIGHEST) / math.sqrt(d)
        seen = cols[None, :] <= (first + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v),
                          precision=HIGHEST)

    qs = q.reshape(b, s // blk, blk, heads, d).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(rows, (qs, jnp.arange(0, s, blk)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, heads * d)


def _layer(p, x, *, heads, eps, theta, rnd):
    b, s, h = x.shape
    d = h // heads

    def lin(x, q):
        return jnp.matmul(rnd(x), rnd(q["weight"]), precision=HIGHEST)

    qkv = lin(_rms_norm(x, p["attn_norm"], eps),
              p["attn_qkv"]).reshape(b, s, heads, 3 * d)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    ctx = _attention(_rotary(q, theta), _rotary(k, theta), v, rnd=rnd)
    x = x + _rms_norm(lin(ctx, p["attn_proj"]), p["attn_out_norm"], eps)
    gate, up = jnp.split(lin(_rms_norm(x, p["mlp_norm"], eps),
                             p["mlp_gate_up"]), 2, axis=-1)
    y = lin(jax.nn.silu(gate) * up, p["mlp_down"])
    return x + _rms_norm(y, p["mlp_out_norm"], eps)


def _exit(p, h, labels, *, eps, rnd):
    """What closes a pass -> (x, per-token losses, gate logits), the
    logits a block of token rows at a time."""
    x = _rms_norm(h, p["final_norm"], eps)
    flat = x.reshape(-1, x.shape[-1])
    blk = _blocks(flat.shape[0])

    @jax.checkpoint
    def rows(args):
        xb, yb = args
        logits = jnp.matmul(rnd(xb), rnd(p["head"]), precision=HIGHEST)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        z = jnp.matmul(rnd(xb), rnd(p["gate_weight"]),
                       precision=HIGHEST) + p["gate_bias"]
        return logz - picked, z

    losses, z = jax.lax.map(rows, (flat.reshape(-1, blk, flat.shape[-1]),
                                   labels.reshape(-1, blk)))
    return x, losses.reshape(labels.shape), z.reshape(labels.shape)


def _combine(losses, z, beta):
    """losses, z: (R, b, s).  The exit distribution in logarithms."""
    log_exit, log_stay = jax.nn.log_sigmoid(z), jax.nn.log_sigmoid(-z)
    before = jnp.zeros_like(z[0])               # sum_{j<t} log(1 - lam_j)
    log_p = []
    for t in range(z.shape[0] - 1):
        log_p.append(log_exit[t] + before)
        before = before + log_stay[t]
    log_p = jnp.stack(log_p + [before])         # the last pass takes the rest
    p = jnp.exp(log_p)
    return jnp.mean(jnp.sum(p * losses, 0) + beta * jnp.sum(p * log_p, 0))


class _Programs:
    """The few jitted pieces, compiled once per shape: every layer
    application has the same shapes, so R x L of them run through two
    programs."""

    def __init__(self, sizes: dict, precision: str):
        rnd = rounder(precision)
        eps = sizes["rms_norm_eps"]
        layer = functools.partial(
            _layer, heads=sizes["num_attention_heads"], eps=eps,
            theta=float(sizes["rope_theta"]), rnd=rnd)
        leave = functools.partial(_exit, eps=eps, rnd=rnd)
        self.layer = jax.jit(layer)
        self.exit = jax.jit(leave)
        self.combine = jax.jit(jax.value_and_grad(
            functools.partial(_combine, beta=sizes["exit_entropy_weight"]),
            argnums=(0, 1)))

        @jax.jit
        def layer_bwd(p, x, dy):
            return jax.vjp(layer, p, x)[1](dy)

        @jax.jit
        def exit_bwd(p, h, labels, dx, dl, dz):
            return jax.vjp(lambda p, h: leave(p, h, labels), p, h)[1](
                (dx, dl, dz))

        @jax.jit
        def embed_bwd(table, tokens, dx):
            return jnp.zeros_like(table).at[tokens].add(dx)

        self.layer_bwd, self.exit_bwd = layer_bwd, exit_bwd
        self.embed_bwd = embed_bwd


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def loss_and_grads(progs: _Programs, params: dict, tokens, labels,
                   n_layers: int, passes: int):
    stack = params["stack"]
    h = params["embed"]["weight"][tokens]
    inputs, closes, losses, z = [], [], [], []
    for _ in range(passes):                     # the same weights
        for i in range(n_layers):
            inputs.append(h)
            h = progs.layer(stack[f"layer_{i}"], h)
        closes.append(h)
        h, l_t, z_t = progs.exit(stack["exit"], h, labels)
        losses.append(l_t)
        z.append(z_t)
    loss, (dl, dz) = progs.combine(jnp.stack(losses), jnp.stack(z))
    grads = {"stack": {}}
    dh = jnp.zeros_like(h)                      # nothing reads x_R
    for t in reversed(range(passes)):
        g, dh = progs.exit_bwd(stack["exit"], closes.pop(), labels, dh,
                               dl[t], dz[t])
        sofar = grads["stack"].get("exit")
        grads["stack"]["exit"] = g if sofar is None else _add(sofar, g)
        for i in reversed(range(n_layers)):
            g, dh = progs.layer_bwd(stack[f"layer_{i}"], inputs.pop(), dh)
            sofar = grads["stack"].get(f"layer_{i}")
            grads["stack"][f"layer_{i}"] = (g if sofar is None
                                           else _add(sofar, g))
    grads["embed"] = {"weight": progs.embed_bwd(
        params["embed"]["weight"], tokens, dh)}
    return loss, grads


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


def _groups(tree):
    """A tree's update groups as (holder, key) pairs: the table, then
    each entry of the stack."""
    return [(tree, "embed")] + [(tree["stack"], k) for k in tree["stack"]]


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
    progs = _Programs(sizes, precision)
    start = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": []}
    for t, (tokens, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grads(
            progs, params, tokens, labels, sizes["num_hidden_layers"],
            sizes["total_ut_steps"])
        out["losses"].append(float(loss))
        gnorm = float(jnp.sqrt(sum(_sumsq(g[k]) for g, k in _groups(grads))))
        clip = jnp.float32(min(1.0, max_norm / (gnorm + 1e-6))
                           if max_norm > 0 else 1.0)
        for (p, k), (g, _), (m_, _), (v_, _) in zip(      # group by group
                *(_groups(tree) for tree in (params, grads, m, v))):
            p[k], m_[k], v_[k] = _adamw(p[k], g.pop(k), m_[k], v_[k],
                                        jnp.float32(t), clip, hyper)
        if t == 1:
            out["grad1"] = as_floats(norms(m),
                                     1.0 / (1.0 - optimizer["beta1"]))
    out["change"] = as_floats(diff_norms(params, start))
    return out
