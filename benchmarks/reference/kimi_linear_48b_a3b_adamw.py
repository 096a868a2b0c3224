"""Plain reference for ``kimi_linear_48b_a3b_adamw``: a hybrid decoder
whose layers mix by Kimi Delta Attention (a gated delta-rule recurrence
with one decay a key channel) or by latent attention without positions,
then a dense gated feed-forward (the first layer) or one holder's share
of a sigmoid-routed expert layer with a shared expert; its loss and
gradients, and AdamW, in straightforward ``jax.numpy`` float32 at
``highest`` matmul precision.  No kernels, no chunked form of the
recurrence, no grouped products, no loss scaling, no buckets, and
nothing imported from the program.

    h' = h + Mixer(RMSNorm(h)),   h'' = h' + FFN(RMSNorm(h'))
    loss = mean cross-entropy(W_head RMSNorm_f(h_L))

KDA, per head (d = kda_head_dim):

    q, k, v = silu(conv_K(x W_{q,k,v}))  (depthwise, causal, no bias)
    q, k <- q / |q|, k / |k|;  beta = sigmoid(x W_b)
    g = -exp(A_log) * softplus(x W_fa W_fb + dt_bias)
    S_t = S_{t-1} * exp(g_t) (rows);  S_t += beta_t k_t (v_t - S_t^T k_t)^T
    o_t = d^-1/2 S_t^T q_t
    y = W_o [RMSNorm_head(o) * w * sigmoid(x W_ga W_gb)]

MLA without positions: q = x W_q; [c ; k^R] = x W_kva;
[k^C ; v] = RMSNorm(c) W_kvb; k = [k^C ; k^R]; causal softmax at
(nope + rope)^-1/2.

Experts: s = sigmoid(x W_r) over ALL E; E_t = top-k of s + b; g =
routed_scale * s / (sum_{E_t} s + 1e-20); y[t] = sum_{e in E_t, e held}
g[t, e] W_down^e(silu(W_gate^e x) * W_up^e x) + the shared expert.

Weights: ``weights.make`` draws normals; ``initial`` maps the draws of
the leaves FLA initialises otherwise (``A_log`` = log U[1, 16],
``dt_bias`` = softplus^-1 of a log-uniform step in [0.001, 0.1], the
convolutions U(-1/2, 1/2)) through the normal's distribution function,
and the program is handed the same mapped tree (the driver calls it).

Memory: the step runs layer by layer (forward keeps each layer's input,
backward re-runs one under ``jax.vjp``); the recurrence a block of 64
tokens at a time under ``jax.checkpoint`` (its gradient keeps the block
boundaries' states only), attention in blocks of query rows, the logits
in blocks of token rows, the experts one by one.

``precision`` other than ``"f32"`` is for the control: every matmul
operand (the recurrence's q, k and v among them) is rounded to that type
before a float32 product.  This model's own faults are this reference
with a size changed: ``kda_delta_rule`` False (beta = 0: no correction,
the state only decays and accumulates), ``kda_channel_decay`` False
(one decay a head, the mean of its channels'), ``num_shared_experts`` 0.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtr

from benchmarks.reference.common import (HIGHEST, as_floats, diff_norms, norms,
                                         rounder, unzip)

BLOCK = 512           # query rows of attention / token rows of the head
STEPS = 64            # tokens of the recurrence between kept states


# ---- what the configuration's sizes mean ----------------------------------

def layer_kinds(sizes: dict):
    """(mixer, dense) of each layer, from 0: MLA where i + 1 is among
    the configuration's ``full_attn_layers``, dense for the first
    ``first_k_dense_replace``."""
    return [("mla" if i + 1 in sizes["full_attn_layers"] else "kda",
             i < sizes["first_k_dense_replace"])
            for i in range(sizes["num_hidden_layers"])]


def param_spec(sizes: dict) -> dict:
    h = sizes["hidden_size"]
    nh, d = sizes["kda_num_heads"], sizes["kda_head_dim"]
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, r = sizes["v_head_dim"], sizes["kv_lora_rank"]
    held, f = sizes["num_experts"], sizes["moe_intermediate_size"]
    fs = f * sizes["num_shared_experts"]
    width = nh * d
    w = ("normal", sizes.get("initializer_range", 0.02))
    raw = ("normal", 1.0)                       # mapped by ``initial``
    ones, zeros = ("ones",), ("zeros",)

    def lin(i, o):
        return {"weight": ((i, o), w)}

    def norm(n=h):
        return {"weight": ((n,), ones)}

    table = ("normal", sizes.get("embedding_initializer_range", 1.0))
    spec = {"embed": {"weight": ((sizes["vocab_size"], h), table)},
            "final_norm": norm(),
            "head": ((h, sizes["vocab_size"]), w)}
    k = sizes["short_conv_kernel_size"]
    for i, (mixer, dense) in enumerate(layer_kinds(sizes)):
        layer = {"attn_norm": norm(), "mlp_norm": norm()}
        if mixer == "kda":
            layer["kda"] = {
                "q_proj": lin(h, width), "k_proj": lin(h, width),
                "v_proj": lin(h, width),
                "q_conv": ((k, width), raw), "k_conv": ((k, width), raw),
                "v_conv": ((k, width), raw),
                "f_a_proj": lin(h, d), "f_b_proj": lin(d, width),
                "b_proj": lin(h, nh),
                "A_log": ((nh,), raw), "dt_bias": ((width,), raw),
                "g_a_proj": lin(h, d), "g_b_proj": lin(d, width),
                "o_norm": ((d,), ones), "o_proj": lin(width, h)}
        else:
            layer["mla"] = {
                "q_proj": lin(h, heads * (nope + rope)),
                "kv_a_proj": lin(h, r + rope), "kv_a_norm": norm(r),
                "kv_b_proj": lin(r, heads * (nope + dv)),
                "o_proj": lin(heads * dv, h)}
        if dense:
            fd = sizes["intermediate_size"]
            layer["mlp_gate_up"] = lin(h, 2 * fd)
            layer["mlp_down"] = lin(fd, h)
        else:
            layer["moe"] = {
                "router": ((h, sizes["router_num_experts"]), w),
                "selection_bias": ((sizes["router_num_experts"],), zeros),
                "gate_up": ((held, h, 2 * f), w),
                "down": ((held, f, h), w),
                "shared_gate_up": ((h, 2 * fs), w),
                "shared_down": ((fs, h), w)}
        spec[f"layer_{i}"] = layer
    return spec


def initial(params: dict) -> dict:
    """The seeded normals -> the initial weights: the leaves FLA draws
    from other distributions are mapped through Phi(z), uniform."""
    def leaf(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        u = ndtr(x)
        if name.endswith("_conv"):                  # U(-K^-1/2, K^-1/2)
            return (2.0 * u - 1.0) * x.shape[0] ** -0.5
        if name == "A_log":                         # log U[1, 16]
            return jnp.log(1.0 + 15.0 * u)
        if name == "dt_bias":                       # softplus^-1(dt)
            dt = jnp.maximum(jnp.exp(u * (math.log(0.1) - math.log(1e-3))
                                     + math.log(1e-3)), 1e-4)
            return dt + jnp.log(-jnp.expm1(-dt))
        return x
    return jax.tree_util.tree_map_with_path(leaf, params)


# ---- arithmetic -------------------------------------------------------------

def _rms_norm(x, weight, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * weight


def _blocks(n, blk):
    blk = min(blk, n)
    if n % blk:
        raise ValueError(f"{n} rows do not divide into blocks of {blk}")
    return blk


def _recurrence(q, k, v, g, beta, scale):
    """(b, s, heads, d) float32 -> o (b, s, heads, dv): the state token
    by token, STEPS tokens a checkpointed block."""
    b, s, nh, dk = q.shape
    sp = -(-s // STEPS) * STEPS
    pad = lambda x: jnp.pad(x, ((0, 0), (0, sp - s)) + ((0, 0),) * (x.ndim - 2))  # noqa: E731
    tm = lambda x: jnp.moveaxis(pad(x), 1, 0).reshape(  # noqa: E731
        (sp // STEPS, STEPS) + x.shape[:1] + x.shape[2:])

    def token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[..., None]
        kv = jnp.einsum("bhk,bhkv->bhv", kt, state, precision=HIGHEST)
        state = state + jnp.einsum("bhk,bhv->bhkv", bt[..., None] * kt,
                                   vt - kv, precision=HIGHEST)
        return state, scale * jnp.einsum("bhkv,bhk->bhv", state, qt,
                                         precision=HIGHEST)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    state = jnp.zeros((b, nh, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block, state, tuple(tm(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((sp,) + o.shape[2:]), 0, 1)[:, :s]


def _kda(p, x, *, sizes, rnd):
    b, s, _ = x.shape
    nh, d = sizes["kda_num_heads"], sizes["kda_head_dim"]
    eps = sizes["rms_norm_eps"]

    def lin(x, name):
        return jnp.matmul(rnd(x), rnd(p[name]["weight"]), precision=HIGHEST)

    def branch(name):
        u = lin(x, f"{name}_proj")
        w = p[f"{name}_conv"]
        kk = w.shape[0]
        up = jnp.pad(u, ((0, 0), (kk - 1, 0), (0, 0)))
        y = sum(w[j] * up[:, j:j + s] for j in range(kk))
        return jax.nn.silu(y).reshape(b, s, nh, d)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q, k, v = l2(branch("q")), l2(branch("k")), branch("v")
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        lin(lin(x, "f_a_proj"), "f_b_proj").reshape(b, s, nh, d)
        + p["dt_bias"].reshape(nh, d))
    if not sizes.get("kda_channel_decay", True):
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(lin(x, "b_proj"))
    if not sizes.get("kda_delta_rule", True):
        beta = jnp.zeros_like(beta)
    o = _recurrence(rnd(q), rnd(k), rnd(v), g, beta, d ** -0.5)
    o = _rms_norm(o, p["o_norm"], eps).reshape(b, s, nh * d)
    o = o * jax.nn.sigmoid(lin(lin(x, "g_a_proj"), "g_b_proj"))
    return lin(o, "o_proj")


def _mla(p, x, *, sizes, rnd):
    b, s, _ = x.shape
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, r = sizes["v_head_dim"], sizes["kv_lora_rank"]

    def lin(x, name):
        return jnp.matmul(rnd(x), rnd(p[name]["weight"]), precision=HIGHEST)

    q = lin(x, "q_proj").reshape(b, s, heads, nope + rope)
    c, kr = jnp.split(lin(x, "kv_a_proj"), [r], axis=-1)
    c = _rms_norm(c, p["kv_a_norm"]["weight"], sizes["rms_norm_eps"])
    kc, v = jnp.split(lin(c, "kv_b_proj").reshape(b, s, heads, nope + dv),
                      [nope], axis=-1)
    k = jnp.concatenate(
        [kc, jnp.broadcast_to(kr[:, :, None, :], (b, s, heads, rope))], -1)
    blk = _blocks(s, BLOCK)
    cols = jnp.arange(s)

    @jax.checkpoint
    def rows(args):
        qb, first = args
        seen = cols[None, :] <= (first + jnp.arange(blk))[:, None]
        sc = jnp.einsum("bqhd,bkhd->bhqk", rnd(qb), rnd(k),
                        precision=HIGHEST) / math.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v),
                          precision=HIGHEST)

    out = jax.lax.map(rows, (q.reshape(b, s // blk, blk, heads, nope + rope)
                             .swapaxes(0, 1), jnp.arange(0, s, blk)))
    out = out.swapaxes(0, 1).reshape(b, s, heads * dv)
    return lin(out, "o_proj")


def _gated(x, gate_up, down, rnd):
    gate, up = jnp.split(jnp.matmul(rnd(x), rnd(gate_up), precision=HIGHEST),
                         2, axis=-1)
    return jnp.matmul(rnd(jax.nn.silu(gate) * up), rnd(down),
                      precision=HIGHEST)


def route(x, router, bias, top_k, scale, rnd):
    """x (T, h) -> (gates (T, k), experts (T, k)) over ALL experts."""
    s = jax.nn.sigmoid(jnp.matmul(rnd(x), rnd(router), precision=HIGHEST))
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(s + bias), top_k)
    chosen = experts[..., None] == jnp.arange(s.shape[-1])
    gates = jnp.sum(jnp.where(chosen, s[:, None, :], 0.0), axis=-1)
    return scale * gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20), experts


def moe(p, x, *, top_k, offset=0, routed_scale=1.0, shared=True,
        rnd=lambda x: x):
    """The held experts' part of the expert layer and the shared expert,
    x (T, h): every held expert applied to every token under a dense
    mask."""
    gates, experts = route(x, p["router"], p["selection_bias"], top_k,
                           routed_scale, rnd)
    y = jnp.zeros_like(x)
    for e in range(p["gate_up"].shape[0]):
        g = jnp.sum(jnp.where(experts == offset + e, gates, 0.0), axis=-1)
        y = y + g[:, None] * _gated(x, p["gate_up"][e], p["down"][e], rnd)
    if shared:
        y = y + _gated(x, p["shared_gate_up"], p["shared_down"], rnd)
    return y


def _layer(p, x, *, sizes, rnd, mixer, dense):
    b, s, h = x.shape
    eps = sizes["rms_norm_eps"]
    xn = _rms_norm(x, p["attn_norm"]["weight"], eps)
    mix = _kda if mixer == "kda" else _mla
    x = x + mix(p[mixer], xn, sizes=sizes, rnd=rnd)
    xn = _rms_norm(x, p["mlp_norm"]["weight"], eps)
    if dense:
        return x + _gated(xn, p["mlp_gate_up"]["weight"],
                          p["mlp_down"]["weight"], rnd)
    y = moe(p["moe"], xn.reshape(b * s, h),
            top_k=sizes["num_experts_per_token"],
            offset=sizes.get("expert_offset", 0),
            routed_scale=sizes["routed_scaling_factor"],
            shared=sizes["num_shared_experts"] > 0, rnd=rnd)
    return x + y.reshape(b, s, h)


def _head(p, x, labels, *, eps, rnd):
    """Mean cross-entropy of the untied head over the final norm's
    output, the logits a block of token rows at a time."""
    flat = _rms_norm(x, p["final_norm"]["weight"], eps).reshape(
        -1, x.shape[-1])
    blk = _blocks(flat.shape[0], BLOCK)

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
    """The few jitted pieces, compiled once per shape: one forward and
    one backward program for each kind of layer."""

    def __init__(self, sizes: dict, precision: str):
        rnd = rounder(precision)
        self.kinds = layer_kinds(sizes)
        self.layer, self.layer_bwd = {}, {}
        for kind in set(self.kinds):
            fn = functools.partial(_layer, sizes=sizes, rnd=rnd,
                                   mixer=kind[0], dense=kind[1])
            self.layer[kind] = jax.jit(fn)
            self.layer_bwd[kind] = jax.jit(
                lambda p, x, dy, fn=fn: jax.vjp(fn, p, x)[1](dy))
        head = functools.partial(_head, eps=sizes["rms_norm_eps"], rnd=rnd)
        self.head = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))

        @jax.jit
        def embed_bwd(table, tokens, dx):
            return jnp.zeros_like(table).at[tokens].add(dx)

        self.embed_bwd = embed_bwd


def loss_and_grads(progs: _Programs, params: dict, tokens, labels):
    """-> (loss, gradients)."""
    h = params["embed"]["weight"][tokens]
    inputs = []
    for i, kind in enumerate(progs.kinds):
        inputs.append(h)
        h = progs.layer[kind](params[f"layer_{i}"], h)
    top = {"final_norm": params["final_norm"], "head": params["head"]}
    loss, (g_top, dh) = progs.head(top, h, labels)
    grads = dict(g_top)
    for i in reversed(range(len(progs.kinds))):
        grads[f"layer_{i}"], dh = progs.layer_bwd[progs.kinds[i]](
            params[f"layer_{i}"], inputs.pop(), dh)
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


def follow(params: dict, batches, sizes: dict, optimizer: dict,
           precision: str = "f32") -> dict:
    """Train from ``params`` (the seeded draws, float32, consumed;
    ``initial`` maps them) over ``batches`` (``(tokens, labels)`` each)
    and return what the comparison reads: each step's loss, the first
    gradient as the optimizer got it (clipped; the first moment over
    1 - beta1) and the parameters' change after the last step, both as
    norms by leaf."""
    params = jax.jit(initial)(params)
    hyper = {k: jnp.float32(optimizer[k]) for k in
             ("lr", "beta1", "beta2", "eps", "weight_decay")}
    max_norm = float(optimizer["max_grad_norm"])
    progs = _Programs(sizes, precision)
    start = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": []}
    for t, (tokens, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grads(progs, params, tokens, labels)
        out["losses"].append(float(loss))
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
