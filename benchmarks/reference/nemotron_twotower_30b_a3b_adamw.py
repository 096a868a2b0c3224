"""Plain reference for ``nemotron_twotower_30b_a3b_adamw``: a hybrid
decoder whose layers are each one pre-norm block of three kinds, read
from the configuration's pattern — a Mamba-2 mixer (a selective
state-space recurrence), one holder's share of a sigmoid-routed layer
of non-gated squared-ReLU experts with a shared expert, or grouped-query
causal attention without positions — its loss and gradients, and
AdamW, in straightforward ``jax.numpy`` float32 at ``highest`` matmul
precision.  No kernels, no chunked form of the recurrence, no grouped
products, no loss scaling, no buckets, and nothing imported from the
program.

    h' = h + Block(RMSNorm(h));  loss = mean cross-entropy(W_head RMSNorm_f(h_L))

Mamba-2, H heads of P, G groups of a state of N (heads h*H/G ..
(h+1)*H/G - 1 read group h's B and C):

    [z | xBC | dt] = x W_in;  xBC = silu(conv_K(xBC) + b)  (depthwise, causal)
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    y = W_out [RMSNorm over groups of H P / G (y * silu(z)) * w]

Experts: s = sigmoid(x W_r) over ALL E; E_t = top-k of s + b; g =
routed_scale * s / (sum_{E_t} s + 1e-20); y[t] = sum_{e in E_t, e held}
g[t, e] W_down^e relu(W_up^e x)^2 + the shared expert, same form.

Attention: q (H heads), k and v (H_kv heads, each read by H / H_kv
query heads), causal softmax at head_dim^-1/2, no positions.

Weights: ``weights.make`` draws normals; ``initial`` maps the draws of
the leaves Mamba-2 initialises otherwise (``A_log`` = log U[1, 16],
``dt_bias`` = softplus^-1 of a log-uniform step in [time_step_min,
time_step_max] floored at time_step_floor, the convolution's weight and
bias U(-K^-1/2, K^-1/2)) through the normal's distribution function,
and the program is handed the same mapped tree (the driver calls it).
Every matrix that writes the residual stream (the mixers' output
projection, attention's, the experts' and the shared expert's down
matrices) is drawn at 0.02 / sqrt(len(pattern)) (``rescale_prenorm_
residual``).  With ``balanced_start_batches`` n in the sizes, each
expert layer's selection bias starts where the balancing update
settles on the first n batches (``balance``), and the program is
handed that bias too.

Memory: the step runs layer by layer (forward keeps each layer's input,
backward re-runs one under ``jax.vjp``); the recurrence a block of 64
positions at a time under ``jax.checkpoint`` (its gradient keeps the
block boundaries' states only), attention in blocks of query rows, the
logits in blocks of token rows, the experts one by one.

``precision`` other than ``"f32"`` is for the control: every matmul
operand (the recurrence's x, B and C among them) is rounded to that type
before a float32 product.  This model's own faults are this reference
with a size changed (``FAULTS``).
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
STEPS = 64            # positions of the recurrence between kept states

# this model's own faults, each a change of sizes the reference reads
FAULTS = {
    "fault_no_decay": {"ssd_decay": False},          # exp(dt A) = 1
    "fault_no_d_skip": {"ssd_d_skip": False},        # D x left out
    "fault_norm_whole": {"gate_norm_groups": 1},     # one norm over H P
    "fault_relu": {"expert_act": "relu"},            # relu, not relu^2
    "fault_bc_tiled": {"ssd_group_of_head": "tiled"},  # head h reads group h % G
}


# ---- what the configuration's sizes mean ----------------------------------

def layer_kinds(sizes: dict):
    """The pattern's letter of each layer, from 0."""
    return list(sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]])


def mamba_widths(sizes: dict):
    """(inner H P, conv width H P + 2 G N, in_proj width)."""
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    return inner, conv, inner + conv + sizes["mamba_num_heads"]


def param_spec(sizes: dict) -> dict:
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    nh = sizes["mamba_num_heads"]
    heads, kvh, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                     sizes["head_dim"])
    held, f = sizes["n_routed_experts"], sizes["moe_intermediate_size"]
    fs = sizes["moe_shared_expert_intermediate_size"]
    inner, conv, proj = mamba_widths(sizes)
    std = sizes.get("initializer_range", 0.02)
    w = ("normal", std)
    out = ("normal", std / math.sqrt(len(sizes["hybrid_override_pattern"])))
    raw = ("normal", 1.0)                       # mapped by ``initial``
    ones, zeros = ("ones",), ("zeros",)

    def lin(i, o, init=w):
        return {"weight": ((i, o), init)}

    table = ("normal", sizes.get("embedding_initializer_range", 1.0))
    spec = {"embed": {"weight": ((v, h), table)},
            "final_norm": {"weight": ((h,), ones)},
            "head": ((h, v), w)}
    k = sizes["conv_kernel"]
    for i, kind in enumerate(layer_kinds(sizes)):
        layer = {"norm": {"weight": ((h,), ones)}}
        if kind == "M":
            layer["mamba"] = {
                "in_proj": lin(h, proj), "conv_weight": ((k, conv), raw),
                "conv_bias": ((conv,), raw), "dt_bias": ((nh,), raw),
                "A_log": ((nh,), raw), "D": ((nh,), ones),
                "gate_norm": ((inner,), ones), "out_proj": lin(inner, h, out)}
        elif kind == "*":
            layer["attn"] = {
                "q_proj": lin(h, heads * d), "k_proj": lin(h, kvh * d),
                "v_proj": lin(h, kvh * d), "o_proj": lin(heads * d, h, out)}
        else:
            r = sizes["router_num_experts"]
            layer["moe"] = {
                "router": ((h, r), w), "selection_bias": ((r,), zeros),
                "up": ((held, h, f), w), "down": ((held, f, h), out),
                "shared_up": ((h, fs), w), "shared_down": ((fs, h), out)}
        spec[f"layer_{i}"] = layer
    return spec


def initial(params: dict, sizes: dict) -> dict:
    """The seeded normals -> the initial weights: the leaves Mamba-2
    draws from other distributions are mapped through Phi(z), uniform."""
    lo, hi = math.log(sizes["time_step_min"]), math.log(sizes["time_step_max"])
    bound = sizes["conv_kernel"] ** -0.5

    def leaf(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        u = ndtr(x)
        if name in ("conv_weight", "conv_bias"):    # U(-K^-1/2, K^-1/2)
            return (2.0 * u - 1.0) * bound
        if name == "A_log":                         # log U[1, 16]
            return jnp.log(1.0 + 15.0 * u)
        if name == "dt_bias":                       # softplus^-1(dt)
            dt = jnp.maximum(jnp.exp(u * (hi - lo) + lo),
                             sizes["time_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        return x
    return jax.tree_util.tree_map_with_path(leaf, params)


def with_biases(params: dict, biases: dict) -> dict:
    """``params`` with the selection bias of each layer named in
    ``biases`` (layer name -> (E,)) replaced."""
    out = dict(params)
    for name, bias in biases.items():
        layer = dict(out[name])
        layer["moe"] = dict(layer["moe"], selection_bias=bias)
        out[name] = layer
    return out


# ---- arithmetic -------------------------------------------------------------

def _rms_norm(x, weight, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * weight


def _blocks(n, blk):
    blk = min(blk, n)
    if n % blk:
        raise ValueError(f"{n} rows do not divide into blocks of {blk}")
    return blk


def _recurrence(x, dt, a, B, C):
    """x (b, s, H, P), dt (b, s, H), a (H,) the decay's exponent rate,
    B and C (b, s, H, N) float32 -> y (b, s, H, P): the state position
    by position, STEPS positions a checkpointed block."""
    b, s, nh, p = x.shape
    sp = -(-s // STEPS) * STEPS
    pad = lambda v: jnp.pad(v, ((0, 0), (0, sp - s)) + ((0, 0),) * (v.ndim - 2))  # noqa: E731
    tm = lambda v: jnp.moveaxis(pad(v), 1, 0).reshape(  # noqa: E731
        (sp // STEPS, STEPS) + v.shape[:1] + v.shape[2:])

    def position(state, xs):
        xt, dtt, bt, ct = xs
        state = (state * jnp.exp(dtt * a)[..., None, None]
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct,
                                 precision=HIGHEST)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(position, state, xs)

    state = jnp.zeros((b, nh, p, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(block, state, tuple(tm(t) for t in (x, dt, B, C)))
    return jnp.moveaxis(y.reshape((sp,) + y.shape[2:]), 0, 1)[:, :s]


def _mamba(p, x, *, sizes, rnd):
    b, s, _ = x.shape
    nh, hp = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, n = sizes["n_groups"], sizes["ssm_state_size"]
    inner, conv, _ = mamba_widths(sizes)
    eps = sizes["layer_norm_epsilon"]

    def lin(v, name):
        return jnp.matmul(rnd(v), rnd(p[name]["weight"]), precision=HIGHEST)

    z, xbc, dt = jnp.split(lin(x, "in_proj"), [inner, inner + conv], axis=-1)
    w = p["conv_weight"]
    kk = w.shape[0]
    up = jnp.pad(xbc, ((0, 0), (kk - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(w[j] * up[:, j:j + s] for j in range(kk))
                      + p["conv_bias"])
    xs, B, C = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    xs = xs.reshape(b, s, nh, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    if not sizes.get("ssd_decay", True):
        a = jnp.zeros_like(a)
    if sizes.get("ssd_group_of_head", "repeated") == "tiled":
        group = jnp.arange(nh) % g
    else:
        group = jnp.arange(nh) // (nh // g)
    B, C = (jnp.take(t.reshape(b, s, g, n), group, axis=2) for t in (B, C))
    y = _recurrence(rnd(xs), dt, a, rnd(B), rnd(C))
    if sizes.get("ssd_d_skip", True):
        y = y + p["D"][:, None] * xs
    y = y.reshape(b, s, inner) * jax.nn.silu(z)
    ng = sizes.get("gate_norm_groups", g)
    y = _rms_norm(y.reshape(b, s, ng, inner // ng), 1.0, eps).reshape(
        b, s, inner) * p["gate_norm"]
    return lin(y, "out_proj")


def _attention(p, x, *, sizes, rnd):
    b, s, _ = x.shape
    heads, kvh, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                     sizes["head_dim"])

    def lin(v, name):
        return jnp.matmul(rnd(v), rnd(p[name]["weight"]), precision=HIGHEST)

    q = lin(x, "q_proj").reshape(b, s, heads, d)
    k, v = (jnp.repeat(lin(x, name).reshape(b, s, kvh, d), heads // kvh,
                       axis=2) for name in ("k_proj", "v_proj"))
    blk = _blocks(s, BLOCK)
    cols = jnp.arange(s)

    @jax.checkpoint
    def rows(args):
        qb, first = args
        seen = cols[None, :] <= (first + jnp.arange(blk))[:, None]
        sc = jnp.einsum("bqhd,bkhd->bhqk", rnd(qb), rnd(k),
                        precision=HIGHEST) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v),
                          precision=HIGHEST)

    out = jax.lax.map(rows, (q.reshape(b, s // blk, blk, heads, d)
                             .swapaxes(0, 1), jnp.arange(0, s, blk)))
    return lin(out.swapaxes(0, 1).reshape(b, s, heads * d), "o_proj")


def _expert(x, up, down, rnd, act="relu2"):
    mid = jax.nn.relu(jnp.matmul(rnd(x), rnd(up), precision=HIGHEST))
    return jnp.matmul(rnd(mid * mid if act == "relu2" else mid), rnd(down),
                      precision=HIGHEST)


def route(x, router, bias, top_k, scale, rnd):
    """x (T, h) -> (gates (T, k), experts (T, k)) over ALL experts."""
    s = jax.nn.sigmoid(jnp.matmul(rnd(x), rnd(router), precision=HIGHEST))
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(s + bias), top_k)
    chosen = experts[..., None] == jnp.arange(s.shape[-1])
    gates = jnp.sum(jnp.where(chosen, s[:, None, :], 0.0), axis=-1)
    return scale * gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20), experts


def moe(p, x, *, top_k, offset=0, routed_scale=1.0, act="relu2",
        rnd=lambda x: x):
    """The held experts' part of the expert layer and the shared expert,
    x (T, h): every held expert applied to every token under a dense
    mask."""
    gates, experts = route(x, p["router"], p["selection_bias"], top_k,
                           routed_scale, rnd)
    y = jnp.zeros_like(x)
    for e in range(p["up"].shape[0]):
        g = jnp.sum(jnp.where(experts == offset + e, gates, 0.0), axis=-1)
        y = y + g[:, None] * _expert(x, p["up"][e], p["down"][e], rnd, act)
    return y + _expert(x, p["shared_up"], p["shared_down"], rnd, act)


def _layer(p, x, *, sizes, rnd, kind):
    b, s, h = x.shape
    xn = _rms_norm(x, p["norm"]["weight"], sizes["layer_norm_epsilon"])
    if kind == "M":
        return x + _mamba(p["mamba"], xn, sizes=sizes, rnd=rnd)
    if kind == "*":
        return x + _attention(p["attn"], xn, sizes=sizes, rnd=rnd)
    y = moe(p["moe"], xn.reshape(b * s, h),
            top_k=sizes["num_experts_per_tok"],
            offset=sizes.get("expert_offset", 0),
            routed_scale=sizes["routed_scaling_factor"],
            act=sizes.get("expert_act", "relu2"), rnd=rnd)
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
            fn = functools.partial(_layer, sizes=sizes, rnd=rnd, kind=kind)
            self.layer[kind] = jax.jit(fn)
            self.layer_bwd[kind] = jax.jit(
                lambda p, x, dy, fn=fn: jax.vjp(fn, p, x)[1](dy))
        head = functools.partial(_head, eps=sizes["layer_norm_epsilon"],
                                 rnd=rnd)
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


# ---- the selection bias a balanced job holds ---------------------------------

BALANCE_ROUNDS = 300
BALANCE_RATES = (1e-2, 1e-5)       # the update's size, first and last round


@functools.partial(jax.jit, static_argnames=("top_k",))
def _settle(scores, top_k):
    """scores (T, E) -> the bias b (E,) after the balancing update b_e
    += rate * sign(T k / E - load_e) (the tokens whose top-k of scores
    + b hold e), its rate falling geometrically over the rounds."""
    t, e = scores.shape
    share = t * top_k / e
    first, last = BALANCE_RATES

    def round_(i, b):
        _, chosen = jax.lax.top_k(scores + b, top_k)
        load = jnp.sum(chosen[..., None] == jnp.arange(e), axis=(0, 1))
        rate = first * (last / first) ** (i / (BALANCE_ROUNDS - 1))
        return b + rate * jnp.sign(share - load)
    return jax.lax.fori_loop(0, BALANCE_ROUNDS, round_,
                             jnp.zeros((e,), jnp.float32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _router_scores(h, norm, router, eps):
    """The sigmoid scores (T, E) of an expert layer's router over its
    input h (b, s, h), float32."""
    x = _rms_norm(h, norm, eps).reshape(-1, h.shape[-1])
    return jax.nn.sigmoid(jnp.matmul(x, router, precision=HIGHEST))


def balance(params: dict, tokens, sizes: dict) -> dict:
    """Layer name -> the selection bias (E,) of each expert layer, where
    the deployment's balancing update settles on the batches ``tokens``
    (the first ``balanced_start_batches`` of the run): every expert
    takes its share of the batches' top-k choices, so the rows routed
    to the experts held here are the same share whatever the seed.
    Layer by layer, in float32: a layer's routing feeds the next
    layer's input.  Empty without ``balanced_start_batches``."""
    n = sizes.get("balanced_start_batches", 0)
    if not n:
        return {}
    if len(tokens) < n:
        raise ValueError(f"the bias settles on {n} batches, got "
                         f"{len(tokens)}")
    faults = {k for change in FAULTS.values() for k in change}
    sizes = {k: v for k, v in sizes.items() if k not in faults}
    progs = _Programs(sizes, "f32")
    eps = sizes["layer_norm_epsilon"]
    hs = [params["embed"]["weight"][t] for t in tokens[:n]]
    biases = {}
    for i, kind in enumerate(progs.kinds):
        name = f"layer_{i}"
        if kind == "E":
            p = params[name]
            scores = jnp.concatenate([_router_scores(
                h, p["norm"]["weight"], p["moe"]["router"], eps) for h in hs])
            biases[name] = _settle(scores, sizes["num_experts_per_tok"])
        p = with_biases(params, biases)[name]
        hs = [progs.layer[kind](p, h) for h in hs]
    return biases


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
    norms by leaf.  The selection biases are ``balance``'s on the
    batches' tokens, whatever ``precision`` and faults."""
    params = jax.jit(functools.partial(initial, sizes=sizes))(params)
    params = with_biases(params, balance(
        params, [tokens for tokens, _ in batches], sizes))
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
