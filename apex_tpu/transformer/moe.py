"""Mixture-of-experts with expert parallelism over the mesh.

NO reference equivalent: apex has no MoE and SURVEY.md §2.5 marks
expert parallelism out of reference scope.  Like ``ring_attention``
(context parallelism), this is a TPU-native extension that makes the
remaining first-class parallelism axis available: experts shard over a
mesh axis and tokens move with ONE ``lax.all_to_all`` each way riding
ICI — the dispatch pattern every TPU MoE uses (the "how to scale your
model" recipe: dense dispatch/combine einsums + all_to_all, static
capacity so shapes never depend on routing).

Per-rank SPMD view (use inside shard_map over ``axis``):

  x (T, H) tokens local to this rank
  -> top-k gating (router replicated)
  -> dispatch einsum to (E, C, H)          [E = global experts]
  -> all_to_all over ``axis``              [tokens to expert owners]
  -> local expert FFN (E/ep experts here)
  -> all_to_all back
  -> combine einsum weighted by gate probs

Static capacity C = ceil(2 * T * capacity_factor / E) (top-2:
two assignments per token); overflow tokens are
dropped by the position-in-expert cumsum mask (standard MoE semantics;
dropped tokens pass through the residual path of the caller).

``DroplessMoE`` / ``dropless_moe`` (further down) is the other kind of
expert layer: top-k of a softmax router, no capacity and no dropped
token, and TOLD WHICH EXPERTS IT HOLDS.  It routes over the router's
whole width and computes its own experts' part of the result, with
grouped matrix products (``lax.ragged_dot``) over the assignments
routed here; the parts of all the holders add up to the whole layer.
It contains no exchange: on one chip it is the chip's share, and an
expert-parallel caller sums the parts.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import comm
from apex_tpu.ops.attention import matmul_precision
from apex_tpu.transformer.tensor_parallel import mappings

Array = jax.Array


def _capacity(tokens: int, num_experts: int,
              capacity_factor: float, k: int = 2) -> int:
    """GShard-style top-k capacity: ceil(k * T * cf / E) — k assignments
    per token must fit in E * C slots at cf=1 under perfect balance."""
    c = -(-(k * tokens * capacity_factor) // num_experts)
    return max(int(c), 1)


def top2_gating(logits: Array, capacity: int,
                jitter_rng: Optional[Array] = None,
                jitter_eps: float = 0.0
                ) -> Tuple[Array, Array, Array]:
    """Top-2 router (Shazeer-style), static shapes throughout.

    logits (T, E) -> (dispatch (T, E, C) bool, combine (T, E, C) f32,
    aux_loss scalar).  combine carries the renormalized gate prob at
    the token's position in its expert's capacity buffer; tokens past
    capacity get all-zero rows (dropped).
    """
    t, e = logits.shape
    if jitter_rng is not None and jitter_eps > 0.0:
        # multiplicative jitter: noise scales with logit magnitude
        logits = logits * jax.random.uniform(
            jitter_rng, logits.shape, logits.dtype,
            1.0 - jitter_eps, 1.0 + jitter_eps)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    g1 = jnp.max(probs, axis=-1)
    i1 = jnp.argmax(probs, axis=-1)
    probs_wo1 = probs * (1.0 - jax.nn.one_hot(i1, e))
    g2 = jnp.max(probs_wo1, axis=-1)
    i2 = jnp.argmax(probs_wo1, axis=-1)

    # load-balancing auxiliary loss (mean prob * mean assignment)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(i1, e, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * e

    # position of each token within its chosen expert, first choice
    # filling before second (the usual priority)
    oh1 = jax.nn.one_hot(i1, e, dtype=jnp.int32)            # (T, E)
    oh2 = jax.nn.one_hot(i2, e, dtype=jnp.int32)
    pos1 = jnp.cumsum(oh1, axis=0) - oh1                    # (T, E)
    count1 = jnp.sum(oh1, axis=0, keepdims=True)
    pos2 = jnp.cumsum(oh2, axis=0) - oh2 + count1
    p1 = jnp.sum(pos1 * oh1, axis=1)                        # (T,)
    p2 = jnp.sum(pos2 * oh2, axis=1)
    keep1 = p1 < capacity
    keep2 = p2 < capacity

    # renormalize the two gates over the kept pair
    denom = g1 * keep1 + g2 * keep2
    denom = jnp.where(denom > 0.0, denom, 1.0)
    w1 = jnp.where(keep1, g1 / denom, 0.0)
    w2 = jnp.where(keep2, g2 / denom, 0.0)

    # one_hot of index==capacity (overflow sentinel) is an all-zero row
    cap_oh1 = jax.nn.one_hot(jnp.where(keep1, p1, capacity), capacity,
                             dtype=jnp.float32)
    cap_oh2 = jax.nn.one_hot(jnp.where(keep2, p2, capacity), capacity,
                             dtype=jnp.float32)
    combine = (w1[:, None, None] * oh1[..., None] * cap_oh1[:, None, :]
               + w2[:, None, None] * oh2[..., None] * cap_oh2[:, None, :])
    dispatch = combine > 0.0
    return dispatch, combine.astype(jnp.float32), aux


class ExpertParallelMLP(nn.Module):
    """Top-2 MoE FFN with experts sharded over a mesh axis.

    hidden/ffn sizes are per-expert; ``num_experts`` is GLOBAL and must
    divide by the axis size.  Call inside shard_map with ``axis`` bound
    (or axis=None / unbound for single-rank execution, where all
    experts live locally — the degenerate path used off-mesh).

    Returns (out (T, H), aux_loss).  Router jitter applies only when
    ``deterministic=False`` (training) — eval calls need no rng.
    """
    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    capacity_factor: float = 1.25
    router_jitter_eps: float = 0.0   # multiplicative routing noise
    axis: Optional[str] = comm.AXIS_MODEL
    activation: Callable = jax.nn.gelu
    param_dtype: jnp.dtype = jnp.float32
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = False):
        t, h = x.shape
        e = self.num_experts
        ep = (comm.bound_axis_size(self.axis)
              if self.axis is not None and comm.axis_is_bound(self.axis)
              else 1)
        if e % ep != 0:
            raise ValueError(f"num_experts {e} % axis size {ep} != 0")
        e_local = e // ep
        dt = self.dtype or x.dtype

        wg = self.param("router", nn.initializers.normal(0.02),
                        (h, e), jnp.float32)
        if ep > 1:
            # replicated router consumed by TOKEN-SHARDED inputs: each
            # rank's router grad sums only its token shard, so the true
            # grad needs a psum over the expert axis — same f/g copy
            # mapping (fwd identity / bwd psum) as the sequence-parallel
            # layernorm params
            wg = mappings.copy_to_tensor_model_parallel_region(
                wg, self.axis)
        # per-rank expert shards, rank-decorrelated init
        def einit(base):
            def init(key, shape, dtype):
                if ep > 1:
                    key = jax.random.fold_in(
                        key, jax.lax.axis_index(self.axis))
                return base(key, shape, dtype)
            return init
        w1 = self.param("w1", einit(nn.initializers.lecun_normal()),
                        (e_local, h, self.ffn_hidden_size),
                        self.param_dtype)
        w2 = self.param("w2", einit(nn.initializers.lecun_normal()),
                        (e_local, self.ffn_hidden_size, h),
                        self.param_dtype)

        cap = _capacity(t, e, self.capacity_factor)
        logits = x.astype(jnp.float32) @ wg
        use_jitter = self.router_jitter_eps > 0.0 and not deterministic
        jrng = self.make_rng("router") if use_jitter else None
        dispatch, combine, aux = top2_gating(
            logits, cap, jitter_rng=jrng,
            jitter_eps=self.router_jitter_eps if use_jitter else 0.0)

        # (T, E, C) x (T, H) -> (E, C, H)
        xe = jnp.einsum("tec,th->ech", dispatch.astype(dt), x.astype(dt))
        if ep > 1:
            # tokens to their expert's owner: split E into (ep, E/ep)
            # and all_to_all the ep dim over the mesh axis
            xe = xe.reshape(ep, e_local, cap, h)
            xe = jax.lax.all_to_all(xe, self.axis, split_axis=0,
                                    concat_axis=0, tiled=False)
            # (ep, e_local, C, H): dim 0 now enumerates source ranks
            xe = jnp.moveaxis(xe, 0, 1).reshape(e_local, ep * cap, h)
        else:
            xe = xe.reshape(e_local, cap, h)

        he = self.activation(
            jnp.einsum("ech,ehf->ecf", xe, w1.astype(dt)))
        ye = jnp.einsum("ecf,efh->ech", he, w2.astype(dt))

        if ep > 1:
            ye = jnp.moveaxis(ye.reshape(e_local, ep, cap, h), 1, 0)
            ye = jax.lax.all_to_all(ye, self.axis, split_axis=0,
                                    concat_axis=0, tiled=False)
            ye = ye.reshape(e, cap, h)
        out = jnp.einsum("tec,ech->th", combine.astype(jnp.float32),
                         ye.astype(jnp.float32))
        return out.astype(x.dtype), aux


def moe_ref(x, router, w1, w2, capacity, activation=jax.nn.gelu):
    """Dense oracle: same gating, every expert applied to every token,
    output = gate-weighted mixture.  w1 (E, H, F), w2 (E, F, H)."""
    logits = x.astype(jnp.float32) @ router
    dispatch, combine, aux = top2_gating(logits, capacity)
    h = activation(jnp.einsum("th,ehf->tef", x.astype(jnp.float32),
                              w1.astype(jnp.float32)))
    y = jnp.einsum("tef,efh->teh", h, w2.astype(jnp.float32))
    weight = jnp.sum(combine, axis=-1)                 # (T, E)
    return jnp.einsum("te,teh->th", weight, y).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# dropless top-k experts, a holder's share
# ---------------------------------------------------------------------------

def route_topk(x, router, top_k: int, norm_topk_prob: bool = True, *,
               score: str = "softmax", selection_bias=None,
               routed_scale: float = 1.0):
    """x (T, H), router (H, E) -> (gates (T, k) float32, experts (T, k)
    int32) over ALL E experts, whoever holds them.  ``score``
    ``"softmax"``: the ``top_k`` largest of a float32 softmax,
    renormalised over the k chosen when ``norm_topk_prob``.
    ``"sigmoid"`` (DeepSeek-V3's router): s = sigmoid(logits) in
    float32; the k chosen are the largest of s + ``selection_bias``
    (E,), which picks and does not weigh (no gradient reaches it); the
    gates are their s, renormalised over the k when ``norm_topk_prob``
    (plus 1e-20), times ``routed_scale``."""
    with jax.named_scope("apex_moe/router"):
        logits = jnp.dot(x, router.astype(x.dtype),
                         preferred_element_type=jnp.float32,
                         precision=matmul_precision(x.dtype))
        if score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        elif score == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"route_topk: unknown score {score!r}")
        pick = jax.lax.stop_gradient(probs)
        if selection_bias is not None:
            pick = pick + jax.lax.stop_gradient(
                selection_bias.astype(jnp.float32))
        _, experts = jax.lax.top_k(pick, top_k)
        # the chosen probabilities by a one-hot select, not by top_k's
        # own values: those transpose to a scatter
        chosen = experts[..., None] == jnp.arange(probs.shape[-1])
        gates = jnp.sum(jnp.where(chosen, probs[:, None, :], 0.0), axis=-1)
        if norm_topk_prob:
            total = jnp.sum(gates, axis=-1, keepdims=True)
            gates = gates / (total + 1e-20 if score == "sigmoid" else total)
        if routed_scale != 1.0:
            gates = gates * routed_scale
    return gates, experts.astype(jnp.int32)


def _shared_expert(x, gate_up, down, activation="swiglu"):
    """The expert every token goes through: down(silu(gate x) * up x),
    gate_up (H, 2F) = [gate | up]; under ``activation`` ``"relu2"``
    down(relu(up x)^2), gate_up the up matrix (H, F); float32 sum of
    products, the activation in float32, x's dtype out."""
    with jax.named_scope("apex_moe/shared"):
        prec = matmul_precision(x.dtype)
        mid = jnp.dot(x, gate_up.astype(x.dtype),
                      preferred_element_type=jnp.float32, precision=prec)
        if activation == "relu2":
            act = jnp.square(jax.nn.relu(mid))
        else:
            gate, up = jnp.split(mid, 2, axis=-1)
            act = jax.nn.silu(gate) * up
        return jnp.dot(act.astype(x.dtype), down.astype(x.dtype),
                       preferred_element_type=jnp.float32, precision=prec)


def _take(x, idx):
    return jnp.take(x, idx, axis=0, mode="clip")


# Both moves between token order and expert order are GATHERS in both
# directions (a row is looked up where it went, never scattered to
# where it goes): XLA's scatter-add runs elements one by one on the TPU
# (PERF.md section 6, PR 26).
#
# The buffers between them have ``rows = T * min(k, held)`` rows, all
# that can arrive, and ``n_used = sum(counts)`` rows in use, sorted to
# the front.  Every pass over such a buffer is a loop over chunks of
# ``_CHUNK`` rows whose trip count is read off ``n_used``: it costs the
# rows routed here, and the rows past the last chunk stay the zeros the
# buffer was made of (the grouped products are handed these buffers).
# The loops live inside custom VJP rules, so JAX never differentiates
# through one; each backward rule is its own loop.

_CHUNK = 2048


class _Routed(NamedTuple):
    """Where the assignments held here are, as integers and copies of
    their gates that carry no gradient (``_route`` makes it)."""
    n_used: Array     # () rows in use
    order: Array      # (T * k,) the assignment t * k + c in each row of
    #                   expert order, rows in use first
    token: Array      # (rows,) the token in each row of expert order
    gate: Array       # (rows,) and its gate
    # the rows in use again, sorted by the assignment they hold (token
    # order), each (rows,): their row in expert order, gate and token
    t_row: Array
    t_gate: Array
    t_token: Array
    last: Array       # (T,) the place in token order of each token's
    #                   last assignment held here
    some: Array       # (T,) whether it has any


def _over_rows_in_use(n_used, after, like, chunk_fn):
    """One buffer for each (shape, dtype) of ``like``, ``rows`` leading
    rows each, made once ``after`` (the arrays the chunks read) is
    there.  For every chunk of rows that holds a row in use,
    ``chunk_fn(start, size)`` gives each buffer's ``size`` rows from
    ``start`` on (zeros at rows past ``n_used``), which are written
    there; the rows of the chunks after them are zeros.  Returns the
    buffers."""
    rows = like[0][0][0]
    size = min(_CHUNK, rows)
    # zeros the compiler cannot fold (``n_used`` is never negative,
    # which it does not know): constant zeros of one shape are merged
    # across a model's layers and lose the scope they were made under,
    # and the layer's metric the time to fill them (0.4 ms for 65 536 x
    # 2048 bfloat16 on a v5e).  Each loop's zero is its own value, there
    # no sooner than what the loop reads: zeros of one value and shape
    # are one buffer to the compiler, held across the loops that share
    # it, and a fill that waits for nothing is made long before its loop
    # (+0.34 and +0.19 GiB of the expert cell's train step's scratch,
    # PERF.md section 6, PR 34)
    zero = jnp.minimum(jax.lax.optimization_barrier((n_used, after))[0], 0)

    def body(i, bufs):
        # the last chunk of a buffer that ``size`` does not divide is
        # moved back to end with it: it writes some rows twice, the same
        start = jnp.minimum(i * size, rows - size)
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(b, c.astype(b.dtype), start, 0)
            for b, c in zip(bufs, chunk_fn(start, size)))
    return jax.lax.fori_loop(
        0, (n_used + size - 1) // size, body,
        tuple(jnp.full(shape, zero, dtype) for shape, dtype in like))


def _in_use(start, size, n_used):
    at = start + jnp.arange(size)
    return ((at >= 0) & (at < n_used))[:, None]


def _window(v, start, size):
    return jax.lax.dynamic_slice_in_dim(v, start, size)


def _sum_by_token(buf, routed, weighted):
    """out[t] = float32 sum over the assignments of token t held here of
    [their gate times] their row of buf, in buf's dtype.  In token
    order a token's assignments are at most ``reach = min(k, held)``
    adjacent entries, so a running sum over ``reach`` neighbours of the
    same token leaves each token's sum at its last entry: ``n_used``
    rows are gathered, summed in chunks, and T looked up."""
    n_used = routed.n_used
    rows = routed.token.shape[0]
    reach = rows // routed.last.shape[0]
    halo = reach - 1
    row, gate = (jnp.pad(v, (halo, 0)) for v in (routed.t_row, routed.t_gate))
    token = jnp.pad(routed.t_token, (halo, 0), constant_values=-1)

    def chunk(start, size):
        # entries start - halo .. start + size are [start, start + halo
        # + size) of the padded vectors
        tok = _window(token, start, halo + size)
        z = _take(buf, _window(row, start, halo + size)).astype(jnp.float32)
        if weighted:
            z = z * _window(gate, start, halo + size)[:, None]
        z = jnp.where(_in_use(start - halo, halo + size, n_used), z, 0.0)
        d = 1
        while d < reach:                   # sums of 2, 4, 8... neighbours
            same = (tok[d:] == tok[:-d])[:, None]
            z = z + jnp.pad(jnp.where(same, z[:-d], 0.0), ((d, 0), (0, 0)))
            d *= 2
        return (z[halo:],)
    sums, = _over_rows_in_use(
        n_used, buf, [((rows,) + buf.shape[1:], buf.dtype)], chunk)
    return jnp.where(routed.some[:, None], _take(sums, routed.last), 0)


@jax.custom_vjp
def _dispatch(x, routed):
    """Rows of x (T, H) in expert order: out[m] = x[token[m]] for the
    ``n_used`` rows in use, zeros after them."""
    return _dispatch_fwd(x, routed)[0]


def _dispatch_fwd(x, routed):
    def chunk(start, size):
        return (jnp.where(_in_use(start, size, routed.n_used),
                          _take(x, _window(routed.token, start, size)), 0),)
    xs, = _over_rows_in_use(
        routed.n_used, x, [(routed.token.shape + x.shape[1:], x.dtype)],
        chunk)
    return xs, routed


def _dispatch_bwd(routed, g):
    return _sum_by_token(g, routed, False), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _gated_silu(mid, n_used):
    """silu(gate) * up of mid (rows, 2F) = [gate | up] through float32,
    over the rows in use; zeros after them."""
    return _gated_silu_fwd(mid, n_used)[0]


def _gate_up(mid, start, size):
    return jnp.split(_window(mid, start, size).astype(jnp.float32), 2,
                     axis=-1)


def _gated_silu_fwd(mid, n_used):
    def chunk(start, size):
        gate, up = _gate_up(mid, start, size)
        return (jnp.where(_in_use(start, size, n_used),
                          jax.nn.silu(gate) * up, 0.0),)
    act, = _over_rows_in_use(
        n_used, mid, [((mid.shape[0], mid.shape[1] // 2), mid.dtype)], chunk)
    return act, (mid, n_used)


def _gated_silu_bwd(res, dact):
    mid, n_used = res

    def chunk(start, size):
        gate, up = _gate_up(mid, start, size)
        d = _window(dact, start, size).astype(jnp.float32)
        sig = jax.nn.sigmoid(gate)
        dgate = d * up * sig * (1.0 + gate * (1.0 - sig))
        return (jnp.where(_in_use(start, size, n_used), jnp.concatenate(
            [dgate, d * gate * sig], axis=-1), 0.0),)
    dmid, = _over_rows_in_use(
        n_used, (mid, dact), [(mid.shape, mid.dtype)], chunk)
    return dmid, None


_gated_silu.defvjp(_gated_silu_fwd, _gated_silu_bwd)


@jax.custom_vjp
def _relu2(mid, n_used):
    """relu(mid)^2 through float32 over the rows in use; zeros after
    them (the non-gated experts' activation)."""
    return _relu2_fwd(mid, n_used)[0]


def _relu2_fwd(mid, n_used):
    def chunk(start, size):
        up = jax.nn.relu(_window(mid, start, size).astype(jnp.float32))
        return (jnp.where(_in_use(start, size, n_used), up * up, 0.0),)
    act, = _over_rows_in_use(n_used, mid, [(mid.shape, mid.dtype)], chunk)
    return act, (mid, n_used)


def _relu2_bwd(res, dact):
    mid, n_used = res

    def chunk(start, size):
        up = jax.nn.relu(_window(mid, start, size).astype(jnp.float32))
        d = _window(dact, start, size).astype(jnp.float32)
        return (jnp.where(_in_use(start, size, n_used), 2.0 * up * d, 0.0),)
    dmid, = _over_rows_in_use(
        n_used, (mid, dact), [(mid.shape, mid.dtype)], chunk)
    return dmid, None


_relu2.defvjp(_relu2_fwd, _relu2_bwd)


@jax.custom_vjp
def _combine(ys, gates, routed):
    """y[t] = sum over the assignments (t, c) held here of gates[t, c]
    times their row of ys, float32 sum, ys' dtype.  The forward reads
    the gates from ``routed``'s sorted copies; the backward rule gives
    ``gates`` (T, k) their gradient."""
    return _combine_fwd(ys, gates, routed)[0]


def _combine_fwd(ys, gates, routed):
    return _sum_by_token(ys, routed, True), (ys, routed)


def _combine_bwd(res, dy):
    ys, routed = res
    rows, n_used = ys.shape[0], routed.n_used

    def chunk(start, size):
        # in expert order: a row's token's dy, once for both results
        dyt = _take(dy, _window(routed.token, start, size)).astype(
            jnp.float32)
        live = _in_use(start, size, n_used)
        dys = _window(routed.gate, start, size)[:, None] * dyt
        dgate = jnp.sum(dyt * _window(ys, start, size).astype(jnp.float32),
                        axis=-1)
        return jnp.where(live, dys, 0.0), jnp.where(live[:, 0], dgate, 0.0)
    dys, dgate = _over_rows_in_use(
        n_used, (dy, ys), [(ys.shape, ys.dtype), ((rows,), jnp.float32)],
        chunk)
    # back to assignment order by a sort on the assignment each row
    # holds (the rows past ``rows`` hold none that is here: zeros)
    order = routed.order
    _, dgate = jax.lax.sort(
        (order, jnp.pad(dgate, (0, order.shape[0] - rows))), num_keys=1)
    return dys, dgate.reshape(-1, order.shape[0] // routed.last.shape[0]), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _route(gates, experts, expert_offset, held):
    """gates, experts (T, k) of ``route_topk`` -> (``_Routed``, counts
    (held,)) for the holder of experts ``expert_offset .. + held``."""
    t, top_k = experts.shape
    local = experts - expert_offset
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(-1)
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :],
                     axis=0, dtype=jnp.int32)
    n_used = jnp.sum(counts)
    rows = t * min(top_k, held)
    # the moves read where a row went from sorted copies, scalars too: a
    # sort carries them, a gather would look each one up
    every = jnp.arange(t * top_k, dtype=jnp.int32)
    _, order, gate = jax.lax.sort(
        (key, every, jax.lax.stop_gradient(gates).reshape(-1)),
        num_keys=1, is_stable=True)
    assignment, row, gate_by_token = jax.lax.sort(
        (jnp.where(every < n_used, order, t * top_k), every, gate),
        num_keys=1)
    return _Routed(
        n_used, order, order[:rows] // top_k, gate[:rows],
        row[:rows], gate_by_token[:rows], assignment[:rows] // top_k,
        jnp.cumsum(jnp.sum(here, axis=-1, dtype=jnp.int32)) - 1,
        jnp.any(here, axis=-1)), counts


def dropless_moe(x, router, gate_up, down, *, top_k: int,
                 expert_offset: int = 0, norm_topk_prob: bool = True,
                 score: str = "softmax", selection_bias=None,
                 routed_scale: float = 1.0, shared=None,
                 activation: str = "swiglu"):
    """One holder's share of a dropless top-k expert layer.

    x (T, H); router (H, E) over ALL E experts; this holder's experts
    ``expert_offset .. expert_offset + held``: gate_up (held, H, 2F) =
    [gate | up], down (held, F, H).  Returns (y (T, H), counts (held,)
    int32):

        y[t] = sum over e in top_k(t), e held here, of
               g[t, e] * down_e( silu(gate_e x[t]) * up_e x[t] )

    with g from ``route_topk`` (normalised over all k chosen experts,
    held here or not; ``score``, ``selection_bias`` and
    ``routed_scale`` are its), and counts[e] the tokens routed to each
    held expert.  ``activation`` ``"relu2"``: non-gated experts,
    down_e(relu(up_e x[t])^2) with gate_up the up matrices (held, H, F).
    ``shared`` = (gate_up (H, 2F'), down (F', H)), (H, F') under
    ``"relu2"``: an expert every token goes through, which this holder
    computes for all of its tokens and adds (``apex_moe/shared``).  No
    token is dropped: the assignments routed here are sorted by expert
    into a buffer of T * min(k, held) rows (all that can arrive), and
    the moves, the activation, the two grouped products and their
    backward passes run over the rows in use
    (``sum(counts)``, in chunks of ``_CHUNK``): the layer costs what is
    routed to it, up to the whole buffer when everything is.
    What the other holders' experts add is left out: the holders' parts
    sum to the whole layer."""
    if activation not in ("swiglu", "relu2"):
        raise ValueError(f"dropless_moe: unknown activation {activation!r}")
    gates, experts = route_topk(x, router, top_k, norm_topk_prob,
                                score=score, selection_bias=selection_bias,
                                routed_scale=routed_scale)
    with jax.named_scope("apex_moe/dispatch"):
        routed, counts = _route(gates, experts, expert_offset,
                                gate_up.shape[0])
        xs = _dispatch(x, routed)
    with jax.named_scope("apex_moe/experts"):
        prec = matmul_precision(x.dtype)
        mid = jax.lax.ragged_dot(xs, gate_up.astype(x.dtype), counts,
                                 precision=prec)
        if activation == "relu2":
            act = _relu2(mid, routed.n_used)
        else:
            with jax.named_scope("apex_swiglu"):
                act = _gated_silu(mid, routed.n_used)
        ys = jax.lax.ragged_dot(act, down.astype(x.dtype), counts,
                                precision=prec)
    with jax.named_scope("apex_moe/combine"):
        y = _combine(ys, gates, routed)
    if shared is not None:
        y = (y.astype(jnp.float32)
             + _shared_expert(x, *shared, activation)).astype(y.dtype)
    return y, counts


def dropless_moe_ref(x, router, gate_up, down, *, top_k: int,
                     expert_offset: int = 0, norm_topk_prob: bool = True,
                     score: str = "softmax", selection_bias=None,
                     routed_scale: float = 1.0, shared=None,
                     activation: str = "swiglu"):
    """Dense oracle of ``dropless_moe``: every held expert applied to
    every token, weighted by its gate where chosen."""
    gates, experts = route_topk(x, router, top_k, norm_topk_prob,
                                score=score, selection_bias=selection_bias,
                                routed_scale=routed_scale)
    held = gate_up.shape[0]
    xf = x.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    y = jnp.zeros(x.shape, jnp.float32)
    counts = []

    def ffn(w_in, w_out):
        mid = jnp.dot(xf, w_in.astype(jnp.float32), precision=hi)
        if activation == "relu2":
            act = jnp.square(jax.nn.relu(mid))
        else:
            gate, up = jnp.split(mid, 2, axis=-1)
            act = jax.nn.silu(gate) * up
        return jnp.dot(act, w_out.astype(jnp.float32), precision=hi)

    for e in range(held):
        chosen = experts == expert_offset + e
        g = jnp.sum(jnp.where(chosen, gates, 0.0), axis=-1)
        y = y + g[:, None] * ffn(gate_up[e], down[e])
        counts.append(jnp.sum(chosen))
    if shared is not None:
        y = y + ffn(*shared)
    return y.astype(x.dtype), jnp.stack(counts).astype(jnp.int32)


class DroplessMoE(nn.Module):
    """``dropless_moe`` with its parameters: ``router`` (H, num_experts),
    ``gate_up`` (experts_held, H, 2 * ffn_hidden_size), ``down``
    (experts_held, ffn_hidden_size, H), float32, normal(0.02) (the down
    matrices at 0.02 times ``down_init_scale``).  With
    ``score`` ``"sigmoid"`` also ``selection_bias`` (num_experts,),
    zeros, which the gradient does not reach (a balancing rule would
    write it between steps); with ``shared_ffn_hidden_size`` also
    ``shared_gate_up`` (H, 2 * that) and ``shared_down``.  With
    ``activation`` ``"relu2"`` (non-gated experts) ``up`` (experts_held,
    H, ffn_hidden_size) in place of ``gate_up``, and ``shared_up`` (H,
    shared_ffn_hidden_size) in place of ``shared_gate_up``.
    ``__call__(x (T, H)) -> (y, counts)``."""
    hidden_size: int
    ffn_hidden_size: int
    num_experts: int                  # the router's width
    experts_held: int
    top_k: int
    expert_offset: int = 0
    norm_topk_prob: bool = True
    score: str = "softmax"
    routed_scale: float = 1.0
    shared_ffn_hidden_size: int = 0
    activation: str = "swiglu"
    down_init_scale: float = 1.0      # the down matrices' normal: 0.02 x

    @nn.compact
    def __call__(self, x):
        h, f = self.hidden_size, self.ffn_hidden_size
        if not 0 <= self.expert_offset <= self.num_experts - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are "
                f"not among the router's {self.num_experts}")
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (h, self.num_experts),
                            jnp.float32)
        gated = self.activation != "relu2"
        w_in, ways = ("gate_up", 2) if gated else ("up", 1)
        gate_up = self.param(w_in, init, (self.experts_held, h, ways * f),
                             jnp.float32)
        down_init = nn.initializers.normal(0.02 * self.down_init_scale)
        down = self.param("down", down_init, (self.experts_held, f, h),
                          jnp.float32)
        bias = shared = None
        if self.score == "sigmoid":
            bias = self.param("selection_bias", nn.initializers.zeros,
                              (self.num_experts,), jnp.float32)
        if self.shared_ffn_hidden_size:
            fs = self.shared_ffn_hidden_size
            shared = (self.param(f"shared_{w_in}", init, (h, ways * fs),
                                 jnp.float32),
                      self.param("shared_down", down_init, (fs, h),
                                 jnp.float32))
        return dropless_moe(x, router, gate_up, down, top_k=self.top_k,
                            expert_offset=self.expert_offset,
                            norm_topk_prob=self.norm_topk_prob,
                            score=self.score, selection_bias=bias,
                            routed_scale=self.routed_scale, shared=shared,
                            activation=self.activation)
