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

from typing import Callable, Optional, Tuple

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

def route_topk(x, router, top_k: int, norm_topk_prob: bool = True):
    """x (T, H), router (H, E) -> (gates (T, k) float32, experts (T, k)
    int32): the ``top_k`` largest of a float32 softmax over ALL E
    experts, renormalised over the k chosen when ``norm_topk_prob`` —
    whoever holds them."""
    with jax.named_scope("apex_moe/router"):
        logits = jnp.dot(x, router.astype(x.dtype),
                         preferred_element_type=jnp.float32,
                         precision=matmul_precision(x.dtype))
        probs = jax.nn.softmax(logits, axis=-1)
        _, experts = jax.lax.top_k(jax.lax.stop_gradient(probs), top_k)
        # the chosen probabilities by a one-hot select, not by top_k's
        # own values: those transpose to a scatter
        chosen = experts[..., None] == jnp.arange(probs.shape[-1])
        gates = jnp.sum(jnp.where(chosen, probs[:, None, :], 0.0), axis=-1)
        if norm_topk_prob:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


def _take(x, idx):
    return jnp.take(x, idx, axis=0, mode="clip")


# Both moves between token order and expert order are GATHERS in both
# directions (a row is looked up where it went, never scattered to
# where it goes): XLA's scatter-add runs elements one by one on the TPU
# (PERF.md section 6, PR 26).

@jax.custom_vjp
def _dispatch(x, source, slot, here):
    """Rows of x (T, H) in expert order: out[m] = x[source[m]]."""
    return _take(x, source)


def _dispatch_fwd(x, source, slot, here):
    return _take(x, source), (slot, here)


def _dispatch_bwd(res, g):
    slot, here = res
    picked = jnp.where(here[..., None], _take(g, slot), 0)
    dx = jnp.sum(picked.astype(jnp.float32), axis=1).astype(g.dtype)
    return dx, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, gates, slot, here, order):
    """y[t] = sum over the assignments (t, c) held here of
    gates[t, c] * ys[slot[t, c]], float32 sum, ys' dtype."""
    return _combine_fwd(ys, gates, slot, here, order)[0]


def _combine_fwd(ys, gates, slot, here, order):
    picked = jnp.where(here[..., None], _take(ys, slot), 0)
    y = jnp.sum(gates[..., None] * picked.astype(jnp.float32), axis=1)
    return y.astype(ys.dtype), (picked, gates, here, order, ys.shape[0])


def _combine_bwd(res, dy):
    picked, gates, here, order, rows = res
    k = gates.shape[1]
    dgates = jnp.where(here, jnp.sum(
        dy.astype(jnp.float32)[:, None] * picked.astype(jnp.float32),
        axis=-1), 0.0)
    first = order[:rows]                  # the assignment in each row
    weight = jnp.where(here.reshape(-1)[first],
                       gates.reshape(-1)[first], 0.0)
    dys = (weight[:, None] * _take(dy, first // k).astype(jnp.float32))
    return dys.astype(picked.dtype), dgates, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def dropless_moe(x, router, gate_up, down, *, top_k: int,
                 expert_offset: int = 0, norm_topk_prob: bool = True):
    """One holder's share of a dropless top-k expert layer.

    x (T, H); router (H, E) over ALL E experts; this holder's experts
    ``expert_offset .. expert_offset + held``: gate_up (held, H, 2F) =
    [gate | up], down (held, F, H).  Returns (y (T, H), counts (held,)
    int32):

        y[t] = sum over e in top_k(t), e held here, of
               g[t, e] * down_e( silu(gate_e x[t]) * up_e x[t] )

    with g from ``route_topk`` (normalised over all k chosen experts,
    held here or not), and counts[e] the tokens routed to each held
    expert.  No token is dropped: the assignments routed here are
    sorted by expert into a buffer of T * min(k, held) rows (all that
    can arrive), and the two grouped products run over the rows in use.
    What the other holders' experts add is left out: the holders' parts
    sum to the whole layer."""
    t, h = x.shape
    held = gate_up.shape[0]
    gates, experts = route_topk(x, router, top_k, norm_topk_prob)
    with jax.named_scope("apex_moe/dispatch"):
        local = experts - expert_offset
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(-1)
        counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :],
                         axis=0, dtype=jnp.int32)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        rows = t * min(top_k, held)
        slot = jnp.minimum(jnp.argsort(order).astype(jnp.int32),
                           rows - 1).reshape(t, top_k)
        xs = _dispatch(x, order[:rows] // top_k, slot, here)
    with jax.named_scope("apex_moe/experts"):
        prec = matmul_precision(x.dtype)
        mid = jax.lax.ragged_dot(xs, gate_up.astype(x.dtype), counts,
                                 precision=prec)
        with jax.named_scope("apex_swiglu"):
            gate, up = jnp.split(mid.astype(jnp.float32), 2, axis=-1)
            act = (jax.nn.silu(gate) * up).astype(x.dtype)
        ys = jax.lax.ragged_dot(act, down.astype(x.dtype), counts,
                                precision=prec)
    with jax.named_scope("apex_moe/combine"):
        y = _combine(ys, gates, slot, here, order)
    return y, counts


def dropless_moe_ref(x, router, gate_up, down, *, top_k: int,
                     expert_offset: int = 0, norm_topk_prob: bool = True):
    """Dense oracle of ``dropless_moe``: every held expert applied to
    every token, weighted by its gate where chosen."""
    gates, experts = route_topk(x, router, top_k, norm_topk_prob)
    held = gate_up.shape[0]
    xf = x.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    y = jnp.zeros(x.shape, jnp.float32)
    counts = []
    for e in range(held):
        chosen = experts == expert_offset + e
        g = jnp.sum(jnp.where(chosen, gates, 0.0), axis=-1)
        gate, up = jnp.split(jnp.dot(xf, gate_up[e].astype(jnp.float32),
                                     precision=hi), 2, axis=-1)
        out = jnp.dot(jax.nn.silu(gate) * up, down[e].astype(jnp.float32),
                      precision=hi)
        y = y + g[:, None] * out
        counts.append(jnp.sum(chosen))
    return y.astype(x.dtype), jnp.stack(counts).astype(jnp.int32)


class DroplessMoE(nn.Module):
    """``dropless_moe`` with its parameters: ``router`` (H, num_experts),
    ``gate_up`` (experts_held, H, 2 * ffn_hidden_size), ``down``
    (experts_held, ffn_hidden_size, H), float32, normal(0.02).
    ``__call__(x (T, H)) -> (y, counts)``."""
    hidden_size: int
    ffn_hidden_size: int
    num_experts: int                  # the router's width
    experts_held: int
    top_k: int
    expert_offset: int = 0
    norm_topk_prob: bool = True

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
        gate_up = self.param("gate_up", init,
                             (self.experts_held, h, 2 * f), jnp.float32)
        down = self.param("down", init, (self.experts_held, f, h),
                          jnp.float32)
        return dropless_moe(x, router, gate_up, down, top_k=self.top_k,
                            expert_offset=self.expert_offset,
                            norm_topk_prob=self.norm_topk_prob)
