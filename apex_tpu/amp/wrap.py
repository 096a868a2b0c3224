"""The O1 casting engine: a trace-time precision rewriter.

Reference: apex/amp/wrap.py + utils.py + opt.py (~900 LoC, SURVEY.md
§2.1): the reference monkey-patches every listed torch function with a
wrapper that casts inputs per the FP16/FP32 lists and caches parameter
casts.  Monkey-patching has no JAX analog — but it doesn't need one:
under `jit` every op is already visible at trace time.  ``auto_cast``
traces the UNMODIFIED function to a jaxpr, then re-evaluates it with
per-primitive dtype rules from apex_tpu.amp.lists:

- HALF_PRIMS (GEMM/conv)        -> operands cast to compute_dtype
- FP32_PRIMS (exp/log/sums/...) -> operands cast to f32
- everything else               -> mixed float operands promote to the
                                   widest dtype present (reference CASTS)

so ``amp.initialize(..., "O1")`` changes an arbitrary model's precision
with zero edits to the model.  The rewrite composes with jit/grad/vmap
(it is itself a tracing transform), and the reference's "cast cache"
falls out for free: a param cast appearing once in the jaxpr is one op
in the compiled program, CSE'd and fused by XLA.

Call-like primitives are recursed into (pjit/remat/custom_jvp), and so
is structured control flow: ``scan`` / ``while`` / ``cond`` bodies are
re-traced with the same per-primitive rules, with loop state cast back
to its traced dtype at every iteration boundary so the loop stays
well-typed (the reference reaches ops inside RNN loops the same way,
via rnn_compat).  Only genuinely dtype-bound opaque primitives
(custom_vjp, pallas_call — e.g. this package's own kernels, which
already manage precision internally) run unmodified at their traced
dtypes.
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.extend import source_info_util
from jax.extend.core import ClosedJaxpr, Literal

from apex_tpu.amp import lists
from apex_tpu.amp.policies import Policy


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.floating)


@jax.named_scope("apex_amp/cast")
def _cast_floats(vals, dtype):
    return [v.astype(dtype) if _is_float(v)
            and jnp.result_type(v) != dtype else v for v in vals]


def _promote_floats(vals):
    """Reference CASTS semantics: widen mixed float operands."""
    fdts = {jnp.result_type(v) for v in vals if _is_float(v)}
    if len(fdts) <= 1:
        return vals
    widest = functools.reduce(jnp.promote_types, fdts)
    return _cast_floats(vals, widest)


@jax.named_scope("apex_amp/cast")
def _restore_dtypes(vals, invars):
    """Cast drifted operands back to the dtypes the eqn was traced at
    (used for opaque primitives whose sub-jaxprs are dtype-bound)."""
    out = []
    for v, var in zip(vals, invars):
        aval = var.aval
        if (_is_float(v) and hasattr(aval, "dtype")
                and jnp.result_type(v) != aval.dtype):
            v = v.astype(aval.dtype)
        out.append(v)
    return out


def _half_params(params, half):
    """For HALF prims: drop a traced f32 accumulation hint so the output
    comes back in compute dtype (XLA still accumulates bf16 dots in f32
    on the MXU)."""
    if params.get("preferred_element_type") is not None:
        p = dict(params)
        if jnp.issubdtype(p["preferred_element_type"], jnp.floating):
            p["preferred_element_type"] = jnp.dtype(half)
        return p
    return params


@jax.named_scope("apex_amp/cast")
def _cast_to_dtypes(vals, dtypes):
    """Cast each float val back to its traced dtype (None = leave)."""
    return [v.astype(d) if d is not None and _is_float(v)
            and jnp.result_type(v) != d else v
            for v, d in zip(vals, dtypes)]


def _aval_dtypes(vars_):
    return [v.aval.dtype for v in vars_]


def _rewrite_scan(vals, params, half):
    """Re-issue a scan with its body O1-rewritten.  The carry is cast
    back to its traced dtype each iteration (dtype-coherent boundary);
    ops INSIDE the body follow the normal HALF/FP32/promote rules."""
    body = params["jaxpr"]                      # ClosedJaxpr
    C, K = params["num_consts"], params["num_carry"]
    consts, init, xs = vals[:C], vals[C:C + K], vals[C + K:]
    carry_dts = _aval_dtypes(body.jaxpr.invars[C:C + K])

    def new_body(carry, x):
        ins = list(consts) + list(carry) + list(x)
        outs = _eval_jaxpr(body.jaxpr, body.consts, ins, half)
        return (tuple(_cast_to_dtypes(outs[:K], carry_dts)),
                tuple(outs[K:]))

    carry_out, ys = jax.lax.scan(
        new_body, tuple(init), tuple(xs), length=params.get("length"),
        reverse=params.get("reverse", False),
        unroll=params.get("unroll", 1))
    return list(carry_out) + list(ys)


def _rewrite_while(vals, params, half):
    """Re-issue a while_loop with cond/body O1-rewritten; loop state is
    cast back to its traced dtype after every body application."""
    cj, bj = params["cond_jaxpr"], params["body_jaxpr"]
    cn, bn = params["cond_nconsts"], params["body_nconsts"]
    cc, bc, init = vals[:cn], vals[cn:cn + bn], vals[cn + bn:]
    carry_dts = _aval_dtypes(bj.jaxpr.invars[bn:])

    def cond_fn(carry):
        return _eval_jaxpr(cj.jaxpr, cj.consts,
                           list(cc) + list(carry), half)[0]

    def body_fn(carry):
        outs = _eval_jaxpr(bj.jaxpr, bj.consts,
                           list(bc) + list(carry), half)
        return tuple(_cast_to_dtypes(outs, carry_dts))

    return list(jax.lax.while_loop(cond_fn, body_fn, tuple(init)))


def _rewrite_cond(vals, params, outvars, half):
    """Re-issue a cond/switch with every branch O1-rewritten.  Branch
    outputs are cast back to the traced output dtypes — the branches
    must agree on out avals, and after an asymmetric rewrite (a GEMM in
    one branch, a pass-through in the other) they wouldn't."""
    out_dts = [getattr(v.aval, "dtype", None) for v in outvars]
    idx, ops = jnp.asarray(vals[0]), vals[1:]
    if idx.dtype == jnp.bool_:
        idx = idx.astype(jnp.int32)

    def mk(br):
        def f(*ops_):
            outs = _eval_jaxpr(br.jaxpr, br.consts, list(ops_), half)
            return tuple(_cast_to_dtypes(outs, out_dts))
        return f

    return list(jax.lax.switch(idx, [mk(b) for b in params["branches"]],
                               *ops))


def _iter_sub_jaxprs(params):
    """Yield every (Closed)Jaxpr reachable from an eqn's params —
    wherever the primitive stashed it (jaxpr/call_jaxpr/branches/
    cond_jaxpr/...), including inside lists/tuples.  Thunks and other
    callables are not forced."""
    stack = list(params.values())
    while stack:
        v = stack.pop()
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            stack.extend(v)
        elif hasattr(v, "eqns") and hasattr(v, "invars"):  # raw Jaxpr
            yield v


def _contains_half_prims(jaxpr) -> bool:
    """Does this sub-jaxpr reach any HALF-list op (GEMM/conv) that O1
    would have rewritten?  ``pallas_call`` interiors don't count: a
    kernel body's dtypes are chosen explicitly by its author (this
    package's kernels manage precision internally), so dots inside one
    are not missed casts."""
    for eqn in jaxpr.eqns:
        nm = eqn.primitive.name
        if nm == "pallas_call":
            continue
        if nm in lists.HALF_PRIMS:
            return True
        for sub in _iter_sub_jaxprs(eqn.params):
            if _contains_half_prims(sub):
                return True
    return False


_OPAQUE_WARNED: set = set()


def _invar_sig(invars):
    return tuple((getattr(v.aval, "shape", None),
                  str(getattr(v.aval, "dtype", None))) for v in invars)


def _body_sig(params, cap=64):
    """Light content fingerprint of an opaque primitive's body: the
    primitive-name sequence of its sub-jaxprs (capped).  Distinguishes
    two different user ops that happen to share operand shapes; two
    ops identical in BOTH operands and op sequence would produce the
    same warning text anyway."""
    names = []
    for sub in _iter_sub_jaxprs(params):
        for eqn in sub.eqns:
            names.append(eqn.primitive.name)
            if len(names) >= cap:
                return tuple(names)
    return tuple(names)


def _warn_opaque(name: str, params, invars) -> None:
    """Honesty warning (VERDICT r3 #4): an opaque primitive whose body
    contains listed GEMMs runs UNREWRITTEN under O1 — the user should
    hear that, not discover it in a profile.  Deduped per (primitive,
    operand signature, body fingerprint) so DISTINCT skipped ops each
    warn once (every user custom_vjp shares one primitive name).  A
    direct pallas_call is itself a kernel body — precision-explicit by
    design, never warned about."""
    if name == "pallas_call":
        return
    key = (name, _invar_sig(invars), _body_sig(params))
    if key in _OPAQUE_WARNED:
        return
    if any(_contains_half_prims(s) for s in _iter_sub_jaxprs(params)):
        _OPAQUE_WARNED.add(key)
        warnings.warn(
            f"amp O1: primitive '{name}' (operands "
            f"{[s for s, _ in key[1]]}) is opaque to the casting "
            "engine but its body contains matmul/conv ops that would "
            "otherwise run in the compute dtype; they will run at "
            "their traced (likely f32) precision. Cast its inputs "
            "explicitly, or apply apex_tpu.amp.auto_cast inside the "
            "custom function, to opt those ops into mixed precision.",
            stacklevel=2)


def _bind(prim, vals, params):
    """Re-issue an eqn the way core.eval_jaxpr does: get_bind_params
    recovers callable sub-arguments (custom_vjp's fun/fwd/bwd, ...)
    that live in eqn.params but bind positionally."""
    subfuns, bind_params = prim.get_bind_params(params)
    ans = prim.bind(*subfuns, *vals, **bind_params)
    return ans if prim.multiple_results else [ans]


def _eval_jaxpr(jaxpr, consts, args, half):
    env = {}

    def read(a):
        return a.val if isinstance(a, Literal) else env[a]

    for v, c in zip(jaxpr.constvars, consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a

    for eqn in jaxpr.eqns:
        prim = eqn.primitive
        name = prim.name
        vals = [read(x) for x in eqn.invars]
        params = eqn.params

        # re-issue the equation under the name stack it was traced
        # with (as core.eval_jaxpr does): the ``apex_*`` scopes of the
        # wrapped function must reach the rewritten program's op names
        with source_info_util.user_context(
                eqn.source_info.traceback,
                name_stack=source_info_util.current_name_stack()
                + eqn.source_info.name_stack):
            if name in lists.RECURSE_PRIMS:
                sub = params.get("jaxpr") or params.get("call_jaxpr")
                if sub is not None:
                    if isinstance(sub, ClosedJaxpr):
                        ans = _eval_jaxpr(sub.jaxpr, sub.consts, vals, half)
                    else:
                        ans = _eval_jaxpr(sub, (), vals, half)
                else:  # unexpected shape: run opaque
                    ans = _bind(prim, _restore_dtypes(vals, eqn.invars),
                                params)
            elif name in lists.HALF_PRIMS:
                ans = _bind(prim, _cast_floats(vals, half),
                            _half_params(params, half))
            elif name in lists.FP32_PRIMS:
                ans = _bind(prim, _cast_floats(vals, jnp.float32), params)
            elif name == "scan" and "jaxpr" in params:
                ans = _rewrite_scan(_restore_dtypes(vals, eqn.invars),
                                    params, half)
            elif name == "while" and "body_jaxpr" in params:
                ans = _rewrite_while(_restore_dtypes(vals, eqn.invars),
                                     params, half)
            elif name == "cond" and "branches" in params:
                ans = _rewrite_cond(_restore_dtypes(vals, eqn.invars),
                                    params, eqn.outvars, half)
            elif "jaxpr" in params or "call_jaxpr" in params or \
                    "branches" in params or "cond_jaxpr" in params or \
                    "fwd_jaxpr_thunk" in params or "num_consts" in params:
                # opaque (custom_vjp, pallas_call, ...): dtype-bound bodies
                _warn_opaque(name, params, eqn.invars)
                ans = _bind(prim, _restore_dtypes(vals, eqn.invars), params)
            else:
                ans = _bind(prim, _promote_floats(vals), params)

        for v, a in zip(eqn.outvars, ans):
            env[v] = a

    return [read(v) for v in jaxpr.outvars]


def auto_cast(fn: Callable, policy: Optional[Policy] = None,
              compute_dtype: Any = None) -> Callable:
    """Wrap ``fn`` so listed ops run at the policy's precision.

    The O1 engine: ``fn`` is any jax-traceable callable (a flax
    ``model.apply``, a bare function, a whole train-step body).  Returns
    a callable computing the same function with GEMMs/convs in
    ``compute_dtype`` and fragile ops in f32, per apex_tpu.amp.lists.

    No-op (returns ``fn`` unchanged) when the compute dtype is f32.
    """
    half = jnp.dtype(compute_dtype if compute_dtype is not None
                     else (policy.compute_dtype if policy is not None
                           else jnp.bfloat16))
    if half == jnp.dtype(jnp.float32):
        return fn

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        flat, in_tree = jax.tree_util.tree_flatten((args, kwargs))

        def flat_fn(*xs):
            a, kw = jax.tree_util.tree_unflatten(in_tree, xs)
            return fn(*a, **kw)

        closed, out_shape = jax.make_jaxpr(
            flat_fn, return_shape=True)(*flat)
        out_tree = jax.tree_util.tree_structure(out_shape)
        outs = _eval_jaxpr(closed.jaxpr, closed.consts, flat, half)
        return jax.tree_util.tree_unflatten(out_tree, outs)

    return wrapped


def cast_inputs(fn: Callable, dtype, argnums=None) -> Callable:
    """O2/O3 forward patch: cast floating inputs to the model dtype.

    Reference: apex/amp/_initialize.py patches ``model.forward`` to cast
    ``*args`` to the cast_model_type; this is the functional analog.
    ``argnums`` restricts casting to those positional args — functional
    code passes params/state as arguments too, and only the DATA inputs
    play the role of the reference's forward(*args).
    """
    dtype = jnp.dtype(dtype)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        cast = lambda x: (x.astype(dtype)
                          if hasattr(x, "dtype") and _is_float(x) else x)
        with jax.named_scope("apex_amp/cast"):
            if argnums is None:
                args = jax.tree_util.tree_map(cast, args)
                kwargs = jax.tree_util.tree_map(cast, kwargs)
            else:
                args = tuple(jax.tree_util.tree_map(cast, a)
                             if i in argnums else a
                             for i, a in enumerate(args))
        return fn(*args, **kwargs)

    return wrapped
