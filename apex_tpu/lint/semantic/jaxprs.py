"""Shared jaxpr/HLO structural analysis for the semantic tier.

PRs 3 and 4 each hand-rolled a recursive jaxpr walk in their tests to
prove "zero transfer primitives, N pallas_calls, one concatenate per
bucket" for one entry point.  This module is that walk, once, as a
library: the invariant verifier (semantic/registry.py) and the tests
both consume it, so an assertion can never be weaker in one place
than the other.

Everything operates on a ``ClosedJaxpr`` (or raw ``Jaxpr``) and
recurses into every sub-jaxpr carried in equation params (cond/scan
branches, pjit bodies, custom_vjp calls), exactly like the original
test walkers did.  The HLO-side check (donation) reads the lowered
StableHLO text — ``tf.aliasing_output`` argument attributes are how
XLA records input-output aliasing — without compiling anything.
"""

from __future__ import annotations

import collections
from typing import Callable, Iterator, List, Set, Tuple

# primitive-name substrings that mean "the host is involved": callbacks
# (pure_callback/io_callback/debug_callback), infeed/outfeed, explicit
# host pulls.  Matched as substrings, as the original tests did, so
# renamed variants (callback_p -> io_callback) keep matching.
# ``device_put`` is deliberately NOT here: jax emits a benign
# device=None/ALIAS device_put inside e.g. segment_sum, and the
# in-jit host-offload placement is an intended overlapped DMA — the
# hazard this invariant polices is the host BLOCKING on the device.
HOST_TRANSFER_MARKERS = ("callback", "infeed", "outfeed", "host",
                         "device_get")

# collective primitives (named-axis); psum shows up as "psum" in 0.4.x
COLLECTIVE_PRIMS = {"psum", "pmax", "pmin", "pmean", "all_gather",
                    "all_to_all", "reduce_scatter", "psum_scatter",
                    "ppermute", "axis_index", "pbroadcast"}


def _as_jaxpr(j):
    return getattr(j, "jaxpr", j)


def iter_eqns(jaxpr) -> Iterator:
    """Every equation in ``jaxpr`` and (recursively) its sub-jaxprs."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(j, "jaxpr"):
                    yield from iter_eqns(j.jaxpr)
                elif hasattr(j, "eqns"):
                    yield from iter_eqns(j)


def walk(jaxpr, visit: Callable) -> None:
    """Call ``visit(eqn)`` on every equation (the PR 4 test's shape)."""
    for eqn in iter_eqns(jaxpr):
        visit(eqn)


def primitive_counts(jaxpr) -> collections.Counter:
    return collections.Counter(e.primitive.name for e in iter_eqns(jaxpr))


def concat_out_shapes(jaxpr) -> List[Tuple[int, ...]]:
    """Output shapes of every FLOATING ``concatenate`` — the
    gradient-pack signature: a pack shows up as exactly one bucket-sized
    concat.  Integer concatenates are not packs.  LAMB's and NovoGrad's
    per-element factor (each tensor's scalar broadcast over its static
    extent) is a second bucket-sized floating one; their specs count
    it."""
    import jax.numpy as jnp       # bf16-aware, unlike numpy's issubdtype
    return [tuple(e.outvars[0].aval.shape) for e in iter_eqns(jaxpr)
            if e.primitive.name == "concatenate"
            and jnp.issubdtype(e.outvars[0].aval.dtype, jnp.floating)]


def host_transfer_prims(jaxpr) -> List[str]:
    """Primitive names that move data to/from the host."""
    return sorted({e.primitive.name for e in iter_eqns(jaxpr)
                   if any(m in e.primitive.name
                          for m in HOST_TRANSFER_MARKERS)})


def fp8_convert_counts(jaxpr) -> dict:
    """Quantize-op census: how many ``convert_element_type`` equations
    produce each fp8 dtype (``{"e4m3": n, "e5m2": m}``, absent = 0).
    THE count the fp8 specs pin exactly — a refactor that re-quantizes
    an operand per consumer (instead of sharing one cast) multiplies
    silently and shows up here."""
    import numpy as np
    out: dict = {}
    for e in iter_eqns(jaxpr):
        if e.primitive.name != "convert_element_type":
            continue
        name = np.dtype(e.params.get("new_dtype", "f4")).name
        if name.startswith("float8_e4m3"):
            out["e4m3"] = out.get("e4m3", 0) + 1
        elif name.startswith("float8_e5m2"):
            out["e5m2"] = out.get("e5m2", 0) + 1
    return out


def int8_convert_counts(jaxpr) -> dict:
    """Int8 cast census for the quantized KV arena: how many
    ``convert_element_type`` equations cast INTO int8 (``to_int8``,
    the quantize-on-scatter side) and how many cast an int8 operand
    OUT (``from_int8``, the dequantize-in-gather side).  The
    ``serving.decode_step_quantized`` spec pins both exactly — one per
    arena side per step; a refactor that dequantizes per layer (or
    re-quantizes per consumer) multiplies the cast count silently and
    shows up here."""
    import numpy as np
    i8 = np.dtype("int8")
    out: dict = {}
    for e in iter_eqns(jaxpr):
        if e.primitive.name != "convert_element_type":
            continue
        if _np_dtype_or_none(e.params.get("new_dtype", "f4")) == i8:
            out["to_int8"] = out.get("to_int8", 0) + 1
        elif any(getattr(iv, "aval", None) is not None
                 and _np_dtype_or_none(
                     getattr(iv.aval, "dtype", None)) == i8
                 for iv in e.invars):
            out["from_int8"] = out.get("from_int8", 0) + 1
    return out


def _np_dtype_or_none(dtype):
    """``np.dtype(...)`` that tolerates JAX extended dtypes (typed
    PRNG keys like ``key<fry>`` have no numpy equivalent — and
    ``np.dtype`` COERCES them to f64 rather than raising, which would
    misread every RNG op as a float64 leak) — an extended dtype is by
    construction not f64/int8, so the census checkers skip it."""
    import numpy as np
    from jax import dtypes as _jd
    try:
        if dtype is not None and _jd.issubdtype(dtype, _jd.extended):
            return None
        return np.dtype(dtype)
    except TypeError:
        return None


def f64_values(jaxpr) -> List[str]:
    """Evidence of float64 entering the program: any
    ``convert_element_type`` to f64, or any equation output aval in
    f64 (TPU has no f64 units — silent downcast or slow path)."""
    import numpy as np
    f64 = np.dtype("float64")
    bad: List[str] = []
    for e in iter_eqns(jaxpr):
        # NB: the None checks are load-bearing — numpy treats None as
        # "the default dtype" in comparisons, i.e. f64 == None is True
        nd = _np_dtype_or_none(e.params.get("new_dtype", "f4"))
        if e.primitive.name == "convert_element_type" \
                and nd is not None and nd == f64:
            bad.append("convert_element_type->float64")
        else:
            for v in e.outvars:
                aval = getattr(v, "aval", None)
                if aval is None or getattr(aval, "dtype", None) is None:
                    continue
                dt = _np_dtype_or_none(aval.dtype)
                if dt is not None and dt == f64:
                    bad.append(f"{e.primitive.name}: f64 output")
                    break
    return bad


def collective_axis_names(jaxpr) -> Set[str]:
    """Every named axis any collective in the program reduces over."""
    axes: Set[str] = set()
    for e in iter_eqns(jaxpr):
        if e.primitive.name not in COLLECTIVE_PRIMS:
            continue
        raw = e.params.get("axes", e.params.get("axis_name", ()))
        for a in (raw if isinstance(raw, (tuple, list)) else (raw,)):
            if isinstance(a, str):
                axes.add(a)
    return axes


def orphan_collectives(jaxpr) -> List[str]:
    """Collectives whose every output is dead — unread by any later
    equation and not a jaxpr output.  A dead collective still executes
    on every rank (and tripped the SPMD partitioner in the
    ring-attention non-causal path); the program should not carry one.
    Checked per (sub)jaxpr, conservatively: a value returned upward
    counts as live."""
    dead: List[str] = []

    def scan(j):
        j = _as_jaxpr(j)
        live = {id(v) for v in j.outvars}
        for eqn in j.eqns:
            live.update(id(v) for v in eqn.invars)
        for eqn in j.eqns:
            if eqn.primitive.name in COLLECTIVE_PRIMS and \
                    not any(id(v) in live for v in eqn.outvars):
                dead.append(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "jaxpr"):
                        scan(sub.jaxpr)
                    elif hasattr(sub, "eqns"):
                        scan(sub)

    scan(jaxpr)
    return dead


def collective_compute_cones(jaxpr, compute_prims=("dot_general",)):
    """Per-scope dependency-cone analysis of the collectives — the
    interleaved-schedule invariant (ROADMAP item 2) made structural.

    For every (sub)jaxpr scope containing collectives, returns
    ``{"collectives": [{"prim", "cone_compute", "cone"}, ...],
    "total_compute": n}`` — per collective its primitive name, the
    NUMBER of compute equations in its transitive input cone, and the
    cone itself as a frozenset of compute-equation indices (so two
    equal-sized but different cones stay distinguishable).  The cone
    of an equation is its transitive input set within the scope (an
    equation carrying nested sub-jaxprs counts their compute
    atomically).  A TRAILING schedule
    is the pathology where every collective's cone contains ALL of the
    program's compute — the reduce depends on the entire backward, so
    no scheduler can overlap it.  An interleaved (chunked-bucket)
    schedule shows collectives whose cones are proper, pairwise
    distinct subsets: bucket k's psum is schedulable while the
    remaining buckets' compute still runs.  This is the property the
    latency-hiding scheduler exploits; the runtime twin is the
    profiler's hidden-overlap fraction
    (telemetry/profiler/attribution.py)."""
    out: List[dict] = []

    def nested_compute(eqn) -> int:
        n = 0
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(sub, "jaxpr",
                            sub if hasattr(sub, "eqns") else None)
                if j is not None:
                    for e in j.eqns:
                        if e.primitive.name in compute_prims:
                            n += 1
                        n += nested_compute(e)
        return n

    def scan(j):
        j = _as_jaxpr(j)
        eqns = j.eqns
        producer = {}
        own = [1 if e.primitive.name in compute_prims else 0
               for e in eqns]
        nested = [nested_compute(e) for e in eqns]
        cone: List[Set[int]] = [set() for _ in eqns]
        for i, e in enumerate(eqns):
            deps: Set[int] = set()
            for v in e.invars:
                pi = producer.get(id(v))
                if pi is not None:
                    deps.add(pi)
                    deps |= cone[pi]
            cone[i] = deps
            for v in e.outvars:
                producer[id(v)] = i
        total = sum(own) + sum(nested)
        colls = [
            {"prim": e.primitive.name,
             "cone_compute": sum(own[d] + nested[d] for d in cone[i]),
             "cone": frozenset(d for d in cone[i]
                               if own[d] or nested[d])}
            for i, e in enumerate(eqns)
            if e.primitive.name in COLLECTIVE_PRIMS
            and e.primitive.name != "axis_index"]
        if colls:
            out.append({"collectives": colls, "total_compute": total})
        for e in eqns:
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    jj = getattr(sub, "jaxpr",
                                 sub if hasattr(sub, "eqns") else None)
                    if jj is not None:
                        scan(jj)

    scan(jaxpr)
    return out


def donated_alias_count(lowered_text: str) -> int:
    """How many input buffers the lowered module gives to outputs —
    ``tf.aliasing_output`` argument attributes in StableHLO are the
    trace of ``donate_argnums`` actually taking effect (a donation
    jax could not match to an output simply lacks the attribute).
    Where the outputs' shardings are the compiler's to choose (a step
    over a mesh) jax marks the donor ``jax.buffer_donor`` instead and
    XLA pairs it with an output; an argument carries one or the
    other."""
    return (lowered_text.count("tf.aliasing_output")
            + lowered_text.count("jax.buffer_donor"))
