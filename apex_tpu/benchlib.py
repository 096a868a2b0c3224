"""Amortized on-device timing.

Every dispatch of a jitted program costs host wall time (launch,
argument handling, the completion round trip), and a loop of
dispatches that each wait for completion pays it per call.
Microkernels in the 50 µs - 5 ms range are therefore distorted by
dispatch-per-iteration timing: the fixed overhead compresses every
speedup ratio toward 1.

The fix is structural: run the measured function N times SERIALLY
INSIDE one compiled program (``lax.fori_loop``), so one dispatch
amortizes over N executions.  Each iteration's inputs and EVERY
output leaf pass through one ``lax.optimization_barrier`` whose
results all feed the next iteration's carry: the barrier pins every
output to be computed in full (no dead-code elimination, no slicing
the computation down to the one element a naive dependence would
read), and the carry's dependence on the outputs stops
loop-invariant hoisting and cross-iteration CSE.  A scalar built from
every barrier result gates a no-op select on the carried leaf — the
select's predicate is data-dependent (the compiler cannot fold it),
but when the outputs are finite it selects the ORIGINAL leaf, so the
carried values are bit-identical across iterations, zeros and -0.0
included.

``dispatch_overhead_ms`` measures that per-call overhead on the
running machine, so a record can carry the number its timings were
amortized against.
"""

from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["chunked_train_bench", "cost_flops", "dispatch_overhead_ms",
           "loop_on_device", "noise_floor_pct", "sync", "timeit"]


def sync(o) -> None:
    """Wait until every array in ``o`` is computed."""
    jax.block_until_ready(o)


def loop_on_device(f, n: int):
    """jit-compiled ``g(*args)`` running ``f`` ``n`` times serially on
    device with an iteration-to-iteration data dependence (see module
    docstring).  ``f``'s positional args must be arrays (pytrees of
    arrays work); close over static configuration."""

    def g(*args):
        flat, treedef = jax.tree_util.tree_flatten(args)
        idx = next((i for i, a in enumerate(flat)
                    if jnp.issubdtype(a.dtype, jnp.floating)), 0)

        def body(_, fl):
            out = f(*jax.tree_util.tree_unflatten(treedef, fl))
            out_leaves = jax.tree_util.tree_leaves(out)
            tied = lax.optimization_barrier(tuple(fl)
                                            + tuple(out_leaves))
            new_fl = list(tied[:len(fl)])
            # one scalar per barrier result keeps every result live;
            # when the outputs are finite the where selects the
            # original leaf bit-exactly (a NaN output poisons the
            # carry — benched functions are expected to stay finite)
            s = sum((t.ravel()[0] if t.ndim else t).astype(jnp.float32)
                    for t in tied[len(fl):])
            new_fl[idx] = jnp.where(
                jnp.isnan(s),
                jnp.asarray(s, dtype=new_fl[idx].dtype), new_fl[idx])
            return new_fl

        return lax.fori_loop(0, n, body, flat)

    return jax.jit(g)


def timeit(f, *args, iters: int = 20, reps: int = 3,
           adaptive: bool = False) -> float:
    """Median ms per execution of ``f(*args)``: ``reps`` timed
    dispatches of an ``iters``-iteration on-device loop (one warmup
    dispatch first for compilation).  Residual dispatch overhead is
    one dispatch / ``iters``.

    adaptive=True: when the probe shows a FAST body (per-iteration
    time under ~2 ms, where even the amortized residual distorts the
    ratio two fast paths are compared by), re-loop with enough
    iterations that one dispatch runs ~200 ms of body — the dispatch
    share becomes negligible.  The probe itself carries the overhead
    it exists to remove, so it OVERestimates per-iteration time and
    one re-loop can land far short of the target body time; iterate
    until the measured body per dispatch reaches the target.
    Each pass costs one extra compile of the (rolled, so body-sized)
    loop; only worth it for microkernels."""

    def run(n):
        g = loop_on_device(f, n)
        sync(g(*args))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            o = g(*args)
            sync(o)
            times.append((time.perf_counter() - t0) / n * 1e3)
        return statistics.median(times)

    n, ms = iters, run(iters)
    if adaptive:
        for _ in range(4):
            if ms >= 2.0 or ms * n >= 180.0:
                break
            n = max(n + 1, int(200.0 / max(ms, 1e-3)))
            ms = run(n)
    return ms


def noise_floor_pct(f, *args, trials: int = 3, iters: int = 10,
                    reps: int = 2, floor: float = 2.0) -> float:
    """Measured repeatability of the amortized timer on this machine /
    session: time the SAME jitted body ``trials`` times and report the
    relative spread (max-min)/median as a percent, floored at
    ``floor``%.  Sweep distillers (tools/autotune.py,
    tools/kernel_bench.py --write-prefs) stamp this into the written
    prefs table and refuse to flip a dispatch decision on an edge
    inside it — a winner within the session's own wobble is noise, not
    a measurement."""
    samples = [timeit(f, *args, iters=iters, reps=reps)
               for _ in range(max(2, trials))]
    med = statistics.median(samples)
    if med <= 0:
        return floor
    return max(floor, (max(samples) - min(samples)) / med * 100.0)


def cost_flops(jitted, *args):
    """FLOPs of one compiled call from XLA's cost analysis (the
    persistent compilation cache dedupes the compile with the later
    execution).  None if the backend doesn't report it."""
    try:
        ca = jitted.lower(*args).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = ca.get("flops")
        return float(f) if f and f > 0 else None
    except Exception:
        return None


def chunked_train_bench(step_fn, state, batch, *, steps: int,
                        chunk: int, want_flops: bool = True):
    """Time a training loop with ``chunk`` steps per dispatch.

    ``step_fn(state, step, *batch) -> state`` threads the full carry
    (params/optimizer/loss...) exactly like a Python step loop; the
    chunking only changes how often the host dispatches.

    Returns {state, step_ms, steps_per_dispatch, flops_per_step}.
    flops_per_step comes from the SAME compiled program the timing
    runs (no second single-step compile); pass want_flops=False where
    MFU won't be reported (CPU tests) — cost analysis via
    .lower().compile() is a second fresh compile when the persistent
    cache is cold."""
    n_chunks = max(1, steps // chunk)

    def multi(state, step0, *b):
        return lax.fori_loop(
            0, chunk, lambda i, s: step_fn(s, step0 + i, *b), state)

    mj = jax.jit(multi, donate_argnums=(0,))
    flops = (cost_flops(mj, state, jnp.int32(1), *batch)
             if want_flops else None)

    state = mj(state, jnp.int32(1), *batch)     # warmup (compile)
    sync(state)
    t0 = time.perf_counter()
    for c in range(n_chunks):
        state = mj(state, jnp.int32(1 + (c + 1) * chunk), *batch)
    sync(state)
    dt = time.perf_counter() - t0
    n = n_chunks * chunk
    return {"state": state, "step_ms": dt / n * 1e3,
            "steps_per_dispatch": chunk,
            "flops_per_step": (flops / chunk) if flops else None}


def dispatch_overhead_ms(reps: int = 10) -> float:
    """Median wall time of one dispatch of a trivial jitted program —
    the per-call overhead that amortized timing divides away.
    Recorded alongside bench rows so each record carries it."""
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8, 128), jnp.float32)
    sync(f(x))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        sync(f(x))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)
