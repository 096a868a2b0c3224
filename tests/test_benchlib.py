"""apex_tpu.benchlib: amortized on-device timing must actually run the
measured body every iteration.

The failure modes these tests pin are silent and catastrophic for the
measurements built on top (kernel_bench speedups -> dispatch prefs):
XLA hoisting the loop-invariant body out of the fori_loop, CSE-ing
iterations together, or slicing the body down to the one element a
naive data dependence reads.  All three would make every kernel
"measure" near-zero time.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import benchlib


def test_loop_preserves_values_bit_exact():
    """The carried args come back bit-identical: the data coupling is
    a no-op select when outputs are finite, so iteration N sees
    iteration 0's inputs — including exact zeros and -0.0 (an
    epsilon-ADD coupling would fail both: f32 has no 1e-30 underflow,
    and -0.0 + 0.0 is +0.0)."""
    x = jax.random.normal(jax.random.key(0), (64, 64), jnp.float32)
    x = x.at[0, :3].set(jnp.asarray([0.0, -0.0, 1.0]))
    w = jax.random.normal(jax.random.key(1), (64, 64), jnp.bfloat16)
    g = benchlib.loop_on_device(lambda a, b: a @ b.astype(a.dtype), 4)
    ox, ow = g(x, w)
    np.testing.assert_array_equal(
        np.asarray(ox).view(np.uint32), np.asarray(x).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(ow, np.float32),
                                  np.asarray(w, np.float32))


def test_loop_body_not_hoisted_or_dced():
    """Wall time must scale with the iteration count.  A compiler that
    hoists, CSEs, or slices the body runs it (at most) once regardless
    of n, and the n=12 loop times like the n=1 loop.

    CPU-only: the body is sized for the CPU backend; on an accelerator
    it is so short that dispatch overhead dominates both loops and the
    ratio says nothing about the compiler (the property under test)."""
    if jax.default_backend() != "cpu":
        import pytest
        pytest.skip("timing-ratio assertion is meaningful on CPU only")
    m = 384
    a = jax.random.normal(jax.random.key(0), (m, m), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (m, m), jnp.float32)

    def chain(a, b):
        # 8 chained matmuls: big enough to dwarf loop bookkeeping
        for _ in range(8):
            a = jnp.tanh(a @ b)
        return a

    def best_of(g, reps=5):
        benchlib.sync(g(a, b))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            benchlib.sync(g(a, b))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t1 = best_of(benchlib.loop_on_device(chain, 1))
    t12 = best_of(benchlib.loop_on_device(chain, 12))
    assert t12 > 4 * t1, (
        f"n=12 loop took {t12:.4f}s vs n=1 {t1:.4f}s — body not "
        f"executed per iteration (hoisted/DCEd/sliced)")


def test_loop_multi_output_keeps_all_outputs_live():
    """A body returning several leaves (grad tuples) must keep every
    leaf's computation: check the loop still returns exact inputs and
    runs with a tuple-returning body."""
    q = jax.random.normal(jax.random.key(0), (8, 128), jnp.float32)

    def body(x):
        return (x @ x.T, jnp.sum(x, axis=0), x * 2.0)

    g = benchlib.loop_on_device(body, 3)
    (oq,) = g(q)
    np.testing.assert_array_equal(np.asarray(oq), np.asarray(q))


def test_timeit_and_overhead_smoke():
    ms = benchlib.timeit(lambda x: x * 2.0,
                         jnp.ones((128, 128), jnp.float32),
                         iters=4, reps=2)
    assert ms > 0
    assert benchlib.dispatch_overhead_ms(reps=3) > 0


def test_timeit_adaptive_converges_past_dispatch_overhead(monkeypatch):
    """A 50 µs body probed under a fixed per-dispatch overhead must
    re-loop until one dispatch runs ~200 ms of wall, so the overhead's
    share of the reported time is negligible — a single capped re-loop
    would bias every fast kernel's speedup toward 1.  Simulated clock:
    wall per dispatch = overhead + n * body (0.5 ms is a generous
    local-dispatch overhead; the convergence rule does not depend on
    its size)."""
    body_ms, rtt_ms = 0.05, 0.5
    clock = [0.0]
    ns = []

    class FakeG:
        def __init__(self, n):
            self.n = n

        def __call__(self, *a):
            ns.append(self.n)
            clock[0] += (rtt_ms + self.n * body_ms) / 1e3
            return jnp.float32(0)

    monkeypatch.setattr(benchlib, "loop_on_device",
                        lambda f, n: FakeG(n))
    monkeypatch.setattr(benchlib, "sync", lambda o: None)
    monkeypatch.setattr(benchlib.time, "perf_counter",
                        lambda: clock[0])

    ms = benchlib.timeit(lambda x: x, None, iters=20, adaptive=True)
    n_final = ns[-1]
    assert n_final * body_ms + rtt_ms >= 180.0      # target body met
    assert ms <= body_ms * 1.01                     # <= ~1% residual
    assert len({n for n in ns}) >= 3                # probed, re-looped
    # non-adaptive keeps the probe's overhead-inflated number
    clock[0] = 0.0
    ns.clear()
    ms_raw = benchlib.timeit(lambda x: x, None, iters=20,
                             adaptive=False)
    assert ms_raw > body_ms * 1.4                   # overhead-inflated


def test_int_only_args_still_loop():
    """No floating-point arg to perturb: the int fallback arm."""
    x = jnp.arange(256, dtype=jnp.int32)
    g = benchlib.loop_on_device(lambda a: a * 2, 3)
    (ox,) = g(x)
    np.testing.assert_array_equal(np.asarray(ox), np.asarray(x))


def test_chunked_train_bench_threads_state():
    """The chunked loop must run step_fn chunk*n_chunks times with the
    carry threaded exactly like a Python loop (same final state), and
    report a positive per-step time."""
    def step_fn(state, step, lr):
        w, loss = state
        w = w - lr * (w - 3.0)
        return (w, jnp.mean(w))

    w0 = jnp.full((8,), 10.0)
    lr = jnp.float32(0.5)
    r = benchlib.chunked_train_bench(
        step_fn, (w0, jnp.float32(0)), (lr,), steps=6, chunk=3,
        want_flops=False)
    assert r["step_ms"] > 0
    assert r["steps_per_dispatch"] == 3
    assert r["flops_per_step"] is None
    # warmup chunk + 2 timed chunks = 9 steps total
    w_ref = np.full((8,), 10.0, np.float32)
    for _ in range(9):
        w_ref = w_ref - 0.5 * (w_ref - 3.0)
    np.testing.assert_allclose(np.asarray(r["state"][0]), w_ref,
                               rtol=1e-6)
