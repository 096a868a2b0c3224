"""fp8 micro-benchmarks: the quantized matmul vs the bf16 baseline,
and the fused packed scale update vs the per-leaf oracle.

Shared by tools/kernel_bench.py (JSON rows ``fp8_matmul`` /
``fp8_scale_update``), bench.py (the ``fp8_matmul_speedup`` TPU
extra that grounds the ``extra.fp8_matmul_speedup`` perf-budget row)
and the tier-1 smoke test (tiny shapes on CPU: proves the harness,
not performance — fp8 wins only where the MXU has fp8 units).
"""

from __future__ import annotations


def bench_fp8_matmul(m: int = 4096, k: int = 4096, n: int = 4096,
                     iters: int = 10, reps: int = 3):
    """fp8 vs bf16 fused_dense forward+backward at one GEMM shape.

    "kernel" = ``fp8_matmul`` (e4m3 fwd / e5m2 bwd, delayed-style
    explicit scales so the quantize path is the packed-state shape),
    "oracle" = the plain bf16 ``fused_dense_function`` dot.  On
    fp8-capable TPUs the floor is 1.5x (tools/perf_budget.json
    ``extra.fp8_matmul_speedup``); elsewhere the ratio only proves
    the harness runs.
    """
    import jax
    import jax.numpy as jnp

    from apex_tpu.amp.fp8 import Fp8Policy
    from apex_tpu.benchlib import timeit
    from apex_tpu.fused_dense import fp8_matmul

    policy = Fp8Policy()
    x = jax.random.normal(jax.random.key(0), (m, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (k, n),
                          jnp.bfloat16) * 0.02
    sx = jnp.float32(8.0)
    sw = jnp.float32(64.0)

    def fp8_fwdbwd(x, w):
        return jax.grad(
            lambda x, w: jnp.sum(fp8_matmul(
                x, w, policy=policy, x_scale=sx, w_scale=sw
            ).astype(jnp.float32) ** 2), argnums=(0, 1))(x, w)

    def bf16_fwdbwd(x, w):
        return jax.grad(
            lambda x, w: jnp.sum(jnp.dot(
                x, w, preferred_element_type=jnp.float32) ** 2),
            argnums=(0, 1))(x, w)

    fp8_ms = timeit(jax.jit(fp8_fwdbwd), x, w, iters=iters, reps=reps,
                    adaptive=True)
    bf16_ms = timeit(jax.jit(bf16_fwdbwd), x, w, iters=iters,
                     reps=reps, adaptive=True)
    return {
        "fp8_matmul_shape": f"{m}x{k}x{n}",
        "fp8_compute": policy.uses_fp8_compute(),
        "fp8_matmul_ms": round(fp8_ms, 4),
        "bf16_matmul_ms": round(bf16_ms, 4),
        "fp8_matmul_speedup": (round(bf16_ms / fp8_ms, 3)
                               if fp8_ms else None),
    }


def bench_fp8_scale_update(layers: int = 48, hidden: int = 256,
                           amax_history_len: int = 16,
                           iters: int = 10, reps: int = 3):
    """Fused packed fp8 scale update (ONE flat per-segment amax pass per
    bucket) vs the per-leaf oracle (amax per leaf via a tree walk) on
    the same many-leaf pytree — the dispatch-amortization win the
    packed state exists for, measured exactly like the other
    bucketing benches."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.amp import fp8
    from apex_tpu.benchlib import timeit
    from apex_tpu.multi_tensor_apply.packer import cached_plan
    from apex_tpu.optimizers.bucketing_bench import many_leaf_params

    policy = fp8.Fp8Policy(amax_history_len=amax_history_len)
    params = many_leaf_params(jax, jnp, layers, hidden)
    plan = cached_plan(params)
    bufs = plan.pack_grads(params)
    state = fp8.init_state(plan, policy)

    def fused(state, bufs):
        new, _ = fp8.update_state(state, bufs, plan, policy)
        return new

    def per_leaf(state, tree):
        new, _ = fp8.update_state_ref(state, tree, plan, policy)
        return new

    fused_ms = timeit(jax.jit(fused), state, bufs, iters=iters,
                      reps=reps, adaptive=True)
    leaf_ms = timeit(jax.jit(per_leaf), state, params, iters=iters,
                     reps=reps, adaptive=True)
    return {
        "fp8_scale_leaves": plan.n_leaves,
        "fp8_scale_history": amax_history_len,
        "fp8_scale_fused_ms": round(fused_ms, 4),
        "fp8_scale_per_leaf_ms": round(leaf_ms, 4),
        "fp8_scale_update_speedup": (round(leaf_ms / fused_ms, 3)
                                     if fused_ms else None),
    }
