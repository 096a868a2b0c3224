"""The dropless expert layer alone, on the chip: device milliseconds of
one forward + backward of ``transformer/moe.py:dropless_moe`` at the
expert cell's shapes (8192 tokens x 2048, top-8 of 128, 8 held of width
768, bf16), by the share of the buffer that is routed here, from a
device trace: the whole call, its ``apex_moe/*`` scopes and the ops
that take most of it.

    chiprun --chips 1 -- python tools/moe_bench.py [--fills 0.0625 1.0]
        [--moe-file <another tree's transformer/moe.py>] [--ops 12]

``--fills``: the share of the T * k assignments routed to held experts
(the router is biased towards them until about that share arrives;
1/16 is an unbiased router's).  ``--moe-file`` times another tree's
layer beside this one (a parent's, unpacked under a directory
``.gitignore`` lists).  About a minute and a half for two fills.
"""

import argparse
import bisect
import importlib.util
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from apex_tpu import platform  # noqa: E402
from benchmarks import programtrace, traceread  # noqa: E402

T, H, E, HELD, K, F = 8192, 2048, 128, 8, 8, 768
CALLS = 4


def load(path):
    if path is None:
        from apex_tpu.transformer import moe
        return moe
    spec = importlib.util.spec_from_file_location("moe_other", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["moe_other"] = mod
    spec.loader.exec_module(mod)
    return mod


def inputs(fill, seed=0):
    """A router whose held columns are lifted until about ``fill`` of
    the assignments are routed to them."""
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    router = 0.02 * jax.random.normal(ks[1], (H, E), jnp.float32)
    logits = x.astype(jnp.float32) @ router

    def share(lift):
        top = jax.lax.top_k(logits.at[:, :HELD].add(lift), K)[1]
        return float(jnp.mean(top < HELD))
    lo, hi = -50.0, 50.0
    for _ in range(30 if fill < 1 else 0):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if share(mid) < fill else (lo, mid)
    # the lift as a column of ones times a row: x gets one more
    # direction, constant 1, so the layer sees the lifted logits
    x = x.at[:, 0].set(1.0)
    router = router.at[0].set(0.0).at[0, :HELD].set(hi)
    return (x, router.astype(jnp.bfloat16),
            0.02 * jax.random.normal(ks[2], (HELD, H, 2 * F), jnp.bfloat16),
            0.02 * jax.random.normal(ks[3], (HELD, F, H), jnp.bfloat16),
            jax.random.normal(ks[4], (T, H), jnp.bfloat16))


def measure(moe, fill, top):
    x, router, gate_up, down, ct = inputs(fill)

    @jax.jit
    def moe_layer(x, router, gate_up, down, ct):
        def loss(*a):
            y, counts = moe.dropless_moe(*a, top_k=K)
            return jnp.sum(y.astype(jnp.float32) * ct), counts
        return jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True)(
            x, router, gate_up, down)
    (_, counts), _ = jax.block_until_ready(
        moe_layer(x, router, gate_up, down, ct))
    logdir = tempfile.mkdtemp(prefix="moe_bench_")
    with jax.profiler.trace(logdir):
        for _ in range(CALLS):
            out = moe_layer(x, router, gate_up, down, ct)
        jax.block_until_ready(out)
    path = traceread.find_xplane(logdir)
    pt = programtrace.load_xplane(path)
    # an op the framework gave no name (a copy, a buffer's zeros) goes
    # by its instruction: ``copy.3 bf16[65536,2048]``
    known = sorted(
        (s, traceread.op_label(n)) for lines in
        traceread.load_xplane(path).devices.values()
        for n, s, _ in lines.get(traceread.OP_LINE, []))
    shutil.rmtree(logdir, ignore_errors=True)
    ops = [op for rows in pt.ops.values() for op in rows
           if op[3] == "moe_layer"]
    by_scope, by_op = {}, {}
    for (name, start, _, _), ns in programtrace.self_times(ops):
        scope = "/".join((programtrace.scope_path(name) or ("(none)",))[:2])
        by_scope[scope] = by_scope.get(scope, 0.0) + ns
        if not name and known:
            name = known[min(bisect.bisect_left(known, (start - 0.5,)),
                             len(known) - 1)][1]
        by_op[name] = by_op.get(name, 0.0) + ns
    ms = 1e-6 / CALLS
    n_used = int(jnp.sum(counts))
    return {
        "fill": round(n_used / (T * min(K, HELD)), 4), "n_used": n_used,
        "ms_a_call": round(sum(by_scope.values()) * ms, 3),
        "by_scope_ms": {k: round(v * ms, 3)
                        for k, v in sorted(by_scope.items())},
        "top_ops_ms": [[round(v * ms, 3), k[-110:]] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fills", type=float, nargs="+",
                    default=[1 / 16, 1.0])
    ap.add_argument("--moe-file", default=None)
    ap.add_argument("--ops", type=int, default=12)
    args = ap.parse_args()
    platform.enable_compilation_cache()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}))
    trees = [("this", None)]
    if args.moe_file:
        trees.append(("other", args.moe_file))
    for tree, path in trees:
        moe = load(path)
        for fill in args.fills:
            print(json.dumps({"tree": tree, **measure(moe, fill, args.ops)}),
                  flush=True)


if __name__ == "__main__":
    main()
