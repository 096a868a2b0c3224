"""The expert layers' grouped products alone, on the chip: milliseconds of
one call, forward and forward + both gradients, of
``ops/grouped_matmul.py:grouped_matmul`` beside ``lax.ragged_dot`` at
the calls of the benchmark's three expert cells (bf16, eight held
experts, the rows in use at the front of the cell's buffer), best of 3
x 20 calls on the host's clock; then each ``apex_gmm_*`` kernel's
device time a call from a trace.

    python tools/gmm_bench.py [--cases mamba_up ...]    # one TPU chip

Prints one JSON line a (case, groups, path) and writes them all to
``chiprun_out/gmm_bench.json``.  About two minutes.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from apex_tpu import platform  # noqa: E402
from apex_tpu.ops import grouped_matmul as gm  # noqa: E402
from benchmarks import traceread  # noqa: E402

# (buffer rows, contraction, columns, expected rows a held expert): the
# up and down products of the Mamba cell (8192 x 6 rows, 384 a held
# expert), the expert cell (8192 x 8, 512) and the hybrid cell (4096 x
# 8, 128); gated experts' up product is twice the width wide
CASES = {
    "mamba_up": (49152, 2688, 1856, 384),
    "mamba_down": (49152, 1856, 2688, 384),
    "keye_up": (65536, 2048, 1536, 512),
    "keye_down": (65536, 768, 2048, 512),
    "kimi_up": (32768, 2304, 2048, 128),
    "kimi_down": (32768, 1024, 2304, 128),
}
REPS, CALLS = 3, 20


def best_ms(fn, *args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / CALLS)
    return round(best * 1e3, 4)


def kernel_ms(fn, *args):
    """Device ms a call of each ``apex_gmm_*`` kernel, from a trace."""
    logdir = tempfile.mkdtemp(prefix="gmm_bench_")
    with jax.profiler.trace(logdir):
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    trace = traceread.load_xplane(traceread.find_xplane(logdir))
    shutil.rmtree(logdir, ignore_errors=True)
    ms = {}
    for name, s, e in trace.devices[min(trace.devices)].get(
            traceread.OP_LINE, []) if trace.devices else ():
        op = traceread.op_name(name)
        if op.startswith("apex_gmm"):
            key = op.split(".")[0]
            ms[key] = ms.get(key, 0.0) + (e - s) * 1e-6 / CALLS
    return {k: round(v, 4) for k, v in sorted(ms.items())}


def paths():
    def kernel(a, b, c):
        return gm.grouped_matmul(a, b, c)

    def xla(a, b, c):
        return jax.lax.ragged_dot(a, b, c)

    def both(f):
        fwd = jax.jit(f)

        @jax.jit
        def fwd_bwd(a, b, c, d):
            out, vjp = jax.vjp(lambda x, w: f(x, w, c), a, b)
            return out, vjp(d)
        return fwd, fwd_bwd
    return {"apex_gmm": both(kernel), "ragged_dot": both(xla)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", nargs="+", default=list(CASES))
    args = ap.parse_args()
    platform.enable_compilation_cache()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}))
    lines = []
    jits = paths()
    for case in args.cases:
        rows, k, n, per = CASES[case]
        ks = jax.random.split(jax.random.key(0), 3)
        lhs = jax.random.normal(ks[0], (rows, k), jnp.bfloat16)
        rhs = 0.02 * jax.random.normal(ks[1], (8, k, n), jnp.bfloat16)
        dout = jax.random.normal(ks[2], (rows, n), jnp.bfloat16)
        for sizes in ([per] * 8, [per - 1] * 8, [per + 1] * 8):
            counts = jnp.array(sizes, jnp.int32)
            for path, (fwd, fwd_bwd) in jits.items():
                line = {"case": case, "rows": rows, "k": k, "n": n,
                        "groups": sizes[0], "path": path,
                        "fwd_ms": best_ms(fwd, lhs, rhs, counts),
                        "fwd_bwd_ms": best_ms(fwd_bwd, lhs, rhs, counts,
                                              dout)}
                if path == "apex_gmm":
                    line["kernels_ms"] = kernel_ms(fwd_bwd, lhs, rhs,
                                                   counts, dout)
                print(json.dumps(line), flush=True)
                lines.append(line)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gmm_bench.json"), "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
