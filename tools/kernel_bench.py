"""Per-kernel micro-benchmarks: each Pallas kernel vs its XLA oracle.

Run on a real TPU (anywhere else it exits non-zero: interpret-mode
timings are not kernel timings):

    python tools/kernel_bench.py [--csv out.csv]

Prints one JSON line per kernel:
    {"kernel": "...", "shape": "...", "dtype": "...",
     "kernel_ms": K, "oracle_ms": O, "speedup": O/K, "backend": "tpu"}

Methodology (apex_tpu.benchlib): each path runs `iters` times serially
INSIDE one compiled fori_loop, so one dispatch amortizes over all
iterations — dispatch-per-iteration timing makes every microkernel
measure the dispatch overhead, not the op (speedups compressed toward
1).  A dispatch_overhead_ms row is emitted so each record carries the
overhead its timings were amortized against.

One process: this tool imports jax and measures in-process; it starts
no child (a chip belongs to one process at a time).
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os as _os
import sys as _sys

# runnable straight from a checkout with no install (tools/lint.py idiom)
_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _ROOT not in _sys.path:
    _sys.path.insert(0, _ROOT)


def time_fn(f, *args, iters=10, reps=3):
    """Median ms per execution, amortized on device (see module
    docstring; benchlib imported lazily so --help needs no jax).
    adaptive: sub-2ms bodies re-loop to ~200 ms per dispatch so the
    residual dispatch share is negligible — write_prefs flips routing
    on these ratios, so they must not carry dispatch noise."""
    from apex_tpu.benchlib import timeit
    return timeit(f, *args, iters=iters, reps=reps, adaptive=True)


def bench_pair(name, shape_desc, dtype, kern, oracle, *args, grad=False):
    """oracle=None benches the kernel alone (shapes where the unfused
    oracle would materialize an infeasible intermediate)."""
    import jax
    import jax.numpy as jnp

    if grad:
        def wrap(f, n=len(args)):
            # differentiate w.r.t. EVERY operand so no backward path is
            # dead-code-eliminated on the oracle side (bench.py idiom)
            return jax.jit(jax.grad(
                lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
                argnums=tuple(range(n))))
    else:
        wrap = jax.jit
    k_ms = time_fn(wrap(kern), *args)
    o_ms = time_fn(wrap(oracle), *args) if oracle is not None else None
    return {"kernel": name + ("_grad" if grad else ""),
            "shape": shape_desc, "dtype": dtype,
            "kernel_ms": round(k_ms, 3),
            "oracle_ms": round(o_ms, 3) if o_ms is not None else None,
            "speedup": (round(o_ms / k_ms, 2)
                        if o_ms is not None and k_ms else None)}


def select_attn_caps(sweep_times):
    """Per-head-dim winner from sweep measurements.

    ``sweep_times``: {(dp, cap): [relative time per swept shape]},
    where each entry is ms / best-ms-for-that-shape.  The winner for a
    dp is the cap with the lowest mean relative time among caps that
    were measured on EVERY swept shape of that dp — a cap only feasible
    (or only surviving compilation) on a subset of shapes must not win
    the tier on a partial sample.  Returns {str(dp): cap}."""
    by_dp = {}
    for (dp, cap), rels in sweep_times.items():
        by_dp.setdefault(dp, {})[cap] = rels
    caps_out = {}
    for dp, capmap in by_dp.items():
        full = max(len(r) for r in capmap.values())
        cands = {c: sum(r) / len(r) for c, r in capmap.items()
                 if len(r) == full}
        if cands:
            caps_out[str(dp)] = min(cands, key=cands.get)
    return caps_out


# the benchmark cells' flash-attention calls (benchmarks/configs): the
# looped decoder's (16 causal heads of 128 at s4096) and the
# sparse-attention expert decoder's (32 query heads over 4 key heads of
# 128 at s8192 under a top-2048 key selection), each with its control:
# (name, b, h, hk, s, d, dtype, topk; 0 = no key_mask)
CELL_ATTN_CALLS = (
    ("looped", 1, 16, 16, 4096, 128, "bf16", 0),
    ("looped_f32", 1, 16, 16, 4096, 128, "f32", 0),
    ("expert_unmasked", 1, 32, 4, 8192, 128, "bf16", 0),
    ("expert", 1, 32, 4, 8192, 128, "bf16", 2048),
)
FLASH_KERNELS = ("apex_flash_attention_fwd", "apex_flash_attention_dq",
                 "apex_flash_attention_dkv")


def attn_geometry(q, k):
    """The tile a causal flash call of these operands runs at and its
    grid (ops/attention.py:_geom, causal_block_plan): the blocks visited
    — wholly under the diagonal, or crossed by it (or by padding) — and
    those that cost no grid step."""
    from apex_tpu.ops import attention as attn
    sq, sk, bq, bk = (attn._geom(q, k)[i] for i in (2, 3, 6, 7))
    plan = attn.causal_block_plan(sq, sk, bq, bk)
    return {"bq": bq, "bk": bk, "visited": plan.interior + plan.diagonal,
            "interior": plan.interior, "diagonal": plan.diagonal,
            "not_visited": plan.not_visited}


def flash_kernel_ms(fn, args, calls=4):
    """ms a call of each of the three flash kernels inside jitted
    ``fn(*args)``, read from a device trace of ``calls`` executions
    the way the benchmark's ``attn_roofline`` reads them inside a step:
    the durations of the device ops named ``apex_flash_attention_*``.
    A kernel's time alone — not the ``di`` / ``lse`` broadcasts around
    it, which a host clock around ``fn`` would count."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))            # compile
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        (path,) = glob.glob(_os.path.join(
            logdir, "plugins", "profile", "*", "*.xplane.pb"))
        planes = [p for p in ProfileData.from_file(path).planes
                  if p.name == "/device:TPU:0"]
        ops = [e for p in planes for ln in p.lines
               if ln.name == "XLA Ops" for e in ln.events]
    out = {}
    for kern in FLASH_KERNELS:
        durs = [e.duration_ns for e in ops
                if e.name.lstrip("%").startswith(kern)]
        if durs:
            out[kern.rsplit("_", 1)[1] + "_ms"] = round(
                sum(durs) / len(durs) / 1e6, 4)
            out[kern.rsplit("_", 1)[1] + "_calls"] = len(durs) // calls
    return out


@contextlib.contextmanager
def _forced_cap(cap):
    """APEX_TPU_ATTN_BLOCK_CAP set to ``cap`` (None: unset, the default
    geometry) for the block, an operator's own value put back after."""
    prev = _os.environ.pop("APEX_TPU_ATTN_BLOCK_CAP", None)
    if cap is not None:
        _os.environ["APEX_TPU_ATTN_BLOCK_CAP"] = str(cap)
    try:
        yield
    finally:
        _os.environ.pop("APEX_TPU_ATTN_BLOCK_CAP", None)
        if prev is not None:
            _os.environ["APEX_TPU_ATTN_BLOCK_CAP"] = prev


def _causal_flash_grad():
    """jit(grad) of a causal flash call w.r.t. q, k, v; a fourth operand
    is its ``key_mask``.  A fresh jit per call ON PURPOSE: the cap is
    read at trace time, so each geometry must trace anew."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.ops.attention import flash_attention

    # apexlint: disable-next=APX302
    return jax.jit(jax.grad(
        lambda q, k, v, *m: jnp.sum(flash_attention(
            q, k, v, causal=True, **({"key_mask": m[0]} if m else {})
        ).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))


def sweep_attn_cells(caps=(512, 1024)):
    """The three flash kernels alone at the cells' calls: one JSON line
    per (call, cap) with the geometry chosen, the causal plan's counts
    and fwd / dq / dkv ms a call (PERF.md section 6 holds the table
    this wrote on the chip).  ``default`` marks the cap whose geometry
    the call gets with nothing forced."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.ops import attention as attn
    from apex_tpu.ops.sparse_index import select_topk

    for name, b, h, hk, s, d, dtype, topk in CELL_ATTN_CALLS:
        dt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
        ks = jax.random.split(jax.random.key(7), 4)
        q = jax.random.normal(ks[0], (b, h, s, d), dt)
        k, v = (jax.random.normal(kk, (b, hk, s, d), dt) for kk in ks[1:3])
        args = (q, k, v)
        if topk:
            causal = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(
                causal, jax.random.normal(ks[3], (b, s, s)), attn._NEG)
            args += (select_topk(scores, topk),)
        with _forced_cap(None):
            default = attn_geometry(q, k)
        for cap in caps:
            row = {"sweep": "attention_cells", "call": name,
                   "shape": f"b{b}h{h}hk{hk}s{s}d{d}", "dtype": dtype,
                   "key_mask_topk": topk, "cap": cap}
            try:
                with _forced_cap(cap):
                    row.update(attn_geometry(q, k))
                    row["default"] = row["bq"] == default["bq"]
                    row.update(flash_kernel_ms(_causal_flash_grad(), args))
            except Exception as e:
                row["error"] = repr(e)[:300]
            print(json.dumps(row), flush=True)


def sweep_attn_caps(backend, noise_pct, write):
    """Flash geometry sweep: the best sequence-block cap per shape
    (re-jit per cap — the env knob is read at trace time) and the
    per-head-dim winner.  ``write`` (--write-prefs) records the winners
    in dispatch_prefs.json, where ``_block_cap`` reads them BEFORE its
    own default — an entry caps every sequence length and operand width
    of that head dim, which the default tells apart (the dp 128 winner
    over a short and a long shape is 512, and written down it would
    undo the long sequences' 1024 tile): record one knowingly."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.ops import attention as attn

    sweep_times = {}          # (dp, cap) -> [relative time per shape]
    # one shape per runtime head-dim tier (dp=128 twice: BERT-ish
    # short-seq AND long-context must agree before a cap becomes
    # that tier's default; dp=256 gets its own winner)
    for (b, h, s, d) in [(8, 16, 512, 64), (4, 16, 2048, 128),
                         (2, 16, 2048, 256)]:
        ks = jax.random.split(jax.random.key(7), 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                   for kk in ks)
        dp = attn._round_up(d, attn._LANES)
        best, shape_ms = None, {}
        for cap in (128, 256, 512, 1024):
            if (cap > attn._round_up(s, attn._LANES)
                    or cap > attn._sweep_cap_ceiling(dp)):
                continue
            try:
                with _forced_cap(cap):
                    ms = time_fn(_causal_flash_grad(), q, k, v)
            except Exception as e:
                print(json.dumps({"sweep": "attention", "cap": cap,
                                  "shape": f"b{b}h{h}s{s}d{d}",
                                  "error": repr(e)[:200]}), flush=True)
                continue
            print(json.dumps({"sweep": "attention", "cap": cap,
                              "shape": f"b{b}h{h}s{s}d{d}",
                              "fwdbwd_ms": round(ms, 3)}), flush=True)
            shape_ms[cap] = ms
            if best is None or ms < best[1]:
                best = (cap, ms)
        if best:
            print(json.dumps({"sweep": "attention",
                              "shape": f"b{b}h{h}s{s}d{d}",
                              "best_cap": best[0],
                              "best_ms": round(best[1], 3)}),
                  flush=True)
            for cap, ms in shape_ms.items():
                sweep_times.setdefault((dp, cap), []).append(
                    ms / best[1])
    caps_out = select_attn_caps(sweep_times)
    print(json.dumps({"attn_caps_measured": caps_out}), flush=True)
    if caps_out and write:
        from apex_tpu.ops import _dispatch
        prefs_doc = _load_trusted_doc(_dispatch._PREFS_PATH)
        prefs_doc.setdefault("source", "tools/kernel_bench.py")
        prefs_doc.setdefault("attn_block_cap", {}).update(caps_out)
        prefs_doc["attn_sweep_backend"] = backend
        prefs_doc["topology"] = _dispatch.topology_block()
        prefs_doc["schema"] = _dispatch.SCHEMA_VERSION
        prefs_doc["noise_floor_pct"] = noise_pct
        # the sweep times with the same amortized timer; a
        # sweep-only run must still produce a table _load_prefs
        # will trust (see write_prefs)
        prefs_doc["methodology"] = "amortized"
        with open(_dispatch._PREFS_PATH, "w") as f:
            json.dump(prefs_doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(json.dumps({"attn_caps_written": caps_out}), flush=True)


# kernel_bench row name -> dispatch op family (apex_tpu.ops._dispatch)
_OP_FAMILY = {
    "flash_attention": "attention",
    "flash_attention_f32": "attention_f32",
    "fused_layer_norm": "layer_norm",
    "scaled_upper_triang_masked_softmax": "softmax",
    "softmax_cross_entropy": "xentropy",
    "flat_unscale_norm": "multi_tensor",
    "flat_accumulate": "multi_tensor",
    "welford_mean_var": "welford",
}


def _load_trusted_doc(path):
    """Existing prefs doc for read-modify-write, with any tables from
    a NON-amortized era stripped first: the whole-file methodology
    stamp both writers emit would otherwise launder the OTHER table's
    stale dispatch-per-iteration data into trusted steering (a
    --write-prefs-only run must not re-bless old sweep caps, nor a
    sweep-only run old prefer_pallas booleans)."""
    try:
        with open(path) as f:
            out = json.load(f)
        if not isinstance(out, dict):
            return {}
    except Exception:
        return {}
    if out.get("methodology") != "amortized":
        for stale in ("prefer_pallas", "speedups", "attn_block_cap",
                      "backend", "attn_sweep_backend", "topology",
                      "noise_floor_pct", "schema", "pipeline"):
            out.pop(stale, None)
    return out


def write_prefs(rows, path, topology=None, noise_floor_pct=None):
    """Distill measured rows into the dispatch preference table
    (VERDICT r2 #2): an op family prefers Pallas only if NO measured
    shape was slower than its XLA oracle (speedup < 1.0 anywhere ->
    the oracle path wins by default; re-tune, then re-measure).

    Read-modify-write: the same file carries the sweep's
    attn_block_cap table, which a plain --write-prefs run (or the
    sweep-then-prefs order inside one run) must not erase.

    ``topology`` (the ops._dispatch.topology_block() dict) and
    ``noise_floor_pct`` (benchlib.noise_floor_pct) stamp WHERE and HOW
    REPEATABLY the table was measured, making hand-run bench output
    schema-compatible with tools/autotune.py's per-topology tables
    (and topology-checked at load: a table benched on one fleet never
    silently steers another)."""
    fam = {}
    for r in rows:
        base = r["kernel"].removesuffix("_grad")
        op = _OP_FAMILY.get(base)
        if op is None or r.get("speedup") is None:
            continue
        fam.setdefault(op, []).append(float(r["speedup"]))
    prefs = {op: min(sp) >= 1.0 for op, sp in fam.items()}
    out = _load_trusted_doc(path)
    out.update({"prefer_pallas": prefs,
                "source": "tools/kernel_bench.py",
                # time_fn uses benchlib's amortized adaptive timer;
                # _load_prefs only lets prefer_pallas steer dispatch
                # under this stamp (pre-amortization tables measured
                # the dispatch, not the kernels)
                "methodology": "amortized",
                "backend": rows[0]["backend"] if rows else "unknown",
                "speedups": {op: sorted(sp) for op, sp in fam.items()}})
    if topology is not None:
        out["topology"] = topology
        out["schema"] = 2        # == ops._dispatch.SCHEMA_VERSION
    if noise_floor_pct is not None:
        out["noise_floor_pct"] = round(float(noise_floor_pct), 2)
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    return prefs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--csv", default="")
    ap.add_argument("--write-prefs", action="store_true",
                    help="write apex_tpu/ops/dispatch_prefs.json from "
                         "the measured speedups")
    ap.add_argument("--sweep-attn", action="store_true",
                    help="run ONLY the flash-attention sweeps: the three "
                         "kernels alone at the benchmark cells' calls per "
                         "block cap (fwd / dq / dkv ms, geometry, causal "
                         "plan), then APEX_TPU_ATTN_BLOCK_CAP's winner "
                         "per padded head dim (recorded in the prefs "
                         "table only with --write-prefs)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from apex_tpu.platform import enable_compilation_cache
    import os
    enable_compilation_cache()
    backend = jax.default_backend()
    if backend != "tpu":
        # interpret-mode Pallas timings are meaningless AND impractically
        # slow: measure on the chip or not at all
        print(f"kernel_bench.py: backend is {backend!r}, not 'tpu' — "
              "nothing to time", file=_sys.stderr)
        _sys.exit(2)

    from apex_tpu.benchlib import dispatch_overhead_ms
    print(json.dumps({"dispatch_overhead_ms":
                      round(dispatch_overhead_ms(), 3),
                      "backend": backend}), flush=True)

    from apex_tpu.ops import attention as attn
    from apex_tpu.ops import layer_norm as ln
    from apex_tpu.ops import multi_tensor as mt
    from apex_tpu.ops import softmax as sm
    from apex_tpu.ops import xentropy as xe

    # Pin every family to its Pallas path WHILE TIMING: the bench's
    # whole purpose is kernel-vs-oracle, but the public entry points
    # route through op_enabled — with a previously written
    # dispatch_prefs.json disabling a family, its "kernel" timing
    # would silently measure the oracle and the preference would
    # oscillate between bench runs (env override beats the table).
    os.environ["APEX_TPU_PREFER_PALLAS"] = ",".join(
        sorted(set(_OP_FAMILY.values())))

    rows = []
    key = jax.random.key(0)

    # session noise floor: the amortized timer's measured repeatability
    # on a representative fused body, stamped into any table this run
    # writes — a dispatch decision must never flip on an edge inside it
    from apex_tpu.benchlib import noise_floor_pct
    xnf = jax.random.normal(key, (4096, 256), jnp.bfloat16)
    noise_pct = round(noise_floor_pct(
        lambda t: jnp.sum(t.astype(jnp.float32) ** 2), xnf), 2)
    print(json.dumps({"noise_floor_pct": noise_pct,
                      "backend": backend}), flush=True)

    if args.sweep_attn:     # the sweeps alone: minutes, not the whole bench
        sweep_attn_cells()
        sweep_attn_caps(backend, noise_pct, args.write_prefs)
        return

    # flash attention: bench shapes (BERT-L-ish, long-context, and the
    # looped decoder cell's b1 x 16 heads x s4096 x d128)
    for (b, h, s, d) in [(8, 16, 512, 64), (4, 16, 2048, 128),
                         (1, 16, 4096, 128), (1, 8, 8192, 128)]:
        ks = jax.random.split(key, 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                   for kk in ks)
        f_k = functools.partial(attn.flash_attention, causal=True)
        # from s=4096 the unfused oracle materializes s^2 score/softmax
        # buffers per head (bench.py skips it there too): kernel-only
        f_o = (functools.partial(attn.attention_ref, causal=True)
               if s < 4096 else None)
        for grad in (False, True):
            row = bench_pair("flash_attention", f"b{b}h{h}s{s}d{d}",
                             "bf16", f_k, f_o, q, k, v, grad=grad)
            row["causal_blocks"] = attn_geometry(q, k)
            rows.append(row)

    # f32 precision class: HIGHEST-precision multi-pass dots — its own
    # dispatch family (attention_f32) so a loss here cannot disable the
    # bf16 kernel
    b, h, s, d = 8, 16, 512, 64
    ks = jax.random.split(jax.random.key(5), 3)
    qf, kf, vf = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
                  for kk in ks)
    rows.append(bench_pair(
        "flash_attention_f32", f"b{b}h{h}s{s}d{d}", "f32",
        functools.partial(attn.flash_attention, causal=True),
        functools.partial(attn.attention_ref, causal=True),
        qf, kf, vf, grad=True))
    rows[-1]["causal_blocks"] = attn_geometry(qf, kf)

    # layer norm
    for (r, hdim) in [(8192, 1024), (4096, 4096)]:
        x = jax.random.normal(key, (r, hdim), jnp.bfloat16)
        w = jnp.ones((hdim,), jnp.bfloat16)
        b_ = jnp.zeros((hdim,), jnp.bfloat16)
        rows.append(bench_pair("fused_layer_norm", f"{r}x{hdim}", "bf16",
                               ln.fused_layer_norm, ln.layer_norm_ref,
                               x, w, b_))
        rows.append(bench_pair("fused_layer_norm", f"{r}x{hdim}", "bf16",
                               ln.fused_layer_norm, ln.layer_norm_ref,
                               x, w, b_, grad=True))

    # fused softmax (attention-shaped)
    x = jax.random.normal(key, (8, 16, 512, 512), jnp.bfloat16)
    rows.append(bench_pair(
        "scaled_upper_triang_masked_softmax", "8x16x512x512", "bf16",
        lambda t: sm.scaled_upper_triang_masked_softmax(
            t.reshape(-1, 512, 512), 1.0),
        lambda t: sm.scaled_upper_triang_masked_softmax_ref(
            t.reshape(-1, 512, 512), 1.0), x))

    # xentropy at BERT vocab
    logits = jax.random.normal(key, (4096, 32768), jnp.bfloat16)
    labels = jax.random.randint(jax.random.key(1), (4096,), 0, 32768)
    rows.append(bench_pair(
        "softmax_cross_entropy", "4096x32768", "bf16",
        lambda l: xe.softmax_cross_entropy(l, labels),
        lambda l: xe.softmax_cross_entropy_ref(l, labels), logits))

    # int8 inference matmuls vs the bf16 baseline (MXU int8 ~2x rate)
    from apex_tpu.quantization import int8_matmul, quantize_int8
    m_, k_, n_ = 4096, 4096, 4096
    xb = jax.random.normal(key, (m_, k_), jnp.bfloat16)
    wf = jax.random.normal(jax.random.key(3), (k_, n_)) * 0.05
    wq = quantize_int8(wf)
    wb = wf.astype(jnp.bfloat16)
    bf16_dot = lambda x: jnp.dot(
        x, wb, preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    for i, (mode, fn) in enumerate((
            ("weight_only", lambda x: int8_matmul(x, wq, dynamic=False)),
            ("dynamic_full", lambda x: int8_matmul(x, wq, dynamic=True)))):
        # time the shared bf16 baseline once; reuse its number after
        r = bench_pair(f"int8_matmul_{mode}", f"{m_}x{k_}x{n_}",
                       "bf16/int8", fn, bf16_dot if i == 0 else None, xb)
        if i > 0 and rows[-1]["oracle_ms"] is not None:
            r["oracle_ms"] = rows[-1]["oracle_ms"]
            r["speedup"] = round(r["oracle_ms"] / r["kernel_ms"], 2)
        rows.append(r)

    # fp8 matmul vs the bf16 baseline (fp8-capable MXUs run e4m3 dots
    # at ~2x the bf16 rate; tools/perf_budget.json floors the speedup
    # at 1.5 once a hardware round restamps it), plus the fused packed
    # fp8 scale update vs the per-leaf amax oracle
    from apex_tpu.amp.fp8_bench import (bench_fp8_matmul,
                                        bench_fp8_scale_update)
    rf8 = bench_fp8_matmul()
    rf8["backend"] = backend
    print(json.dumps(rf8), flush=True)
    rows.append({
        "kernel": "fp8_matmul",
        "shape": rf8["fp8_matmul_shape"],
        "dtype": "e4m3/e5m2" if rf8["fp8_compute"] else "bf16-oracle",
        "kernel_ms": rf8["fp8_matmul_ms"],
        "oracle_ms": rf8["bf16_matmul_ms"],
        "speedup": rf8.get("fp8_matmul_speedup")})
    rsu = bench_fp8_scale_update()
    rsu["backend"] = backend
    print(json.dumps(rsu), flush=True)
    rows.append({
        "kernel": "fp8_scale_update",
        "shape": (f"{rsu['fp8_scale_leaves']}leaves/"
                  f"H{rsu['fp8_scale_history']}"),
        "dtype": "f32",
        "kernel_ms": rsu["fp8_scale_fused_ms"],
        "oracle_ms": rsu["fp8_scale_per_leaf_ms"],
        "speedup": rsu.get("fp8_scale_update_speedup")})

    # serving decode step: the paged-arena decode window vs the
    # contiguous-cache oracle ("kernel" = paged, "oracle" = dense —
    # near-1.0 IS the pass condition: the flat-arena page indirection
    # must not tax the decode hot path; tokens/sec rides along for
    # the perf-budget serving rows)
    from apex_tpu.serving.bench import bench_decode_step
    rd = bench_decode_step(n_layers=4, hidden=256, n_heads=8,
                           max_slots=8, page_size=16,
                           pages_per_slot=8, window=16)
    rd["backend"] = backend
    print(json.dumps(rd), flush=True)
    rows.append({
        "kernel": "decode_step",
        "shape": (f"b{rd['decode_slots']}w{rd['decode_window']}"
                  f"ctx{rd['decode_ctx']}p{rd['decode_page_size']}"),
        "dtype": "f32",
        "kernel_ms": rd["decode_step_paged_ms"],
        "oracle_ms": rd["decode_step_dense_ms"],
        "speedup": (round(rd["decode_step_dense_ms"]
                          / rd["decode_step_paged_ms"], 2)
                    if rd["decode_step_paged_ms"] else None)})

    # KV quantization: int8 gather+dequant vs bf16 gather ("kernel" =
    # int8, "oracle" = bf16) — the memory-frontier trade: ~0.53x the
    # HBM bytes per cached token (the extra.kv_bytes_per_token budget
    # ceiling, 0.55) for whatever cast overhead shows here
    from apex_tpu.serving.bench import bench_kv_quant_gather
    rq = bench_kv_quant_gather(n_layers=4, hidden=256, n_heads=4,
                               max_slots=8, page_size=16,
                               pages_per_slot=8)
    rq["backend"] = backend
    print(json.dumps(rq), flush=True)
    rows.append({
        "kernel": "kv_quant_gather",
        "shape": (f"b{rq['kv_gather_slots']}ctx{rq['kv_gather_ctx']}"
                  f"d{rq['kv_gather_head_dim']}"),
        "dtype": "int8",
        "kernel_ms": rq["kv_quant_gather_int8_ms"],
        "oracle_ms": rq["kv_quant_gather_bf16_ms"],
        "speedup": (round(rq["kv_quant_gather_bf16_ms"]
                          / rq["kv_quant_gather_int8_ms"], 2)
                    if rq["kv_quant_gather_int8_ms"] else None)})

    # prefix-sharing admission: 8 requests, one shared prompt — the
    # structural prefill-savings factor (extra.prefix_prefill_savings
    # floor 2.0) plus the admission wall clock; "oracle" here is the
    # no-sharing cost model (n_requests full prefills), folded into
    # the savings number rather than a second timed leg
    from apex_tpu.serving.bench import bench_prefix_admission
    rp = bench_prefix_admission(n_requests=8, n_layers=4, hidden=256,
                                n_heads=8, page_size=16,
                                pages_per_slot=8, prompt_len=48,
                                window=8)
    rp["backend"] = backend
    print(json.dumps(rp), flush=True)
    rows.append({
        "kernel": "prefix_admission",
        "shape": (f"n{rp['prefix_requests']}"
                  f"p{rp['prefix_prompt_len']}"),
        "dtype": "f32",
        "kernel_ms": rp["prefix_admission_ms"],
        "oracle_ms": None,
        "speedup": rp.get("prefix_prefill_savings")})

    # speculative verify step: the K-token self-drafting decode window
    # vs the plain (K=0) window on the repetitive-suffix fixture
    # ("kernel" = speculative, "oracle" = plain); speedup is the
    # structural accept rate (extra.spec_accept_rate budget floor) —
    # the wall-clock ratio only pays off where the forward is
    # bandwidth-bound, which CPU is not
    from apex_tpu.serving.bench import bench_spec_decode
    rs = bench_spec_decode(n_requests=4, n_layers=4, hidden=256,
                           n_heads=8, page_size=8, pages_per_slot=8,
                           window=8, spec_k=4)
    rs["backend"] = backend
    print(json.dumps(rs), flush=True)
    rows.append({
        "kernel": "spec_verify_step",
        "shape": f"k{rs['spec_k']}", "dtype": "f32",
        "kernel_ms": rs["spec_verify_step_ms"],
        "oracle_ms": rs["spec_plain_window_ms"],
        "speedup": rs.get("spec_accept_rate")})

    # int8 weight matmul: the weight-only dequant-into-dot serving
    # path vs the plain f32 dot at decode-ish shape ("kernel" = int8,
    # "oracle" = f32) — halves weight HBM per verify pass; the compute
    # tax shows here
    from apex_tpu.benchlib import timeit as _timeit
    from apex_tpu.quantization import int8_matmul, quantize_int8
    m, k_, n = 8, 1024, 1024
    x = jax.random.normal(jax.random.key(11), (m, k_), jnp.float32)
    w = jax.random.normal(jax.random.key(12), (k_, n), jnp.float32)
    wq = quantize_int8(w, axis=0)
    # one program per weight dtype by design
    # apexlint: disable-next=APX302
    int8_ms = _timeit(jax.jit(lambda x: int8_matmul(x, wq)), x)
    # apexlint: disable-next=APX302
    f32_ms = _timeit(jax.jit(lambda x: x @ w), x)
    rw = {"int8_weight_matmul_ms": round(int8_ms, 4),
          "f32_weight_matmul_ms": round(f32_ms, 4),
          "int8_weight_matmul_shape": f"{m}x{k_}x{n}",
          "backend": backend}
    print(json.dumps(rw), flush=True)
    rows.append({
        "kernel": "int8_weight_matmul",
        "shape": rw["int8_weight_matmul_shape"], "dtype": "int8",
        "kernel_ms": rw["int8_weight_matmul_ms"],
        "oracle_ms": rw["f32_weight_matmul_ms"],
        "speedup": (round(f32_ms / int8_ms, 2) if int8_ms else None)})

    # welford mean/var (SyncBN's local-stats kernel), NHWC-flat shape
    from apex_tpu.ops import welford as wf
    xw = jax.random.normal(key, (64 * 56 * 56, 256), jnp.bfloat16)
    rows.append(bench_pair("welford_mean_var", "200704x256", "bf16",
                           wf.welford_mean_var, wf.welford_mean_var_ref,
                           xw))

    # multi-tensor substrate
    n = 1 << 24
    g = jax.random.normal(jax.random.key(2), (n,), jnp.float32) * 0.01
    # fused amp gradient epilogue: unscale + non-finite + Σg² in ONE
    # HBM read, vs the same three answers computed the per-leaf way
    # (scale pass + isfinite pass + l2norm pass over the same buffer)
    inv = jnp.float32(1.0 / 65536.0)
    rows.append(bench_pair(
        "flat_unscale_norm", f"n={n}", "f32",
        lambda g_: mt.flat_unscale_norm(g_, inv),
        lambda g_: mt.flat_unscale_norm_ref(g_, inv), g))

    # per-leaf vs bucketed fused-optimizer step on a many-leaf pytree —
    # the end-to-end number the flat buffers exist for (recorded in the
    # bench round via bench.py extras too)
    from apex_tpu.optimizers.bucketing_bench import \
        bench_amp_pipeline, bench_optimizer_bucketing
    r = bench_optimizer_bucketing()
    r["backend"] = backend
    print(json.dumps(r), flush=True)
    rows.append({
        "kernel": "fused_adam_bucketed_step",
        "shape": f"{r['optim_leaves']}leaves/{r['optim_elements']}elem",
        "dtype": "f32",
        "kernel_ms": r["optim_step_bucketed_ms"],
        "oracle_ms": r["optim_step_perleaf_ms"],
        "speedup": r.get("optim_bucketing_speedup")})

    # full AMP gradient pipeline, flat vs per-leaf (pack-once + fused
    # unscale/norm/clip vs 3-4 pytree sweeps) on the same many-leaf tree
    ra = bench_amp_pipeline()
    ra["backend"] = backend
    print(json.dumps(ra), flush=True)
    rows.append({
        "kernel": "amp_flat_pipeline_step",
        "shape": f"{ra['amp_leaves']}leaves/{ra['amp_elements']}elem",
        "dtype": "f32",
        "kernel_ms": ra["amp_step_flat_ms"],
        "oracle_ms": ra["amp_step_per_leaf_ms"],
        "speedup": ra.get("amp_pipeline_speedup")})

    # microbatch accumulation loop body, fused flat_accumulate (one
    # RMW per bucket + found_inf latch) vs the per-leaf tree-map add
    # (the APX103 shape) on the same many-leaf tree
    from apex_tpu.optimizers.bucketing_bench import bench_flat_accumulate
    rg = bench_flat_accumulate()
    rg["backend"] = backend
    print(json.dumps(rg), flush=True)
    rows.append({
        "kernel": "flat_accumulate",
        "shape": f"{rg['accum_leaves']}leaves/{rg['accum_elements']}elem",
        "dtype": "f32",
        "kernel_ms": rg["accum_flat_ms"],
        "oracle_ms": rg["accum_per_leaf_ms"],
        "speedup": rg.get("accum_flat_speedup")})

    # training-state snapshot+serialize, bucket-native (v2: one device
    # copy + one d2h per bucket) vs per-leaf (v1: state_dict walk) on a
    # mixed-dtype many-leaf tree — the checkpoint cost a step loop pays
    from apex_tpu.optimizers.bucketing_bench import \
        bench_checkpoint_snapshot
    rc = bench_checkpoint_snapshot()
    rc["backend"] = backend
    print(json.dumps(rc), flush=True)
    rows.append({
        "kernel": "checkpoint_snapshot",
        "shape": f"{rc['ckpt_leaves']}leaves/{rc['ckpt_elements']}elem",
        "dtype": "bf16+f32",
        "kernel_ms": rc["ckpt_snapshot_bucketed_ms"],
        "oracle_ms": rc["ckpt_snapshot_perleaf_ms"],
        "speedup": rc.get("ckpt_snapshot_speedup")})

    # telemetry overhead: the IDENTICAL flat-AMP train step, metric
    # ring on vs off ("kernel" = instrumented, "oracle" = plain — a
    # speedup of ~1.0 IS the pass condition: the ring must be free)
    from apex_tpu.telemetry.bench import bench_telemetry_overhead
    rt = bench_telemetry_overhead()
    rt["backend"] = backend
    print(json.dumps(rt), flush=True)
    rows.append({
        "kernel": "telemetry_overhead",
        "shape": (f"{rt['telemetry_leaves']}leaves/"
                  f"w{rt['telemetry_window']}x{rt['telemetry_metrics']}"),
        "dtype": "f32",
        "kernel_ms": rt["telemetry_on_ms"],
        "oracle_ms": rt["telemetry_off_ms"],
        "speedup": (round(rt["telemetry_off_ms"] / rt["telemetry_on_ms"],
                          2) if rt["telemetry_on_ms"] else None)})

    # profiler overhead: the identical step, annotate_step-wrapped vs
    # plain with NO capture running ("kernel" = profile-capable,
    # "oracle" = plain — ~1.0 IS the pass condition: a profiled-capable
    # step must cost nothing until a trace window opens; the
    # profiler.annotated_step apexverify spec proves the same fact
    # structurally)
    from apex_tpu.telemetry.bench import bench_profiler_overhead
    rp = bench_profiler_overhead()
    rp["backend"] = backend
    print(json.dumps(rp), flush=True)
    rows.append({
        "kernel": "profiler_overhead",
        "shape": f"{rp['profiler_leaves']}leaves",
        "dtype": "f32",
        "kernel_ms": rp["profiler_on_ms"],
        "oracle_ms": rp["profiler_off_ms"],
        "speedup": (round(rp["profiler_off_ms"] / rp["profiler_on_ms"],
                          2) if rp["profiler_on_ms"] else None)})

    # exporter overhead: the same instrumented step with the live
    # MetricsServer attached vs the bare step ("kernel" = exported,
    # "oracle" = bare — ~1.0 IS the pass condition: /metrics
    # republishes already-flushed host data only; the flush-time
    # republish cost shows up separately as export_publish_ms.  The
    # telemetry.exported_step apexverify spec proves the same fact
    # structurally)
    from apex_tpu.telemetry.bench import bench_exporter_overhead
    rex = bench_exporter_overhead()
    rex["backend"] = backend
    print(json.dumps(rex), flush=True)
    rows.append({
        "kernel": "exporter_overhead",
        "shape": (f"{rex['exporter_leaves']}leaves/"
                  f"w{rex['exporter_window']}x"
                  f"{rex['exporter_metrics']}"),
        "dtype": "f32",
        "kernel_ms": rex["exporter_on_ms"],
        "oracle_ms": rex["exporter_off_ms"],
        "speedup": (round(rex["exporter_off_ms"]
                          / rex["exporter_on_ms"], 2)
                    if rex["exporter_on_ms"] else None)})

    # watchdog overhead: the same instrumented step with the anomaly
    # watchdog attached vs the bare step ("kernel" = watchdog-attached,
    # "oracle" = bare — ~1.0 IS the pass condition: detectors are
    # host-side, window-cadence only; the host detector cost shows up
    # separately as watchdog_observe_ms)
    from apex_tpu.telemetry.bench import bench_watchdog_overhead
    rwd = bench_watchdog_overhead()
    rwd["backend"] = backend
    print(json.dumps(rwd), flush=True)
    rows.append({
        "kernel": "watchdog_overhead",
        "shape": (f"{rwd['watchdog_leaves']}leaves/"
                  f"w{rwd['watchdog_window']}"
                  f"x{rwd['watchdog_detectors']}det"),
        "dtype": "f32",
        "kernel_ms": rwd["watchdog_on_ms"],
        "oracle_ms": rwd["watchdog_off_ms"],
        "speedup": (round(rwd["watchdog_off_ms"] / rwd["watchdog_on_ms"],
                          2) if rwd["watchdog_on_ms"] else None)})

    # fleet overhead: the same instrumented step with a FleetMonitor
    # attached vs the bare step ("kernel" = fleet-monitored, "oracle"
    # = bare — ~1.0 IS the pass condition: the liveness beacon is
    # host-side and out-of-band; the per-boundary host cost shows up
    # separately as fleet_beat_ms.  The fleet.instrumented_step
    # apexverify spec proves the same fact structurally)
    from apex_tpu.telemetry.bench import bench_fleet_overhead
    rfl = bench_fleet_overhead()
    rfl["backend"] = backend
    print(json.dumps(rfl), flush=True)
    rows.append({
        "kernel": "fleet_overhead",
        "shape": (f"{rfl['fleet_leaves']}leaves/"
                  f"{rfl['fleet_hosts']}hosts"),
        "dtype": "f32",
        "kernel_ms": rfl["fleet_on_ms"],
        "oracle_ms": rfl["fleet_off_ms"],
        "speedup": (round(rfl["fleet_off_ms"] / rfl["fleet_on_ms"], 2)
                    if rfl["fleet_on_ms"] else None)})

    # lockwatch overhead: the identical flush-shaped critical section
    # under a WatchedLock vs a plain Lock with no sink registered
    # ("kernel" = watched, "oracle" = plain — ~1.0 IS the pass
    # condition: an unobserved watched lock must be free; the raw
    # per-acquire surcharge shows up separately as
    # lockwatch_acquire_ns)
    from apex_tpu.telemetry.bench import bench_lockwatch_overhead
    rlw = bench_lockwatch_overhead()
    rlw["backend"] = backend
    print(json.dumps(rlw), flush=True)
    rows.append({
        "kernel": "lockwatch_overhead",
        "shape": (f"w{rlw['lockwatch_window']}x"
                  f"{rlw['lockwatch_metrics']}"),
        "dtype": "f32",
        "kernel_ms": rlw["lockwatch_on_ms"],
        "oracle_ms": rlw["lockwatch_off_ms"],
        "speedup": (round(rlw["lockwatch_off_ms"]
                          / rlw["lockwatch_on_ms"], 2)
                    if rlw["lockwatch_on_ms"] else None)})

    # autoscaler overhead: the same instrumented step with a
    # FleetController (+ monitor) observing the session vs the bare
    # step ("kernel" = controller-observed, "oracle" = bare — ~1.0 IS
    # the pass condition: load-driven scaling is host-side window-flush
    # intake + one decide per boundary, measured separately as
    # autoscaler_decide_ms.  The fleet.autoscaled_step apexverify spec
    # proves the same fact structurally)
    from apex_tpu.telemetry.bench import bench_autoscaler_overhead
    ras = bench_autoscaler_overhead()
    ras["backend"] = backend
    print(json.dumps(ras), flush=True)
    rows.append({
        "kernel": "autoscaler_overhead",
        "shape": (f"{ras['autoscaler_leaves']}leaves/"
                  f"{ras['autoscaler_hosts']}hosts"),
        "dtype": "f32",
        "kernel_ms": ras["autoscaler_on_ms"],
        "oracle_ms": ras["autoscaler_off_ms"],
        "speedup": (round(ras["autoscaler_off_ms"]
                          / ras["autoscaler_on_ms"], 2)
                    if ras["autoscaler_on_ms"] else None)})

    # reqtrace overhead: the identical serve stream through a traced
    # engine vs trace=False ("kernel" = traced, "oracle" = untraced —
    # ~1.0 IS the pass condition: request tracing is host-side
    # bookkeeping assembled from events the loop already has; the
    # serving.traced_decode_step apexverify spec proves the same fact
    # structurally — zero added prims in the lowered window)
    from apex_tpu.serving.bench import bench_reqtrace_overhead
    rrt = bench_reqtrace_overhead()
    rrt["backend"] = backend
    print(json.dumps(rrt), flush=True)
    rows.append({
        "kernel": "reqtrace_overhead",
        "shape": f"{rrt['reqtrace_traces']}req",
        "dtype": "f32",
        "kernel_ms": rrt["reqtrace_on_ms"],
        "oracle_ms": rrt["reqtrace_off_ms"],
        "speedup": (round(rrt["reqtrace_off_ms"]
                          / rrt["reqtrace_on_ms"], 2)
                    if rrt["reqtrace_on_ms"] else None)})

    # apexcost ledger-build time: amortized ms per cost card over the
    # full spec registry — the static-analysis tier's own budget line
    # (tests/test_lint_cost.py smokes the same hook on a small subset)
    from apex_tpu.lint.cost.bench import bench_cost_extract
    rcx = bench_cost_extract()
    rcx["backend"] = backend
    print(json.dumps(rcx), flush=True)
    rows.append({
        "kernel": "cost_extract",
        "shape": f"{rcx['cost_specs']}specs",
        "dtype": "-",
        "kernel_ms": rcx["cost_extract_ms"],
        "oracle_ms": None,
        "speedup": None})

    for r in rows:
        r["backend"] = backend
        print(json.dumps(r), flush=True)
    if args.csv:
        import csv
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
    if args.write_prefs:
        from apex_tpu.ops import _dispatch
        prefs = write_prefs(rows, _dispatch._PREFS_PATH,
                            topology=_dispatch.topology_block(),
                            noise_floor_pct=noise_pct)
        _dispatch.invalidate_prefs_cache()
        print(json.dumps({"prefs_written": prefs}), flush=True)


if __name__ == "__main__":
    main()
