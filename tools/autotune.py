"""Kernel autotuner: sweep the dispatch candidate spaces and persist
per-topology dispatch tables (+ restamped perf budgets).

The measurement substrate existed (benchlib amortized timing, the PR-8
device-event attribution, perf_budget provenance, the stale-table
RuntimeWarning contract); this is its consumer.  Per (op family, shape
class, dtype, topology) the sweep times:

- **routing**: each Pallas kernel family vs its XLA oracle
  (``prefer_pallas`` booleans, the VERDICT r2 #2 table);
- **attn_block_cap**: flash-attention sequence-block geometries per
  padded head dim (the kernel_bench --sweep-attn grid);
- **pipeline.max_bucket_bytes**: flat-pipeline bucket chunking for the
  comm/compute overlap schedule;
- **pipeline.reduce_decompose**: psum vs reduce-scatter+all-gather for
  the bucketed all-reduce.

Every timing uses benchlib's amortized on-device loop; a decision that
flips a design default must beat it beyond the session's measured
noise floor (``benchlib.noise_floor_pct``), and wall-clock winners are
cross-checked against device-event attribution
(``telemetry.profiler``): a winner whose edge disappears in the device
timeline is rejected as noise.  Results persist as ONE prefs table per
topology — ``apex_tpu/ops/dispatch_prefs.<topology>.json`` with
methodology + topology + noise-floor stamps — which
``ops/_dispatch.py`` selects by runtime topology (falling back to the
shipped default table with the loud-warning discipline).  The sweep
also restamps ``tools/perf_budget.json`` rows it can ground, so the
perf gate and the tuner share one source of truth.

    python tools/autotune.py --cpu-smoke [--out DIR]
        # deterministic plumbing run: tiny shapes, fixed candidate
        # lists, CPU interpret mode; writes the per-topology table and
        # a restamped budget COPY into --out (never the repo files),
        # then demonstrates the table changes >= 1 dispatch decision
    python tools/autotune.py --full
        # hardware sweep: full candidate spaces; installs
        # apex_tpu/ops/dispatch_prefs.<topology>.json and restamps
        # tools/perf_budget.json in place (refuses off-TPU)
    python tools/autotune.py --validate [FILES...]
        # stdlib-only schema check over shipped dispatch_prefs*.json
        # (tools/check.sh runs this: a hand-edited table fails fast
        # instead of being silently discarded at import)
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os as _os
import re
import sys as _sys
import time

# runnable straight from a checkout with no install (tools/lint.py idiom)
_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _ROOT not in _sys.path:
    _sys.path.insert(0, _ROOT)

_TOOLS = _os.path.join(_ROOT, "tools")
DEFAULT_OUT = _os.path.join(_TOOLS, "artifacts", "autotune")
BUDGET_PATH = _os.path.join(_TOOLS, "perf_budget.json")

# keep in sync with apex_tpu.ops._dispatch.SCHEMA_VERSION (asserted by
# tests/test_autotune.py); duplicated so --validate stays jax-free.
SCHEMA_VERSION = 2

_REDUCE_CHOICES = ("psum", "reduce_scatter")

# keep in sync with apex_tpu.ops._dispatch.KV_DTYPE_CHOICES; duplicated
# so --validate stays jax-free.
_KV_DTYPE_CHOICES = ("f32", "bf16", "int8")
_WEIGHT_DTYPE_CHOICES = ("f32", "int8")


def _load_sibling(name):
    """Import a sibling tools/ module (tools/ is not a package)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, _os.path.join(_TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _now() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


# ---------------------------------------------------------------------------
# schema validation (stdlib only — check.sh runs this on every push)
# ---------------------------------------------------------------------------

def validate_table(doc, *, per_topology: bool, path: str = "") -> list:
    """Schema errors for one dispatch-prefs doc (empty list = valid).

    The default ``dispatch_prefs.json`` (``per_topology=False``) needs
    the methodology stamp and in-domain values; a per-topology
    ``dispatch_prefs.<key>.json`` additionally needs the schema
    version, a topology block whose key matches the filename, and a
    noise-floor stamp — everything ``ops/_dispatch.py`` would silently
    discard the table for lacking must fail loudly here instead."""
    errs = []
    if not isinstance(doc, dict):
        return [f"{path or '<doc>'}: not a JSON object"]

    def err(msg):
        errs.append(f"{path or '<doc>'}: {msg}")

    if doc.get("methodology") != "amortized":
        err(f"methodology must be 'amortized', found "
            f"{doc.get('methodology')!r} (tables without the stamp "
            "measured the dispatch, not the kernels, and are ignored at "
            "import)")

    prefs = doc.get("prefer_pallas", {})
    if not isinstance(prefs, dict):
        err("prefer_pallas must be an object")
    else:
        for k, v in prefs.items():
            if not isinstance(v, bool):
                err(f"prefer_pallas[{k!r}] must be a JSON boolean, "
                    f"found {v!r}")

    caps = doc.get("attn_block_cap", {})
    if not isinstance(caps, dict):
        err("attn_block_cap must be an object")
    else:
        for k, v in caps.items():
            if not isinstance(v, int) or isinstance(v, bool) \
                    or v <= 0 or v % 128:
                err(f"attn_block_cap[{k!r}] must be a positive "
                    f"multiple of 128, found {v!r}")

    pipe = doc.get("pipeline", {})
    if not isinstance(pipe, dict):
        err("pipeline must be an object")
    else:
        if "max_bucket_bytes" in pipe:
            v = pipe["max_bucket_bytes"]
            if v is not None and (not isinstance(v, int)
                                  or isinstance(v, bool) or v <= 0):
                err(f"pipeline.max_bucket_bytes must be a positive "
                    f"integer or null, found {v!r}")
        if "reduce_decompose" in pipe \
                and pipe["reduce_decompose"] not in _REDUCE_CHOICES:
            err(f"pipeline.reduce_decompose must be one of "
                f"{_REDUCE_CHOICES}, found {pipe['reduce_decompose']!r}")

    f8 = doc.get("fp8", {})
    if not isinstance(f8, dict):
        err("fp8 must be an object")
    else:
        for k in ("amax_history_len", "interval"):
            if k in f8 and (not isinstance(f8[k], int)
                            or isinstance(f8[k], bool) or f8[k] <= 0):
                err(f"fp8.{k} must be a positive integer, "
                    f"found {f8[k]!r}")

    quant = doc.get("quantization", {})
    if not isinstance(quant, dict):
        err("quantization must be an object")
    elif "int8_dynamic" in quant \
            and not isinstance(quant["int8_dynamic"], bool):
        err(f"quantization.int8_dynamic must be a JSON boolean, "
            f"found {quant['int8_dynamic']!r}")

    srv = doc.get("serving", {})
    if not isinstance(srv, dict):
        err("serving must be an object")
    else:
        for k in ("page_size", "decode_window", "prefill_batch"):
            if k in srv and (not isinstance(srv[k], int)
                             or isinstance(srv[k], bool)
                             or srv[k] <= 0):
                err(f"serving.{k} must be a positive integer, "
                    f"found {srv[k]!r}")
        # spec_k is the one serving integer where 0 is a VALID value
        # (speculation off), so it cannot ride the positive-int loop
        if "spec_k" in srv and (not isinstance(srv["spec_k"], int)
                                or isinstance(srv["spec_k"], bool)
                                or srv["spec_k"] < 0):
            err(f"serving.spec_k must be a non-negative integer, "
                f"found {srv['spec_k']!r}")
        if "kv_dtype" in srv and srv["kv_dtype"] not in _KV_DTYPE_CHOICES:
            err(f"serving.kv_dtype must be one of {_KV_DTYPE_CHOICES}, "
                f"found {srv['kv_dtype']!r}")
        if "weight_dtype" in srv \
                and srv["weight_dtype"] not in _WEIGHT_DTYPE_CHOICES:
            err(f"serving.weight_dtype must be one of "
                f"{_WEIGHT_DTYPE_CHOICES}, "
                f"found {srv['weight_dtype']!r}")
        if "prefix_share" in srv \
                and not isinstance(srv["prefix_share"], bool):
            err(f"serving.prefix_share must be a JSON boolean, "
                f"found {srv['prefix_share']!r}")

    topo = doc.get("topology")
    if topo is not None:
        if not isinstance(topo, dict) or not isinstance(
                topo.get("key"), str) or not topo.get("key"):
            err("topology block must be an object with a string 'key'")
        else:
            for field, typ in (("device_kind", str),
                               ("device_count", int)):
                if not isinstance(topo.get(field), typ) \
                        or isinstance(topo.get(field), bool):
                    err(f"topology.{field} must be a {typ.__name__}")

    if per_topology:
        if doc.get("schema") != SCHEMA_VERSION:
            err(f"per-topology tables require schema={SCHEMA_VERSION}, "
                f"found {doc.get('schema')!r}")
        if topo is None:
            err("per-topology tables require a topology block")
        elif isinstance(topo, dict) and isinstance(topo.get("key"), str) \
                and path:
            want = f"dispatch_prefs.{topo['key']}.json"
            if _os.path.basename(path) != want:
                err(f"filename must match topology.key "
                    f"(expected {want})")
        nf = doc.get("noise_floor_pct")
        if not isinstance(nf, (int, float)) or isinstance(nf, bool) \
                or nf < 0:
            err(f"noise_floor_pct must be a non-negative number, "
                f"found {nf!r}")
    return errs


LEDGER_PATH = _os.path.join(_ROOT, "apex_tpu", "lint", "cost",
                            "ledger.json")


def _ledger_schema():
    """The apexcost ledger schema validator, loaded from its module
    FILE so --validate stays jax-free (importing the apex_tpu.lint
    package would pull the whole lint stack; ledger.py itself is
    stdlib-only)."""
    import importlib.util
    p = _os.path.join(_ROOT, "apex_tpu", "lint", "cost", "ledger.py")
    spec = importlib.util.spec_from_file_location("_apexcost_ledger", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def validate_paths(paths=None) -> list:
    """Validate every shipped dispatch_prefs*.json plus the apexcost
    cost ledger (or the given paths); returns all errors.  Unreadable
    JSON is an error — a hand-edit that truncates a file must fail CI,
    not degrade to design defaults silently.  A path named
    ``ledger.json`` (or any doc carrying a ``cards`` map) validates
    against the apexcost ledger schema instead of the dispatch-table
    schema."""
    if not paths:
        paths = sorted(glob.glob(_os.path.join(
            _ROOT, "apex_tpu", "ops", "dispatch_prefs*.json")))
        paths.append(LEDGER_PATH)
    errs = []
    for p in paths:
        base = _os.path.basename(p)
        try:
            with open(p, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            errs.append(f"{p}: unreadable ({e})")
            continue
        if base == "ledger.json" or (isinstance(doc, dict)
                                     and "cards" in doc):
            errs.extend(_ledger_schema().validate(doc, p))
            continue
        per_topo = re.fullmatch(r"dispatch_prefs\..+\.json",
                                base) is not None
        errs.extend(validate_table(doc, per_topology=per_topo, path=p))
    return errs


# ---------------------------------------------------------------------------
# budget restamp (stdlib only)
# ---------------------------------------------------------------------------

def restamp_budget(budget: dict, measured: dict, *, topology: str,
                   backend: str, noise_floor_pct: float, mode: str,
                   when: str) -> list:
    """Restamp ``perf_budget.json`` rows the sweep grounded: for each
    measured metric present in the budget, the floor (or ceiling)
    moves to the measured value and the row gains sweep provenance, so
    the perf gate defends what the tuner just measured — one source of
    truth.  The top-level stamp date only moves on a HARDWARE sweep
    (perf_gate's auto-gating keys off it; a CPU smoke restamp is
    plumbing, not a perf claim).  Mutates ``budget``; returns the
    restamped row names."""
    rows = []
    metrics = budget.setdefault("metrics", {})
    for name, value in sorted(measured.items()):
        spec = metrics.get(name)
        if not isinstance(spec, dict) or not isinstance(
                value, (int, float)) or isinstance(value, bool):
            continue
        if spec.get("direction", "higher") == "higher":
            spec["floor"] = round(float(value), 3)
        else:
            spec["ceiling"] = round(float(value), 3)
        spec["restamped"] = {
            "by": "tools/autotune.py", "mode": mode,
            "topology": topology, "backend": backend,
            "measured": round(float(value), 4),
            "noise_floor_pct": round(float(noise_floor_pct), 2),
            "at": when}
        rows.append(name)
    if rows and backend == "tpu":
        budget["stamped_at"] = when
        budget["stamped_from"] = (f"tools/autotune.py sweep on "
                                  f"{topology} at {when}")
    return rows


# ---------------------------------------------------------------------------
# sweep machinery (jax imported lazily)
# ---------------------------------------------------------------------------

def smoke_config() -> dict:
    """Fixed tiny candidate spaces: the whole sweep -> table ->
    dispatch-decision-change -> budget-restamp pipeline runs
    deterministically in CPU interpret mode (tier-1), no hardware."""
    return {
        "mode": "cpu-smoke", "iters": 20, "reps": 3,
        "mt_n": 4096,
        "welford_shape": (256, 128),
        "attn_shapes": [(1, 1, 256, 64)],
        "attn_caps": [128, 256],
        "attn_grad": False,
        "chunk_candidates": [None, 16384],
        "pipe_layers": 4, "pipe_hidden": 32, "pipe_batch": 8,
        "reduce_n": 8192,
        "accum": dict(layers=3, hidden=32, batch=8, n_micro=(8,),
                      iters=2, reps=2),
        "fp8_hist_candidates": [4, 16],
        "fp8_interval_candidates": [1, 4],
        "fp8_layers": 4, "fp8_hidden": 32, "fp8_batch": 8,
        "int8_mkn": (64, 64, 64),
        "serving_page_candidates": [4, 8],
        "serving_window_candidates": [4, 8],
        "serving_layers": 2, "serving_hidden": 32,
        "serving_heads": 2, "serving_slots": 2, "serving_ctx": 16,
        # the kv-dtype leg pins head_dim=64 (hidden/heads): the bytes
        # ratio is structural in head_dim and the budget ceiling (0.55)
        # is stamped at the production width, not the smoke width
        "serving_quant_hidden": 256, "serving_quant_heads": 4,
        "serving_share_requests": 4,
        # one non-zero K: the smoke proves the sweep plumbing + the
        # bit-exact oracle; the K frontier itself is a --full question
        "serving_spec_candidates": [0, 2],
        "serving_prefill_batch": 2,
        "device_check_families": ["multi_tensor"],
    }


def full_config() -> dict:
    """Hardware candidate spaces (the overdue re-measure: run this in
    the first live TPU window — it restamps everything that predates
    the flat pipeline and the overlap schedule)."""
    return {
        "mode": "full", "iters": 10, "reps": 3,
        "mt_n": 1 << 24,
        "welford_shape": (64 * 56 * 56, 256),
        "attn_shapes": [(8, 16, 512, 64), (4, 16, 2048, 128),
                        (2, 16, 2048, 256)],
        "attn_caps": [128, 256, 512, 1024],
        "attn_grad": True,
        "chunk_candidates": [None, 1 << 25, 1 << 26, 1 << 27],
        "pipe_layers": 48, "pipe_hidden": 256, "pipe_batch": 64,
        "reduce_n": 1 << 22,
        "accum": dict(layers=16, hidden=128, batch=32, n_micro=(8,),
                      iters=5, reps=3),
        "fp8_hist_candidates": [4, 16, 64],
        "fp8_interval_candidates": [1, 4, 16],
        "fp8_layers": 24, "fp8_hidden": 512, "fp8_batch": 64,
        "int8_mkn": (4096, 4096, 4096),
        "serving_page_candidates": [8, 16, 32, 64],
        "serving_window_candidates": [8, 16, 32],
        "serving_layers": 8, "serving_hidden": 512,
        "serving_heads": 8, "serving_slots": 16, "serving_ctx": 1024,
        "serving_quant_hidden": 512, "serving_quant_heads": 8,
        "serving_share_requests": 8,
        "serving_spec_candidates": [0, 2, 4, 8],
        "serving_prefill_batch": 4,
        "device_check_families": ["multi_tensor", "welford",
                                  "layer_norm", "pipeline", "fp8"],
    }


def _time(fn, *args, cfg):
    import jax

    from apex_tpu.benchlib import timeit
    return timeit(jax.jit(fn), *args, iters=cfg["iters"],
                  reps=cfg["reps"], adaptive=(cfg["mode"] == "full"))


def measure_noise_floor(cfg) -> float:
    """Session noise floor from a representative fused body (the
    welford oracle at this config's shape)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.benchlib import noise_floor_pct
    from apex_tpu.ops import welford as wf
    r, c = cfg["welford_shape"]
    x = jax.random.normal(jax.random.key(11), (r, c), jnp.bfloat16)
    return round(noise_floor_pct(
        jax.jit(wf.welford_mean_var_ref), x,
        trials=3, iters=cfg["iters"], reps=cfg["reps"]), 2)


def device_event_check(label: str, fast, slow, outdir: str) -> dict:
    """Cross-check a wall-clock verdict against the device timeline:
    capture the winner and the loser under short profiler windows and
    compare device-busy time (compute+collective+transfer, interval-
    union).  ``fast``/``slow`` are (callable, args) with the
    wall-clock winner first.  Verdict "rejected" means the wall-clock
    edge disappeared on device — the decision must not flip a default
    on it."""
    import jax

    from apex_tpu.benchlib import sync
    from apex_tpu.telemetry.profiler import attribution, capture, events
    busy, n_events = {}, {}
    for side, (fn, args) in (("fast", fast), ("slow", slow)):
        d = _os.path.join(outdir, "device_check",
                          re.sub(r"[^A-Za-z0-9_.-]", "_",
                                 f"{label}_{side}"))
        _os.makedirs(d, exist_ok=True)
        try:
            # two sides = two programs by design (one jit each, not a
            # per-iteration retrace: the capture loop reuses jf)
            # apexlint: disable-next=APX302
            jf = jax.jit(fn)
            out = jf(*args)
            sync(out)                 # compile OUTSIDE the window
            with capture.trace(d):
                for _ in range(3):
                    out = jf(*args)
                sync(out)
            evs = events.load_device_events(d)
        except Exception as e:       # a failed capture must not kill
            return {"checked": False,  # the sweep — record and move on
                    "reason": f"capture failed: {e!r}"[:200]}
        b = attribution.attribute(evs)
        busy[side] = round(b.compute_ms + b.collective_ms
                           + b.transfer_ms, 4)
        n_events[side] = b.n_events
    if not n_events["fast"] or not n_events["slow"]:
        return {"checked": False, "reason": "no device events parsed",
                "n_events": n_events}
    verdict = "confirmed" if busy["fast"] < busy["slow"] else "rejected"
    return {"checked": True, "verdict": verdict,
            "fast_busy_ms": busy["fast"], "slow_busy_ms": busy["slow"],
            "n_events": n_events}


def _routing_cases(cfg):
    """(family, shape_desc, dtype, kernel_fn, oracle_fn, args) per
    measured shape class.  Smoke keeps the two cheapest families; full
    covers every family kernel_bench maps (tools/kernel_bench.py
    _OP_FAMILY)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import multi_tensor as mt
    from apex_tpu.ops import welford as wf
    cases = []
    key = jax.random.key(0)

    n = cfg["mt_n"]
    g = jax.random.normal(jax.random.key(2), (n,), jnp.float32) * 0.01
    inv = jnp.float32(1.0 / 65536.0)
    cases.append(("multi_tensor", f"flat_unscale_norm/n={n}", "f32",
                  lambda g_: mt.flat_unscale_norm(g_, inv),
                  lambda g_: mt.flat_unscale_norm_ref(g_, inv),
                  (g,)))

    r, c = cfg["welford_shape"]
    xw = jax.random.normal(key, (r, c), jnp.bfloat16)
    cases.append(("welford", f"{r}x{c}", "bf16",
                  wf.welford_mean_var, wf.welford_mean_var_ref, (xw,)))

    if cfg["mode"] == "full":
        from apex_tpu.ops import attention as attn
        from apex_tpu.ops import layer_norm as ln
        from apex_tpu.ops import softmax as sm
        from apex_tpu.ops import xentropy as xe

        def grad_of(f, n_args):
            return jax.grad(
                lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
                argnums=tuple(range(n_args)))

        for (b, h, s, d) in cfg["attn_shapes"][:2]:
            ks = jax.random.split(key, 3)
            q, k, v_ = (jax.random.normal(kk, (b, h, s, d),
                                          jnp.bfloat16) for kk in ks)
            f_k = functools.partial(attn.flash_attention, causal=True)
            f_o = functools.partial(attn.attention_ref, causal=True)
            cases.append(("attention", f"b{b}h{h}s{s}d{d}", "bf16",
                          grad_of(f_k, 3), grad_of(f_o, 3),
                          (q, k, v_)))
        qf, kf, vf = (jax.random.normal(kk, (8, 16, 512, 64),
                                        jnp.float32)
                      for kk in jax.random.split(jax.random.key(5), 3))
        cases.append(("attention_f32", "b8h16s512d64", "f32",
                      grad_of(functools.partial(attn.flash_attention,
                                                causal=True), 3),
                      grad_of(functools.partial(attn.attention_ref,
                                                causal=True), 3),
                      (qf, kf, vf)))
        for (r_, hdim) in [(8192, 1024), (4096, 4096)]:
            x = jax.random.normal(key, (r_, hdim), jnp.bfloat16)
            w = jnp.ones((hdim,), jnp.bfloat16)
            b_ = jnp.zeros((hdim,), jnp.bfloat16)
            cases.append(("layer_norm", f"{r_}x{hdim}", "bf16",
                          ln.fused_layer_norm, ln.layer_norm_ref,
                          (x, w, b_)))
        xs = jax.random.normal(key, (8 * 16, 512, 512), jnp.bfloat16)
        cases.append(("softmax", "128x512x512", "bf16",
                      functools.partial(
                          sm.scaled_upper_triang_masked_softmax,
                          scale=1.0),
                      functools.partial(
                          sm.scaled_upper_triang_masked_softmax_ref,
                          scale=1.0), (xs,)))
        logits = jax.random.normal(key, (4096, 32768), jnp.bfloat16)
        labels = jax.random.randint(jax.random.key(1), (4096,), 0,
                                    32768)
        cases.append(("xentropy", "4096x32768", "bf16",
                      lambda l: xe.softmax_cross_entropy(l, labels),
                      lambda l: xe.softmax_cross_entropy_ref(l, labels),
                      (logits,)))
    return cases


def sweep_routing(cfg, noise_pct: float, outdir: str) -> list:
    """Pallas-vs-XLA-oracle routing per family × shape class.  A
    family flips to the XLA path only when some shape lost beyond the
    noise floor AND (where a device check ran) the edge survives in
    the device timeline."""
    records = []
    by_family = {}
    for fam, shape, dtype, kern, oracle, args in _routing_cases(cfg):
        k_ms = _time(kern, *args, cfg=cfg)
        o_ms = _time(oracle, *args, cfg=cfg)
        rec = {"space": "routing", "family": fam, "shape": shape,
               "dtype": dtype, "kernel_ms": round(k_ms, 4),
               "oracle_ms": round(o_ms, 4),
               "speedup": round(o_ms / k_ms, 3) if k_ms else None,
               "noise_floor_pct": noise_pct}
        records.append(rec)
        by_family.setdefault(fam, []).append(
            (rec, kern, oracle, args))

    for fam, shapes in by_family.items():
        sps = [r["speedup"] for r, *_ in shapes
               if r["speedup"] is not None]
        lost = [x for x in sps if x < 1.0 - noise_pct / 100.0]
        prefer = not lost
        if lost and fam in cfg["device_check_families"]:
            # cross-check the WORST shape's verdict on the device
            # timeline before routing the whole family off Pallas
            worst = min(shapes, key=lambda s: s[0]["speedup"] or 1.0)
            rec, kern, oracle, args = worst
            check = device_event_check(
                f"routing_{fam}", fast=(oracle, args),
                slow=(kern, args), outdir=outdir)
            rec["device_check"] = check
            if check.get("checked") and check["verdict"] == "rejected":
                prefer = True
                rec["rejected_as_noise"] = True
        for rec, *_ in shapes:
            rec["decision"] = {"prefer_pallas": {fam: prefer}}
    return records


def sweep_attn_caps(cfg, noise_pct: float) -> list:
    """Flash-attention sequence-block-cap sweep (the kernel_bench
    --sweep-attn grid through the same amortized timer); winner per
    padded head dim via kernel_bench.select_attn_caps (a cap must be
    measured on EVERY swept shape of its dp to win)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import attention as attn
    kb = _load_sibling("kernel_bench")
    records = []
    sweep_times = {}
    for (b, h, s, d) in cfg["attn_shapes"]:
        ks = jax.random.split(jax.random.key(7), 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                   for kk in ks)
        dp = attn._round_up(d, attn._LANES)
        if cfg["attn_grad"]:
            fn = jax.grad(
                lambda q, k, v: jnp.sum(attn.flash_attention(
                    q, k, v, causal=True).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))
        else:
            fn = functools.partial(attn.flash_attention, causal=True)
        shape_ms = {}
        # save/restore an operator's own cap override (the pop-only
        # shape would delete it for the rest of the process)
        prev_cap = _os.environ.get("APEX_TPU_ATTN_BLOCK_CAP")
        for cap in cfg["attn_caps"]:
            if (cap > attn._round_up(s, attn._LANES)
                    or cap > attn._sweep_cap_ceiling(dp)):
                continue
            _os.environ["APEX_TPU_ATTN_BLOCK_CAP"] = str(cap)
            try:
                # re-jit per cap ON PURPOSE: the env knob changes
                # kernel geometry (apexlint: disable-next=APX302)
                ms = _time(fn, q, k, v, cfg=cfg)
            except Exception as e:
                records.append({"space": "attn_block_cap",
                                "family": "attention",
                                "shape": f"b{b}h{h}s{s}d{d}",
                                "cap": cap, "error": repr(e)[:200]})
                continue
            finally:
                if prev_cap is None:
                    _os.environ.pop("APEX_TPU_ATTN_BLOCK_CAP", None)
                else:
                    _os.environ["APEX_TPU_ATTN_BLOCK_CAP"] = prev_cap
            shape_ms[cap] = ms
        if not shape_ms:
            continue
        best = min(shape_ms.values())
        for cap, ms in shape_ms.items():
            sweep_times.setdefault((dp, cap), []).append(ms / best)
        records.append({"space": "attn_block_cap",
                        "family": "attention",
                        "shape": f"b{b}h{h}s{s}d{d}", "dtype": "bf16",
                        "dp": dp, "noise_floor_pct": noise_pct,
                        "candidates_ms": {str(c): round(m, 4)
                                          for c, m in shape_ms.items()}})
    caps = kb.select_attn_caps(sweep_times)
    if caps:
        records.append({"space": "attn_block_cap", "family": "attention",
                        "decision": {"attn_block_cap": caps}})
    return records


def sweep_pipeline_chunk(cfg, noise_pct: float, outdir: str) -> list:
    """``max_bucket_bytes`` candidates through a full flat-AMP train
    step (pack → unscale/norm → fused optimizer) on a many-leaf tree;
    the monolithic plan (None) is the design default and a chunked
    winner must beat it beyond the noise floor."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.bucketing_bench import (many_leaf_loss,
                                                     many_leaf_params)
    params = many_leaf_params(jax, jnp, cfg["pipe_layers"],
                              cfg["pipe_hidden"])
    x = jax.random.normal(jax.random.key(1),
                          (cfg["pipe_batch"], cfg["pipe_hidden"]))
    scaler = amp.LossScaleState.create(2.0 ** 12)
    # the SAME toy model bench_grad_accum measures (and the budget row
    # this sweep restamps) — see bucketing_bench.many_leaf_loss
    loss_fn = many_leaf_loss(jnp)

    times, steps = {}, {}
    for mbb in cfg["chunk_candidates"]:
        opt = FusedAdam(params, lr=1e-3, max_bucket_bytes=mbb)
        pipe = amp.FlatGradPipeline(optimizer=opt)
        hypers = {k: jnp.asarray(v, jnp.float32)
                  for k, v in opt.hypers.items()
                  if isinstance(v, float)}

        def step(work, opt_state, x, s, pipe=pipe, opt=opt,
                 hypers=hypers):
            loss, flat = pipe.scaled_value_and_grad(
                loss_fn, scaler, pipe.plan.unpack(work), x)
            new_w, _, new_s = opt._full_step_flat(
                work, None, opt_state, flat.bufs, s, 1.0, hypers,
                flat.found_inf)
            return loss, new_w, new_s

        # each candidate is its own bucket layout, so its own program
        # by design (apexlint: disable-next=APX302)
        times[mbb] = _time(step, opt._param_bufs, opt.opt_state, x,
                           jnp.int32(2), cfg=cfg)
        steps[mbb] = (step, (opt._param_bufs, opt.opt_state, x,
                             jnp.int32(2)))

    default_ms = times[None] if None in times else None
    winner = min(times, key=times.get)
    rec = {"space": "pipeline.max_bucket_bytes", "family": "pipeline",
           "shape": f"{cfg['pipe_layers']}layers"
                    f"x{cfg['pipe_hidden']}", "dtype": "f32",
           "noise_floor_pct": noise_pct,
           "candidates_ms": {str(k): round(v, 4)
                             for k, v in times.items()}}
    if winner is not None and default_ms is not None \
            and times[winner] < default_ms * (1.0 - noise_pct / 100.0):
        if "pipeline" in cfg["device_check_families"]:
            check = device_event_check(
                "pipeline_chunk", fast=steps[winner],
                slow=steps[None], outdir=outdir)
            rec["device_check"] = check
            if check.get("checked") and check["verdict"] == "rejected":
                rec["rejected_as_noise"] = True
                return [rec]
        rec["decision"] = {"pipeline": {"max_bucket_bytes": winner}}
    return [rec]


def sweep_reduce_decompose(cfg, noise_pct: float) -> list:
    """psum vs reduce-scatter+all-gather for the bucketed all-reduce,
    timed under shard_map over every local device; psum is the design
    default and reduce_scatter must win beyond the noise floor."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu import comm
    from apex_tpu.parallel.distributed import all_reduce_flat_buffers
    comm.destroy()
    mesh = comm.initialize(data=jax.device_count())
    try:
        buf = jax.random.normal(jax.random.key(3), (cfg["reduce_n"],),
                                jnp.float32)
        times = {}
        for dec in _REDUCE_CHOICES:
            def f(b, dec=dec):
                return all_reduce_flat_buffers(
                    [b], comm.AXIS_DATA, decompose=dec)[0]
            # the two decompositions are two programs by design
            # (apexlint: disable-next=APX302)
            fn = comm.shard_map(f, mesh, in_specs=(P(),), out_specs=P())
            times[dec] = _time(fn, buf, cfg=cfg)
    finally:
        comm.destroy()
    rec = {"space": "pipeline.reduce_decompose", "family": "pipeline",
           "shape": f"n={cfg['reduce_n']}/dev{jax.device_count()}",
           "dtype": "f32", "noise_floor_pct": noise_pct,
           "candidates_ms": {k: round(v, 4) for k, v in times.items()}}
    if times["reduce_scatter"] < times["psum"] * (1.0
                                                  - noise_pct / 100.0):
        rec["decision"] = {"pipeline":
                           {"reduce_decompose": "reduce_scatter"}}
    return [rec]


def sweep_fp8_cadence(cfg, noise_pct: float, outdir: str) -> list:
    """fp8 scaling-cadence sweep (amax history length x scale-update
    interval) through a full fp8 flat-AMP train step — fp8_matmul
    forward, packed grad-side scale update, fused optimizer with fp8
    weight slots.  The Fp8Policy defaults are the design default; a
    candidate cadence must beat them beyond the noise floor (and,
    where enabled, survive the device-timeline cross-check) before
    the table steers ``amp.fp8.tuned_policy()``."""
    import itertools

    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.amp import fp8 as fp8_mod
    from apex_tpu.fused_dense import fp8_matmul
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.bucketing_bench import many_leaf_params
    params = many_leaf_params(jax, jnp, cfg["fp8_layers"],
                              cfg["fp8_hidden"])
    x = jax.random.normal(jax.random.key(9),
                          (cfg["fp8_batch"], cfg["fp8_hidden"]))
    scaler = amp.LossScaleState.create(2.0 ** 12)
    default = (fp8_mod.Fp8Policy.amax_history_len,
               fp8_mod.Fp8Policy.interval)
    cands = sorted(set(itertools.product(
        cfg["fp8_hist_candidates"], cfg["fp8_interval_candidates"])
        ) | {default})

    times, steps = {}, {}
    for hist, interval in cands:
        policy = fp8_mod.Fp8Policy(amax_history_len=hist,
                                   interval=interval)
        opt = FusedAdam(params, lr=1e-3)
        opt.enable_fp8(policy)
        pipe = amp.FlatGradPipeline(optimizer=opt, fp8=policy)
        f8 = pipe.fp8_init()
        hypers = {k: jnp.asarray(v, jnp.float32)
                  for k, v in opt.hypers.items()
                  if isinstance(v, float)}

        def loss_fn(p, scales, x, policy=policy):
            h = x
            for k in sorted(p):
                h = jnp.tanh(fp8_matmul(h, p[k]["w"], policy=policy,
                                        w_scale=scales[k]["w"])
                             + p[k]["b"]) * p[k]["scale"] \
                    + p[k]["shift"]
            return jnp.mean(h ** 2)

        def step(work, opt_state, f8, x, s, pipe=pipe, opt=opt,
                 hypers=hypers, loss_fn=loss_fn):
            scales = opt.fp8_scales(opt_state)
            loss, flat, new_f8 = pipe.scaled_value_and_grad(
                loss_fn, scaler, pipe.plan.unpack(work), scales, x,
                fp8_state=f8)
            new_w, _, new_s = opt._full_step_flat(
                work, None, opt_state, flat.bufs, s, 1.0, hypers,
                flat.found_inf)
            return loss, new_w, new_s, new_f8

        # each cadence is its own program (history shapes differ) by
        # design (apexlint: disable-next=APX302)
        times[(hist, interval)] = _time(
            step, opt._param_bufs, opt.opt_state, f8, x,
            jnp.int32(2), cfg=cfg)
        steps[(hist, interval)] = (step, (opt._param_bufs,
                                          opt.opt_state, f8, x,
                                          jnp.int32(2)))

    winner = min(times, key=times.get)
    rec = {"space": "fp8.cadence", "family": "fp8",
           "shape": f"{cfg['fp8_layers']}layers"
                    f"x{cfg['fp8_hidden']}", "dtype": "e4m3/e5m2",
           "noise_floor_pct": noise_pct,
           "candidates_ms": {f"H{h}/N{n}": round(v, 4)
                             for (h, n), v in times.items()}}
    if winner != default and times[winner] \
            < times[default] * (1.0 - noise_pct / 100.0):
        if "fp8" in cfg["device_check_families"]:
            check = device_event_check(
                "fp8_cadence", fast=steps[winner],
                slow=steps[default], outdir=outdir)
            rec["device_check"] = check
            if check.get("checked") and check["verdict"] == "rejected":
                rec["rejected_as_noise"] = True
                return [rec]
        rec["decision"] = {"fp8": {"amax_history_len": winner[0],
                                   "interval": winner[1]}}
    return [rec]


def sweep_quantization(cfg, noise_pct: float) -> list:
    """int8 inference routing: dynamic full-int8 vs weight-only at one
    GEMM shape.  Weight-only is the design default (activation
    precision untouched); dynamic steers ``int8_matmul(dynamic=None)``
    only when it wins beyond the noise floor on THIS topology."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.quantization import int8_matmul, quantize_int8
    m, k, n = cfg["int8_mkn"]
    x = jax.random.normal(jax.random.key(12), (m, k), jnp.bfloat16)
    wq = quantize_int8(jax.random.normal(jax.random.key(13),
                                         (k, n)) * 0.05)
    times = {}
    for mode, dyn in (("weight_only", False), ("dynamic", True)):
        # the two modes are two programs by design
        # (apexlint: disable-next=APX302)
        times[mode] = _time(
            lambda x, dyn=dyn: int8_matmul(x, wq, dynamic=dyn), x,
            cfg=cfg)
    rec = {"space": "quantization.int8_dynamic", "family":
           "quantization", "shape": f"{m}x{k}x{n}", "dtype": "int8",
           "noise_floor_pct": noise_pct,
           "candidates_ms": {k_: round(v, 4)
                             for k_, v in times.items()}}
    if times["dynamic"] < times["weight_only"] * (1.0
                                                  - noise_pct / 100.0):
        rec["decision"] = {"quantization": {"int8_dynamic": True}}
    return [rec]


def sweep_serving_geometry(cfg, noise_pct: float) -> list:
    """Serving decode shape-bucket geometry: (page_size x decode
    window) through one compiled decode window at mid-generation
    occupancy, normalized to ms per emitted token (a bigger window
    amortizes dispatch but holds admission longer — the sweep only
    weighs device cost; the engine's latency SLO stays a caller
    knob).  (8, 8) is the design default; a candidate must beat it
    beyond the noise floor before the table steers
    ``serving.Engine``'s defaults via ``_dispatch.serving_pref``."""
    import itertools

    import jax

    from apex_tpu.serving.bench import bench_decode_step

    default = (8, 8)
    cands = sorted(set(itertools.product(
        cfg["serving_page_candidates"],
        cfg["serving_window_candidates"])) | {default})
    times = {}
    for page, window in cands:
        r = bench_decode_step(
            n_layers=cfg["serving_layers"],
            hidden=cfg["serving_hidden"],
            n_heads=cfg["serving_heads"],
            max_slots=cfg["serving_slots"], page_size=page,
            pages_per_slot=max(1, cfg["serving_ctx"] // page),
            window=window, iters=cfg["iters"], reps=cfg["reps"])
        times[(page, window)] = (r["decode_step_paged_ms"]
                                 / (cfg["serving_slots"] * window))
    winner = min(times, key=times.get)
    rec = {"space": "serving.decode_geometry", "family": "serving",
           "shape": f"b{cfg['serving_slots']}ctx{cfg['serving_ctx']}"
                    f"x{cfg['serving_layers']}L",
           "dtype": "f32", "noise_floor_pct": noise_pct,
           "candidates_ms_per_token": {
               f"p{p}/w{w}": round(v, 5)
               for (p, w), v in sorted(times.items())}}
    if winner != default and times[winner] \
            < times[default] * (1.0 - noise_pct / 100.0):
        rec["decision"] = {"serving": {"page_size": winner[0],
                                       "decode_window": winner[1]}}
    return [rec]


_SERVING_MEMORY_MEMO = {}


def _serving_memory_benches(cfg):
    """Run (once per config) the two serving-memory benches that both
    the sweep and the budget restamp consume — each builds and
    compiles its own engine, so re-running them for the budget rows
    would double the sweep's compile bill for identical numbers."""
    from apex_tpu.serving.bench import bench_kv_quant_gather, \
        bench_prefix_admission
    key = (cfg["serving_layers"], cfg["serving_quant_hidden"],
           cfg["serving_quant_heads"], cfg["serving_slots"],
           cfg["serving_hidden"], cfg["serving_heads"],
           cfg["serving_share_requests"], cfg["iters"], cfg["reps"])
    if key not in _SERVING_MEMORY_MEMO:
        rq = bench_kv_quant_gather(
            n_layers=cfg["serving_layers"],
            hidden=cfg["serving_quant_hidden"],
            n_heads=cfg["serving_quant_heads"],
            max_slots=cfg["serving_slots"], page_size=8,
            pages_per_slot=2, iters=cfg["iters"], reps=cfg["reps"])
        rp = bench_prefix_admission(
            n_requests=cfg["serving_share_requests"],
            n_layers=cfg["serving_layers"],
            hidden=cfg["serving_hidden"],
            n_heads=cfg["serving_heads"], page_size=4,
            pages_per_slot=8, prompt_len=12, window=4)
        _SERVING_MEMORY_MEMO[key] = (rq, rp)
    return _SERVING_MEMORY_MEMO[key]


def sweep_serving_memory(cfg, noise_pct: float) -> list:
    """Serving memory frontier: kv_dtype and prefix_share.

    kv_dtype weighs the int8 gather+dequantize leg against the bf16
    gather (bench_kv_quant_gather) — the bytes halving is structural,
    so int8 wins unless its cast overhead exceeds the noise floor (the
    memory is free; only the compute tax can disqualify it).
    prefix_share is graded structurally: an N-way shared-prompt serve
    (bench_prefix_admission) must show prefill savings at or above the
    budget floor (2.0) with every request completed — wall clock never
    decides, the engine's prefill/extend counters do."""
    rq, rp = _serving_memory_benches(cfg)
    rec_q = {"space": "serving.kv_dtype", "family": "serving",
             "shape": f"b{rq['kv_gather_slots']}"
                      f"ctx{rq['kv_gather_ctx']}"
                      f"d{rq['kv_gather_head_dim']}",
             "dtype": "int8", "noise_floor_pct": noise_pct,
             "candidates_ms": {
                 "bf16": rq["kv_quant_gather_bf16_ms"],
                 "int8": rq["kv_quant_gather_int8_ms"]},
             "kv_bytes_per_token_ratio": rq["kv_bytes_per_token_ratio"]}
    if rq["kv_quant_gather_int8_ms"] <= \
            rq["kv_quant_gather_bf16_ms"] * (1.0 + noise_pct / 100.0):
        rec_q["decision"] = {"serving": {"kv_dtype": "int8"}}

    n_req = cfg["serving_share_requests"]
    rec_p = {"space": "serving.prefix_share", "family": "serving",
             "shape": f"n{n_req}p{rp['prefix_prompt_len']}",
             "dtype": "f32", "noise_floor_pct": noise_pct,
             "candidates_ms": {
                 "shared": rp["prefix_admission_ms"]},
             "prefix_prefill_savings": rp["prefix_prefill_savings"],
             "prefix_completed": rp["prefix_completed"]}
    if rp["prefix_prefill_savings"] >= 2.0 \
            and rp["prefix_completed"] == n_req:
        rec_p["decision"] = {"serving": {"prefix_share": True}}
    return [rec_q, rec_p]


_SERVING_COMPUTE_MEMO = {}


def _serving_compute_benches(cfg):
    """Run (once per config) the speculative-decode and batched-
    prefill benches that both the compute sweep and the budget
    restamp consume — each builds and AOT-compiles engines, the most
    expensive fixtures in the sweep."""
    from apex_tpu.serving.bench import bench_batched_prefill, \
        bench_spec_decode
    key = (cfg["serving_layers"], cfg["serving_hidden"],
           cfg["serving_heads"],
           tuple(cfg["serving_spec_candidates"]),
           cfg["serving_prefill_batch"])
    if key not in _SERVING_COMPUTE_MEMO:
        spec_runs = {}
        for k in cfg["serving_spec_candidates"]:
            if k == 0:
                continue    # the K=0 leg rides every spec run
            spec_runs[k] = bench_spec_decode(
                n_requests=cfg["serving_slots"],
                n_layers=cfg["serving_layers"],
                hidden=cfg["serving_hidden"],
                n_heads=cfg["serving_heads"], spec_k=k)
        rb = bench_batched_prefill(
            n_requests=cfg["serving_prefill_batch"],
            n_layers=cfg["serving_layers"],
            hidden=cfg["serving_hidden"],
            n_heads=cfg["serving_heads"],
            prefill_batch=cfg["serving_prefill_batch"])
        _SERVING_COMPUTE_MEMO[key] = (spec_runs, rb)
    return _SERVING_COMPUTE_MEMO[key]


def sweep_serving_compute(cfg, noise_pct: float) -> list:
    """Serving compute frontier: spec_k, weight_dtype and
    prefill_batch.

    spec_k weighs each candidate K's speculative window wall-clock
    against the plain window on the repetitive-suffix fixture — a K
    only becomes the table's decision when it beats K=0 beyond the
    noise floor AND its greedy stream stayed bit-exact (the free
    oracle; a K that ever diverges is a bug, not a slow candidate).
    weight_dtype times the decode window with int8-quantized matmul
    weights against f32 — the HBM halving is structural, so int8 wins
    unless its dequant tax exceeds the noise floor (the kv_dtype
    rule, applied to the weight planes).  prefill_batch is graded
    structurally from program-invocation counters: B requests must
    drain through ONE call with the serial stream reproduced
    bit-exactly."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import serving
    from apex_tpu.benchlib import timeit
    from apex_tpu.serving.bench import _tiny_setup

    spec_runs, rb = _serving_compute_benches(cfg)

    # --- serving.spec_k -------------------------------------------------
    cands_ms = {"k0": None}
    exact = True
    for k, r in sorted(spec_runs.items()):
        cands_ms[f"k{k}"] = r["spec_verify_step_ms"]
        if cands_ms["k0"] is None:
            cands_ms["k0"] = r["spec_plain_window_ms"]
        exact = exact and bool(r["spec_bit_exact"])
    rec_k = {"space": "serving.spec_k", "family": "serving",
             "shape": f"L{cfg['serving_layers']}"
                      f"h{cfg['serving_hidden']}",
             "dtype": "f32", "noise_floor_pct": noise_pct,
             "candidates_ms": cands_ms,
             "spec_accept_rates": {
                 f"k{k}": r["spec_accept_rate"]
                 for k, r in sorted(spec_runs.items())},
             "spec_bit_exact": int(exact)}
    timed = {k: r["spec_verify_step_ms"]
             for k, r in spec_runs.items()}
    if timed and exact:
        best = min(timed, key=timed.get)
        if timed[best] < cands_ms["k0"] * (1.0 - noise_pct / 100.0):
            rec_k["decision"] = {"serving": {"spec_k": best}}

    # --- serving.weight_dtype -------------------------------------------
    cfg2, params, spec2, state = _tiny_setup(
        jax, jnp, cfg["serving_layers"], cfg["serving_hidden"],
        cfg["serving_heads"], cfg["serving_slots"], 8,
        max(1, cfg["serving_ctx"] // 8), 8)
    win = serving.decode_window_fn(cfg2, spec2, 8)
    times = {}
    for wd in ("f32", "int8"):
        wp = serving.quantize_serving_params(params, wd)
        # one program per weight dtype by design
        # apexlint: disable-next=APX302
        times[wd] = timeit(jax.jit(win), wp, state,
                           iters=cfg["iters"], reps=cfg["reps"])
    rec_w = {"space": "serving.weight_dtype", "family": "serving",
             "shape": f"L{cfg['serving_layers']}"
                      f"h{cfg['serving_hidden']}",
             "dtype": "int8", "noise_floor_pct": noise_pct,
             "candidates_ms": {k: round(v, 4)
                               for k, v in times.items()}}
    if times["int8"] <= times["f32"] * (1.0 + noise_pct / 100.0):
        rec_w["decision"] = {"serving": {"weight_dtype": "int8"}}

    # --- serving.prefill_batch ------------------------------------------
    b = cfg["serving_prefill_batch"]
    rec_b = {"space": "serving.prefill_batch", "family": "serving",
             "shape": f"b{b}", "dtype": "f32",
             "noise_floor_pct": noise_pct,
             "candidates_ms": {
                 "batched": rb["batched_prefill_ms"],
                 "serial": rb["serial_prefill_ms"]},
             "batched_prefill_speedup": rb["batched_prefill_speedup"],
             "batched_prefill_bit_exact":
                 rb["batched_prefill_bit_exact"]}
    if rb["batched_prefill_speedup"] >= 1.5 \
            and rb["batched_prefill_bit_exact"]:
        rec_b["decision"] = {"serving": {"prefill_batch": b}}
    return [rec_k, rec_w, rec_b]


def measure_budget_rows(cfg) -> dict:
    """Sweep measurements that ground perf_budget rows (dotted metric
    path -> value).  grad_accum_n8_speedup comes from the same flat-vs-
    per-leaf accumulation legs bench.py reports, at this config's
    scale; the serving rows come from the same end-to-end engine
    bench.py's serving extra runs — autotune --full is the designated
    restamp vehicle for both (they grade no-data until then)."""
    from apex_tpu.optimizers.bucketing_bench import bench_grad_accum
    from apex_tpu.serving.bench import bench_serving
    r = bench_grad_accum(**cfg["accum"])
    out = {}
    if "grad_accum_n8_speedup" in r:
        out["extra.grad_accum_n8_speedup"] = r["grad_accum_n8_speedup"]
    s = bench_serving(
        n_requests=2 * cfg["serving_slots"],
        n_layers=cfg["serving_layers"], hidden=cfg["serving_hidden"],
        n_heads=cfg["serving_heads"], max_slots=cfg["serving_slots"],
        page_size=8, pages_per_slot=max(1, cfg["serving_ctx"] // 8),
        window=8)
    out["extra.decode_tokens_per_sec"] = s["decode_tokens_per_sec"]
    out["extra.serving_p99_ms"] = s["serving_p99_ms"]
    q, p = _serving_memory_benches(cfg)
    out["extra.kv_bytes_per_token"] = q["kv_bytes_per_token_ratio"]
    out["extra.prefix_prefill_savings"] = p["prefix_prefill_savings"]
    spec_runs, rb = _serving_compute_benches(cfg)
    if spec_runs:
        # the largest candidate K: the budget floor grades the
        # drafter's ceiling on the repetitive-suffix fixture
        out["extra.spec_accept_rate"] = \
            spec_runs[max(spec_runs)]["spec_accept_rate"]
    out["extra.batched_prefill_speedup"] = \
        rb["batched_prefill_speedup"]
    return out


# ---------------------------------------------------------------------------
# table assembly + decision-change demonstration
# ---------------------------------------------------------------------------

def build_table(records, topology: dict, backend: str,
                noise_pct: float, mode: str) -> dict:
    """Fold sweep records into one schema-versioned per-topology prefs
    doc (the layout ops/_dispatch.py selects by runtime topology)."""
    prefer, caps, pipeline, speedups = {}, {}, {}, {}
    fp8, quant, srv = {}, {}, {}
    for rec in records:
        if rec.get("space") == "routing" and rec.get("speedup") \
                is not None:
            speedups.setdefault(rec["family"], []).append(
                rec["speedup"])
        dec = rec.get("decision")
        if not dec:
            continue
        prefer.update(dec.get("prefer_pallas", {}))
        caps.update(dec.get("attn_block_cap", {}))
        pipeline.update(dec.get("pipeline", {}))
        fp8.update(dec.get("fp8", {}))
        quant.update(dec.get("quantization", {}))
        srv.update(dec.get("serving", {}))
    return {
        "schema": SCHEMA_VERSION,
        "methodology": "amortized",
        "source": "tools/autotune.py",
        "mode": mode,
        "backend": backend,
        "generated_at": _now(),
        "topology": topology,
        "noise_floor_pct": noise_pct,
        "prefer_pallas": prefer,
        "attn_block_cap": caps,
        "pipeline": pipeline,
        "fp8": fp8,
        "quantization": quant,
        "serving": srv,
        "speedups": {k: sorted(v) for k, v in speedups.items()},
        "sweep": {"records": records},
    }


def demonstrate_decision_changes(doc) -> list:
    """Install the table through the new accessor and report every
    dispatch decision it changes vs the uninstalled (file-backed /
    default) state — the proof the sweep's output actually steers.
    Restores the prior installed state."""
    from apex_tpu.ops import _dispatch

    prev = _dispatch._INSTALLED
    try:
        _dispatch.install_prefs(None)
        # probe a FIXED decision set (union of both tables' keys, so a
        # per-topology table that DROPS a default-table entry — back to
        # the design default — counts as the decision change it is)
        base = _dispatch.dispatch_tables()
        fams = sorted(set(doc.get("prefer_pallas", {}))
                      | set(base.prefer_pallas)
                      | {"multi_tensor", "welford", "attention"})
        dps = sorted(set(doc.get("attn_block_cap", {}))
                     | set(base.attn_block_cap))

        def snapshot():
            out = {}
            for f in fams:
                out[f"op_enabled:{f}"] = _dispatch.op_enabled(f)
            for dp in dps:
                out[f"attn_block_cap:{dp}"] = \
                    _dispatch.attn_block_cap(dp)
            out["pipeline:max_bucket_bytes"] = _dispatch.pipeline_pref(
                "max_bucket_bytes")
            out["pipeline:reduce_decompose"] = _dispatch.pipeline_pref(
                "reduce_decompose", "psum")
            out["fp8:amax_history_len"] = _dispatch.fp8_pref(
                "amax_history_len")
            out["fp8:interval"] = _dispatch.fp8_pref("interval")
            out["quantization:int8_dynamic"] = \
                _dispatch.quantization_pref("int8_dynamic", False)
            out["serving:page_size"] = _dispatch.serving_pref(
                "page_size")
            out["serving:decode_window"] = _dispatch.serving_pref(
                "decode_window")
            out["serving:kv_dtype"] = _dispatch.serving_pref(
                "kv_dtype", "f32")
            out["serving:prefix_share"] = _dispatch.serving_pref(
                "prefix_share", False)
            out["serving:spec_k"] = _dispatch.serving_pref(
                "spec_k", 0)
            out["serving:weight_dtype"] = _dispatch.serving_pref(
                "weight_dtype", "f32")
            out["serving:prefill_batch"] = _dispatch.serving_pref(
                "prefill_batch", 1)
            return out

        before = snapshot()
        _dispatch.install_prefs(doc)
        after = snapshot()
    finally:
        _dispatch._INSTALLED = prev
        _dispatch.invalidate_prefs_cache()
    return [{"decision": k, "before": before[k], "after": after[k]}
            for k in before if before[k] != after[k]]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

_SWEPT_FAMILIES = ("multi_tensor", "welford", "attention",
                   "attention_f32", "layer_norm", "softmax", "xentropy")


def run_sweep(cfg, out_dir: str, budget_path: str,
              install: bool) -> dict:
    """The whole pipeline: sweep -> per-topology table -> decision-
    change demonstration -> budget restamp.  Returns the summary dict
    (also written to <out>/autotune_summary.json)."""
    import jax

    from apex_tpu.ops import _dispatch
    from apex_tpu.platform import enable_compilation_cache
    enable_compilation_cache()
    backend = jax.default_backend()
    topology = _dispatch.topology_block()
    _os.makedirs(out_dir, exist_ok=True)

    # pin every family to its Pallas path WHILE TIMING (kernel_bench
    # discipline: a previously written table must not make the
    # "kernel" leg silently measure the oracle)
    prev_pin = _os.environ.get("APEX_TPU_PREFER_PALLAS")
    _os.environ["APEX_TPU_PREFER_PALLAS"] = ",".join(_SWEPT_FAMILIES)
    try:
        noise_pct = measure_noise_floor(cfg)
        records = []
        records += sweep_routing(cfg, noise_pct, out_dir)
        records += sweep_attn_caps(cfg, noise_pct)
        records += sweep_pipeline_chunk(cfg, noise_pct, out_dir)
        records += sweep_reduce_decompose(cfg, noise_pct)
        records += sweep_fp8_cadence(cfg, noise_pct, out_dir)
        records += sweep_quantization(cfg, noise_pct)
        records += sweep_serving_geometry(cfg, noise_pct)
        records += sweep_serving_memory(cfg, noise_pct)
        records += sweep_serving_compute(cfg, noise_pct)
        budget_rows = measure_budget_rows(cfg)
    finally:
        if prev_pin is None:
            _os.environ.pop("APEX_TPU_PREFER_PALLAS", None)
        else:
            _os.environ["APEX_TPU_PREFER_PALLAS"] = prev_pin

    doc = build_table(records, topology, backend, noise_pct,
                      cfg["mode"])
    # the writer must never emit a table its own validator (and thus
    # check.sh) would reject
    errs = validate_table(doc, per_topology=True)
    if errs:
        raise RuntimeError(f"autotune produced an invalid table: {errs}")

    table_path = _os.path.join(out_dir,
                               f"dispatch_prefs.{topology['key']}.json")
    with open(table_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    # demonstrate BEFORE installing into the live ops directory: the
    # baseline snapshot must see the pre-sweep state, or an installed
    # run would compare the new table against itself (zero changes)
    changes = demonstrate_decision_changes(doc)
    installed_path = None
    if install:
        installed_path = _dispatch.topology_prefs_path(topology["key"])
        with open(installed_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        _dispatch.invalidate_prefs_cache()

    with open(budget_path, encoding="utf-8") as f:
        budget = json.load(f)
    when = _now()
    restamped = restamp_budget(
        budget, budget_rows, topology=topology["key"], backend=backend,
        noise_floor_pct=noise_pct, mode=cfg["mode"], when=when)
    budget_out = (budget_path if install
                  else _os.path.join(out_dir, "perf_budget.json"))
    with open(budget_out, "w") as f:
        json.dump(budget, f, indent=1, sort_keys=True)
        f.write("\n")

    summary = {"mode": cfg["mode"], "backend": backend,
               "topology": topology, "noise_floor_pct": noise_pct,
               "table": table_path, "installed": installed_path,
               "decision_changes": changes,
               "budget": budget_out, "budget_rows_restamped": restamped,
               "budget_measurements": budget_rows,
               "records": len(records)}
    with open(_os.path.join(out_dir, "autotune_summary.json"),
              "w") as f:
        json.dump({**summary, "sweep_records": records}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-topology kernel autotuner "
                    "(see module docstring)")
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--cpu-smoke", action="store_true",
                      help="deterministic tiny sweep; writes table + "
                           "restamped budget copy into --out only")
    mode.add_argument("--full", action="store_true",
                      help="hardware sweep; installs the per-topology "
                           "table and restamps tools/perf_budget.json")
    mode.add_argument("--validate", nargs="*", metavar="FILE",
                      help="schema-check dispatch_prefs*.json "
                           "(default: every shipped table)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="artifact directory (cpu-smoke writes here "
                         "INSTEAD of the repo tables)")
    ap.add_argument("--budget", default=BUDGET_PATH)
    args = ap.parse_args(argv)

    if args.validate is not None:
        errs = validate_paths(args.validate)
        if errs:
            for e in errs:
                print(f"autotune --validate: {e}", file=_sys.stderr)
            return 1
        if args.validate:
            n, suffix = len(args.validate), ""
        else:
            n = len(glob.glob(_os.path.join(
                _ROOT, "apex_tpu", "ops",
                "dispatch_prefs*.json"))) + 1
            suffix = " (incl. the apexcost cost ledger)"
        print(f"autotune --validate: {n} table(s) schema-valid{suffix}")
        return 0

    if args.cpu_smoke:
        # interpret mode (every non-TPU backend): same kernels, no
        # hardware needed
        cfg = smoke_config()
        summary = run_sweep(cfg, args.out, args.budget, install=False)
    else:
        cfg = full_config()
        import jax

        if jax.default_backend() != "tpu":
            print(json.dumps({
                "error": "--full needs TPU hardware (interpret-mode "
                         "timings must never steer real dispatch); "
                         "use --cpu-smoke to exercise the plumbing",
                "backend": jax.default_backend()}))
            return 2
        summary = run_sweep(cfg, args.out, args.budget, install=True)

    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
