"""Kernel dispatch policy: real Mosaic on TPU, interpreter elsewhere.

Plays the role of the reference's dtype-dispatch/build-flag glue
(csrc/type_shim.h, setup.py extension gating): decide at trace time whether
a Pallas kernel compiles for hardware or runs interpreted (CPU CI), and
whether to prefer the plain-XLA path where fusion already wins.

Measured preferences are **per topology** since the autotuner
(tools/autotune.py): the ops directory may hold a default table
(``dispatch_prefs.json``, topology-agnostic) and
``dispatch_prefs.<topology>.json`` files, one per (chip kind, device
count) a sweep has measured.  The repo ships NONE: no table means the
design defaults (prefer Pallas, static block geometry), which is also
what every tier-1 test exercises (tests/conftest.py).  At dispatch time the runtime topology
selects the matching table; a missing or untrusted per-topology table
falls back to the default table with the existing loud-warning
discipline (a discarded table must never be silent — the operator
believes measured routing is active when the design default is).
"""

from __future__ import annotations

import functools
import json
import os
import re
import warnings
from typing import NamedTuple, Optional

import jax

from apex_tpu.telemetry import retrace

# measured dispatch preferences: op family -> False means "the XLA
# oracle path beat the Pallas kernel on hardware, prefer it".
# Generated by tools/kernel_bench.py --write-prefs / tools/autotune.py
# from real-chip timings (VERDICT r2 #2); absent file or absent key =
# prefer Pallas, the design default (SURVEY.md §2.4).
_PREFS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "dispatch_prefs.json")

#: Schema version per-topology tables are written at (tools/autotune.py
#: and kernel_bench --write-prefs both stamp it; the check.sh validator
#: enforces it on shipped per-topology files).
SCHEMA_VERSION = 2

#: Legal ``pipeline`` table values for ``reduce_decompose``.
REDUCE_DECOMPOSE_CHOICES = ("psum", "reduce_scatter")

# Test/override hooks: a dict installed here (tests monkeypatch these;
# conftest's _neutral_dispatch pins all five to {}) IS the active
# table for that component, bypassing the file-backed tables entirely.
# None = consult install_prefs()/the per-topology loader.
_PREFS: Optional[dict] = None
_ATTN_CAPS: Optional[dict] = None
_PIPELINE: Optional[dict] = None
_FP8: Optional[dict] = None
_QUANT: Optional[dict] = None
_SERVING: Optional[dict] = None


class DispatchTables(NamedTuple):
    """One resolved set of measured dispatch tables."""

    prefer_pallas: dict      # op family -> bool
    attn_block_cap: dict     # str(padded head dim) -> cap
    pipeline: dict           # flat-pipeline knobs (validated domain)
    source: Optional[str]    # file path / "<installed>" / None=defaults
    topology: Optional[str]  # topology key the table claims, if any
    fp8: dict = {}           # fp8 scaling-cadence knobs
    quantization: dict = {}  # inference quantization routing
    serving: dict = {}       # decode-engine shape-bucket geometry


EMPTY_TABLES = DispatchTables({}, {}, {}, None, None)

# cached-with-invalidation state for the file-backed loader: tests and
# the autotuner install tables via install_prefs() (no module reload),
# and a re-written prefs file invalidates via its mtime in the key.
_INSTALLED: Optional[DispatchTables] = None
_CACHE: Optional[tuple] = None      # (key, DispatchTables)


# ---------------------------------------------------------------------------
# runtime topology
# ---------------------------------------------------------------------------

def topology_key() -> str:
    """Normalized runtime topology: ``<chip_kind>-<device_count>``
    (e.g. ``tpu_v5e-8``, ``cpu-1``).  The selector per-topology prefs
    tables key on — chip kind and device count are what change kernel
    and collective cost models; finer detail (mesh shape, process
    count) lives in the table's ``topology`` block as provenance."""
    dev = jax.devices()[0]
    kind = re.sub(r"[^a-z0-9]+", "_", dev.device_kind.lower()).strip("_")
    return f"{kind}-{jax.device_count()}"


def topology_block(mesh_shape: Optional[dict] = None) -> dict:
    """Provenance block table writers stamp (tools/kernel_bench.py
    --write-prefs, tools/autotune.py): the runtime topology plus
    everything needed to audit where a measurement came from."""
    dev = jax.devices()[0]
    out = {"key": topology_key(),
           "device_kind": dev.device_kind,
           "device_count": jax.device_count(),
           "process_count": jax.process_count()}
    if mesh_shape:
        out["mesh_shape"] = dict(mesh_shape)
    return out


def topology_prefs_path(key: Optional[str] = None) -> str:
    """Path of the per-topology prefs table for ``key`` (default: the
    runtime topology), next to the default table so a monkeypatched
    ``_PREFS_PATH`` relocates the whole family."""
    base = os.path.dirname(os.path.abspath(_PREFS_PATH))
    return os.path.join(base,
                        f"dispatch_prefs.{key or topology_key()}.json")


# ---------------------------------------------------------------------------
# table parsing (per-entry tolerance, loud discard)
# ---------------------------------------------------------------------------

def _normalize_caps(raw) -> dict:
    """Per-entry tolerance: one bad value ("auto", 100, -128) drops
    that entry only, never the sibling tables."""
    if not isinstance(raw, dict):
        return {}
    out = {}
    for k, v in raw.items():
        try:
            # JSON host data, never a traced value (the table loader
            # runs at trace/dispatch-decision time on the host)
            iv = int(v)   # apexlint: disable=APX101
        except (TypeError, ValueError):
            continue
        if iv > 0 and iv % 128 == 0:
            out[str(k)] = iv
    return out


def _normalize_pipeline(raw) -> dict:
    """Flat-pipeline knob domain: ``max_bucket_bytes`` a positive int
    (or None = monolithic buckets), ``reduce_decompose`` one of
    REDUCE_DECOMPOSE_CHOICES.  A malformed value drops that entry only."""
    if not isinstance(raw, dict):
        return {}
    out = {}
    if "max_bucket_bytes" in raw:
        v = raw["max_bucket_bytes"]
        if v is None:
            out["max_bucket_bytes"] = None
        else:
            try:
                # JSON host data, never traced
                iv = int(v)   # apexlint: disable=APX101
            except (TypeError, ValueError):
                iv = 0
            if iv > 0 and not isinstance(v, bool):
                out["max_bucket_bytes"] = iv
    if raw.get("reduce_decompose") in REDUCE_DECOMPOSE_CHOICES:
        out["reduce_decompose"] = raw["reduce_decompose"]
    return out


def _normalize_fp8(raw) -> dict:
    """fp8 scaling-cadence domain: ``amax_history_len`` / ``interval``
    positive ints.  A malformed value drops that entry only."""
    if not isinstance(raw, dict):
        return {}
    out = {}
    for k in ("amax_history_len", "interval"):
        if k in raw:
            try:
                # JSON host data, never traced
                iv = int(raw[k])   # apexlint: disable=APX101
            except (TypeError, ValueError):
                continue
            if iv > 0 and not isinstance(raw[k], bool):
                out[k] = iv
    return out


KV_DTYPE_CHOICES = ("f32", "bf16", "int8")


def _normalize_serving(raw) -> dict:
    """Serving decode-geometry domain: ``page_size`` /
    ``decode_window`` positive ints, ``kv_dtype`` one of
    KV_DTYPE_CHOICES (the arena storage precision), ``prefix_share``
    a JSON boolean (compile the extend/COW programs and alias shared
    prompt prefixes).  The autotuner's serving sweeps write these;
    ``serving.Engine`` consumes them when built with the defaults.
    A malformed (foreign/stale) value drops that entry only."""
    if not isinstance(raw, dict):
        return {}
    out = {}
    for k in ("page_size", "decode_window"):
        if k in raw:
            try:
                # JSON host data, never traced
                iv = int(raw[k])   # apexlint: disable=APX101
            except (TypeError, ValueError):
                continue
            if iv > 0 and not isinstance(raw[k], bool):
                out[k] = iv
    if raw.get("kv_dtype") in KV_DTYPE_CHOICES:
        out["kv_dtype"] = raw["kv_dtype"]
    if isinstance(raw.get("prefix_share"), bool):
        out["prefix_share"] = raw["prefix_share"]
    return out


def _normalize_quantization(raw) -> dict:
    """Inference-quantization routing domain: ``int8_dynamic`` a JSON
    boolean (True = per-row dynamic activation quant wins on this
    topology, False = weight-only)."""
    if not isinstance(raw, dict):
        return {}
    out = {}
    if isinstance(raw.get("int8_dynamic"), bool):
        out["int8_dynamic"] = raw["int8_dynamic"]
    return out


def _normalize_doc(raw: dict, source: Optional[str]) -> DispatchTables:
    prefs = raw.get("prefer_pallas", {})
    if not isinstance(prefs, dict):
        prefs = {}
    topo = raw.get("topology")
    return DispatchTables(
        # JSON host data, never traced
        prefer_pallas={str(k): bool(v)   # apexlint: disable=APX101
                       for k, v in prefs.items()},
        attn_block_cap=_normalize_caps(raw.get("attn_block_cap", {})),
        pipeline=_normalize_pipeline(raw.get("pipeline", {})),
        source=source,
        topology=(topo.get("key") if isinstance(topo, dict) else None),
        fp8=_normalize_fp8(raw.get("fp8", {})),
        quantization=_normalize_quantization(
            raw.get("quantization", {})),
        serving=_normalize_serving(raw.get("serving", {})))


def _trusted(raw: dict, where: str, runtime_topo: Optional[str]) -> bool:
    """The two discard rules, both loud (RuntimeWarning, once per
    message): tables without the amortized-methodology stamp timed one
    dispatch per iteration, so every sub-ms body measured the dispatch
    overhead and not the kernel (apex_tpu/benchlib.py), and a
    table measured on a DIFFERENT topology must not steer this one
    (block geometry and collective decomposition winners do not
    transfer between chip generations or fleet sizes)."""
    if raw.get("methodology") != "amortized":
        warnings.warn(
            f"apex_tpu: dispatch preferences at {where} "
            f"lack methodology='amortized' (found "
            f"{raw.get('methodology')!r}) and are IGNORED — "
            "re-run tools/kernel_bench.py --write-prefs or "
            "tools/autotune.py on hardware to regenerate them",
            RuntimeWarning, stacklevel=3)
        return False
    topo = raw.get("topology")
    claimed = topo.get("key") if isinstance(topo, dict) else None
    if claimed is not None and runtime_topo is not None \
            and claimed != runtime_topo:
        warnings.warn(
            f"apex_tpu: dispatch preferences at {where} were measured "
            f"for topology {claimed!r} but the runtime topology is "
            f"{runtime_topo!r}; the table is IGNORED — re-run "
            "tools/autotune.py on this topology to regenerate it",
            RuntimeWarning, stacklevel=3)
        return False
    return True


def _read_doc(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            raw = json.load(f)
        return raw if isinstance(raw, dict) else None
    except Exception:
        return None


def _load_table(path: str,
                runtime_topo: Optional[str]) -> Optional[DispatchTables]:
    """Parse one prefs file into DispatchTables, or None when the file
    is missing/corrupt or fails the trust rules (which warn).  Never
    raises: a malformed prefs file must not break ``import apex_tpu``
    or any dispatch decision — the documented default (prefer Pallas,
    static geometry) always remains reachable."""
    raw = _read_doc(path)
    if raw is None:
        return None
    if not _trusted(raw, path, runtime_topo):
        return None
    return _normalize_doc(raw, source=path)


def _load_prefs() -> tuple:
    """Back-compat helper: the DEFAULT table's (prefer_pallas,
    attn_block_cap) pair, with the same tolerance and loud-discard
    contract as always (tests drive this directly with a monkeypatched
    ``_PREFS_PATH``).  Topology is NOT checked here — the default
    table is the topology-agnostic fallback; per-topology enforcement
    lives in :func:`dispatch_tables`."""
    t = _load_table(_PREFS_PATH, runtime_topo=None)
    if t is None:
        return {}, {}
    return t.prefer_pallas, t.attn_block_cap


# ---------------------------------------------------------------------------
# cached accessor + installation
# ---------------------------------------------------------------------------

def _mtime(path: str) -> Optional[float]:
    try:
        return os.stat(path).st_mtime
    except OSError:
        return None


def dispatch_tables() -> DispatchTables:
    """THE resolved-tables accessor: an installed table
    (:func:`install_prefs`) wins; else the per-topology file for the
    runtime topology; else the default ``dispatch_prefs.json``; else
    empty (= design defaults).  File results are cached keyed on
    (paths, topology, file mtimes), so a table the autotuner just
    wrote is picked up without a module reload and repeated trace-time
    calls cost two ``os.stat``."""
    global _CACHE
    if _INSTALLED is not None:
        return _INSTALLED
    topo = topology_key()
    topo_path = topology_prefs_path(topo)
    key = (_PREFS_PATH, topo, _mtime(_PREFS_PATH), _mtime(topo_path))
    if _CACHE is not None and _CACHE[0] == key:
        return _CACHE[1]
    tables = _load_table(topo_path, runtime_topo=topo)
    if tables is None:
        # fallback: the shipped/default table.  A default table that
        # carries an explicit topology block (kernel_bench --write-prefs
        # stamps one now) is still topology-checked: measurements from
        # another fleet must not silently steer this one.
        tables = _load_table(_PREFS_PATH, runtime_topo=topo)
    if tables is None:
        tables = EMPTY_TABLES
    _CACHE = (key, tables)
    return tables


def invalidate_prefs_cache() -> None:
    """Drop the cached file-backed tables; the next dispatch decision
    re-reads disk.  (A re-written file invalidates automatically via
    its mtime; this is the explicit hammer for tests and tools.)"""
    global _CACHE
    _CACHE = None


def install_prefs(doc: Optional[dict] = None, *,
                  check_topology: bool = True) -> Optional[DispatchTables]:
    """Install a parsed prefs doc as the active tables WITHOUT a module
    reload — the accessor tests and tools/autotune.py use to make a
    fresh sweep's table steer dispatch immediately.

    Unlike the file loader (which degrades to defaults, warning), an
    explicit install is strict: a doc without the amortized stamp, or
    measured for a different topology (unless ``check_topology=False``),
    raises ValueError — installing a table you believe steers and
    having it silently ignored is exactly the failure mode PR 1's
    warning contract exists to kill.

    ``install_prefs(None)`` uninstalls and re-enables the file-backed
    per-topology lookup."""
    global _INSTALLED
    if doc is None:
        _INSTALLED = None
        invalidate_prefs_cache()
        return None
    if not isinstance(doc, dict):
        raise ValueError(f"install_prefs needs a parsed dict, got "
                         f"{type(doc).__name__}")
    runtime = topology_key() if check_topology else None
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        try:
            ok = _trusted(doc, "<installed>", runtime)
        except RuntimeWarning as w:
            raise ValueError(str(w)) from None
    if not ok:   # pragma: no cover - _trusted warns (raised above)
        raise ValueError("untrusted prefs doc")
    _INSTALLED = _normalize_doc(doc, source="<installed>")
    return _INSTALLED


# ---------------------------------------------------------------------------
# dispatch decisions
# ---------------------------------------------------------------------------

def attn_block_cap(dp: int):
    """Measured-best flash-attention sequence-block cap for padded head
    dim ``dp``, or None when no sweep has recorded one for this
    topology (the kernel then uses its static VMEM-safe default)."""
    caps = _ATTN_CAPS if _ATTN_CAPS is not None \
        else dispatch_tables().attn_block_cap
    return caps.get(str(dp))


def pipeline_pref(name: str, default=None):
    """Measured flat-pipeline knob (``max_bucket_bytes``,
    ``reduce_decompose``) for this topology, or ``default`` when the
    sweep never recorded one.  Consumed by
    ``amp.FlatGradPipeline(...="auto")`` and
    ``parallel.DistributedDataParallel(reduce_decompose="auto")``."""
    table = _PIPELINE if _PIPELINE is not None \
        else dispatch_tables().pipeline
    return table.get(name, default)


def fp8_pref(name: str, default=None):
    """Measured fp8 scaling-cadence knob (``amax_history_len``,
    ``interval``) for this topology, or ``default``.  Consumed by
    ``amp.fp8.tuned_policy()`` — the autotuner's fp8 cadence sweep
    (tools/autotune.py) writes these."""
    table = _FP8 if _FP8 is not None else dispatch_tables().fp8
    return table.get(name, default)


def quantization_pref(name: str, default=None):
    """Measured inference-quantization routing (``int8_dynamic``) for
    this topology, or ``default``.  Consumed by
    ``quantization.int8_matmul(dynamic=None)`` /
    ``QuantDense(dynamic=None)`` so the autotuner can steer the
    dynamic-vs-weight-only choice per topology instead of callers
    hardcoding it."""
    table = _QUANT if _QUANT is not None \
        else dispatch_tables().quantization
    return table.get(name, default)


def serving_pref(name: str, default=None):
    """Measured serving preferences (``page_size``,
    ``decode_window``, ``kv_dtype``, ``prefix_share``, ``spec_k``,
    ``weight_dtype``, ``prefill_batch``) for this topology, or
    ``default``.  Consumed by ``serving.Engine`` when built without
    explicit geometry — the autotuner's serving sweeps
    (tools/autotune.py) write these."""
    table = _SERVING if _SERVING is not None \
        else dispatch_tables().serving
    return table.get(name, default)


def _env_ops(var: str):
    return {s.strip() for s in os.environ.get(var, "").split(",")
            if s.strip()}


def op_enabled(op: str, _prefs=None) -> bool:
    """Dispatch gate for one kernel family: Pallas unless (a) globally
    disabled, (b) the measured preference table (per-topology, see
    :func:`dispatch_tables`) says XLA won for this op, or (c)
    overridden by APEX_TPU_PREFER_XLA / APEX_TPU_PREFER_PALLAS
    (comma-separated op names; PALLAS wins ties, for re-measuring).

    ``_prefs`` substitutes the measured table (prefs_disabled passes {}
    to ask "would this be enabled if the table were ignored?")."""
    if not pallas_enabled():
        return False
    if op in _env_ops("APEX_TPU_PREFER_PALLAS"):
        return True
    if op in _env_ops("APEX_TPU_PREFER_XLA"):
        return False
    if _prefs is None:
        _prefs = _PREFS if _PREFS is not None \
            else dispatch_tables().prefer_pallas
    return _prefs.get(op, True)


def prefs_disabled(op: str) -> bool:
    """True when the MEASURED preference table is the only reason
    ``op_enabled(op)`` is False — i.e. no operator override is in play
    (not the APEX_TPU_DISABLE_PALLAS escape hatch, not an explicit
    APEX_TPU_PREFER_XLA).  Shape-aware gates (e.g. the attention dense
    fallback's memory budget) may overrule the table for shapes the
    bench never measured, but must never overrule the operator.

    Derived from op_enabled itself (re-asked with the table ignored) so
    a future override added there cannot silently diverge from this
    classification."""
    return not op_enabled(op) and op_enabled(op, _prefs={})


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """True when pallas_call must run interpreted: a CPU-test facility
    only.  On a TPU backend nothing selects it — the kernels compile
    through Mosaic or the program fails."""
    if on_tpu():
        return False
    # AOT TPU lowering/compiling on a CPU host (tests/test_tpu_lowering.py,
    # tests/test_tpu_compile.py): Mosaic serialization and its verifier
    # run at lowering time, no device needed — checked per call so tests
    # can flip it with monkeypatch
    return os.environ.get("APEX_TPU_FORCE_MOSAIC", "") != "1"


def lowered_once(scope: str, static_argnums=()):
    """Decorator for a module-level function that calls Pallas kernels,
    ``fn(*args, interpret)``: every call with the same shapes and the
    same ``static_argnums`` arguments shares ONE traced jaxpr, which
    jax lowers once a program (a private function called from each
    site) where a ``pallas_call`` traced at each site lowers its kernel
    to Mosaic at each (unrolled layers: one lowering a layer and pass).

    The body runs under ``jax.named_scope(scope)``: a shared function's
    operations carry no name of its callers' scopes.  ``interpret``
    (``interpret_mode()`` at the call) is a static argument, so a
    trace made for the interpreter is never handed to a TPU lowering.
    Each trace is counted in the process's set-up account
    (``retrace.kernel_trace``, its summary's ``kernel_traces``).
    Call sites pass arrays and hashable statics: a fresh
    ``functools.partial`` or closure there would miss the cache."""
    def wrap(fn):
        def traced(interpret, *args):
            retrace.kernel_trace()
            with jax.named_scope(scope):
                return fn(*args, interpret=interpret)

        jitted = jax.jit(traced, static_argnums=(0,) + tuple(
            n + 1 for n in static_argnums))

        @functools.wraps(fn)
        def call(*args):
            return jitted(interpret_mode(), *args)

        return call

    return wrap


def pallas_enabled() -> bool:
    """Global escape hatch: fall back to pure-XLA reference paths.

    Read per call, like the other dispatch knobs: freezing the env at
    import (the pre-apexlint behavior, APX601) silently ignored any
    APEX_TPU_DISABLE_PALLAS set after `import apex_tpu` — launchers and
    monkeypatching tests both hit that."""
    return os.environ.get("APEX_TPU_DISABLE_PALLAS", "") != "1"
