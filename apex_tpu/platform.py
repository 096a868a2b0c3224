"""Process-level jax configuration the entry points share: backend
selection for ``--cpu`` flags, the latency-hiding-scheduler flag sets,
and the one persistent-compile-cache setter.

``JAX_PLATFORMS=cpu python script.py`` is the standard way to run
anything here on the CPU (the tests do; add
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for a virtual
mesh).  ``select_platform`` is the same thing from INSIDE a process
that has already imported jax — an example's ``--cpu`` flag — where
setting the environment variable would be too late.

(Reference context: the reference picks devices with CUDA_VISIBLE_DEVICES
+ ``torch.cuda.set_device``; device selection there is an env concern
too, see examples/imagenet/main_amp.py in SURVEY.md §1 L6.)
"""

from __future__ import annotations

import os
from typing import Optional


def select_platform(platform: str) -> None:
    """Pin the jax backend platform ("cpu", "tpu") for this process.
    Call before any jax backend use."""
    import jax

    jax.config.update("jax_platforms", platform)


# Async-collective / latency-hiding-scheduler flags: the lowering-side
# half of the interleaved grad-reduce schedule (amp/flat_pipeline.py's
# chunked buckets + reduce-in-backward seam give XLA per-bucket
# collectives with bucket-local dependency cones; these flags tell the
# TPU compiler to actually SCHEDULE them under the remaining backward
# compute).  DebugOptions-level flags ride XLA_FLAGS; libtpu-scoped
# ones ride LIBTPU_INIT_ARGS (unknown XLA_FLAGS entries are fatal at
# backend init, so the split matters).
_LHS_XLA_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
)
_LHS_LIBTPU_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
)

_LHS_PROVENANCE: Optional[dict] = None


def latency_hiding_provenance() -> Optional[dict]:
    """The record of what :func:`enable_latency_hiding_scheduler` did
    this process (None if never called) — bench artifacts embed it so
    a measured overlap fraction names the schedule it ran under."""
    return _LHS_PROVENANCE


def enable_latency_hiding_scheduler(force: bool = False,
                                    target: Optional[str] = None) -> dict:
    """Arm XLA's latency-hiding scheduler + async collectives (TPU).

    Appends the flag sets above to ``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS``
    — idempotent (already-present flags are recorded as skipped, never
    duplicated) and effective only if called BEFORE the first jax
    backend use; a late call is recorded as ``applied=False`` with a
    RuntimeWarning, never a silent half-configuration.  The flags are
    applied only when the resolved target IS tpu — ``target="tpu"``
    explicitly (what bench.py passes), or the JAX_PLATFORMS env
    saying so; anything else
    (cpu, or no platform selection at all) withholds them
    (``force=True`` overrides): a non-TPU backend may reject unknown
    ``XLA_FLAGS`` entries at init, and a CPU timing run under TPU
    scheduler flags would carry false provenance.

    Returns (and stashes, see :func:`latency_hiding_provenance`) a
    provenance dict: target backend, flags added, flags skipped,
    whether the environment mutation can still take effect.
    """
    import warnings

    global _LHS_PROVENANCE

    if target is None:
        target = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    from jax._src import xla_bridge
    backend_up = xla_bridge.backends_are_initialized()
    prov = {"target": target or "default", "applied": False,
            "xla_flags_added": [], "libtpu_flags_added": [],
            "skipped": [], "reason": None}
    if target != "tpu" and not force:
        prov["reason"] = (f"target {target or 'default'!r} is not tpu:"
                          " TPU scheduler flags withheld (pass "
                          "target='tpu' or force=True)")
        _LHS_PROVENANCE = prov
        return prov
    if backend_up:
        prov["reason"] = ("jax backend already initialized — flags "
                          "appended to the env take effect only in a "
                          "NEW process")
        warnings.warn(
            "apex_tpu.platform.enable_latency_hiding_scheduler called "
            "after jax backend init: the schedule flags cannot apply "
            "to this process", RuntimeWarning, stacklevel=2)
    for env_var, flags, key in (
            ("XLA_FLAGS", _LHS_XLA_FLAGS, "xla_flags_added"),
            ("LIBTPU_INIT_ARGS", _LHS_LIBTPU_FLAGS,
             "libtpu_flags_added")):
        current = os.environ.get(env_var, "")
        # whole-token presence, never substring: `..._fusion` must not
        # read as present because `..._fusion_fuse_all_gather` is
        present = {t.split("=", 1)[0] for t in current.split()}
        added = []
        for f in flags:
            if f.split("=", 1)[0] in present:
                prov["skipped"].append(f)
            else:
                added.append(f)
        if added:
            os.environ[env_var] = (current + " " + " ".join(added)).strip()
        prov[key] = added
    prov["applied"] = not backend_up
    _LHS_PROVENANCE = prov
    return prov


def enable_compilation_cache(min_compile_secs: float = 1.0) -> None:
    """Turn on jax's persistent executable cache so repeat runs skip
    the slow first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set
    jax reads it itself and nothing is set in code; otherwise the cache
    is ``<checkout>/.jax_cache`` — a FIXED path, because the path is
    part of the cache key's neighbourhood: a directory that moves
    (tempfile, pid, time) never hits.  The one setter of
    ``jax_compilation_cache_dir`` in the repo (tests/conftest.py and
    every tool go through here)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
