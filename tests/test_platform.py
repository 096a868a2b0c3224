"""apex_tpu.platform: the backend-pin and compile-cache knobs every
entry point (chip_smoke.py, bench.py, tools/*, the examples' --cpu)
depends on."""

import os
import subprocess
import sys

import jax
import pytest

from apex_tpu import platform as plat


def _restore(key, value):
    jax.config.update(key, value)


def test_select_platform_pins_the_live_config():
    """--cpu inside a process that already imported jax: the live
    config flips (the environment variable would be too late)."""
    orig = jax.config.jax_platforms
    try:
        plat.select_platform("cpu")
        assert jax.config.jax_platforms == "cpu"
    finally:
        _restore("jax_platforms", orig)


def test_enable_compilation_cache_fixed_in_checkout_path(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the cache is the FIXED
    <checkout>/.jax_cache (a path from tempfile/pid/time never hits)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    orig_dir = jax.config.jax_compilation_cache_dir
    orig_min = jax.config.jax_persistent_cache_min_compile_time_secs
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        plat.enable_compilation_cache(min_compile_secs=2.5)
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            root, ".jax_cache")
        assert (jax.config.jax_persistent_cache_min_compile_time_secs
                == 2.5)
    finally:
        _restore("jax_compilation_cache_dir", orig_dir)
        _restore("jax_persistent_cache_min_compile_time_secs", orig_min)


def test_enable_compilation_cache_leaves_env_placed_cache_alone(
        monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set jax reads it itself: the
    helper sets NO cache directory in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    orig_dir = jax.config.jax_compilation_cache_dir
    orig_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        _restore("jax_compilation_cache_dir", "sentinel-untouched")
        plat.enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"
    finally:
        _restore("jax_compilation_cache_dir", orig_dir)
        _restore("jax_persistent_cache_min_compile_time_secs", orig_min)


def test_one_cache_dir_setter_in_the_repo():
    """Every entry point goes through the helper: exactly one
    ``jax_compilation_cache_dir`` update in package, tools and tests'
    conftest together."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    needle = 'config.update("jax_compilation' + '_cache_dir"'
    hits = []
    for sub in ("apex_tpu", "tools", "examples", "tests"):
        for dirpath, _, files in os.walk(os.path.join(root, sub)):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(dirpath, f)
                    with open(path, encoding="utf-8") as fh:
                        if needle in fh.read():
                            hits.append(os.path.relpath(path, root))
    for f in ("bench.py", "chip_smoke.py", "__graft_entry__.py"):
        with open(os.path.join(root, f), encoding="utf-8") as fh:
            if needle in fh.read():
                hits.append(f)
    assert hits == ["apex_tpu/platform.py"], hits


@pytest.mark.parametrize("argv", [[], ["--multichip"]],
                         ids=["one_chip", "multichip"])
def test_chip_smoke_fails_without_a_tpu(argv):
    """The CPU rehearsal of chip_smoke.py's contract: on a backend that
    is not a TPU it exits non-zero and never prints the result line —
    no CPU stand-in, no smaller model, no recorded result."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")] + argv,
        capture_output=True, text=True, env=env, cwd=root, timeout=120)
    assert p.returncode != 0, p.stdout
    assert '"ok": true' not in p.stdout
    assert '"platform": "cpu"' in p.stdout      # it said what it found
    assert "not 'tpu'" in p.stderr
