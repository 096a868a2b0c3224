"""The bucketed optimizer holds one copy of everything (ISSUE 33).

Contracts:
  * the step program, as ``step()`` launches it, donates every packed
    parameter, master and state buffer and the lowered module aliases
    each to an output — plain and replicated over a mesh;
  * the optimizer owns what it donates: whatever a caller hands in
    (constructor, setters, ``load_state_dict``, ``load_packed_snapshot``)
    is copied in, whatever it hands out stays readable across steps —
    a one-leaf bucket of a flat leaf, which packs to the leaf itself,
    among the cases; with masters, that bucket's model-dtype buffer is
    the leaf ``params`` hands out, and the step does not donate it;
  * a snapshot taken before a step resumes to the same bits;
  * a skipped step writes the old bits back through the aliased buffers;
  * construction builds the state packed and keeps no masters' tree.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.lint.semantic import jaxprs
from apex_tpu.optimizers import (FusedAdagrad, FusedAdam, FusedLAMB,
                                 FusedNovoGrad, FusedSGD)

OPTS = {
    "adam": (FusedAdam, dict(lr=1e-2, weight_decay=0.01)),
    "lamb": (FusedLAMB, dict(lr=1e-2, weight_decay=0.01)),
    "sgd": (FusedSGD, dict(lr=0.1, momentum=0.9)),
    "novograd": (FusedNovoGrad, dict(lr=1e-2, weight_decay=0.01)),
    "adagrad": (FusedAdagrad, dict(lr=1e-2)),
}
DTYPES = {"bf16_masters": jnp.bfloat16, "f32": jnp.float32}
# 1200 bytes a bucket: "flat" (300 float32) gets a bucket to itself, so
# its pack is ``jnp.ravel(leaf)`` — the leaf's own array
CAP = 1200


def _params(dtype):
    ks = jax.random.split(jax.random.key(0), 3)

    def make(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)
    return {"flat": make(ks[0], (300,)), "w": make(ks[1], (12, 5)),
            "b": jnp.ones((5,), dtype), "v": make(ks[2], (7, 3))}


def _grads(params, seed):
    return jax.tree_util.tree_map(
        lambda p: (jax.random.normal(jax.random.key(seed), p.shape,
                                     jnp.float32) * 0.1).astype(p.dtype),
        params)


def _build(name, dtype, **kw):
    cls, hypers = OPTS[name]
    params = _params(dtype)
    masters = None
    if dtype == jnp.bfloat16:
        masters = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32) * 1.0009765625, params)
    opt = cls(params, masters=masters, max_bucket_bytes=CAP,
              **hypers, **kw)
    assert opt.fuse_buckets and len(opt._plan.buckets) >= 2
    assert any(len(b.leaves) == 1 and b.leaves[0].shape == (300,)
               for b in opt._plan.buckets)
    return opt, params, masters


def _packed(opt):
    return jax.tree_util.tree_leaves(
        (opt._param_bufs, opt._master_bufs, opt.opt_state))


def _donated(opt):
    """The packed buffers the step donates: all of them, but for
    FusedLAMB, which keeps its work buffers out (fused_lamb.py), and
    with masters the model-dtype buffer of the one-leaf bucket, which
    is the leaf ``params`` hands out (``_shared``)."""
    shared = opt._shared
    assert shared == (() if opt._master_bufs is None else tuple(
        bi for bi, b in enumerate(opt._plan.buckets)
        if len(b.leaves) == 1))
    params = [b for bi, b in enumerate(opt._param_bufs)
              if bi not in shared]
    held = (params, opt._master_bufs, opt.opt_state)
    donated = opt._donation["donate_argnums"]
    work = 0 if opt._master_bufs is None else 1
    assert donated == tuple(
        i for i in (0, 1, 2)
        if not isinstance(opt, FusedLAMB) or i != work)
    return jax.tree_util.tree_leaves([held[i] for i in donated])


def _bits(tree):
    return [np.atleast_1d(np.asarray(x)).view(np.uint8).copy()
            for x in jax.tree_util.tree_leaves(tree)]


def _same_bits(got, want):
    got, want = _bits(got), _bits(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def _account_tool():
    """``tools/hbm_account.py``, whose ``hlo_summary`` reads a compiled
    module's aliased arguments."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hbm_account.py")
    spec = importlib.util.spec_from_file_location("hbm_account", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def _readable(tree):
    """Every array of ``tree`` is alive: a donated buffer raises on
    any read."""
    arrays = [x for x in jax.tree_util.tree_leaves(tree)
              if isinstance(x, jax.Array)]
    assert arrays
    for leaf in arrays:
        assert not leaf.is_deleted()
        np.asarray(leaf)


# ---- the step program aliases what the optimizer holds ---------------------

@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("name", sorted(OPTS))
def test_step_program_aliases_every_packed_buffer(name, dtype):
    opt, params, _ = _build(name, DTYPES[dtype])
    args = opt._step_args(_grads(params, 1), 1.0, jnp.int32(0))
    lowered = opt._jit_step.lower(*args).as_text()
    old, gone = _packed(opt), _donated(opt)
    assert jaxprs.donated_alias_count(lowered) == len(gone)
    # and the program that ran is that one: the donated buffers are
    # gone, the new ones have their shapes and dtypes
    opt.step(_grads(params, 1), found_inf=jnp.int32(0))
    assert all(b.is_deleted() for b in gone)
    assert [(b.shape, b.dtype) for b in _packed(opt)] \
        == [(b.shape, b.dtype) for b in old]


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("name", ["adam", "lamb", "sgd"])
def test_replicated_step_aliases_every_packed_buffer(name, dtype):
    opt, params, _ = _build(name, DTYPES[dtype])
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    replicated = NamedSharding(mesh, P())
    grads = jax.device_put(_grads(params, 2), replicated)
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                       sharding=replicated),
        opt._step_args(grads, 1.0, jnp.int32(0)))
    lowered = opt._replicated_step(mesh).lower(*args)
    n = len(_donated(opt))
    assert jaxprs.donated_alias_count(lowered.as_text()) == n
    # over a mesh the pairing is XLA's: read it off the compiled module
    assert _account_tool().hlo_summary(
        lowered.compile().as_text(), 386)["aliased_arguments"] == n
    before = _bits(opt.params)
    out = opt.step(grads, found_inf=jnp.int32(0))
    opt.step(grads, found_inf=jnp.int32(0))
    _readable((out, params))
    assert any((a != b).any() for a, b in zip(_bits(out), before))


# ---- the optimizer owns what it donates ------------------------------------

def _hand_in(how, opt, params, masters):
    """Hand the optimizer arrays through ``how``; -> what the caller
    still holds and must be able to read after any number of steps."""
    if how == "constructor":
        return params, masters
    if how == "params_setter":
        mine = jax.tree_util.tree_map(lambda x: x * 0.5, params)
        opt.params = mine
        return mine
    if how == "masters_setter":
        mine = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32) * 0.25, params)
        if opt._master_bufs is None:
            pytest.skip("no masters over float32 parameters")
        opt.masters = mine
        return mine
    if how == "load_state_dict":
        sd = opt.state_dict()
        opt.load_state_dict(sd)
        return sd["state"], sd["masters"]
    if how == "load_packed_snapshot":
        snap = opt.packed_snapshot()
        opt.step(_grads(params, 9))
        opt.load_packed_snapshot(snap["step"], snap["hypers"],
                                 snap["param_bufs"], snap["master_bufs"],
                                 snap["state"])
        return snap["param_bufs"], snap["master_bufs"], snap["state"]
    if how == "rechunk":
        held = opt.params, opt.masters
        assert opt.rechunk(None)
        return held
    raise AssertionError(how)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("how", ["constructor", "params_setter",
                                 "masters_setter", "load_state_dict",
                                 "load_packed_snapshot", "rechunk"])
def test_what_a_caller_hands_in_stays_readable(how, dtype):
    opt, params, masters = _build("adam", DTYPES[dtype])
    opt.step(_grads(params, 3))             # non-trivial state
    held = _hand_in(how, opt, params, masters)
    want = _bits(held)
    for i in range(3):
        opt.step(_grads(params, 4 + i), found_inf=jnp.int32(i == 1))
    _readable(held)
    _same_bits(held, want)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("name", sorted(OPTS))
def test_what_the_optimizer_hands_out_stays_readable(name, dtype):
    opt, params, _ = _build(name, DTYPES[dtype])
    out = [opt.step(_grads(params, 5))]
    out += [opt.params, opt.masters, opt.state_dict(),
            opt.packed_snapshot()["param_bufs"]]
    want = _bits(out)
    for i in range(3):
        out.append(opt.step(_grads(params, 6 + i)))
    _readable(out)
    _same_bits(out[:5], want)
    # no view handed out is a buffer the step donates; with masters
    # the one-leaf bucket's leaf IS its model-dtype buffer (one copy)
    shown = {id(x) for x in jax.tree_util.tree_leaves(
        (opt.params, opt.masters))}
    assert not {id(b) for b in _donated(opt)} & shown
    assert {id(opt._param_bufs[bi]) for bi in opt._shared} <= shown


# ---- snapshot, step, resume ------------------------------------------------

@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("via", ["state_dict", "packed_snapshot"])
@pytest.mark.parametrize("name", ["adam", "lamb", "novograd"])
def test_resume_across_a_step_reaches_the_same_bits(name, via, dtype):
    opt, params, _ = _build(name, DTYPES[dtype])
    opt.step(_grads(params, 10))
    if via == "state_dict":
        saved = (opt.state_dict(), opt.params)
    else:
        saved = opt.packed_snapshot()
    for i in range(2):                      # donates what was live then
        opt.step(_grads(params, 11 + i))
    resumed, _, _ = _build(name, DTYPES[dtype])
    if via == "state_dict":
        resumed.load_state_dict(saved[0])
        resumed.params = saved[1]
    else:
        resumed.load_packed_snapshot(
            saved["step"], saved["hypers"], saved["param_bufs"],
            saved["master_bufs"], saved["state"])
    for i in range(2):
        resumed.step(_grads(params, 11 + i))
    assert int(resumed.step_count) == int(opt.step_count) == 3
    _same_bits(_packed(resumed), _packed(opt))
    _readable(saved)


# ---- the skip, through aliased buffers -------------------------------------

@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("name", sorted(OPTS))
def test_skipped_step_leaves_bits_unchanged_in_place(name, dtype):
    opt, params, _ = _build(name, DTYPES[dtype])
    good = _grads(params, 20)
    opt.step(good, found_inf=jnp.int32(0))
    held = _bits(_packed(opt))
    shown = _bits(opt.params)
    bad = dict(good, flat=good["flat"].at[7].set(jnp.inf),
               w=good["w"].at[0, 0].set(jnp.nan))
    out = opt.step(bad, found_inf=jnp.int32(1))
    assert int(opt.step_count) == 1
    _same_bits(_packed(opt), held)
    _same_bits(out, shown)


# ---- construction holds one copy -------------------------------------------

@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("name", sorted(OPTS))
def test_construction_builds_the_state_packed(name, dtype, monkeypatch):
    """``init_state`` runs only under the trace of the state program,
    whose outputs are the packed buffers: no per-leaf state array is
    ever made.  The masters' tree is not kept; the property unpacks."""
    cls = OPTS[name][0]
    seen = []
    plain = cls.init_state

    def spy(self, work):
        state = plain(self, work)
        seen.extend(jax.tree_util.tree_leaves(state))
        return state
    monkeypatch.setattr(cls, "init_state", spy)
    opt, params, masters = _build(name, DTYPES[dtype])
    assert seen and all(isinstance(x, jax.core.Tracer) for x in seen)
    for field in opt.opt_state.values():
        assert len(field) == len(opt._plan.buckets)
        for buf, b in zip(field, opt._plan.buckets):
            assert buf.shape in ((b.size,), (len(b.leaves),))
            assert not np.asarray(buf).any()
    assert opt._masters_cache is None
    assert opt._params_cache is params      # the tree the model reads
    if masters is not None:
        _same_bits(opt.masters, masters)
    _same_bits(opt.params, params)


# ---- the account tool's reading of a compiled step -------------------------

@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_compiled_step_aliases_and_the_account_tool_counts_them(dtype):
    """``tools/hbm_account.py --hlo`` reads a compiled step program:
    every packed buffer is an aliased argument (XLA kept jax's pairing)
    and a copy is counted once it is a bucket's size."""
    tool = _account_tool()
    opt, params, _ = _build("adam", DTYPES[dtype])
    text = opt._jit_step.lower(
        *opt._step_args(_grads(params, 1), 1.0, jnp.int32(0))
    ).compile().as_text()
    got = tool.hlo_summary(text, n_params=386)
    assert got["aliased_arguments"] == len(_donated(opt))
    fake = text + "\n  %c = f32[300]{0} copy(f32[300]{0} %p)\n"
    assert tool.hlo_summary(fake, 386)["bucket_sized_copies"].get(
        "f32[300]", 0) == got["bucket_sized_copies"].get("f32[300]", 0) + 1
