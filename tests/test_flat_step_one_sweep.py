"""One sweep per phase in the bucketed optimizer step (ISSUE 28).

The overflow skip and the model-dtype copy of the masters ride the
update's own sweep: the bucket steps run the ``jnp`` update math
(``ops.multi_tensor.flat_*_ref``, which take ``keep`` and
``model_dtype``), and XLA fuses the three into one pass per phase.
Contracts:

  * three steps through ``opt.step`` equal, to the bit, the
    composition the step was before: the update alone
    (``flat_*_ref``), then ``_skip_on_overflow`` over every buffer,
    then the cast of the masters — params, masters, state, fp8 slots
    and the step clock;
  * a skipped step returns every buffer bit-identical to its input,
    with ``inf`` in the gradients too (LAMB's apply included);
  * the traced step holds no bucket-sized op under the scopes of the
    passes that are gone, no kernel the compiler could not fuse
    around, and every donated state buffer is aliased to an output
    (tests/test_tpu_compile.py holds the fusion count at a real size).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.lint.semantic import jaxprs, specs
from apex_tpu.optimizers import (FusedAdagrad, FusedAdam, FusedLAMB,
                                 FusedMixedPrecisionLamb, FusedNovoGrad,
                                 FusedSGD)
from apex_tpu.optimizers._base import _skip_on_overflow

OPTS = {
    "FusedAdam": (FusedAdam, dict(lr=1e-2, weight_decay=0.01)),
    "FusedLAMB": (FusedLAMB, dict(lr=1e-2, weight_decay=0.01)),
    "FusedMixedPrecisionLamb": (FusedMixedPrecisionLamb,
                                dict(lr=1e-2, weight_decay=0.01)),
    "FusedSGD": (FusedSGD, dict(lr=0.1, momentum=0.9, weight_decay=1e-4)),
    "FusedAdagrad": (FusedAdagrad, dict(lr=1e-2, weight_decay=0.01)),
    "FusedNovoGrad": (FusedNovoGrad, dict(lr=1e-2, weight_decay=0.01)),
}
# The CPU compiler contracts a multiply and an add into one fused
# multiply-add wherever its instruction fusion puts them into one loop,
# and a select or a second output beside them moves that choice: two
# programs with the same arithmetic then differ in a last bit here and
# there.  With the pass off every HLO instruction is a loop of its own
# and the arithmetic is what the program says, on both sides.  (The
# TPU's vector unit has no fused multiply-add to contract into.)
AS_WRITTEN = {"xla_disable_hlo_passes": "fusion"}

# found_inf of each of the three steps
FLAGS = {"found_inf_0": (0, 0, 0), "found_inf_1": (0, 1, 0),
         "found_inf_none": (None, None, None)}


def _params(dtype):
    ks = jax.random.split(jax.random.key(3), 3)
    make = lambda k, shape: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    return {"w1": make(ks[0], (40, 33)), "b1": jnp.ones((33,), dtype),
            "w2": make(ks[1], (33, 7)), "scale": make(ks[2], (300,))}


def _grads(params, seed):
    return jax.tree_util.tree_map(
        lambda p: (jax.random.normal(jax.random.key(seed), p.shape,
                                     jnp.float32) * 0.1).astype(p.dtype),
        params)


def _build(name, dtype, fp8=False):
    cls, kw = OPTS[name]
    if cls is FusedMixedPrecisionLamb and dtype == jnp.float32:
        cls = FusedLAMB                # forces masters; none over f32
    # two buckets, so a bucket boundary is in the comparison
    opt = cls(_params(dtype), max_bucket_bytes=4096, **kw)
    assert opt.fuse_buckets and len(opt._plan.buckets) >= 2
    assert (opt._master_bufs is not None) == (dtype == jnp.bfloat16)
    if fp8:
        opt.enable_fp8()
    opt._jit_step = jax.jit(opt._full_step_impl, **opt._donation,
                            compiler_options=AS_WRITTEN)
    return opt


def _as_before(opt):
    """The step body as it was before the skip and the cast moved into
    the sweeps: the update alone, ``_skip_on_overflow`` over every
    buffer, the cast of the masters."""
    def body(param_bufs, master_bufs, opt_state, grads, step, grad_scale,
             hypers, found_inf):
        work = master_bufs if master_bufs is not None else param_bufs
        new_work, new_state, _ = opt._flat_step_math(
            work, opt._plan.pack(grads), opt_state, step, grad_scale,
            hypers)
        if found_inf is not None:
            new_work, new_state = _skip_on_overflow(
                found_inf, new_work, work, new_state, opt_state)
        if master_bufs is None:
            return new_work, None, new_state
        return ([w.astype(b.model_dtype)
                 for w, b in zip(new_work, opt._plan.buckets)],
                new_work, new_state)
    return jax.jit(body, compiler_options=AS_WRITTEN)


def _bits(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_same_bits(got, want, what):
    got, want = _bits(got), _bits(want)
    assert len(got) == len(want), what
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.dtype == b.dtype and a.shape == b.shape, (what, i)
        np.testing.assert_array_equal(
            a.view(np.uint8), b.view(np.uint8), err_msg=f"{what}[{i}]")


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_masters", "f32"])
@pytest.mark.parametrize("flags", sorted(FLAGS))
@pytest.mark.parametrize("name", sorted(OPTS))
def test_three_steps_equal_update_then_select_then_cast(name, flags, dtype):
    opt = _build(name, dtype, fp8=True)
    before = _as_before(opt)
    params, masters = opt._param_bufs, opt._master_bufs
    state = jax.tree_util.tree_map(jnp.copy, opt.opt_state)
    clock = 0
    for i, flag in enumerate(FLAGS[flags]):
        grads = _grads(_params(dtype), 10 + i)
        found_inf = None if flag is None else jnp.int32(flag)
        held = (_bits(opt._param_bufs), _bits(opt._master_bufs),
                _bits(opt.opt_state))
        # the oracle's own clock: advanced before the step, put back
        # after a skipped one
        args = opt._step_args(grads, 1.0, found_inf)
        params, masters, state = before(
            params, masters, state, grads, jnp.int32(clock + 1), *args[5:])
        clock += 0 if flag else 1
        opt.step(grads, found_inf=found_inf)
        assert int(opt.step_count) == clock
        _assert_same_bits(opt._param_bufs, params, f"step {i} params")
        _assert_same_bits(opt._master_bufs, masters, f"step {i} masters")
        _assert_same_bits(opt.opt_state, state, f"step {i} state")
        if flag:
            # the skipped step held everything, the fp8 slots with it
            _assert_same_bits(opt._param_bufs, held[0], "held params")
            _assert_same_bits(opt._master_bufs, held[1], "held masters")
            _assert_same_bits(opt.opt_state, held[2], "held state")
        else:
            assert any((a != b).any() for a, b in
                       zip(_bits(opt._param_bufs), held[0]))
    assert set(opt.opt_state) >= {"fp8_amax_history", "fp8_scale"}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_masters", "f32"])
@pytest.mark.parametrize("name", sorted(OPTS))
def test_overflowed_gradients_leave_every_buffer_as_it_was(name, dtype):
    """``found_inf=1`` with ``inf`` and ``nan`` in the gradient buckets:
    the update computes non-finite values everywhere (LAMB's ``update``
    and trust ratio too) and none of them reaches an output."""
    opt = _build(name, dtype)
    good = _grads(_params(dtype), 20)
    opt.step(good, found_inf=jnp.int32(0))      # non-trivial moments
    held = (_bits(opt._param_bufs), _bits(opt._master_bufs),
            _bits(opt.opt_state))
    bad = dict(good, w1=good["w1"].at[0, 0].set(jnp.inf),
               scale=good["scale"].at[5].set(jnp.nan))
    opt.step(bad, found_inf=jnp.int32(1))
    assert int(opt.step_count) == 1
    _assert_same_bits(opt._param_bufs, held[0], "params")
    _assert_same_bits(opt._master_bufs, held[1], "masters")
    _assert_same_bits(opt.opt_state, held[2], "state")
    for leaf in jax.tree_util.tree_leaves(opt.params):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


def test_functional_step_with_packed_state_takes_the_skip_in_the_sweep():
    opt = _build("FusedAdam", jnp.float32)
    params = _params(jnp.float32)
    grads = _grads(params, 30)
    step = jax.jit(opt.functional_step)
    new_p, new_s = step(params, opt.opt_state, grads, jnp.int32(1),
                        found_inf=jnp.int32(1))
    _assert_same_bits(new_p, params, "params")
    _assert_same_bits(new_s, opt.opt_state, "state")
    went_p, _ = step(params, opt.opt_state, grads, jnp.int32(1),
                     found_inf=jnp.int32(0))
    plain_p, _ = step(params, opt.opt_state, grads, jnp.int32(1))
    _assert_same_bits(went_p, plain_p, "a kept step is the plain step")


# ---- structure of the traced step -------------------------------------------

def _scoped_eqns(jaxpr, outer=""):
    """(equation, its whole name stack) of ``jaxpr`` and its sub-programs."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn, stack
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scoped_eqns(sub, stack)


@pytest.mark.parametrize("name", sorted(specs._BUCKETED_OPTIMIZERS))
def test_bucketed_step_has_no_pass_after_the_update(name):
    """Beside the semantic specs, with masters and a ``found_inf``:
    every bucket-sized select and every bucket-sized cast to the model
    dtype is traced inside the update's own phase (``moments``,
    ``apply``), none under the scopes of the passes that went
    (``skip_select``, ``cast_model``); no kernel stands in XLA's way;
    every buffer the optimizer donates (``_donation``: LAMB keeps its
    masters out) is aliased to an output."""
    built = specs._build_bucketed(name, masters=True,
                                  **specs._OPT_KW.get(name, {}))
    param_bufs, master_bufs = built["args"][:2]
    assert master_bufs is not None and built["args"][-1] is not None
    assert all(b.dtype == jnp.bfloat16 for b in param_bufs)
    smallest = min(int(b.size) for b in master_bufs)
    closed = jax.make_jaxpr(built["fn"])(*built["args"])
    selects = casts = 0
    for eqn, stack in _scoped_eqns(closed.jaxpr):
        big = [v.aval for v in eqn.outvars
               if getattr(v.aval, "size", 0) >= smallest]
        if not big:
            continue
        assert "apex_optim/skip_select" not in stack, (stack, eqn)
        assert "apex_optim/cast_model" not in stack, (stack, eqn)
        select = eqn.primitive.name == "select_n"
        cast = (eqn.primitive.name == "convert_element_type"
                and big[0].dtype == jnp.bfloat16)
        if select or cast:
            assert ("apex_optim/moments" in stack
                    or "apex_optim/apply" in stack), (stack, eqn)
            selects, casts = selects + select, casts + cast
    assert casts == len(master_bufs)        # one model copy a bucket
    assert selects >= 2 * len(master_bufs)  # masters and a moment, at least
    assert jaxprs.primitive_counts(closed.jaxpr).get("pallas_call", 0) == 0
    lowered = jax.jit(built["fn"], **built["jit_kwargs"]).lower(
        *built["args"]).as_text()
    donated = built["jit_kwargs"]["donate_argnums"]
    assert donated == ((0, 2) if name == "FusedLAMB" else (0, 1, 2))
    assert (jaxprs.donated_alias_count(lowered)
            == built["expect"]["donated_aliases"]
            == len(jax.tree_util.tree_leaves(
                [built["args"][i] for i in donated])))
