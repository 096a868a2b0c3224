"""The hybrid Mamba-2 / expert decoder (apex_tpu/models/nemotron_h.py)
at a small size on seeded weights, on the CPU: against the plain
reference the benchmark keeps
(benchmarks/reference/nemotron_twotower_30b_a3b_adamw.py, float32
``highest``, nothing of the program), and the names its work carries in
a lowered step.
"""

import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import amp  # noqa: E402
from benchmarks import weights  # noqa: E402
from benchmarks.programtrace import scope_path, under  # noqa: E402
from benchmarks.reference import nemotron_twotower_30b_a3b_adamw as reference  # noqa: E402


def load_example():
    spec = importlib.util.spec_from_file_location(
        "train_nemotron_t", os.path.join(ROOT, "examples", "gpt",
                                         "train_nemotron_h.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


EXAMPLE = load_example()
SIZES = dict(EXAMPLE.CPU_PROXY, expert_offset=4, initializer_range=0.02)
B, S = 2, 150                 # a chunk of 128 and a padded one


def seeded(seed=5, raw=False):
    """The seeded draws (``raw``: before ``initial``) with a wider
    router, so that the routing is decided by more than round-off."""
    params = weights.make(reference.param_spec(SIZES), seed)
    for layer in params.values():
        if isinstance(layer, dict) and "moe" in layer:
            layer["moe"]["router"] = 10 * layer["moe"]["router"]
    key = jax.random.fold_in(weights.seed_key(seed), 1)
    tokens, labels = jax.random.randint(key, (2, B, S), 0,
                                        SIZES["vocab_size"])
    return (params if raw else jax.jit(
        lambda p: reference.initial(p, SIZES))(params)), tokens, labels


def flat(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_init_gives_the_tree_the_reference_describes():
    params, tokens, labels = seeded()
    made = EXAMPLE.build_model(SIZES, jnp.float32).init(
        jax.random.key(0), tokens, labels)["params"]
    assert (jax.tree_util.tree_map(jnp.shape, made)
            == jax.tree_util.tree_map(jnp.shape, params))
    # one whole period of the pattern: MEMEM*E
    assert [next(k for k in ("mamba", "moe", "attn") if k in made[f"layer_{i}"])
            for i in range(7)] == ["mamba", "moe", "mamba", "moe", "mamba",
                                   "attn", "moe"]


def test_init_draws_each_normal_leaf_at_the_references_scale():
    # the model's own init, as the reference's spec: every matrix that
    # writes the residual stream (out_proj, o_proj, the experts' and the
    # shared expert's down) at 0.02 / sqrt(52), the others at 0.02
    _, tokens, labels = seeded()
    made = flat(EXAMPLE.build_model(SIZES, jnp.float32).init(
        jax.random.key(3), tokens, labels)["params"])
    spec = reference.param_spec(SIZES)
    raw = ("conv_weight", "conv_bias", "dt_bias", "A_log")  # ``initial``'s
    normal = {jax.tree_util.keystr(k): v[1][1] for k, v in
              jax.tree_util.tree_flatten_with_path(
                  spec, is_leaf=lambda x: isinstance(x, tuple))[0]
              if v[1][0] == "normal" and not any(r in jax.tree_util.keystr(k)
                                                 for r in raw)}
    low = 0.02 / 52 ** 0.5
    # three mixers, one attention layer, three expert layers (two each)
    assert sum(std == pytest.approx(low) for std in normal.values()) == 10
    for name, std in normal.items():
        assert float(jnp.std(made[name])) == pytest.approx(std, rel=0.1), name


def test_initial_draws_the_mixers_as_mamba2_does():
    params, _, _ = seeded()
    mamba = params["layer_0"]["mamba"]
    a = jnp.exp(mamba["A_log"])
    assert bool(jnp.all((a >= 1.0) & (a <= 16.0)))
    dt = jax.nn.softplus(mamba["dt_bias"])
    assert bool(jnp.all((dt >= 0.999e-3) & (dt <= 0.1001)))
    for leaf in ("conv_weight", "conv_bias"):
        assert float(jnp.max(jnp.abs(mamba[leaf]))) <= 0.5
    # rescale_prenorm_residual: 0.02 / sqrt(52) for the output projection
    std = float(jnp.std(mamba["out_proj"]["weight"]))
    assert std == pytest.approx(0.02 / 52 ** 0.5, rel=0.1)


def test_loss_and_every_leafs_gradient_agree_with_the_reference_in_float32():
    params, tokens, labels = seeded()
    model = EXAMPLE.build_model(SIZES, jnp.float32)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss({"params": p}, tokens, labels),
        has_aux=True))(params)
    want_loss, want = reference.loss_and_grads(
        reference._Programs(SIZES, "f32"), params, tokens, labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    assert aux["expert_counts"].shape == (3, 4)
    got, want = flat(grads), flat(want)
    assert set(got) == set(want)
    for leaf, g in want.items():
        scale = float(jnp.max(jnp.abs(g)))
        if "selection_bias" in leaf:        # picks, and is not trained
            assert scale == 0.0 and float(jnp.max(jnp.abs(got[leaf]))) == 0
            continue
        assert scale > 0, leaf
        np.testing.assert_allclose(got[leaf], g, rtol=0, atol=2e-4 * scale,
                                   err_msg=leaf)


@pytest.mark.parametrize("change", [
    {"ssd_decay": False}, {"ssd_d_skip": False}, {"gate_norm_groups": 1},
    {"expert_act": "relu"}, {"ssd_group_of_head": "tiled"}],
    ids=["no_decay", "no_d_skip", "norm_whole", "relu", "bc_tiled"])
def test_each_fault_of_the_reference_moves_the_loss_or_a_gradient(change):
    """The faults the limits are read against are real changes of the
    model: each moves the float32 loss or some leaf's gradient by far
    more than the program's rounding."""
    params, tokens, labels = seeded()
    want_loss, want = reference.loss_and_grads(
        reference._Programs(SIZES, "f32"), params, tokens, labels)
    loss, got = reference.loss_and_grads(
        reference._Programs(dict(SIZES, **change), "f32"), params, tokens,
        labels)
    gaps = [float(jnp.max(jnp.abs(g - w)) / (jnp.max(jnp.abs(w)) + 1e-30))
            for g, w in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want))]
    assert max(gaps) > 1e-2 or abs(float(loss - want_loss)) > 1e-3


def test_three_amp_o2_adamw_steps_agree_with_the_reference():
    """The example's own step and optimizer (amp O2, FusedAdam with its
    masters, the clip folded in) against the reference's three steps:
    each loss, the first gradient and the change, as norms by leaf."""
    adam = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
            "weight_decay": 0.1, "max_grad_norm": 1.0}
    params, tokens, labels = seeded()
    start = jax.tree_util.tree_map(jnp.copy, params)
    batches = [(tokens, labels), (labels, tokens), (tokens, labels)]
    model = EXAMPLE.build_model(SIZES, jnp.bfloat16)
    p, amp_state = amp.initialize(jax.tree_util.tree_map(jnp.copy, params),
                                  opt_level="O2")
    opt, amp_state = EXAMPLE.build_optimizer(
        p, amp_state, lr=adam["lr"], betas=(0.9, 0.95),
        weight_decay=adam["weight_decay"])
    step = EXAMPLE.build_step(model, amp_state, adam["max_grad_norm"])
    losses = []
    for i, batch in enumerate(batches):
        loss, grads, found_inf, clip, aux = step(
            opt.params, amp_state.scaler, *batch)
        opt.step(grads, found_inf=found_inf, clip_coef=clip)
        amp_state = amp.update_scaler(amp_state, found_inf)
        losses.append(float(loss))
        if i == 0:
            grad1 = flat(jax.tree_util.tree_map(
                lambda m: float(jnp.linalg.norm(m)) / (1 - adam["beta1"]),
                opt._plan.unpack_state_field(opt.opt_state["exp_avg"])))
    change = flat(jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b)),
        opt.masters, start))
    ref = reference.follow(seeded(raw=True)[0], batches, SIZES, adam)
    # bfloat16 matmuls against float32: a few parts in a thousand on a
    # loss of log(512), a few percent on a leaf's norm
    assert losses == pytest.approx(ref["losses"], rel=4e-3)
    for got, want in ((grad1, flat(ref["grad1"])),
                      (change, flat(ref["change"]))):
        floor = np.median(list(want.values()))
        for leaf, w in want.items():
            assert abs(got[leaf] - w) <= 8e-2 * max(w, floor), leaf


def test_the_lowered_step_names_the_new_scopes_and_kernels():
    params, tokens, labels = seeded()
    model = EXAMPLE.build_model(SIZES, jnp.bfloat16)
    p, amp_state = amp.initialize(params, opt_level="O2")
    step = EXAMPLE.build_step(model, amp_state)
    text = step.lower(p, amp_state.scaler, tokens, labels).compile(
        ).as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    paths = {scope_path(n) for n in names}
    for scope in ("apex_ssd/conv", "apex_ssd/dt", "apex_ssd/scan",
                  "apex_ssd/gate_norm", "apex_moe/shared", "apex_moe/router",
                  "apex_moe/experts", "apex_linear", "apex_attention"):
        assert any(under(path, [scope]) for path in paths), scope
    # the squared-ReLU experts carry no gated activation's scope
    assert not any(under(path, ["apex_swiglu"]) for path in paths)
    assert any("transpose(" in n and "apex_ssd/scan" in n for n in names)
    assert "apex_ssd_fwd" in text and "apex_ssd_bwd" in text
