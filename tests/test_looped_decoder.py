"""The looped decoder (apex_tpu/models/looped.py) at a small size on
seeded weights, on the CPU: against the plain reference the benchmark
keeps (benchmarks/reference/ouro_2p6b_adamw.py, float32 ``highest``,
nothing of the program), and against itself with the loop undone.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import amp  # noqa: E402
from apex_tpu.models import LoopedDecoder  # noqa: E402
from apex_tpu.models.looped import (LoopedPass, combine_exits,  # noqa: E402
                                    exit_distribution, rotary_freqs)
from apex_tpu.optimizers import FusedAdam  # noqa: E402
from benchmarks import weights  # noqa: E402
from benchmarks.reference import ouro_2p6b_adamw as reference  # noqa: E402

SIZES = {"hidden_size": 64, "num_attention_heads": 2, "head_dim": 32,
         "intermediate_size": 96, "num_hidden_layers": 2,
         "total_ut_steps": 3, "vocab_size": 256, "rms_norm_eps": 1e-6,
         "rope_theta": 1e6, "exit_entropy_weight": 0.1,
         "initializer_range": 0.02}
ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
        "weight_decay": 0.1, "max_grad_norm": 1.0}
B, S = 2, 32


def model_for(sizes=SIZES, dtype=jnp.float32, **kw):
    args = dict(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_layers=sizes["num_hidden_layers"],
        ffn_hidden_size=sizes["intermediate_size"],
        num_passes=sizes["total_ut_steps"],
        entropy_weight=sizes["exit_entropy_weight"], dtype=dtype)
    args.update(kw)
    return LoopedDecoder(**args)


def seeded(seed=5, sizes=SIZES):
    params = weights.make(reference.param_spec(sizes), seed)
    # move the gate off its symmetric start so that every exit matters
    params["stack"]["exit"]["gate_bias"] = jnp.array([0.3], jnp.float32)
    key = jax.random.fold_in(weights.seed_key(seed), 1)
    tokens, labels = jax.random.randint(key, (2, B, S), 0,
                                        sizes["vocab_size"])
    return params, tokens, labels


def copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def flat(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def load_example():
    path = os.path.join(ROOT, "examples", "gpt", "train_looped.py")
    spec = importlib.util.spec_from_file_location("train_looped_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---- against the plain reference ------------------------------------------------

def test_init_gives_the_tree_the_reference_describes():
    params, tokens, labels = seeded()
    made = model_for().init(jax.random.key(0), tokens, labels)["params"]
    assert (jax.tree_util.tree_map(jnp.shape, made)
            == jax.tree_util.tree_map(jnp.shape, params))


def test_loss_and_every_leafs_gradient_agree_with_the_reference_in_float32():
    params, tokens, labels = seeded()
    model = model_for()
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss({"params": p}, tokens, labels)))(params)
    want_loss, want = reference.loss_and_grads(
        reference._Programs(SIZES, "f32"), params, tokens, labels,
        SIZES["num_hidden_layers"], SIZES["total_ut_steps"])
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    got, want = flat(grads), flat(want)
    assert set(got) == set(want)
    for leaf, g in want.items():
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0, leaf
        np.testing.assert_allclose(got[leaf], g, rtol=0, atol=2e-4 * scale,
                                   err_msg=leaf)


@pytest.mark.parametrize("opt_level, loss_rel, norm_rel", [
    ("O0", 1e-5, 2e-3), ("O2", 2e-3, 4e-2)])
def test_three_adamw_steps_agree_with_the_reference(opt_level, loss_rel,
                                                    norm_rel):
    """The example's own step and optimizer (amp.initialize ->
    scaled_value_and_grad -> FusedAdam.step(clip_coef=) ->
    update_scaler) against the reference's three steps: each loss, the
    clipped first gradient (from Adam's first moment) and the
    parameters' change, as norms by leaf."""
    example = load_example()
    params, tokens, labels = seeded()
    start = copy(params)
    batches = [(tokens, labels), (labels, tokens), (tokens, labels)]
    half = jnp.bfloat16 if opt_level == "O2" else jnp.float32
    model = model_for(dtype=half)
    p, amp_state = amp.initialize(copy(params), opt_level=opt_level)
    if opt_level == "O2":
        opt, amp_state = example.build_optimizer(
            p, amp_state, lr=ADAM["lr"], betas=(0.9, 0.95),
            weight_decay=ADAM["weight_decay"])
    else:
        opt = FusedAdam(p, lr=ADAM["lr"], betas=(0.9, 0.95),
                        weight_decay=ADAM["weight_decay"])
    step = example.build_step(model, amp_state, ADAM["max_grad_norm"])
    losses = []
    for i, batch in enumerate(batches):
        loss, grads, found_inf, clip = step(opt.params, amp_state.scaler,
                                            *batch)
        opt.step(grads, found_inf=found_inf, clip_coef=clip)
        amp_state = amp.update_scaler(amp_state, found_inf)
        losses.append(float(loss))
        if i == 0:
            assert float(clip) < 1.0        # the clip is at work
            state = opt.opt_state["exp_avg"]
            if opt._plan is not None:
                state = opt._plan.unpack_state_field(state)
            grad1 = flat(jax.tree_util.tree_map(
                lambda m: float(jnp.linalg.norm(m)) / (1 - ADAM["beta1"]),
                state))
    masters = opt.masters if opt_level == "O2" else opt.params
    change = flat(jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b)),
        masters, start))
    ref = reference.follow(copy(params), batches, SIZES, ADAM)
    assert losses == pytest.approx(ref["losses"], rel=loss_rel)
    floor1 = np.median(list(flat(ref["grad1"]).values()))
    floor3 = np.median(list(flat(ref["change"]).values()))
    for leaf, want in flat(ref["grad1"]).items():
        assert abs(grad1[leaf] - want) <= norm_rel * max(want, floor1), leaf
    for leaf, want in flat(ref["change"]).items():
        assert abs(change[leaf] - want) <= norm_rel * max(want, floor3), leaf


# ---- the loop --------------------------------------------------------------------

def _untied_loss(model, copies, embed, tokens, labels):
    """The same computation with a copy of the stack's weights for each
    pass: ``LoopedPass`` applied once per copy, outside any scan."""
    one_pass = LoopedPass(
        model.vocab_size, model.hidden_size, model.num_heads,
        model.num_layers, model.ffn_hidden_size, model.rms_norm_eps,
        model.dtype)
    freqs = rotary_freqs(tokens.shape[1],
                         model.hidden_size // model.num_heads,
                         model.rope_theta)
    h = embed["weight"][tokens]
    losses, z = [], []
    for p in copies:
        h, (l_t, z_t) = one_pass.apply({"params": p}, h, (freqs, labels))
        losses.append(l_t)
        z.append(z_t)
    return combine_exits(jnp.stack(losses), jnp.stack(z),
                         model.entropy_weight)


def test_a_shared_weights_gradient_is_the_sum_over_untied_copies():
    params, tokens, labels = seeded()
    model = model_for()
    tied = jax.jit(jax.grad(
        lambda p: model.loss({"params": p}, tokens, labels)))(params)
    copies = [copy(params["stack"]) for _ in range(model.num_passes)]
    loss = model.loss({"params": params}, tokens, labels)
    assert float(_untied_loss(model, copies, params["embed"], tokens,
                              labels)) == pytest.approx(float(loss),
                                                        rel=1e-6)
    per_copy = jax.jit(jax.grad(
        lambda c: _untied_loss(model, c, params["embed"], tokens,
                               labels)))(copies)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_copy)
    # every copy contributes (none of the R terms is nought) ...
    qkv = [c["layer_0"]["attn_qkv"]["weight"] for c in per_copy]
    assert all(float(jnp.linalg.norm(g)) > 0 for g in qkv)
    # ... and the shared weight's gradient is their sum
    for leaf, want in flat(summed).items():
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(flat(tied["stack"])[leaf], want, rtol=0,
                                   atol=1e-5 * scale, err_msg=leaf)


def test_one_pass_is_the_plain_stack_run_once():
    """R = 1: the exit distribution is all on the one exit, its entropy
    nought, so the loss is the stack's mean cross-entropy."""
    params, tokens, labels = seeded()
    model = model_for(num_passes=1)
    plain = _untied_loss(model, [params["stack"]], params["embed"], tokens,
                         labels)
    losses, z = model.apply({"params": params}, tokens, labels,
                            method="exits")
    assert losses.shape == z.shape == (1, B, S)
    loss = float(model.loss({"params": params}, tokens, labels))
    assert loss == pytest.approx(float(jnp.mean(losses[0])), rel=1e-6)
    assert loss == pytest.approx(float(plain), rel=1e-6)
    want, _ = reference.loss_and_grads(
        reference._Programs({**SIZES, "total_ut_steps": 1}, "f32"), params,
        tokens, labels, SIZES["num_hidden_layers"], 1)
    assert loss == pytest.approx(float(want), rel=2e-6)


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_exit_distribution_sums_to_one(passes):
    z = 3.0 * jax.random.normal(jax.random.key(passes), (passes, 5, 7))
    p = jnp.exp(exit_distribution(z))
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    lam, stay, want = jax.nn.sigmoid(z), 1.0, []
    for t in range(passes - 1):
        want.append(lam[t] * stay)
        stay = stay * (1 - lam[t])
    np.testing.assert_allclose(p, jnp.stack(want + [stay * jnp.ones_like(
        lam[0])]), atol=1e-6)


def test_closed_gates_leave_the_last_passs_loss():
    params, tokens, labels = seeded()
    params["stack"]["exit"]["gate_bias"] = jnp.array([-40.0], jnp.float32)
    model = model_for()
    losses, _ = model.apply({"params": params}, tokens, labels,
                            method="exits")
    loss = float(model.loss({"params": params}, tokens, labels))
    assert loss == pytest.approx(float(jnp.mean(losses[-1])), rel=1e-6)
    assert abs(loss - float(jnp.mean(losses[0]))) > 1e-4


def test_the_lowered_step_holds_the_stack_once():
    """The passes are one scan: the program has one while loop for
    them, the stack's matmuls in it once (so it does not grow with the
    number of passes), and takes each layer weight as one argument."""
    example = load_example()
    params, tokens, labels = seeded()
    p16, amp_state = amp.initialize(params, opt_level="O2")
    texts = {}
    for passes in (2, 4):
        step = example.build_step(model_for(dtype=jnp.bfloat16,
                                            num_passes=passes), amp_state)
        texts[passes] = step.lower(p16, amp_state.scaler, tokens,
                                   labels).as_text()
    dots = {r: t.count("stablehlo.dot_general") for r, t in texts.items()}
    assert dots[2] == dots[4] > 0
    assert "stablehlo.while" in texts[4]
    leaves = len(jax.tree_util.tree_leaves(p16))
    main = texts[4][texts[4].index("func.func public @main"):]
    arguments = main[:main.index(") -> (")]
    assert arguments.count("tensor<96x64xbf16>") == SIZES[
        "num_hidden_layers"]                     # mlp_down, once a layer
    assert arguments.count("%arg") >= leaves
