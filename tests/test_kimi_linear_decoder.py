"""The hybrid linear-attention expert decoder
(apex_tpu/models/kimi_linear.py) at a small size on seeded weights, on
the CPU: against the plain reference the benchmark keeps
(benchmarks/reference/kimi_linear_48b_a3b_adamw.py, float32
``highest``, nothing of the program), the expert layer's share with the
sigmoid router and the shared expert, and the names its work carries in
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
from apex_tpu.transformer import moe  # noqa: E402
from benchmarks import weights  # noqa: E402
from benchmarks.programtrace import scope_path, under  # noqa: E402
from benchmarks.reference import kimi_linear_48b_a3b_adamw as reference  # noqa: E402

SIZES = {"hidden_size": 64, "num_hidden_layers": 5,
         "num_attention_heads": 2, "kda_num_heads": 2, "kda_head_dim": 32,
         "short_conv_kernel_size": 4, "full_attn_layers": [4],
         "first_k_dense_replace": 1, "intermediate_size": 96,
         "moe_intermediate_size": 32, "router_num_experts": 16,
         "num_experts": 4, "num_experts_per_token": 2, "expert_offset": 4,
         "routed_scaling_factor": 2.446, "num_shared_experts": 1,
         "kv_lora_rank": 32, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
         "v_head_dim": 32, "rms_norm_eps": 1e-5, "vocab_size": 256,
         "initializer_range": 0.02}
B, S = 2, 80                  # a chunk of 64 and a padded one


def load_example():
    spec = importlib.util.spec_from_file_location(
        "train_kimi_t", os.path.join(ROOT, "examples", "gpt",
                                     "train_kimi_linear.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


EXAMPLE = load_example()


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
    return (params if raw else jax.jit(reference.initial)(params)), \
        tokens, labels


def flat(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


# ---- against the plain reference ------------------------------------------------

def test_init_gives_the_tree_the_reference_describes():
    params, tokens, labels = seeded()
    made = EXAMPLE.build_model(SIZES, jnp.float32).init(
        jax.random.key(0), tokens, labels)["params"]
    assert (jax.tree_util.tree_map(jnp.shape, made)
            == jax.tree_util.tree_map(jnp.shape, params))
    # the layers are of the kinds the configuration says
    assert [("kda" in made[f"layer_{i}"], "moe" in made[f"layer_{i}"])
            for i in range(5)] == [(True, False), (True, True), (True, True),
                                   (False, True), (True, True)]


def test_initial_draws_the_gates_as_fla_does():
    params, _, _ = seeded()
    kda = params["layer_0"]["kda"]
    a = jnp.exp(kda["A_log"])
    assert bool(jnp.all((a >= 1.0) & (a <= 16.0)))
    dt = jax.nn.softplus(kda["dt_bias"])
    assert bool(jnp.all((dt >= 0.999e-3) & (dt <= 0.1001)))
    assert float(jnp.max(jnp.abs(kda["q_conv"]))) <= 0.5


def test_loss_and_every_leafs_gradient_agree_with_the_reference_in_float32():
    params, tokens, labels = seeded()
    model = EXAMPLE.build_model(SIZES, jnp.float32)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss({"params": p}, tokens, labels),
        has_aux=True))(params)
    want_loss, want = reference.loss_and_grads(
        reference._Programs(SIZES, "f32"), params, tokens, labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    assert aux["expert_counts"].shape == (4, 4)
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
    # loss of log(256), a few percent on a leaf's norm
    assert losses == pytest.approx(ref["losses"], rel=4e-3)
    for got, want in ((grad1, flat(ref["grad1"])),
                      (change, flat(ref["change"]))):
        floor = np.median(list(want.values()))
        for leaf, w in want.items():
            assert abs(got[leaf] - w) <= 8e-2 * max(w, floor), leaf


# ---- the expert layer's share -------------------------------------------------------

def _expert_layer(seed=3, tokens=96, hidden=64, width=32, experts=16):
    ks = jax.random.split(jax.random.key(seed), 7)
    return {"x": jax.random.normal(ks[0], (tokens, hidden)),
            "router": 0.3 * jax.random.normal(ks[1], (hidden, experts)),
            "gate_up": 0.2 * jax.random.normal(
                ks[2], (experts, hidden, 2 * width)),
            "down": 0.2 * jax.random.normal(ks[3], (experts, width, hidden)),
            "bias": 0.5 * jax.random.normal(ks[4], (experts,)),
            "shared": (0.2 * jax.random.normal(ks[5], (hidden, 2 * width)),
                       0.2 * jax.random.normal(ks[6], (width, hidden)))}


def _share(layer, offset, held, **kw):
    return moe.dropless_moe(
        layer["x"], layer["router"], layer["gate_up"][offset:offset + held],
        layer["down"][offset:offset + held], top_k=2, expert_offset=offset,
        score="sigmoid", selection_bias=layer["bias"], routed_scale=2.446,
        shared=layer["shared"], **kw)


def test_the_shares_with_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four holders of four experts each, a non-zero selection bias:
    their parts, less the shared expert each computed for all its
    tokens but once, sum to what the reference gives for all sixteen."""
    layer = _expert_layer()
    parts = [_share(layer, off, 4) for off in range(0, 16, 4)]
    p = {"router": layer["router"], "selection_bias": layer["bias"],
         "gate_up": layer["gate_up"], "down": layer["down"],
         "shared_gate_up": layer["shared"][0],
         "shared_down": layer["shared"][1]}
    whole = reference.moe(p, layer["x"], top_k=2, routed_scale=2.446)
    shared = reference.moe(dict(p, gate_up=p["gate_up"][:0],
                                down=p["down"][:0]), layer["x"], top_k=2)
    np.testing.assert_allclose(sum(y for y, _ in parts) - 3 * shared, whole,
                               rtol=0, atol=2e-5)
    assert sum(int(jnp.sum(c)) for _, c in parts) == 96 * 2
    # the bias picks: without it other experts are chosen
    _, with_bias = moe.route_topk(layer["x"], layer["router"], 2,
                                  score="sigmoid",
                                  selection_bias=layer["bias"])
    _, without = moe.route_topk(layer["x"], layer["router"], 2,
                                score="sigmoid")
    assert bool(jnp.any(with_bias != without))


def test_the_bias_picks_and_does_not_weigh():
    layer = _expert_layer()
    gates, experts = moe.route_topk(layer["x"], layer["router"], 2,
                                    score="sigmoid",
                                    selection_bias=layer["bias"],
                                    routed_scale=2.446)
    np.testing.assert_allclose(jnp.sum(gates, axis=-1), 2.446, rtol=1e-6)
    s = jax.nn.sigmoid(layer["x"] @ layer["router"])
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    np.testing.assert_allclose(
        gates, 2.446 * chosen / jnp.sum(chosen, -1, keepdims=True),
        rtol=1e-5)
    g = jax.grad(lambda b: jnp.sum(_share(dict(layer, bias=b), 4, 4)[0]))(
        layer["bias"])
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_sigmoid_routed_gradients_agree_with_the_dense_oracle():
    layer = _expert_layer()
    ct = jax.random.normal(jax.random.key(9), layer["x"].shape)
    kw = dict(top_k=2, expert_offset=4, score="sigmoid",
              selection_bias=layer["bias"], routed_scale=2.446)

    def total(fn):
        return lambda x, r, gu, d, s: jnp.sum(
            fn(x, r, gu, d, shared=s, **kw)[0] * ct)
    args = (layer["x"], layer["router"], layer["gate_up"][4:8],
            layer["down"][4:8], layer["shared"])
    got = jax.grad(total(moe.dropless_moe), (0, 1, 2, 3, 4))(*args)
    want = jax.grad(total(moe.dropless_moe_ref), (0, 1, 2, 3, 4))(*args)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=1e-4 * float(jnp.max(jnp.abs(w))))


# ---- the names its work carries -------------------------------------------------------

def test_the_lowered_step_names_the_new_scopes_and_kernels():
    params, tokens, labels = seeded()
    model = EXAMPLE.build_model(SIZES, jnp.bfloat16)
    p, amp_state = amp.initialize(params, opt_level="O2")
    step = EXAMPLE.build_step(model, amp_state)
    text = step.lower(p, amp_state.scaler, tokens, labels).compile(
        ).as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    paths = {scope_path(n) for n in names}
    for scope in ("apex_kda/conv", "apex_kda/gate", "apex_kda/scan",
                  "apex_kda/norm", "apex_mla", "apex_moe/shared",
                  "apex_moe/router", "apex_linear", "apex_swiglu"):
        assert any(under(path, [scope]) for path in paths), scope
    # forward, rematerialised forward and backward, in the backward too
    assert any("transpose(" in n and "apex_kda/scan" in n for n in names)
    assert "apex_kda_fwd" in text and "apex_kda_bwd" in text
