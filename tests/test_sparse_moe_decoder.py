"""The sparse-attention expert decoder (apex_tpu/models/sparse_moe.py)
at a small size on seeded weights, on the CPU: against the plain
reference the benchmark keeps
(benchmarks/reference/keye_vl2_30b_a3b_adamw.py, float32 ``highest``,
nothing of the program), and the contracts of its two mechanisms — the
experts' share and the indexer's isolation.
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
from apex_tpu.models import SparseMoEDecoder, sparse_moe  # noqa: E402
from apex_tpu.optimizers import FusedAdam  # noqa: E402
from apex_tpu.ops import sparse_index  # noqa: E402
from apex_tpu.transformer import moe  # noqa: E402
from benchmarks import weights  # noqa: E402
from benchmarks.reference import keye_vl2_30b_a3b_adamw as reference  # noqa: E402

SIZES = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "moe_intermediate_size": 32, "num_experts": 4,
         "router_num_experts": 16, "num_experts_per_tok": 2,
         "norm_topk_prob": True, "expert_offset": 4,
         "indexer_num_heads": 2, "indexer_head_dim": 8, "indexer_topk": 12,
         "index_loss_weight": 1.0, "num_hidden_layers": 2,
         "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1e7,
         "initializer_range": 0.02}
ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
        "weight_decay": 0.1, "max_grad_norm": 1.0}
B, S = 2, 32
INDEXER = ("index_proj", "index_k_norm")


def model_for(sizes=SIZES, dtype=jnp.float32, **kw):
    args = dict(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], num_layers=sizes["num_hidden_layers"],
        moe_ffn_hidden_size=sizes["moe_intermediate_size"],
        num_experts=sizes["router_num_experts"],
        experts_held=sizes["num_experts"],
        top_k=sizes["num_experts_per_tok"],
        index_heads=sizes["indexer_num_heads"],
        index_head_dim=sizes["indexer_head_dim"],
        index_topk=sizes["indexer_topk"],
        expert_offset=sizes["expert_offset"],
        rms_norm_eps=sizes["rms_norm_eps"],
        rope_theta=float(sizes["rope_theta"]),
        index_loss_weight=sizes["index_loss_weight"], dtype=dtype)
    args.update(kw)
    return SparseMoEDecoder(**args)


def seeded(seed=5, sizes=SIZES):
    params = weights.make(reference.param_spec(sizes), seed)
    # wider indexer and router weights, so that the selection and the
    # routing are decided by more than round-off
    for name, layer in params.items():
        if name.startswith("layer_"):
            layer["index_proj"]["weight"] = 10 * layer["index_proj"]["weight"]
            layer["moe"]["router"] = 10 * layer["moe"]["router"]
    key = jax.random.fold_in(weights.seed_key(seed), 1)
    tokens, labels = jax.random.randint(key, (2, B, S), 0,
                                        sizes["vocab_size"])
    return params, tokens, labels


def copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def flat(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def load_script(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_example():
    return load_script("train_moe_t", "examples", "gpt", "train_moe.py")


def reference_grads(params, tokens, labels, sizes=SIZES):
    return reference.loss_and_grads(reference._Programs(sizes, "f32"),
                                    params, tokens, labels, sizes)


# ---- against the plain reference ------------------------------------------------

def test_init_gives_the_tree_the_reference_describes():
    params, tokens, labels = seeded()
    made = model_for().init(jax.random.key(0), tokens, labels)["params"]
    assert (jax.tree_util.tree_map(jnp.shape, made)
            == jax.tree_util.tree_map(jnp.shape, params))


def test_losses_and_every_leafs_gradient_agree_with_the_reference_in_float32():
    params, tokens, labels = seeded()
    model = model_for()
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss({"params": p}, tokens, labels),
        has_aux=True))(params)
    want_loss, want_lm, want_index, want = reference_grads(params, tokens,
                                                           labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    assert float(aux["lm_loss"]) == pytest.approx(float(want_lm), rel=2e-6)
    assert float(want_index) > 1e-3         # the objective is at work
    assert float(aux["index_loss"]) == pytest.approx(float(want_index),
                                                     rel=2e-5)
    got, want = flat(grads), flat(want)
    assert set(got) == set(want)
    for leaf, g in want.items():
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0, leaf
        np.testing.assert_allclose(got[leaf], g, rtol=0, atol=2e-4 * scale,
                                   err_msg=leaf)


@pytest.mark.parametrize("opt_level, loss_rel, norm_rel", [
    ("O0", 1e-5, 2e-3), ("O2", 4e-3, 8e-2)])
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
        loss, grads, found_inf, clip, aux = step(
            opt.params, amp_state.scaler, *batch)
        opt.step(grads, found_inf=found_inf, clip_coef=clip)
        amp_state = amp.update_scaler(amp_state, found_inf)
        losses.append(float(loss))
        assert int(jnp.sum(aux["expert_counts"])) > 0
        if i == 0:
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


# ---- the experts' share ------------------------------------------------------------

def _expert_layer(seed=3, tokens=96, hidden=64, width=32, experts=16):
    ks = jax.random.split(jax.random.key(seed), 4)
    return {"x": jax.random.normal(ks[0], (tokens, hidden)),
            "router": jax.random.normal(ks[1], (hidden, experts)),
            "gate_up": 0.2 * jax.random.normal(
                ks[2], (experts, hidden, 2 * width)),
            "down": 0.2 * jax.random.normal(ks[3], (experts, width, hidden))}


def _share(layer, offset, held, top_k=2, **kw):
    return moe.dropless_moe(
        layer["x"], layer["router"], layer["gate_up"][offset:offset + held],
        layer["down"][offset:offset + held], top_k=top_k,
        expert_offset=offset, **kw)


def test_the_shares_add_up_to_the_uncut_references_layer():
    """Four holders of four experts each: their parts of the result sum
    to what the reference gives for all sixteen experts at once."""
    layer = _expert_layer()
    parts = [_share(layer, off, 4) for off in range(0, 16, 4)]
    whole = reference.moe(
        {k: layer[k] for k in ("router", "gate_up", "down")}, layer["x"],
        top_k=2)
    np.testing.assert_allclose(sum(y for y, _ in parts), whole, rtol=0,
                               atol=2e-5)
    # every assignment is counted by exactly one holder
    assert sum(int(jnp.sum(c)) for _, c in parts) == 96 * 2
    # and a share alone is the reference's share
    one = reference.moe(
        {"router": layer["router"], "gate_up": layer["gate_up"][8:12],
         "down": layer["down"][8:12]}, layer["x"], top_k=2, offset=8)
    np.testing.assert_allclose(parts[2][0], one, rtol=0, atol=2e-5)


def test_no_token_is_dropped_when_the_router_sends_all_to_one_expert():
    layer = _expert_layer()
    layer["x"] = jnp.abs(layer["x"])
    layer["router"] = jnp.zeros_like(layer["router"]).at[:, 5].set(10.0)
    y, counts = _share(layer, 4, 4)
    assert counts.tolist() == [0, 96, 0, 0]       # expert 5 took them all
    want, _ = moe.dropless_moe_ref(
        layer["x"], layer["router"], layer["gate_up"][4:8],
        layer["down"][4:8], top_k=2, expert_offset=4)
    np.testing.assert_allclose(y, want, rtol=0, atol=2e-5)
    assert float(jnp.min(jnp.linalg.norm(y, axis=-1))) > 0


def test_gates_are_normalised_over_absent_experts_too():
    layer = _expert_layer()
    gates, experts = moe.route_topk(layer["x"], layer["router"], 2)
    np.testing.assert_allclose(jnp.sum(gates, axis=-1), 1.0, atol=1e-6)
    # a token with one chosen expert here and one elsewhere keeps the
    # gate it has among both: the share's output is that gate's, not 1
    here = (experts >= 4) & (experts < 8)
    split = jnp.sum(here, axis=-1) == 1
    assert int(jnp.sum(split)) > 0
    y, _ = _share(layer, 4, 4)
    unnormalised, _ = _share(layer, 4, 4, norm_topk_prob=False)
    raw = jnp.sum(jax.lax.top_k(jax.nn.softmax(
        layer["x"] @ layer["router"], axis=-1), 2)[0], axis=-1)
    np.testing.assert_allclose(y * raw[:, None], unnormalised, rtol=0,
                               atol=2e-5)


def test_dropless_moe_gradients_agree_with_the_dense_oracle():
    layer = _expert_layer()
    ct = jax.random.normal(jax.random.key(9), layer["x"].shape)
    args = (layer["x"], layer["router"], layer["gate_up"][4:8],
            layer["down"][4:8])

    def total(fn):
        return lambda *a: jnp.sum(fn(*a, top_k=2, expert_offset=4)[0] * ct)
    got = jax.grad(total(moe.dropless_moe), (0, 1, 2, 3))(*args)
    want = jax.grad(total(moe.dropless_moe_ref), (0, 1, 2, 3))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=1e-4 * float(jnp.max(jnp.abs(w))))


# ---- the cost follows the rows routed here: every fill of the buffer ---------------

FILL_T, FILL_K, FILL_HELD = 1536, 2, 4


def _forced_layer(pairs, experts, dtype):
    """A layer whose router sends token t to the experts ``pairs[t]``
    (first choice, second choice): the first ``experts`` directions of
    x carry the wanted logits, the router passes them on."""
    layer = _expert_layer(4, len(pairs), experts=experts)
    pairs = np.asarray(pairs)
    logits = np.zeros((len(pairs), experts), np.float32)
    logits[np.arange(len(pairs)), pairs[:, 0]] = 6.0
    logits[np.arange(len(pairs)), pairs[:, 1]] = 5.0
    layer["x"] = layer["x"].at[:, :experts].set(logits)
    layer["router"] = jnp.zeros_like(layer["router"]).at[:experts].set(
        jnp.eye(experts))
    return {k: v.astype(dtype) for k, v in layer.items()}


def _pairs_with(n_here, tokens=FILL_T):
    """Choices of which exactly ``n_here`` fall on experts 4..8: some
    tokens with both choices there, some with one, the rest with none,
    anywhere in token order."""
    rng = np.random.default_rng(n_here)
    both = n_here // 3
    one = n_here - 2 * both
    none = tokens - both - one
    assert none >= 0
    elsewhere = np.array([0, 1, 2, 3, 8, 9, 12, 15])
    inside = rng.integers(4, 8, both + one)
    out = rng.integers(0, 8, one + none)
    first = np.concatenate([inside, elsewhere[out[one:]]])
    second = np.concatenate([
        (inside[:both] - 4 + rng.integers(1, 4, both)) % 4 + 4,
        elsewhere[out[:one]], elsewhere[(out[one:] + 3) % 8]])
    pairs = np.stack([first, second], axis=1)
    rng.shuffle(pairs)
    return pairs


# name -> (the router's width, the first held expert, the choices or
# None for a seeded router, n_used or None, dtype); FILL_HELD are held
FILLS = {
    "none_routed_here": (16, 4, _pairs_with(0), 0, jnp.float32),
    "a_quarter": (16, 4, None, None, jnp.float32),
    "on_a_chunk_boundary": (16, 4, _pairs_with(2048), 2048, jnp.float32),
    "one_past_a_chunk_boundary": (16, 4, _pairs_with(2049), 2049,
                                  jnp.float32),
    "every_assignment_here": (4, 0, None, 2 * FILL_T, jnp.float32),
    "a_quarter_bf16": (16, 4, None, None, jnp.bfloat16),
}


def _fill_case(name):
    experts, offset, pairs, n_used, dtype = FILLS[name]
    if pairs is None:
        layer = _expert_layer(7, FILL_T, experts=experts)
        layer = {k: v.astype(dtype) for k, v in layer.items()}
    else:
        layer = _forced_layer(pairs, experts, dtype)
    args = (layer["x"], layer["router"],
            layer["gate_up"][offset:offset + FILL_HELD],
            layer["down"][offset:offset + FILL_HELD])
    return args, dict(top_k=FILL_K, expert_offset=offset), n_used, dtype


@pytest.mark.parametrize("name", list(FILLS))
def test_every_fill_of_the_buffer_agrees_with_the_dense_oracle(name):
    """The loops' trip counts follow ``sum(counts)``: nothing routed
    here, the expected share, a chunk's last row and the next chunk's
    first, and the whole buffer, forward and the four gradients."""
    args, kw, n_used, dtype = _fill_case(name)
    assert moe._CHUNK == 2048 and FILL_T * FILL_K % moe._CHUNK   # a last
    # chunk that the buffer's rows do not divide is in every case
    ct = jax.random.normal(jax.random.key(9), args[0].shape)

    def total(fn):
        def f(*a):
            y, counts = fn(*a, **kw)
            return jnp.sum(y.astype(jnp.float32) * ct), (y, counts)
        return jax.jit(jax.value_and_grad(f, (0, 1, 2, 3), has_aux=True))
    (_, (y, counts)), got = total(moe.dropless_moe)(*args)
    (_, (want_y, want_counts)), want = total(moe.dropless_moe_ref)(*args)
    assert counts.tolist() == want_counts.tolist()
    if n_used is not None:
        assert int(jnp.sum(counts)) == n_used
    else:                                 # about 1/4 of 2 T assignments
        assert 600 < int(jnp.sum(counts)) < 950
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    f32 = lambda a: np.asarray(a, np.float32)             # noqa: E731
    np.testing.assert_allclose(
        f32(y), f32(want_y), rtol=0,
        atol=tol * max(float(jnp.max(jnp.abs(want_y))), 1e-30))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(
            f32(g), f32(w), rtol=0,
            atol=tol * float(jnp.max(jnp.abs(w.astype(jnp.float32)))))
    if n_used == 0:
        assert float(jnp.max(jnp.abs(y))) == 0.0


@pytest.mark.parametrize("name", ["none_routed_here", "a_quarter",
                                  "one_past_a_chunk_boundary",
                                  "a_quarter_bf16"])
def test_rows_past_the_rows_in_use_are_zeros_in_every_buffer(name):
    """Expert order keeps the rows in use first; every buffer the
    layer's own passes write (the moved rows, the activation, and their
    gradients) is exact zeros after them, whatever the chunk."""
    (x, router, gate_up, down), kw, _, dtype = _fill_case(name)
    gates, experts = moe.route_topk(x, router, FILL_K)
    routed, counts = moe._route(gates, experts, kw["expert_offset"],
                                gate_up.shape[0])
    n = int(routed.n_used)
    assert n == int(jnp.sum(counts))
    rows = FILL_T * min(FILL_K, gate_up.shape[0])
    xs, pull_x = jax.vjp(lambda x: moe._dispatch(x, routed), x)
    mid = jax.lax.ragged_dot(xs, gate_up, counts)
    act, pull_mid = jax.vjp(lambda m: moe._gated_silu(m, routed.n_used), mid)
    ys = jax.lax.ragged_dot(act, down, counts)
    y, pull_y = jax.vjp(lambda ys, g: moe._combine(ys, g, routed), ys, gates)
    dys, dgates = pull_y(jnp.ones_like(y))
    dmid, = pull_mid(jnp.ones_like(act))
    for label, buf in (("xs", xs), ("act", act), ("dys", dys),
                       ("dmid", dmid)):
        assert buf.shape[0] == rows and buf.dtype == dtype, label
        assert float(jnp.max(jnp.abs(buf[n:].astype(jnp.float32)),
                             initial=0.0)) == 0.0, label
        if n:
            assert float(jnp.min(jnp.max(jnp.abs(buf[:n].astype(
                jnp.float32)), axis=-1))) > 0.0, label
    # the gates of assignments held elsewhere get no gradient
    local = experts - kw["expert_offset"]
    here = (local >= 0) & (local < gate_up.shape[0])
    assert float(jnp.max(jnp.abs(jnp.where(here, 0.0, dgates)))) == 0.0
    assert pull_x(jnp.ones_like(xs))[0].shape == x.shape


@pytest.mark.parametrize("fill", [0.0625, 0.25, 1.0])
def test_the_layer_bench_routes_about_the_share_it_is_asked_for(
        monkeypatch, fill):
    """``tools/moe_bench.py`` lifts the held experts' logits until about
    ``fill`` of the assignments are routed to them."""
    bench = load_script("moe_bench_t", "tools", "moe_bench.py")
    for name, value in dict(T=512, H=128, E=16, HELD=4, K=2, F=64).items():
        monkeypatch.setattr(bench, name, value)
    x, router, gate_up, down, ct = bench.inputs(fill)
    assert ct.shape == x.shape == (512, 128)
    _, counts = moe.dropless_moe(x, router, gate_up, down, top_k=2)
    assert abs(int(jnp.sum(counts)) / (512 * 2) - fill) < 0.03


# ---- the indexer's isolation ---------------------------------------------------------

REMAT = pytest.mark.parametrize("remat", [True, False],
                                ids=["remat", "no_remat"])


def _layers_as_written(monkeypatch, remat):
    """``remat`` False: the decoder's layers run as plain modules, so a
    test holds what the model computes and not what rematerialisation
    makes of it."""
    if not remat:
        monkeypatch.setattr(sparse_moe.nn, "remat", lambda cls, **kw: cls)


def _grads_of(which):
    params, tokens, labels = seeded()
    model = model_for()
    return jax.jit(jax.grad(
        lambda p: model.loss({"params": p}, tokens, labels)[1][which]))(
            params)


@REMAT
def test_the_language_loss_sends_the_indexer_no_gradient(monkeypatch, remat):
    _layers_as_written(monkeypatch, remat)
    for leaf, g in flat(_grads_of("lm_loss")).items():
        if any(name in leaf for name in INDEXER):
            assert float(jnp.max(jnp.abs(g))) == 0.0, leaf


@REMAT
def test_the_indexers_objective_sends_no_gradient_elsewhere(monkeypatch,
                                                            remat):
    _layers_as_written(monkeypatch, remat)
    for leaf, g in flat(_grads_of("index_loss")).items():
        if any(name in leaf for name in INDEXER):
            assert float(jnp.max(jnp.abs(g))) > 0.0, leaf
        else:
            assert float(jnp.max(jnp.abs(g))) == 0.0, leaf


def _straight_objective(scores_of, theta):
    """``sparse_moe._index_objective`` as ``jax.grad`` would have it:
    the scores differentiable in the leaves, the loss in the scores."""
    scores = scores_of(theta)
    return scores, lambda key_mask, q, k, lse: sparse_index.index_loss(
        scores, key_mask, q, k, lse)


def _losses_and_grads(ct=1.0, **kw):
    params, tokens, labels = seeded()
    model = model_for(**kw)

    def scaled(p):
        loss, aux = model.loss({"params": p}, tokens, labels)
        return ct * loss, aux
    (_, aux), grads = jax.jit(jax.value_and_grad(scaled, has_aux=True))(
        params)
    return aux, flat(grads)


@REMAT
def test_the_gradient_kept_in_the_forward_pass_is_the_straight_one(
        monkeypatch, remat):
    """The objective's parameter gradient is made where the objective
    is computed and scaled by the cotangent afterwards: both losses and
    every leaf's gradient are those of differentiating straight through
    ``index_loss``, and the indexer's leaves follow the cotangent (a
    loss scale, the objective's weight) exactly."""
    _layers_as_written(monkeypatch, remat)
    aux, grads = _losses_and_grads()
    _, scaled = _losses_and_grads(ct=2.0 ** 15, index_loss_weight=0.5)
    monkeypatch.setattr(sparse_moe, "_index_objective", _straight_objective)
    aux0, grads0 = _losses_and_grads()
    for name in ("lm_loss", "index_loss"):
        np.testing.assert_allclose(aux[name], aux0[name], rtol=1e-6)
    assert grads.keys() == grads0.keys()
    for leaf, g0 in grads0.items():
        top = float(jnp.max(jnp.abs(g0)))
        assert top > 0.0, leaf
        assert float(jnp.max(jnp.abs(grads[leaf] - g0))) <= 1e-6 * top, leaf
        if any(name in leaf for name in INDEXER):
            np.testing.assert_array_equal(scaled[leaf],
                                          2.0 ** 14 * grads[leaf], leaf)


def test_selecting_every_key_is_dense_causal_attention():
    """``index_topk >= s``: the selection is every causal pair, and the
    language loss is that of the same model without an indexer at
    all."""
    params, tokens, labels = seeded()
    dense = model_for(index_topk=S)
    wider = model_for(index_topk=4 * S)
    a = dense.loss({"params": params}, tokens, labels)[1]["lm_loss"]
    b = wider.loss({"params": params}, tokens, labels)[1]["lm_loss"]
    assert float(a) == float(b)
    sparse = model_for().loss({"params": params}, tokens, labels)[1]
    assert float(sparse["lm_loss"]) != float(a)
