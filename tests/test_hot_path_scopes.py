"""The names the training hot path gives its work (PR 25).

Device side: ``jax.named_scope("apex_<layer>/<phase>")`` inside the two
step programs must reach the ``op_name`` of the compiled program's
instructions — that is what a TPU trace carries per device op, and what
``benchmarks/programtrace.py`` attributes device time by.  Backward ops
are wrapped (``transpose(jvp(...))``) and must still resolve to the
same token.

Host side: the library's own ``telemetry.span`` at its boundaries
(``apex/optim/step`` with its children, ``apex/amp/update_scaler``,
``apex/data/next``), read here through a registered sink.

Everything compiles on the CPU backend at toy sizes; nothing is timed.
"""

import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import amp
from apex_tpu.data import DevicePrefetcher
from apex_tpu.models.bert import BertModel
from apex_tpu.optimizers import (FusedAdam, FusedLAMB, FusedNovoGrad,
                                 FusedSGD)
from apex_tpu.telemetry import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the one matching rule, kept with the benchmark's readers
from benchmarks.programtrace import scope_path, under  # noqa: E402

EVERY_OPTIMIZER = {"apex_optim/pack_grads", "apex_optim/moments"}
# the overflow skip and the model-dtype copy ride the update's sweeps
# since PR 28: their scopes mark the per-leaf path only
PER_LEAF_ONLY = {"apex_optim/skip_select", "apex_optim/cast_model"}
OPTIMIZERS = [
    (FusedSGD, dict(lr=0.1, momentum=0.9), set()),
    (FusedAdam, dict(lr=1e-2), set()),
    (FusedLAMB, dict(lr=1e-2), {"apex_optim/grad_norm",
                                "apex_optim/trust_ratio",
                                "apex_optim/apply"}),
    (FusedNovoGrad, dict(lr=1e-2), {"apex_optim/grad_norm"}),
]


def op_names(compiled):
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def _tree():
    # two buckets of two leaves each, so packing concatenates
    k = jax.random.split(jax.random.key(0), 4)
    return {"a": jax.random.normal(k[0], (16, 128), jnp.bfloat16),
            "b": jax.random.normal(k[1], (128,), jnp.bfloat16),
            "c": jax.random.normal(k[2], (16, 128), jnp.bfloat16),
            "d": jax.random.normal(k[3], (128,), jnp.bfloat16)}


def _optimizer(cls, kw):
    params = _tree()
    opt = cls(params, master_weights=True,
              max_bucket_bytes=(16 * 128 + 128) * 4, **kw)
    assert len(opt._plan.buckets) == 2
    return opt, jax.tree_util.tree_map(lambda p: p * 0.01, params)


@pytest.mark.parametrize("cls, kw, phases", OPTIMIZERS,
                         ids=[o[0].__name__ for o in OPTIMIZERS])
def test_optimizer_program_names_its_phases(cls, kw, phases):
    opt, grads = _optimizer(cls, kw)
    compiled = opt._jit_step.lower(
        *opt._step_args(grads, 1.0, jnp.int32(0))).compile()
    found = {"/".join(n.split("/")[i:i + 2])
             for n in op_names(compiled)
             for i, part in enumerate(n.split("/"))
             if part == "apex_optim"}
    assert EVERY_OPTIMIZER | phases <= found, sorted(found)
    # a phase another optimizer owns does not appear in this program
    others = set().union(*(o[2] for o in OPTIMIZERS)) - phases
    assert not (others & found), sorted(found)
    assert not (PER_LEAF_ONLY & found), sorted(found)


@pytest.fixture(scope="module")
def bert_step_names():
    spec = importlib.util.spec_from_file_location(
        "scopes_pretrain_mlm",
        os.path.join(ROOT, "examples", "bert", "pretrain_mlm.py"))
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    out = {}
    for level in ("O2", "O1"):
        model = BertModel(vocab_size=256, hidden_size=128, num_heads=2,
                          num_layers=2, max_seq_len=128,
                          dtype=jnp.bfloat16)
        tok = jnp.zeros((2, 128), jnp.int32)
        params = model.init(jax.random.key(0), tok)["params"]
        params, state = amp.initialize(params, opt_level=level)
        step = example.build_step(model, state)
        out[level] = op_names(
            step.lower(params, state.scaler, tok, tok).compile())
    return out


@pytest.mark.parametrize("level", ["O2", "O1"])
@pytest.mark.parametrize("token, backward", [
    ("apex_amp", False),            # apex_amp/unscale, apex_amp/scale_loss
    ("apex_linear", False), ("apex_linear", True),
    ("apex_layernorm", False), ("apex_layernorm", True),
    ("apex_attention", False), ("apex_attention", True),
    ("apex_xentropy", False), ("apex_xentropy", True)])
def test_train_step_names_its_layers_in_both_directions(
        bert_step_names, level, token, backward):
    """Forward ops sit under ``jvp(...)`` or nothing, backward ops under
    ``transpose(jvp(...))``: the token is found in both.  O1 rewrites
    the traced forward equation by equation (amp/wrap.py) and must
    re-issue each under the name stack it was traced with."""
    mine = [n for n in bert_step_names[level]
            if ("transpose(" in n) == backward
            and under(scope_path(n + ":"), [token])]
    assert mine, (level, token, backward)


@pytest.mark.parametrize("scope", ["apex_amp/unscale", "apex_amp/scale_loss",
                                   "apex_amp/cast"])
def test_train_step_names_the_amp_phases(bert_step_names, scope):
    level = "O1" if scope == "apex_amp/cast" else "O2"
    assert any(under(scope_path(n + ":"), [scope])
               for n in bert_step_names[level]), scope


# ---- host spans ------------------------------------------------------------

@pytest.fixture
def records():
    got = []

    def sink(name, record):
        got.append(record)
    spans.add_sink(sink)
    try:
        yield got
    finally:
        spans.remove_sink(sink)


def test_optimizer_step_opens_its_spans(records):
    opt, grads = _optimizer(FusedLAMB, dict(lr=1e-2))
    opt.step(grads, found_inf=jnp.int32(0))
    by_name = {}
    for r in records:
        by_name.setdefault(r.name, []).append(r)
    step, = by_name["apex/optim/step"]
    assert step.parent is None
    children = [r for r in records if r.parent == "apex/optim/step"]
    assert {r.name for r in children} == {
        "apex/optim/args", "apex/optim/dispatch", "apex/optim/clock",
        "apex/optim/unpack_model"}
    assert len(by_name["apex/optim/clock"]) == 2     # the add, the where
    # the lazy unpack is step's last child today; a later read is cached
    assert children[-1].name == "apex/optim/unpack_model"
    assert all(step.start <= r.start <= r.end <= step.end
               for r in children)
    n = len(records)
    opt.params
    assert len(records) == n
    # ... and a read after the cache is dropped opens the span alone
    opt._params_cache = None
    opt.params
    assert records[-1].name == "apex/optim/unpack_model"
    assert records[-1].parent is None


def test_scaler_update_and_prefetcher_open_their_spans(records):
    params, state = amp.initialize({"w": jnp.ones((4, 4))}, opt_level="O2")
    amp.update_scaler(state, jnp.int32(0))
    with DevicePrefetcher(iter([{"x": jnp.ones((2,))}] * 2)) as it:
        assert len(list(it)) == 2
    names = [r.name for r in records]
    assert names.count("apex/amp/update_scaler") == 1
    assert names.count("apex/data/next") == 3        # two batches, the end


# ---- the dropless expert layer (PR 34) ---------------------------------------------

MOE_SCOPES = ["apex_moe/router", "apex_moe/dispatch", "apex_moe/experts",
              "apex_moe/combine"]
# what moves or multiplies rows: the ops ``moe_ms`` is there to read
MOE_WORK = r"\s(gather|while|sort|dot|dynamic-update-slice|custom-call)\("


@pytest.fixture(scope="module")
def moe_layer_ops():
    """(opcode, op_name) of the working instructions of ``dropless_moe``
    forward and of its four gradients, compiled here at a toy size."""
    from apex_tpu.transformer.moe import dropless_moe
    k = jax.random.split(jax.random.key(0), 5)
    args = (jax.random.normal(k[0], (96, 64), jnp.bfloat16),
            jax.random.normal(k[1], (64, 16), jnp.bfloat16),
            jax.random.normal(k[2], (4, 64, 64), jnp.bfloat16),
            jax.random.normal(k[3], (4, 32, 64), jnp.bfloat16))
    ct = jax.random.normal(k[4], (96, 64), jnp.bfloat16)

    def layer(*a):
        return dropless_moe(*a, top_k=2, expert_offset=4)[0]

    def working_ops(fn):
        text = jax.jit(fn).lower(*args).compile().as_text()
        return [
            (m.group(1), re.search(r'op_name="([^"]*)"', line).group(1))
            for line in text.splitlines()
            if (m := re.search(MOE_WORK, line.partition(" = ")[2]))
            and "op_name=" in line]
    return {"forward": working_ops(layer),
            "backward": working_ops(lambda *a: jax.vjp(layer, *a)[1](ct))}


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_expert_layer_keeps_every_move_under_its_scopes(moe_layer_ops,
                                                        direction):
    """Every gather, loop, sort, product and chunk write of the layer,
    forward and backward, carries one of the four ``apex_moe/*`` scopes
    in its name (by the rule ``moe_ms``'s reader matches with), and the
    loops over the rows in use are there to be read."""
    from benchmarks.readers.scope_span import _components, _holds
    ops = moe_layer_ops[direction]
    assert ops

    def scopes_of(name):
        parts = _components(name + ":")
        return [s for s in MOE_SCOPES if _holds(parts, s.split("/"))]
    stray = [(op, name) for op, name in ops if not scopes_of(name)]
    assert not stray, stray
    if direction == "backward":
        ops = [(op, name) for op, name in ops if "transpose(" in name]
    loops = {s for op, name in ops if op == "while" for s in scopes_of(name)}
    assert loops == {"apex_moe/dispatch", "apex_moe/experts",
                     "apex_moe/combine"}, loops
    gathers = {s for op, name in ops if op == "gather"
               for s in scopes_of(name)}
    assert {"apex_moe/dispatch", "apex_moe/combine"} <= gathers, gathers
