"""Ask the CHIP'S compiler, without the chip.

libtpu is installed here, and it compiles for a TPU that is described
and not attached (``jax.experimental.topologies``): these tests compile
the main training path's Pallas kernels at their REAL widths for one
chip of a described ``v5e:2x2`` and read the compiled program.  That is
what interpret mode (every other CPU test) and Mosaic serialisation
(tests/test_tpu_lowering.py) cannot show — an unaligned slice, a VMEM
budget, a kernel that cannot be partitioned, a program that does not
fit 16 GB — and it costs no chip time.  Each kernel case also asserts
``tpu_custom_call`` is IN the compiled text, so a shape gate that
silently gives way to the XLA oracle fails here.

Nothing runs (there is no device to hold an array): shapes in, compiled
text out.  A compile that passes is not a chip run; ``chip_smoke.py``
is.

The topology is described inside the module-scoped ``topo`` fixture —
never at import, in a ``skipif``/``parametrize`` argument, in conftest
or in a child process: only one process may load libtpu, every xdist
worker imports every test file, and only the worker that RUNS this
file may load it.  Keep all such tests in this ONE file.
"""

import collections
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _force_mosaic(monkeypatch):
    # code under test asks jax.default_backend() and sees the CPU: steer
    # it to emit real (non-interpreted) kernels, as on the chip
    monkeypatch.setenv("APEX_TPU_FORCE_MOSAIC", "1")


def _compile(f, sharding, *specs):
    """Compile ``f`` for the described device(s); ``specs`` are
    (shape, dtype) pairs.  Returns the compiled text after checking the
    program fits one chip's HBM."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in specs]
    compiled = jax.jit(f).lower(*args).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.1f} GiB on a 16 GiB chip"
    return compiled.as_text()


def _grads(f, n):
    return jax.grad(lambda *a: jnp.sum(f(*a).astype(F32) ** 2),
                    argnums=tuple(range(n)))


def _assert_kernels(text, *names):
    assert "tpu_custom_call" in text, \
        "no Mosaic kernel in the compiled program: the gate gave way " \
        "to the XLA oracle"
    for name in names:
        assert name in text, f"kernel {name} absent from the program"


# ---------------------------------------------------------------------------
# BERT-Large's kernels: LayerNorm at (b8*s512, 1024), attention at s512
# ---------------------------------------------------------------------------

def test_layer_norm_fwd_bwd_bert_large_width(one_chip):
    from apex_tpu.ops.layer_norm import fused_layer_norm
    specs = (((4096, 1024), BF16), ((1024,), BF16), ((1024,), BF16))
    _assert_kernels(_compile(fused_layer_norm, one_chip, *specs),
                    "apex_fused_layer_norm_fwd")
    _assert_kernels(_compile(_grads(fused_layer_norm, 3), one_chip,
                             *specs),
                    "apex_fused_layer_norm_bwd")


@pytest.mark.parametrize("shape,causal", [((8, 16, 512, 64), False),
                                          ((2, 16, 2048, 64), True),
                                          ((1, 16, 4096, 128), True)],
                         ids=["bert_s512", "causal_s2048",
                              "looped_causal_s4096"])
def test_flash_attention_fwd_bwd(one_chip, shape, causal):
    from apex_tpu.ops.attention import flash_attention
    f = functools.partial(flash_attention, causal=causal)
    specs = ((shape, BF16),) * 3
    _assert_kernels(_compile(f, one_chip, *specs),
                    "apex_flash_attention_fwd")
    _assert_kernels(_compile(_grads(f, 3), one_chip, *specs),
                    "apex_flash_attention_dq", "apex_flash_attention_dkv")


def test_flash_attention_latent_widths_fwd_bwd(one_chip):
    """Latent attention's call: scores at 192, values at 128, causal,
    4096 positions."""
    from apex_tpu.ops.attention import flash_attention
    q = ((1, 32, 4096, 192), BF16)
    v = ((1, 32, 4096, 128), BF16)
    _assert_kernels(_compile(_grads(
        lambda q, k, v: flash_attention(q, k, v, True), 3), one_chip, q, q, v),
        "apex_flash_attention_fwd", "apex_flash_attention_dq",
        "apex_flash_attention_dkv")


def test_kda_scan_fwd_bwd_at_the_cell_width(one_chip):
    """The chunked delta rule at 32 heads of 128 and 4096 positions,
    forward and backward: both kernels, and the program fits."""
    from apex_tpu.ops.kda import chunk_kda
    a = ((1, 32, 4096, 128), BF16)
    txt = _compile(_grads(chunk_kda, 5), one_chip, a, a, a,
                   ((1, 32, 4096, 128), F32), ((1, 32, 4096), F32))
    assert "apex_kda_fwd" in txt and "apex_kda_bwd" in txt


def test_ssd_scan_fwd_bwd_at_the_cell_width(one_chip):
    """The SSD scan at 64 heads of 64, 8 groups of a state of 128 and
    8192 positions, forward and backward: both kernels, and the program
    fits."""
    from apex_tpu.ops.ssd import chunk_ssd
    txt = _compile(_grads(chunk_ssd, 5), one_chip, ((1, 8192, 64, 64), BF16),
                   ((1, 8192, 64), F32), ((64,), F32),
                   ((1, 8192, 8, 128), BF16), ((1, 8192, 8, 128), BF16))
    assert "apex_ssd_fwd" in txt and "apex_ssd_bwd" in txt


def test_flash_attention_segments_dropout_at_the_1024_tile(one_chip):
    """A long 16-bit sequence tiles at 1024 (PR 32): the variant that
    holds most beside the 4 MiB score tile — segment ids, the dropout
    hash, grouped heads, padding — still fits the kernels' VMEM."""
    from apex_tpu.ops.attention import _geom, flash_attention

    def f(q, k, v, ids, seed):
        return flash_attention(q, k, v, causal=True, segment_ids=(ids, ids),
                               dropout_rate=0.1, dropout_seed=seed)
    s = 4000
    specs = (((1, 4, s, 128), BF16), ((1, 2, s, 128), BF16),
             ((1, 2, s, 128), BF16), ((1, s), I32), ((), I32))
    q, k = (jax.ShapeDtypeStruct(sh, dt) for sh, dt in specs[:2])
    assert _geom(q, k)[6:10] == (1024, 1024, 4096, 4096)
    _assert_kernels(_compile(f, one_chip, *specs),
                    "apex_flash_attention_fwd")
    grads = jax.grad(lambda *a: jnp.sum(f(*a).astype(F32) ** 2),
                     argnums=(0, 1, 2))
    _assert_kernels(_compile(grads, one_chip, *specs),
                    "apex_flash_attention_dq", "apex_flash_attention_dkv")


# ---------------------------------------------------------------------------
# learned sparse attention at the expert decoder's cell: b1 s8192, 32
# query heads over 4 key heads of 128, a 16 x 64 indexer, 2048 keys a query
# ---------------------------------------------------------------------------

S8K = 8192


def test_flash_attention_under_a_key_selection_fwd_bwd(one_chip):
    from apex_tpu.ops.attention import flash_attention

    def f(q, k, v, m):
        return flash_attention(q, k, v, causal=True, key_mask=m)
    specs = (((1, 32, S8K, 128), BF16), ((1, 4, S8K, 128), BF16),
             ((1, 4, S8K, 128), BF16), ((1, S8K, S8K), jnp.int8))
    _assert_kernels(_compile(f, one_chip, *specs),
                    "apex_flash_attention_fwd")
    grads = jax.grad(lambda *a: jnp.sum(f(*a).astype(F32) ** 2),
                     argnums=(0, 1, 2))
    _assert_kernels(_compile(grads, one_chip, *specs),
                    "apex_flash_attention_dq", "apex_flash_attention_dkv")


def test_index_scores_selection_and_objective(one_chip):
    from apex_tpu.ops import sparse_index as si
    scorer = (((1, 16, S8K, 64), BF16), ((1, S8K, 64), BF16),
              ((1, S8K, 16), F32))
    _assert_kernels(_compile(si.index_scores, one_chip, *scorer),
                    "apex_index_scores_fwd")
    _assert_kernels(_compile(_grads(si.index_scores, 3), one_chip, *scorer),
                    "apex_index_scores_bwd")
    square = ((1, S8K, S8K), F32)
    text = _compile(lambda x: si.select_topk(x, 2048), one_chip, square)
    _assert_kernels(text, "apex_index_select")
    assert " sort(" not in text
    loss = jax.value_and_grad(si.index_loss)
    _assert_kernels(
        _compile(loss, one_chip, square, ((1, S8K, S8K), jnp.int8),
                 ((1, 32, S8K, 128), BF16), ((1, 4, S8K, 128), BF16),
                 ((1, 32, S8K), F32)), "apex_index_loss")


def _kernel_calls(text):
    """Mosaic kernel name -> its custom-call instructions in a compiled
    program's text."""
    return collections.Counter(re.findall(
        r'%([A-Za-z_]\w*?)(?:\.\d+)* = [^\n]*'
        r'custom_call_target="tpu_custom_call"', text))


# the same program at the parent of PR 36, where the rematerialised pass
# ran the index scores and the objective's kernel a second time
_TWO_LAYER_GRAD_TEMP_BEFORE = 2_304_238_592


def test_a_rematerialised_layer_runs_each_indexer_kernel_once(one_chip):
    """Two layers of the expert decoder at the cell's widths, loss and
    gradient: the objective's parameter gradient is made in the forward
    pass and kept (``models/sparse_moe.py:_index_objective``), so the
    rematerialised pass holds nothing of the indexer, a program that
    asks for no gradient holds nothing of its backward, and the step's
    temporaries are not above what they were."""
    from apex_tpu.models import SparseMoEDecoder
    layers = 2
    model = SparseMoEDecoder(
        vocab_size=18992, hidden_size=2048, num_heads=32, num_kv_heads=4,
        head_dim=128, num_layers=layers, moe_ffn_hidden_size=768,
        num_experts=128, experts_held=8, top_k=8, index_heads=16,
        index_head_dim=64, index_topk=2048, dtype=BF16)
    few = jnp.zeros((1, 128), I32)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, BF16, sharding=one_chip),
        jax.eval_shape(lambda: model.init(jax.random.key(0), few,
                                          few)["params"]))
    tokens = jax.ShapeDtypeStruct((1, S8K), I32, sharding=one_chip)

    def loss(p, t, y):
        return model.loss({"params": p}, t, y)
    step = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        params, tokens, tokens).compile()
    calls = _kernel_calls(step.as_text())
    for name in ("apex_index_loss", "apex_index_scores_fwd",
                 "apex_index_scores_bwd", "apex_index_select"):
        assert calls[name] == layers, (name, calls)
    assert calls["apex_flash_attention_fwd"] == 2 * layers
    assert (step.memory_analysis().temp_size_in_bytes
            <= _TWO_LAYER_GRAD_TEMP_BEFORE)
    forward = _kernel_calls(jax.jit(loss).lower(
        params, tokens, tokens).compile().as_text())
    assert forward["apex_index_loss"] == layers
    assert forward["apex_index_scores_bwd"] == 0, forward


def test_dropless_experts_are_grouped_products(one_chip):
    """One holder's share at the cell's size: 8 of 128 experts, 8192
    tokens top-8; the two expert products and their transposes compile
    to the compiler's grouped matmul, and no scatter moves a row."""
    from apex_tpu.transformer.moe import dropless_moe

    def f(x, router, gate_up, down):
        return dropless_moe(x, router, gate_up, down, top_k=8)[0]
    specs = (((S8K, 2048), BF16), ((2048, 128), BF16),
             ((8, 2048, 1536), BF16), ((8, 768, 2048), BF16))
    for text in (_compile(f, one_chip, *specs),
                 _compile(_grads(f, 4), one_chip, *specs)):
        assert "ragged-dot" in text and "tpu_custom_call" in text
        assert " scatter(" not in text


# ---------------------------------------------------------------------------
# the flat optimizer updates (XLA sweeps) and the AMP kernel at real
# bucket sizes
# ---------------------------------------------------------------------------

# one 128 MiB-capped BERT-Large bucket: two encoder layers and a third
# of the next, 28 leaves (mlp_in, its bias, mlp_out, bias, two
# LayerNorms, attn_proj, attn_qkv's bias, attn_qkv, ...)
_BERT_LAYER = (3145728, 4096, 4194304, 1024, 4194304, 1024, 1024, 1024,
               1024, 1024, 1048576, 3072)
LAMB_BUCKET_SIZES = _BERT_LAYER * 2 + _BERT_LAYER[:4]
N_LAMB_BUCKET = sum(LAMB_BUCKET_SIZES)
N_RESNET50 = 25_557_032


def _lamb(sizes):
    from apex_tpu.ops import multi_tensor as mt
    return lambda p, g, m, v: mt.flat_lamb(
        p, g, m, v, sizes, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-6,
        weight_decay=0.01, step=3)


def test_flat_lamb_one_bert_large_bucket(one_chip):
    n = N_LAMB_BUCKET
    assert n == 32_537_600 and len(LAMB_BUCKET_SIZES) == 28
    text = _compile(_lamb(LAMB_BUCKET_SIZES), one_chip,
                    *(((n,), F32),) * 4)
    assert "tpu_custom_call" not in text
    # per-tensor norms are reduces over static slices: nothing is
    # scattered through or gathered from an element->segment id vector
    assert "scatter" not in text and "gather" not in text
    assert f"s32[{n}]" not in text


def _bucket_sized_ops(text, n):
    """Instruction name -> count, over the entry computation's
    instructions that produce ``n`` elements or more (parameters,
    bitcasts and tuples aside)."""
    import collections
    import re
    ops = collections.Counter()
    for line in text.split("ENTRY")[1].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w\-]+?)(?:\.\d+)? = "
                     r"\(?[a-z0-9]+\[([\d,]*)\]", line)
        if not m or not m.group(2):
            continue
        size = 1
        for d in m.group(2).split(","):
            size *= int(d)
        if size >= n and not re.search(
                r" (parameter|bitcast|tuple|get-tuple-element)\(", line):
            ops[m.group(1)] += 1
    return ops


def test_lamb_bucket_step_is_one_sweep_per_phase(one_chip):
    """What the bucketed LAMB step runs on one BERT-Large bucket, in
    the benchmark's shape (bf16 gradients, a traced ``keep``, the bf16
    copy of the masters, donated moments): the compiler fuses the skip,
    the cast and the per-tensor broadcast into the two phases' sweeps.
    No kernel stands in its way, nothing bucket-sized is copied, and
    the temporaries are the ``update`` buffer alone."""
    from apex_tpu.ops import multi_tensor as mt
    n = N_LAMB_BUCKET

    def step(m, v, p, g, found_inf):
        p, m, v, p_model = mt.flat_lamb(
            p, g, m, v, LAMB_BUCKET_SIZES, lr=1e-3, beta1=0.9, beta2=0.999,
            eps=1e-6, weight_decay=0.01, step=3, keep=found_inf == 0,
            model_dtype=BF16)
        return m, v, p, p_model

    args = [jax.ShapeDtypeStruct((n,), d, sharding=one_chip)
            for d in (F32, F32, F32, BF16)]
    args.append(jax.ShapeDtypeStruct((), I32, sharding=one_chip))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    ops = _bucket_sized_ops(text, n)
    assert not [k for k in ops if k.startswith("copy")], ops
    # the trust factor is written into one buffer a tensor at a time
    # (in-place dynamic-update-slices, one sweep in all); beside them:
    # moments+update and the apply, each with the skip (and the cast)
    # inside, not a pass each as in the old step
    sweeps = {k: c for k, c in ops.items()
              if k.endswith("fusion") and "dynamic-update-slice" not in k}
    assert sum(sweeps.values()) == 2, ops
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == 8 * n      # m and v in place
    assert stats.temp_size_in_bytes < 5 * n        # ``update`` alone


def test_adam_bucket_step_is_one_sweep(one_chip):
    """A 128 MiB bucket of the looped decoder's plan under the bucketed
    Adam step: one fusion reads p, g, m, v and writes p, m, v and the
    bf16 parameters; no temporaries, no copies."""
    from apex_tpu.ops import multi_tensor as mt
    n = 32 * 2 ** 20

    def step(m, v, p, g, found_inf):
        p, m, v, p_model = mt.flat_adam(
            p, g, m, v, lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8,
            weight_decay=0.1, step=3, grad_scale=1024.0,
            keep=found_inf == 0, model_dtype=BF16)
        return m, v, p, p_model

    args = [jax.ShapeDtypeStruct((n,), d, sharding=one_chip)
            for d in (F32, F32, F32, BF16)]
    args.append(jax.ShapeDtypeStruct((), I32, sharding=one_chip))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    ops = _bucket_sized_ops(compiled.as_text(), n)
    assert sum(ops.values()) == 1 and not ops.get("copy"), ops
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == 8 * n
    assert stats.temp_size_in_bytes < 2 ** 20


def test_sgd_bucket_step_is_one_sweep(one_chip):
    """ResNet-50's parameters as one bucket under the bucketed SGD
    step: one fusion reads p, g and the momentum buffer and writes p,
    the buffer and the bf16 parameters; no temporaries, no copies."""
    from apex_tpu.ops import multi_tensor as mt
    n = N_RESNET50

    def step(buf, p, g, count, found_inf):
        p, buf, p_model = mt.flat_sgd(
            p, g, buf, lr=0.1, momentum=0.9, weight_decay=1e-4,
            first_run=count == 1, grad_scale=128.0, keep=found_inf == 0,
            model_dtype=BF16)
        return buf, p, p_model

    args = [jax.ShapeDtypeStruct((n,), d, sharding=one_chip)
            for d in (F32, F32, BF16)]
    args += [jax.ShapeDtypeStruct((), I32, sharding=one_chip)] * 2
    compiled = jax.jit(step, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    ops = _bucket_sized_ops(text, n)
    assert sum(ops.values()) == 1 and not ops.get("copy"), ops
    stats = compiled.memory_analysis()
    # the momentum buffer in place (its allocation padded to a tile)
    assert 4 * n <= stats.alias_size_in_bytes < 4 * n + 2 ** 13
    assert stats.temp_size_in_bytes < 2 ** 20


def test_flat_unscale_norm_resnet50_size(one_chip):
    from apex_tpu.ops import multi_tensor as mt
    text = _compile(lambda g: mt.flat_unscale_norm(g, 1 / 128.0),
                    one_chip, ((N_RESNET50,), BF16))
    _assert_kernels(text, "apex_multi_tensor_unscale_norm")


def test_segment_boundaries_are_not_baked_into_the_program(one_chip):
    """The plan's static segment sizes must stay slice bounds: the
    per-element factor is a concatenate of broadcasts built inside the
    program, never a bucket-sized literal (as a baked-in constant
    BERT-Large's one-bucket LAMB step was 1.25 GB of program)."""
    from apex_tpu.multi_tensor_apply.packer import BucketPlan
    leaves = [jnp.zeros((1024, 1024), F32)] * 8 + [jnp.zeros((1024,), F32)]
    plan = BucketPlan.from_tree(leaves)
    n = plan.buckets[0].size
    x = jax.ShapeDtypeStruct((n,), F32, sharding=one_chip)
    compiled = jax.jit(_lamb(plan.segment_sizes(0))).lower(
        x, x, x, x).compile()
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code < n, f"{code} bytes of program for a {n}-element bucket"


# ---------------------------------------------------------------------------
# ResNet-50 (SyncBatchNorm) and the loss kernel
# ---------------------------------------------------------------------------

def test_welford_resnet50_last_stage(one_chip):
    from apex_tpu.ops.welford import welford_mean_var
    _assert_kernels(_compile(welford_mean_var, one_chip,
                             ((6272, 2048), F32)),
                    "apex_syncbn_welford")


def test_xentropy_fwd_bwd_lane_aligned_vocab(one_chip):
    """At a 128-multiple vocabulary the fused loss is a kernel.  BERT's
    own 30528 and GPT-2's 50257 are not multiples: there the lane gate
    hands the loss to XLA (chip_smoke.py prints that, by design)."""
    from apex_tpu.ops.xentropy import softmax_cross_entropy
    n, c = 4096, 30592

    def fwd_bwd(logits, labels):
        return jax.value_and_grad(
            lambda z: jnp.sum(softmax_cross_entropy(z, labels)))(logits)
    _assert_kernels(_compile(fwd_bwd, one_chip, ((n, c), F32),
                             ((n,), I32)),
                    "apex_xentropy_fwd", "apex_xentropy_bwd")


# ---------------------------------------------------------------------------
# four chips: the DDP gradient reduce under shard_map
# ---------------------------------------------------------------------------

def test_ddp_flat_reduce_over_four_described_chips(topo):
    """A ``data=4`` Mesh over the described devices: the bucketed DDP
    all-reduce followed by the AMP unscale+norm kernel, per shard."""
    import numpy as np

    from apex_tpu import comm
    from apex_tpu.ops import multi_tensor as mt
    from apex_tpu.parallel.distributed import all_reduce_flat_buffers

    mesh = Mesh(np.asarray(topo.devices).reshape(4), (comm.AXIS_DATA,))
    n = 4 * 1024 * 1024

    def shard_step(g):                          # g: this shard's bucket
        (g,) = all_reduce_flat_buffers([g], comm.AXIS_DATA)
        out, norm_sq, bad = mt.flat_unscale_norm(g, 1 / 128.0)
        return out, norm_sq[None], bad[None]

    step = comm.shard_map(
        shard_step, mesh, in_specs=(P(comm.AXIS_DATA),),
        out_specs=(P(comm.AXIS_DATA),) * 3)
    text = _compile(step, NamedSharding(mesh, P(comm.AXIS_DATA)),
                    ((4 * n,), BF16))
    assert "all-reduce" in text
    _assert_kernels(text, "apex_multi_tensor_unscale_norm")


def test_fused_sgd_step_replicated_over_four_described_chips(topo):
    """The data-parallel optimizer step: FusedSGD's flat update on
    gradients REPLICATED over a 4-chip mesh.  A plain multi-device jit
    refuses it ("Mosaic kernels cannot be automatically partitioned" —
    what the first four-chip run of PR 21 died of, and what interpret
    mode on virtual CPU devices cannot show); the optimizer's
    replicated step wraps the same body in shard_map."""
    import numpy as np

    from apex_tpu import comm
    from apex_tpu.optimizers import FusedSGD

    mesh = Mesh(np.asarray(topo.devices).reshape(4), (comm.AXIS_DATA,))
    params = {"w": jnp.zeros((512, 1024), BF16),
              "b": jnp.zeros((1024,), BF16)}
    opt = FusedSGD(params, lr=0.1, momentum=0.9, master_weights=True)
    replicated = NamedSharding(mesh, P())
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                       sharding=replicated),
        opt._step_args(params, found_inf=jnp.int32(0)))
    text = opt._replicated_step(mesh).lower(*args).compile().as_text()
    # since PR 28 the update is XLA sweeps: nothing here needs a kernel
    # partitioned, and the program still has to compile replicated
    assert "tpu_custom_call" not in text and "fusion" in text


# ---------------------------------------------------------------------------
# serving: the example's prefill and decode-window programs
# ---------------------------------------------------------------------------

def test_serving_prefill_and_decode_window_at_example_geometry(one_chip):
    """examples/gpt/serve.py's geometry (2 layers, hidden 32, page 4 x
    8 pages, 2 slots, window 4), lowered from the step functions with
    described-device shapes — the engine is not touched.  Its first
    real configuration is ROADMAP R1's."""
    from apex_tpu import serving
    from apex_tpu.serving import steps

    cfg = serving.DecoderConfig(vocab_size=128, hidden=32, n_layers=2,
                                n_heads=2, n_kv_heads=2, ffn=64,
                                max_seq=64, eos_token=1)
    spec = serving.ArenaSpec(n_layers=2, n_kv_heads=2, head_dim=16,
                             page_size=4, n_pages=32, max_slots=2,
                             pages_per_slot=8)
    params = serving.init_params(jax.random.key(0), cfg)
    arena = serving.KVArena(spec)

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(jnp.shape(l),
                                           jnp.asarray(l).dtype,
                                           sharding=one_chip), tree)

    window = jax.jit(steps.decode_window_fn(cfg, spec, 4),
                     donate_argnums=(1,)).lower(
        sds(params), sds(steps.init_state(arena, 4, 0))).compile()
    assert "while" in window.as_text()          # the fori_loop window

    bucket = 16
    scalars = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
               for s, d in (((bucket // 4,), I32), ((bucket,), I32),
                            ((), I32), ((2,), jnp.uint32), ((), F32),
                            ((), I32), ((), F32))]
    prefill = jax.jit(steps.prefill_fn(cfg, spec, bucket),
                      donate_argnums=(1, 2, 3, 4)).lower(
        sds(params), *sds((arena.k, arena.v, arena.k_scale,
                           arena.v_scale)), *scalars).compile()
    _assert_kernels(prefill.as_text(), "apex_flash_attention_fwd")
