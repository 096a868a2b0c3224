"""Benchmark harness for the BASELINE.md tracked metrics.

Primary metric (north star): ResNet-50 ImageNet-shape training
throughput, amp O2 + FusedSGD (the reference's
examples/imagenet/main_amp.py config, synthetic data).
Secondary metric: BERT-Large FusedLAMB step time (BASELINE tracked
metric 2), reported in the same JSON line under "extra".

It measures on the chip or it exits non-zero: no replayed record, no
CPU stand-in, no relabelled result.  The last stdout line is ONE JSON
object
  {"metric": ..., "value": N, "unit": "imgs/sec/chip",
   "vs_baseline": N, "backend": "tpu", "device": {...}, ...}
and the exit code is 0 only when the backend is a TPU and every leg
ran; a failed leg is recorded under "errors" AND fails the run.

Process shape: the top-level process is a pure orchestrator that never
imports jax (a chip belongs to one process: a parent that touched jax
would hold it).  It starts exactly ONE watchdogged child, which runs
every leg; the child flushes a JSON line after each leg so a late hang
still leaves what was measured on stdout.

vs_baseline compares against the A100 amp target named in BASELINE.json
(~2500 imgs/sec/chip for ResNet-50 AMP on DGX A100, the number the
north star says to get within 10% of).

(ROADMAP S0 replaces this body with the workloads benchmark.)
"""

import json
import os
import subprocess
import sys
import time
import traceback

A100_IMGS_PER_SEC = 2500.0


def _mfu(flops, step_s):
    """Cost-model MFU via the observatory's one chip-spec table
    (apex_tpu.telemetry.profiler.mfu).  None when the running chip is
    not in that table — never a default peak; ``flops`` comes from the
    compiled step's own cost analysis, so wherever this is non-None
    the matching ``*_mfu_source`` extra reads "cost_analysis"."""
    from apex_tpu.telemetry.profiler.mfu import (device_peak_flops,
                                                 mfu as mfu_of)
    return mfu_of(flops, step_s, device_peak_flops())


def _err(leg, stage, error):
    """One structured, machine-readable error entry ({"leg", "stage",
    "error"})."""
    return {"leg": leg, "stage": stage, "error": str(error)}


def _resnet50_one_batch(jax, jnp, batch, size, steps):
    from apex_tpu import amp
    from apex_tpu.benchlib import chunked_train_bench
    from apex_tpu.models import resnet50
    from apex_tpu.optimizers import FusedSGD

    # space-to-depth stem: same function as the 7x7/s2
    # conv (tests pin numerical equality) but the MXU sees 12 input
    # channels instead of 3 — the MLPerf TPU ResNet transform.  MFU
    # caveat: cost analysis counts the folded kernel's 192 taps vs
    # the 7x7's 147 (structural zeros), reading ~1-2% high vs a
    # conv7x7 run at equal throughput; the 'stem' field records which
    # program the number belongs to
    model = resnet50(num_classes=1000, dtype=jnp.bfloat16,
                     stem_space_to_depth=True)
    rng = jax.random.key(0)
    x = jax.random.normal(rng, (batch, size, size, 3), jnp.bfloat16)
    labels = jax.random.randint(jax.random.key(1), (batch,), 0, 1000)

    variables = model.init(jax.random.key(2), x, train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    # amp O2: bf16 weights + f32 masters, static scale (bf16).  The
    # masters come from amp.initialize (cast from the ORIGINAL f32
    # init), not from re-upcasting the rounded bf16 params.
    params_bf16, amp_state = amp.initialize(params, opt_level="O2")
    masters0 = amp_state.master_params
    # Build the optimizer state from the amp masters directly
    # (master_weights=False: the functional path below threads masters
    # explicitly, and letting the ctor cast a second f32 master copy
    # would transiently double master memory).
    opt = FusedSGD(masters0, lr=0.1, momentum=0.9, weight_decay=1e-4,
                   master_weights=False)

    def train_step(params, masters, opt_state, batch_stats, step, x, y):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"])
            onehot = jax.nn.one_hot(y, 1000, dtype=jnp.float32)
            loss = -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * onehot, axis=-1))
            return loss, updates["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_masters, opt_state = opt.functional_step(
            masters, opt_state, grads, step)
        new_params = amp.master_params_to_model_params(params, new_masters)
        return new_params, new_masters, opt_state, new_stats, loss

    r = chunked_train_bench(
        lambda c, step, x, y: train_step(c[0], c[1], c[2], c[3],
                                         step, x, y),
        (params_bf16, masters0, opt.opt_state, batch_stats,
         jnp.float32(0)),
        (x, labels), steps=steps, chunk=10, want_flops=True)
    float(r["state"][4])  # loss: forces the donated-buffer chain
    return {"imgs_per_sec": batch / r["step_ms"] * 1e3,
            "batch": batch, "image_size": size,
            "step_ms": r["step_ms"],
            "steps_per_dispatch": r["steps_per_dispatch"],
            "stem": "space_to_depth",
            # gradient-HANDLING provenance: "flat" = grads packed once
            # into dtype buckets and stepped by the flat kernels.  These
            # bf16/static-scale legs have no unscale/clip work, so the
            # fused unscale+norm+clip epilogue is NOT part of this
            # number — bench_amp_pipeline measures that separately
            # (amp_step_{flat,per_leaf}_ms extras).
            "amp_pipeline": "flat" if opt.fuse_buckets else "per_leaf",
            # tracked legs run with the metric ring OFF so the tracked
            # number stays comparable across rounds; the ring's cost is
            # quantified separately (telemetry_on/off extras)
            "telemetry": "off",
            "mfu": _mfu(r["flops_per_step"], r["step_ms"] / 1e3)}


def bench_resnet50_amp_o2(jax, jnp):
    """North-star metric.  Batch is swept (the reference target is
    imgs/sec/chip at the submitter's batch of choice) and the best
    throughput is reported, every candidate recorded in extra; a
    candidate that fails (e.g. OOM at b256) fails the leg."""
    best, sweep = None, {}
    for batch in (128, 256):
        r = _resnet50_one_batch(jax, jnp, batch, 224, 50)
        sweep[f"b{batch}_imgs_per_sec"] = round(r["imgs_per_sec"], 2)
        if best is None or r["imgs_per_sec"] > best["imgs_per_sec"]:
            best = r
    best["batch_sweep"] = sweep
    return best


def _amp_lamb_train_bench(jax, jnp, model_loss, params0, batch, *,
                          steps, chunk, want_flops):
    """Shared amp-O2 + FusedLAMB benching scaffold: every BERT leg
    (tracked b8, b32 extra, packed-varlen extra) measures under ONE
    contract — O2 masters from amp.initialize, functional LAMB step,
    master→model copy-back, chunked dispatch."""
    from apex_tpu import amp
    from apex_tpu.benchlib import chunked_train_bench
    from apex_tpu.optimizers import FusedLAMB

    params_bf16, amp_state = amp.initialize(params0, opt_level="O2")
    masters0 = amp_state.master_params
    opt = FusedLAMB(masters0, lr=1e-3, weight_decay=0.01,
                    master_weights=False)

    def train_step(params, masters, opt_state, step, *b):
        loss, grads = jax.value_and_grad(model_loss)(params, *b)
        new_masters, opt_state = opt.functional_step(
            masters, opt_state, grads, step)
        new_params = amp.master_params_to_model_params(params, new_masters)
        return new_params, new_masters, opt_state, loss

    r = chunked_train_bench(
        lambda c, step, *b: train_step(c[0], c[1], c[2], step, *b),
        (params_bf16, masters0, opt.opt_state, jnp.float32(0)),
        batch, steps=steps, chunk=chunk, want_flops=want_flops)
    float(r["state"][3])  # loss: forces the donated-buffer chain
    # gradient-handling provenance only (see _resnet50_one_batch): the
    # fused unscale/clip epilogue is benched by bench_amp_pipeline
    r["amp_pipeline"] = "flat" if opt.fuse_buckets else "per_leaf"
    r["telemetry"] = "off"     # ring-on cost: telemetry_on/off extras
    return r


def _bert_lamb_one_batch(jax, jnp, batch, seq, steps, config):
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    from apex_tpu.models.bert import bert_large

    model = bert_large(dtype=jnp.bfloat16)

    vocab = model.vocab_size
    tokens = jax.random.randint(jax.random.key(0), (batch, seq), 0, vocab)
    mlm_labels = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                    vocab)
    variables = model.init(jax.random.key(2), tokens)

    def loss_fn(p, tokens, labels):
        logits = model.mlm_logits({"params": p}, tokens)  # (s,b,V) f32
        flat = logits.transpose(1, 0, 2).reshape(-1, vocab)
        losses = softmax_cross_entropy_loss(
            flat, labels.reshape(-1), smoothing=0.0, padding_idx=-1)
        return jnp.mean(losses)

    r = _amp_lamb_train_bench(
        jax, jnp, loss_fn, variables["params"], (tokens, mlm_labels),
        steps=steps, chunk=10, want_flops=True)
    return {"step_ms": r["step_ms"], "config": config,
            "batch": batch, "seq": seq,
            "steps_per_dispatch": r["steps_per_dispatch"],
            "amp_pipeline": r.get("amp_pipeline"),
            "telemetry": r.get("telemetry", "off"),
            "mfu": _mfu(r["flops_per_step"], r["step_ms"] / 1e3)}


def bench_bert_lamb(jax, jnp):
    """BERT-Large FusedLAMB step time (BASELINE tracked metric 2) at
    the fixed b8 s512 config (step-time numbers only compare at a
    fixed config).  The b32 throughput datapoint runs SEPARATELY in
    run_child, after this tracked metric has been flushed."""
    return _bert_lamb_one_batch(jax, jnp, 8, 512, 20,
                                "bert-large b8 s512")


def bench_bert_packed_varlen(jax, jnp, model=None, rows=32, seq=512,
                             steps=20, chunk=10):
    """Packed-varlen vs padded-dense BERT throughput on REAL tokens
    (VERDICT r4 item 6: packing + flash + LAMB).  A synthetic varlen
    corpus (lengths seq/8..seq) is (a) FFD-packed into (rows, seq)
    rows via data.pack_sequences — segment-masked flash attention,
    per-sequence positions — and (b) naively padded one sequence per
    row.  Both train LAMB steps; the reported unit is real (non-pad)
    tokens per second, the number padding wastes.  TPU extra at
    BERT-L defaults; the tiny-model override is CPU-CI's."""
    import numpy as np

    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    from apex_tpu.data import pack_sequences
    from apex_tpu.models.bert import bert_large

    if model is None:
        model = bert_large(dtype=jnp.bfloat16)
    vocab = model.vocab_size
    rng = np.random.default_rng(11)
    seqs, packed = [], None
    while True:                       # enough sequences to fill rows
        seqs += [rng.integers(1, vocab, size=int(n))
                 for n in rng.uniform(seq // 8, seq, size=16)]
        packed = pack_sequences(seqs, max_len=seq, pad_id=0)
        if packed["tokens"].shape[0] >= rows:
            break
    pk = {k: jnp.asarray(v[:rows]) for k, v in packed.items()}
    real_packed = int(np.sum(packed["segment_ids"][:rows] > 0))

    out = {}
    for mode in ("packed", "dense"):
        if mode == "packed":
            tokens = pk["tokens"]
            seg, pos = pk["segment_ids"], pk["positions"]
            labels = jnp.where(seg > 0, jnp.asarray(
                rng.integers(0, vocab, size=tokens.shape),
                jnp.int32), -1)
            kw = dict(segment_ids=seg, positions=pos)
            real = real_packed
        else:
            lens = np.array([len(s) for s in seqs[:rows]])
            tokens = np.zeros((rows, seq), np.int32)
            for i, s in enumerate(seqs[:rows]):
                tokens[i, :len(s)] = s
            mask = jnp.asarray(
                np.arange(seq)[None, :] < lens[:, None])
            tokens = jnp.asarray(tokens)
            labels = jnp.where(mask, jnp.asarray(
                rng.integers(0, vocab, size=(rows, seq)),
                jnp.int32), -1)
            kw = dict(attention_mask=mask)
            real = int(lens.sum())

        variables = model.init(jax.random.key(2), tokens)

        def loss_of(p, tokens, labels, kw=kw):
            logits = model.mlm_logits({"params": p}, tokens, **kw)
            flat = logits.transpose(1, 0, 2).reshape(-1, vocab)
            losses = softmax_cross_entropy_loss(
                flat, labels.reshape(-1), smoothing=0.0,
                padding_idx=-1)
            keep = (labels.reshape(-1) >= 0)
            return jnp.sum(losses) / jnp.maximum(jnp.sum(keep), 1)

        r = _amp_lamb_train_bench(
            jax, jnp, loss_of, variables["params"], (tokens, labels),
            steps=steps, chunk=chunk, want_flops=False)
        out[f"bert_varlen_{mode}_step_ms"] = round(r["step_ms"], 2)
        out[f"bert_varlen_{mode}_real_tokens_per_sec"] = round(
            real / r["step_ms"] * 1e3, 1)
    out["bert_varlen_packed_speedup"] = round(
        out["bert_varlen_packed_real_tokens_per_sec"]
        / out["bert_varlen_dense_real_tokens_per_sec"], 2)
    return out


def bench_flash_attention(jax, jnp):
    """Flash kernel vs unfused XLA oracle (done-criterion: kernel >=
    oracle at 2k; kernel handles 8k)."""
    from apex_tpu.benchlib import timeit as time_fn
    from apex_tpu.ops.attention import attention_ref, flash_attention

    out = {}
    # s=512 exercises the single-KV-block fast path; 2048 the generic
    # online kernel; 8192 the O(S)-memory story (oracle would need 48G)
    for s, run_oracle in ((512, True), (2048, True), (8192, False)):
        b, h, d = 4, 16, 128
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)

        def fwd_bwd(f):
            # all three grads returned so neither backward kernel is
            # dead-code-eliminated
            def g(q, k, v):
                return jax.grad(
                    lambda q, k, v: jnp.sum(
                        f(q, k, v).astype(jnp.float32)),
                    argnums=(0, 1, 2))(q, k, v)
            return jax.jit(g)

        # adaptive: the s=512 bodies are sub-ms — a single dispatch
        # would be mostly launch overhead
        out[f"flash_{s}_fwdbwd_ms"] = round(time_fn(
            fwd_bwd(lambda q, k, v: flash_attention(q, k, v, True)),
            q, k, v, adaptive=True), 2)
        if run_oracle:
            out[f"oracle_{s}_fwdbwd_ms"] = round(time_fn(
                fwd_bwd(lambda q, k, v: attention_ref(q, k, v,
                                                      causal=True)),
                q, k, v, adaptive=True), 2)
    return out


def bench_overlap_schedule(jax, jnp, steps=10, layers=16, hidden=256):
    """Interleaved vs trailing grad-reduce schedule, measured (ISSUE
    10): the SAME chunked-bucket flat-AMP DDP step under shard_map
    over every local device, once with the reduce-in-backward seam
    (``interleave=True``) and once trailing, each under a short
    observatory capture — ``overlap_pct`` (the hidden-collective
    fraction from telemetry/profiler/attribution.py) is the number the
    static ``amp.interleaved_flat_step`` spec promises and this leg
    verifies on hardware."""
    import shutil
    import tempfile

    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu import amp, comm
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.bucketing_bench import many_leaf_params
    from apex_tpu.telemetry.profiler import build_report, capture

    devs = jax.devices()
    mesh = Mesh(np.array(devs), (comm.AXIS_DATA,))
    params = many_leaf_params(jax, jnp, layers, hidden)
    n_bytes = sum(int(l.size) * l.dtype.itemsize
                  for l in jax.tree_util.tree_leaves(params))
    scaler = amp.LossScaleState.create(2.0 ** 12)
    x = jax.random.normal(jax.random.key(1),
                          (8 * len(devs), hidden), jnp.float32)

    def loss_fn(p, x):
        h = x
        for k in sorted(p):
            h = jnp.tanh(h @ p[k]["w"] + p[k]["b"]) \
                * p[k]["scale"] + p[k]["shift"]
        return jnp.mean(h ** 2)

    out = {"overlap_devices": len(devs)}
    for label, interleave in (("interleaved", True), ("trailing", False)):
        # ~4 chunks: multiple per-bucket collectives to hide
        opt = FusedAdam(params, lr=1e-3,
                        max_bucket_bytes=max(1, n_bytes // 4))
        pipe = amp.FlatGradPipeline(
            optimizer=opt, max_grad_norm=1.0,
            axis_name=comm.AXIS_DATA, interleave=interleave)
        hypers = {k: jnp.asarray(v, jnp.float32)
                  for k, v in opt.hypers.items()
                  if isinstance(v, float)}

        def step_fn(work, opt_state, x, step):
            ptree = pipe.plan.unpack(work)
            loss, flat = pipe.scaled_value_and_grad(
                loss_fn, scaler, ptree, x)
            new_w, _, new_s = opt._full_step_flat(
                work, None, opt_state, flat.bufs, step, 1.0,
                hypers, flat.found_inf)
            return loss, new_w, new_s

        # interleaved vs trailing are two programs by design
        # apexlint: disable-next=APX302
        jstep = jax.jit(comm.shard_map(
            step_fn, mesh,
            in_specs=(P(), P(), P(comm.AXIS_DATA), P()),
            out_specs=P()), donate_argnums=(1,))
        work, state = opt._param_bufs, opt.opt_state
        # warmup OUTSIDE the window (capture.py's rule)
        loss, work, state = jstep(work, state, x, jnp.int32(1))
        jax.block_until_ready(loss)
        tdir = tempfile.mkdtemp(prefix="apex_tpu_overlap_")
        try:
            with capture.trace(tdir):
                for i in range(steps):
                    loss, work, state = jstep(work, state, x,
                                              jnp.int32(2 + i))
                jax.block_until_ready(loss)
            rep = build_report(tdir, steps=steps)
            if not rep.get("error"):
                out[f"overlap_{label}_pct"] = rep.get("overlap_pct")
                out[f"overlap_{label}_step_ms"] = (
                    rep["breakdown"].get("step_ms"))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        out["overlap_buckets"] = len(opt._plan.buckets)
    return out


NORTH_STAR_METRIC = "resnet50_amp_o2_fused_sgd_train_throughput"


def _empty_result():
    return {
        "metric": NORTH_STAR_METRIC,
        "value": 0.0,
        "unit": "imgs/sec/chip",
        "vs_baseline": 0.0,
        "backend": "tpu",
        "extra": {},
        "errors": [],
    }


def _dump(out):
    """One JSON line, with an empty errors list elided."""
    return json.dumps({k: v for k, v in out.items()
                       if k != "errors" or v})


def _stamp_measured_at(out):
    """Capture timestamp on the final bench line (perf_gate's
    auto-gating compares it against the budget's ``stamped_at``)."""
    out.setdefault("measured_at", time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    return out


def _leg(out, leg, fn):
    """Run one bench leg.  A failure is recorded structurally under
    ``errors`` — which makes the run exit non-zero — and the remaining
    legs still measure; the line so far is flushed either way, so a
    later hang + watchdog kill loses nothing already measured."""
    try:
        fn()
    except Exception:
        out["errors"].append(_err(
            leg, "run",
            traceback.format_exc(limit=3).replace("\n", " | ")))
    print(_dump(out), flush=True)


def run_child():
    """Bench body: every leg on the TPU, one JSON line flushed per leg.
    Returns the process exit code: 2 without a TPU backend (nothing is
    measured), 1 when any leg failed, else 0."""
    out = _empty_result()
    pinned = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    if pinned not in ("", "tpu"):
        # said before jax starts: the TPU scheduler flags armed below
        # are fatal to any other backend's flag parser
        print(f"bench.py: JAX_PLATFORMS={pinned!r} — the benchmark "
              "measures on a TPU or not at all", file=sys.stderr)
        return 2
    # arm the latency-hiding scheduler BEFORE the first backend use and
    # record what was set: the measured overlap fractions below must
    # name the schedule they ran under
    from apex_tpu.platform import (enable_compilation_cache,
                                   enable_latency_hiding_scheduler)
    out["extra"]["lhs_flags"] = enable_latency_hiding_scheduler(
        target="tpu")
    import jax
    import jax.numpy as jnp
    enable_compilation_cache()
    dev = jax.devices()[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"bench.py: backend is {dev.platform!r}, not 'tpu' — "
              "nothing to measure (tests and examples take --cpu; "
              "the benchmark does not)", file=sys.stderr)
        return 2

    def dispatch_overhead():
        from apex_tpu.benchlib import dispatch_overhead_ms
        out["extra"]["dispatch_overhead_ms"] = round(
            dispatch_overhead_ms(), 3)

    def resnet50():
        r = bench_resnet50_amp_o2(jax, jnp)
        out["value"] = round(r["imgs_per_sec"], 2)
        out["vs_baseline"] = round(r["imgs_per_sec"] / A100_IMGS_PER_SEC,
                                   4)
        out["extra"]["resnet50_step_ms"] = round(r["step_ms"], 2)
        out["extra"]["resnet50_batch"] = r["batch"]
        out["extra"]["resnet50_image_size"] = r["image_size"]
        out["extra"]["resnet50_steps_per_dispatch"] = r.get(
            "steps_per_dispatch")
        out["extra"]["resnet50_batch_sweep"] = r.get("batch_sweep")
        out["extra"]["resnet50_stem"] = r.get("stem")
        out["extra"]["resnet50_amp_pipeline"] = r.get("amp_pipeline")
        out["extra"]["resnet50_telemetry"] = r.get("telemetry")
        if r.get("mfu") is not None:
            out["extra"]["resnet50_mfu"] = r["mfu"]
            # provenance: flops from the compiled step's cost analysis
            # over the profiler.mfu chip table (docs/perf.md)
            out["extra"]["resnet50_mfu_source"] = "cost_analysis"

    def bert_lamb():
        b = bench_bert_lamb(jax, jnp)
        out["extra"]["bert_large_fused_lamb_step_ms"] = round(
            b["step_ms"], 2)
        out["extra"]["bert_config"] = b["config"]
        out["extra"]["bert_amp_pipeline"] = b.get("amp_pipeline")
        out["extra"]["bert_telemetry"] = b.get("telemetry")
        if b.get("mfu") is not None:
            out["extra"]["bert_mfu"] = b["mfu"]
            out["extra"]["bert_mfu_source"] = "cost_analysis"

    def optimizer_bucketing():
        # per-leaf vs bucketed fused-optimizer step on a many-leaf
        # pytree (amortized on-device timing)
        from apex_tpu.optimizers.bucketing_bench import \
            bench_optimizer_bucketing
        r = bench_optimizer_bucketing()
        out["extra"].update({k: v for k, v in r.items()
                             if k != "optim_buckets"})

    def amp_pipeline():
        # full AMP gradient epilogue, flat pipeline vs per-leaf amp
        # ops on the same many-leaf tree
        from apex_tpu.optimizers.bucketing_bench import \
            bench_amp_pipeline
        out["extra"].update(bench_amp_pipeline())

    def telemetry_overhead():
        # metric ring on vs off over the identical flat-AMP step
        from apex_tpu.telemetry.bench import bench_telemetry_overhead
        out["extra"].update(bench_telemetry_overhead())

    def grad_accum():
        # per-leaf vs flat accumulation at N_micro in {1,4,8}
        from apex_tpu.optimizers.bucketing_bench import \
            bench_grad_accum
        out["extra"].update(bench_grad_accum())

    def fp8_matmul():
        from apex_tpu.amp.fp8_bench import bench_fp8_matmul
        out["extra"].update(bench_fp8_matmul())

    def serving():
        from apex_tpu.serving.bench import bench_serving
        out["extra"].update(bench_serving(
            n_requests=16, n_layers=4, hidden=256, n_heads=8,
            max_slots=8, page_size=16, pages_per_slot=8,
            window=16, max_new_tokens=64))

    def bert_b32():
        r32 = _bert_lamb_one_batch(jax, jnp, 32, 512, 20,
                                   "bert-large b32 s512")
        out["extra"]["bert_b32_step_ms"] = round(r32["step_ms"], 2)
        out["extra"]["bert_b32_tokens_per_sec"] = round(
            32 * 512 / r32["step_ms"] * 1e3, 1)
        if r32.get("mfu") is not None:
            out["extra"]["bert_b32_mfu"] = r32["mfu"]

    def resnet50_profile():
        # observatory capture: a short device-only trace of the
        # north-star step, attributed into compute / collective /
        # transfer / idle.  The trace is scratch output (read, then
        # removed); tools/profile_step.py is the keep-the-trace path.
        import shutil
        import tempfile

        from apex_tpu.telemetry.profiler import build_report, capture
        tdir = tempfile.mkdtemp(prefix="apex_tpu_bench_trace_")
        try:
            # warmup OUTSIDE the window (capture.py's rule): this
            # identical un-traced leg compiles the step, so the traced
            # call below does not record the compile as idle
            _resnet50_one_batch(jax, jnp, 128, 224, 10)
            with capture.trace(tdir):
                _resnet50_one_batch(jax, jnp, 128, 224, 10)
            # chunked_train_bench dispatches a warmup chunk (10 steps)
            # before the timed chunk INSIDE this window, so the device
            # timeline holds 20 executed steps (plus one init pass)
            rep = build_report(tdir, steps=20)
            if rep.get("error"):
                raise RuntimeError(rep["error"])
            bd = rep["breakdown"]
            out["extra"]["resnet50_overlap_pct"] = rep.get("overlap_pct")
            out["extra"]["resnet50_breakdown"] = {
                k: bd.get(k)
                for k in ("compute_ms", "collective_ms",
                          "transfer_ms", "idle_ms")}
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    # tracked metrics first (each flushed as it lands), extras after;
    # flash runs BEFORE the memory-hungry b32 leg
    for leg, fn in (
            ("dispatch_overhead", dispatch_overhead),
            ("resnet50", resnet50),
            ("bert_lamb", bert_lamb),
            ("flash_attention",
             lambda: out["extra"].update(bench_flash_attention(jax, jnp))),
            ("optim_bucketing", optimizer_bucketing),
            ("amp_pipeline", amp_pipeline),
            ("telemetry_overhead", telemetry_overhead),
            ("grad_accum", grad_accum),
            ("overlap_schedule",
             lambda: out["extra"].update(bench_overlap_schedule(jax, jnp))),
            ("fp8_matmul", fp8_matmul),
            ("serving", serving),
            ("bert_b32", bert_b32),
            ("bert_varlen",
             lambda: out["extra"].update(
                 bench_bert_packed_varlen(jax, jnp))),
            ("resnet50_profile", resnet50_profile)):
        _leg(out, leg, fn)

    print(_dump(_stamp_measured_at(out)), flush=True)
    return 1 if out["errors"] else 0


def _last_json_line(stdout):
    """Last parseable JSON object line in a child's stdout, or None."""
    for line in reversed((stdout or "").strip().splitlines()):
        try:
            out = json.loads(line)
            if isinstance(out, dict) and "metric" in out:
                return out
        except ValueError:
            continue
    return None


CHILD_TIMEOUT_S = 3000.0


def main():
    """Orchestrator: never imports jax, starts exactly ONE child, prints
    the child's last JSON line and exits with the child's code (124 if
    the watchdog had to kill it)."""
    if sys.argv[1:] == ["--child"]:
        return run_child()
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            timeout=CHILD_TIMEOUT_S, stdout=subprocess.PIPE, text=True)
        stdout, rc = r.stdout, r.returncode
    except subprocess.TimeoutExpired as e:
        stdout = (e.stdout.decode(errors="replace")
                  if isinstance(e.stdout, bytes) else e.stdout)
        rc = 124
    out = _last_json_line(stdout)
    if out is not None:
        if rc:
            out.setdefault("errors", []).append(
                _err("child", "exit", f"rc={rc}"))
        print(json.dumps(out))
    return rc or (0 if out is not None else 1)


if __name__ == "__main__":
    sys.exit(main())
