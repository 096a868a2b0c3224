"""apex_tpu.telemetry: the zero-host-sync contract, end to end.

Covers the ring (write/flush round trip under jit, donation), the
structural no-per-step-host-transfer guarantee (jaxpr walk of an
instrumented flat-AMP train step), JSONL schema stability, span
nesting/exception safety, the retrace counter (monitoring hook + the
forced-retrace wrapper), rank-0-only emission under a faked
multi-process config, and the pyprof satellite fixes (thread-local
nvtx stack, prof --json + newest-by-mtime)."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, telemetry
from apex_tpu.optimizers import FusedAdam
from apex_tpu.telemetry import _tape
from apex_tpu.telemetry.cli import main as telemetry_cli, summarize
from apex_tpu.telemetry.ring import MetricRing
from apex_tpu.telemetry.session import JSONL_NAME

tree_map = jax.tree_util.tree_map


# ---------------------------------------------------------------------------
# MetricRing
# ---------------------------------------------------------------------------

def test_ring_record_flush_round_trip_under_jit():
    ring = MetricRing(("loss", "grad_norm"), window=4)
    buf = ring.init()
    rec = jax.jit(ring.record)
    for i in range(6):          # wraps: steps 2..5 survive, 0..1 evicted
        buf = rec(buf, {"loss": jnp.float32(i * 0.5),
                        "grad_norm": jnp.float32(i)}, i)
    out = ring.decode(jax.device_get(buf))
    assert [r["step"] for r in out] == [2, 3, 4, 5]
    assert [r["loss"] for r in out] == [1.0, 1.5, 2.0, 2.5]
    assert [r["grad_norm"] for r in out] == [2.0, 3.0, 4.0, 5.0]
    # decode is incremental: after_step skips already-flushed rows
    assert [r["step"] for r in ring.decode(jax.device_get(buf),
                                           after_step=4)] == [5]


def test_ring_partial_writes_compose_and_unknown_names_ignored():
    ring = MetricRing(("a", "b"), window=2)
    buf = ring.init()
    buf = ring.record(buf, {"a": 1.0, "other": 9.0}, 0)
    buf = ring.record(buf, {"b": 2.0}, 0)     # same step, second producer
    (r,) = ring.decode(jax.device_get(buf))
    assert r == {"step": 0, "a": 1.0, "b": 2.0}


def test_ring_nan_metric_decodes_to_none_with_stable_schema():
    ring = MetricRing(("a", "b"), window=2)
    buf = ring.record(ring.init(), {"a": jnp.float32(jnp.nan)}, 3)
    (r,) = ring.decode(jax.device_get(buf))
    assert set(r) == {"step", "a", "b"}       # full key set always
    assert r["a"] is None and r["b"] is None


def test_ring_wrap_clears_evicted_rows_and_midstep_flush_is_safe(
        tmp_path):
    """Two producers per step + a wrapping ring: no stale metric may
    survive row eviction, and the window flush must never cut off a
    step that is still accumulating (both were real bugs)."""
    ring = MetricRing(("loss", "gn"), window=3)
    buf = ring.init()
    for s in range(5):
        buf = ring.record(buf, {"loss": float(s)}, s)
        if s != 1:                        # step 1's producer-2 missing
            buf = ring.record(buf, {"gn": 10.0 * s}, s)
    out = ring.decode(jax.device_get(buf))
    assert [r["step"] for r in out] == [2, 3, 4]
    assert [r["gn"] for r in out] == [20.0, 30.0, 40.0]
    # step 1's gn=10.0 must not reappear on the row step 4 reclaimed
    assert all(r["loss"] == float(r["step"]) for r in out)

    # session: auto-flush fires mid-step without losing producer 2
    d = str(tmp_path / "run")
    with telemetry.Telemetry(d, metrics=("loss", "gn"), window=3,
                             retrace=False) as tel:
        for s in range(5):
            tel.record({"loss": float(s)}, s)
            tel.record({"gn": 10.0 * s}, s)
    lines = [json.loads(l) for l in
             open(os.path.join(d, JSONL_NAME)) if l.strip()]
    steps = {l["step"]: l for l in lines
             if l.get("kind", "step") == "step"}
    assert sorted(steps) == [0, 1, 2, 3, 4]
    for s, r in steps.items():
        assert r["loss"] == float(s), r
        assert r["gn"] == 10.0 * s, r


def test_ring_step_exact_beyond_f32_integers():
    """Step ids stay exact past 2^24 (lo/hi split cells): neighboring
    huge steps must not merge into one row."""
    ring = MetricRing(("a",), window=4)
    buf = ring.init()
    s0 = (1 << 24)                     # 16_777_216: f32 folds s0+1 into s0
    for i in range(3):
        buf = ring.record(buf, {"a": float(i)}, s0 + i)
    out = ring.decode(jax.device_get(buf))
    assert [r["step"] for r in out] == [s0, s0 + 1, s0 + 2]
    assert [r["a"] for r in out] == [0.0, 1.0, 2.0]


def test_tape_stack_is_thread_local():
    """A background thread's producer emissions must not land on the
    main thread's step tape (same hazard class as the nvtx stack)."""
    _tape.push()
    done = threading.Event()

    def background():
        _tape.emit("bg_metric", 1.0)          # no tape in THIS thread
        _tape.push()
        _tape.emit("bg_own", 2.0)
        assert float(_tape.pop().values["bg_own"]) == 2.0
        done.set()

    t = threading.Thread(target=background)
    t.start()
    t.join()
    assert done.is_set()
    tape = _tape.pop()
    assert "bg_metric" not in tape.values
    assert "bg_own" not in tape.values


def test_ring_rejects_bad_config():
    with pytest.raises(ValueError, match="window"):
        MetricRing(("a",), window=0)
    with pytest.raises(ValueError, match="reserved"):
        MetricRing(("step", "a"))
    with pytest.raises(ValueError, match="at least one"):
        MetricRing(())


def test_session_commit_donates_ring_buffer():
    tel = telemetry.Telemetry(run_dir=None, metrics=("loss",), window=8,
                              retrace=False)
    b0 = tel.buf
    tel.record({"loss": jnp.float32(1.0)}, 0)
    assert b0.is_deleted()      # donated: never two live ring copies
    tel.close()


# ---------------------------------------------------------------------------
# structural guarantee: telemetry adds ZERO per-step host transfers —
# now owned by the shared apexverify spec `telemetry.instrumented_step`
# (apex_tpu/lint/semantic/specs.py traces the same instrumented
# flat-AMP step this test used to build by hand)
# ---------------------------------------------------------------------------

def test_instrumented_step_jaxpr_has_no_host_callbacks():
    """A telemetry-on flat-AMP train step contains no callback/transfer
    primitives — the ring writes are plain dynamic_update_slices; the
    only device_get in the subsystem is the window flush, which lives
    OUTSIDE the step program entirely.  Asserted by the registered
    invariant spec (the same walker the `--semantic` CI gate runs)."""
    from apex_tpu.lint import semantic

    res = semantic.verify_spec(
        semantic.get_spec("telemetry.instrumented_step"))
    assert res.ok, res.failures
    # assertion strength preserved: the spec checked both the zero-
    # transfer invariant and the presence of the ring write (the
    # VALUES are asserted by
    # test_instrument_records_producer_metrics_end_to_end)
    assert {"no_host_transfer", "dus_min"} <= set(res.checked)


def test_instrument_records_producer_metrics_end_to_end():
    params = {"w": jnp.ones((8, 8)) * 0.1, "b": jnp.zeros((8,))}
    x = jax.random.normal(jax.random.key(1), (4, 8))
    scaler = amp.LossScaleState.create()
    opt = FusedAdam(params, lr=1e-3)
    pipe = amp.FlatGradPipeline(optimizer=opt, max_grad_norm=1.0)
    tel = telemetry.Telemetry(run_dir=None, window=4, retrace=False)

    def loss_fn(p, x):
        return jnp.mean((x @ p["w"] + p["b"]) ** 2)

    def train_step(work_bufs, opt_state, scaler, x, step):
        ptree = opt._plan.unpack_model(work_bufs)
        loss, flat = pipe.scaled_value_and_grad(loss_fn, scaler, ptree, x)
        new_bufs, _, new_state = opt._full_step_flat(
            work_bufs, None, opt_state, flat.bufs, step, 1.0,
            {}, flat.found_inf)
        return loss, new_bufs, new_state

    step_fn = jax.jit(tel.instrument(train_step), donate_argnums=(0,))
    bufs, state = opt._param_bufs, opt.opt_state
    for i in range(3):
        tbuf, (loss, bufs, state) = step_fn(
            tel.buf, i, bufs, state, scaler, x, jnp.int32(i + 1))
        tel.update(tbuf, i)
    recs = tel.flush()
    assert [r["step"] for r in recs] == [0, 1, 2]
    for r in recs:
        assert r["loss"] is not None
        assert r["amp/grad_norm"] is not None and r["amp/grad_norm"] > 0
        assert r["amp/clip_coef"] is not None
        assert r["amp/found_inf"] == 0.0
        assert r["amp/loss_scale"] == float(scaler.loss_scale)
        assert r["optim/skipped"] == 0.0
    tel.close()


def test_functional_step_applies_found_inf_skip_and_emits():
    """The public embed-in-your-jit entry point honors the overflow
    flag (docs wiring table: optim/skipped) — both with an explicit
    found_inf and with a FlatGrads bundle."""
    params = {"w": jnp.ones((8, 8)) * 0.5, "b": jnp.zeros((8,))}
    opt = FusedAdam(params, lr=1e-2)
    grads = tree_map(lambda p: p * 1e-2 + 1e-3, params)
    bundle = amp.FlatGradPipeline(optimizer=opt).unscale_and_norm(
        opt._plan.pack_grads(grads))

    _tape.push()
    new_p, new_s = opt.functional_step(params, opt.opt_state, grads,
                                       jnp.int32(1),
                                       found_inf=jnp.int32(1))
    t = _tape.pop()
    assert float(t.values["optim/skipped"]) == 1.0
    for a, b in zip(jax.tree_util.tree_leaves(new_p),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # FlatGrads bundle: found_inf/clip ride along; finite -> steps
    new_p2, _ = opt.functional_step(params, opt.opt_state, bundle,
                                    jnp.int32(1))
    assert not np.allclose(np.asarray(new_p2["w"]),
                           np.asarray(params["w"]))
    # per-leaf state rejects the bundle loudly (step() parity)
    opt_pl = FusedAdam(params, lr=1e-2, fuse_buckets=False)
    with pytest.raises(ValueError, match="FlatGrads"):
        opt_pl.functional_step(params, opt_pl.opt_state, bundle,
                               jnp.int32(1))


def test_tape_reduce_combines():
    _tape.push()
    _tape.emit("m", 3.0, reduce="max")
    _tape.emit("m", 5.0, reduce="max")
    _tape.emit("s", 1.0, reduce="sum")
    _tape.emit("s", 2.0, reduce="sum")
    _tape.emit("n", 3.0, reduce="rss")
    _tape.emit("n", 4.0, reduce="rss")
    t = _tape.pop()
    assert float(t.values["m"]) == 5.0
    assert float(t.values["s"]) == 3.0
    assert float(t.values["n"]) == pytest.approx(5.0)
    # no active tape: emit is a no-op, never an error
    _tape.emit("m", 1.0)


def test_eager_tape_drops_foreign_tracers():
    """A tape opened eagerly must not capture tracers from a nested jit
    (they would escape that trace); concrete values still land."""
    _tape.push()

    @jax.jit
    def inner(x):
        _tape.emit("inner_metric", x)
        return x + 1

    inner(jnp.float32(1.0))
    _tape.emit("outer_metric", jnp.float32(2.0))
    t = _tape.pop()
    assert "inner_metric" not in t.values
    assert float(t.values["outer_metric"]) == 2.0


def test_traced_tape_drops_nested_jit_tracers():
    """An instrumented step calling a separately-jitted helper that
    emits must not capture the helper's tracers (they belong to the
    inner trace) — the metric is absent, never an escape crash."""
    ring = MetricRing(("own", "foreign"), window=2)

    @jax.jit
    def helper(x):
        _tape.emit("foreign", x * 2)
        return x * 2

    def step(x):
        _tape.emit("own", x + 1)
        return helper(x)

    def wrapped(buf, step_i, x):
        tape = _tape.push()
        try:
            out = step(x)
        finally:
            _tape.pop()
        return ring.record(buf, tape.values, step_i), out

    buf, _ = jax.jit(wrapped)(ring.init(), 0, jnp.float32(3.0))
    (rec,) = ring.decode(jax.device_get(buf))
    assert rec["own"] == 4.0
    assert rec["foreign"] is None


def test_flush_cadence_counts_records_not_step_numbers(tmp_path):
    """Recording every k-th step (metrics cadence != step cadence) must
    still flush before the ring wraps — nothing is silently lost."""
    d = str(tmp_path / "sparse")
    with telemetry.Telemetry(d, metrics=("loss",), window=4,
                             retrace=False) as tel:
        for step in range(0, 100, 10):        # 10 records, window 4
            tel.record({"loss": float(step)}, step)
    lines = [json.loads(l) for l in
             open(os.path.join(d, JSONL_NAME)) if l.strip()]
    steps = [l["step"] for l in lines
             if l.get("kind", "step") == "step" and "step" in l]
    assert steps == list(range(0, 100, 10))   # all 10 survived


# ---------------------------------------------------------------------------
# emitters / JSONL schema / rank gating
# ---------------------------------------------------------------------------

def test_jsonl_schema_stability(tmp_path):
    d = str(tmp_path / "run")
    with telemetry.Telemetry(d, metrics=("loss", "amp/grad_norm"),
                             window=4, retrace=False) as tel:
        for i in range(5):
            tel.record({"loss": float(i)} if i % 2 == 0
                       else {"loss": float(i),
                             "amp/grad_norm": 0.5}, i)
    lines = [json.loads(l) for l in
             open(os.path.join(d, JSONL_NAME)) if l.strip()]
    assert lines[0]["kind"] == "schema"
    assert lines[0]["metrics"] == ["loss", "amp/grad_norm"]
    steps = [l for l in lines if l.get("kind", "step") == "step"
             or ("step" in l and "kind" not in l)]
    # every record carries the full schema key set, missing -> null
    for r in steps:
        assert set(r) == {"step", "loss", "amp/grad_norm"}
    assert steps[0]["amp/grad_norm"] is None      # even steps omit it
    assert steps[1]["amp/grad_norm"] == 0.5
    # CSV twin exists with matching header
    with open(os.path.join(d, "scalars.csv")) as f:
        assert f.readline().strip() == "step,loss,amp/grad_norm"


def test_console_logger_rate_limited(capsys):
    import io
    out = io.StringIO()
    lg = telemetry.StepLogger(interval_s=3600.0, stream=out,
                              metrics=("loss",))
    lg.emit([{"step": 0, "loss": 1.0}])
    lg.emit([{"step": 1, "loss": 2.0}])       # inside the interval
    assert out.getvalue().count("telemetry:") == 1
    lg2 = telemetry.StepLogger(interval_s=0.0, stream=out,
                               metrics=("loss",))
    lg2.emit([{"step": 2, "loss": 3.0}])
    lg2.emit([{"step": 3, "loss": 4.0}])
    assert out.getvalue().count("telemetry:") == 3


def test_rank0_only_emission_under_faked_multiprocess(tmp_path,
                                                     monkeypatch):
    d = str(tmp_path / "rank1")
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    tel = telemetry.Telemetry(d, metrics=("loss",), window=2,
                              retrace=False)
    tel.record({"loss": 1.0}, 0)
    tel.record({"loss": 2.0}, 1)              # window boundary
    assert tel.flush() == []                  # non-writer: no fetch
    tel.close()
    assert not os.path.exists(os.path.join(d, JSONL_NAME))
    # rank 0 writes (rank0_only respected, not inverted)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    d0 = str(tmp_path / "rank0")
    with telemetry.Telemetry(d0, metrics=("loss",), window=2,
                             retrace=False) as tel0:
        tel0.record({"loss": 1.0}, 0)
    assert os.path.exists(os.path.join(d0, JSONL_NAME))


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_exception_unwind():
    tel = telemetry.Telemetry(run_dir=None, metrics=("loss",),
                              retrace=False)
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            time.sleep(0.01)
    with pytest.raises(RuntimeError):
        with telemetry.span("raises"):
            raise RuntimeError("boom")
    recs = {r["name"]: r for r in tel.spans.records()}
    assert recs["inner"]["count"] == 1
    assert recs["outer"]["total_ms"] >= recs["inner"]["total_ms"] >= 10.0
    assert recs["raises"]["count"] == 1       # recorded despite the raise
    tel.close()
    # after close the sink is gone: spans no longer accumulate
    with telemetry.span("after"):
        pass
    assert "after" not in {r["name"] for r in tel.spans.records()}


def test_checkpoint_manager_reports_spans(tmp_path):
    from apex_tpu.resilience import CheckpointManager
    tel = telemetry.Telemetry(run_dir=None, metrics=("loss",),
                              retrace=False)
    params = {"w": jnp.ones((4,))}
    with CheckpointManager(str(tmp_path), keep=2, every=1) as mgr:
        mgr.maybe_save(0, params)
        mgr.wait()
        assert mgr.restore_latest(params) is not None
    names = {r["name"] for r in tel.spans.records()}
    assert {"checkpoint/save", "checkpoint/restore"} <= names
    tel.close()


def test_checkpoint_counters_flow_to_jsonl_and_summarize(tmp_path,
                                                         capsys):
    """ckpt/save_ms, ckpt/bytes_written, ckpt/blocked_ms and
    ckpt/restore_step ride the session flush as counter records and
    render in the summarize counter table (ISSUE 6 satellite)."""
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import CheckpointManager

    d = str(tmp_path / "run")
    ckdir = str(tmp_path / "ckpts")
    with telemetry.Telemetry(d, window=4) as tel:
        params = {"w": jnp.ones((32,))}
        opt = FusedAdam(params, lr=0.1)
        g = {"w": jnp.full((32,), 0.01)}
        with CheckpointManager(ckdir, keep=2, every=1) as mgr:
            for step in range(1, 4):
                opt.step(g)
                tel.record({"loss": 1.0 / step}, step)
                mgr.maybe_save(step, optimizer=opt)
            mgr.wait()
            assert mgr.restore_latest({"w": jnp.zeros((32,))},
                                      opt) is not None
        recs = {r["name"]: r for r in tel.counters.records()}
        assert recs["ckpt/save_ms"]["count"] == 3
        assert recs["ckpt/bytes_written"]["total"] > 0
        assert recs["ckpt/restore_step"]["last"] == 3.0
    # counter records landed in the jsonl...
    with open(os.path.join(d, "telemetry.jsonl")) as f:
        kinds = [json.loads(l).get("kind") for l in f if l.strip()]
    assert "counter" in kinds
    # ...and summarize renders them next to the span tables
    assert telemetry_cli(["summarize", d]) == 0
    out = capsys.readouterr().out
    assert "counters (cumulative):" in out
    assert "ckpt/save_ms" in out and "ckpt/bytes_written" in out
    assert telemetry_cli(["summarize", d, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert any(c["name"] == "ckpt/save_ms" for c in payload["counters"])


def test_counter_sink_removed_after_close():
    from apex_tpu.telemetry import hostmetrics
    tel = telemetry.Telemetry(run_dir=None, metrics=("loss",),
                              retrace=False)
    hostmetrics.emit("ckpt/save_ms", 1.0)
    assert tel.counters.records()
    tel.close()
    hostmetrics.emit("ckpt/save_ms", 99.0)
    assert tel.counters.records()[0]["count"] == 1   # no longer sunk


# ---------------------------------------------------------------------------
# retrace counter
# ---------------------------------------------------------------------------

def test_retrace_counter_fires_on_forced_retrace():
    c = telemetry.RetraceCounter()

    def f(x):
        return x * 2

    wrapped = jax.jit(c.wrap(f, name="f"))
    wrapped(jnp.zeros((4,)))
    wrapped(jnp.zeros((4,)))                  # cache hit: no retrace
    assert c.counts["f"] == 1
    wrapped(jnp.zeros((8,)))                  # forced retrace: new shape
    assert c.counts["f"] == 2
    assert c.retraces() == {"f": 1}
    recs = c.records(step=7)
    assert {"kind": "retrace", "name": "f", "traces": 2, "retraces": 1,
            "step": 7} in recs


def test_retrace_counter_monitoring_hook_counts_compiles():
    c = telemetry.RetraceCounter()
    if not c.install():
        pytest.skip("jax.monitoring unavailable")
    try:
        # apexlint: disable-next=APX302
        jax.jit(lambda x: x + 1)(jnp.zeros((3,)))
        # apexlint: disable-next=APX302
        jax.jit(lambda x: x + 2)(jnp.zeros((5,)))
        assert c.traces() >= 2
        assert c.compile_secs > 0
        assert any(r["name"] == "<process>" for r in c.records())
    finally:
        c.uninstall()
    before = c.traces()
    jax.jit(lambda x: x + 3)(jnp.zeros((7,)))  # apexlint: disable=APX302
    assert c.traces() == before               # uninstalled: no counting


def _compile_two(counter):
    def alpha(x):
        return jnp.sin(x) + 1

    def beta(x):
        return jnp.where(x > 0, jnp.tanh(x), x)     # nested jits inside

    counter.install()
    try:
        # apexlint: disable-next=APX302
        jax.jit(alpha)(jnp.zeros((3, 11)))
        # apexlint: disable-next=APX302
        jax.jit(beta)(jnp.zeros((5, 13)))
    finally:
        counter.uninstall()


def test_retrace_counter_keeps_seconds_and_counts_by_kind():
    from apex_tpu.telemetry import retrace
    c = telemetry.RetraceCounter()
    _compile_two(c)
    kinds = (retrace.TRACE_EVENT, retrace.LOWER_EVENT,
             retrace.BACKEND_COMPILE_EVENT)
    assert all(c.events[k] >= 2 and c.seconds[k] > 0 for k in kinds)
    # compile_secs is the kinds' sum, no accumulator of its own
    assert c.compile_secs == pytest.approx(sum(c.seconds[k] for k in kinds))
    # the tracing of `beta` traced jnp.where and jnp.tanh inside it:
    # jax counts those too, the kept spans hold the outermost only
    traced = [sp for sp in c.spans if sp.kind == "trace"]
    assert c.events[retrace.TRACE_EVENT] > len(traced)
    assert {"alpha", "beta"} <= {sp.name for sp in traced}
    assert not {"_where", "tanh"} & {sp.name for sp in c.spans}
    process, = [r for r in c.records() if r["name"] == "<process>"]
    assert process["compile_s"] == pytest.approx(c.compile_secs, abs=1e-3)
    assert {"trace_s", "lower_s", "backend_s", "cache_hits",
            "cache_misses", "cache_retrieval_s"} <= set(process)


def test_retrace_counter_keeps_a_row_a_program_and_each_events_span():
    from apex_tpu.telemetry import retrace
    c = telemetry.RetraceCounter()
    _compile_two(c)
    rows = c.program_rows()
    for name in ("alpha", "beta"):
        # tracing says `alpha`, lowering and the backend `jit(alpha)`
        row = rows[name]
        assert row["traces"] == 1
        assert min(row["trace_s"], row["lower_s"], row["backend_s"]) > 0
        assert row["compile_s"] == pytest.approx(
            row["trace_s"] + row["lower_s"] + row["backend_s"])
        mine = [sp for sp in c.spans if sp.name == name]
        assert [sp.kind for sp in mine] == ["trace", "lower", "backend"]
        assert all(a.start <= a.end <= b.start
                   for a, b in zip(mine, mine[1:]))
    # one thread's kept spans never overlap: their seconds are wall time
    assert all(a.end <= b.start for a, b in zip(c.spans, c.spans[1:]))
    named = {r["name"]: r for r in c.records(step=3)}
    assert named["program/alpha"]["traces"] == 1
    assert named["program/alpha"]["step"] == 3
    assert c.spans_dropped == 0
    # beyond max_spans the counts go on and the spans stop
    small = telemetry.RetraceCounter(max_spans=2)
    _compile_two(small)
    assert len(small.spans) == 2 and small.spans_dropped > 0
    assert small.events[retrace.BACKEND_COMPILE_EVENT] >= 2


class _Clock:
    """Stands in for the ``time`` module in telemetry/retrace.py."""

    def __init__(self, now=1000.0):
        self.now, self.reads = now, 0

    def time(self):
        self.reads += 1
        return self.now

    def perf_counter(self):
        self.reads += 1
        return self.now


def _listeners():
    from jax._src import monitoring
    return (monitoring.get_event_time_span_listeners()
            + monitoring.get_event_duration_listeners()
            + monitoring.get_event_listeners())


def _account(monkeypatch, **caps):
    """A fresh process account in place of the process's, on a clock
    the test sets, fed by hand."""
    from apex_tpu.telemetry import retrace
    clock = _Clock()
    monkeypatch.setattr(retrace, "time", clock)
    account = retrace.ProcessAccount(**caps)
    monkeypatch.setattr(retrace, "_PROCESS", account)
    account.install()
    return retrace, account, clock


def _feed(account, kind, name, start, end):
    from apex_tpu.telemetry import retrace
    event = {v: k for k, v in retrace.KINDS.items()}[kind]
    account._on_time_span(event, start, end, fun_name=name)


def test_account_until_step_leaves_out_what_came_after_the_mark(monkeypatch):
    retrace, account, clock = _account(monkeypatch)
    try:
        _feed(account, "trace", "step", 1001.0, 1003.0)
        _feed(account, "lower", "jit(step)", 1003.0, 1004.5)
        account._on_event(retrace.CACHE_HIT_EVENT)
        account._on_duration(retrace.CACHE_RETRIEVAL_EVENT, 0.25)
        _feed(account, "backend", "jit(step)", 1004.5, 1005.0)
        for at in (1006.0, 1007.0, 1008.0, 1011.0, 1012.0):
            clock.now = at
            retrace.mark_step()
            if at == 1008.0:        # step 2 compiles something more
                account._on_event(retrace.CACHE_MISS_EVENT)
                _feed(account, "backend", "jit(late)", 1009.0, 1010.0)
    finally:
        account.close()
    assert account.until_step(5) is None        # never began
    first = account.until_step(0)
    assert first["wall_s"] == pytest.approx(6.0)
    assert (first["trace_s"], first["lower_s"], first["backend_s"]) == \
        pytest.approx((2.0, 1.5, 0.5))
    assert (first["cache_hits"], first["cache_misses"]) == (1, 0)
    assert first["cache_retrieval_s"] == pytest.approx(0.25)
    assert first["programs"]["step"]["cache"] == "hit"
    assert account.until_step(2) == {**first, "wall_s": 2.0 + 6.0}
    later = account.until_step(3)               # after jit(late)
    assert later["backend_s"] == pytest.approx(1.5)
    assert later["cache_misses"] == 1 and "late" in later["programs"]
    assert account.compile_secs == pytest.approx(5.0)
    # steps 0 and 1 were quiet; step 2 (1008-1011) was not
    assert account.first_quiet_step() == 0
    report = account.report()
    assert "to the first step" in report and "program step" in report
    assert account.report_once() and account.report_once() is None


def test_account_nests_compile_events_under_the_open_phase(monkeypatch):
    retrace, account, clock = _account(monkeypatch)
    try:
        _feed(account, "backend", "jit(before)", 1000.5, 1000.9)
        clock.now = 1001.0
        with retrace.phase("apex/test/build"):
            _feed(account, "trace", "init_state_packed", 1001.5, 1001.75)
            _feed(account, "backend", "jit(init_state_packed)", 1002.0, 1003.0)
            clock.now = 1005.0
        _feed(account, "backend", "jit(after)", 1005.5, 1006.0)
    finally:
        account.close()
    row = account.summary()["phases"]["apex/test/build"]
    assert row["seconds"] == pytest.approx(4.0)
    assert row["own_s"] == pytest.approx(4.0 - 0.25 - 1.0)
    assert (row["trace_s"], row["backend_s"], row["backend_n"]) == \
        pytest.approx((0.25, 1.0, 1))
    # a phase that ended after the cut is not in it
    assert account.summary(before=1004.0)["phases"] == {}
    # another thread's event in the same seconds is not inside it
    stray = retrace.CompileSpan("lower", "x", 1002.0, 1002.5, thread=-1)
    account.spans.append(stray)
    assert account.summary()["phases"]["apex/test/build"]["lower_s"] == 0


def test_account_unregisters_itself_at_its_caps(monkeypatch):
    retrace, account, clock = _account(monkeypatch, max_steps=3)
    mine = set(account._listeners)
    assert mine <= set(_listeners())
    for _ in range(3):
        retrace.mark_step()
    assert not account.open and not mine & set(_listeners())
    reads = clock.reads
    retrace.mark_step()                          # one attribute test
    with retrace.phase("apex/test/late"):
        pass
    assert len(account.marks) == 3 and account.phases == []
    assert clock.reads == reads
    assert retrace.process() is account and not account.open

    # the other cap: spans.  Full, it drops what comes; its listeners
    # go at the next mark (inside jax's walk over them is no place)
    retrace, account, clock = _account(monkeypatch, max_spans=2)
    mine = set(account._listeners)
    for i in range(4):
        _feed(account, "backend", f"jit(p{i})", 1001.0 + i, 1001.5 + i)
    assert len(account.spans) == 2 and account.spans_dropped == 2
    assert account.open
    retrace.mark_step()
    assert not account.open and account.marks == []
    assert not mine & set(_listeners())


def test_closed_account_costs_a_step_and_a_span_no_clock_read(monkeypatch):
    from apex_tpu.telemetry import spans
    params = {"w": jnp.ones((8, 8)), "b": jnp.zeros((8,))}
    grads = tree_map(jnp.ones_like, params)
    retrace, account, clock = _account(monkeypatch)
    monkeypatch.setattr(spans, "time", clock)
    try:
        opt = FusedAdam(params, lr=1e-3)         # a phase: start and end
        assert [p[0] for p in account.phases] == ["apex/optim/init"]
        reads = clock.reads
        opt.step(grads)                          # open: the mark's one read
        assert clock.reads == reads + 1 and len(account.marks) == 1
    finally:
        account.close()
    reads = clock.reads
    opt.step(grads)
    with telemetry.span("apex/test/hot"):
        pass
    FusedAdam(params, lr=1e-3)
    amp.initialize(params, opt_level="O2")
    assert clock.reads == reads
    assert len(account.marks) == 1 and len(account.phases) == 1


# ---------------------------------------------------------------------------
# lockwatch (the RetraceCounter pattern for locks)
# ---------------------------------------------------------------------------

def test_watched_lock_counters_ride_flush_and_summarize(tmp_path,
                                                        capsys):
    d = str(tmp_path / "run")
    with telemetry.Telemetry(d, window=2, retrace=False) as tel:
        lk = telemetry.WatchedLock("export")
        for step in (1, 2):
            with lk:
                pass
            tel.record({"loss": 1.0 / step}, step)
        recs = {r["name"]: r for r in tel.counters.records()}
        assert recs["lock/export/held_ms"]["count"] == 2
        assert recs["lock/export/wait_ms"]["count"] == 2
        assert recs["lock/export/held_ms"]["total"] >= 0.0
    # the lock/* counters render next to ckpt/* in summarize
    assert telemetry_cli(["summarize", d]) == 0
    out = capsys.readouterr().out
    assert "counters (cumulative):" in out
    assert "lock/export/held_ms" in out and "lock/export/wait_ms" in out


def test_watched_lock_rlock_reentrancy_one_pair_per_cycle():
    tel = telemetry.Telemetry(run_dir=None, metrics=("loss",),
                              retrace=False)
    rl = telemetry.WatchedLock("nested", lock=threading.RLock())
    with rl:
        with rl:                      # inner acquire: no wait, no emit
            assert rl.locked()
    pairs = {r["name"]: r["count"] for r in tel.counters.records()}
    assert pairs == {"lock/nested/wait_ms": 1,
                     "lock/nested/held_ms": 1}
    tel.close()


def test_watched_lock_off_path_and_mid_hold_sink_registration():
    """With no sink the wrapper emits nothing; a sink registered
    MID-hold must not be charged a bogus held time for a cycle whose
    acquire ran untimed (the sentinel guard)."""
    # the premise is "telemetry off": a sink leaked by an earlier test
    # anywhere in the suite would turn the first acquire into a timed
    # cycle and break it, so assert the suite-hygiene contract here
    from apex_tpu.telemetry import hostmetrics
    assert not hostmetrics.active(), \
        "hostmetrics sink leaked by an earlier test"
    lk = telemetry.WatchedLock("race")
    lk.acquire()                      # telemetry off: untimed cycle
    tel = telemetry.Telemetry(run_dir=None, metrics=("loss",),
                              retrace=False)
    lk.release()
    assert tel.counters.records() == []
    with lk:                          # fully-observed cycle: one pair
        pass
    pairs = {r["name"]: r["count"] for r in tel.counters.records()}
    assert pairs == {"lock/race/wait_ms": 1, "lock/race/held_ms": 1}
    tel.close()


def test_watched_lock_actually_excludes():
    """The proxy is a real lock: racing increments through it lose
    nothing (barrier start, exact final count)."""
    lk = telemetry.WatchedLock("mutex")
    n_threads, per_thread = 4, 5_000
    state = {"n": 0}
    barrier = threading.Barrier(n_threads + 1)

    def worker():
        barrier.wait()
        for _ in range(per_thread):
            with lk:
                state["n"] += 1

    threads = [threading.Thread(target=worker)
               for _ in range(n_threads)]
    for t in threads:
        t.start()
    barrier.wait()
    for t in threads:
        t.join()
    assert state["n"] == n_threads * per_thread
    assert not lk.locked()


# ---------------------------------------------------------------------------
# CLI summarize
# ---------------------------------------------------------------------------

def test_summarize_renders_step_spans_retraces(tmp_path, capsys):
    d = str(tmp_path / "run")
    with telemetry.Telemetry(d, window=4) as tel:
        with telemetry.span("eval"):
            pass
        for i in range(6):
            tel.record({"loss": 1.0 / (i + 1),
                        "amp/grad_norm": 0.1 * i,
                        "amp/loss_scale": 65536.0,
                        "amp/found_inf": 1.0 if i == 2 else 0.0}, i)
    assert telemetry_cli(["summarize", d]) == 0
    out = capsys.readouterr().out
    assert "grad_norm" in out and "loss_scale" in out
    assert "overflow steps: 1" in out
    assert "eval" in out                      # span table
    assert "compilation:" in out              # retrace table
    # --json is machine-parseable with the same content
    assert telemetry_cli(["summarize", d, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["overflow_steps"] == 1
    assert len(payload["steps"]) == 6
    assert any(s["name"] == "eval" for s in payload["spans"])


def test_summarize_exit_codes(tmp_path, capsys):
    assert summarize(str(tmp_path / "nope")) == 1
    empty = tmp_path / "telemetry.jsonl"
    empty.write_text('{"kind": "schema", "version": 1, "metrics": []}\n')
    assert summarize(str(tmp_path)) == 1      # schema but zero steps
    capsys.readouterr()


# ---------------------------------------------------------------------------
# bench harness smoke (tier-1 keeps the tooling runnable)
# ---------------------------------------------------------------------------

def test_telemetry_overhead_bench_smoke():
    from apex_tpu.telemetry.bench import bench_telemetry_overhead
    r = bench_telemetry_overhead(layers=3, hidden=32, window=8,
                                 iters=2, reps=1)
    assert r["telemetry_off_ms"] > 0
    assert r["telemetry_on_ms"] > 0
    assert "telemetry_overhead_pct" in r
    assert r["telemetry_flush_ms"] >= 0


def test_lockwatch_overhead_bench_smoke():
    from apex_tpu.telemetry.bench import bench_lockwatch_overhead
    r = bench_lockwatch_overhead(window=8, n_metrics=4, iters=5,
                                 reps=2)
    assert r["lockwatch_off_ms"] > 0
    assert r["lockwatch_on_ms"] > 0
    assert "lockwatch_overhead_pct" in r
    assert r["lockwatch_acquire_ns"] >= 0


# ---------------------------------------------------------------------------
# pyprof satellites: thread-local nvtx, prof --json + newest-by-mtime
# ---------------------------------------------------------------------------

def test_nvtx_stack_is_thread_local():
    from apex_tpu.pyprof import nvtx
    errors = []

    def worker(tag):
        try:
            for _ in range(50):
                d1 = nvtx.range_push(f"{tag}/a")
                d2 = nvtx.range_push(f"{tag}/b")
                assert d2 == d1 + 1           # no cross-thread depth
                assert nvtx.range_pop() == d1
                assert nvtx.range_pop() == d1 - 1
        except BaseException as e:            # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []


def test_nvtx_exception_unwind_balances_stack():
    from apex_tpu.pyprof import nvtx
    nvtx.range_push("outer")
    try:
        nvtx.range_push("inner")
        raise RuntimeError("body raised")
    except RuntimeError:
        # best-effort unwind from the except branch never raises and
        # always balances, whatever state named_scope was left in
        assert nvtx.range_pop() == 1
        assert nvtx.range_pop() == 0
    assert nvtx.range_pop() == 0              # extra pop still harmless
    # the stack is usable again afterwards
    assert nvtx.range_push("again") == 1
    assert nvtx.range_pop() == 0


def _write_trace(outdir, name, ops, mtime=None):
    import gzip
    d = outdir / "plugins" / "profile" / name
    d.mkdir(parents=True, exist_ok=True)
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 7, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
    ] + [{"ph": "X", "pid": 3, "tid": 7, "name": op, "dur": dur}
         for op, dur in ops]
    p = d / "vm.trace.json.gz"
    with gzip.open(p, "wt") as f:
        json.dump({"traceEvents": events}, f)
    if mtime is not None:
        os.utime(p, (mtime, mtime))


def test_prof_picks_newest_trace_by_mtime(tmp_path):
    from apex_tpu.pyprof import prof
    now = time.time()
    # lexicographically LATER dir holds the OLDER capture
    _write_trace(tmp_path, "z_old_run", [("stale.1", 1000)],
                 mtime=now - 1000)
    _write_trace(tmp_path, "a_new_run", [("fresh.2", 2000)], mtime=now)
    rows = prof.summarize_device_ops(str(tmp_path))
    assert [r[0] for r in rows] == ["fresh.2"]


def test_prof_json_output_and_empty_exit_code(tmp_path, capsys):
    from apex_tpu.pyprof import prof
    _write_trace(tmp_path, "run", [("fusion.9", 3000), ("conv", 1000)])
    assert prof.main([str(tmp_path), "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert rows == [{"op": "fusion.9", "where": "device",
                     "total_ms": 3.0, "pct": 75.0},
                    {"op": "conv", "where": "device",
                     "total_ms": 1.0, "pct": 25.0}]
    # empty-trace path: exit 1, and --json stays parseable
    empty = tmp_path / "empty"
    empty.mkdir()
    assert prof.main([str(empty)]) == 1
    capsys.readouterr()
    assert prof.main([str(empty), "--json"]) == 1
    assert json.loads(capsys.readouterr().out) == []


# ---------------------------------------------------------------------------
# end-to-end: examples/simple with telemetry on -> summarize (slow tier)
# ---------------------------------------------------------------------------

def test_train_toy_telemetry_end_to_end(tmp_path, capsys):
    import runpy
    import sys
    d = str(tmp_path / "toyrun")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "examples", "simple", "train_toy.py")
    old = sys.argv
    sys.argv = [path, "--telemetry-dir", d]
    try:
        runpy.run_path(path, run_name="__main__")
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "OK: loss" in out
    assert telemetry_cli(["summarize", d]) == 0
    table = capsys.readouterr().out
    assert "grad_norm" in table and "loss_scale" in table
    assert "final_eval" in table


# ---------------------------------------------------------------------------
# ISSUE 7: flush observers, rewind, anomaly timeline (watchdog surface)
# ---------------------------------------------------------------------------

def test_flush_observer_sees_records_and_injects_events(tmp_path):
    d = str(tmp_path / "run")
    seen, tel = [], telemetry.Telemetry(d, metrics=("loss",), window=4,
                                        retrace=False)

    def obs(records):
        seen.extend(r["step"] for r in records)
        if records:
            return [{"kind": "anomaly", "anomaly": "test_kind",
                     "severity": "warn", "step": records[-1]["step"],
                     "first_step": records[0]["step"],
                     "detector": "t", "evidence": {}}]

    tel.add_observer(obs)
    for i in range(6):
        tel.record({"loss": float(i)}, i)
    tel.close()
    assert seen == [0, 1, 2, 3, 4, 5]         # every step reached it
    lines = [json.loads(l) for l in
             open(os.path.join(d, JSONL_NAME))]
    assert any(r.get("kind") == "anomaly" and
               r.get("anomaly") == "test_kind" for r in lines)


def test_flush_observer_runs_on_nonwriter_rank(tmp_path, monkeypatch):
    """Multi-host watchdogs must all reach the same verdict: with an
    observer attached, a rank0_only session still fetches and decodes
    its LOCAL ring on non-zero ranks — emitters stay silent."""
    d = str(tmp_path / "rank1")
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    tel = telemetry.Telemetry(d, metrics=("loss",), window=2,
                              retrace=False)
    seen = []
    tel.add_observer(lambda records:
                     seen.extend(r["step"] for r in records))
    tel.record({"loss": 1.0}, 0)
    tel.record({"loss": 2.0}, 1)
    assert tel.flush() == []                  # contract: returns []
    tel.close()
    assert seen == [0, 1]                     # ...but the observer saw
    assert not os.path.exists(os.path.join(d, JSONL_NAME))


def test_remove_observer_and_no_observer_skips_fetch(monkeypatch):
    tel = telemetry.Telemetry(run_dir=None, metrics=("loss",),
                              window=4, retrace=False)
    calls = []
    obs = lambda records: calls.append(len(records))
    tel.add_observer(obs)
    tel.remove_observer(obs)
    tel.remove_observer(obs)                  # idempotent
    tel.record({"loss": 1.0}, 0)
    tel.flush()
    tel.close()
    assert calls == []


def test_rewind_replays_steps_and_summarize_keeps_newest(tmp_path,
                                                         capsys):
    """After a rollback, replayed step numbers must re-record and
    re-emit; the raw JSONL keeps both passes, the summarize surface
    renders the REPLAYED (newest) values."""
    d = str(tmp_path / "run")
    with telemetry.Telemetry(d, metrics=("loss",), window=4,
                             retrace=False) as tel:
        for i in range(1, 7):
            tel.record({"loss": 100.0 + i}, i)    # the "bad" pass
        tel.rewind(2)                             # rollback to step 2
        for i in range(3, 7):
            tel.record({"loss": float(i)}, i)     # the replay
    lines = [json.loads(l) for l in open(os.path.join(d, JSONL_NAME))]
    steps = [r for r in lines if r.get("kind", "step") == "step"
             and "step" in r]
    # both passes of step 4 are on the record
    assert sorted(r["loss"] for r in steps
                  if r["step"] == 4) == [4.0, 104.0]
    assert telemetry_cli(["summarize", d, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    by_step = {r["step"]: r for r in payload["steps"]}
    assert by_step[4]["loss"] == 4.0              # replay wins
    assert by_step[1]["loss"] == 101.0            # pre-rollback kept


def test_summarize_renders_anomaly_timeline(tmp_path, capsys):
    d = tmp_path
    recs = [
        {"kind": "schema", "version": 1, "metrics": ["loss"]},
        {"kind": "step", "step": 1, "loss": 1.0},
        {"kind": "step", "step": 2, "loss": 999.0},
        {"kind": "anomaly", "anomaly": "loss_spike",
         "severity": "warn", "step": 2, "first_step": 2,
         "detector": "loss_spike", "evidence": {"zscore": 12.5}},
        {"kind": "watchdog", "action": "rollback", "step": 3,
         "to_step": 1, "anomaly": "loss_spike", "rollbacks": 1},
        {"kind": "step", "step": 3, "loss": 1.1},
    ]
    (d / "telemetry.jsonl").write_text(
        "\n".join(json.dumps(r) for r in recs) + "\n")
    assert telemetry_cli(["summarize", str(d)]) == 0
    out = capsys.readouterr().out
    assert "anomaly timeline:" in out
    assert "loss_spike" in out and "zscore=12.5" in out
    assert "rollback" in out and "to_step=1" in out
    assert telemetry_cli(["summarize", str(d), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    kinds = [r["kind"] for r in payload["anomalies"]]
    assert kinds == ["anomaly", "watchdog"]
