"""The program's view of a traced run (benchmarks/programtrace.py) and
the three readers on it (PR 25), on the CPU: nothing here times
anything.

``program_trace_small.json`` is hand-built on the pattern of a TPU v5e
trace of ``bert_large_lamb.phase2_s512``: two steps of 10 us, the two
step programs with their scopes (a forward op, a ``transpose(jvp(...))``
op, an unscoped op, an op that encloses two others), three tiny
programs, and three idle gaps a step — one inside ``apex/optim/args``,
one under no span of the library, one inside
``apex/amp/update_scaler``.  ``trace`` is what ``traceread`` keeps of
the run, ``program`` what ``programtrace`` adds.
"""

import json
import os
import sys
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import programtrace, traceread  # noqa: E402
from benchmarks.readers import (module_count, program_span,  # noqa: E402
                                scope_time)

HERE = os.path.dirname(os.path.abspath(__file__))
PROGRAMS = {"fwd_bwd": "step", "optimizer": "_full_step_flat"}


def small_ctx():
    with open(os.path.join(HERE, "program_trace_small.json")) as f:
        both = json.load(f)
    trace = traceread.Trace.from_json(both["trace"])
    return types.SimpleNamespace(
        trace=trace, steady=traceread.steady_window(trace, "step"),
        programs=PROGRAMS,
        program_trace=programtrace.ProgramTrace.from_json(both["program"]))


def metric(name):
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    reader = {"scope_time": scope_time, "program_span": program_span,
              "module_count": module_count}[spec["reader"]]
    return lambda ctx: reader.read(ctx, **spec.get("params", {}))


# ---- the matching rule -----------------------------------------------------

@pytest.mark.parametrize("name, path", [
    ("jit(step)/jvp(BertModel.mlm_logits)/BertModel/layer_3/attn_qkv/"
     "apex_linear/dot_general:", ("apex_linear", "dot_general")),
    # backward: the wrappers go, the token stays
    ("jit(step)/transpose(jvp(BertModel.mlm_logits))/BertModel/layer_3/"
     "mlp_layernorm/apex_layernorm/apex_fused_layer_norm_bwd/pallas_call:",
     ("apex_layernorm", "apex_fused_layer_norm_bwd", "pallas_call")),
    # a scope named with a slash is one Scope: the wrapper closes after it
    ("jit(step)/jvp(apex_amp/scale_loss)/mul:",
     ("apex_amp", "scale_loss", "mul")),
    ("jit(step)/transpose(jvp(apex_xentropy))/apex_xentropy_bwd/"
     "pallas_call:", ("apex_xentropy", "apex_xentropy_bwd", "pallas_call")),
    # the innermost scope wins; a kernel's own name is not a scope
    ("jit(f)/jvp(apex_linear)/apex_amp/cast/convert_element_type:",
     ("apex_amp", "cast", "convert_element_type")),
    ("jit(_full_step_flat)/apex_optim/grad_norm/apex_multi_tensor_l2norm/"
     "pallas_call:", ("apex_optim", "grad_norm",
                      "apex_multi_tensor_l2norm", "pallas_call")),
    ("jit(f)/apex_multi_tensor_scale/pallas_call:", None),
    ("jit(step)/transpose(jvp(BertModel.mlm_logits))/BertModel/embed/"
     "jit(_take)/scatter-add:", None),
    ("jit(step)/transpose(jvp())/div:", None),
    ("", None),
])
def test_scope_path_resolves_forward_and_backward_alike(name, path):
    assert programtrace.scope_path(name) == path


def test_under_matches_whole_components_only():
    path = programtrace.scope_path("jit(f)/apex_optim/trust_ratio/gather:")
    assert programtrace.under(path, ["apex_optim/trust_ratio"])
    assert programtrace.under(path, ["apex_optim"])
    assert not programtrace.under(path, ["apex_optim/trust"])
    assert not programtrace.under(path, ["apex_optim/apply", "apex_amp"])
    assert not programtrace.under(None, ["apex_optim"])


def test_an_enclosing_op_keeps_only_its_own_time():
    own = {op[0] or "(while)": ns for op, ns in programtrace.self_times([
        ("", 0.0, 100.0, "p"), ("a", 0.0, 40.0, "p"),
        ("b", 40.0, 90.0, "p"), ("c", 100.0, 130.0, "p")])}
    assert own == {"(while)": 10.0, "a": 40.0, "b": 50.0, "c": 30.0}


# ---- the readers on the small trace -----------------------------------------

@pytest.mark.parametrize("name, want", [
    ("opt_reduce_ms", 5100e-6), ("opt_update_ms", 700e-6),
    ("amp_unscale_ms", 100e-6), ("layernorm_ms", 400e-6),
    ("linear_ms", 1600e-6),
    ("unscoped_device_share", 300 / 8800 * 100),
    ("programs_per_step", 5),
    ("optim_host_ms", 410e-6), ("amp_host_ms", 450e-6),
    ("lib_idle_ms", 800e-6),
])
def test_each_new_metric_reads_the_small_trace(name, want):
    assert metric(name)(small_ctx()) == pytest.approx(want, rel=1e-9)


def test_scoped_optimizer_time_adds_up_to_its_module():
    ctx = small_ctx()
    module = sum(e - s for _, s, e in traceread.clipped(
        traceread.module_events(ctx.trace, 0, "_full_step_flat"),
        ctx.steady)) / 1e6 / ctx.steady.steps
    assert (metric("opt_reduce_ms")(ctx) + metric("opt_update_ms")(ctx)
            == pytest.approx(module))


def test_idle_time_splits_by_the_innermost_span(capsys):
    assert metric("lib_idle_ms")(small_ctx()) > 0
    said = capsys.readouterr().err
    assert "'apex/optim/args': 0.0002" in said
    assert "'apex/amp/update_scaler': 0.0006" in said
    assert "apex/optim/step" not in said


def test_the_librarys_spans_lie_inside_the_harness_span_of_their_call():
    ctx = small_ctx()
    got = programtrace.nesting(ctx.program_trace, ctx.trace.host)
    assert got["apex/optim/step"] == {"dispatch_optimizer": 2}
    assert got["apex/amp/update_scaler"] == {"dispatch_optimizer": 2}
    for child in ("args", "dispatch", "clock", "unpack_model"):
        assert set(got["apex/optim/" + child]) == {"apex/optim/step"}


@pytest.mark.parametrize("name", [
    "opt_reduce_ms", "opt_update_ms", "amp_unscale_ms", "layernorm_ms",
    "linear_ms", "unscoped_device_share", "optim_host_ms", "amp_host_ms",
    "lib_idle_ms"])
def test_a_program_without_spans_or_scopes_reads_nothing(name):
    """The parent commit's program under this PR's benchmark files: no
    ``apex/*`` span, no ``apex_*`` scope (its Pallas kernels' own names
    do not count) — nothing to read, never 0 and never an error."""
    ctx = small_ctx()
    pt = ctx.program_trace
    ctx.program_trace = programtrace.ProgramTrace(
        [h for h in pt.host if not h[0].startswith("apex/")],
        {0: [("jit(f)/apex_multi_tensor_lamb_apply/pallas_call:"
              if "pallas_call" in n else "jit(f)/mul:", s, e, p)
             for n, s, e, p in pt.ops[0]]})
    assert metric(name)(ctx) is None
    ctx = small_ctx()
    ctx.trace = None                      # a run without --trace
    del ctx.program_trace
    assert metric(name)(ctx) is None


def test_programs_per_step_needs_no_scope():
    ctx = small_ctx()
    ctx.program_trace = programtrace.ProgramTrace([], {})
    assert metric("programs_per_step")(ctx) == 5


# ---- the file ---------------------------------------------------------------

def test_xplane_file_is_read_without_a_protobuf_library(tmp_path,
                                                        monkeypatch):
    """A CPU trace: the host plane's ``apex/*`` spans and JAX's
    ``PjitFunction`` events come back on the profiler's clock, with no
    chip's plane; the newest file under ``.bench_trace`` is found."""
    from apex_tpu import telemetry
    f = jax.jit(lambda x: x + 1)
    f(1.0).block_until_ready()
    logdir = tmp_path / ".bench_trace" / "cell.1"
    with jax.profiler.trace(str(logdir)):
        with telemetry.span("apex/optim/step", step=4):
            with telemetry.span("apex/optim/dispatch"):
                f(1.0).block_until_ready()
        with jax.profiler.TraceAnnotation("dispatch_optimizer"):
            pass
    monkeypatch.setattr(programtrace, "ROOT", str(tmp_path))
    path = programtrace.find_xplane()
    assert path == traceread.find_xplane(str(logdir))
    pt = programtrace.load_xplane(path)
    assert pt.ops == {}
    names = [h[0] for h in pt.host]
    assert names.count("apex/optim/step") == 1
    assert "dispatch_optimizer" not in names       # the harness's, not ours
    step, = [h for h in pt.host if h[0] == "apex/optim/step"]
    inner, = [h for h in pt.host if h[0] == "apex/optim/dispatch"]
    assert step[1] <= inner[1] <= inner[2] <= step[2]
    assert any(h[0] == "PjitFunction(<lambda>)"
               and inner[1] <= h[1] and h[2] <= inner[2] for h in pt.host)
    # the same event, on the same clock, as traceread reads it
    same, = traceread.load_xplane(path, ["apex/optim/step"]).host
    assert same[1] == pytest.approx(step[1], abs=1.0)
    assert same[2] == pytest.approx(step[2], abs=1.0)
    again = programtrace.ProgramTrace.from_json(
        json.loads(json.dumps(pt.to_json())))
    assert again == pt
