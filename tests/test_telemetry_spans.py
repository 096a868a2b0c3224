"""``telemetry.span`` is a real profiler event (PR 25).

Under a running ``jax.profiler`` trace a span lands on the host plane
under its own name, its child inside it, on the clock the device ops
use; with a sink registered it is also a record (name, start, end,
parent, step); with neither it is the annotation alone — no clock
reading, nothing appended anywhere.
"""

import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from apex_tpu import telemetry
from apex_tpu.telemetry import spans


def _host_events(logdir):
    path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats)) for line in plane.lines for e in line.events]


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("apex/test/outer", step=7):
            with telemetry.span("apex/test/inner"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    outer, = [e for e in events if e[0] == "apex/test/outer"]
    inner, = [e for e in events if e[0] == "apex/test/inner"]
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    assert outer[3].get("step") == 7 and "step" not in inner[3]
    # JAX's own dispatch event of the jitted call shares the plane and
    # the clock: it lies inside the span that made the call
    calls = [e for e in events if e[0].startswith("PjitFunction(")]
    assert any(inner[1] <= c[1] and c[2] <= inner[2] for c in calls)


def test_span_without_sink_or_profiler_is_the_annotation_alone(monkeypatch):
    def no_clock():
        raise AssertionError("a span with no sink read the clock")
    monkeypatch.setattr(spans.time, "perf_counter", no_clock)
    assert not spans._registry.active()
    before = list(getattr(spans._tls, "stack", []))
    cm = telemetry.span("apex/test/quiet")
    assert isinstance(cm, jax.profiler.TraceAnnotation)
    with cm:
        with telemetry.span("apex/test/quiet_child", step=1):
            pass
    assert list(getattr(spans._tls, "stack", [])) == before == []
    # the span primitive no longer rides pyprof's named-scope stack
    assert not hasattr(spans, "nvtx")


def test_span_with_a_sink_records_parent_and_step():
    got = []

    def sink(name, record):
        got.append((name, record))
    spans.add_sink(sink)
    try:
        with telemetry.span("outer", step=3):
            with telemetry.span("inner"):
                time.sleep(0.002)
        with pytest.raises(RuntimeError):
            with telemetry.span("raises"):
                raise RuntimeError("boom")
    finally:
        spans.remove_sink(sink)
    (n1, inner), (n2, outer), (n3, raised) = got    # closed in this order
    assert (n1, n2, n3) == ("inner", "outer", "raises")
    assert inner.parent == "outer" and outer.parent is None
    assert outer.step == 3 and inner.step is None
    assert outer.start <= inner.start < inner.end <= outer.end
    assert inner.seconds >= 0.002
    assert raised.parent is None            # the stack unwound
    with telemetry.span("after"):           # sink gone: no record
        pass
    assert len(got) == 3


def test_construction_phases_reach_the_account_and_a_sink(monkeypatch):
    """``apex/amp/initialize`` and ``apex/optim/init`` (PR 35) are spans
    like the others, and while the process's set-up account is open
    their start and end go there too, the programs they load inside."""
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.telemetry import retrace

    account = retrace.ProcessAccount()
    monkeypatch.setattr(retrace, "_PROCESS", account)
    account.install()
    got = []

    def sink(name, record):
        got.append((name, record.parent))

    spans.add_sink(sink)
    try:
        params = {"w": jnp.ones((16, 24)), "b": jnp.zeros((24,))}
        params, state = amp.initialize(params, opt_level="O2")
        opt = FusedAdam(params, masters=state.master_params, lr=1e-3)
        opt.step(jax.tree_util.tree_map(jnp.ones_like, params))
    finally:
        spans.remove_sink(sink)
        account.close()
    assert ("apex/amp/initialize", None) in got
    assert ("apex/optim/init", None) in got
    assert [p[0] for p in account.phases] == ["apex/amp/initialize",
                                              "apex/optim/init"]
    assert len(account.marks) == 1
    phases = account.until_step(0)["phases"]
    # the state's one program is loaded inside the constructor
    assert phases["apex/optim/init"]["backend_n"] >= 1
    assert phases["apex/optim/init"]["seconds"] >= \
        phases["apex/optim/init"]["backend_s"] > 0
    assert phases["apex/amp/initialize"]["seconds"] > 0
