"""Autotuner + per-topology dispatch tables.

Covers the three layers ISSUE 11 added: (a) the per-topology
dispatch-table selection in ops/_dispatch.py (wrong-topology tables
ignored loudly, missing tables fall back, malformed entries drop
per-entry, the cached-with-invalidation accessor and install_prefs),
(b) the stdlib schema validator + budget restamp logic in
tools/autotune.py, and the perf_gate auto-gating mode, and (c) the
acceptance flow: ``tools/autotune.py --cpu-smoke`` end to end —
sweep -> schema-valid table -> installed table changes a dispatch
decision (via the new accessor) -> perf_budget row restamped with
sweep provenance."""

import importlib.util
import json
import os
import warnings

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def _load_tool(name):
    return _load_path(name, os.path.join(_ROOT, "tools", f"{name}.py"))


at = _load_tool("autotune")
pg = _load_tool("perf_gate")


def _topo_block(key="cpu-8", kind="cpu", n=8):
    return {"key": key, "device_kind": kind, "device_count": n,
            "process_count": 1}


def _good_table(key="cpu-8"):
    return {
        "schema": at.SCHEMA_VERSION,
        "methodology": "amortized",
        "source": "tools/autotune.py",
        "topology": _topo_block(key),
        "noise_floor_pct": 3.5,
        "prefer_pallas": {"multi_tensor": False, "welford": True},
        "attn_block_cap": {"128": 512},
        "pipeline": {"max_bucket_bytes": 1 << 25,
                     "reduce_decompose": "reduce_scatter"},
        "serving": {"page_size": 8, "decode_window": 8,
                    "kv_dtype": "int8", "prefix_share": True,
                    "spec_k": 4, "weight_dtype": "int8",
                    "prefill_batch": 4},
    }


# ---------------------------------------------------------------------------
# schema validation (the check.sh gate)
# ---------------------------------------------------------------------------

class TestValidateTable:
    def test_schema_version_in_sync_with_dispatch(self):
        from apex_tpu.ops import _dispatch
        assert at.SCHEMA_VERSION == _dispatch.SCHEMA_VERSION

    def test_good_per_topology_table_passes(self):
        assert at.validate_table(
            _good_table(), per_topology=True,
            path="x/dispatch_prefs.cpu-8.json") == []

    def test_shipped_tables_validate(self):
        assert at.validate_paths() == []

    def test_spec_k_zero_is_valid(self):
        # spec_k is the one serving integer where 0 is a VALID value
        # (speculation off) — it must not ride the positive-int check
        doc = _good_table()
        doc["serving"]["spec_k"] = 0
        assert at.validate_table(
            doc, per_topology=True,
            path="x/dispatch_prefs.cpu-8.json") == []

    @pytest.mark.parametrize("mutate,needle", [
        (lambda d: d.pop("methodology"), "methodology"),
        (lambda d: d.pop("topology"), "topology block"),
        (lambda d: d.pop("noise_floor_pct"), "noise_floor_pct"),
        (lambda d: d.update(schema=1), "schema=2"),
        (lambda d: d["prefer_pallas"].update(softmax="yes"),
         "JSON boolean"),
        (lambda d: d["attn_block_cap"].update({"128": 100}),
         "multiple of 128"),
        (lambda d: d["pipeline"].update(reduce_decompose="allreduce"),
         "reduce_decompose"),
        (lambda d: d["pipeline"].update(max_bucket_bytes=-4),
         "max_bucket_bytes"),
        (lambda d: d["topology"].pop("key"), "string 'key'"),
        (lambda d: d["serving"].update(page_size=0),
         "serving.page_size"),
        (lambda d: d["serving"].update(kv_dtype="fp4"),
         "serving.kv_dtype"),
        (lambda d: d["serving"].update(prefix_share="yes"),
         "serving.prefix_share"),
        (lambda d: d["serving"].update(spec_k=-1),
         "serving.spec_k"),
        (lambda d: d["serving"].update(weight_dtype="fp4"),
         "serving.weight_dtype"),
        (lambda d: d["serving"].update(prefill_batch=0),
         "serving.prefill_batch"),
    ])
    def test_each_violation_fails_fast(self, mutate, needle):
        doc = _good_table()
        mutate(doc)
        errs = at.validate_table(doc, per_topology=True,
                                 path="x/dispatch_prefs.cpu-8.json")
        assert errs and any(needle in e for e in errs), (needle, errs)

    def test_filename_must_match_topology_key(self):
        errs = at.validate_table(_good_table("tpu_v4-8"),
                                 per_topology=True,
                                 path="x/dispatch_prefs.cpu-8.json")
        assert any("filename must match" in e for e in errs)

    def test_default_table_needs_no_topology(self):
        # the shipped topology-agnostic default stays valid...
        assert at.validate_table(
            {"methodology": "amortized",
             "prefer_pallas": {"welford": True}},
            per_topology=False) == []
        # ...but the methodology stamp is still mandatory everywhere
        errs = at.validate_table({"prefer_pallas": {}},
                                 per_topology=False)
        assert any("methodology" in e for e in errs)

    def test_validate_paths_flags_unreadable_and_bad(self, tmp_path):
        good = tmp_path / "dispatch_prefs.cpu-8.json"
        good.write_text(json.dumps(_good_table()))
        bad = tmp_path / "dispatch_prefs.json"
        bad.write_text("{truncated")
        errs = at.validate_paths([str(good), str(bad)])
        assert len(errs) == 1 and "unreadable" in errs[0]


# ---------------------------------------------------------------------------
# budget restamp
# ---------------------------------------------------------------------------

class TestRestampBudget:
    BUDGET = {
        "stamped_at": "2026-07-31T03:41:18Z",
        "metrics": {
            "extra.grad_accum_n8_speedup": {
                "floor": 1.0, "direction": "higher", "noise_pct": 10.0},
            "extra.resnet50_step_ms": {
                "ceiling": 60.71, "direction": "lower",
                "noise_pct": 5.0},
        }}

    def test_floor_moves_with_provenance(self):
        b = json.loads(json.dumps(self.BUDGET))
        rows = at.restamp_budget(
            b, {"extra.grad_accum_n8_speedup": 1.84},
            topology="tpu_v5e-8", backend="tpu", noise_floor_pct=3.0,
            mode="full", when="2026-08-04T00:00:00Z")
        assert rows == ["extra.grad_accum_n8_speedup"]
        spec = b["metrics"]["extra.grad_accum_n8_speedup"]
        assert spec["floor"] == 1.84
        assert spec["restamped"]["by"] == "tools/autotune.py"
        assert spec["restamped"]["topology"] == "tpu_v5e-8"
        # a hardware restamp moves the gate's auto-mode anchor
        assert b["stamped_at"] == "2026-08-04T00:00:00Z"

    def test_lower_is_better_moves_ceiling(self):
        b = json.loads(json.dumps(self.BUDGET))
        at.restamp_budget(
            b, {"extra.resnet50_step_ms": 55.2}, topology="t",
            backend="tpu", noise_floor_pct=3.0, mode="full",
            when="2026-08-04T00:00:00Z")
        assert b["metrics"]["extra.resnet50_step_ms"]["ceiling"] == 55.2

    def test_cpu_smoke_never_moves_the_stamp_date(self):
        # row provenance lands (the plumbing proof) but the gate's
        # auto-mode anchor only moves on hardware
        b = json.loads(json.dumps(self.BUDGET))
        rows = at.restamp_budget(
            b, {"extra.grad_accum_n8_speedup": 0.4}, topology="cpu-8",
            backend="cpu", noise_floor_pct=12.0, mode="cpu-smoke",
            when="2026-08-04T00:00:00Z")
        assert rows == ["extra.grad_accum_n8_speedup"]
        assert b["stamped_at"] == "2026-07-31T03:41:18Z"
        assert b["metrics"]["extra.grad_accum_n8_speedup"][
            "restamped"]["mode"] == "cpu-smoke"

    def test_unknown_metrics_ignored(self):
        b = json.loads(json.dumps(self.BUDGET))
        assert at.restamp_budget(
            b, {"extra.never_heard_of_it": 9.9}, topology="t",
            backend="tpu", noise_floor_pct=3.0, mode="full",
            when="w") == []


# ---------------------------------------------------------------------------
# perf_gate auto-gating mode
# ---------------------------------------------------------------------------

class TestPerfGateAutoMode:
    BUDGET = {"stamped_at": "2026-07-31T03:41:18Z", "metrics": {}}

    @staticmethod
    def _round(backend="tpu", when="2026-08-01T00:00:00Z",
               cached=False, value=100.0):
        p = {"backend": backend, "value": value}
        if cached:
            p["extra"] = {"cached_measured_at": when}
        else:
            p["measured_at"] = when
        return p

    def test_newer_live_round_gates(self):
        gating, reason = pg.choose_mode(
            self.BUDGET, [(4, self._round(when="2026-07-31T03:41:18Z")),
                          (6, self._round(when="2026-08-04T01:00:00Z"))])
        assert gating and "postdates" in reason

    def test_round_covered_by_stamp_reports_only(self):
        gating, reason = pg.choose_mode(
            self.BUDGET,
            [(5, self._round(when="2026-07-31T03:41:18Z",
                             cached=True))])
        assert not gating and "does not postdate" in reason

    def test_cpu_newest_round_reports_only(self):
        gating, reason = pg.choose_mode(
            self.BUDGET, [(4, self._round(when="2026-08-04T01:00:00Z")),
                          (6, self._round(backend="cpu-fallback"))])
        assert not gating and "not a hardware round" in reason

    def test_missing_timestamps_report_only(self):
        p = {"backend": "tpu", "value": 10.0}
        gating, reason = pg.choose_mode(self.BUDGET, [(4, p)])
        assert not gating and "cannot compare" in reason
        gating, _ = pg.choose_mode({"metrics": {}}, [(4, self._round())])
        assert not gating

    def test_no_rounds_report_only(self):
        gating, reason = pg.choose_mode(self.BUDGET, [])
        assert not gating

    def test_repo_state_is_report_only_today(self):
        """The committed r04/r05 cached rounds re-serve the window the
        budget was stamped from — flipping to gating on them would
        block exactly the PRs that will re-measure them."""
        with open(os.path.join(_ROOT, "tools",
                               "perf_budget.json")) as f:
            budget = json.load(f)
        gating, _ = pg.choose_mode(budget, pg.load_rounds(_ROOT))
        assert not gating

    def test_cli_exit_codes(self, tmp_path):
        budget = tmp_path / "b.json"
        budget.write_text(json.dumps({
            "stamped_at": "2026-07-01T00:00:00Z",
            "metrics": {"value": {"floor": 200.0,
                                  "direction": "higher",
                                  "noise_pct": 5.0}}}))
        art = tmp_path / "BENCH_r01.json"
        art.write_text(json.dumps({"parsed": {
            "backend": "tpu", "value": 100.0,
            "measured_at": "2026-08-01T00:00:00Z"}}))
        # auto mode gates (round postdates stamp) and the breach fails
        assert pg.main(["--budget", str(budget), "--root",
                        str(tmp_path)]) == 1
        # forced report-only always exits 0
        assert pg.main(["--budget", str(budget), "--root",
                        str(tmp_path), "--report"]) == 0
        # an older round does not gate even on a breach
        art.write_text(json.dumps({"parsed": {
            "backend": "tpu", "value": 100.0,
            "measured_at": "2026-06-01T00:00:00Z"}}))
        assert pg.main(["--budget", str(budget), "--root",
                        str(tmp_path)]) == 0
        # --gate forces it back on
        assert pg.main(["--budget", str(budget), "--root",
                        str(tmp_path), "--gate"]) == 1


# ---------------------------------------------------------------------------
# per-topology table selection (ops/_dispatch.py)
# ---------------------------------------------------------------------------

@pytest.fixture
def live_dispatch(monkeypatch, tmp_path):
    """Undo conftest's neutralization so the file-backed accessor is
    live, rooted at an empty tmp dir (no shipped table in play)."""
    from apex_tpu.ops import _dispatch
    monkeypatch.setattr(_dispatch, "_PREFS", None)
    monkeypatch.setattr(_dispatch, "_ATTN_CAPS", None)
    monkeypatch.setattr(_dispatch, "_PIPELINE", None)
    monkeypatch.setattr(_dispatch, "_INSTALLED", None)
    monkeypatch.setattr(_dispatch, "_CACHE", None)
    monkeypatch.setattr(_dispatch, "_PREFS_PATH",
                        str(tmp_path / "dispatch_prefs.json"))
    return _dispatch, tmp_path


def _write(path, doc):
    path.write_text(json.dumps(doc))


class TestTopologySelection:
    def test_matching_topology_table_wins_over_default(
            self, live_dispatch):
        _dispatch, root = live_dispatch
        key = _dispatch.topology_key()
        _write(root / "dispatch_prefs.json",
               {"methodology": "amortized",
                "prefer_pallas": {"welford": True}})
        _write(root / f"dispatch_prefs.{key}.json", _good_table(key))
        assert not _dispatch.op_enabled("multi_tensor")
        assert _dispatch.op_enabled("welford")
        assert _dispatch.attn_block_cap(128) == 512
        assert _dispatch.pipeline_pref("reduce_decompose") \
            == "reduce_scatter"
        assert _dispatch.dispatch_tables().topology == key

    def test_wrong_topology_table_ignored_with_warning(
            self, live_dispatch):
        _dispatch, root = live_dispatch
        key = _dispatch.topology_key()
        _write(root / "dispatch_prefs.json",
               {"methodology": "amortized",
                "prefer_pallas": {"multi_tensor": True}})
        # the file is NAMED for this topology but stamped for another
        # (a copied-over table): ignored, loudly, default table steers
        _write(root / f"dispatch_prefs.{key}.json",
               _good_table("tpu_v4-8"))
        with pytest.warns(RuntimeWarning, match="topology"):
            assert _dispatch.op_enabled("multi_tensor")
        assert _dispatch.dispatch_tables().topology is None

    def test_missing_topology_table_falls_back_to_default(
            self, live_dispatch):
        _dispatch, root = live_dispatch
        _write(root / "dispatch_prefs.json",
               {"methodology": "amortized",
                "prefer_pallas": {"softmax": False},
                "attn_block_cap": {"128": 256}})
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert not _dispatch.op_enabled("softmax")
            assert _dispatch.attn_block_cap(128) == 256

    def test_default_table_with_foreign_topology_ignored(
            self, live_dispatch):
        """kernel_bench --write-prefs stamps topology into the default
        table now: a table benched on one fleet must not silently
        steer another (absent block = legacy/portable, still steers)."""
        _dispatch, root = live_dispatch
        doc = {"methodology": "amortized",
               "prefer_pallas": {"welford": False},
               "topology": _topo_block("tpu_v4-8", "TPU v4", 8)}
        _write(root / "dispatch_prefs.json", doc)
        with pytest.warns(RuntimeWarning, match="topology"):
            assert _dispatch.op_enabled("welford")

    def test_malformed_entries_drop_per_entry(self, live_dispatch):
        _dispatch, root = live_dispatch
        key = _dispatch.topology_key()
        doc = _good_table(key)
        doc["attn_block_cap"] = {"128": 256, "256": "auto", "64": -128}
        doc["pipeline"] = {"max_bucket_bytes": "lots",
                           "reduce_decompose": "reduce_scatter",
                           "unknown_knob": 7}
        doc["serving"] = {"page_size": 16, "decode_window": "wide",
                          "kv_dtype": "fp4", "prefix_share": "yes"}
        _write(root / f"dispatch_prefs.{key}.json", doc)
        t = _dispatch.dispatch_tables()
        assert t.attn_block_cap == {"128": 256}
        # bad max_bucket_bytes dropped, good reduce_decompose kept
        assert t.pipeline == {"reduce_decompose": "reduce_scatter"}
        assert _dispatch.pipeline_pref("max_bucket_bytes") is None
        # serving: good page_size kept; out-of-domain kv_dtype,
        # non-bool prefix_share, and non-int window all dropped
        assert t.serving == {"page_size": 16}
        assert _dispatch.serving_pref("kv_dtype", "f32") == "f32"
        assert _dispatch.serving_pref("prefix_share", False) is False
        # the routing table survived its siblings' bad entries
        assert not _dispatch.op_enabled("multi_tensor")

    def test_stale_methodology_per_topology_table_warns(
            self, live_dispatch):
        _dispatch, root = live_dispatch
        key = _dispatch.topology_key()
        doc = _good_table(key)
        doc["methodology"] = "dispatch-per-iteration"
        _write(root / f"dispatch_prefs.{key}.json", doc)
        with pytest.warns(RuntimeWarning, match="IGNORED"):
            assert _dispatch.op_enabled("multi_tensor")

    def test_no_tables_at_all_is_design_default(self, live_dispatch):
        _dispatch, _ = live_dispatch
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert _dispatch.op_enabled("anything")
            assert _dispatch.attn_block_cap(128) is None
            assert _dispatch.pipeline_pref("reduce_decompose",
                                           "psum") == "psum"


class TestCachedAccessor:
    def test_rewritten_file_invalidates_via_mtime(self, live_dispatch):
        _dispatch, root = live_dispatch
        key = _dispatch.topology_key()
        p = root / f"dispatch_prefs.{key}.json"
        _write(p, _good_table(key))
        assert not _dispatch.op_enabled("multi_tensor")
        doc = _good_table(key)
        doc["prefer_pallas"]["multi_tensor"] = True
        _write(p, doc)
        os.utime(p, (os.path.getmtime(p) + 2,) * 2)
        assert _dispatch.op_enabled("multi_tensor")

    def test_explicit_invalidate(self, live_dispatch):
        _dispatch, root = live_dispatch
        key = _dispatch.topology_key()
        p = root / f"dispatch_prefs.{key}.json"
        _write(p, _good_table(key))
        assert not _dispatch.op_enabled("multi_tensor")
        p.unlink()
        _dispatch.invalidate_prefs_cache()
        assert _dispatch.op_enabled("multi_tensor")

    def test_install_prefs_steers_without_reload(self, live_dispatch):
        _dispatch, _ = live_dispatch
        key = _dispatch.topology_key()
        assert _dispatch.op_enabled("multi_tensor")   # design default
        t = _dispatch.install_prefs(_good_table(key))
        assert t.source == "<installed>"
        assert not _dispatch.op_enabled("multi_tensor")
        assert _dispatch.attn_block_cap(128) == 512
        assert _dispatch.pipeline_pref("max_bucket_bytes") == 1 << 25
        # prefs_disabled classification works through the accessor
        assert _dispatch.prefs_disabled("multi_tensor")
        _dispatch.install_prefs(None)
        assert _dispatch.op_enabled("multi_tensor")

    def test_install_rejects_stale_or_foreign_tables(
            self, live_dispatch):
        _dispatch, _ = live_dispatch
        doc = _good_table(_dispatch.topology_key())
        doc["methodology"] = "dispatch-per-iteration"
        with pytest.raises(ValueError, match="IGNORED"):
            _dispatch.install_prefs(doc)
        with pytest.raises(ValueError, match="topology"):
            _dispatch.install_prefs(_good_table("tpu_v4-8"))
        # ...unless the caller explicitly opts out of the check
        t = _dispatch.install_prefs(_good_table("tpu_v4-8"),
                                    check_topology=False)
        assert not _dispatch.op_enabled("multi_tensor")
        assert t.topology == "tpu_v4-8"
        _dispatch.install_prefs(None)

    def test_topology_block_shape(self):
        from apex_tpu.ops import _dispatch
        b = _dispatch.topology_block()
        assert b["key"] == _dispatch.topology_key()
        assert b["device_count"] >= 1 and b["device_kind"]
        assert at.validate_table(
            {**_good_table(), "topology": b}, per_topology=True) == []


class TestAutoKnobConsumers:
    def test_flat_pipeline_auto_resolves_from_table(self,
                                                    live_dispatch):
        import jax.numpy as jnp

        from apex_tpu import amp
        _dispatch, _ = live_dispatch
        _dispatch.install_prefs(_good_table(_dispatch.topology_key()))
        try:
            params = {"w": jnp.ones((64,), jnp.float32)}
            pipe = amp.FlatGradPipeline(params=params,
                                        reduce_decompose="auto",
                                        max_bucket_bytes="auto")
            assert pipe.reduce_decompose == "reduce_scatter"
            assert pipe.max_bucket_bytes == 1 << 25
        finally:
            _dispatch.install_prefs(None)

    def test_flat_pipeline_auto_defers_to_supplied_plan(
            self, live_dispatch):
        import jax.numpy as jnp

        from apex_tpu import amp
        from apex_tpu.multi_tensor_apply.packer import BucketPlan
        _dispatch, _ = live_dispatch
        _dispatch.install_prefs(_good_table(_dispatch.topology_key()))
        try:
            params = {"w": jnp.ones((64,), jnp.float32)}
            plan = BucketPlan.from_tree(params)
            # "auto" + an explicit plan: the plan owns its chunking —
            # no conflict error, no silent re-chunk
            pipe = amp.FlatGradPipeline(plan=plan,
                                        max_bucket_bytes="auto")
            assert pipe.max_bucket_bytes == getattr(
                plan, "max_bucket_bytes", None)
        finally:
            _dispatch.install_prefs(None)

    def test_ddp_auto_resolves_from_table(self, live_dispatch):
        from apex_tpu.parallel import DistributedDataParallel
        _dispatch, _ = live_dispatch
        _dispatch.install_prefs(_good_table(_dispatch.topology_key()))
        try:
            ddp = DistributedDataParallel(lambda p, x: x,
                                          reduce_decompose="auto")
            assert ddp.reduce_decompose == "reduce_scatter"
        finally:
            _dispatch.install_prefs(None)
        ddp = DistributedDataParallel(lambda p, x: x,
                                      reduce_decompose="auto")
        assert ddp.reduce_decompose == "psum"


# ---------------------------------------------------------------------------
# acceptance: the full --cpu-smoke pipeline in tier-1
# ---------------------------------------------------------------------------

def test_cpu_smoke_end_to_end(tmp_path, monkeypatch):
    """sweep -> schema-valid per-topology table -> installed table
    demonstrably changes >= 1 dispatch decision (via the accessor) ->
    perf_budget row restamped with sweep provenance.  Runs the REAL
    tools/autotune.py main in-process (tiny fixed candidate lists,
    interpret mode)."""
    from apex_tpu.ops import _dispatch

    # undo conftest's neutralization: the demonstration must flow
    # through the live accessor
    monkeypatch.setattr(_dispatch, "_PREFS", None)
    monkeypatch.setattr(_dispatch, "_ATTN_CAPS", None)
    monkeypatch.setattr(_dispatch, "_PIPELINE", None)
    monkeypatch.setattr(_dispatch, "_INSTALLED", None)
    monkeypatch.setattr(_dispatch, "_CACHE", None)
    out = tmp_path / "autotune"

    assert at.main(["--cpu-smoke", "--out", str(out)]) == 0

    key = _dispatch.topology_key()
    table_path = out / f"dispatch_prefs.{key}.json"
    assert table_path.exists()
    # schema-valid per the SAME validator check.sh runs
    assert at.validate_paths([str(table_path)]) == []
    doc = json.loads(table_path.read_text())
    assert doc["schema"] == _dispatch.SCHEMA_VERSION
    assert doc["methodology"] == "amortized"
    assert doc["topology"]["key"] == key
    assert doc["noise_floor_pct"] >= 0
    assert doc["sweep"]["records"]           # provenance retained

    summary = json.loads((out / "autotune_summary.json").read_text())
    # the sweep demonstrated (through install_prefs + the accessor)
    # that installing the table changes at least one dispatch decision
    assert summary["decision_changes"], summary
    # ...and the demonstration is reproducible here, via the accessor
    before = {c["decision"]: c["before"]
              for c in summary["decision_changes"]}
    _dispatch.install_prefs(doc)
    try:
        for c in summary["decision_changes"]:
            name = c["decision"]
            if name.startswith("op_enabled:"):
                got = _dispatch.op_enabled(name.split(":", 1)[1])
            elif name.startswith("attn_block_cap:"):
                got = _dispatch.attn_block_cap(name.split(":", 1)[1])
            elif name == "pipeline:max_bucket_bytes":
                got = _dispatch.pipeline_pref("max_bucket_bytes")
            else:
                got = _dispatch.pipeline_pref("reduce_decompose",
                                              "psum")
            assert got == c["after"] and got != before[name], c
    finally:
        _dispatch.install_prefs(None)

    # the budget COPY (never the repo file) gained sweep provenance
    assert summary["budget_rows_restamped"]
    budget = json.loads((out / "perf_budget.json").read_text())
    for row in summary["budget_rows_restamped"]:
        stamp = budget["metrics"][row]["restamped"]
        assert stamp["by"] == "tools/autotune.py"
        assert stamp["mode"] == "cpu-smoke"
        assert stamp["topology"] == key
    # a cpu restamp must not move the gate's auto-mode anchor
    with open(os.path.join(_ROOT, "tools", "perf_budget.json")) as f:
        assert budget["stamped_at"] == json.load(f)["stamped_at"]

    # device-timeline cross-check ran for any flipped routing family
    # the smoke config nominates for checking
    records = json.loads(
        (out / "autotune_summary.json").read_text())["sweep_records"]
    routing = [r for r in records if r.get("space") == "routing"]
    assert routing
    for r in routing:
        flip = r.get("decision", {}).get("prefer_pallas", {})
        if r["family"] == "multi_tensor" and flip \
                and not all(flip.values()):
            assert "device_check" in r, r
