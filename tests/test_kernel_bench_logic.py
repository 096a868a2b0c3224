"""Pure-logic tests for the hardware kernel-bench distillers: the
pieces that turn measured timings into dispatch tables must be right
BEFORE chip time is spent running them."""

import importlib.util
import json
import os


def _load_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    return _load_path(name, os.path.join(_ROOT, "tools", f"{name}.py"))


kb = _load_tool("kernel_bench")
ps = _load_tool("profile_step")


def _load_bench():
    return _load_path("bench_mod", os.path.join(_ROOT, "bench.py"))


class TestSelectAttnCaps:
    def test_lowest_mean_relative_time_wins(self):
        caps = kb.select_attn_caps({
            (128, 128): [1.5, 1.2],
            (128, 256): [1.0, 1.1],
            (128, 512): [1.3, 1.0],
        })
        assert caps == {"128": 256}

    def test_partial_sample_cannot_win(self):
        # cap 1024 was only feasible on the long-sequence shape and won
        # there, but must not become the tier default on one sample
        caps = kb.select_attn_caps({
            (128, 256): [1.0, 1.0],
            (128, 512): [1.1, 1.2],
            (128, 1024): [0.8],
        })
        assert caps == {"128": 256}

    def test_per_dp_winners_are_independent(self):
        caps = kb.select_attn_caps({
            (128, 256): [1.0],
            (128, 512): [1.4],
            (256, 128): [1.0],
            (256, 512): [1.6],
        })
        assert caps == {"128": 256, "256": 128}

    def test_empty(self):
        assert kb.select_attn_caps({}) == {}


class TestWritePrefs:
    def test_merge_preserves_attn_caps(self, tmp_path):
        p = tmp_path / "prefs.json"
        p.write_text(json.dumps({"methodology": "amortized",
                                 "attn_block_cap": {"128": 256}}))
        rows = [
            {"kernel": "fused_layer_norm", "speedup": 1.3, "backend": "tpu"},
            {"kernel": "fused_layer_norm_grad", "speedup": 1.1,
             "backend": "tpu"},
            {"kernel": "flash_attention", "speedup": 0.9, "backend": "tpu"},
        ]
        prefs = kb.write_prefs(rows, str(p))
        doc = json.loads(p.read_text())
        assert doc["attn_block_cap"] == {"128": 256}
        assert doc["prefer_pallas"] == prefs == {
            "layer_norm": True, "attention": False}
        assert doc["backend"] == "tpu"
        # the stamp that lets _load_prefs trust this table's routing
        assert doc["methodology"] == "amortized"

    def test_any_slower_shape_flips_family_to_xla(self, tmp_path):
        p = tmp_path / "prefs.json"
        rows = [
            {"kernel": "flash_attention", "speedup": 1.5, "backend": "tpu"},
            {"kernel": "flash_attention_grad", "speedup": 0.95,
             "backend": "tpu"},
        ]
        assert kb.write_prefs(rows, str(p)) == {"attention": False}

    def test_stale_era_tables_not_laundered(self, tmp_path):
        # read-modify-write + a whole-file methodology stamp must not
        # re-bless the OTHER table's dispatch-per-iteration data: a
        # prefs-only run drops the old caps, a sweep-only merge (via
        # _load_trusted_doc) drops the old routing
        p = tmp_path / "prefs.json"
        p.write_text(json.dumps({
            "methodology": "dispatch-per-iteration",
            "prefer_pallas": {"attention": False},
            "attn_block_cap": {"128": 256}}))
        kb.write_prefs([{"kernel": "welford_mean_var", "speedup": 1.2,
                         "backend": "tpu"}], str(p))
        doc = json.loads(p.read_text())
        assert doc["methodology"] == "amortized"
        assert "attn_block_cap" not in doc       # stale caps dropped
        assert doc["prefer_pallas"] == {"welford": True}

        p.write_text(json.dumps({
            "methodology": "dispatch-per-iteration",
            "prefer_pallas": {"attention": False},
            "attn_block_cap": {"128": 256}}))
        doc = kb._load_trusted_doc(str(p))
        assert "prefer_pallas" not in doc
        assert "attn_block_cap" not in doc

        # an amortized-era doc survives the merge intact
        p.write_text(json.dumps({
            "methodology": "amortized",
            "attn_block_cap": {"128": 512}}))
        kb.write_prefs([{"kernel": "welford_mean_var", "speedup": 1.2,
                         "backend": "tpu"}], str(p))
        assert json.loads(p.read_text())["attn_block_cap"] == {
            "128": 512}

    def test_topology_and_noise_metadata(self, tmp_path):
        """--write-prefs records WHERE (topology block) and HOW
        REPEATABLY (noise floor) the table was measured, making
        hand-run bench output schema-compatible with autotune's
        per-topology tables — and topology-checked at load."""
        at = _load_tool("autotune")
        p = tmp_path / "prefs.json"
        topo = {"key": "tpu_v5e-8", "device_kind": "TPU v5e",
                "device_count": 8, "process_count": 2}
        rows = [{"kernel": "welford_mean_var", "speedup": 1.2,
                 "backend": "tpu"}]
        kb.write_prefs(rows, str(p), topology=topo,
                       noise_floor_pct=3.456)
        doc = json.loads(p.read_text())
        assert doc["topology"] == topo
        assert doc["schema"] == 2
        assert doc["noise_floor_pct"] == 3.46
        # the written table passes the check.sh schema validator
        assert at.validate_table(doc, per_topology=False) == []
        # legacy call shape (no metadata) stays valid and stamp-free
        kb.write_prefs(rows, str(p.with_name("p2.json")))
        doc2 = json.loads(p.with_name("p2.json").read_text())
        assert "topology" not in doc2 and "noise_floor_pct" not in doc2

    def test_stale_era_doc_strips_topology_metadata(self, tmp_path):
        """_load_trusted_doc must not launder a stale-era table's
        topology/noise stamps into the fresh doc (they describe the
        discarded measurements, not the new ones)."""
        p = tmp_path / "prefs.json"
        p.write_text(json.dumps({
            "methodology": "dispatch-per-iteration",
            "topology": {"key": "tpu_v4-8"}, "schema": 2,
            "noise_floor_pct": 1.0,
            "pipeline": {"reduce_decompose": "reduce_scatter"}}))
        doc = kb._load_trusted_doc(str(p))
        for k in ("topology", "schema", "noise_floor_pct", "pipeline"):
            assert k not in doc, k

    def test_corrupt_existing_file_does_not_abort(self, tmp_path):
        p = tmp_path / "prefs.json"
        p.write_text("{truncated")
        rows = [{"kernel": "welford_mean_var", "speedup": 2.0,
                 "backend": "tpu"}]
        assert kb.write_prefs(rows, str(p)) == {"welford": True}
        assert json.loads(p.read_text())["prefer_pallas"] == {
            "welford": True}

    def test_discarded_stale_table_warns(self, tmp_path, monkeypatch):
        """A prefs table dropped for lacking the amortized stamp must
        say so: silence here hid a stale-benchmark misconfiguration
        (the operator believes measured routing is active when the
        design default is)."""
        import pytest
        from apex_tpu.ops import _dispatch
        p = tmp_path / "prefs.json"
        p.write_text(json.dumps({
            "methodology": "dispatch-per-iteration",
            "prefer_pallas": {"softmax": False}}))
        monkeypatch.setattr(_dispatch, "_PREFS_PATH", str(p))
        with pytest.warns(RuntimeWarning, match="IGNORED"):
            assert _dispatch._load_prefs() == ({}, {})

    def test_absent_or_trusted_table_stays_silent(self, tmp_path,
                                                  monkeypatch):
        """Only the DISCARD warns: a missing file and an amortized
        table are both healthy states."""
        import warnings
        from apex_tpu.ops import _dispatch
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            monkeypatch.setattr(_dispatch, "_PREFS_PATH",
                                str(tmp_path / "absent.json"))
            assert _dispatch._load_prefs() == ({}, {})
            good = tmp_path / "good.json"
            good.write_text(json.dumps({
                "methodology": "amortized",
                "prefer_pallas": {"softmax": False}}))
            monkeypatch.setattr(_dispatch, "_PREFS_PATH", str(good))
            assert _dispatch._load_prefs() == ({"softmax": False}, {})


class TestTraceOpSummarizer:
    """profile_step.summarize_device_ops distills the profiler's
    Chrome trace into the top-device-ops table; it must aggregate ONLY
    the device XLA-Ops thread (the round-4 capture had 998909 host
    python events vs 434 device ops — counting hosts would bury the
    signal it exists to surface)."""

    def _write_trace(self, tmp_path, events):
        import gzip
        d = tmp_path / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        with gzip.open(d / "vm.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": events}, f)
        return str(tmp_path)

    def test_aggregates_device_ops_only(self, tmp_path):
        events = [
            {"ph": "M", "pid": 3, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 3, "tid": 7, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 9, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
             "args": {"name": "python"}},
            # device ops: fusion.1 twice (3ms), conv once (1ms)
            {"ph": "X", "pid": 3, "tid": 7, "name": "fusion.1",
             "dur": 2000},
            {"ph": "X", "pid": 3, "tid": 7, "name": "fusion.1",
             "dur": 1000},
            {"ph": "X", "pid": 3, "tid": 7, "name": "conv", "dur": 1000},
            # host noise that must NOT count
            {"ph": "X", "pid": 9, "tid": 1, "name": "python_call",
             "dur": 999999},
            # device process, non-op thread must not count either
            {"ph": "X", "pid": 3, "tid": 8, "name": "Steps",
             "dur": 888888},
        ]
        rows = ps.summarize_device_ops(self._write_trace(tmp_path,
                                                         events))
        assert rows == [["fusion.1", 3.0, 75.0], ["conv", 1.0, 25.0]]

    def test_empty_or_missing_trace(self, tmp_path):
        assert ps.summarize_device_ops(str(tmp_path)) == []
        rows = ps.summarize_device_ops(self._write_trace(
            tmp_path, [{"ph": "M", "pid": 3, "name": "process_name",
                        "args": {"name": "/device:TPU:0"}}]))
        assert rows == []


def test_run_test_suite_map_covers_every_test_file():
    """The reference-shaped suite driver (tests/run_test.py) maps suite
    names onto pytest files; a new test module left out of the map is
    silently skipped by `--include`-style invocations."""
    import glob

    rt = _load_path("run_test_mod",
                    os.path.join(_ROOT, "tests", "run_test.py"))
    mapped = {f for fs in rt.SUITES.values() for f in fs}
    have = {"tests/" + os.path.basename(p)
            for p in glob.glob(os.path.join(_ROOT, "tests",
                                            "test_*.py"))}
    assert have <= mapped, f"unmapped test files: {sorted(have - mapped)}"
    # and no dangling entries: a renamed module must not leave a map
    # entry pytest would abort on
    assert mapped <= have, f"stale suite entries: {sorted(mapped - have)}"


class TestBertPackedVarlenBench:
    """The packed-vs-dense varlen extra must run end to end on a tiny
    model before it spends window time: both legs train, the real-token
    accounting is consistent, and packed fits more real tokens into
    the same device batch."""

    def test_tiny_cpu(self):
        import jax
        import jax.numpy as jnp

        bench = _load_bench()

        from apex_tpu.models.bert import BertModel
        tiny = BertModel(vocab_size=128, hidden_size=32, num_heads=4,
                         num_layers=1, max_seq_len=64,
                         dtype=jnp.float32)
        out = bench.bench_bert_packed_varlen(
            jax, jnp, model=tiny, rows=2, seq=64, steps=2, chunk=2)
        for k in ("bert_varlen_packed_step_ms",
                  "bert_varlen_dense_step_ms",
                  "bert_varlen_packed_real_tokens_per_sec",
                  "bert_varlen_dense_real_tokens_per_sec",
                  "bert_varlen_packed_speedup"):
            assert k in out and out[k] > 0, (k, out)


def test_bench_final_line_carries_measured_at():
    """The child's final bench line must stamp its capture time:
    perf_gate's auto-gating compares it against the budget's
    stamped_at, so a live hardware round without it could NEVER arm
    the gate (it would fall into the 'cannot compare' report-only
    branch forever)."""
    import re

    bench = _load_bench()
    pg = _load_tool("perf_gate")
    out = bench._stamp_measured_at({"backend": "tpu", "value": 1.0})
    assert re.fullmatch(r"\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}Z",
                        out["measured_at"])
    # ...and perf_gate reads exactly this field
    assert pg.round_when(out) == out["measured_at"]
    # an existing stamp is preserved
    assert bench._stamp_measured_at(
        {"measured_at": "2026-07-31T03:41:18Z"})["measured_at"] \
        == "2026-07-31T03:41:18Z"
