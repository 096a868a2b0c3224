"""Examples tier under CI: run the fast examples in-process (reference
model: examples are the reference's L6 layer; keeping them green is part
of the public contract — SURVEY.md §1)."""

import runpy
import sys

import pytest


def _run(path, argv):
    old = sys.argv
    sys.argv = [path] + argv
    try:
        runpy.run_path(path, run_name="__main__")
    finally:
        sys.argv = old


def test_train_multiproc_via_launcher():
    """The reference's torch.distributed.launch example flow, end to
    end: launcher -> N processes -> initialize_distributed handshake ->
    cross-process grad all-reduce -> converging loss on every rank."""
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    for k in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_COORDINATOR_ADDRESS",
              "COORDINATOR_ADDRESS", "WORLD_SIZE", "RANK",
              "NUM_PROCESSES", "PROCESS_ID", "APEX_TPU_SMOKE"):
        env.pop(k, None)
    env["PYTHONPATH"] = root
    p = subprocess.run(
        [sys.executable, "-m", "apex_tpu.launch", "--nproc", "2",
         os.path.join("examples", "simple", "distributed",
                      "train_multiproc.py")],
        capture_output=True, text=True, env=env, cwd=root, timeout=300)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "[rank 0] OK" in p.stdout and "[rank 1] OK" in p.stdout


def test_train_toy_runs_and_converges(capsys):
    _run("examples/simple/train_toy.py", [])
    assert "OK: loss" in capsys.readouterr().out


def test_train_toy_preempt_and_resume(tmp_path, capsys):
    """Kill-and-resume the toy run — the acceptance flow a
    preemptible-fleet user copies: a preemption notice produces one
    final durable checkpoint and a clean exit; rerunning with the same
    --checkpoint-dir resumes from that step and finishes; and the
    checkpoint telemetry (ckpt/* counters, checkpoint/* spans) renders
    on the summarize surface."""
    ckpt = str(tmp_path / "ckpt")
    tel = str(tmp_path / "telemetry")
    _run("examples/simple/train_toy.py",
         ["--steps", "24", "--save-every", "6",
          "--checkpoint-dir", ckpt, "--preempt-at-step", "10"])
    out = capsys.readouterr().out
    assert "preempted: final checkpoint durable at step 10" in out
    assert "OK" not in out                  # partial run: no bar
    _run("examples/simple/train_toy.py",
         ["--steps", "24", "--save-every", "6",
          "--checkpoint-dir", ckpt, "--telemetry-dir", tel])
    out = capsys.readouterr().out
    assert "resumed at step 10" in out and "OK: resumed" in out
    from apex_tpu.telemetry.cli import main as telemetry_cli
    assert telemetry_cli(["summarize", tel]) == 0
    out = capsys.readouterr().out
    assert "ckpt/save_ms" in out and "checkpoint/save" in out


def test_train_toy_watchdog_self_heals_nan_fault(tmp_path, capsys):
    """The self-healing acceptance flow: an injected NaN fault storms
    past the scaler's backoff, the watchdog detects the streak at a
    window flush, rolls back to the last-known-good checkpoint,
    replays to completion — and the anomaly timeline (detection +
    rollback action) renders on the summarize surface."""
    import warnings as _warnings

    ckpt = str(tmp_path / "ckpt")
    tel = str(tmp_path / "telemetry")
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")      # the rollback warns: fine
        _run("examples/simple/train_toy.py",
             ["--steps", "48", "--save-every", "6",
              "--checkpoint-dir", ckpt, "--telemetry-dir", tel,
              "--watchdog", "--inject-nan-at", "20"])
    out = capsys.readouterr().out
    assert "run self-healed" in out
    assert "OK:" in out                       # replay converged
    from apex_tpu.telemetry.cli import main as telemetry_cli
    assert telemetry_cli(["summarize", tel]) == 0
    out = capsys.readouterr().out
    assert "anomaly timeline:" in out
    assert "nan_streak" in out and "rollback" in out


def test_train_toy_fleet_kill_one_host_shrinks_and_recovers(tmp_path,
                                                            capsys):
    """The multi-host failure-domain acceptance flow: one faked host
    of the toy's 3-host fleet stops beaconing mid-run, the survivors
    agree on the death within the step-lag deadline, shrink, restore
    the last checkpoint and replay to completion — and the whole
    sequence (beacon gap -> host_dead -> shrink -> resume) renders as
    the fleet timeline on the summarize surface."""
    import warnings as _warnings

    ckpt = str(tmp_path / "ckpt")
    tel = str(tmp_path / "telemetry")
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")      # the recovery warns: fine
        _run("examples/simple/train_toy.py",
             ["--steps", "48", "--save-every", "6",
              "--checkpoint-dir", ckpt, "--telemetry-dir", tel,
              "--fleet", "--kill-host-at", "20"])
    out = capsys.readouterr().out
    assert "fleet: 3 hosts (2 simulated peers)" in out
    assert "shrank to healthy mesh" in out
    assert "OK:" in out                       # replay converged
    from apex_tpu.telemetry.cli import main as telemetry_cli
    assert telemetry_cli(["summarize", tel]) == 0
    out = capsys.readouterr().out
    assert "fleet timeline:" in out
    assert "host_dead" in out and "shrink" in out
    assert "fleet/hosts_dead" in out          # counters table rows


def test_train_toy_live_metrics_scrape_and_incident_timeline(
        tmp_path, capsys):
    """The live-observability acceptance flow: train with
    --serve-metrics while a background scraper polls /metrics.  The
    fleet death + the injected NaN storm must FLIP the exported
    gauges mid-run (fleet_hosts_dead / watchdog rollback totals go
    0 -> >=1, monotone so the scraper cannot miss them), and
    afterwards the whole beacon-gap -> agreement -> shrink -> replay
    chain must share ONE incident_id — rendered by ``telemetry
    timeline`` as a single closed incident."""
    import json as _json
    import socket
    import threading
    import urllib.request
    import warnings as _warnings

    ckpt = str(tmp_path / "ckpt")
    tel = str(tmp_path / "telemetry")
    with socket.socket() as s:                # pick a free port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    samples, stop = [], threading.Event()

    def scrape():
        url = f"http://127.0.0.1:{port}/metrics"
        while not stop.is_set():
            try:
                with urllib.request.urlopen(url, timeout=1) as r:
                    body = r.read().decode()
                g = {}
                for line in body.splitlines():
                    if not line.startswith("#") and " " in line:
                        n, v = line.rsplit(" ", 1)
                        g[n] = float(v)
                samples.append(g)
            except OSError:
                pass                          # server not up/gone yet
            stop.wait(0.005)

    t = threading.Thread(target=scrape, daemon=True)
    t.start()
    try:
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")  # the recoveries warn
            _run("examples/simple/train_toy.py",
                 ["--steps", "64", "--save-every", "6",
                  "--checkpoint-dir", ckpt, "--telemetry-dir", tel,
                  "--fleet", "--kill-host-at", "40",
                  "--watchdog", "--inject-nan-at", "18",
                  "--serve-metrics", str(port)])
    finally:
        stop.set()
        t.join(timeout=5)
    out = capsys.readouterr().out
    assert f"serving live metrics at http://127.0.0.1:{port}" in out
    assert "shrank to healthy mesh" in out
    assert "run self-healed" in out
    assert len(samples) > 2                   # genuinely scraped live
    # the gauges FLIPPED mid-run: an early scrape predates both
    # incidents, a later one carries them (totals are monotone)
    dead = [g.get("apex_tpu_fleet_hosts_dead_total", 0.0)
            for g in samples]
    assert dead[0] == 0.0 and max(dead) >= 1.0
    last = samples[-1]
    assert last.get("apex_tpu_fleet_mesh_shrinks_total", 0) >= 1
    assert last.get("apex_tpu_watchdog_rollback_events_total", 0) >= 1
    assert last.get("apex_tpu_anomaly_nan_streak_events_total", 0) >= 1
    assert last.get("apex_tpu_exported_step", -1) > 0
    # the shrink chain shares ONE incident_id end to end
    recs = []
    with open(tmp_path / "telemetry" / "telemetry.jsonl",
              encoding="utf-8") as f:
        for line in f:
            recs.append(_json.loads(line))
    by_ev = {}
    for r in recs:
        if r.get("kind") == "fleet" and "incident_id" in r:
            by_ev.setdefault(r["event"], set()).add(r["incident_id"])
    assert by_ev["host_dead"] == by_ev["shrink"] \
        == by_ev["replay_complete"]
    assert len(by_ev["shrink"]) == 1
    from apex_tpu.telemetry.cli import main as telemetry_cli
    assert telemetry_cli(["timeline", tel, "--json"]) == 0
    doc = _json.loads(capsys.readouterr().out)
    shrink_incs = [i for i in doc["incidents"]
                   if any(e.get("event") == "shrink"
                          for e in i["events"])]
    assert len(shrink_incs) == 1
    inc = shrink_incs[0]
    assert inc["closed"] and inc["opened_by"] == "fleet:host_dead"
    evs = [e.get("event") or e.get("action") for e in inc["events"]]
    assert "host_dead" in evs and "shrink" in evs \
        and "replay_complete" in evs


def test_train_toy_revive_host_admits_and_grows(tmp_path, capsys):
    """The elastic scale-UP acceptance flow, end to end: kill ->
    shrink -> return -> admit -> grow.  The killed peer comes back
    under a fresh incarnation, the members admit it at a step
    boundary, the mesh grows back to full strength and the checkpoint
    reshards onto it — with the whole timeline (host_dead -> shrink ->
    host_return -> grow) visible in ``telemetry summarize``."""
    import warnings as _warnings

    ckpt = str(tmp_path / "ckpt")
    tel = str(tmp_path / "telemetry")
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")      # the recoveries warn: fine
        _run("examples/simple/train_toy.py",
             ["--steps", "60", "--save-every", "6",
              "--checkpoint-dir", ckpt, "--telemetry-dir", tel,
              "--fleet", "--kill-host-at", "16",
              "--revive-host-at", "34"])
    out = capsys.readouterr().out
    assert "shrank to healthy mesh" in out
    assert "grew back to full mesh" in out
    assert "OK:" in out                       # replay converged
    from apex_tpu.telemetry.cli import main as telemetry_cli
    assert telemetry_cli(["summarize", tel]) == 0
    out = capsys.readouterr().out
    assert "fleet timeline:" in out
    assert "host_dead" in out and "shrink" in out
    assert "host_return" in out and "grow" in out
    assert "fleet/mesh_grows" in out          # counters table row


def test_serve_gpt_chaos_scrape_and_incident_timeline(tmp_path,
                                                      capsys):
    """The serving acceptance flow: the engine demo decodes with
    --port while a background scraper polls /metrics, and
    --inject-hung-decode-at drives detect -> evict -> re-admit.  A
    mid-run scrape must carry the ``serving_*`` gauges, and the whole
    failover chain (hung_decode -> eviction -> resolution) must share
    ONE incident id rendered by ``telemetry timeline --json`` as a
    single closed incident."""
    import json as _json
    import socket
    import threading
    import urllib.request

    tel = str(tmp_path / "telemetry")
    with socket.socket() as s:                # pick a free port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    samples, stop = [], threading.Event()

    def scrape():
        url = f"http://127.0.0.1:{port}/metrics"
        while not stop.is_set():
            try:
                with urllib.request.urlopen(url, timeout=1) as r:
                    body = r.read().decode()
                g = {}
                for line in body.splitlines():
                    if not line.startswith("#") and " " in line \
                            and "{" not in line:
                        n, v = line.rsplit(" ", 1)
                        g[n] = float(v)
                samples.append(g)
            except OSError:
                pass                          # server not up/gone yet
            stop.wait(0.005)

    t = threading.Thread(target=scrape, daemon=True)
    t.start()
    try:
        _run("examples/gpt/serve.py",
             ["--requests", "5", "--max-new-tokens", "10",
              "--telemetry-dir", tel, "--port", str(port),
              "--inject-hung-decode-at", "3"])
    finally:
        stop.set()
        t.join(timeout=5)
    out = capsys.readouterr().out
    assert f"serving live metrics at http://127.0.0.1:{port}" in out
    assert "re-admitting evicted request" in out
    assert "incident chain: inc-001-hung_decode-e0 [closed]" in out
    assert "OK:" in out
    assert len(samples) > 2                   # genuinely scraped live
    # a MID-RUN scrape carries the serving gauges
    mid = [g for g in samples
           if "apex_tpu_serving_queue_depth" in g]
    assert mid, "no scrape saw serving gauges"
    last = samples[-1]
    assert last.get("apex_tpu_serving_completed_total", 0) >= 4
    assert last.get("apex_tpu_serving_evictions_total", 0) >= 1
    assert last.get(
        "apex_tpu_serving_hung_decode_events_total", 0) >= 1
    assert "apex_tpu_serving_p99_token_ms" in last
    # the failover chain shares ONE incident id end to end
    from apex_tpu.telemetry.cli import main as telemetry_cli
    assert telemetry_cli(["timeline", tel, "--json"]) == 0
    doc = _json.loads(capsys.readouterr().out)
    assert len(doc["incidents"]) == 1
    inc = doc["incidents"][0]
    assert inc["incident_id"] == "inc-001-hung_decode-e0"
    assert inc["closed"]
    assert inc["opened_by"] == "serving:hung_decode"
    evs = [e.get("event") for e in inc["events"]]
    assert "hung_decode" in evs and "request_evicted" in evs \
        and "incident_resolved" in evs


def test_serve_gpt_shared_prefix_int8_gauges_live_and_summarize(
        tmp_path, capsys):
    """The serving memory frontier demo: --shared-system-prompt +
    --kv-dtype int8 + --sample decodes with --port while a background
    scraper polls /metrics.  A MID-RUN scrape must carry the prefix-
    sharing gauges (``apex_tpu_serving_prefix_hits`` /
    ``_kv_bytes_saved``), and ``telemetry summarize`` renders the same
    counters afterwards — the step-less serving run has a summarize
    surface too."""
    import socket
    import threading
    import urllib.request

    tel = str(tmp_path / "telemetry")
    with socket.socket() as s:                # pick a free port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    samples, stop = [], threading.Event()

    def scrape():
        url = f"http://127.0.0.1:{port}/metrics"
        while not stop.is_set():
            try:
                with urllib.request.urlopen(url, timeout=1) as r:
                    body = r.read().decode()
                g = {}
                for line in body.splitlines():
                    if not line.startswith("#") and " " in line \
                            and "{" not in line:
                        n, v = line.rsplit(" ", 1)
                        g[n] = float(v)
                samples.append(g)
            except OSError:
                pass                          # server not up/gone yet
            stop.wait(0.005)

    t = threading.Thread(target=scrape, daemon=True)
    t.start()
    try:
        _run("examples/gpt/serve.py",
             ["--requests", "5", "--max-new-tokens", "10",
              "--kv-dtype", "int8", "--sample", "0.8:0.95",
              "--shared-system-prompt",
              "--telemetry-dir", tel, "--port", str(port)])
    finally:
        stop.set()
        t.join(timeout=5)
    out = capsys.readouterr().out
    assert "'quantized': True" in out and "'dtype': 'int8'" in out
    assert "prefix sharing:" in out
    assert "OK:" in out
    assert len(samples) > 2                   # genuinely scraped live
    # a MID-RUN scrape carries the prefix-sharing gauges
    mid = [g for g in samples
           if "apex_tpu_serving_prefix_hits" in g]
    assert mid, "no scrape saw the prefix gauges"
    assert any(g.get("apex_tpu_serving_kv_bytes_saved", 0) > 0
               for g in samples)
    last = samples[-1]
    assert last.get("apex_tpu_serving_prefix_hits", 0) >= 1
    # ...and the counters land on the summarize surface afterwards
    from apex_tpu.telemetry.cli import main as telemetry_cli
    assert telemetry_cli(["summarize", tel]) == 0
    summary = capsys.readouterr().out
    assert "serving/prefix_hits" in summary
    assert "serving/kv_bytes_saved" in summary


def test_serve_gpt_speculative_int8_weights_gauges_live(
        tmp_path, capsys):
    """The compute frontier demo: --speculate + --weight-dtype int8 +
    --prefill-batch decodes with --port while a background scraper
    polls /metrics.  A MID-RUN scrape must carry the speculation
    counters (``apex_tpu_serving_spec_accepted`` / ``_drafted``), and
    the final stdout summary reports the accept rate and the batched
    prefill program-call count."""
    import socket
    import threading
    import urllib.request

    tel = str(tmp_path / "telemetry")
    with socket.socket() as s:                # pick a free port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    samples, stop = [], threading.Event()

    def scrape():
        url = f"http://127.0.0.1:{port}/metrics"
        while not stop.is_set():
            try:
                with urllib.request.urlopen(url, timeout=1) as r:
                    body = r.read().decode()
                g = {}
                for line in body.splitlines():
                    if not line.startswith("#") and " " in line \
                            and "{" not in line:
                        n, v = line.rsplit(" ", 1)
                        g[n] = float(v)
                samples.append(g)
            except OSError:
                pass                          # server not up/gone yet
            stop.wait(0.005)

    t = threading.Thread(target=scrape, daemon=True)
    t.start()
    try:
        _run("examples/gpt/serve.py",
             ["--requests", "4", "--max-new-tokens", "8",
              "--speculate", "2", "--weight-dtype", "int8",
              "--prefill-batch", "2",
              "--telemetry-dir", tel, "--port", str(port)])
    finally:
        stop.set()
        t.join(timeout=5)
    out = capsys.readouterr().out
    assert "speculation: K=2" in out
    assert "batched prefill:" in out
    assert "OK:" in out
    assert len(samples) > 2                   # genuinely scraped live
    # a MID-RUN scrape carries the speculation counters
    mid = [g for g in samples
           if "apex_tpu_serving_spec_accepted" in g]
    assert mid, "no scrape saw the speculation counters"
    last = samples[-1]
    assert last.get("apex_tpu_serving_spec_drafted", 0) > 0
    assert last.get("apex_tpu_serving_spec_accepted", 0) >= 0


def test_serve_gpt_trace_dir_slo_histograms_live(tmp_path, capsys):
    """The observability acceptance flow: --trace-dir records request
    lifecycle traces while --port serves live metrics.  A MID-RUN
    scrape must carry the Prometheus SLO histograms
    (``apex_tpu_serving_ttft_ms_bucket``), the dumped reqtrace.jsonl
    must be gap-free for every request, and ``telemetry summarize``
    renders the per-run SLO table off the same dir."""
    import json as _json
    import os
    import socket
    import threading
    import urllib.request

    trace = str(tmp_path / "trace")
    with socket.socket() as s:                # pick a free port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    bodies, stop = [], threading.Event()

    def scrape():
        url = f"http://127.0.0.1:{port}/metrics"
        while not stop.is_set():
            try:
                with urllib.request.urlopen(url, timeout=1) as r:
                    bodies.append(r.read().decode())
            except OSError:
                pass                          # server not up/gone yet
            stop.wait(0.005)

    t = threading.Thread(target=scrape, daemon=True)
    t.start()
    try:
        _run("examples/gpt/serve.py",
             ["--requests", "4", "--max-new-tokens", "8",
              "--trace-dir", trace, "--port", str(port)])
    finally:
        stop.set()
        t.join(timeout=5)
    out = capsys.readouterr().out
    assert "request traces written to" in out
    assert "SLO summary" in out
    assert "OK:" in out
    assert len(bodies) > 2                    # genuinely scraped live
    # a MID-RUN scrape carries the Prometheus SLO histograms — the
    # third metric class next to gauges and counters
    mid = [b for b in bodies
           if "apex_tpu_serving_ttft_ms_bucket" in b]
    assert mid, "no scrape saw the SLO histograms"
    last = mid[-1]
    assert "# TYPE apex_tpu_serving_ttft_ms histogram" in last
    assert 'apex_tpu_serving_ttft_ms_bucket{le="+Inf"}' in last
    assert "apex_tpu_serving_ttft_ms_sum" in last
    assert "apex_tpu_serving_ttft_ms_count" in last
    # the dumped trace file is gap-free for every request
    from apex_tpu.telemetry import trace_gaps
    with open(os.path.join(trace, "reqtrace.jsonl")) as f:
        recs = [_json.loads(line) for line in f]
    assert len(recs) == 4
    for rec in recs:
        assert rec["verdict"] == "completed"
        assert trace_gaps(rec) == [], rec
    # ...and the SLO table renders off the same dir
    from apex_tpu.telemetry.cli import main as telemetry_cli
    assert telemetry_cli(["summarize", trace]) == 0
    summary = capsys.readouterr().out
    assert "serving SLO:" in summary
    assert "ttft_ms" in summary


def test_imagenet_preempt_and_resume(tmp_path, capsys):
    """The imagenet example's save path rides the same resilience
    manager: --checkpoint-dir rotates bucket-native checkpoints and a
    preemption notice leaves a resumable final one."""
    ckpt = str(tmp_path / "ckpt")
    common = ["--cpu", "--batch-size", "2", "--image-size", "32",
              "--arch", "resnet18", "--save-every", "3",
              "--checkpoint-dir", ckpt]
    _run("examples/imagenet/main_amp.py",
         common + ["--steps", "6", "--preempt-at-step", "4"])
    out = capsys.readouterr().out
    assert "preempted: final checkpoint durable at step 4" in out
    _run("examples/imagenet/main_amp.py", common + ["--steps", "6"])
    out = capsys.readouterr().out
    # --steps is the TOTAL: the resumed run finishes at 6, not 4+6
    assert "resumed at step 4" in out and "(step 6)" in out


# --steps 3: the examples time from after their 2 warm-up steps (the
# compile step and the second-call variant), so 3 is the least that
# prints a throughput line


def test_imagenet_tiny_cpu(capsys):
    _run("examples/imagenet/main_amp.py",
         ["--cpu", "--steps", "3", "--batch-size", "2",
          "--image-size", "32", "--arch", "resnet18"])
    assert "throughput" in capsys.readouterr().out


def test_imagenet_grad_accum_flat(capsys):
    # microbatches= adoption: the flat-accumulation path (ISSUE 10)
    # drives the same loop — 2 microbatches per step, fused adds, the
    # latched found_inf feeding the branch-free skip
    _run("examples/imagenet/main_amp.py",
         ["--cpu", "--steps", "3", "--batch-size", "4",
          "--image-size", "32", "--arch", "resnet18",
          "--grad-accum", "2"])
    out = capsys.readouterr().out
    assert "throughput" in out and "grad-accum 2 (flat)" in out


def test_imagenet_space_to_depth_stem(capsys):
    # the MXU-efficient stem bench.py enables on hardware, reachable
    # from the reference-shaped CLI too
    _run("examples/imagenet/main_amp.py",
         ["--cpu", "--steps", "3", "--batch-size", "2",
          "--image-size", "32", "--arch", "resnet18",
          "--stem-space-to-depth"])
    assert "throughput" in capsys.readouterr().out


def test_dcgan_two_scalers(capsys):
    _run("examples/dcgan/main_amp.py",
         ["--cpu", "--steps", "2", "--batch-size", "4"])
    out = capsys.readouterr().out
    assert "loss_scaler0" in out and "loss_scaler1" in out


@pytest.mark.slow
def test_bert_pretrain_mlm_tiny(capsys):
    _run("examples/bert/pretrain_mlm.py",
         ["--cpu", "--steps", "3"])
    assert "step time" in capsys.readouterr().out


@pytest.mark.slow
def test_bert_pretrain_mlm_packed(capsys):
    _run("examples/bert/pretrain_mlm.py",
         ["--cpu", "--steps", "3", "--packed"])
    out = capsys.readouterr().out
    assert "packed" in out and "step time" in out


@pytest.mark.slow
def test_gpt_block_tiny(capsys):
    _run("examples/gpt/train_block.py",
         ["--cpu", "--steps", "2", "--layers", "1", "--hidden", "64",
          "--heads", "4", "--seq-len", "64", "--batch-size", "2"])
    assert "step time" in capsys.readouterr().out


@pytest.mark.slow
def test_gpt_looped_tiny(capsys):
    _run("examples/gpt/train_looped.py",
         ["--cpu", "--steps", "4", "--layers", "1", "--passes", "2"])
    out = capsys.readouterr().out
    assert "looped decoder L1 x2" in out and "step time" in out


def test_gpt_moe_tiny(capsys):
    _run("examples/gpt/train_moe.py",
         ["--cpu", "--steps", "3", "--layers", "1"])
    out = capsys.readouterr().out
    assert "sparse-attention expert decoder L1" in out
    assert "4/16 experts top-2" in out and "step time" in out


def test_gpt_kimi_linear_tiny(capsys):
    example = runpy.run_path("examples/gpt/train_kimi_linear.py")
    summary = example["main"](["--cpu", "--steps", "3", "--layers", "4"])
    out = capsys.readouterr().out
    assert "hybrid linear-attention expert decoder L4" in out
    assert "4/16 experts top-2 + shared" in out and "step time" in out
    assert summary["found_inf"] == 0
    assert len(summary["expert_counts"]) == 3       # layers 2-4 hold experts


def test_gpt_nemotron_h_tiny(capsys):
    example = runpy.run_path("examples/gpt/train_nemotron_h.py")
    summary = example["main"](["--cpu", "--steps", "3", "--layers", "7"])
    out = capsys.readouterr().out
    assert "hybrid Mamba-2 expert decoder MEMEM*E" in out
    assert "4/16 experts top-2 + shared" in out and "step time" in out
    assert summary["found_inf"] == 0
    assert len(summary["expert_counts"]) == 3       # layers 1, 3, 6


def test_train_tp_converges(capsys):
    _run("examples/simple/train_tp.py", [])
    assert "OK: loss" in capsys.readouterr().out


def test_train_ddp_converges(capsys):
    _run("examples/simple/distributed/train_ddp.py", [])
    assert "OK: loss" in capsys.readouterr().out


def test_train_pp_1f1b_converges(capsys):
    _run("examples/simple/train_pp.py", [])
    assert "OK: loss" in capsys.readouterr().out


def test_train_pp_interleaved_converges(capsys):
    _run("examples/simple/train_pp.py", ["--virtual", "2"])
    out = capsys.readouterr().out
    assert "OK: loss" in out and "interleaved-1F1B V=2" in out


def test_train_4d_gpt_converges_with_grad_accum(capsys):
    # microbatches= adoption on the per-leaf path (3-axis-sharded
    # state: the packer declines by design, the scan oracle runs)
    _run("examples/gpt/train_4d.py", ["--steps", "8", "--accum", "2"])
    assert "OK:" in capsys.readouterr().out


def test_train_4d_gpt_converges(capsys):
    _run("examples/gpt/train_4d.py", ["--steps", "8"])
    out = capsys.readouterr().out
    assert "OK: loss" in out and "pp=2x2chunks" in out
