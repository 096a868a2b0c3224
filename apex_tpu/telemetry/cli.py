"""``python -m apex_tpu.telemetry summarize <run_dir>...`` — render a
training run's JSONL telemetry as a step table plus span/retrace
summaries (multiple run dirs merge through the timeline front-end:
host-tagged, steps deduped newest-per-(host, step)) — ``... timeline
<run_dir>...`` — merge N hosts' run dirs into one ordered fleet
timeline grouped by incident id (``--json`` / ``--chrome-trace`` for
Perfetto) — and ``... profile <trace_dir>`` — render a captured
profiler trace as the observatory report (step breakdown, collective
overlap, MFU, top ops).  All with no dependency beyond the standard
library (works on a login host with no jax installed)."""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional, Tuple

JSONL_NAME = "telemetry.jsonl"


def load_jsonl(path: str) -> Tuple[Optional[dict], List[dict]]:
    """(schema record or None, all other records).  Unparseable lines
    are skipped (a run killed mid-write leaves a torn last line)."""
    schema, records = None, []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "schema" and schema is None:
                schema = rec
            else:
                records.append(rec)
    return schema, records


def _resolve(path: str) -> Optional[str]:
    if os.path.isdir(path):
        path = os.path.join(path, JSONL_NAME)
    return path if os.path.isfile(path) else None


def _fmt_cell(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def _anomaly_row(r: dict) -> List[str]:
    """One anomaly-timeline row from a ``kind:"anomaly"`` detection or
    a ``kind:"watchdog"`` action event."""
    step = str(r.get("step", "-"))
    if r.get("kind") == "watchdog":
        action = r.get("action", "-")
        detail = []
        if r.get("anomaly"):
            detail.append(f"anomaly={r['anomaly']}")
        if r.get("to_step") is not None:
            detail.append(f"to_step={r['to_step']}")
        if r.get("rollbacks") is not None:
            detail.append(f"rollbacks={r['rollbacks']}")
        if r.get("incident_id"):
            detail.append(f"incident={r['incident_id']}")
        return [step, "action", action, " ".join(detail) or "-"]
    detail = " ".join(f"{k}={_fmt_cell(v)}" for k, v in
                      sorted((r.get("evidence") or {}).items()))
    if r.get("incident_id"):
        detail += (" " if detail else "") + \
            f"incident={r['incident_id']}"
    return [step, r.get("anomaly", "-"), r.get("severity", "-"),
            detail or "-"]


def _fleet_row(r: dict) -> List[str]:
    """One fleet-timeline row from a ``kind:"fleet"`` liveness event
    (host_dead / host_slow / host_return), a resize action (shrink /
    grow / admission_refused), an autoscaler decision, or a deadline
    event."""
    step = str(r.get("step", "-"))
    event = r.get("event", "-")
    if event == "shrink":
        detail = (f"survivors={r.get('survivors')} "
                  f"dead={r.get('dead')} epoch={r.get('epoch')}")
        if r.get("reason") and r.get("reason") != "failure":
            detail += f" reason={r['reason']}"
        if r.get("to_step") is not None:
            detail += f" to_step={r['to_step']}"
        if r.get("incident_id"):
            detail += f" incident={r['incident_id']}"
        return [step, event, "-", detail]
    if event == "grow":
        detail = (f"members={r.get('members')} "
                  f"admitted={r.get('admitted')} epoch={r.get('epoch')}")
        if r.get("to_step") is not None:
            detail += f" to_step={r['to_step']}"
        if r.get("incident_id"):
            detail += f" incident={r['incident_id']}"
        return [step, event, "-", detail]
    if event == "admission_refused":
        return [step, event, str(r.get("host", "-")),
                f"reason={r.get('reason')} "
                f"incarnation={_fmt_cell(r.get('incarnation'))}"]
    if event == "autoscale":
        return [step, event, "-",
                f"action={r.get('action')} reason={r.get('reason')} "
                f"signal={_fmt_cell(r.get('signal'))}"]
    if event == "deadline_exceeded":
        return [step, event, "-",
                f"phase={r.get('phase')} "
                f"deadline_s={_fmt_cell(r.get('deadline_s'))}"]
    if event == "replay_complete":
        return [step, event, "-",
                f"incident={r.get('incident_id', '-')}"]
    detail = (f"gap_s={_fmt_cell(r.get('gap_s'))} "
              f"lag_steps={_fmt_cell(r.get('lag_steps'))} "
              f"peer_step={_fmt_cell(r.get('peer_step'))}")
    inc = (r.get("evidence") or {}).get("incarnation")
    if event == "host_return" and inc is not None:
        detail += f" incarnation={inc}"
    if r.get("incident_id"):
        detail += f" incident={r['incident_id']}"
    return [step, event, str(r.get("host", "-")), detail]


def _slo_section(reqtraces: List[dict],
                 hist_recs: List[dict]) -> Optional[dict]:
    """The per-run serving SLO summary: verdict counts (by reason),
    latency quantiles off the ``kind:"hist"`` snapshots (merged when
    several replicas contribute — associative, order-free), and
    tokens/sec over the traced span.  None when the run served
    nothing."""
    if not reqtraces and not hist_recs:
        return None
    from apex_tpu.telemetry import hist as _hist
    verdicts: dict = {}
    reasons: dict = {}
    tok_total = 0
    t_lo = t_hi = None
    for r in reqtraces:
        v = r.get("verdict")
        if v is None:
            continue        # open partial (a dead replica's shard)
        verdicts[v] = verdicts.get(v, 0) + 1
        if r.get("reason"):
            key = (v, r["reason"])
            reasons[key] = reasons.get(key, 0) + 1
        tok_total += int(r.get("tokens", 0))
        enq = r.get("enqueue_t")
        if isinstance(enq, (int, float)):
            t_lo = enq if t_lo is None else min(t_lo, enq)
        tv = r.get("t")
        if isinstance(tv, (int, float)):
            t_hi = tv if t_hi is None else max(t_hi, tv)
    by_name: dict = {}
    for rec in hist_recs:
        by_name.setdefault(rec.get("name", ""), []).append(rec)
    latency: dict = {}
    for name in sorted(by_name):
        try:
            h = _hist.merge_records(by_name[name])
        except (KeyError, TypeError, ValueError):
            continue      # torn/foreign hist record
        if h is None or h.count == 0:
            continue
        latency[name] = {"count": int(h.count),
                         "p50": round(h.quantile(0.5), 3),
                         "p99": round(h.quantile(0.99), 3)}
    out = {"requests": sum(verdicts.values()), "verdicts": verdicts,
           "reasons": {f"{v}:{r}": n
                       for (v, r), n in sorted(reasons.items())},
           "latency_ms": latency, "tokens": tok_total}
    if t_lo is not None and t_hi is not None and t_hi > t_lo:
        out["tokens_per_sec"] = round(tok_total / (t_hi - t_lo), 3)
    return out


def _render_slo(slo: dict, out) -> None:
    tps = slo.get("tokens_per_sec")
    print(f"\nserving SLO: {slo['requests']} request(s), "
          f"{slo['tokens']} token(s)"
          + (f", {_fmt_cell(tps)} tokens/sec" if tps is not None
             else ""), file=out)
    if slo["verdicts"]:
        rows = []
        for v in sorted(slo["verdicts"]):
            why = ", ".join(
                f"{k.split(':', 1)[1]}={n}"
                for k, n in sorted(slo["reasons"].items())
                if k.startswith(v + ":"))
            rows.append([v, str(slo["verdicts"][v]), why or "-"])
        _render_table(["verdict", "count", "by reason"], rows, out)
    if slo["latency_ms"]:
        _render_table(
            ["latency", "count", "p50_ms", "p99_ms"],
            [[n.rsplit("/", 1)[-1], str(q["count"]),
              _fmt_cell(q["p50"]), _fmt_cell(q["p99"])]
             for n, q in sorted(slo["latency_ms"].items())], out)


def _render_table(header: List[str], rows: List[List[str]], out) -> None:
    widths = [max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
              for i, h in enumerate(header)]
    print("  ".join(h.rjust(w) for h, w in zip(header, widths)), file=out)
    for r in rows:
        print("  ".join(c.rjust(w) for c, w in zip(r, widths)), file=out)


def summarize(path, tail: int = 32, as_json: bool = False,
              out=None) -> int:
    """Render the run's telemetry; returns a process exit code (1 when
    there is nothing to render — missing file or zero step records).
    ``path`` may be one run dir (or its .jsonl) or a LIST of run dirs:
    multiple dirs merge through the timeline front-end (host-tagged,
    steps deduped newest-per-(host, step)) so a faked-multi-host chaos
    run inspects in one command."""
    out = out or sys.stdout
    if not isinstance(path, str):
        paths = list(path)
        if len(paths) != 1:
            return _summarize_merged(paths, tail, as_json, out)
        path = paths[0]
    resolved = _resolve(path)
    if resolved is None:
        print(f"no {JSONL_NAME} under {path!r} (run with telemetry on: "
              "apex_tpu.telemetry.Telemetry(run_dir=...))", file=out)
        return 1
    schema, records = load_jsonl(resolved)
    steps = [r for r in records if r.get("kind", "step") == "step"]
    # span/counter/retrace records are cumulative snapshots: keep the
    # newest per name; anomaly/watchdog/fleet records are EVENTS —
    # every one is a timeline row
    spans, counters, retraces, anomalies = {}, {}, {}, []
    fleet_events: List[dict] = []
    reqtraces: List[dict] = []
    hists: dict = {}
    for r in records:
        if r.get("kind") == "span":
            spans[r["name"]] = r
        elif r.get("kind") == "counter":
            counters[r["name"]] = r
        elif r.get("kind") == "retrace":
            retraces[r["name"]] = r
        elif r.get("kind") in ("anomaly", "watchdog"):
            anomalies.append(r)
        elif r.get("kind") == "fleet":
            fleet_events.append(r)
        elif r.get("kind") == "reqtrace":
            reqtraces.append(r)
        elif r.get("kind") == "hist":
            # cumulative snapshot: newest per name wins
            hists[r.get("name", "")] = r
    if not steps and not (counters or spans or anomalies
                          or fleet_events or retraces
                          or reqtraces or hists):
        print(f"{resolved}: no step records", file=out)
        return 1
    slo = _slo_section(reqtraces, list(hists.values()))
    # a step-less run still renders: the serving engine emits only
    # counters (serving/prefix_hits, serving/kv_bytes_saved, ...) and
    # events, and those need a summarize surface too
    # a step flushed twice (flush() + close()) keeps the newest record
    by_step = {}
    for r in steps:
        by_step[r["step"]] = r
    steps = [by_step[s] for s in sorted(by_step)]

    metrics = (schema or {}).get("metrics")
    if not metrics:
        seen = {k for r in steps for k in r}
        metrics = sorted(seen - {"step", "kind"})
    overflows = sum(1 for r in steps if (r.get("amp/found_inf") or 0) > 0)
    # profiler headline counters (perf/step_ms, perf/mfu,
    # perf/overlap_pct, ... — emitted by a profile_window capture taken
    # during the run) get their own section; last value wins, like the
    # gauges they are
    perf = {n.split("/", 1)[1]: c.get("last")
            for n, c in sorted(counters.items())
            if n.startswith("perf/")}

    if as_json:
        json.dump({"source": resolved, "steps": steps,
                   "overflow_steps": overflows,
                   "anomalies": anomalies,
                   "fleet": fleet_events,
                   "perf": perf,
                   "serving": slo,
                   "spans": sorted(spans.values(),
                                   key=lambda r: r["name"]),
                   "counters": sorted(counters.values(),
                                      key=lambda r: r["name"]),
                   "retraces": sorted(retraces.values(),
                                      key=lambda r: r["name"])},
                  out)
        out.write("\n")
        return 0

    print(f"telemetry: {resolved}", file=out)
    print(f"steps recorded: {len(steps)}   overflow steps: {overflows}",
          file=out)
    print("", file=out)
    if steps:
        show = steps[-tail:] if tail and tail > 0 else steps
        header = ["step"] + [m.rsplit("/", 1)[-1] if m.count("/") else m
                             for m in metrics]
        rows = [[str(r["step"])]
                + [_fmt_cell(r.get(m)) for m in metrics]
                for r in show]
        _render_table(header, rows, out)
    if anomalies:
        # the watchdog's anomaly timeline: detections (kind:"anomaly")
        # interleaved with the actions taken (kind:"watchdog") in
        # event order, stably sorted by step
        print("\nanomaly timeline:", file=out)
        _render_table(
            ["step", "event", "severity/action", "detail"],
            [_anomaly_row(r)
             for r in sorted(anomalies,
                             key=lambda r: r.get("step", 0))], out)
    if fleet_events:
        # the fleet timeline: beacon-gap liveness events (host_slow /
        # host_dead) interleaved with the actions taken (shrink,
        # deadline_exceeded) in step order
        print("\nfleet timeline:", file=out)
        _render_table(
            ["step", "event", "host", "detail"],
            [_fleet_row(r)
             for r in sorted(fleet_events,
                             key=lambda r: r.get("step", 0))], out)
    if slo is not None:
        _render_slo(slo, out)
    if spans:
        print("\nspans (cumulative):", file=out)
        _render_table(
            ["name", "count", "total_ms", "max_ms"],
            [[n, str(s.get("count", "-")), _fmt_cell(s.get("total_ms")),
              _fmt_cell(s.get("max_ms"))]
             for n, s in sorted(spans.items())], out)
    if perf:
        print("\nperf (profiler capture):", file=out)
        _render_table(
            ["metric", "value"],
            [[n, _fmt_cell(v)] for n, v in sorted(perf.items())], out)
    if counters:
        # host counters (ckpt/save_ms, ckpt/bytes_written, ...):
        # count/total/max/last, cumulative like the span table
        print("\ncounters (cumulative):", file=out)
        _render_table(
            ["name", "count", "total", "max", "last"],
            [[n, str(c.get("count", "-")), _fmt_cell(c.get("total")),
              _fmt_cell(c.get("max")), _fmt_cell(c.get("last"))]
             for n, c in sorted(counters.items())], out)
    if retraces:
        print("\ncompilation:", file=out)
        # <process>: every event's seconds by kind; program/<name>: the
        # outermost events' (wall time); a wrapped function: its traces
        _render_table(
            ["name", "traces", "retraces", "compile_s", "trace_s",
             "lower_s", "backend_s", "cache"],
            [[n, str(r.get("traces", "-")),
              str(r.get("retraces", "-")),
              _fmt_cell(r.get("compile_s")), _fmt_cell(r.get("trace_s")),
              _fmt_cell(r.get("lower_s")), _fmt_cell(r.get("backend_s")),
              (f"{r['cache_hits']} hits, {r['cache_misses']} misses"
               if "cache_hits" in r else str(r.get("cache") or "-"))]
             for n, r in sorted(retraces.items())], out)
    return 0


def _summarize_merged(paths: List[str], tail: int, as_json: bool,
                      out) -> int:
    """Multi-dir summarize: the timeline merge front-end feeding the
    familiar tables, with a host column on everything per-host."""
    from apex_tpu.telemetry import timeline as _timeline
    merged = _timeline.merge_run_dirs(paths)
    if merged is None:
        print(f"no {JSONL_NAME} under any of: {' '.join(paths)} "
              "(run with telemetry on: "
              "apex_tpu.telemetry.Telemetry(run_dir=...))", file=out)
        return 1
    steps = merged["steps"]
    spans, counters, retraces = {}, {}, {}
    anomalies: List[dict] = []
    fleet_events: List[dict] = []
    reqtraces: List[dict] = []
    hists: dict = {}
    for r in merged["records"]:
        key = (r.get("host", 0), r.get("name", ""))
        if r.get("kind") == "span":
            spans[key] = r
        elif r.get("kind") == "counter":
            counters[key] = r
        elif r.get("kind") == "retrace":
            retraces[key] = r
        elif r.get("kind") in ("anomaly", "watchdog", "incident"):
            anomalies.append(r)
        elif r.get("kind") == "fleet":
            fleet_events.append(r)
        elif r.get("kind") == "reqtrace":
            reqtraces.append(r)
        elif r.get("kind") == "hist":
            # newest cumulative snapshot per (host, name); the SLO
            # section then merges ACROSS hosts (associative fold)
            hists[key] = r
    slo = _slo_section(reqtraces, [hists[k] for k in sorted(hists)])
    if not steps and slo is None and not (counters or anomalies
                                          or fleet_events):
        print(f"{' '.join(merged['sources'])}: no step records",
              file=out)
        return 1
    seen = {k for r in steps for k in r}
    metrics = sorted(seen - {"step", "kind", "host"})
    overflows = sum(1 for r in steps
                    if (r.get("amp/found_inf") or 0) > 0)
    if as_json:
        json.dump({"sources": merged["sources"],
                   "hosts": merged["hosts"],
                   "offsets": merged["offsets"],
                   "steps": steps, "overflow_steps": overflows,
                   "anomalies": anomalies, "fleet": fleet_events,
                   "serving": slo,
                   "spans": [spans[k] for k in sorted(spans)],
                   "counters": [counters[k] for k in sorted(counters)],
                   "retraces": [retraces[k]
                                for k in sorted(retraces)]}, out)
        out.write("\n")
        return 0
    print(f"telemetry: {len(merged['sources'])} run dirs merged, "
          f"hosts {merged['hosts']}", file=out)
    print(f"steps recorded: {len(steps)}   overflow steps: "
          f"{overflows}", file=out)
    print("", file=out)
    if steps:
        show = steps[-tail:] if tail and tail > 0 else steps
        header = ["host", "step"] + [m.rsplit("/", 1)[-1]
                                     if m.count("/") else m
                                     for m in metrics]
        rows = [[str(r.get("host", "-")), str(r["step"])]
                + [_fmt_cell(r.get(m)) for m in metrics]
                for r in show]
        _render_table(header, rows, out)
    if anomalies:
        print("\nanomaly timeline:", file=out)
        _render_table(
            ["host", "step", "event", "severity/action", "detail"],
            [[str(r.get("host", "-"))] + _anomaly_row(r)
             for r in anomalies], out)
    if fleet_events:
        print("\nfleet timeline:", file=out)
        _render_table(
            ["host", "step", "event", "subject", "detail"],
            [[str(r.get("host", "-"))] + _fleet_row(r)
             for r in fleet_events], out)
    if slo is not None:
        _render_slo(slo, out)
    if counters:
        print("\ncounters (cumulative, per host):", file=out)
        _render_table(
            ["host", "name", "count", "total", "max", "last"],
            [[str(h), n, str(c.get("count", "-")),
              _fmt_cell(c.get("total")), _fmt_cell(c.get("max")),
              _fmt_cell(c.get("last"))]
             for (h, n), c in sorted(counters.items())], out)
    return 0


def timeline(paths: List[str], as_json: bool = False,
             chrome_trace_path: Optional[str] = None,
             out=None) -> int:
    """Render the merged fleet timeline (incident-grouped) for N run
    dirs; optionally export the Chrome trace for Perfetto.  Exit 1
    when no run dir resolves to a JSONL file."""
    from apex_tpu.telemetry import timeline as _timeline
    out = out or sys.stdout
    doc = _timeline.build(paths)
    if doc is None:
        print(f"no {JSONL_NAME} under any of: {' '.join(paths)}",
              file=out)
        return 1
    if chrome_trace_path:
        trace = _timeline.chrome_trace(doc)
        if chrome_trace_path == "-":
            json.dump(trace, out)
            out.write("\n")
        else:
            with open(chrome_trace_path, "w", encoding="utf-8") as f:
                json.dump(trace, f)
            print(f"chrome trace written to {chrome_trace_path} "
                  f"({len(trace['traceEvents'])} events) — load in "
                  "Perfetto / chrome://tracing", file=out)
    if as_json:
        json.dump(doc, out)
        out.write("\n")
    elif chrome_trace_path != "-":
        _timeline.render_text(doc, out)
    return 0


def profile(trace_dir: str, *, top: int = 12,
            steps: Optional[int] = None, as_json: bool = False,
            out=None) -> int:
    """Render the observatory report for a captured trace dir; exit 1
    when the directory holds no device events (host-only trace, wrong
    directory) — machine-parseable either way under ``--json``."""
    from apex_tpu.telemetry.profiler import report as _report
    out = out or sys.stdout
    rep = _report.build_report(trace_dir, top=top, steps=steps)
    if as_json:
        json.dump(rep, out)
        out.write("\n")
    else:
        _report.render_text(rep, out)
    return 1 if rep.get("error") else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.telemetry",
        description="training telemetry tooling")
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("summarize",
                       help="render a run's telemetry.jsonl as tables "
                            "(several run dirs merge host-tagged)")
    s.add_argument("run_dir", nargs="+",
                   help="run directory (or the .jsonl itself); "
                        "several merge through the timeline front-end")
    s.add_argument("--tail", type=int, default=32,
                   help="show only the newest N steps (0 = all)")
    s.add_argument("--json", action="store_true",
                   help="machine-readable output")
    t = sub.add_parser(
        "timeline",
        help="merge N hosts' run dirs into one ordered fleet "
             "timeline grouped by incident id")
    t.add_argument("run_dirs", nargs="+",
                   help="run directories (or .jsonl files), one per "
                        "host")
    t.add_argument("--json", action="store_true",
                   help="machine-readable output")
    t.add_argument("--chrome-trace", metavar="PATH", default=None,
                   help="also write a Chrome trace (Perfetto / "
                        "chrome://tracing); '-' writes it to stdout")
    p = sub.add_parser(
        "profile",
        help="render a captured jax.profiler trace dir as the "
             "observatory report (breakdown, overlap, MFU, top ops)")
    p.add_argument("trace_dir",
                   help="trace directory (profiler.capture outdir)")
    p.add_argument("--top", type=int, default=12,
                   help="rows in the top-op table")
    p.add_argument("--steps", type=int, default=None,
                   help="step count override (traces without a "
                        "profile_meta.json sidecar)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    args = ap.parse_args(argv)
    try:
        if args.cmd == "profile":
            return profile(args.trace_dir, top=args.top,
                           steps=args.steps, as_json=args.json)
        if args.cmd == "timeline":
            return timeline(args.run_dirs, as_json=args.json,
                            chrome_trace_path=args.chrome_trace)
        return summarize(args.run_dir, tail=args.tail, as_json=args.json)
    except BrokenPipeError:
        return 0          # |head etc. closing the pipe is not an error
