"""Performance regression gate over the BENCH_r*.json trajectory.

The observatory's verdict half: ``bench.py`` measures, the rounds
accumulate as ``BENCH_r0N.json``, and THIS turns the trajectory into
an exit code — the same sensor→verdict discipline the telemetry ring
(PR 4) and the watchdog (PR 7) apply to training health, applied to
performance.  No jax import, stdlib only: the gate must run on any CI
box in milliseconds.

    python tools/perf_gate.py             # gate: exit 1 on regression
    python tools/perf_gate.py --report    # report-only: always exit 0
    python tools/perf_gate.py --json      # machine-readable verdicts

Budget: ``tools/perf_budget.json`` maps a dotted metric path (into
the round's parsed bench line, e.g. ``extra.resnet50_mfu``) to a
floor (or ceiling, for lower-is-better metrics) plus a per-metric
noise band.  The noise bands encode benchlib's amortized-timing
methodology: tracked train metrics repeat within a few percent
between windows, so only an ABOVE-NOISE drop is a regression —
within-band wobble reports as ``ok (within noise)``.

Two checks per metric, both noise-banded:

- **budget**: the newest hardware measurement vs its committed
  floor/ceiling — the "never ship slower than this" line, restamped
  from each accepted hardware window;
- **trajectory**: the newest measurement vs the best previous
  hardware round — catches a slide the budget's slack would hide.

A metric the NEWEST hardware round stopped reporting grades
``stale`` and fails the gate: a perf loss that manifests as a crashed
bench leg (the BENCH_r05 flash shape) must not read as green by
comparing an older round's value against the floor.

Only real hardware rounds count (``backend`` "tpu" or "tpu-cached",
positive value): the CPU-fallback liveness lines prove the harness,
not performance, and a cached round re-served across windows compares
equal to itself (no false regression between hardware rounds).

**Structural rows** (``"source": "ledger"`` in the budget) grade from
the committed apexcost ledger (``apex_tpu/lint/cost/ledger.json``)
instead of BENCH rounds: ``ledger_entry`` names the cost card,
``ledger_field`` the dotted field (e.g.
``extras.serving_hbm_bytes_per_slot``).  Their values are
deterministic facts of the tree, so they default to a ZERO noise band
and gate in auto mode regardless of hardware-round recency; only a
forced ``--report`` waives them.  A vanished card or field grades
``stale`` (gating) — a deleted ledger must not read as green.

An **empty trajectory** (no ``BENCH_r*.json`` with a parsed bench
line at all) grades ``no-rounds`` explicitly: one line saying there is
nothing to grade, exit 0 in auto/report mode (a forced ``--gate``
exits 1 — an empty record cannot defend a budget).

**Gating is automatic**: with neither ``--report`` nor ``--gate``, the
gate flips on exactly when the newest BENCH round is a hardware round
measured AFTER the budget's ``stamped_at`` date — fresh hardware
numbers must be defended, while the cached pre-flat-pipeline rounds
(whose capture date the budget was stamped from) stay report-only so
they cannot block the PRs that will re-measure them.  The chosen mode
and its reason are always printed.

Every hardware round additionally prints its **measurement age**
(capture timestamp + days since) — the cached rounds re-serve the
2026-07-31 window, and that staleness should be visible in every
``tools/check.sh`` run, not only in ROADMAP prose.  When the newest
hardware data predates the budget's ``stamped_at`` by more than
``--stale-days`` (default 14), the gate prints a WARNING: the budget
is defending numbers nobody has re-measured in that long.  Neither
the age lines nor the warning change the exit code.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Optional, Tuple

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET_PATH = os.path.join(_ROOT, "tools", "perf_budget.json")
# the apexcost ledger: committed static cost cards, the source for
# budget rows marked {"source": "ledger"}
LEDGER_PATH = os.path.join(_ROOT, "apex_tpu", "lint", "cost",
                           "ledger.json")

_HW_BACKENDS = {"tpu", "tpu-cached"}


def load_rounds(root: str = _ROOT) -> List[Tuple[int, dict]]:
    """[(round_number, parsed bench line), ...] sorted by round, for
    every round whose artifact holds a parseable bench line."""
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            out.append((int(m.group(1)), parsed))
    out.sort()
    return out


def _numeric(v) -> float:
    """Best-effort float; malformed values read as 0 (a hand-edited
    artifact must degrade to "not a hardware round", not a traceback
    aborting the whole check run)."""
    try:
        return float(v or 0)
    except (TypeError, ValueError):
        return 0.0


def hardware_rounds(rounds: List[Tuple[int, dict]]) -> List[Tuple[int, dict]]:
    return [(n, p) for n, p in rounds
            if p.get("backend") in _HW_BACKENDS
            and _numeric(p.get("value")) > 0]


def metric_value(parsed: dict, dotted: str) -> Optional[float]:
    """Resolve ``"extra.resnet50_mfu"``-style paths; None when any
    segment is missing or the leaf is not a number."""
    node = parsed
    for seg in dotted.split("."):
        if not isinstance(node, dict) or seg not in node:
            return None
        node = node[seg]
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        return None
    return float(node)


def _check(name: str, spec: dict,
           rounds: List[Tuple[int, dict]]) -> dict:
    """One metric's verdict dict (status: ok | regression | no-data)."""
    direction = spec.get("direction", "higher")
    noise_pct = float(spec.get("noise_pct", 5.0))
    limit = spec.get("floor" if direction == "higher" else "ceiling")
    series = [(n, metric_value(p, name)) for n, p in rounds]
    series = [(n, v) for n, v in series if v is not None]
    verdict = {"metric": name, "direction": direction,
               "noise_pct": noise_pct, "limit": limit,
               "rounds": [n for n, _ in series]}
    if not series:
        verdict.update(status="no-data",
                       detail="no hardware round reports this metric")
        return verdict
    newest_round, newest = series[-1]
    verdict.update(newest=newest, newest_round=newest_round)
    if rounds and newest_round != rounds[-1][0]:
        # the newest hardware round stopped reporting this metric — a
        # perf loss that manifests as a crashed leg must not read as
        # green; grading r(N-1)'s value against the floor would mask it
        verdict.update(
            status="stale",
            detail=f"newest hardware round r{rounds[-1][0]:02d} does "
                   f"not report this metric (last seen "
                   f"r{newest_round:02d}) — a crashed bench leg "
                   "cannot pass the gate")
        return verdict
    worse = ((lambda a, b: a < b) if direction == "higher"
             else (lambda a, b: a > b))
    band = 1.0 - noise_pct / 100.0
    failures = []

    if limit is not None:
        # budget check: newest vs floor/ceiling, noise-banded
        lim = float(limit)
        threshold = lim * band if direction == "higher" else lim / band
        if worse(newest, threshold):
            failures.append(
                f"newest {newest:g} (r{newest_round:02d}) breaches "
                f"{'floor' if direction == 'higher' else 'ceiling'} "
                f"{lim:g} beyond the {noise_pct:g}% noise band")

    prev = [v for _, v in series[:-1]]
    if prev:
        best_prev = max(prev) if direction == "higher" else min(prev)
        threshold = (best_prev * band if direction == "higher"
                     else best_prev / band)
        verdict["best_prev"] = best_prev
        if worse(newest, threshold):
            failures.append(
                f"newest {newest:g} (r{newest_round:02d}) regressed "
                f"beyond {noise_pct:g}% noise vs best prior {best_prev:g}")

    verdict["status"] = "regression" if failures else "ok"
    if failures:
        verdict["detail"] = "; ".join(failures)
    return verdict


def load_ledger(path: str = LEDGER_PATH) -> Optional[dict]:
    """The committed apexcost ledger, or None when absent/unreadable
    (the --cost lint gate owns failing on THAT; here a missing ledger
    just grades its rows stale)."""
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _check_ledger(name: str, spec: dict,
                  ledger_doc: Optional[dict]) -> dict:
    """Verdict for a STRUCTURAL budget row graded from the apexcost
    ledger instead of BENCH rounds.  These are deterministic program
    facts (bytes per decode slot, collective payload per step), so the
    noise band defaults to zero and the verdict gates in auto mode
    regardless of hardware-round recency — the value comes from the
    committed tree, not from a measurement."""
    direction = spec.get("direction", "lower")
    noise_pct = float(spec.get("noise_pct", 0.0))
    limit = spec.get("floor" if direction == "higher" else "ceiling")
    verdict = {"metric": name, "direction": direction,
               "noise_pct": noise_pct, "limit": limit,
               "source": "ledger", "rounds": [],
               "ledger_entry": spec.get("ledger_entry"),
               "ledger_field": spec.get("ledger_field")}
    card = (ledger_doc or {}).get("cards", {}) \
        .get(spec.get("ledger_entry"))
    value = metric_value(card, spec.get("ledger_field", "")) \
        if isinstance(card, dict) else None
    if value is None:
        # a vanished card/field must not read as green — same
        # crashed-leg discipline as the stale trajectory check
        verdict.update(
            status="stale",
            detail=f"ledger entry {spec.get('ledger_entry')!r} does "
                   f"not report {spec.get('ledger_field')!r} "
                   "(ledger missing, stale or field removed) — "
                   "regenerate with `python -m apex_tpu.lint "
                   "--write-ledger`")
        return verdict
    verdict["newest"] = value
    worse = ((lambda a, b: a < b) if direction == "higher"
             else (lambda a, b: a > b))
    band = 1.0 - noise_pct / 100.0
    if limit is not None:
        lim = float(limit)
        threshold = lim * band if direction == "higher" else lim / band
        if worse(value, threshold):
            verdict.update(
                status="regression",
                detail=f"ledger value {value:g} breaches "
                       f"{'floor' if direction == 'higher' else 'ceiling'} "
                       f"{lim:g} (noise band {noise_pct:g}%) — an "
                       "intended change must restamp the budget row "
                       "alongside --write-ledger")
            return verdict
    verdict["status"] = "ok"
    return verdict


def parse_when(when) -> Optional["datetime.datetime"]:
    """Parse the bench stamp format (``2026-07-31T03:41:18Z``); None
    for anything else — a malformed stamp degrades to "no age", never
    a traceback out of the gate."""
    import datetime
    try:
        return datetime.datetime.strptime(when, "%Y-%m-%dT%H:%M:%SZ")
    except (TypeError, ValueError):
        return None


def age_days(when, now=None) -> Optional[int]:
    """Whole days between a bench capture stamp and ``now`` (UTC)."""
    import datetime
    t = parse_when(when)
    if t is None:
        return None
    if now is None:
        now = datetime.datetime.now(datetime.timezone.utc) \
            .replace(tzinfo=None)
    return (now - t).days


def round_when(parsed: dict) -> Optional[str]:
    """ISO capture timestamp of one bench line: live rounds carry
    ``measured_at``; cached rounds re-serve the original window's
    stamp as ``extra.cached_measured_at``."""
    when = parsed.get("measured_at")
    if isinstance(when, str) and when:
        return when
    extra = parsed.get("extra")
    if isinstance(extra, dict):
        when = extra.get("cached_measured_at")
        if isinstance(when, str) and when:
            return when
    return None


def choose_mode(budget: dict,
                rounds: List[Tuple[int, dict]]) -> Tuple[bool, str]:
    """(gating, reason) for auto mode: gate exactly when the newest
    BENCH round is a hardware round measured after the budget's
    ``stamped_at`` (ISO strings compare lexicographically).  Anything
    unprovable — no rounds, a CPU newest round, missing timestamps —
    stays report-only, loudly."""
    if not rounds:
        return False, "report-only: no BENCH rounds found"
    n, parsed = rounds[-1]
    if parsed.get("backend") not in _HW_BACKENDS \
            or _numeric(parsed.get("value")) <= 0:
        return False, (f"report-only: newest round r{n:02d} is not a "
                       "hardware round")
    when = round_when(parsed)
    stamped = budget.get("stamped_at")
    if not when or not isinstance(stamped, str) or not stamped:
        return False, (f"report-only: cannot compare newest round "
                       f"r{n:02d} ({when or 'no timestamp'}) against "
                       f"budget stamp ({stamped or 'no stamped_at'})")
    if when > stamped:
        return True, (f"gating: newest hardware round r{n:02d} "
                      f"({when}) postdates the budget stamp "
                      f"({stamped}) — fresh numbers are defended")
    return False, (f"report-only: newest hardware round r{n:02d} "
                   f"({when}) does not postdate the budget stamp "
                   f"({stamped}); the budget already covers it")


def evaluate(budget: dict, rounds: List[Tuple[int, dict]],
             ledger_doc: Optional[dict] = None) -> List[dict]:
    hw = hardware_rounds(rounds)
    out = []
    for name, spec in sorted(budget.get("metrics", {}).items()):
        if spec.get("source") == "ledger":
            out.append(_check_ledger(name, spec, ledger_doc))
        else:
            out.append(_check(name, spec, hw))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="BENCH trajectory regression gate "
                    "(tools/perf_budget.json)")
    ap.add_argument("--budget", default=BUDGET_PATH)
    ap.add_argument("--root", default=_ROOT,
                    help="directory holding BENCH_r*.json")
    ap.add_argument("--report", action="store_true",
                    help="force report-only: print verdicts, always "
                         "exit 0")
    ap.add_argument("--gate", action="store_true",
                    help="force gating regardless of round/stamp dates")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--stale-days", type=int, default=14,
                    help="warn when the newest hardware data predates "
                         "the budget stamp by more than this many "
                         "days (warning only — never the exit code)")
    args = ap.parse_args(argv)

    try:
        with open(args.budget, encoding="utf-8") as f:
            budget = json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf_gate: cannot read budget {args.budget}: {e}",
              file=sys.stderr)
        return 2
    rounds = load_rounds(args.root)
    ledger_doc = load_ledger()
    ledger_rows = {n for n, s in budget.get("metrics", {}).items()
                   if s.get("source") == "ledger"}
    if not rounds and ledger_rows:
        # structural ledger rows grade even on an empty BENCH
        # trajectory — they come from the committed tree, not from
        # measurements; hardware rows still report no-rounds below
        pass
    elif not rounds:
        # an EMPTY trajectory is its own explicit verdict, not an
        # N-way "no hardware round reports this metric" chorus: there
        # is literally nothing to grade, say so in one line and exit
        # clean (auto/report — a forced --gate still refuses to pass
        # silently, there is nothing defending the budget)
        reason = ("no-rounds: BENCH trajectory is empty (no "
                  "BENCH_r*.json with a parsed bench line under "
                  f"{args.root}) — nothing to grade; run bench.py on "
                  "hardware to start the trajectory")
        if args.json:
            print(json.dumps({"verdicts": [], "hardware_rounds": [],
                              "regressions": 0, "gating": args.gate,
                              "status": "no-rounds",
                              "mode_reason": reason}))
        else:
            print(f"perf_gate: {reason}")
        return 1 if args.gate else 0
    if args.report:
        gating, reason = False, "report-only: forced by --report"
    elif args.gate:
        gating, reason = True, "gating: forced by --gate"
    else:
        gating, reason = choose_mode(budget, rounds)
    verdicts = evaluate(budget, rounds, ledger_doc)
    # stale (metric vanished from the newest hardware round) gates
    # like a regression: a crashed leg must not pass
    regressions = [v for v in verdicts
                   if v["status"] in ("regression", "stale")]
    # ledger-sourced rows gate unconditionally in auto mode: their
    # values are deterministic facts of the committed tree, so there
    # is no "stale hardware" excuse — only --report waives them
    structural = [v for v in regressions
                  if v.get("source") == "ledger"]

    # measurement ages: when each hardware round's data was actually
    # captured (cached rounds re-serve their original window's stamp),
    # plus a staleness warning when the newest hardware data predates
    # the budget stamp by more than --stale-days — report-only, the
    # exit code never depends on either
    hw = hardware_rounds(rounds)
    ages = [{"round": n, "backend": p.get("backend"),
             "measured_at": round_when(p),
             "age_days": age_days(round_when(p))} for n, p in hw]
    stale_warning = None
    if hw:
        stamped_dt = parse_when(budget.get("stamped_at"))
        newest_dt = parse_when(round_when(hw[-1][1]))
        if stamped_dt and newest_dt:
            behind = (stamped_dt - newest_dt).days
            if behind > args.stale_days:
                stale_warning = (
                    f"WARNING: newest hardware data "
                    f"({round_when(hw[-1][1])}) predates the budget "
                    f"stamp ({budget.get('stamped_at')}) by {behind} "
                    f"days (> {args.stale_days}) — the budget defends "
                    "numbers nobody has re-measured; run bench.py on "
                    "hardware")

    if args.json:
        print(json.dumps({"verdicts": verdicts,
                          "hardware_rounds":
                          [n for n, _ in hw],
                          "measurement_ages": ages,
                          "stale_warning": stale_warning,
                          "regressions": len(regressions),
                          "structural_regressions": len(structural),
                          "gating": gating, "mode_reason": reason}))
    else:
        print(f"perf_gate: {len(hw)} hardware round(s) "
              f"{[n for n, _ in hw]} of {len(rounds)} total")
        for a in ages:
            if a["measured_at"]:
                line = (f"  r{a['round']:02d} {a['backend']}: "
                        f"measured {a['measured_at']}")
                if a["age_days"] is not None:
                    line += f" ({a['age_days']} day(s) ago)"
            else:
                line = (f"  r{a['round']:02d} {a['backend']}: "
                        "no capture timestamp")
            print(line)
        if stale_warning:
            print(f"perf_gate: {stale_warning}")
        print(f"perf_gate: {reason}")
        for v in verdicts:
            line = f"  {v['status']:<10} {v['metric']}"
            if v.get("newest") is not None:
                line += f"  newest={v['newest']:g}"
                if v.get("newest_round") is not None:
                    line += f" (r{v['newest_round']:02d})"
                elif v.get("source") == "ledger":
                    line += " (ledger)"
            if v.get("limit") is not None:
                kind = ("floor" if v["direction"] == "higher"
                        else "ceiling")
                line += f"  {kind}={v['limit']:g}"
            if v.get("detail"):
                line += f"  [{v['detail']}]"
            print(line)
        if regressions:
            tag = "" if gating else (
                " (report-only, not gating)" if not structural
                else " (structural ledger row(s) gate regardless)")
            print(f"perf_gate: {len(regressions)} above-noise "
                  f"regression(s){tag}")
        else:
            print("perf_gate: trajectory clean")
    if structural and not args.report:
        return 1
    return 0 if (not gating or not regressions) else 1


if __name__ == "__main__":
    raise SystemExit(main())
