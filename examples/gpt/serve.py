"""Minimal apex_tpu.serving engine demo — the serving acceptance flow.

A tiny GPT-style decoder behind the AOT-compiled, continuously-batched
:class:`~apex_tpu.serving.Engine`: a batch of requests streams through
the bounded admission queue, prefills into the paged KV arena through
per-bucket compiled programs, and decodes in fixed-shape windows with
zero per-token host syncs.  The request-level robustness story is the
point:

- ``--port PORT`` serves LIVE ``/metrics`` (Prometheus text) +
  ``/healthz`` while requests decode — scrape it mid-run and watch
  ``apex_tpu_serving_*`` gauges (queue depth, tokens/sec, p50/p99
  token latency, evictions) move, plus the SLO histograms
  (``apex_tpu_serving_ttft_ms_bucket`` et al.);
- ``--trace-dir DIR`` records per-request lifecycle traces (enqueue
  -> admit -> decode windows -> typed verdict) and prints an SLO
  quantile summary; the dir doubles as the telemetry run dir when
  ``--telemetry-dir`` is absent, so ``python -m apex_tpu.telemetry
  summarize DIR`` renders the per-run SLO table afterwards;
- ``--inject-hung-decode-at W`` wedges the decode dispatch of serve
  window W: the deadline-armed runner converts the hang into a typed
  ``DecodeDeadlineExceeded``, the engine evicts ONLY the suspect
  request, the survivors continue from their KV pages bit-exactly,
  and the demo then re-submits the evicted request (detect -> evict
  -> re-admit) — the whole chain lands under one incident id,
  rendered afterwards by ``python -m apex_tpu.telemetry timeline
  DIR`` as a single closed incident.

Run it::

    python examples/gpt/serve.py --requests 6 \
        --telemetry-dir /tmp/serve_run --port 0 \
        --inject-hung-decode-at 3
"""

import argparse
import os

import jax

import apex_tpu
from apex_tpu import serving, telemetry


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--requests", type=int, default=6,
                   help="synthetic request count")
    p.add_argument("--max-new-tokens", type=int, default=12)
    p.add_argument("--telemetry-dir",
                   default=os.environ.get("APEX_TPU_TELEMETRY_DIR")
                   or None,
                   help="record serving telemetry (events + counters) "
                        "under this directory; inspect with "
                        "`python -m apex_tpu.telemetry timeline DIR`")
    p.add_argument("--trace-dir", default=None,
                   help="record request-level traces: dumps "
                        "reqtrace.jsonl + prints the SLO quantile "
                        "summary; doubles as the telemetry run dir "
                        "when --telemetry-dir is absent")
    p.add_argument("--port", type=int, default=None, metavar="PORT",
                   help="serve live /metrics + /healthz on this port "
                        "while decoding (0 = ephemeral; needs "
                        "--telemetry-dir or --trace-dir)")
    p.add_argument("--inject-hung-decode-at", type=int, default=None,
                   metavar="W",
                   help="chaos: wedge the decode dispatch of serve "
                        "window W (detect -> evict suspect -> "
                        "survivors continue -> re-admit)")
    p.add_argument("--deadline-s", type=float, default=None,
                   help="decode-window deadline (default 30, or 0.2 "
                        "when injecting the hang)")
    p.add_argument("--kv-dtype", default=None,
                   choices=("f32", "bf16", "int8"),
                   help="KV arena storage dtype; int8 stores "
                        "quantized pages + per-vector f32 scales "
                        "(~half the HBM per cached token)")
    p.add_argument("--speculate", type=int, default=None, metavar="K",
                   help="self-drafting speculative decoding: draft up "
                        "to K tokens per decode-window iteration from "
                        "each slot's recent token ring and verify them "
                        "in ONE dense pass — greedy output stays "
                        "bit-exact for any K (watch "
                        "apex_tpu_serving_spec_accepted / _drafted "
                        "on /metrics)")
    p.add_argument("--weight-dtype", default=None,
                   choices=("f32", "int8"),
                   help="decoder matmul weight storage; int8 "
                        "quantizes at engine build with per-channel "
                        "scales (weight-only: dequant folds into the "
                        "dot)")
    p.add_argument("--prefill-batch", type=int, default=None,
                   metavar="B",
                   help="admission drains up to B queued same-bucket "
                        "requests into ONE padded batched prefill "
                        "call")
    p.add_argument("--sample", default=None, metavar="TEMP:TOP_P",
                   help="device-side sampling, e.g. 0.8:0.95 — each "
                        "request draws seeded temperature/top-p "
                        "samples on device (default: greedy)")
    p.add_argument("--shared-system-prompt", action="store_true",
                   help="prefix every request with one shared system "
                        "prompt and enable refcounted prefix sharing: "
                        "the prefix prefills ONCE, later requests "
                        "alias its pages (watch "
                        "apex_tpu_serving_prefix_hits / "
                        "_kv_bytes_saved on /metrics)")
    return p.parse_args(argv)


def parse_sample(spec):
    """``TEMP:TOP_P`` -> (temperature, top_p)."""
    temp, _, top_p = spec.partition(":")
    return float(temp), float(top_p) if top_p else 1.0


def main(argv=None):
    args = parse_args(argv)
    print(f"apex_tpu {apex_tpu.__version__} serving on "
          f"{jax.default_backend()}")

    cfg = serving.DecoderConfig(vocab_size=128, hidden=32, n_layers=2,
                                n_heads=2, n_kv_heads=2, ffn=64,
                                max_seq=64, eos_token=1)
    params = serving.init_params(jax.random.key(0), cfg)

    # --trace-dir doubles as the telemetry run dir so a single flag
    # gets traces on disk AND the reqtrace/hist records riding the
    # telemetry JSONL for `telemetry summarize` / `timeline`
    tel_dir = args.telemetry_dir or args.trace_dir
    tel = telemetry.Telemetry(tel_dir, window=8, retrace=False) \
        if tel_dir else None
    metrics_srv = None
    if args.port is not None:
        if tel is None:
            raise SystemExit("--port needs --telemetry-dir or "
                             "--trace-dir (the exporter republishes "
                             "the telemetry session's flushes)")
        metrics_srv = telemetry.MetricsServer(telemetry=tel,
                                              port=args.port)
        print(f"serving live metrics at {metrics_srv.url}/metrics")

    deadline = args.deadline_s if args.deadline_s is not None else (
        0.2 if args.inject_hung_decode_at is not None else 30.0)
    eng = serving.Engine(params, cfg, page_size=4, n_pages=32,
                         max_slots=2, pages_per_slot=8, window=4,
                         telemetry=tel, decode_deadline_s=deadline,
                         flush_every=1, kv_dtype=args.kv_dtype,
                         spec_k=args.speculate,
                         weight_dtype=args.weight_dtype,
                         prefill_batch=args.prefill_batch,
                         prefix_share=(True if args.shared_system_prompt
                                       else None))
    print(f"engine: {eng.arena.describe()}  "
          f"prefill buckets {eng.programs.prefill_buckets}  "
          f"decode window {eng.window}")

    injector = None
    if args.inject_hung_decode_at is not None:
        from apex_tpu.resilience.faults import FaultInjector, FaultSpec
        injector = FaultInjector([FaultSpec(
            "hung_decode", at_step=args.inject_hung_decode_at,
            delay_s=max(0.5, 3 * deadline))]).install()

    samp = {}
    if args.sample is not None:
        temp, top_p = parse_sample(args.sample)
        samp = dict(temperature=temp, top_p=top_p)
    # the shared system prompt spans two full pages (page_size 4), so
    # every later request aliases them instead of re-prefilling
    system = [7, 8, 9, 10, 11, 12, 13, 14, 15] \
        if args.shared_system_prompt else []
    for i in range(args.requests):
        eng.submit(serving.Request(
            id=f"req-{i}",
            prompt=system + [2 + (i % 7), 3 + (i % 5), 4],
            max_new_tokens=args.max_new_tokens, seed=i, **samp))
    results = eng.serve()

    evicted = [r for r in results.values()
               if r.verdict == serving.EVICTED]
    for r in evicted:
        # detect -> evict -> RE-ADMIT: the evicted request retries and
        # completes once the wedge has cleared
        rid = f"{r.id}-retry"
        print(f"re-admitting evicted request {r.id} as {rid} "
              f"(incident {r.incident_id})")
        eng.submit(serving.Request(
            id=rid, prompt=[2, 3, 4],
            max_new_tokens=args.max_new_tokens))
    if evicted:
        results = eng.serve()

    if injector is not None:
        injector.uninstall()

    counts = {}
    for r in results.values():
        counts[r.verdict] = counts.get(r.verdict, 0) + 1
    tokens = sum(len(r.tokens) for r in results.values())
    print(f"served {len(results)} request(s): "
          + ", ".join(f"{k}={v}" for k, v in sorted(counts.items()))
          + f", {tokens} tokens")
    for rid in sorted(results):
        r = results[rid]
        inc = f"  incident={r.incident_id}" if r.incident_id else ""
        print(f"  {rid}: {r.verdict} "
              f"({len(r.tokens)} tokens){inc}")
    if eng.incidents.history:
        state = ("closed" if eng.incidents.current is None
                 else "OPEN")
        print(f"incident chain: {eng.incidents.history[0]} [{state}]")
    if eng.spec_k:
        rate = (eng._spec_accepted / eng._spec_drafted
                if eng._spec_drafted else 0.0)
        print(f"speculation: K={eng.spec_k}, "
              f"{eng._spec_accepted}/{eng._spec_drafted} drafts "
              f"accepted ({rate:.2f})")
    if eng.prefill_batch > 1:
        print(f"batched prefill: {eng._n_prefills} request(s) in "
              f"{eng._n_prefill_calls} program call(s)")
    if args.shared_system_prompt:
        print(f"prefix sharing: {eng._prefix_hits} hit(s), "
              f"{eng._n_prefills} prefill(s), "
              f"{eng._cow_copies} cow cop(ies), "
              f"{eng._kv_bytes_saved} KV bytes saved")

    eng.close()
    if args.trace_dir and eng.tracer is not None:
        import json
        os.makedirs(args.trace_dir, exist_ok=True)
        path = os.path.join(args.trace_dir, "reqtrace.jsonl")
        with open(path, "w") as f:
            for rec in eng.tracer.records:
                f.write(json.dumps(rec) + "\n")
        print(f"request traces written to {path}")

        def q(name, p):
            return eng.tracer.slo.hist(name).quantile(p)
        print("SLO summary (histogram quantiles, ms):")
        for name in ("serving/ttft_ms", "serving/e2e_ms",
                     "serving/intertoken_ms", "serving/queue_ms"):
            h = eng.tracer.slo.hist(name)
            if h.count:
                short = name.rsplit("/", 1)[-1]
                print(f"  {short:>14}: n={h.count:<4d} "
                      f"p50={q(name, 0.5):9.3f} "
                      f"p99={q(name, 0.99):9.3f}")
    if tel is not None:
        tel.close()                  # also stops the metrics server
        if metrics_srv is not None:
            metrics_srv.close()      # idempotent
        print(f"telemetry written to {tel_dir} — inspect "
              f"with: python -m apex_tpu.telemetry timeline "
              f"{tel_dir}")

    completed = counts.get(serving.COMPLETED, 0)
    assert completed >= args.requests - 1, counts
    print(f"OK: {completed} completed")


if __name__ == "__main__":
    main()
