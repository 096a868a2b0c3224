"""Minimal end-to-end training with apex_tpu (reference: examples/simple).

A user-style script: tiny MLP regression, amp O2 (bf16 params + f32
masters + loss scaling), FusedAdam stepping the flat AMP gradient
pipeline (pack-once grads, fused unscale+norm, branch-free overflow
skip), FusedLayerNorm — and optional run telemetry: pass
``--telemetry-dir DIR`` (or set APEX_TPU_TELEMETRY_DIR) to record
loss / grad norm / loss scale / overflow into a device-side metric
ring, flushed to ``DIR/telemetry.jsonl`` once per window and rendered
afterwards by ``python -m apex_tpu.telemetry summarize DIR``.  Add
``--serve-metrics PORT`` for LIVE observability: a Prometheus-format
``/metrics`` endpoint (plus ``/healthz``) republishing every window
flush while the run is still going — scrape it mid-run and watch the
fleet/watchdog gauges move; afterwards ``python -m apex_tpu.telemetry
timeline DIR`` groups the run's recovery events by incident id.

Elastic resilience (the acceptance flow a preemptible-fleet user
copies): ``--checkpoint-dir DIR`` drives the loop through
``resilience.run_elastic`` — rotating bucket-native (v2) checkpoints
every ``--save-every`` steps, resume-from-newest-valid on restart, and
a :class:`~apex_tpu.resilience.PreemptionGuard` that converts SIGTERM
(or the deterministic ``--preempt-at-step N``) into one final forced
checkpoint and a clean exit.  Kill it, rerun it, and it continues
bit-exactly where it left off.

Multi-host failure domains (``--fleet``, needs ``--checkpoint-dir``):
a :class:`~apex_tpu.resilience.FleetMonitor` over an in-process beacon
channel plus N-1 simulated peer hosts — each step boundary publishes a
liveness beacon and classifies the peers.  Prove the recovery with
``--kill-host-at N``: the last simulated peer stops beaconing at step
N, the survivors agree on the death within the step-lag deadline,
"shrink" the mesh, restore the last-known-good checkpoint and replay —
the whole sequence (beacon gap -> host_dead -> shrink -> resume)
renders as the fleet timeline in ``telemetry summarize``.  Add
``--revive-host-at M`` (M > the shrink) for the GROW half: the killed
peer returns under a fresh incarnation, the members admit it at a
step boundary (``agree_admission``), the mesh grows back and the
checkpoint reshards onto it — kill -> shrink -> return -> admit ->
grow, end to end, on the same timeline.

Self-healing (``--watchdog``, needs both dirs above): a
:class:`~apex_tpu.resilience.Watchdog` watches the telemetry window
flushes for NaN storms, loss spikes and loss-scale collapse, and
escalates quarantine (loss-scale re-anchor) -> rollback to the
last-known-good checkpoint -> abort-with-diagnostics.  Prove it with
``--inject-nan-at N``: a NaN fault poisons a few steps, the watchdog
rolls back and replays, and the anomaly shows up in
``python -m apex_tpu.telemetry summarize DIR``.
"""

import argparse
import os

import jax
import jax.numpy as jnp

import apex_tpu
from apex_tpu import amp, telemetry
from apex_tpu.normalization import fused_layer_norm
from apex_tpu.optimizers import FusedAdam


def init_params(key, din=64, dh=128, dout=1):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (din, dh)) * 0.05,
        "b1": jnp.zeros((dh,)),
        "ln_w": jnp.ones((dh,)),
        "ln_b": jnp.zeros((dh,)),
        "w2": jax.random.normal(k2, (dh, dout)) * 0.05,
        "b2": jnp.zeros((dout,)),
    }


def forward(params, x):
    h = x @ params["w1"] + params["b1"]
    h = fused_layer_norm(h, params["ln_w"], params["ln_b"])
    h = jax.nn.relu(h)
    return h @ params["w2"] + params["b2"]


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--telemetry-dir",
                   default=os.environ.get("APEX_TPU_TELEMETRY_DIR")
                   or None,
                   help="record run telemetry under this directory")
    p.add_argument("--serve-metrics", type=int, default=None,
                   metavar="PORT",
                   help="live observability: serve /metrics "
                        "(Prometheus text) + /healthz on this port "
                        "while training (0 = ephemeral; needs "
                        "--telemetry-dir)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="rotating resilient checkpoints (run_elastic); "
                        "rerun with the same dir to resume")
    p.add_argument("--save-every", type=int, default=10,
                   help="checkpoint cadence in steps")
    p.add_argument("--preempt-at-step", type=int, default=None,
                   help="simulate a preemption notice at step N "
                        "(save-now-then-clean-exit)")
    p.add_argument("--watchdog", action="store_true",
                   help="self-healing: anomaly watchdog over the "
                        "telemetry flushes (needs --telemetry-dir and "
                        "--checkpoint-dir)")
    p.add_argument("--inject-nan-at", type=int, default=None,
                   help="chaos: poison gradients with NaN for a few "
                        "steps starting at N (the watchdog detects, "
                        "rolls back to last-known-good and replays)")
    p.add_argument("--inject-nan-steps", type=int, default=6,
                   help="how many steps the NaN fault poisons")
    p.add_argument("--fleet", action="store_true",
                   help="multi-host failure domains: liveness beacons "
                        "+ a FleetMonitor over simulated peer hosts "
                        "(needs --checkpoint-dir)")
    p.add_argument("--fleet-hosts", type=int, default=3,
                   help="fleet size incl. this host (the others are "
                        "simulated peers on an in-process channel)")
    p.add_argument("--kill-host-at", type=int, default=None,
                   help="chaos: the last simulated peer stops "
                        "beaconing at step N (the monitor detects the "
                        "death, survivors agree, shrink and resume "
                        "from the last checkpoint)")
    p.add_argument("--revive-host-at", type=int, default=None,
                   help="chaos: the killed peer returns with a fresh "
                        "incarnation at step N (the members admit it "
                        "at a step boundary, the mesh grows back and "
                        "the checkpoint reshards onto it; needs "
                        "--kill-host-at with N past the shrink)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)

    print(f"apex_tpu {apex_tpu.__version__} on {jax.default_backend()}")
    key = jax.random.key(0)
    params = init_params(key)

    # amp O2: bf16 model weights, f32 masters, loss scaling
    params, amp_state = amp.initialize(params, opt_level="O2",
                                       loss_scale="dynamic")
    opt = FusedAdam(params, lr=1e-2, weight_decay=1e-4)
    # flat gradient pipeline over the optimizer's bucket plan: grads
    # pack once, unscale+norm fuse per bucket, found_inf drives the
    # branch-free skip inside opt.step
    pipe = amp.FlatGradPipeline(optimizer=opt)

    tel = telemetry.Telemetry(args.telemetry_dir, window=16) \
        if args.telemetry_dir else None

    metrics_srv = None
    if args.serve_metrics is not None:
        if tel is None:
            raise SystemExit("--serve-metrics needs --telemetry-dir "
                             "(the exporter republishes the telemetry "
                             "session's window flushes)")
        metrics_srv = telemetry.MetricsServer(telemetry=tel,
                                              port=args.serve_metrics)
        print(f"serving live metrics at {metrics_srv.url}/metrics")

    xk, yk = jax.random.split(jax.random.key(1))
    x = jax.random.normal(xk, (256, 64))
    y = jnp.sum(x[:, :4], axis=1, keepdims=True) + \
        0.1 * jax.random.normal(yk, (256, 1))

    def loss_fn(p, x, y):
        pred = forward(p, x.astype(jnp.bfloat16))
        return jnp.mean((pred.astype(jnp.float32) - y) ** 2)

    fault_specs = []
    if args.inject_nan_at is not None:
        from apex_tpu.resilience.faults import FaultSpec
        fault_specs.append(FaultSpec(
            "nan_grads", at_step=args.inject_nan_at,
            n_steps=args.inject_nan_steps))
    if args.kill_host_at is not None:
        from apex_tpu.resilience.faults import FaultSpec
        fault_specs.append(FaultSpec("peer_death",
                                     at_step=args.kill_host_at))
    if args.revive_host_at is not None:
        if args.kill_host_at is None:
            raise SystemExit("--revive-host-at needs --kill-host-at "
                             "(only a killed peer can return)")
        from apex_tpu.resilience.faults import FaultSpec
        fault_specs.append(FaultSpec("host_return",
                                     at_step=args.revive_host_at))
    injector = None
    if fault_specs:
        from apex_tpu.resilience.faults import FaultInjector
        injector = FaultInjector(fault_specs).install()
    from apex_tpu.resilience.faults import training_fault

    box = {"amp": amp_state}
    losses = []

    def train_one(step):
        batch = x
        fault = training_fault(step)   # no-op None without --inject-*
        if fault is not None and fault.kind == "nan_grads":
            batch = x * jnp.nan        # poisoned batch -> NaN grads
        loss, flat = pipe.scaled_value_and_grad(
            loss_fn, box["amp"].scaler, opt.params, batch, y)
        opt.step(flat)                    # skips itself on overflow
        box["amp"] = amp.update_scaler(box["amp"], flat.found_inf)
        if tel is not None:
            # on-device scalars straight into the ring: the host fetch
            # happens once per window at the flush, not here
            tel.record({"loss": loss, "amp/grad_norm": flat.grad_norm,
                        "amp/clip_coef": flat.clip_coef,
                        **box["amp"].telemetry_values()}, step)
        losses.append(float(loss))
        if step % 10 == 0:
            # 1-in-10-steps console echo; the per-step record above
            # already lands these in the ring without a sync
            print(f"step {step:3d} loss {losses[-1]:.4f} "
                  f"scale {float(box['amp'].scaler.loss_scale):.0f} "   # apexlint: disable=APX102
                  f"inf {int(flat.found_inf)}")   # apexlint: disable=APX102

    wd = None
    if args.watchdog:
        if tel is None or not args.checkpoint_dir:
            raise SystemExit("--watchdog needs --telemetry-dir and "
                             "--checkpoint-dir (the sensor and the "
                             "actuator of the self-healing loop)")
        from apex_tpu.resilience.watchdog import (GradNormDetector,
                                                  LossSpikeDetector,
                                                  NanStreakDetector,
                                                  ScaleCollapseDetector,
                                                  Watchdog)
        # toy-scaled thresholds: a short run needs a short streak and
        # a clean window that ages within a few save cadences
        wd = Watchdog(
            detectors=[NanStreakDetector(streak=4),
                       LossSpikeDetector(),
                       GradNormDetector(),
                       ScaleCollapseDetector()],
            telemetry=tel, clean_window=8)

    fleet_mon = None
    if args.fleet:
        if not args.checkpoint_dir:
            raise SystemExit("--fleet needs --checkpoint-dir (shrink "
                             "recovery restores from the rotating "
                             "checkpoints)")
        from apex_tpu.resilience import fleet as fleet_mod
        # in-process fleet: this host plus N-1 simulated peers on a
        # LocalChannel; step-lag deadlines keep detection
        # deterministic at toy step rates
        channel = fleet_mod.LocalChannel()
        fleet_mon = fleet_mod.FleetMonitor(
            channel=channel, host=0, n_hosts=args.fleet_hosts,
            slow_after_steps=4, dead_after_steps=8,
            slow_after_s=None, dead_after_s=None,
            agreement_timeout_s=0.2, telemetry=tel)
        fleet_mod.SimulatedPeers(
            channel,
            hosts=list(range(1, args.fleet_hosts))).attach(fleet_mon)
        print(f"fleet: {args.fleet_hosts} hosts "
              f"({args.fleet_hosts - 1} simulated peers)")

    preempted = False
    resumed = False
    if args.checkpoint_dir:
        from apex_tpu.resilience import (CheckpointManager,
                                         PreemptionGuard, run_elastic)
        with CheckpointManager(args.checkpoint_dir, keep=3,
                               every=args.save_every) as mgr:
            res = run_elastic(
                train_one, mgr, opt, total_steps=args.steps,
                guard=PreemptionGuard(
                    preempt_at_step=args.preempt_at_step),
                watchdog=wd, fleet=fleet_mon,
                on_quarantine=lambda anomaly: box.update(
                    amp=box["amp"].re_anchor()),
                save_extras=lambda: {
                    "amp_state": box["amp"].state_dict()},
                on_restore=lambda amp_sd, extra, step: box.update(
                    amp=box["amp"].load_state_dict(amp_sd))
                if amp_sd else None)
        if res.restored_from is not None:
            resumed = True
            print(f"resumed at step {res.restored_from}")
        if res.rollbacks:
            print(f"watchdog: rolled back and replayed "
                  f"{res.rollbacks}x — run self-healed")
        if res.mesh_shrinks:
            print(f"fleet: peer failure survived — shrank to healthy "
                  f"mesh {res.mesh_shrinks}x and resumed")
        if res.mesh_grows:
            print(f"fleet: returned host re-admitted — grew back to "
                  f"full mesh {res.mesh_grows}x and resumed")
        preempted = res.preempted
        if preempted:
            print(f"preempted: final checkpoint durable at step "
                  f"{res.step} — rerun to resume")
    else:
        for step in range(1, args.steps + 1):
            train_one(step)
    if fleet_mon is not None:
        fleet_mon.close()
    if wd is not None:
        wd.close()
    if injector is not None:
        injector.uninstall()

    final_loss = None
    if tel is not None:
        with telemetry.span("toy/final_eval"):
            final_loss = float(loss_fn(opt.params, x, y))
        print(f"final eval loss {final_loss:.4f}")
        tel.close()                 # also stops the metrics server
        if metrics_srv is not None:
            metrics_srv.close()     # idempotent
        print(f"telemetry written to {args.telemetry_dir} — inspect "
              f"with: python -m apex_tpu.telemetry summarize "
              f"{args.telemetry_dir}")

    if preempted:
        return                       # partial run: no convergence bar
    if final_loss is None:
        final_loss = float(loss_fn(opt.params, x, y))
    if not resumed:                  # fresh run saw the early loss
        assert final_loss < losses[0] * 0.2, (losses[0], final_loss)
        print(f"OK: loss {losses[0]:.3f} -> {final_loss:.3f}")
    else:                            # resumed mid-descent
        print(f"OK: resumed, final loss {final_loss:.3f}")


if __name__ == "__main__":
    main()
