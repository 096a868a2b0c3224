"""Multi-process launcher (reference: ``python -m
torch.distributed.launch --nproc_per_node=N train.py`` and the
examples/simple/distributed run.sh flows, SURVEY.md §2.6).

    python -m apex_tpu.launch --nproc 4 train.py --lr 0.1

Spawns ``nproc`` worker processes with the launcher env contract set —
``WORLD_SIZE``, ``RANK``, ``LOCAL_RANK``, ``JAX_COORDINATOR_ADDRESS``
— which is exactly what ``comm.initialize_distributed()`` (the
``init_process_group`` analog) consumes inside each worker.  Multi-node
use passes ``--nnodes``/``--node-rank``/``--coordinator`` so every node
agrees on the rendezvous (rank = node_rank * nproc + local_rank).

TPU use is ONE PROCESS PER HOST: a chip belongs to one process at a
time and the launcher assigns no chips to its children, so ``--nproc N``
with N > 1 on one TPU host would have every child claim every chip.
On a single TPU host run the script directly — one process drives the
whole mesh of the host's chips (README "Scope").  Across TPU hosts,
start one launcher per host with ``--nproc 1 --nnodes H --node-rank r
--coordinator host:port``.  ``--nproc N`` is for CPU/GPU-style
multi-process development, CI (workers pin ``JAX_PLATFORMS=cpu``) and
the reference's launch idiom.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["main"]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.launch",
        description="spawn N processes with the distributed env "
                    "contract (reference: torch.distributed.launch)")
    ap.add_argument("--nproc", "--nproc-per-node", type=int, default=1,
                    dest="nproc", help="processes on this node")
    ap.add_argument("--nnodes", type=int, default=1)
    ap.add_argument("--node-rank", type=int, default=0)
    ap.add_argument("--coordinator", default=None,
                    help="host:port every node can reach; default: a "
                         "free local port (single-node)")
    ap.add_argument("--module", "-m", action="store_true",
                    help="run script as a module (python -m)")
    ap.add_argument("script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)

    if args.nproc < 1 or args.nnodes < 1:
        ap.error(f"--nproc/--nnodes must be >= 1 (got {args.nproc}/"
                 f"{args.nnodes}); a zero-worker launch exiting 0 "
                 "would report success with no training run")
    if not 0 <= args.node_rank < args.nnodes:
        ap.error(f"--node-rank {args.node_rank} outside "
                 f"[0, {args.nnodes})")
    if args.nnodes > 1 and not args.coordinator:
        ap.error("--coordinator host:port is required with --nnodes>1 "
                 "(every node must name the same rendezvous)")
    if args.nproc > 1 and os.environ.get(
            "JAX_PLATFORMS", "").split(",")[0] in ("", "tpu"):
        print(f"apex_tpu.launch: --nproc {args.nproc} starts "
              f"{args.nproc} processes on this host and assigns them no "
              "chips: on a TPU host each would claim every chip and all "
              "but one fail or hang.  TPU use is one process per host "
              "(--nproc 1); multi-process workers must pin another "
              "backend (JAX_PLATFORMS=cpu).", file=sys.stderr)
    coordinator = args.coordinator or f"127.0.0.1:{_free_port()}"
    world = args.nnodes * args.nproc

    procs = []
    try:
        for local_rank in range(args.nproc):
            env = dict(os.environ)
            env["JAX_COORDINATOR_ADDRESS"] = coordinator
            env["WORLD_SIZE"] = str(world)
            env["RANK"] = str(args.node_rank * args.nproc + local_rank)
            env["LOCAL_RANK"] = str(local_rank)
            cmd = [sys.executable]
            if args.module:
                cmd += ["-m", args.script]
            else:
                cmd += [args.script]
            cmd += args.script_args
            procs.append(subprocess.Popen(cmd, env=env))
        # first nonzero exit wins and tears the rest down (the finally
        # below) — a crashed rank must not leave siblings hanging in
        # collectives forever (torchrun semantics)
        rc = 0
        alive = list(procs)
        while alive and rc == 0:
            for p in list(alive):
                r = p.poll()
                if r is not None:
                    alive.remove(p)
                    rc = rc or r
            if alive and rc == 0:
                time.sleep(0.2)
        return rc
    finally:
        # one worker failing (or ^C) must not leave siblings running:
        # the reference launcher's kill-the-group semantics
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


if __name__ == "__main__":
    sys.exit(main())
