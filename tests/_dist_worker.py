"""Worker body for tests/test_distributed_launch.py — one OS process
per rank, the reference's `torch.distributed.launch` child shape
(SURVEY.md §2.6; the reference idiom is init_process_group(backend=
"nccl") inside each launched process).

Run:  python _dist_worker.py <rank> <world> <port>
or (launcher mode — rendezvous already in the env, the way
`python -m apex_tpu.launch` spawns workers):  python _dist_worker.py

Pins the CPU platform BEFORE first backend use (a test worker must
never claim a chip), enables the gloo CPU collectives implementation,
then goes through the REAL `comm.initialize_distributed()` →
`jax.distributed.initialize()` handshake from the launcher env
contract (WORLD_SIZE/RANK/JAX_COORDINATOR_ADDRESS), builds the global
mesh, and runs one cross-process psum.  Prints "DIST_OK <rank>" only
if the reduced value is exactly the closed-form sum over ranks.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))     # repo root: apex_tpu is not installed


def main() -> int:
    if len(sys.argv) > 1:
        rank, world, port = (int(sys.argv[1]), int(sys.argv[2]),
                             sys.argv[3])
        # launcher env contract (what comm.initialize_distributed
        # parses)
        os.environ["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        os.environ["WORLD_SIZE"] = str(world)
        os.environ["RANK"] = str(rank)
    else:                       # apex_tpu.launch already set the env
        rank = int(os.environ["RANK"])
        world = int(os.environ["WORLD_SIZE"])
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import comm

    timeout = os.environ.get("APEX_DIST_INIT_TIMEOUT")
    mesh = comm.initialize_distributed(      # coords come from env
        timeout=float(timeout) if timeout else None)
    assert jax.process_count() == world, jax.process_count()
    assert jax.process_index() == rank, jax.process_index()
    n = world * 2                            # 2 local devices per rank
    assert len(mesh.devices.flatten()) == n

    # one shard per GLOBAL device, value = global row + 1 (assigned by
    # global index, so no assumption about rank-to-slot order); the
    # jitted sum is a cross-process all-reduce on the gloo backend
    sharding = NamedSharding(mesh, P(("data", "pipe", "ctx", "model")))

    def shard_for(idx):
        rows = np.arange(n, dtype=np.float32)[idx[0]]
        return np.broadcast_to((rows + 1.0)[:, None], (len(rows), 4))

    arr = jax.make_array_from_callback((n, 4), sharding, shard_for)
    total = jax.jit(jnp.sum,
                    out_shardings=NamedSharding(mesh, P()))(arr)
    want = 4.0 * n * (n + 1) / 2.0
    got = float(np.asarray(total))
    assert got == want, (got, want)
    print(f"DIST_OK {rank}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
