"""What the two training drivers share: the amp O2 + fused-optimizer
step as the examples' loops drive it, the harness's host spans, and the
readings the comparison takes from the program's first steps.

A driver (``benchmarks/drivers/<name>.py``) subclasses ``AmpTrainJob``
and supplies the model, the jitted forward+backward and the batches;
``run.py`` drives ``step(i)`` for set-up's first steps and for the
window alike, on the one object.
"""

from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp

from apex_tpu import amp

from benchmarks import weights

# host spans of the loop, in the profiler's trace under these names
SPAN_NAMES = ("input_wait", "dispatch_fwd_bwd", "dispatch_optimizer",
              "sync")


class Spans:
    """``jax.profiler.TraceAnnotation`` spans that also keep, while
    ``recording`` (the traced part of a traced run; nothing is kept
    otherwise), their wall time and the CPU time of the calling thread:
    a dispatch that blocks on the device (donated buffers still in use)
    takes wall time but no CPU time."""

    def __init__(self):
        self.recording = False
        self.rows = []          # (name, wall seconds, thread-CPU seconds)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0, c0 = time.perf_counter(), time.thread_time()
        with jax.profiler.TraceAnnotation(name):
            yield
        if self.recording:
            self.rows.append((name, time.perf_counter() - t0,
                              time.thread_time() - c0))


def _norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


class AmpTrainJob:
    """Subclasses set, in ``__init__`` before calling ``_finish_init``:
    ``spec`` (the reference's parameter spec), ``seed``, ``opt`` (the
    fused optimizer, built on amp.initialize's params), ``amp_state``,
    ``programs`` (role -> jitted function name), ``units_per_step``,
    ``counts`` and the optimizer-state field that holds the first
    gradient (``first_update_field``, with ``first_update_scale``)."""

    first_program = "fwd_bwd"

    # the reference's names and the program's, where a module renames
    # its parameters (SyncBatchNorm): identity by default
    @staticmethod
    def to_program(tree):
        return tree

    @staticmethod
    def reference_name(name: str) -> str:
        return name

    def _finish_init(self):
        self.spans = Spans()
        self.losses, self.found_infs = [], []
        plan = self.opt._plan
        if plan is None or self.opt._master_bufs is None:
            raise SystemExit("benchmark drivers expect the bucketed "
                             "optimizer path with f32 masters (amp O2)")
        make = weights.maker(self.spec)

        @jax.jit
        def first_update(bufs):
            return jax.tree_util.tree_map(
                lambda x: _norm(x) * self.first_update_scale,
                plan.unpack_state_field(bufs))

        @jax.jit
        def change(master_bufs, key):
            return jax.tree_util.tree_map(
                lambda a, b: _norm(a - b), plan.unpack(master_bufs),
                self.to_program(make(key)))

        self._first_update, self._change = first_update, change

    # ---- the step, as the examples' loops drive it -------------------------
    def forward_backward(self, batch):
        """-> (loss, grads, found_inf); dispatches only."""
        raise NotImplementedError

    def next_batch(self, i):
        raise NotImplementedError

    def step(self, i):
        with self.spans("input_wait"):
            batch = self.next_batch(i)
        with self.spans("dispatch_fwd_bwd"):
            loss, grads, found_inf = self.forward_backward(batch)
        with self.spans("dispatch_optimizer"):
            self.opt.step(grads, found_inf=found_inf)
            self.amp_state = amp.update_scaler(self.amp_state, found_inf)
        self.losses.append(loss)
        self.found_infs.append(found_inf)
        return loss

    def wait(self, i):
        """Block on the loss of step ``i`` (at most two in flight)."""
        with self.spans("sync"):
            jax.block_until_ready(self.losses[i])

    def drain(self):
        with self.spans("sync"):
            jax.block_until_ready((self.opt.params, self.losses[-1:]))

    # ---- what the comparison reads (set-up, never the window) --------------
    def first_update_norms(self):
        """Norm by leaf of the first gradient as the optimizer got it,
        from its state after ONE step (device scalars)."""
        return self._first_update(self.opt.opt_state[self.first_update_field])

    def change_norms(self):
        """Norm by leaf of masters-now minus the seeded weights."""
        return self._change(self.opt._master_bufs,
                            weights.seed_key(self.seed))

    def failed_steps(self, first: int) -> int:
        """Steps from ``first`` on whose update was skipped or whose
        loss is not finite, read from the device-side flags."""
        loss = jax.device_get(self.losses[first:])
        inf = jax.device_get(self.found_infs[first:])
        return sum(1 for lo, fi in zip(loss, inf)
                   if int(fi) != 0 or not bool(jnp.isfinite(lo)))

    def compiled_programs(self):
        """role -> compiled executable of each program, for the kernel
        census of a traced run."""
        return {}

    def close(self):
        """Stop what the job started and drop its device state."""
        self.opt = self.amp_state = None
        self.losses, self.found_infs = [], []
