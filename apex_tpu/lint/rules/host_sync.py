"""APX101 host-sync-in-hot-path.

A device->host synchronization inside code reachable from a jitted
function either aborts tracing (``.item()`` / ``float()`` on a tracer
raises ConcretizationTypeError) or — when the function also runs
eagerly — serializes the dispatch pipeline: the host blocks on the
device every step and cannot enqueue step N+1 until step N's value
has come back (apex_tpu/benchlib.py module docstring).
Timing/checkpoint code that syncs on purpose belongs outside the
jit-reachable set, or behind ``# apexlint: disable=APX101``.
"""

from __future__ import annotations

import ast

from apex_tpu.lint.engine import Rule
from apex_tpu.lint.findings import ERROR

_SYNC_METHODS = {"item", "tolist", "block_until_ready", "copy_to_host_async"}
_SYNC_CALLS = {"numpy.asarray", "numpy.array", "numpy.float32",
               "numpy.float64", "jax.device_get"}
_CONCRETIZERS = {"float", "int", "bool"}


class HostSyncRule(Rule):
    id = "APX101"
    name = "host-sync-in-hot-path"
    severity = ERROR
    description = (
        "`.item()`, `float()/int()` on arrays, `np.asarray`, "
        "`jax.device_get`, or `.block_until_ready()` inside a function "
        "reachable from `jax.jit` (or a train step): breaks tracing or "
        "stalls the dispatch pipeline.")

    def check(self, ctx):
        for fn in ctx.functions_in(ctx.jit_reachable):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _SYNC_METHODS \
                        and not node.args:
                    # zero-arg method calls: x.item(), x.block_until_ready()
                    q = ctx.qualname(node.func)
                    if q is not None and q.startswith(
                            ("numpy.", "math.", "statistics.")):
                        continue
                    yield self.finding(
                        ctx, node,
                        f"`.{node.func.attr}()` in jit-reachable "
                        f"`{fn.name}` forces a device->host sync; return "
                        "the array and sync outside the hot path")
                    continue
                q = ctx.qualname(node.func)
                if q in _SYNC_CALLS:
                    yield self.finding(
                        ctx, node,
                        f"`{q}` in jit-reachable `{fn.name}` pulls the "
                        "value to host; use jnp/lax ops (device-side) "
                        "instead")
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in _CONCRETIZERS \
                        and node.args \
                        and not isinstance(node.args[0], ast.Constant):
                    yield self.finding(
                        ctx, node,
                        f"`{node.func.id}(...)` on a non-literal in "
                        f"jit-reachable `{fn.name}` concretizes a traced "
                        "value (ConcretizationTypeError under jit); keep "
                        "it an array or hoist to the host side")
