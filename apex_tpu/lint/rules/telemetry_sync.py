"""APX102 telemetry-sync-in-loop.

The runtime twin of APX101: APX101 catches a host sync that breaks (or
stalls) a JITTED function; APX102 catches the *telemetry* variant that
hides in plain host code — a train/eval loop that pulls a metric value
to the host every iteration (``float(loss_scale)``,
``grad_norm.item()``, ``jax.device_get(metrics)``,
``found_inf.block_until_ready()``).  Each pull serializes the dispatch
pipeline once per step — a full host round trip per metric per
iteration — for numbers nobody reads
at step rate.  The fix is the telemetry subsystem's whole design:
write metrics into a device-side ``apex_tpu.telemetry.MetricRing``
inside the step and flush ONCE per window
(``docs/observability.md``).

Scope: loop bodies in host-side code only (jit-reachable functions are
APX101's jurisdiction — one hazard, one rule), and only syncs whose
operand LOOKS like a telemetry metric (name mentions loss/grad_norm/
found_inf/clip_coef/...): precision beats recall, a deliberate
per-iteration sync on non-metric data is not this rule's business.
"""

from __future__ import annotations

import ast

from apex_tpu.lint.engine import Rule
from apex_tpu.lint.findings import WARNING

_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_SYNC_CALLS = {"jax.device_get", "numpy.asarray", "numpy.array"}
_CONCRETIZERS = {"float", "int"}

# substrings that mark a value as a training metric; lowercase-matched
# against every identifier in the synced expression
_METRIC_HINTS = (
    "loss_scale", "grad_norm", "found_inf", "clip_coef", "trust_ratio",
    "update_norm", "growth_tracker", "metric", "telemetry",
)

_FIX_HINT = ("record it into an apex_tpu.telemetry.MetricRing inside "
             "the step and flush once per window instead")


def _identifiers(node: ast.AST):
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            yield n.id
        elif isinstance(n, ast.Attribute):
            yield n.attr
        elif isinstance(n, ast.Constant) and isinstance(n.value, str):
            yield n.value


def _mentions_metric(node: ast.AST) -> bool:
    return any(h in ident.lower()
               for ident in _identifiers(node) for h in _METRIC_HINTS)


class TelemetrySyncRule(Rule):
    id = "APX102"
    name = "telemetry-sync-in-loop"
    severity = WARNING
    description = (
        "`jax.device_get` / `float()` / `.item()` / "
        "`.block_until_ready()` on a telemetry metric value inside a "
        "loop body: one device->host sync per iteration for a number "
        "read once per window; use MetricRing window flush "
        "(apex_tpu.telemetry).")

    def _sync_target(self, ctx, node: ast.Call):
        """The synced operand expression, or None if not a sync call."""
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _SYNC_METHODS and not node.args:
            q = ctx.qualname(node.func)
            if q is not None and q.startswith(
                    ("numpy.", "math.", "statistics.")):
                return None
            return node.func.value
        q = ctx.qualname(node.func)
        if q in _SYNC_CALLS and node.args:
            return node.args[0]
        if isinstance(node.func, ast.Name) \
                and node.func.id in _CONCRETIZERS \
                and node.args and not isinstance(node.args[0], ast.Constant):
            return node.args[0]
        return None

    def check(self, ctx):
        jit_fns = set(ctx.jit_reachable)
        seen = set()              # nested loops walk shared call nodes
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            fn = ctx.enclosing_function(loop)
            if fn is not None and fn.name in jit_fns:
                continue          # APX101's jurisdiction
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                target = self._sync_target(ctx, node)
                if target is None or not _mentions_metric(target):
                    continue
                what = (f"`.{node.func.attr}()`"
                        if isinstance(node.func, ast.Attribute)
                        else f"`{ctx.qualname(node.func) or ast.unparse(node.func)}(...)`")
                yield self.finding(
                    ctx, node,
                    f"{what} on a telemetry metric inside a loop body "
                    f"syncs the device every iteration; {_FIX_HINT}")
