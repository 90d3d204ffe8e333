"""trace-hygiene pass — no tracer leaks or host syncs inside ops/ kernels.

Invariant (CLAUDE.md "Architecture invariants"): host = control plane,
device = compute plane. Inside ``ops/`` function bodies the following are
either a ConcretizationTypeError waiting to happen under jit, or a hidden
device→host round trip:

- ``float(x)`` / ``int(x)`` / ``bool(x)`` applied to a function
  parameter (parameters are traced under jit/vmap/shard_map);
- ``.item()`` — a per-call device→host fetch;
- ``np.asarray(x)`` / ``np.array(x)`` on a function parameter — silently
  materializes a traced value on the host;
- ``jax.device_get`` — fetches belong to the operator/telemetry layers;
- ``print`` — host I/O that under jit fires at trace time only.

Host-side helpers that legitimately live in ops/ carry a
``# sfcheck: ok=trace-hygiene`` pragma with a justification, or sit in an
allowlisted fully-host module (ops/counters.py).
"""

from __future__ import annotations

import ast

from tools.sfcheck.core import Pass
from tools.sfcheck.passes._shared import Bindings, ScopedVisitor

_SCALARIZERS = {"float", "int", "bool"}


class _Visitor(ScopedVisitor):
    def __init__(self, bindings: Bindings):
        super().__init__()
        self.b = bindings

    def _param_arg(self, node):
        if (len(node.args) >= 1 and isinstance(node.args[0], ast.Name)
                and self.is_param(node.args[0].id)):
            return node.args[0].id
        return None

    def visit_Call(self, node):
        if self.fn_depth > 0:
            func = node.func
            if (isinstance(func, ast.Name) and func.id in _SCALARIZERS
                    and len(node.args) == 1 and not node.keywords):
                param = self._param_arg(node)
                if param is not None:
                    self.out.append((
                        node,
                        f"`{func.id}({param})` concretizes the kernel "
                        "parameter — under jit this is a tracer→host "
                        "sync (ConcretizationTypeError on traced "
                        "values); keep it traced or hoist to the host "
                        "layer",
                    ))
            if isinstance(func, ast.Name) and func.id == "print":
                self.out.append((
                    node,
                    "`print(…)` inside an ops/ function — host I/O in "
                    "a traced path (fires at trace time only under "
                    "jit); report through telemetry.py / mn/ instead",
                ))
            if (isinstance(func, ast.Attribute) and func.attr == "item"
                    and not node.args and not node.keywords):
                self.out.append((
                    node,
                    "`.item()` inside an ops/ function — a per-call "
                    "device→host fetch (device round trip); fetch once "
                    "in the operator layer",
                ))
            np_name = self.b.np_call(func)
            if np_name in ("asarray", "array"):
                param = self._param_arg(node)
                if param is not None:
                    self.out.append((
                        node,
                        f"`np.{np_name}({param})` materializes the "
                        "kernel parameter on the host — traced values "
                        "must stay on device (use jnp, or move this "
                        "helper to the host layer)",
                    ))
            if self.b.jax_call(func) == "device_get":
                self.out.append((
                    node,
                    "`jax.device_get` inside an ops/ function — "
                    "device→host fetches belong to the operator/"
                    "telemetry layers (telemetry.fetch accounts them)",
                ))
        self.generic_visit(node)


class TraceHygienePass(Pass):
    name = "trace-hygiene"
    description = ("no tracer concretization or host syncs inside ops/ "
                   "kernel functions")
    invariant = ("host = control plane, device = compute plane; kernels "
                 "stay traced end to end")
    allow_basenames = frozenset({"counters.py"})

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith("spatialflink_tpu/ops/")

    def run(self, ctx):
        v = _Visitor(ctx.bindings)
        v.visit(ctx.tree)
        return v.out
