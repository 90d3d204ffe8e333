"""hotpath pass — the original tools/lint_hotpath.py rules, migrated.

Invariant (CLAUDE.md "Environment rules"): kernels in ``ops/`` are pure
functions. Two leak classes repeatedly cost real debugging time:

1. **Eager jax.numpy at module scope**: a module-level ``jnp.foo(...)``
   is an un-jitted XLA dispatch (a compile plus a device round trip)
   re-run in every process at import. Constants belong
   in plain numpy; device staging belongs to the operators.
2. **Wall-clock reads inside ops/ functions**: under ``jax.jit`` the
   trace-time value is baked into the program and the "timing" measures
   nothing (this produced one bogus 106M pts/s number). Timing belongs
   to the host layers (telemetry.py spans, mn/ reporters).
"""

from __future__ import annotations

import re

from tools.sfcheck.core import Pass
from tools.sfcheck.passes._shared import Bindings, ScopedVisitor, dotted


class _Visitor(ScopedVisitor):
    def __init__(self, bindings: Bindings, check_wall_clock: bool = True):
        super().__init__()
        self.b = bindings
        self.check_wall_clock = check_wall_clock

    def visit_Call(self, node):
        if self.fn_depth == 0 and self.b.jnp_call(node.func) is not None:
            self.out.append((
                node,
                f"module-level jax.numpy call `{dotted(node.func)}(…)` "
                "runs eagerly at import (un-jitted XLA dispatch; use "
                "numpy for host constants, jit for device code)",
            ))
        if self.check_wall_clock and self.fn_depth > 0 \
                and self.b.wall_clock_call(node.func) is not None:
            self.out.append((
                node,
                f"wall-clock call `{dotted(node.func)}(…)` inside an "
                "ops/ function (bakes the trace-time value under jit; "
                "time on the host side — telemetry.py spans)",
            ))
        self.generic_visit(node)


class HotpathPass(Pass):
    name = "hotpath"
    description = ("no import-time jax.numpy dispatch; no wall-clock "
                   "reads inside ops/ functions")
    invariant = ("ops/ kernels are pure: device work only under jit, "
                 "timing only on the host")
    allow_basenames = frozenset({"counters.py"})
    legacy_pragma = re.compile(r"#\s*hotpath:\s*ok\b")

    #: Host-side fault-tolerance modules: module-scope eager jnp would be
    #: an import-time XLA dispatch (an import-time device touch — the
    #: one thing the fault layer exists to survive), so the import-purity
    #: rule covers them too. The wall-clock rule stays ops/-only: the
    #: driver's retry backoff and the injector's hang kind legitimately
    #: read the clock (they are host control plane, never traced).
    #: overload.py joined with the overload work — the fire-site hooks
    #: import it from every assembler, so an import-time dispatch there
    #: would touch the device from the host control plane.
    _HOST_FT_MODULES = ("spatialflink_tpu/driver.py",
                        "spatialflink_tpu/faults.py",
                        "spatialflink_tpu/overload.py")

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("spatialflink_tpu/ops/")
                or relpath in self._HOST_FT_MODULES)

    def run(self, ctx):
        v = _Visitor(
            ctx.bindings,
            check_wall_clock=ctx.relpath not in self._HOST_FT_MODULES,
        )
        v.visit(ctx.tree)
        return v.out
