"""sync-discipline pass — ban ``jax.block_until_ready`` outside telemetry.py.

Invariant (CLAUDE.md "Environment rules"): the host waits for the device
in ONE way — a real device→host fetch (``telemetry.fetch`` /
``jax.device_get`` / ``np.asarray``) — because the fetch is where sync
time and device→host bytes are accounted, and a result the host never
read is not a result a window can commit or a benchmark can time. A
bare ``block_until_ready`` waits outside that accounting. (On the
directly attached TPU v5e it does wait for the device — CHANGES.md
PR 21 — so whether to keep the ban is ROADMAP C9's call, not a
correctness matter.) The ban covers everything —
bench_suite.py, the driver entry, the tests, the SLO engine
(``spatialflink_tpu/slo.py``), the sfprof stream/recover modules, and
the fault-tolerance layer (``spatialflink_tpu/driver.py``'s retry/
failover paths and ``spatialflink_tpu/faults.py`` — a "sync" before a
checkpoint commit that doesn't fetch would checkpoint un-finished
state) — except ``spatialflink_tpu/telemetry.py``, the ONE module
allowed to
talk about sync primitives directly (which is also why the link-health
probe, whose fetch IS its measurement, lives there and nowhere else).
"""

from __future__ import annotations

import ast

from tools.sfcheck.core import Pass
from tools.sfcheck.passes._shared import Bindings

_MSG = (
    "`block_until_ready` waits for the device outside the accounted sync "
    "path — synchronize with a device→host fetch instead: "
    "telemetry.fetch / jax.device_get / np.asarray"
)


class _Visitor(ast.NodeVisitor):
    def __init__(self, bindings: Bindings):
        self.b = bindings
        self.out = []

    def visit_Call(self, node):
        if self.b.jax_call(node.func) == "block_until_ready":
            self.out.append((node, _MSG))
        elif (isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"):
            # Method form: arr.block_until_ready()
            self.out.append((node, _MSG))
        self.generic_visit(node)


class SyncDisciplinePass(Pass):
    name = "sync-discipline"
    description = ("no jax.block_until_ready anywhere outside "
                   "spatialflink_tpu/telemetry.py")
    invariant = ("every sync is an accounted device→host fetch, never "
                 "a bare block_until_ready")

    def applies_to(self, relpath: str) -> bool:
        return relpath not in ("spatialflink_tpu/telemetry.py",
                               "telemetry.py")

    def run(self, ctx):
        v = _Visitor(ctx.bindings)
        v.visit(ctx.tree)
        return v.out
