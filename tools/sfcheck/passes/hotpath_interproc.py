"""hotpath-interproc pass — the CLAUDE.md hot-path rule, call-graph-true.

Invariant (CLAUDE.md "Environment rules"): **never call JAX ops eagerly
in a per-window/per-record path** — each un-jitted op is an XLA compile
plus a device round trip, once per window. The per-file
``hotpath`` pass can only see module-scope ``jnp`` in ops/; this pass
re-grounds the rule in reachability: an eager ``jax.numpy`` COMPUTE call
(``asarray``/``array`` device ships are the sanctioned ship idiom —
operators/base.py:ship) is a finding when it executes per window, i.e.
when it sits

- lexically inside a per-window loop (project.py's window-loop
  heuristic), or
- in any function transitively reachable from a call site inside such a
  loop (the helper-called-from-a-loop blind spot),

UNLESS the enclosing function is device-classified (decorated/passed
into ``jax.jit``/``jitted``/``shard_map``/… or transitively called from
such a function) — traced code is exactly where jnp belongs. Findings
carry the resolved call path from the loop to the eager op.
"""

from __future__ import annotations

from typing import List

from tools.sfcheck.core import Finding, ProjectPass
from tools.sfcheck.project import MODULE_FN


def _within(spans, lineno: int) -> bool:
    return any(a <= lineno <= b for a, b in spans)


class HotpathInterprocPass(ProjectPass):
    name = "hotpath-interproc"
    description = ("no eager jax.numpy compute reachable from a "
                   "per-window loop (call-graph transitive)")
    invariant = ("everything hot goes through jax.jit: eager JAX work "
                 "on a per-window path is one XLA dispatch per window")

    def in_scope(self, relpath: str) -> bool:
        return relpath.startswith("spatialflink_tpu/")

    def run_project(self, project, graph, in_scope) -> List[Finding]:
        findings: List[Finding] = []
        for rel, facts, fn in project.iter_functions():
            if not in_scope(rel):
                continue
            if graph.is_device(rel, fn.qualname):
                continue
            chain = graph.hot_chain(rel, fn.qualname)
            where = ("module scope" if fn.qualname == MODULE_FN
                     else f"`{fn.name}`")
            for site in fn.eager_jnp:
                evidence = None
                if site.get("in_window_loop"):
                    evidence = [
                        f"{rel}:{site['lineno']}: eager `{site['expr']}(…)` "
                        f"directly inside a per-window loop at {where}",
                    ]
                elif chain is not None:
                    evidence = [f"{s.relpath}:{s.lineno}: {s.note}"
                                for s in chain]
                    evidence.append(
                        f"{rel}:{site['lineno']}: eager `{site['expr']}(…)` "
                        f"in `{fn.name}`")
                if evidence is None:
                    continue
                findings.append(Finding(
                    rel, site["lineno"], site["end_lineno"], self.name,
                    f"eager `{site['expr']}(…)` executes per window "
                    "(un-jitted XLA dispatch + device round trip each "
                    "time) — route through jax.jit "
                    "(operators/base.py:jitted) or hoist out of the "
                    "window path",
                    evidence=tuple(evidence),
                ))
        return findings
