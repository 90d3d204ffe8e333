"""donation-safety pass — no use of a buffer after it was donated.

Invariant (ahead of the ROADMAP item 1 double-buffered executor):
``donate_argnums`` hands the argument's device buffer to XLA — after the
call the Python name points at a DELETED buffer, and the failure mode
can be silent garbage or a deferred crash on the next fetch, not an
exception at the use site. So: once a local is passed at a
donated position, reading it again (without rebinding) is a finding.

Donating call sites are recognized in three spellings, resolved
project-wide:

- inline: ``jax.jit(f, donate_argnums=(0,))(x)``;
- wrapper assignment: ``step = jax.jit(f, donate_argnums=(0,))`` then
  ``step(x)`` — including wrappers defined at module scope in ANOTHER
  file and imported (the cross-file evidence case);
- decorator: ``@partial(jax.jit, donate_argnums=(0,))`` on a def, then
  direct calls to it.

The liveness rule is linear-with-loops: a load of the donated name after
the call (before any rebind) is a finding; inside a loop, a load
anywhere else in the loop body counts too (it executes on the next
iteration) unless the loop rebinding idiom ``x = step(x)`` is used.
Only plain-Name arguments are tracked — attribute/container donation is
out of heuristic scope (documented).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from tools.sfcheck.core import Finding, ProjectPass
from tools.sfcheck.project import MODULE_FN, FunctionFacts


def _donating_wrappers(fn: FunctionFacts) -> Dict[str, Tuple[List[int], int]]:
    """Names bound (in this scope) to a jit wrapper with literal
    donate_argnums: ``name -> (argnums, def_line)``. Recognized by a
    store to the name on the same line as a wrapper-creating
    ``…jit(…, donate_argnums=…)`` call."""
    out: Dict[str, Tuple[List[int], int]] = {}
    donate_lines = {}
    for call in fn.calls:
        if call.donate is None or call.target.endswith("()"):
            continue
        if call.target.split(".")[-1] in ("jit", "pjit", "jitted"):
            for ln in range(call.lineno, call.end_lineno + 1):
                donate_lines[ln] = call.donate
    for name, lines in fn.stores.items():
        for ln in lines:
            if ln in donate_lines:
                out[name] = (donate_lines[ln], ln)
    return out


class DonationSafetyPass(ProjectPass):
    name = "donation-safety"
    description = ("no read of a local after it was passed at a "
                   "donate_argnums position (use-after-donate)")
    invariant = ("a donated buffer is deleted at dispatch: rebind "
                 "(`x = step(x)`) or never touch it again")

    def in_scope(self, relpath: str) -> bool:
        return (relpath.startswith("spatialflink_tpu/")
                or relpath in ("bench_suite.py", "__graft_entry__.py"))

    # -- donation resolution -------------------------------------------------

    def _call_donation(self, graph, facts, fn, call, local_wrappers,
                       module_wrappers) -> Optional[Tuple[List[int], str]]:
        """(argnums, evidence-of-where-donation-was-declared) if this
        call donates, else None."""
        if call.donate is not None and call.target.endswith("()"):
            return (call.donate,
                    f"{facts.relpath}:{call.lineno}: inline "
                    f"`{call.target[:-2]}(…, donate_argnums=…)` call")
        if "." not in call.target:
            hit = local_wrappers.get(call.target) \
                or module_wrappers.get(call.target)
            if hit is not None:
                argnums, ln, where = hit
                return (argnums,
                        f"{where}:{ln}: donating wrapper "
                        f"`{call.target} = …jit(…, donate_argnums=…)`")
            imp = facts.imports.get(call.target)
            if imp is not None and imp["kind"] == "object":
                src = graph.project.by_module().get(imp["target"])
                if src is not None:
                    mod_fn = src.functions.get(MODULE_FN)
                    if mod_fn is not None:
                        w = _donating_wrappers(mod_fn).get(imp["attr"])
                        if w is not None:
                            return (w[0],
                                    f"{src.relpath}:{w[1]}: donating "
                                    f"wrapper `{imp['attr']}` (imported "
                                    f"here as `{call.target}`)")
        for ref in graph.resolve(facts, fn, call.target):
            callee = graph.functions.get(ref)
            if callee is not None and callee.donate_decorator:
                return (callee.donate_decorator,
                        f"{ref[0]}:{callee.lineno}: `{callee.name}` is "
                        "decorated with donate_argnums")
        return None

    # -- liveness ------------------------------------------------------------

    def _violation(self, fn: FunctionFacts, name: str, call) \
            -> Optional[int]:
        """Line of the first read of ``name`` after its donation at
        ``call``, or None if it is rebound / never read again."""
        lo, hi = call.lineno, call.end_lineno
        stores = sorted(fn.stores.get(name, []))
        loads = sorted(fn.loads.get(name, []))
        if any(lo <= s <= hi for s in stores):
            return None                      # `x = step(x)` rebind idiom
        loop = next((sp for sp in fn.loops if sp[0] <= lo and hi <= sp[1]),
                    None)
        if loop is not None:
            if any(loop[0] <= s <= loop[1] for s in stores):
                return None                  # rebound somewhere in the loop
            for ld in loads:
                if loop[0] <= ld <= loop[1] and not lo <= ld <= hi:
                    return ld                # runs again next iteration
            # no rebind anywhere in the loop: the donating call itself
            # re-reads the deleted buffer on the next iteration
            return lo
        next_store = min((s for s in stores if s > hi), default=None)
        for ld in loads:
            if ld > hi and (next_store is None or ld < next_store):
                return ld
        return None

    def run_project(self, project, graph, in_scope) -> List[Finding]:
        findings: List[Finding] = []
        # module-level donating wrappers, per file (for same-file use
        # from inside functions): name -> (argnums, line, relpath)
        module_wrappers_by_file: Dict[str, Dict] = {}
        for rel, facts in project.files.items():
            mod_fn = facts.functions.get(MODULE_FN)
            module_wrappers_by_file[rel] = {
                k: (v[0], v[1], rel)
                for k, v in (_donating_wrappers(mod_fn) or {}).items()
            } if mod_fn is not None else {}
        for rel, facts, fn in project.iter_functions():
            if not in_scope(rel):
                continue
            local_wrappers = {
                k: (v[0], v[1], rel)
                for k, v in _donating_wrappers(fn).items()
            }
            module_wrappers = module_wrappers_by_file.get(rel, {})
            for call in fn.calls:
                don = self._call_donation(graph, facts, fn, call,
                                          local_wrappers, module_wrappers)
                if don is None:
                    continue
                argnums, declared = don
                for pos in argnums:
                    if pos >= len(call.args):
                        continue
                    arg = call.args[pos]
                    if arg is None or "." in arg:
                        continue             # only plain Names tracked
                    bad = self._violation(fn, arg, call)
                    if bad is None:
                        continue
                    findings.append(Finding(
                        rel, bad, bad, self.name,
                        f"`{arg}` is read after being donated at "
                        f"line {call.lineno} — the device buffer is "
                        "deleted at dispatch; rebind "
                        f"(`{arg} = …({arg})`) or stop using it",
                        evidence=(
                            declared,
                            f"{rel}:{call.lineno}: `{arg}` passed at "
                            f"donated position {pos}",
                            f"{rel}:{bad}: `{arg}` read again "
                            "(use-after-donate)",
                        ),
                    ))
        return findings
