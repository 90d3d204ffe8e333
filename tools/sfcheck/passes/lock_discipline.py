"""lock-discipline pass — no cross-module work under a held lock, and a
cycle-free cross-module lock-order graph.

Invariant (the PR 9 inversion class, generalized): **a held lock scopes
a critical section, not a transaction** — while any lock is held,
nothing may transitively reach

- a **telemetry emit/flush** (``emit_instant`` / ``maybe_flush_stream``
  / ``seal_stream`` / ``flush_trace`` / the faults wrappers) owned by a
  DIFFERENT module: the emit path takes telemetry's own lock, so an
  emit under a foreign lock nests two module singletons' locks — the
  sanctioned idiom is the overload controller's queued
  ``_emit_locked``/``_drain_emits`` pair (queue under the lock, emit
  after release);
- a **user callback** (``*_provider``/``*callback*`` attribute calls):
  arbitrary code running under the caller's lock is how the
  ``python -m``-era deadlock happened live — providers must be invoked
  lock-free or under an explicitly documented re-entrancy contract;
- a **true-sync fetch** (``jax.device_get`` — a full device round
  trip) or other **blocking work** (``time.sleep``, ``subprocess.*``):
  a wedged device would wedge every thread queued on the lock.

Additionally, every span "lock A held → function acquiring lock B
reached" contributes a directed edge ``A → B`` to a project-wide
lock-order graph; **any cycle is a finding** (two modules that disagree
about acquisition order deadlock under the right interleaving — the
exact PR 9 lock-order inversion).

Lock identity is canonical to the DEFINING module: ``with self._lock:``
regions attribute to the enclosing class
(``spatialflink_tpu.telemetry:Telemetry._lock``); module-level
``with _LOCK:`` regions to the module, with imported locks resolved
through the import facts — ``from m1 import _LOCK`` acquired in m2 is
the same graph node as m1's own acquisitions, so opposite-order direct
acquisition across files still closes a cycle. A multi-item
``with a, b:`` contributes the ``a → b`` order edge (items acquire
left-to-right). ``acquire()``/``release()`` pairs on lock-named
receivers form regions too. Call-graph traversal is STRICT (no
unique-method-name guessing) so ``file.flush()`` can never fabricate an
edge. Same-module emits are exempt — telemetry buffering its own trace
writes under its own lock is that module's documented design, not an
inversion.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from tools.sfcheck.core import Finding, ProjectPass
from tools.sfcheck.project import is_test_relpath

#: Emit/flush terminals that take the telemetry singleton's lock.
HAZARD_EMIT_TERMINALS = frozenset({
    "emit_instant", "maybe_flush_stream", "seal_stream", "flush_trace",
    "_telemetry_instant", "_telemetry_fired",
})

#: Blocking-call detection: (exact dotted target) or (terminal, module
#: prefix of the dotted target).
BLOCKING_TERMINALS = frozenset({"sleep", "device_get"})
BLOCKING_PREFIXES = ("subprocess.",)

_CALLBACK_SUFFIXES = ("_provider", "callback", "_cb")

FnKey = Tuple[str, str]


def _terminal(target: str) -> str:
    return target.split(".")[-1].rstrip("()")


def _hazard_kind(call, rel: str) -> Optional[Tuple[str, str]]:
    """(kind, description) when this call is a direct hazard."""
    term = _terminal(call.target)
    if term in BLOCKING_TERMINALS or any(
            call.target.startswith(p) for p in BLOCKING_PREFIXES):
        what = ("true-sync fetch (a full device round trip)"
                if term == "device_get" else "blocking call")
        return ("blocking", f"{what} `{call.target}(…)`")
    if any(term.endswith(s) for s in _CALLBACK_SUFFIXES):
        return ("callback", f"user callback `{call.target}(…)` — "
                            "arbitrary code under the caller's lock")
    if term in HAZARD_EMIT_TERMINALS:
        return ("emit", f"telemetry emit/flush `{call.target}(…)` "
                        "(takes the telemetry singleton's lock)")
    return None


class LockDisciplinePass(ProjectPass):
    name = "lock-discipline"
    description = ("no cross-module emit/flush, user callback, "
                   "true-sync fetch, or blocking call reachable while a "
                   "lock is held; the cross-module lock-order graph "
                   "must be acyclic")
    invariant = ("a held lock scopes a critical section, not a "
                 "transaction: queue emits for after release "
                 "(overload._emit_locked idiom) and keep lock "
                 "acquisition order globally consistent")

    def in_scope(self, relpath: str) -> bool:
        return not is_test_relpath(relpath)

    # -- lock identity --------------------------------------------------------

    def _owner_class(self, facts, fn) -> Optional[str]:
        q = fn
        while q is not None:
            if q.cls is not None:
                return q.cls
            q = facts.functions.get(q.nested_in) \
                if q.nested_in is not None else None
        return None

    def _lock_id(self, facts, fn, token: str) -> str:
        """Canonical identity, keyed by the DEFINING module so a lock
        imported into another module is the same graph node as the
        owner's own acquisitions — `from m1 import _LOCK_A` acquired in
        m2 must collide with m1's `_LOCK_A`, or opposite-order
        acquisition across the two files is invisible."""
        if token.startswith("self."):
            cls = self._owner_class(facts, fn) or "?"
            return f"{facts.module}:{cls}.{token.split('.', 1)[1]}"
        parts = token.split(".")
        imp = facts.imports.get(parts[0])
        if imp is not None:
            if imp["kind"] == "object" and len(parts) == 1:
                return f"{imp['target']}:{imp['attr']}"
            if imp["kind"] == "module" and len(parts) > 1:
                return f"{imp['target']}:{'.'.join(parts[1:])}"
        return f"{facts.module}:{token}"

    # -- per-function summaries (fixpoint over strict edges) ------------------

    def _build_summaries(self, project, graph):
        """For every function: hazards and lock acquisitions reachable
        through strict call edges, each with the first-found call
        chain (list of "rel:line: note" steps)."""
        strict_edges: Dict[FnKey, List[Tuple[FnKey, int]]] = {}
        direct_hazards: Dict[FnKey, List[dict]] = {}
        direct_locks: Dict[FnKey, List[dict]] = {}
        for rel, facts, fn in project.iter_functions():
            key = (rel, fn.qualname)
            out = []
            for call in fn.calls:
                for ref in graph.resolve(facts, fn, call.target,
                                         strict=True):
                    out.append((ref, call.lineno))
            strict_edges[key] = out
            hz = []
            for call in fn.calls:
                kind_desc = _hazard_kind(call, rel)
                if kind_desc is not None:
                    hz.append({"kind": kind_desc[0],
                               "desc": kind_desc[1],
                               "rel": rel, "lineno": call.lineno,
                               "end_lineno": call.end_lineno,
                               "target": call.target})
            direct_hazards[key] = hz
            direct_locks[key] = [
                {"lock": self._lock_id(facts, fn, sp["lock"]),
                 "rel": rel, "lineno": sp["lineno"]}
                for sp in fn.lock_spans
            ]

        # Fixpoint: reachable[key] maps an item id to its chain.
        reach_h: Dict[FnKey, Dict[Tuple, List[str]]] = {}
        reach_l: Dict[FnKey, Dict[str, List[str]]] = {}
        for key in strict_edges:
            reach_h[key] = {
                (h["rel"], h["lineno"], h["kind"]): [
                    f"{h['rel']}:{h['lineno']}: {h['desc']}"
                ]
                for h in direct_hazards[key]
            }
            reach_l[key] = {
                lk["lock"]: [f"{lk['rel']}:{lk['lineno']}: acquires "
                             f"`{lk['lock'].split(':')[-1]}`"]
                for lk in direct_locks[key]
            }
        changed = True
        guard = 0
        while changed and guard < 50:
            changed = False
            guard += 1
            for key, edges in strict_edges.items():
                for ref, lineno in edges:
                    if ref == key:
                        continue
                    callee = graph.functions.get(ref)
                    if callee is None:
                        continue
                    step = (f"{key[0]}:{lineno}: "
                            f"`{graph.functions[key].name}` calls "
                            f"`{callee.name}(…)`")
                    for hid, chain in reach_h.get(ref, {}).items():
                        if hid not in reach_h[key]:
                            reach_h[key][hid] = [step] + chain
                            changed = True
                    for lid, chain in reach_l.get(ref, {}).items():
                        if lid not in reach_l[key]:
                            reach_l[key][lid] = [step] + chain
                            changed = True
        return strict_edges, direct_hazards, direct_locks, reach_h, reach_l

    # -- the pass -------------------------------------------------------------

    def run_project(self, project, graph, in_scope) -> List[Finding]:
        (strict_edges, direct_hazards, direct_locks,
         reach_h, reach_l) = self._build_summaries(project, graph)

        findings: List[Finding] = []
        seen_hazards = set()
        # lock-order edges: (A, B) -> evidence chain
        edges: Dict[Tuple[str, str], List[str]] = {}

        def emit_hazard(hid, rel, lineno, end_lineno, kind, desc,
                        lock_id, head, chain):
            dedup = (hid, lock_id)
            if dedup in seen_hazards:
                return
            seen_hazards.add(dedup)
            lock_disp = lock_id.split(":")[-1]
            fixes = {
                "emit": "queue the emit and drain it after release "
                        "(overload._emit_locked idiom)",
                "callback": "invoke providers/callbacks after the lock "
                            "is released, or document the re-entrancy "
                            "contract with a pragma",
                "blocking": "move the blocking work outside the "
                            "critical section",
            }
            findings.append(Finding(
                rel, lineno, end_lineno, self.name,
                f"{desc} executes while `{lock_disp}` is held — "
                f"{fixes[kind]}",
                evidence=tuple([head] + chain),
            ))

        for rel, facts, fn in project.iter_functions():
            key = (rel, fn.qualname)
            own_module = rel
            for sp in fn.lock_spans:
                lock_id = self._lock_id(facts, fn, sp["lock"])
                head = (f"{rel}:{sp['lineno']}: `{fn.name}` holds "
                        f"`{lock_id.split(':')[-1]}` "
                        f"(lines {sp['lineno']}–{sp['end_lineno']})")
                # nested lock spans inside this one → direct order
                # edges; a multi-item `with a, b:` shares one lineno,
                # so same-statement spans order by item rank (items
                # acquire left-to-right)
                for sp2 in fn.lock_spans:
                    if sp2 is sp:
                        continue
                    nested = (sp["lineno"] < sp2["lineno"]
                              <= sp["end_lineno"])
                    same_stmt = (sp2["lineno"] == sp["lineno"]
                                 and sp2.get("rank", 0)
                                 > sp.get("rank", 0))
                    if nested or same_stmt:
                        b = self._lock_id(facts, fn, sp2["lock"])
                        if b != lock_id:
                            edges.setdefault((lock_id, b), [
                                head,
                                f"{rel}:{sp2['lineno']}: acquires "
                                f"`{b.split(':')[-1]}` while holding it",
                            ])
                for call in fn.calls:
                    if not (sp["lineno"] <= call.lineno
                            <= sp["end_lineno"]):
                        continue
                    # direct hazard at the call site
                    kd = _hazard_kind(call, rel)
                    if kd is not None and in_scope(rel):
                        kind, desc = kd
                        if not (kind == "emit"
                                and self._emit_is_same_module(
                                    graph, facts, fn, call, own_module)):
                            emit_hazard(
                                (rel, call.lineno, kind), rel,
                                call.lineno, call.end_lineno, kind,
                                desc, lock_id, head,
                                [f"{rel}:{call.lineno}: direct call "
                                 f"inside the locked region"])
                # transitive hazards + lock edges via the strict edges
                # _build_summaries already resolved for this function
                for ref, call_line in strict_edges.get(key, ()):
                    if not (sp["lineno"] <= call_line
                            <= sp["end_lineno"]) or ref == key:
                        continue
                    step = (f"{rel}:{call_line}: locked region "
                            f"calls "
                            f"`{graph.functions[ref].name}(…)`")
                    for hid, chain in reach_h.get(ref, {}).items():
                        h_rel, h_line, h_kind = hid
                        if not in_scope(h_rel):
                            continue
                        if h_kind == "emit" and self._is_emit_file(
                                h_rel):
                            continue  # telemetry's own internals
                        emit_hazard(
                            hid, h_rel, h_line, h_line, h_kind,
                            chain[-1].split(": ", 1)[1], lock_id,
                            head, [step] + chain)
                    for lid, chain in reach_l.get(ref, {}).items():
                        if lid != lock_id:
                            edges.setdefault(
                                (lock_id, lid),
                                [head, step] + chain)

        # -- lock-order cycles (DFS over the edge graph) ----------------------
        adj: Dict[str, List[str]] = {}
        for (a, b) in edges:
            adj.setdefault(a, []).append(b)
        seen_cycles = set()
        for start in sorted(adj):
            path = [start]
            on_path = {start}

            def dfs(node):
                for nxt in sorted(adj.get(node, [])):
                    if nxt == start and len(path) > 1:
                        cyc = frozenset(path)
                        if cyc in seen_cycles:
                            continue
                        seen_cycles.add(cyc)
                        ev = []
                        ring = path + [start]
                        for a, b in zip(ring, ring[1:]):
                            ev.extend(edges[(a, b)])
                        first = edges[(ring[0], ring[1])]
                        anchor_rel = first[0].split(":")[0]
                        anchor_line = int(first[0].split(":")[1])
                        if in_scope(anchor_rel):
                            findings.append(Finding(
                                anchor_rel, anchor_line, anchor_line,
                                self.name,
                                "lock-order cycle: "
                                + " → ".join(
                                    x.split(":")[-1] for x in ring)
                                + " — two code paths acquire these "
                                  "locks in opposite orders; a "
                                  "deadlock needs only the right "
                                  "interleaving. Pick one global "
                                  "order (PARITY.md \"Concurrency "
                                  "discipline\")",
                                evidence=tuple(ev),
                            ))
                    elif nxt not in on_path:
                        path.append(nxt)
                        on_path.add(nxt)
                        dfs(nxt)
                        on_path.discard(nxt)
                        path.pop()

            dfs(start)

        findings.sort(key=lambda f: (f.path, f.lineno))
        return findings

    # -- helpers --------------------------------------------------------------

    @staticmethod
    def _is_emit_file(rel: str) -> bool:
        """Telemetry emitting under telemetry's own lock is that
        module's buffered-writer design, not a cross-module inversion."""
        return rel.split("/")[-1] == "telemetry.py"

    def _emit_is_same_module(self, graph, facts, fn, call,
                             own_module: str) -> bool:
        refs = graph.resolve(facts, fn, call.target, strict=True)
        if refs:
            return all(ref[0] == own_module for ref in refs)
        # Unresolvable receiver (`self.tel.emit_instant`): the emit
        # terminals live in telemetry — same-module only there.
        return self._is_emit_file(own_module)
