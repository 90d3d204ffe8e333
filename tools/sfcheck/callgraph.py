"""sfcheck call graph — cross-file call resolution + jit-boundary classes.

Builds, from a ``project.Project``, the three classifications the
interprocedural passes gate on:

- **device entries**: functions that execute as traced/compiled XLA code
  — decorated with ``jax.jit``/``jitted``/``partial(jax.jit, …)``, passed
  by name into a jit wrapper (``jax.jit(f)``, ``shard_map(local, …)``,
  ``jax.vmap``, ``lax.scan/map/...``, the repo's ``jitted`` /
  ``window_program`` / ``sharded_window_kernel`` / ``instrument_jit``),
  or defined inside such a function (closures traced with it).
- **device-reachable**: transitive callees of device entries — their
  ``jnp`` calls are traced, never eager, so the interprocedural hotpath
  rules must not fire inside them.
- **hot** (per-window-reachable): transitive callees of call sites inside
  a per-window loop (project.py's window-loop heuristic), NOT crossing
  into device code. Each hot function carries a parent chain back to the
  originating loop call site — the evidence chain findings print.

Resolution is heuristic by design (this is a linter, not an importer):

- bare names resolve through local defs, enclosing-function nested defs,
  then the file's import map (one ``from x import y`` hop);
- ``mod.attr`` resolves through module imports;
- ``self.m`` resolves through the enclosing class, then its bases (by
  name, project-wide), then a unique-method-name match;
- ``obj.m`` / ``.m`` on unknown receivers resolves only when exactly
  ONE project class defines method ``m`` (ambiguity = no edge, keeping
  reachability conservative).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from tools.sfcheck.project import MODULE_FN, FileFacts, FunctionFacts, Project

#: Terminal names of calls whose function-valued arguments enter a
#: traced/compiled region.
JIT_WRAPPER_TERMINALS = frozenset({
    "jit", "jitted", "vmap", "pmap", "shard_map", "scan", "map",
    "fori_loop", "while_loop", "cond", "switch", "checkpoint", "remat",
    "window_program", "sharded_window_kernel", "instrument_jit",
    "custom_jvp", "custom_vjp", "pallas_call",
})

#: Decorator terminal names that make the decorated def a device entry.
JIT_DECORATOR_TERMINALS = frozenset({
    "jit", "jitted", "vmap", "pmap", "shard_map", "custom_jvp",
    "custom_vjp",
})

#: Memoized functions run once per distinct key, not once per window —
#: the repo's per-bucket program/constant caches. Hot reachability does
#: not cross into them.
MEMO_DECORATOR_TERMINALS = frozenset({"lru_cache", "cache", "cached_property"})


@dataclasses.dataclass
class FnRef:
    """A resolved project function: (relpath, qualname)."""
    relpath: str
    qualname: str

    @property
    def key(self) -> Tuple[str, str]:
        return (self.relpath, self.qualname)


@dataclasses.dataclass
class HotPathStep:
    relpath: str
    lineno: int
    note: str


class CallGraph:
    def __init__(self, project: Project):
        self.project = project
        # (relpath, qualname) -> FunctionFacts
        self.functions: Dict[Tuple[str, str], FunctionFacts] = {}
        # method name -> [(relpath, qualname)] across every project class
        self._methods: Dict[str, List[Tuple[str, str]]] = {}
        # class name -> (relpath, class dict)
        self._classes: Dict[str, List[Tuple[str, dict]]] = {}
        for rel, facts, fn in project.iter_functions():
            self.functions[(rel, fn.qualname)] = fn
        for rel, facts in project.files.items():
            for cname, c in facts.classes.items():
                self._classes.setdefault(cname, []).append((rel, c))
                for m, q in c["methods"].items():
                    self._methods.setdefault(m, []).append((rel, q))
        self.edges: Dict[Tuple[str, str], List[Tuple[Tuple[str, str], int]]] = {}
        self._build_edges()
        self.device_entries: Set[Tuple[str, str]] = set()
        self.device_reachable: Set[Tuple[str, str]] = set()
        self._classify_device()
        self.hot: Dict[Tuple[str, str], List[HotPathStep]] = {}
        self._classify_hot()

    # -- resolution ----------------------------------------------------------

    def _resolve_in_module(self, facts: FileFacts, name: str) \
            -> Optional[Tuple[str, str]]:
        if name in facts.functions:
            return (facts.relpath, name)
        imp = facts.imports.get(name)
        if imp is not None and imp["kind"] == "object":
            target = self.project.by_module().get(imp["target"])
            if target is not None:
                attr = imp["attr"]
                if attr in target.functions:
                    return (target.relpath, attr)
        return None

    def _resolve_method(self, cls_name: Optional[str], method: str,
                        facts: FileFacts,
                        strict: bool = False) -> List[Tuple[str, str]]:
        seen: Set[str] = set()
        stack = [cls_name] if cls_name else []
        while stack:
            cname = stack.pop()
            if cname in seen:
                continue
            seen.add(cname)
            for rel, c in self._classes.get(cname, []):
                if method in c["methods"]:
                    return [(rel, c["methods"][method])]
                for b in c["bases"]:
                    stack.append(b.split(".")[-1])
        if strict:
            # Strict callers (lock-discipline) reject the global
            # unique-method-name guess: `file.flush()` resolving into an
            # unrelated class's `flush` would fabricate lock edges.
            return []
        hits = self._methods.get(method, [])
        if len(hits) == 1:
            return list(hits)
        return []

    def resolve(self, facts: FileFacts, caller: FunctionFacts,
                target: str, strict: bool = False) -> List[Tuple[str, str]]:
        """Project functions a call-fact target may refer to ([] if the
        call leaves the project or cannot be resolved). ``strict``
        drops the unique-method-name last resorts — only edges grounded
        in a def, an import, or a class walk survive."""
        if target.startswith("."):                 # method on expression
            if strict:
                return []
            return self._resolve_method(None, target[1:], facts)
        parts = target.split(".")
        if parts[0] == "self" and len(parts) == 2:
            return self._resolve_method(caller.cls, parts[1], facts,
                                        strict=strict)
        if len(parts) == 1:
            # nested defs of the caller / its enclosing chain first
            q = caller.qualname
            while True:
                cand = (facts.relpath,
                        f"{q}.{parts[0]}" if q != MODULE_FN else parts[0])
                if cand in self.functions:
                    return [cand]
                fn = facts.functions.get(q)
                if fn is None or fn.nested_in is None:
                    break
                q = fn.nested_in
            hit = self._resolve_in_module(facts, parts[0])
            return [hit] if hit else []
        # mod.attr / mod.sub.attr through a module import
        imp = facts.imports.get(parts[0])
        if imp is not None and imp["kind"] == "module":
            mod = ".".join([imp["target"]] + parts[1:-1])
            target_facts = self.project.by_module().get(mod)
            if target_facts is not None and parts[-1] in target_facts.functions:
                return [(target_facts.relpath, parts[-1])]
            return []
        if imp is not None and imp["kind"] == "object" and len(parts) == 2:
            # method call on an imported OBJECT (e.g. telemetry.span):
            # unique-method-name heuristic scoped to the source module.
            target_facts = self.project.by_module().get(imp["target"])
            if target_facts is not None:
                for c in target_facts.classes.values():
                    if parts[1] in c["methods"]:
                        return [(target_facts.relpath,
                                 c["methods"][parts[1]])]
            if strict:
                return []
            return self._resolve_method(None, parts[1], facts)
        # ClassName.method / class instantiation chains: best effort
        if parts[0] in self._classes and len(parts) == 2:
            return self._resolve_method(parts[0], parts[1], facts,
                                        strict=strict)
        # method on an unresolved receiver (local var, param): the
        # unique-method-name heuristic is the last resort
        if not strict and len(parts) == 2 \
                and parts[0] not in facts.functions:
            return self._resolve_method(None, parts[1], facts)
        return []

    def _build_edges(self):
        for rel, facts, fn in self.project.iter_functions():
            out: List[Tuple[Tuple[str, str], int]] = []
            for call in fn.calls:
                for ref in self.resolve(facts, fn, call.target):
                    out.append((ref, call.lineno))
            self.edges[(rel, fn.qualname)] = out

    # -- device classification -----------------------------------------------

    def _canonical_terminal(self, facts: FileFacts, target: str) -> str:
        """Terminal name of a call target, following one import hop so
        aliased jit wrappers still match."""
        parts = target.split(".")
        imp = facts.imports.get(parts[0])
        if imp is not None and imp["kind"] == "object" and len(parts) == 1:
            return imp["attr"].split(".")[-1]
        return parts[-1].rstrip("()")

    def _classify_device(self):
        entries: Set[Tuple[str, str]] = set()
        for rel, facts, fn in self.project.iter_functions():
            # decorator-based
            for dec in fn.decorators:
                if self._canonical_terminal(facts, dec) \
                        in JIT_DECORATOR_TERMINALS:
                    entries.add((rel, fn.qualname))
            # argument-based: fn names passed into jit wrappers
            for call in fn.calls:
                term = self._canonical_terminal(facts, call.target)
                if term not in JIT_WRAPPER_TERMINALS:
                    continue
                # bare `map`/`cond`/… are builtins or locals, not lax:
                # generic terminals only count when dotted (lax.map) or
                # import-resolved.
                if term in ("map", "scan", "cond", "switch", "while_loop",
                            "fori_loop", "checkpoint", "remat") \
                        and "." not in call.target \
                        and call.target not in facts.imports:
                    continue
                cand_names = [a for a in call.args if a] + \
                    [v for v in call.kw_args.values() if v]
                for name in cand_names:
                    for ref in self.resolve(facts, fn, name):
                        entries.add(ref)
        # closures defined inside a device entry are traced with it
        grew = True
        while grew:
            grew = False
            for key, fn in self.functions.items():
                if key in entries or fn.nested_in is None:
                    continue
                if (key[0], fn.nested_in) in entries:
                    entries.add(key)
                    grew = True
        self.device_entries = entries
        # transitive callees are traced too
        reach = set(entries)
        stack = list(entries)
        while stack:
            key = stack.pop()
            for ref, _ in self.edges.get(key, []):
                if ref not in reach:
                    reach.add(ref)
                    stack.append(ref)
        self.device_reachable = reach

    # -- per-window (hot) classification -------------------------------------

    def _is_memoized(self, ref: Tuple[str, str]) -> bool:
        fn = self.functions.get(ref)
        if fn is None:
            return False
        facts = self.project.files[ref[0]]
        return any(self._canonical_terminal(facts, d)
                   in MEMO_DECORATOR_TERMINALS for d in fn.decorators)

    def _classify_hot(self):
        hot: Dict[Tuple[str, str], List[HotPathStep]] = {}
        stack: List[Tuple[str, str]] = []
        for rel, facts, fn in self.project.iter_functions():
            if (rel, fn.qualname) in self.device_reachable:
                continue
            for call in fn.calls:
                if not call.in_window_loop:
                    continue
                for ref in self.resolve(facts, fn, call.target):
                    if ref in self.device_reachable or ref in hot \
                            or self._is_memoized(ref):
                        continue
                    hot[ref] = [HotPathStep(
                        rel, call.lineno,
                        f"per-window loop in `{fn.name}` calls "
                        f"`{call.target}(…)`")]
                    stack.append(ref)
        while stack:
            key = stack.pop()
            chain = hot[key]
            for ref, lineno in self.edges.get(key, []):
                if ref in self.device_reachable or ref in hot \
                        or self._is_memoized(ref):
                    continue
                callee = self.functions[ref]
                hot[ref] = chain + [HotPathStep(
                    key[0], lineno,
                    f"`{self.functions[key].name}` calls "
                    f"`{callee.name}(…)`")]
                stack.append(ref)
        self.hot = hot

    # -- queries -------------------------------------------------------------

    def is_device(self, relpath: str, qualname: str) -> bool:
        return (relpath, qualname) in self.device_reachable

    def hot_chain(self, relpath: str, qualname: str) \
            -> Optional[List[HotPathStep]]:
        return self.hot.get((relpath, qualname))

    def counterpart_edges(self, relpath: str, qualname: str,
                          depth: int = 3) -> List[Tuple[str, str]]:
        """Transitive callees (≤ depth hops), with calls made by nested
        defs attributed to their enclosing function — used by mesh-parity
        to find a sharded kernel's single-device counterpart."""
        out: List[Tuple[str, str]] = []
        seen: Set[Tuple[str, str]] = set()
        frontier = [(relpath, qualname)]
        # nested defs count as part of the root (and of each callee)
        for d in range(depth):
            nxt: List[Tuple[str, str]] = []
            for key in frontier:
                group = [key] + [
                    k for k, fn in self.functions.items()
                    if k[0] == key[0] and fn.nested_in is not None
                    and (k[0], fn.nested_in) == key
                ]
                # include transitively nested closures
                grew = True
                while grew:
                    grew = False
                    for k, fn in self.functions.items():
                        if k in group or fn.nested_in is None:
                            continue
                        if (k[0], fn.nested_in) in group:
                            group.append(k)
                            grew = True
                for g in group:
                    for ref, _ in self.edges.get(g, []):
                        if ref not in seen:
                            seen.add(ref)
                            out.append(ref)
                            nxt.append(ref)
            frontier = nxt
        return out
