"""sfcheck project model — per-file fact extraction for whole-program passes.

The file passes (tools/sfcheck/passes/) see one AST at a time; the
project passes (hotpath-interproc, mesh-parity, recompile-surface,
donation-safety) need the cross-file picture CLAUDE.md's invariants are
actually written about. This module extracts, from each file's AST, a
compact JSON-serializable ``FileFacts`` summary holding everything those
passes need:

- **imports**: local name → module / object it resolves to;
- **functions** (incl. methods and nested defs, qualname-indexed):
  params, decorators, span, every call site (resolved-enough target
  expression + argument names + ``donate_argnums`` if literal + whether
  the call sits inside a per-window loop), per-name load/store lines,
  loop spans;
- **candidate sites** evaluated later under call-graph gating:
  ``eager_jnp`` (jax.numpy COMPUTE calls — ``asarray``/``array`` device
  ships are sanctioned) and ``shape_sites`` (device-shape sinks whose
  dimension derives from a data-dependent Python int — ``len()`` of a
  runtime collection, ``.shape`` subscripts, loop indices — without
  passing a compaction-ladder sanitizer: ``pick_capacity`` /
  ``wire_pane_bucket`` / ``next_bucket`` / ``capacity_ladder``);
- **classes** (bases + methods + annotated field names) and
  **names_used** (every identifier, for mesh-parity's "referenced by a
  parity test" check);
- **pragmas**: ``# sfcheck: ok`` comment tokens (tokenize-based, so
  pragmas inside string literals — the test corpus embeds some — are
  not mistaken for real suppressions), consumed-or-stale tracked by the
  pragma-staleness rule;
- **concurrency & contract facts** (the v3 passes): per-function
  lock-scope spans (``with self._lock:`` blocks plus paired
  ``acquire()``/``release()`` regions on lock-named receivers),
  ``global`` declarations, env-var access sites
  (``os.environ.get/[]``/``getenv``/``.pop`` with a literal name),
  instant-event emit sites (``emit_instant``/``_emit_locked``/
  ``_telemetry_instant`` with a literal name or literal f-string head),
  module-level singleton instantiations (``name = SameModuleClass()``),
  the module's ``if __name__ == "__main__":`` guard (and whether it
  delegates to the canonical import), and module-level literal
  constants (strings/ints, string sequences, string-keyed dicts — the
  twin-contract surfaces: version pins, ``SPEC_KEYS``,
  ``INJECTION_POINTS``, the chaos ``MATRIX``, ``ENV_VARS``).

- **checkpoint & determinism facts** (the v4 passes): per-function
  checkpoint payload writes (string dict-literal keys, ``out["k"] = …``
  subscript stores, ``save_checkpoint(p, k=…)`` kwargs — each with a
  CONDITIONAL flag from enclosing If/except context) and reads (bare
  ``state["k"]`` subscripts incl. literal-string loop vars,
  ``.get("k"[, default])``, ``"k" in state`` guards), kept only for
  publisher/restorer-shaped functions; and ``nondet_sites`` — wall-clock
  reads, global unseeded RNG draws, set-order iteration, unsorted
  filesystem enumeration, ``id()``-keyed ordering — for every function
  (replay-determinism's taint sources).

Facts round-trip through JSON (``to_dict``/``facts_from_dict``) so the
incremental cache can skip re-parsing unchanged files entirely.

The per-window loop heuristic matches the repo's (very regular) window
plumbing: a ``for`` whose iterator is a call to one of
``WINDOW_ITER_CALLEES`` (``self.windows(...)``, ``asm.stream(...)``,
``soa_point_batches(...)``, …), or whose loop target is literally
``win``/``window``. Everything lexically inside such a loop runs once
per window — the path CLAUDE.md bans eager JAX work on.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from tools.sfcheck.passes._shared import Bindings, dotted

#: Iterator-call terminal names that mark a per-window / per-record loop.
WINDOW_ITER_CALLEES = frozenset({
    "windows", "stream", "soa_point_batches", "count_window_batches",
    "_checkpointable_windows", "_checkpointable_soa_windows", "feed",
    "flush",
})

#: Loop targets that mark a per-window loop even without a recognized
#: iterator call (the repo convention: ``for win in …``).
WINDOW_TARGET_NAMES = frozenset({"win", "window"})

#: jax.numpy attributes that are device SHIPS, not compute — sanctioned
#: per window at the documented ship sites (operators/base.py:ship).
JNP_SHIP_ATTRS = frozenset({"asarray", "array"})

#: jax.numpy attributes that are pure host-side METADATA — no XLA
#: dispatch happens (dtype lattice queries), so they are never "eager".
JNP_META_ATTRS = frozenset({
    "finfo", "iinfo", "dtype", "result_type", "promote_types",
    "issubdtype", "shape", "ndim",
})

#: Calls that launder a data-dependent int into a static bucket — the
#: compaction ladder (ops/compaction.py) + the padding bucketer.
SHAPE_SANITIZERS = frozenset({
    "pick_capacity", "wire_pane_bucket", "next_bucket", "capacity_ladder",
    "max_window_cell_count",
})

#: Device-shape allocators: a dimension carrying taint here IS a
#: per-window recompile (one XLA compile per distinct value).
JNP_SHAPE_SINKS = frozenset({
    "zeros", "ones", "full", "empty", "arange", "linspace", "eye",
})

MODULE_FN = "<module>"

#: Call terminals that emit a structured instant event with the event
#: name as their first argument — the producer side of the
#: emitted-event ↔ sfprof-consumer contract. ``_emit_locked`` is the
#: overload controller's queued-emit idiom, ``_telemetry_instant`` the
#: fault injector's lazy-import wrapper; both forward to
#: ``telemetry.emit_instant`` verbatim.
EMIT_NAME_TERMINALS = frozenset({
    "emit_instant", "_emit_locked", "_telemetry_instant",
})

#: The framed-CRC checkpoint publish/load entry points
#: (spatialflink_tpu/checkpoint.py) — a function calling one is a
#: checkpoint publisher/restorer even without the naming convention.
CKPT_SAVE_TERMINALS = frozenset({"save_checkpoint"})
CKPT_LOAD_TERMINALS = frozenset({"load_checkpoint"})

#: Module-level ``random`` draws that consult the shared, unseeded
#: global generator (the seeded ``random.Random(seed)`` / ``np.random.
#: default_rng(seed)`` instance idiom is NOT matched — receivers are
#: local names, not the module).
NONDET_RANDOM_FNS = frozenset({
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "sample", "uniform", "gauss", "normalvariate", "lognormvariate",
    "expovariate", "betavariate", "gammavariate", "triangular",
    "vonmisesvariate", "paretovariate", "weibullvariate", "getrandbits",
    "randbytes",
})
NONDET_NP_RANDOM_FNS = frozenset({
    "rand", "randn", "randint", "random", "random_sample", "ranf",
    "sample", "choice", "shuffle", "permutation", "uniform", "normal",
    "standard_normal", "poisson", "exponential", "beta", "binomial",
    "gamma", "bytes",
})
#: RNG constructors that are only deterministic when SEEDED — a
#: zero-argument call is a nondeterminism source.
NONDET_RNG_CTORS = frozenset({"default_rng", "RandomState", "Random"})
#: Filesystem enumerations whose order is filesystem-dependent unless
#: wrapped in ``sorted(…)``.
NONDET_FS_FNS = frozenset({"listdir", "scandir", "iterdir", "glob",
                           "iglob", "rglob"})
#: ``datetime``/``date`` classmethods that read the wall clock
#: (``fromtimestamp``/``strptime`` are pure conversions — not listed).
NONDET_DATETIME_FNS = frozenset({"now", "utcnow", "today"})

_ENV_NAME_RE = None  # compiled lazily (module import stays light)


def _is_lockish(token: str) -> bool:
    """A dotted expression whose terminal segment names a lock
    (``self._lock``, ``_LOCK_A``, ``registry_lock``)."""
    return "lock" in token.split(".")[-1].lower()


def _env_name(value) -> Optional[str]:
    """The literal env-var name of an access site, or None. Restricted
    to SHOUTY_SNAKE names so dict ``.pop("key")`` calls don't flood the
    facts."""
    global _ENV_NAME_RE
    if not isinstance(value, str) or not value:
        return None
    if _ENV_NAME_RE is None:
        import re
        _ENV_NAME_RE = re.compile(r"^_?[A-Z][A-Z0-9_]*$")
    return value if _ENV_NAME_RE.match(value) and "_" in value else None


@dataclasses.dataclass
class CallFact:
    target: str            # dotted expr ("np.zeros", "self.windows", ".item")
    lineno: int
    end_lineno: int
    args: List[Optional[str]]            # dotted names of positional args
    kw_args: Dict[str, Optional[str]]    # keyword name -> dotted value name
    donate: Optional[List[int]] = None   # literal donate_argnums, if any
    in_window_loop: bool = False


@dataclasses.dataclass
class FunctionFacts:
    name: str
    qualname: str
    lineno: int
    end_lineno: int
    cls: Optional[str] = None            # enclosing class name
    nested_in: Optional[str] = None      # enclosing function qualname
    params: List[str] = dataclasses.field(default_factory=list)
    decorators: List[str] = dataclasses.field(default_factory=list)
    calls: List[CallFact] = dataclasses.field(default_factory=list)
    eager_jnp: List[dict] = dataclasses.field(default_factory=list)
    shape_sites: List[dict] = dataclasses.field(default_factory=list)
    loops: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    window_loops: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    loads: Dict[str, List[int]] = dataclasses.field(default_factory=dict)
    stores: Dict[str, List[int]] = dataclasses.field(default_factory=dict)
    #: literal donate_argnums from a @jit/@partial(jax.jit, …) decorator
    donate_decorator: Optional[List[int]] = None
    #: lock-scope regions: {"lock": raw token, "lineno", "end_lineno"}
    #: from ``with <lock>:`` blocks and acquire()/release() pairs
    lock_spans: List[dict] = dataclasses.field(default_factory=list)
    #: names this function declares ``global``
    global_decls: List[str] = dataclasses.field(default_factory=list)
    #: env-var access sites: {"var", "how": get|getitem|getenv|pop|set
    #: |contains, "lineno", "end_lineno"}
    env_reads: List[dict] = dataclasses.field(default_factory=list)
    #: instant-event emit sites: {"name": literal name or f-string head
    #: or None (dynamic), "prefix": bool, "via", "lineno", "end_lineno"}
    emit_sites: List[dict] = dataclasses.field(default_factory=list)
    #: checkpoint payload writes (v4, kept only for publisher/restorer-
    #: shaped functions): {"key", "lineno", "conditional": bool,
    #: "recv": dotted receiver of a subscript store or None (dict
    #: literal / save_checkpoint kwarg)}
    ckpt_writes: List[dict] = dataclasses.field(default_factory=list)
    #: checkpoint payload reads (v4): {"key", "how": getitem|get|
    #: get_default|contains, "lineno", "conditional": bool, "recv"}
    ckpt_reads: List[dict] = dataclasses.field(default_factory=list)
    #: the payload is built/consumed dynamically (``.update(…)``,
    #: ``**unpack``, ``save_checkpoint(p, **comps)``) — key-set checks
    #: that need the FULL set must not run against this side
    ckpt_dynamic: bool = False
    #: nondeterminism sites (v4): {"kind": wall-clock|unseeded-random|
    #: set-iteration|fs-order|id-order, "desc", "lineno", "end_lineno"}
    nondet_sites: List[dict] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class FileFacts:
    relpath: str
    module: str                           # dotted module name within project
    imports: Dict[str, dict] = dataclasses.field(default_factory=dict)
    functions: Dict[str, FunctionFacts] = dataclasses.field(default_factory=dict)
    classes: Dict[str, dict] = dataclasses.field(default_factory=dict)
    names_used: List[str] = dataclasses.field(default_factory=list)
    pragmas: List[dict] = dataclasses.field(default_factory=list)
    #: module-level literal constants: name → {"lineno", "end_lineno",
    #: "const"} where const is a str/int/float, a list of strings, or
    #: {"__kind__": "dict", "keys": [...], "map": {k: const|None}}
    constants: Dict[str, dict] = dataclasses.field(default_factory=dict)
    #: the module-level ``if __name__ == "__main__":`` guard, if any:
    #: {"lineno", "end_lineno", "delegates_to_self"}
    main_guard: Optional[dict] = None
    #: module-level ``name = SameModuleClass()`` singletons:
    #: [{"name", "cls", "lineno"}]
    module_instances: List[dict] = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def facts_from_dict(d: dict) -> FileFacts:
    f = FileFacts(d["relpath"], d["module"], d.get("imports", {}),
                  {}, d.get("classes", {}), d.get("names_used", []),
                  d.get("pragmas", []), d.get("constants", {}),
                  d.get("main_guard"), d.get("module_instances", []))
    for q, fd in d.get("functions", {}).items():
        # .get, never .pop: the dict may be a live cache entry that will
        # be re-serialized — mutating it here gutted the on-disk cache.
        calls = [CallFact(**c) for c in fd.get("calls", [])]
        fn = FunctionFacts(**{k: v for k, v in fd.items() if k != "calls"})
        fn.calls = calls
        fn.loops = [tuple(s) for s in fn.loops]
        fn.window_loops = [tuple(s) for s in fn.window_loops]
        f.functions[q] = fn
    return f


def module_name_of(relpath: str) -> str:
    """Dotted module name for a project-relative path ("a/b/c.py" →
    "a.b.c"; "__init__.py" collapses to the package)."""
    mod = relpath[:-3] if relpath.endswith(".py") else relpath
    parts = [p for p in mod.split("/") if p]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


# Pragma scanning lives in core (the file passes' suppression shares
# the same tokenize inventory); re-exported here for the facts builder.
from tools.sfcheck.core import PRAGMA_AT_START, scan_pragmas  # noqa: F401,E402


def _literal_int_tuple(node: ast.AST) -> Optional[List[int]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int) \
                    and not isinstance(elt.value, bool):
                out.append(elt.value)
            else:
                return None
        return out
    return None


class _Extractor(ast.NodeVisitor):
    """One source-ordered walk collecting every FileFacts field."""

    #: propagate taint through these plain builtins
    _TAINT_PROPAGATORS = frozenset({"int", "max", "min", "abs", "sum"})

    def __init__(self, facts: FileFacts, bindings: Bindings):
        self.facts = facts
        self.b = bindings
        self.fn_stack: List[FunctionFacts] = []
        self.cls_stack: List[str] = []
        self.loop_stack: List[Tuple[int, int, bool]] = []  # (start, end, window)
        self.taint_stack: List[set] = []
        self.names_used: set = set()
        #: depth of enclosing If/IfExp/except-handler within the current
        #: function — a checkpoint write/read at depth > 0 is CONDITIONAL
        #: (the legacy-default schema analysis keys on this)
        self._cond = 0
        #: per-function: loop var bound to a literal string tuple/list
        #: (``for key in ("a", "b"): st[key]`` reads both keys)
        self._str_loopvars: Dict[str, List[str]] = {}
        #: per-function set-taint: local name -> why it holds a set
        self._set_taint_stack: List[dict] = []
        #: ast node ids sanctioned by an enclosing ``sorted(…)`` — an
        #: fs-order/set source fed straight into sorted is deterministic
        self._sorted_args: set = set()
        module_fn = FunctionFacts(MODULE_FN, MODULE_FN, 1, 10 ** 9)
        facts.functions[MODULE_FN] = module_fn
        self.fn_stack.append(module_fn)

    # -- helpers -------------------------------------------------------------

    @property
    def fn(self) -> FunctionFacts:
        return self.fn_stack[-1]

    def _qual(self, name: str) -> str:
        parts = []
        if len(self.fn_stack) > 1:
            parts.append(self.fn_stack[-1].qualname)
        elif self.cls_stack:
            parts.append(".".join(self.cls_stack))
        parts.append(name)
        return ".".join(parts)

    def _in_window_loop(self) -> bool:
        return any(w for _, _, w in self.loop_stack)

    def _taint_top(self) -> dict:
        return self.taint_stack[-1] if self.taint_stack else {}

    # -- taint evaluation ----------------------------------------------------

    def _taints(self, node: ast.AST) -> Optional[str]:
        """A short description of why ``node`` is a data-dependent Python
        int, or None if it is not (conservatively)."""
        if isinstance(node, ast.Name):
            return self._taint_top().get(node.id)
        if isinstance(node, ast.Call):
            d = dotted(node.func)
            term = (d or "").split(".")[-1]
            if term in SHAPE_SANITIZERS:
                return None
            if d == "len" and node.args and not isinstance(
                    node.args[0], ast.Constant):
                return f"`{ast.unparse(node)}`"
            if term in self._TAINT_PROPAGATORS:
                for a in node.args:
                    why = self._taints(a)
                    if why:
                        return why
            return None
        if isinstance(node, ast.Subscript):
            # x.shape[0] — a runtime array dimension.
            v = node.value
            if isinstance(v, ast.Attribute) and v.attr == "shape":
                return f"`{ast.unparse(node)}`"
            return None
        if isinstance(node, ast.BinOp):
            return self._taints(node.left) or self._taints(node.right)
        if isinstance(node, (ast.Tuple, ast.List)):
            for elt in node.elts:
                why = self._taints(elt)
                if why:
                    return why
            return None
        if isinstance(node, ast.Starred):
            return self._taints(node.value)
        return None

    def _record_store_taint(self, target: ast.AST, value: ast.AST):
        if not self.taint_stack:
            return
        tset = self.taint_stack[-1]
        if isinstance(target, ast.Name):
            why = self._taints(value)
            if why:
                tset[target.id] = why
            else:
                tset.pop(target.id, None)
        elif isinstance(target, (ast.Tuple, ast.List)) and isinstance(
                value, (ast.Tuple, ast.List)) \
                and len(target.elts) == len(value.elts):
            for t, v in zip(target.elts, value.elts):
                self._record_store_taint(t, v)

    # -- scope plumbing ------------------------------------------------------

    def _visit_function(self, node):
        for dec in node.decorator_list:
            self.visit(dec)
        for d in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]:
            self.visit(d)
        qual = self._qual(node.name)
        fn = FunctionFacts(
            name=node.name, qualname=qual, lineno=node.lineno,
            end_lineno=node.end_lineno or node.lineno,
            cls=self.cls_stack[-1] if self.cls_stack and len(
                self.fn_stack) == 1 else None,
            nested_in=self.fn.qualname if len(self.fn_stack) > 1 else None,
            params=[a.arg for a in node.args.posonlyargs + node.args.args
                    + node.args.kwonlyargs]
            + ([node.args.vararg.arg] if node.args.vararg else [])
            + ([node.args.kwarg.arg] if node.args.kwarg else []),
            decorators=[d for d in (
                dotted(dec.func) if isinstance(dec, ast.Call) else dotted(dec)
                for dec in node.decorator_list) if d],
        )
        # partial(jax.jit, ...) decorators: keep the wrapped target too,
        # and literal donate_argnums make the def a donating callable.
        for dec in node.decorator_list:
            if isinstance(dec, ast.Call):
                if dec.args:
                    inner = dotted(dec.args[0])
                    if inner:
                        fn.decorators.append(inner)
                for kw in dec.keywords:
                    if kw.arg == "donate_argnums":
                        fn.donate_decorator = _literal_int_tuple(kw.value)
        self.facts.functions[qual] = fn
        self.fn_stack.append(fn)
        self.taint_stack.append({})
        self._set_taint_stack.append({})
        saved_loops = self.loop_stack
        saved_cond = self._cond
        saved_slv = self._str_loopvars
        self.loop_stack = []
        self._cond = 0          # a nested def runs unconditionally
        self._str_loopvars = {}  # relative to its own entry
        for stmt in node.body:
            self.visit(stmt)
        self.loop_stack = saved_loops
        self._cond = saved_cond
        self._str_loopvars = saved_slv
        self._set_taint_stack.pop()
        self.taint_stack.pop()
        self.fn_stack.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    def visit_Lambda(self, node):
        # Lambdas stay anonymous: record their body's calls against the
        # enclosing function (they execute in its dynamic extent).
        self.generic_visit(node)

    def visit_ClassDef(self, node):
        bases = [d for d in (dotted(b) for b in node.bases) if d]
        self.cls_stack.append(node.name)
        self.facts.classes[node.name] = {
            "bases": bases, "methods": {}, "fields": [],
            "lineno": node.lineno,
        }
        for stmt in node.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.facts.classes[node.name]["methods"][stmt.name] = \
                    self._qual(stmt.name)
            elif isinstance(stmt, ast.AnnAssign) \
                    and isinstance(stmt.target, ast.Name):
                # Annotated class attributes — dataclass fields (the
                # SloSpec ↔ SPEC_KEYS twin surface).
                self.facts.classes[node.name]["fields"].append(
                    stmt.target.id)
            self.visit(stmt)
        self.cls_stack.pop()

    def visit_Global(self, node):
        for name in node.names:
            if name not in self.fn.global_decls:
                self.fn.global_decls.append(name)

    # -- conditional context (checkpoint-schema's legacy-default rule) -------

    def visit_If(self, node):
        self.visit(node.test)
        self._cond += 1
        for stmt in node.body:
            self.visit(stmt)
        for stmt in node.orelse:
            self.visit(stmt)
        self._cond -= 1

    def visit_IfExp(self, node):
        self.visit(node.test)
        self._cond += 1
        self.visit(node.body)
        self.visit(node.orelse)
        self._cond -= 1

    def visit_Try(self, node):
        # The try body is the MAIN path (a publish inside ``try`` is
        # attempted unconditionally); only handlers/orelse branch.
        for stmt in node.body:
            self.visit(stmt)
        self._cond += 1
        for handler in node.handlers:
            self.visit(handler)
        for stmt in node.orelse:
            self.visit(stmt)
        self._cond -= 1
        for stmt in node.finalbody:
            self.visit(stmt)

    # -- lock scopes ---------------------------------------------------------

    def _visit_with(self, node):
        for rank, item in enumerate(node.items):
            self.visit(item.context_expr)
            if item.optional_vars is not None:
                self.visit(item.optional_vars)
            d = dotted(item.context_expr)
            if d and _is_lockish(d):
                # rank: the item's position in a multi-item
                # ``with a, b:`` — items acquire left-to-right, so rank
                # order IS acquisition order for same-statement spans
                # (they share a lineno, which hides them from the
                # nested-span test alone).
                self.fn.lock_spans.append({
                    "lock": d, "lineno": node.lineno,
                    "end_lineno": node.end_lineno or node.lineno,
                    "rank": rank,
                })
        for stmt in node.body:
            self.visit(stmt)

    visit_With = _visit_with
    visit_AsyncWith = _visit_with

    # -- imports -------------------------------------------------------------

    def visit_Import(self, node):
        for alias in node.names:
            bound = alias.asname or alias.name.split(".")[0]
            target = alias.name if alias.asname else alias.name.split(".")[0]
            self.facts.imports[bound] = {"kind": "module", "target": target,
                                         "attr": None}
            self.names_used.add(bound)

    def visit_ImportFrom(self, node):
        # Import-only references still count as "referenced by name" —
        # a parity test importing a kernel names it.
        for alias in node.names:
            self.names_used.add(alias.asname or alias.name)
            self.names_used.add(alias.name)
        if node.module is None or node.level:
            return  # relative imports: out of heuristic resolution scope
        for alias in node.names:
            bound = alias.asname or alias.name
            self.facts.imports[bound] = {
                "kind": "object", "target": node.module, "attr": alias.name,
            }

    # -- loops ---------------------------------------------------------------

    def _iter_is_window(self, node: ast.For) -> bool:
        it = node.iter
        if isinstance(it, ast.Call):
            d = dotted(it.func)
            if d and d.split(".")[-1] in WINDOW_ITER_CALLEES:
                return True
        targets = []
        t = node.target
        for n in ast.walk(t):
            if isinstance(n, ast.Name):
                targets.append(n.id)
        return any(t in WINDOW_TARGET_NAMES for t in targets)

    def visit_For(self, node):
        window = self._iter_is_window(node)
        span = (node.lineno, node.end_lineno or node.lineno)
        self.fn.loops.append(span)
        if window:
            self.fn.window_loops.append(span)
        # ``for key in ("a", "b"):`` binds a literal-string loop var —
        # later ``rec[key]`` subscripts read every listed key (the
        # restore_dag counter-loop idiom).
        if isinstance(node.target, ast.Name) \
                and isinstance(node.iter, (ast.Tuple, ast.List)) \
                and node.iter.elts and all(
                    isinstance(e, ast.Constant) and isinstance(e.value, str)
                    for e in node.iter.elts):
            self._str_loopvars[node.target.id] = [
                e.value for e in node.iter.elts]
        self._check_iter_nondet(node.iter)
        self.visit(node.iter)
        # Loop indices over runtime collections are data-dependent ints.
        if self.taint_stack and isinstance(node.iter, ast.Call):
            d = dotted(node.iter.func)
            if d in ("range", "enumerate"):
                why = any(self._taints(a) for a in node.iter.args)
                if d == "enumerate" or why:
                    for n in ast.walk(node.target):
                        if isinstance(n, ast.Name):
                            self.taint_stack[-1][n.id] = (
                                f"loop index `{n.id}`")
                            break  # first target only (the index)
        self.visit(node.target)
        self.loop_stack.append((span[0], span[1], window))
        for stmt in node.body:
            self.visit(stmt)
        self.loop_stack.pop()
        for stmt in node.orelse:
            self.visit(stmt)

    # -- nondeterminism sites (replay-determinism) ---------------------------

    def _nondet(self, kind: str, desc: str, node: ast.AST):
        self.fn.nondet_sites.append({
            "kind": kind, "desc": desc, "lineno": node.lineno,
            "end_lineno": getattr(node, "end_lineno", None) or node.lineno,
        })

    def _check_iter_nondet(self, it: ast.AST):
        """Iterating a set is order-nondeterministic (hash-seed order);
        ``sorted(…)`` wrappers are deterministic by construction."""
        if id(it) in self._sorted_args:
            return
        why = self._set_valued(it)
        if why is None and isinstance(it, ast.Name):
            reason = self._set_taint().get(it.id)
            if reason:
                why = f"`{it.id}` holds {reason}"
        if why:
            self._nondet("set-iteration",
                         f"iteration over {why} — element order follows "
                         f"the hash seed, not the data", it)

    def _visit_comp(self, node):
        # SetComp output is itself unordered — re-collecting a set from
        # a set adds no ordering dependency, so only list/dict/generator
        # comprehensions check their sources.
        if not isinstance(node, ast.SetComp):
            for gen in node.generators:
                self._check_iter_nondet(gen.iter)
        self.generic_visit(node)

    visit_ListComp = _visit_comp
    visit_SetComp = _visit_comp
    visit_GeneratorExp = _visit_comp
    visit_DictComp = _visit_comp

    def visit_While(self, node):
        span = (node.lineno, node.end_lineno or node.lineno)
        self.fn.loops.append(span)
        self.visit(node.test)
        self.loop_stack.append((span[0], span[1], False))
        for stmt in node.body:
            self.visit(stmt)
        self.loop_stack.pop()
        for stmt in node.orelse:
            self.visit(stmt)

    # -- assignments (taint) -------------------------------------------------

    def visit_Assign(self, node):
        self.visit(node.value)
        for t in node.targets:
            self._record_store_taint(t, node.value)
            self._record_set_taint(t, node.value)
            self.visit(t)

    def _set_taint(self) -> dict:
        return self._set_taint_stack[-1] if self._set_taint_stack else {}

    def _set_valued(self, value: ast.AST) -> Optional[str]:
        """Why ``value`` is a set (order-unstable container), or None."""
        if isinstance(value, (ast.Set, ast.SetComp)):
            return "a set literal"
        if isinstance(value, ast.Call):
            d = dotted(value.func)
            if d in ("set", "frozenset"):
                return f"a `{d}(…)` result"
        return None

    def _record_set_taint(self, target: ast.AST, value: ast.AST):
        if not self._set_taint_stack or not isinstance(target, ast.Name):
            return
        why = self._set_valued(value)
        if why:
            self._set_taint_stack[-1][target.id] = why
        else:
            self._set_taint_stack[-1].pop(target.id, None)

    def visit_AugAssign(self, node):
        self.visit(node.value)
        if self.taint_stack and isinstance(node.target, ast.Name):
            why = self._taints(node.value)
            if why:
                self.taint_stack[-1][node.target.id] = why
        self.visit(node.target)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self.visit(node.value)
            self._record_store_taint(node.target, node.value)
            self._record_set_taint(node.target, node.value)
        self.visit(node.target)

    # -- checkpoint payload facts (checkpoint-schema) ------------------------

    def visit_Dict(self, node):
        # String-literal dict keys are checkpoint payload writes when
        # the enclosing function is publisher-shaped (pruned otherwise).
        for k in node.keys:
            if k is None:
                # ``{**base, …}`` unpacking: the key set is dynamic
                self.fn.ckpt_dynamic = True
            elif isinstance(k, ast.Constant) and isinstance(k.value, str):
                self.fn.ckpt_writes.append({
                    "key": k.value, "lineno": k.lineno,
                    "conditional": self._cond > 0, "recv": None,
                })
        self.generic_visit(node)

    def _check_ckpt_subscript(self, node: ast.Subscript, d: Optional[str]):
        if d and (d == "environ" or d.endswith(".environ")):
            return  # env access, not checkpoint payload
        keys = None
        if isinstance(node.slice, ast.Constant) \
                and isinstance(node.slice.value, str):
            keys = [node.slice.value]
        elif isinstance(node.slice, ast.Name):
            keys = self._str_loopvars.get(node.slice.id)
        if not keys:
            return
        if isinstance(node.ctx, ast.Store):
            for k in keys:
                self.fn.ckpt_writes.append({
                    "key": k, "lineno": node.lineno,
                    "conditional": self._cond > 0, "recv": d,
                })
        else:
            for k in keys:
                self.fn.ckpt_reads.append({
                    "key": k, "how": "getitem", "lineno": node.lineno,
                    "conditional": self._cond > 0, "recv": d,
                })

    # -- names ---------------------------------------------------------------

    def visit_Name(self, node):
        self.names_used.add(node.id)
        book = self.fn.loads if isinstance(node.ctx, ast.Load) else \
            self.fn.stores
        book.setdefault(node.id, []).append(node.lineno)

    def visit_Attribute(self, node):
        self.names_used.add(node.attr)
        self.generic_visit(node)

    # -- calls ---------------------------------------------------------------

    def _arg_name(self, node: ast.AST) -> Optional[str]:
        return dotted(node)

    def visit_Call(self, node):
        d = dotted(node.func)
        if d is None and isinstance(node.func, ast.Attribute):
            d = "." + node.func.attr      # method on a non-name expression
        if d is None and isinstance(node.func, ast.Call):
            # jax.jit(f, donate_argnums=…)(x): record the OUTER call as a
            # donating call on the inner jit's wrapped function.
            inner = node.func
            idott = dotted(inner.func)
            donate = None
            for kw in inner.keywords:
                if kw.arg == "donate_argnums":
                    donate = _literal_int_tuple(kw.value)
            if idott and donate is not None:
                self.fn.calls.append(CallFact(
                    target=idott + "()", lineno=node.lineno,
                    end_lineno=node.end_lineno or node.lineno,
                    args=[self._arg_name(a) for a in node.args],
                    kw_args={kw.arg: self._arg_name(kw.value)
                             for kw in node.keywords if kw.arg},
                    donate=donate, in_window_loop=self._in_window_loop(),
                ))
        if d is not None:
            donate = None
            for kw in node.keywords:
                if kw.arg == "donate_argnums":
                    donate = _literal_int_tuple(kw.value)
            self.fn.calls.append(CallFact(
                target=d, lineno=node.lineno,
                end_lineno=node.end_lineno or node.lineno,
                args=[self._arg_name(a) for a in node.args],
                kw_args={kw.arg: self._arg_name(kw.value)
                         for kw in node.keywords if kw.arg},
                donate=donate, in_window_loop=self._in_window_loop(),
            ))
        self._check_eager_jnp(node)
        self._check_shape_sink(node, d)
        self._check_env_access(node, d)
        self._check_emit_site(node, d)
        self._check_ckpt_call(node, d)
        self._check_nondet(node, d)
        if d is not None and d.split(".")[-1] == "sorted":
            # arguments fed straight into sorted() are order-laundered
            for a in node.args:
                self._sorted_args.add(id(a))
        self.generic_visit(node)

    def _check_ckpt_call(self, node: ast.Call, d: Optional[str]):
        """Checkpoint payload facts carried by calls: ``X.get("k"[, dflt])``
        defaulted reads, ``X.update(…)`` dynamic builds, and
        ``save_checkpoint(path, comp=…)`` kwarg publishes."""
        if d is None:
            return
        parts = d.split(".")
        term = parts[-1]
        if term in ("get", "pop") and len(parts) >= 2 and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            recv = ".".join(parts[:-1])
            if recv == "os" or recv == "environ" \
                    or recv.endswith(".environ"):
                return  # env access is its own fact kind
            how = "get" if len(node.args) == 1 else "get_default"
            if term == "pop" and len(node.args) == 1:
                how = "getitem"  # .pop(k) raises like a bare subscript
            self.fn.ckpt_reads.append({
                "key": node.args[0].value, "how": how,
                "lineno": node.lineno, "conditional": self._cond > 0,
                "recv": recv,
            })
        elif term == "update" and len(parts) >= 2:
            self.fn.ckpt_dynamic = True
        elif term in CKPT_SAVE_TERMINALS:
            for kw in node.keywords:
                if kw.arg:
                    self.fn.ckpt_writes.append({
                        "key": kw.arg, "lineno": node.lineno,
                        "conditional": self._cond > 0, "recv": None,
                    })
                else:
                    self.fn.ckpt_dynamic = True

    def _check_nondet(self, node: ast.Call, d: Optional[str]):
        if self.b.wall_clock_call(node.func) is not None:
            self._nondet("wall-clock",
                         f"wall-clock read `{d or '…'}(…)`", node)
            return
        if d is None:
            return
        parts = d.split(".")
        term = parts[-1]
        if term in NONDET_DATETIME_FNS and any(
                p in ("datetime", "date") for p in parts[:-1]):
            self._nondet("wall-clock", f"wall-clock read `{d}(…)`", node)
            return
        imp = self.facts.imports.get(parts[0])
        # module-level random draws: ``random.shuffle`` / bare
        # ``shuffle`` via ``from random import shuffle``
        if len(parts) == 2 and term in NONDET_RANDOM_FNS \
                and imp is not None and imp["kind"] == "module" \
                and imp["target"] == "random":
            self._nondet("unseeded-random",
                         f"global unseeded RNG draw `{d}(…)`", node)
            return
        if len(parts) == 1 and imp is not None \
                and imp["kind"] == "object" and imp["target"] == "random" \
                and imp["attr"] in NONDET_RANDOM_FNS:
            self._nondet("unseeded-random",
                         f"global unseeded RNG draw `random.{imp['attr']}(…)`",
                         node)
            return
        # numpy global draws: np.random.shuffle etc.
        if term in NONDET_NP_RANDOM_FNS and len(parts) >= 2 \
                and parts[-2] == "random":
            root_is_np = parts[0] in self.b.np_modules \
                or parts[0] == "numpy" \
                or (imp is not None and imp["kind"] == "module"
                    and imp["target"] == "numpy")
            if (len(parts) == 2 and imp is not None
                    and imp["kind"] == "object"
                    and imp["target"] == "numpy") or \
                    (len(parts) == 3 and root_is_np):
                self._nondet("unseeded-random",
                             f"global unseeded RNG draw `{d}(…)`", node)
                return
        # unseeded RNG constructors: default_rng() / Random() with no seed
        if term in NONDET_RNG_CTORS and not node.args and not node.keywords:
            rng_root = (len(parts) >= 2 and (
                parts[-2] == "random"
                or (imp is not None and imp["kind"] == "module"
                    and imp["target"] in ("random", "numpy")))) \
                or (len(parts) == 1 and imp is not None
                    and imp["kind"] == "object"
                    and imp["target"] in ("random", "numpy.random"))
            if rng_root:
                self._nondet("unseeded-random",
                             f"unseeded RNG constructor `{d}()` — pass an "
                             f"explicit seed", node)
                return
        # filesystem enumeration order
        if term in NONDET_FS_FNS and id(node) not in self._sorted_args:
            fs_root = (len(parts) == 2 and (
                parts[0] in ("os", "glob")
                or (imp is not None and imp["kind"] == "module"
                    and imp["target"] in ("os", "glob")))) \
                or (len(parts) == 1 and imp is not None
                    and imp["kind"] == "object"
                    and imp["target"] in ("os", "glob")) \
                or term in ("iterdir", "rglob")
            if fs_root:
                self._nondet("fs-order",
                             f"unsorted filesystem enumeration `{d}(…)` — "
                             f"wrap in sorted(…)", node)
                return
        # id()-keyed ordering: sorted(xs, key=id)
        if term in ("sorted", "sort", "min", "max"):
            for kw in node.keywords:
                if kw.arg != "key":
                    continue
                kd = dotted(kw.value)
                uses_id = kd == "id" or (
                    isinstance(kw.value, ast.Lambda) and any(
                        isinstance(n, ast.Call) and dotted(n.func) == "id"
                        for n in ast.walk(kw.value)))
                if uses_id:
                    self._nondet("id-order",
                                 f"`{d}(…, key=id)` orders by object "
                                 f"address (ASLR-reshuffled per process)",
                                 node)

    def _check_env_access(self, node: ast.Call, d: Optional[str]):
        """os.environ.get / os.getenv / environ.setdefault reads and
        ``.pop`` scrubs with a literal SHOUTY name."""
        if d is None or not node.args:
            return
        term = d.split(".")[-1]
        how = None
        if d.endswith("environ.get") or d == "environ.get":
            how = "get"
        elif term == "getenv" and (d == "getenv" or d.endswith(".getenv")):
            how = "getenv"
        elif d.endswith("environ.setdefault"):
            how = "get"
        elif d.endswith("environ.pop"):
            how = "pop"
        elif term == "pop":
            how = "pop"
        if how is None:
            return
        arg = node.args[0]
        if not isinstance(arg, ast.Constant):
            return
        var = _env_name(arg.value)
        if var is None:
            return
        self.fn.env_reads.append({
            "var": var, "how": how, "lineno": node.lineno,
            "end_lineno": node.end_lineno or node.lineno,
        })

    def _check_emit_site(self, node: ast.Call, d: Optional[str]):
        if d is None or not node.args:
            return
        term = d.split(".")[-1]
        if term not in EMIT_NAME_TERMINALS:
            return
        arg = node.args[0]
        name = None
        prefix = False
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
        elif isinstance(arg, ast.JoinedStr):
            prefix = True
            if arg.values and isinstance(arg.values[0], ast.Constant) \
                    and isinstance(arg.values[0].value, str) \
                    and arg.values[0].value:
                name = arg.values[0].value
            # else: dynamic head — name stays None, the contract-twin
            # pass reports it as statically uncheckable
        else:
            return  # a plain variable: a forwarding wrapper, not an emit
        self.fn.emit_sites.append({
            "name": name, "prefix": prefix, "via": term,
            "lineno": node.lineno,
            "end_lineno": node.end_lineno or node.lineno,
        })

    def visit_Compare(self, node):
        # ``"SFT_X" in os.environ`` membership tests are read sites too
        # — a var read only this way must not count as registry drift.
        if len(node.ops) == 1 \
                and isinstance(node.ops[0], (ast.In, ast.NotIn)) \
                and isinstance(node.left, ast.Constant):
            d = dotted(node.comparators[0])
            if d and (d == "environ" or d.endswith(".environ")):
                var = _env_name(node.left.value)
                if var is not None:
                    self.fn.env_reads.append({
                        "var": var, "how": "contains",
                        "lineno": node.lineno,
                        "end_lineno": node.end_lineno or node.lineno,
                    })
            elif isinstance(node.left.value, str):
                # ``"key" in state`` — the legacy-default guard idiom
                self.fn.ckpt_reads.append({
                    "key": node.left.value, "how": "contains",
                    "lineno": node.lineno,
                    "conditional": self._cond > 0, "recv": d,
                })
        self.generic_visit(node)

    def visit_Subscript(self, node):
        # os.environ["X"] reads / os.environ["X"] = ... writes
        d = dotted(node.value)
        if d and (d == "environ" or d.endswith(".environ")) \
                and isinstance(node.slice, ast.Constant):
            var = _env_name(node.slice.value)
            if var is not None:
                self.fn.env_reads.append({
                    "var": var,
                    "how": "set" if isinstance(node.ctx, ast.Store)
                    else "getitem",
                    "lineno": node.lineno,
                    "end_lineno": node.end_lineno or node.lineno,
                })
        self._check_ckpt_subscript(node, d)
        # ``table[id(obj)] = …`` — id()-keyed maps iterate in address
        # order, which ASLR reshuffles every process
        if isinstance(node.slice, ast.Call) \
                and dotted(node.slice.func) == "id":
            self._nondet("id-order",
                         "`id(…)`-keyed container — key order follows "
                         "object addresses (ASLR-reshuffled per process)",
                         node)
        self.generic_visit(node)

    def _check_eager_jnp(self, node: ast.Call):
        attr = self.b.jnp_call(node.func)
        if attr is None:
            return
        term = attr.split(".")[-1]
        if term in JNP_SHIP_ATTRS or term in JNP_META_ATTRS:
            return
        self.fn.eager_jnp.append({
            "attr": attr, "lineno": node.lineno,
            "end_lineno": node.end_lineno or node.lineno,
            "expr": ast.unparse(node.func),
            "in_window_loop": self._in_window_loop(),
        })

    def _check_shape_sink(self, node: ast.Call, d: Optional[str]):
        """Device-shape sinks fed by a data-dependent Python int."""
        jattr = self.b.jnp_call(node.func)
        why = None
        desc = None
        if jattr in JNP_SHAPE_SINKS and node.args:
            why = self._taints(node.args[0])
            desc = f"`{ast.unparse(node.func)}(…)` dimension"
        elif d and d.split(".")[-1] == "pad_to_bucket" and len(node.args) >= 2:
            # The one shape that ALWAYS reaches the device. A host-side
            # numpy stage (np.zeros(n)/.reshape(n, …) later padded) is
            # deliberately not a sink — only device shapes recompile.
            why = self._taints(node.args[1])
            desc = "`pad_to_bucket(…, bucket)` bucket"
        if why:
            self.fn.shape_sites.append({
                "lineno": node.lineno,
                "end_lineno": node.end_lineno or node.lineno,
                "desc": desc, "src": why,
                "in_window_loop": self._in_window_loop(),
            })


def _literal_const(node: ast.AST, depth: int = 0):
    """JSON-able mirror of a module-level literal constant: scalars,
    string sequences (incl. ``frozenset({...})``/``tuple((...))``
    wrappers), and string-keyed dicts (values captured recursively,
    ``None`` where unresolvable — the chaos MATRIX's lambdas). Returns
    ``None`` for anything else."""
    if depth > 3:
        return None
    if isinstance(node, ast.Constant) and isinstance(
            node.value, (str, int, float)) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        vals = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                vals.append(elt.value)
            else:
                return None
        return vals
    if isinstance(node, ast.Call) and not node.keywords \
            and len(node.args) == 1:
        d = dotted(node.func)
        if d in ("frozenset", "set", "tuple", "list"):
            return _literal_const(node.args[0], depth + 1)
    if isinstance(node, ast.Dict):
        keys = []
        mapping = {}
        for k, v in zip(node.keys, node.values):
            if not (isinstance(k, ast.Constant)
                    and isinstance(k.value, str)):
                return None
            keys.append(k.value)
            mapping[k.value] = _literal_const(v, depth + 1)
        return {"__kind__": "dict", "keys": keys, "map": mapping}
    return None


def _main_guard_of(tree: ast.AST, module: str) -> Optional[dict]:
    """The module-level ``if __name__ == "__main__":`` block, with
    whether its body delegates to the canonical import of this very
    module (the dual-module-singleton escape hatch)."""
    for node in tree.body:
        if not isinstance(node, ast.If):
            continue
        test = node.test
        if not (isinstance(test, ast.Compare) and len(test.ops) == 1
                and isinstance(test.ops[0], ast.Eq)):
            continue
        sides = [test.left] + list(test.comparators)
        names = [s.id for s in sides if isinstance(s, ast.Name)]
        consts = [s.value for s in sides if isinstance(s, ast.Constant)]
        if "__name__" not in names or "__main__" not in consts:
            continue
        delegates = any(
            isinstance(n, ast.ImportFrom) and n.level == 0
            and n.module == module
            for stmt in node.body for n in ast.walk(stmt)
        )
        return {"lineno": node.lineno,
                "end_lineno": node.end_lineno or node.lineno,
                "delegates_to_self": delegates}
    return None


def _module_scan(facts: FileFacts, tree: ast.AST):
    """Module-level constants + same-module singleton instantiations."""
    for node in tree.body:
        target = None
        value = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            target, value = node.targets[0].id, node.value
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name) \
                and node.value is not None:
            target, value = node.target.id, node.value
        if target is None:
            continue
        const = _literal_const(value)
        if const is not None:
            facts.constants[target] = {
                "lineno": node.lineno,
                "end_lineno": node.end_lineno or node.lineno,
                "const": const,
            }
        elif isinstance(value, ast.Call):
            d = dotted(value.func)
            if d is not None and d.split(".")[-1] in facts.classes:
                facts.module_instances.append({
                    "name": target, "cls": d.split(".")[-1],
                    "lineno": node.lineno,
                })
    facts.main_guard = _main_guard_of(tree, facts.module)


def _pair_lock_acquires(fn: FunctionFacts):
    """``lock.acquire()`` … ``lock.release()`` pairs become lock spans
    (unreleased acquires extend to the function end — conservative)."""
    acquires = []
    releases = {}
    for call in fn.calls:
        parts = call.target.split(".")
        if len(parts) < 2:
            continue
        receiver = ".".join(parts[:-1])
        if not _is_lockish(receiver):
            continue
        if parts[-1] == "acquire":
            acquires.append((receiver, call.lineno))
        elif parts[-1] == "release":
            releases.setdefault(receiver, []).append(call.lineno)
    for receiver, start in acquires:
        ends = [ln for ln in releases.get(receiver, []) if ln >= start]
        fn.lock_spans.append({
            "lock": receiver, "lineno": start,
            "end_lineno": min(ends) if ends else fn.end_lineno,
        })


def is_test_relpath(relpath: str) -> bool:
    parts = relpath.split("/")
    return parts[0] == "tests" or parts[-1].startswith("test_")


def is_ckpt_publisher_name(name: str) -> bool:
    """The repo's checkpoint-publish naming convention: ``state`` /
    ``substate`` methods and ``<stem>_state`` functions."""
    return name in ("state", "substate") or (
        name.endswith("_state") and not name.startswith("restore"))


def is_ckpt_restorer_name(name: str) -> bool:
    return name == "restore" or name.startswith("restore_")


def _calls_ckpt_io(fn: FunctionFacts) -> bool:
    for call in fn.calls:
        term = call.target.split(".")[-1]
        if term in CKPT_SAVE_TERMINALS or term in CKPT_LOAD_TERMINALS:
            return True
    return False


def _prune_ckpt(fn: FunctionFacts):
    """Checkpoint payload facts only matter for publisher/restorer-shaped
    functions — dict literals and ``.get("k")`` calls are everywhere
    else, and keeping them would bloat every cache entry."""
    if is_ckpt_publisher_name(fn.name) or is_ckpt_restorer_name(fn.name) \
            or _calls_ckpt_io(fn):
        return
    fn.ckpt_writes = []
    fn.ckpt_reads = []
    fn.ckpt_dynamic = False


def _prune_books(fn: FunctionFacts):
    """Keep load/store lines only for names the donation-safety pass can
    ever ask about — positional call arguments and names stored on a
    donating-call line — so cache entries stay small."""
    keep = set()
    for call in fn.calls:
        for a in call.args:
            if a and "." not in a:
                keep.add(a)
        if call.donate is not None:
            for name, lines in fn.stores.items():
                if any(call.lineno <= ln <= call.end_lineno
                       for ln in lines):
                    keep.add(name)
    fn.loads = {k: v for k, v in fn.loads.items() if k in keep}
    fn.stores = {k: v for k, v in fn.stores.items() if k in keep}


def extract_facts(relpath: str, tree: ast.AST, source: str,
                  bindings: Optional[Bindings] = None) -> FileFacts:
    """Extract the whole-program fact summary of one parsed file."""
    facts = FileFacts(relpath=relpath, module=module_name_of(relpath))
    b = bindings if bindings is not None else Bindings.scan(tree)
    ex = _Extractor(facts, b)
    ex.visit(tree)
    # names_used feeds exactly one question — "does any test reference
    # this kernel's name" (mesh-parity) — so only test files carry it.
    facts.names_used = sorted(ex.names_used) if is_test_relpath(relpath) \
        else []
    for fn in facts.functions.values():
        _prune_books(fn)
        _prune_ckpt(fn)
        _pair_lock_acquires(fn)
    _module_scan(facts, tree)
    facts.pragmas = scan_pragmas(source)
    return facts


class Project:
    """The whole-program view: FileFacts per project-relative path."""

    def __init__(self, files: Optional[Dict[str, FileFacts]] = None):
        self.files: Dict[str, FileFacts] = files or {}
        self._by_module: Optional[Dict[str, FileFacts]] = None

    def add(self, facts: FileFacts):
        self.files[facts.relpath] = facts
        self._by_module = None

    def by_module(self) -> Dict[str, FileFacts]:
        if self._by_module is None:
            self._by_module = {f.module: f for f in self.files.values()}
        return self._by_module

    def test_files(self) -> List[FileFacts]:
        return [f for rel, f in self.files.items() if is_test_relpath(rel)]

    def iter_functions(self):
        for rel, f in self.files.items():
            for fn in f.functions.values():
                yield rel, f, fn
