"""sfcheck core — shared walker, pragma suppression, file loading, report.

The framework parses each file ONCE and runs every selected pass over the
shared AST. Passes are small visitor classes (tools/sfcheck/passes/) that
return ``(node, message)`` tuples; this module owns everything common:

- **Scoping**: each pass declares ``applies_to(relpath)`` (repo-relative
  path, or just the basename for files outside the repo). Directory scans
  always respect scope; explicitly-listed FILES can be force-checked
  (``force_files=True`` — the CLI does this when ``--pass`` is given, so
  fixtures and ad-hoc files can be linted regardless of location).
- **Allowlists**: per-pass ``allow_basenames`` skip fully host-side
  modules (e.g. ops/counters.py) even under force.
- **Pragma suppression**: ``# sfcheck: ok`` silences every pass on that
  line; ``# sfcheck: ok=<pass>[,<pass>…]`` silences only the named
  pass(es). Anything after the pass list is the human justification —
  convention: ``# sfcheck: ok=trace-hygiene -- host-side by design``.
  A finding attached to a multi-line node is suppressed by a pragma on
  ANY line the node spans (formatter-wrapped calls keep their pragma).
  Passes may additionally honor a ``legacy_pragma`` regex (hotpath keeps
  ``# hotpath: ok`` working).
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from typing import List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

PRAGMA_RE = re.compile(r"#\s*sfcheck:\s*ok(?:=(?P<passes>[A-Za-z0-9_,\-]+))?")

#: Anchored twin: a comment IS a pragma only when it starts with one (a
#: doc comment *mentioning* ``# sfcheck: ok`` is prose, not a
#: suppression).
PRAGMA_AT_START = re.compile(
    r"^#\s*sfcheck:\s*ok(?:=(?P<passes>[A-Za-z0-9_,\-]+))?")


def scan_pragmas(source: str) -> List[dict]:
    """``# sfcheck: ok`` COMMENT tokens only — never string contents
    (the test corpus embeds pragma-looking text in source strings), and
    only comments that start with the pragma."""
    out: List[dict] = []
    try:
        toks = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in toks:
            if tok.type != tokenize.COMMENT:
                continue
            m = PRAGMA_AT_START.match(tok.string)
            if m is None:
                continue
            names = m.group("passes")
            out.append({
                "line": tok.start[0],
                "passes": None if names is None
                else sorted({n.strip() for n in names.split(",")
                             if n.strip()}),
            })
    except (tokenize.TokenError, IndentationError, SyntaxError):
        pass
    return out

# Never scanned in directory walks: build trash plus the deliberate-
# violation corpus (tests/fixtures/sfcheck — loaded explicitly by tests).
EXCLUDE_DIR_NAMES = {".git", "__pycache__", "artifacts", "native", ".claude"}
EXCLUDE_REL_PREFIXES = ("tests/fixtures/sfcheck",)

# Scanned by default when the CLI gets no paths: every Python layer the
# invariants govern (ops/operators/streams/… plus the driver surface,
# the tools themselves, and the tests — sync-discipline bans
# block_until_ready there too).
DEFAULT_TARGETS = (
    "spatialflink_tpu",
    "tools",
    "tests",
    "bench_suite.py",
    "__graft_entry__.py",
)


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    lineno: int
    end_lineno: int
    pass_name: str
    message: str
    #: the resolved call-path / cross-file evidence chain, one
    #: "relpath:line: note" string per step (project passes fill this)
    evidence: Tuple[str, ...] = ()

    def format(self) -> str:
        head = f"{self.path}:{self.lineno}: [{self.pass_name}] {self.message}"
        if not self.evidence:
            return head
        return head + "".join(f"\n    ↳ {e}" for e in self.evidence)


@dataclasses.dataclass
class Report:
    findings: List[Finding]
    files: int
    pass_names: List[str]
    #: analyzer-cost telemetry (driver runs fill these): per-pass wall
    #: seconds of actual analysis (cache hits contribute nothing),
    #: cache hit/miss counts, and the end-to-end wall time.
    timings: dict = dataclasses.field(default_factory=dict)
    cache_hits: int = 0
    cache_misses: int = 0
    elapsed_s: float = 0.0
    #: True when the run covered the default whole-tree target set
    #: (the CLI prints its cost summary only there).
    default_mode: bool = False

    def counts(self) -> dict:
        out = {name: 0 for name in self.pass_names}
        for f in self.findings:
            out[f.pass_name] = out.get(f.pass_name, 0) + 1
        return out


class FileContext:
    """One parsed file shared by every pass."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self._bindings = None
        self._pragmas = None

    @property
    def pragmas(self) -> List[dict]:
        """Tokenize-based pragma inventory (COMMENT tokens only —
        pragma-looking text inside string literals never suppresses)."""
        if self._pragmas is None:
            self._pragmas = scan_pragmas(self.source)
        return self._pragmas

    @property
    def bindings(self):
        """Import bindings, scanned once and shared by every pass."""
        if self._bindings is None:
            from tools.sfcheck.passes._shared import Bindings
            self._bindings = Bindings.scan(self.tree)
        return self._bindings


class Pass:
    """Base class for analysis passes (registered in passes/__init__.py)."""

    name: str = ""
    description: str = ""
    #: one-line statement of the architecture invariant being enforced
    invariant: str = ""
    #: basenames skipped even when force-checked (host-side modules)
    allow_basenames: frozenset = frozenset()
    #: extra pragma regex honored besides ``# sfcheck: ok`` (back-compat)
    legacy_pragma: Optional[re.Pattern] = None

    def applies_to(self, relpath: str) -> bool:
        raise NotImplementedError

    def run(self, ctx: FileContext) -> List[Tuple[ast.AST, str]]:
        raise NotImplementedError


class ProjectPass:
    """Base class for whole-program passes (registered in
    passes/__init__.py). Runs once over the project model + call graph
    instead of once per file; findings carry an evidence chain."""

    name: str = ""
    description: str = ""
    invariant: str = ""

    def in_scope(self, relpath: str) -> bool:
        """Files whose code this pass may REPORT findings in (the whole
        project always contributes context). Driver force mode widens
        this to everything."""
        raise NotImplementedError

    def run_project(self, project, graph, in_scope) -> List[Finding]:
        """``in_scope`` is a callable(relpath) merging self.in_scope with
        the driver's force flag."""
        raise NotImplementedError


def relpath_of(path: str) -> str:
    ap = os.path.abspath(path)
    if ap == REPO_ROOT or ap.startswith(REPO_ROOT + os.sep):
        return os.path.relpath(ap, REPO_ROOT).replace(os.sep, "/")
    return os.path.basename(ap)


def _suppressing_pragma(p: Pass, ctx: FileContext,
                        node: ast.AST) -> Optional[Tuple[str, int]]:
    """("sfcheck"|"legacy", line) of the pragma suppressing this finding,
    or None. sfcheck pragmas come from the tokenize inventory (comment
    tokens only — pragma-looking text inside a string argument of the
    flagged node never suppresses); only they count for staleness."""
    lineno = getattr(node, "lineno", 1)
    last = max(getattr(node, "end_lineno", None) or lineno, lineno)
    for pr in ctx.pragmas:
        if lineno <= pr["line"] <= last:
            if pr["passes"] is None or p.name in pr["passes"]:
                return ("sfcheck", pr["line"])
    if p.legacy_pragma is not None:
        for ln in range(lineno, min(last, len(ctx.lines)) + 1):
            if p.legacy_pragma.search(ctx.lines[ln - 1]):
                return ("legacy", ln)
    return None


def suppressed_by_pragmas(pass_name: str, lineno: int, end_lineno: int,
                          pragmas) -> Optional[int]:
    """Pragma-line suppressing a PROJECT-pass finding, from a pragma
    inventory (project.scan_pragmas dicts) instead of source lines."""
    for pr in pragmas:
        if lineno <= pr["line"] <= max(end_lineno, lineno):
            if pr["passes"] is None or pass_name in pr["passes"]:
                return pr["line"]
    return None


def analyze_source(
    path: str,
    source: str,
    passes: Sequence[Pass],
    relpath: Optional[str] = None,
    force: bool = False,
) -> Tuple[List[Finding], List[Tuple[int, str]], Optional["FileContext"]]:
    """File passes over one source: (findings, consumed-pragma records,
    parsed context). ``consumed`` lists (pragma_line, pass_name) for every
    suppressed finding — the pragma-staleness rule's liveness evidence."""
    relpath = relpath_of(path) if relpath is None else relpath
    try:
        ctx = FileContext(path, relpath, source)
    except SyntaxError as e:
        return ([Finding(path, e.lineno or 1, e.lineno or 1, "syntax",
                         f"file does not parse: {e.msg}")], [], None)
    findings: List[Finding] = []
    consumed: List[Tuple[int, str]] = []
    base = os.path.basename(relpath)
    for p in passes:
        if base in p.allow_basenames:
            continue
        if not force and not p.applies_to(relpath):
            continue
        for node, message in p.run(ctx):
            sup = _suppressing_pragma(p, ctx, node)
            if sup is not None:
                if sup[0] == "sfcheck":
                    consumed.append((sup[1], p.name))
                continue
            lineno = getattr(node, "lineno", 1)
            end = getattr(node, "end_lineno", None) or lineno
            findings.append(Finding(path, lineno, end, p.name, message))
    findings.sort(key=lambda f: (f.path, f.lineno, f.pass_name))
    return findings, consumed, ctx


def check_source(
    path: str,
    source: str,
    passes: Sequence[Pass],
    relpath: Optional[str] = None,
    force: bool = False,
) -> List[Finding]:
    return analyze_source(path, source, passes, relpath, force)[0]


def check_file(path: str, passes: Sequence[Pass],
               force: bool = False) -> List[Finding]:
    with open(path, encoding="utf-8") as f:
        return check_source(path, f.read(), passes, force=force)


def iter_python_files(root: str, rel_excludes: bool = True):
    """Walk ``root`` for .py files. ``rel_excludes=False`` drops the
    repo-relative prefix excludes (the deliberate-violation fixture
    corpus) — used when a fixture mini-repo IS the analysis target."""
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames
            if d not in EXCLUDE_DIR_NAMES
            and (not rel_excludes
                 or not relpath_of(os.path.join(dirpath, d)).startswith(
                     EXCLUDE_REL_PREFIXES))
        )
        for name in sorted(filenames):
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def run_paths(
    paths: Sequence[str],
    passes: Optional[Sequence[Pass]] = None,
    force_files: bool = False,
) -> Report:
    """Analyze files/directories. Directories are walked (scope-filtered);
    explicit file paths are force-checked when ``force_files`` is set."""
    if passes is None:
        from tools.sfcheck.passes import ALL_PASSES
        passes = ALL_PASSES
    findings: List[Finding] = []
    files = 0
    for p in paths:
        if os.path.isdir(p):
            for fp in iter_python_files(p):
                files += 1
                findings.extend(check_file(fp, passes, force=False))
        else:
            files += 1
            findings.extend(check_file(p, passes, force=force_files))
    return Report(findings, files, [ps.name for ps in passes])


def default_targets() -> List[str]:
    return [
        os.path.join(REPO_ROOT, t)
        for t in DEFAULT_TARGETS
        if os.path.exists(os.path.join(REPO_ROOT, t))
    ]
