"""fstring-numpy pass — float-formatted egress values must be wrapped.

Invariant (CLAUDE.md "Environment rules"): values formatted into egress
strings must be wrapped in ``float()`` (or ``int()``) first. The actual
numpy ≥2 leak vectors are repr contexts — a scalar inside a container
(``f"{results[:3]}"`` → ``[np.int32(50), …]``, the bug that shipped
twice) or ``!r`` — which no cheap static check can prove safe. So the
enforced rule is the CONVENTION that keeps the boundary uniformly safe:
in the known egress layers (sncb/, mn/, telemetry.py), any
f-string ``FormattedValue`` or constant-string ``.format(…)`` argument
carrying a float presentation spec (``f``/``e``/``g``/``%``) must be an
obviously-host scalar — a numeric literal or a call to
``float``/``int``/``round``/``len``. Wrapping a value that was already a
Python float is free; the habit is what prevents the container/repr
leaks the analyzer cannot see.
"""

from __future__ import annotations

import ast
import re
import string

from tools.sfcheck.core import Pass

_FLOAT_SPEC = re.compile(r"[eEfFgG%]$")
_SAFE_CALLS = {"float", "int", "round", "len"}


def _safe(value: ast.AST) -> bool:
    if isinstance(value, ast.Constant) and isinstance(
            value.value, (int, float)):
        return True
    if (isinstance(value, ast.Call) and isinstance(value.func, ast.Name)
            and value.func.id in _SAFE_CALLS):
        return True
    return False


def _spec_text(format_spec) -> str:
    # format_spec is a JoinedStr; dynamic specs (nested FormattedValue)
    # return "" and are skipped — can't reason statically.
    if format_spec is None or len(format_spec.values) != 1:
        return ""
    part = format_spec.values[0]
    if isinstance(part, ast.Constant) and isinstance(part.value, str):
        return part.value
    return ""


class _Visitor(ast.NodeVisitor):
    def __init__(self):
        self.out = []

    def visit_FormattedValue(self, node):
        spec = _spec_text(node.format_spec)
        if _FLOAT_SPEC.search(spec.strip()) and not _safe(node.value):
            expr = ast.unparse(node.value)
            self.out.append((
                node,
                f"float-formatted f-string value `{{{expr}:{spec}}}` is "
                "not wrapped in float()/int() — egress convention "
                "(CLAUDE.md): uniform wrapping at this boundary is what "
                "keeps numpy ≥2 scalar reprs (np.float32(…)) out of "
                "egress records",
            ))
        self.generic_visit(node)

    def visit_Call(self, node):
        func = node.func
        if (isinstance(func, ast.Attribute) and func.attr == "format"
                and isinstance(func.value, ast.Constant)
                and isinstance(func.value.value, str)):
            self._check_format(node, func.value.value)
        self.generic_visit(node)

    def _check_format(self, node, fmt: str):
        try:
            fields = list(string.Formatter().parse(fmt))
        except ValueError:
            return
        auto = 0
        for _lit, field, spec, conv in fields:
            if field is None:
                continue
            root = re.split(r"[.\[]", field, 1)[0]
            index = None
            if root == "":
                index = auto
                auto += 1
            elif root.isdigit():
                index = int(root)
            floatish = (spec and _FLOAT_SPEC.search(spec.strip())
                        and not conv)
            if not floatish:
                continue
            arg = None
            if index is not None:
                if index < len(node.args) and not any(
                        isinstance(a, ast.Starred) for a in node.args):
                    arg = node.args[index]
            else:
                for kw in node.keywords:
                    if kw.arg == root:
                        arg = kw.value
            if arg is not None and not _safe(arg):
                self.out.append((
                    node,
                    f"float-formatted .format() argument for "
                    f"`{{{field}:{spec}}}` is not wrapped in "
                    "float()/int() — egress convention (CLAUDE.md): "
                    "uniform wrapping keeps numpy ≥2 scalar reprs out "
                    "of egress records",
                ))


class FstringNumpyPass(Pass):
    name = "fstring-numpy"
    description = ("float-format specs in egress f-strings/.format must "
                   "wrap values in float()/int()")
    invariant = ("egress strings never embed numpy scalar reprs; wrap "
                 "in float() first (CLAUDE.md)")

    def applies_to(self, relpath: str) -> bool:
        # tools/sfprof is an egress layer too: report/diff/health/
        # recover print values parsed straight out of ledgers and
        # streams (the ledger/stream writers themselves live in
        # telemetry.py, and the SLO engine's check rows/violation events
        # land in both artifacts) — the np.float32(…) repr class must
        # not reach any of these surfaces. driver.py/faults.py joined
        # the scope with the fault-tolerance work: the driver's egress
        # helpers render the exactly-once sink lines (the chaos matrix
        # byte-compares them), and fault events land in the ledger
        # stream. overload.py joined with the overload work — its
        # transition events and smoke output are egress surfaces too.
        return (relpath in ("spatialflink_tpu/telemetry.py",
                            "spatialflink_tpu/slo.py",
                            "spatialflink_tpu/driver.py",
                            "spatialflink_tpu/faults.py",
                            "spatialflink_tpu/overload.py")
                or relpath.startswith("spatialflink_tpu/sncb/")
                or relpath.startswith("spatialflink_tpu/mn/")
                or relpath.startswith("tools/sfprof/"))

    def run(self, ctx):
        v = _Visitor()
        v.visit(ctx.tree)
        return v.out
