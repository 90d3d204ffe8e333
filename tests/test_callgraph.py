"""Tier-1 call-graph units (tools/sfcheck/{project,callgraph}): fact
extraction, cross-file call resolution (bare names, aliased module
imports, from-imports, methods incl. inheritance, nested defs), the
jit-boundary classification (device entries / device-reachable / hot
per-window reachability with parent chains), and taint extraction."""

import ast
import os
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.sfcheck.callgraph import CallGraph  # noqa: E402
from tools.sfcheck.project import (  # noqa: E402
    Project,
    extract_facts,
    facts_from_dict,
    module_name_of,
)


def _project(files: dict):
    p = Project()
    for rel, src in files.items():
        src = textwrap.dedent(src)
        p.add(extract_facts(rel, ast.parse(src), src))
    return p, CallGraph(p)


# -- module naming / facts ---------------------------------------------------

def test_module_name_of():
    assert module_name_of("a/b/c.py") == "a.b.c"
    assert module_name_of("a/b/__init__.py") == "a.b"
    assert module_name_of("top.py") == "top"


def test_facts_roundtrip_preserves_calls():
    src = "def f():\n    g(1)\n\ndef g(x):\n    return x\n"
    facts = extract_facts("m.py", ast.parse(src), src)
    back = facts_from_dict(facts.to_dict())
    assert len(back.functions["f"].calls) == 1
    assert back.functions["f"].calls[0].target == "g"
    # and the source dict is NOT mutated by reconstruction (cache re-save)
    d = facts.to_dict()
    facts_from_dict(d)
    assert d["functions"]["f"]["calls"], "cache entry gutted by from_dict"


# -- resolution --------------------------------------------------------------

def test_bare_name_resolves_in_module():
    p, g = _project({"m.py": """
        def helper():
            pass
        def caller():
            helper()
    """})
    assert (("m.py", "helper"), 5) in [
        (r, ln) for r, ln in g.edges[("m.py", "caller")]
    ]


def test_from_import_resolves_cross_file():
    p, g = _project({
        "pkg/util.py": "def helper():\n    pass\n",
        "pkg/main.py": """
            from pkg.util import helper
            def caller():
                helper()
        """,
    })
    assert [r for r, _ in g.edges[("pkg/main.py", "caller")]] == \
        [("pkg/util.py", "helper")]


def test_aliased_module_import_resolves():
    p, g = _project({
        "pkg/util.py": "def helper():\n    pass\n",
        "pkg/main.py": """
            import pkg.util as u
            def caller():
                u.helper()
        """,
    })
    assert [r for r, _ in g.edges[("pkg/main.py", "caller")]] == \
        [("pkg/util.py", "helper")]


def test_aliased_from_import_resolves():
    p, g = _project({
        "pkg/util.py": "def helper():\n    pass\n",
        "pkg/main.py": """
            from pkg.util import helper as h
            def caller():
                h()
        """,
    })
    assert [r for r, _ in g.edges[("pkg/main.py", "caller")]] == \
        [("pkg/util.py", "helper")]


def test_self_method_resolves_through_base_class():
    p, g = _project({
        "base.py": """
            class Base:
                def shared(self):
                    pass
        """,
        "sub.py": """
            from base import Base
            class Sub(Base):
                def run(self):
                    self.shared()
        """,
    })
    assert [r for r, _ in g.edges[("sub.py", "Sub.run")]] == \
        [("base.py", "Base.shared")]


def test_unique_method_name_heuristic():
    # method call on an unknown receiver resolves iff exactly one class
    # project-wide defines it
    p, g = _project({
        "a.py": """
            class Telemetry:
                def record(self):
                    pass
        """,
        "b.py": """
            def caller(t):
                t.record()
        """,
    })
    assert [r for r, _ in g.edges[("b.py", "caller")]] == \
        [("a.py", "Telemetry.record")]
    # ambiguous (two classes define it) -> no edge
    p2, g2 = _project({
        "a.py": "class A:\n    def record(self):\n        pass\n",
        "c.py": "class C:\n    def record(self):\n        pass\n",
        "b.py": "def caller(t):\n    t.record()\n",
    })
    assert g2.edges[("b.py", "caller")] == []


def test_nested_def_resolves_before_module_scope():
    p, g = _project({"m.py": """
        def helper():
            pass
        def outer():
            def helper():
                pass
            helper()
    """})
    assert [r for r, _ in g.edges[("m.py", "outer")]] == \
        [("m.py", "outer.helper")]


# -- jit-boundary classification ---------------------------------------------

def test_decorated_def_is_device_entry():
    p, g = _project({"m.py": """
        import jax
        @jax.jit
        def kernel(x):
            return x
    """})
    assert ("m.py", "kernel") in g.device_entries


def test_partial_jit_decorator_is_device_entry():
    p, g = _project({"m.py": """
        import functools
        import jax
        @functools.partial(jax.jit, static_argnames=("k",))
        def kernel(x, k):
            return x
    """})
    assert ("m.py", "kernel") in g.device_entries


def test_fn_passed_to_jit_wrapper_is_device_entry_and_callees_reachable():
    p, g = _project({"m.py": """
        import jax
        def inner(x):
            return x
        def kernel(x):
            return inner(x)
        prog = jax.jit(kernel)
    """})
    assert ("m.py", "kernel") in g.device_entries
    assert g.is_device("m.py", "inner")          # transitively traced
    assert not g.is_device("m.py", "<module>")


def test_shard_map_closure_is_device():
    p, g = _project({"m.py": """
        from jax import shard_map
        def wrapper(mesh, x):
            def local(x_l):
                return x_l
            return shard_map(local, mesh=mesh)(x)
    """})
    assert ("m.py", "wrapper.local") in g.device_entries


def test_builtin_map_is_not_a_jit_wrapper():
    p, g = _project({"m.py": """
        def f(x):
            return x
        def caller(xs):
            return list(map(f, xs))
    """})
    assert ("m.py", "f") not in g.device_entries


def test_window_loop_hot_chain_two_hops():
    p, g = _project({"m.py": """
        def b():
            return 1
        def a():
            return b()
        def run(stream):
            for win in windows(stream):
                a()
    """})
    chain_a = g.hot_chain("m.py", "a")
    chain_b = g.hot_chain("m.py", "b")
    assert chain_a is not None and len(chain_a) == 1
    assert "per-window loop" in chain_a[0].note
    assert chain_b is not None and len(chain_b) == 2
    assert "`a` calls `b" in chain_b[1].note
    assert g.hot_chain("m.py", "run") is None    # the loop owner itself


def test_hot_does_not_cross_into_device_or_memoized():
    p, g = _project({"m.py": """
        import functools
        import jax
        @jax.jit
        def kernel(x):
            return x
        @functools.lru_cache(maxsize=None)
        def cached_const(n):
            return n
        def run(stream):
            for win in windows(stream):
                kernel(win)
                cached_const(8)
    """})
    assert g.hot_chain("m.py", "kernel") is None
    assert g.hot_chain("m.py", "cached_const") is None


# -- candidate-site extraction ----------------------------------------------

def test_eager_jnp_sites_exclude_ship_and_meta():
    src = textwrap.dedent("""
        import jax.numpy as jnp
        def f(x):
            a = jnp.asarray(x)      # ship: sanctioned
            b = jnp.finfo(a.dtype)  # metadata: free
            return jnp.sort(a)      # compute: eager site
    """)
    facts = extract_facts("m.py", ast.parse(src), src)
    sites = facts.functions["f"].eager_jnp
    assert [s["attr"] for s in sites] == ["sort"]


def test_shape_taint_len_and_sanitizer():
    src = textwrap.dedent("""
        import jax.numpy as jnp
        def bad(events):
            n = len(events)
            return jnp.zeros((n, 2))
        def good(events):
            n = len(events)
            b = next_bucket(n)
            return jnp.zeros((b, 2))
    """)
    facts = extract_facts("m.py", ast.parse(src), src)
    assert len(facts.functions["bad"].shape_sites) == 1
    assert "len(events)" in facts.functions["bad"].shape_sites[0]["src"]
    assert facts.functions["good"].shape_sites == []
