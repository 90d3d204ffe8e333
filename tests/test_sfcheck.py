"""Tier-1 sfcheck (tools/sfcheck): the multi-pass analyzer keeps the whole
tree clean, every pass provably detects its target class (fixture corpus
under tests/fixtures/sfcheck/), pragma suppression and the --json CLI
contract hold, and the violations fixed in this tree stay fixed
(block_until_ready egress, numpy-scalar f-strings).
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.sfcheck import core, driver  # noqa: E402
from tools.sfcheck.passes import (  # noqa: E402
    ALL_PASSES,
    PASS_NAMES,
    PROJECT_PASSES,
    get_pass,
)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "sfcheck")

# Subprocesses are CPU runs: they never take the chip.
SUBPROC_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _check(src, pass_name, name="mod.py"):
    return core.check_source(name, textwrap.dedent(src),
                             [get_pass(pass_name)], force=True)


def _fixture(name, pass_names):
    path = os.path.join(FIXTURES, name)
    return core.check_file(path, [get_pass(n) for n in pass_names],
                           force=True)


def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.sfcheck", *args],
        capture_output=True, text=True, cwd=REPO, env=SUBPROC_ENV,
    )


# -- the analyzer itself -----------------------------------------------------

def test_all_seventeen_passes_registered():
    assert set(PASS_NAMES) == {
        # file passes
        "hotpath", "trace-hygiene", "fixed-shape", "sync-discipline",
        "fstring-numpy",
        # whole-program passes
        "hotpath-interproc", "mesh-parity", "recompile-surface",
        "donation-safety", "pragma-staleness",
        # v3: concurrency discipline + cross-module contracts
        "lock-discipline", "module-singleton", "env-registry",
        "contract-twin",
        # v4: checkpoint/replay/collective contract analysis
        "checkpoint-schema", "replay-determinism",
        "collective-accounting",
    }
    for p in ALL_PASSES + PROJECT_PASSES:
        assert p.description and p.invariant


def test_repo_tree_is_clean_file_passes():
    # The per-file framework alone (back-compat surface: run_paths).
    report = core.run_paths(core.default_targets())
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings
    )
    # The scan actually covered the tree, not an empty walk.
    assert report.files > 100


def test_repo_tree_is_clean_whole_program():
    # The full driver: file passes + project passes + pragma-staleness.
    report = driver.run(use_cache=False)
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings
    )
    assert report.files > 100
    assert set(report.pass_names) == set(PASS_NAMES)


def test_cli_json_breakdown_over_subtree():
    # Explicit targets form a PARTIAL project view: the file passes
    # report a zero breakdown; whole-program passes are deliberately
    # absent (they would see an incomplete world — no tests/, missing
    # callers — and manufacture findings). The full ten-pass verdict is
    # the default no-args run (test_repo_tree_is_clean_whole_program).
    res = _cli("--json", "spatialflink_tpu", "__graft_entry__.py", "tools")
    assert res.returncode == 0, res.stdout + res.stderr
    data = json.loads(res.stdout)
    assert data["findings"] == []
    assert set(data["counts"]) == {p.name for p in ALL_PASSES}
    assert all(v == 0 for v in data["counts"].values())
    assert data["files"] > 70


def test_single_file_invocation_has_no_partial_view_false_positives():
    # `sfcheck <file I edited>` must not exit 1 with bogus mesh-parity /
    # staleness findings just because the rest of the program is outside
    # the view.
    res = _cli("--no-cache", "spatialflink_tpu/parallel/sharded.py")
    assert res.returncode == 0, res.stdout + res.stderr


# -- fixture corpus: one true-positive + one clean file per pass -------------

@pytest.mark.parametrize("pass_name,expect_bad", [
    ("hotpath", 5),
    ("trace-hygiene", 5),
    ("fixed-shape", 6),
    ("sync-discipline", 3),
    ("fstring-numpy", 4),
])
def test_fixture_corpus(pass_name, expect_bad):
    stem = pass_name.replace("-", "_")
    bad = _fixture(f"{stem}_bad.py", [pass_name])
    assert len(bad) == expect_bad, "\n".join(f.format() for f in bad)
    assert all(f.pass_name == pass_name for f in bad)
    assert _fixture(f"{stem}_clean.py", [pass_name]) == []


def test_pragma_fixture_suppresses_every_class():
    assert _fixture("pragmas_ok.py", [p.name for p in ALL_PASSES]) == []


# -- pragma semantics --------------------------------------------------------

def test_bare_pragma_suppresses_all_passes():
    src = """
        import jax
        def f(x):
            jax.block_until_ready(x)  # sfcheck: ok
    """
    assert _check(src, "sync-discipline") == []


def test_named_pragma_suppresses_only_that_pass():
    src = """
        import jax
        def f(x):
            jax.block_until_ready(x)  # sfcheck: ok=sync-discipline -- why
    """
    assert _check(src, "sync-discipline") == []
    # The same pragma naming a DIFFERENT pass does not suppress.
    wrong = src.replace("ok=sync-discipline", "ok=hotpath")
    assert len(_check(wrong, "sync-discipline")) == 1


def test_pragma_spans_multiline_call():
    src = """
        import jax.numpy as jnp
        def f(mask):
            return jnp.nonzero(
                mask,
            )  # sfcheck: ok=fixed-shape -- fixture: pragma on the close paren
    """
    assert _check(src, "fixed-shape") == []


def test_string_embedded_pragma_does_not_suppress_file_pass():
    # pragma-looking text inside a string ARGUMENT of the flagged node
    # must not suppress (the old line-regex suppression did): only real
    # comment tokens count.
    src = """
        import jax
        def f(x):
            return jax.block_until_ready(
                x, "docs say use # sfcheck: ok here"
            )
    """
    assert len(_check(src, "sync-discipline")) == 1


def test_syntax_error_is_reported_not_swallowed():
    findings = core.check_source("broken.py", "def f(:\n", ALL_PASSES,
                                 force=True)
    assert len(findings) == 1 and findings[0].pass_name == "syntax"


# -- CLI contract ------------------------------------------------------------

def test_cli_exit_codes_and_human_output(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\ndef f(x):\n    jax.block_until_ready(x)\n")
    res = _cli("--pass", "sync-discipline", str(bad))
    assert res.returncode == 1
    assert "bad.py:3" in res.stdout and "[sync-discipline]" in res.stdout
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    res = _cli("--pass", "sync-discipline", str(clean))
    assert res.returncode == 0 and res.stdout == ""


def test_cli_json_on_fixture():
    res = _cli("--pass", "fixed-shape", "--json",
               os.path.join(FIXTURES, "fixed_shape_bad.py"))
    assert res.returncode == 1
    data = json.loads(res.stdout)
    assert data["counts"] == {"fixed-shape": 6}
    assert {f["pass"] for f in data["findings"]} == {"fixed-shape"}
    assert all(f["line"] > 0 and f["message"] for f in data["findings"])


def test_cli_json_carries_evidence_chain():
    res = _cli("--no-cache", "--pass", "hotpath-interproc", "--json",
               os.path.join(FIXTURES, "hotpath_interproc_bad.py"))
    assert res.returncode == 1, res.stdout + res.stderr
    data = json.loads(res.stdout)
    assert data["counts"]["hotpath-interproc"] == 2
    evs = [f["evidence"] for f in data["findings"]]
    assert all(evs), "every project finding carries evidence"
    assert any(len(e) >= 3 for e in evs), "2-hop call path resolved"


def test_cli_mesh_parity_fixture_repo_via_project_root():
    root = os.path.join(FIXTURES, "meshparity_bad")
    res = _cli("--no-cache", "--pass", "mesh-parity",
               "--project-root", root, "--json", root)
    assert res.returncode == 1, res.stdout + res.stderr
    data = json.loads(res.stdout)
    assert data["counts"]["mesh-parity"] == 3
    assert any("counterpart: ops/single.py:base_kernel" in e
               for f in data["findings"] for e in f["evidence"])


def test_cli_broken_pipe_preserves_gate_verdict(monkeypatch):
    """`sfcheck | head` closing the pipe mid-print must not flip the
    exit code: findings stay 1, clean stays 0 (the exit code IS the
    pre-commit gate)."""
    import builtins

    from tools.sfcheck import cli
    from tools.sfcheck.core import Finding, Report

    # neutralize the stdout detach under pytest's fd-level capture
    monkeypatch.setattr(os, "dup2", lambda a, b: None)

    def exploding_print(*a, **k):
        raise BrokenPipeError

    monkeypatch.setattr(builtins, "print", exploding_print)
    monkeypatch.setattr(cli.driver, "run", lambda **k: Report(
        [Finding("f.py", 1, 1, "hotpath", "boom")], 1, ["hotpath"]))
    assert cli.main([]) == 1
    monkeypatch.setattr(cli.driver, "run",
                        lambda **k: Report([], 1, ["hotpath"]))
    assert cli.main([]) == 0
    # a pipe break OUTSIDE the guarded print sections: verdict unknown,
    # fail safe
    def boom(args):
        raise BrokenPipeError

    monkeypatch.setattr(cli, "_run", boom)
    assert cli.main([]) == 1


def test_cli_internal_crash_is_exit_three(monkeypatch, capsys):
    from tools.sfcheck import cli

    def crash(**kwargs):
        raise RuntimeError("injected analyzer crash")

    monkeypatch.setattr(cli.driver, "run", crash)
    assert cli.main([]) == 3
    assert "injected analyzer crash" in capsys.readouterr().err


def test_cli_unknown_pass_is_usage_error():
    res = _cli("--pass", "no-such-pass")
    assert res.returncode == 2
    assert "unknown pass" in res.stderr


def test_cli_missing_path_is_usage_error_not_crash():
    res = _cli("no_such_file_xyz.py")
    assert res.returncode == 2
    assert "no such file" in res.stderr
    assert "Traceback" not in res.stderr


def test_cli_list_passes():
    res = _cli("--list-passes")
    assert res.returncode == 0
    for name in PASS_NAMES:
        assert name in res.stdout


# -- whole-program passes: fixture corpus + evidence chains ------------------

def _project_fixture(name, pass_name, project_root=None):
    path = os.path.join(FIXTURES, name)
    report = driver.run(
        paths=[path], pass_names=[pass_name], use_cache=False,
        project_root=project_root,
    )
    return report.findings


@pytest.mark.parametrize("pass_name,expect_bad", [
    ("hotpath-interproc", 2),
    ("recompile-surface", 2),
    ("donation-safety", 4),
])
def test_project_fixture_corpus(pass_name, expect_bad):
    stem = pass_name.replace("-", "_")
    bad = _project_fixture(f"{stem}_bad.py", pass_name)
    assert len(bad) == expect_bad, "\n".join(f.format() for f in bad)
    assert all(f.pass_name == pass_name for f in bad)
    # every finding carries a resolved evidence chain
    assert all(f.evidence for f in bad)
    assert _project_fixture(f"{stem}_clean.py", pass_name) == []


def test_mesh_parity_fixture_repo():
    root = os.path.join(FIXTURES, "meshparity_bad")
    bad = _project_fixture("meshparity_bad", "mesh-parity",
                           project_root=root)
    # sharded_untested: no test; sharded_orphan: no counterpart + no test
    assert len(bad) == 3, "\n".join(f.format() for f in bad)
    msgs = "\n".join(f.message for f in bad)
    assert "referenced by no test" in msgs
    assert "no single-device ops/ counterpart" in msgs
    # cross-file evidence: the resolved counterpart for the tested half
    ev = "\n".join(e for f in bad for e in f.evidence)
    assert "counterpart: ops/single.py:base_kernel" in ev
    clean_root = os.path.join(FIXTURES, "meshparity_clean")
    assert _project_fixture("meshparity_clean", "mesh-parity",
                            project_root=clean_root) == []


def test_interproc_catches_what_the_syntactic_pass_misses():
    """The acceptance pin: eager jnp two call hops from a per-window
    loop. The per-file hotpath pass (module-scope jnp in ops/) finds
    NOTHING even force-run on the file; the call-graph pass finds it and
    names every hop."""
    path = os.path.join(FIXTURES, "hotpath_interproc_bad.py")
    assert _fixture("hotpath_interproc_bad.py", ["hotpath"]) == []
    findings = _project_fixture("hotpath_interproc_bad.py",
                                "hotpath-interproc")
    two_hop = [f for f in findings if len(f.evidence) >= 3]
    assert two_hop, "\n".join(f.format() for f in findings)
    ev = two_hop[0].evidence
    assert "per-window loop" in ev[0]
    assert "`tally` calls `summarize" in ev[1]
    assert "eager `jnp.sort" in ev[2]
    # and the direct-in-loop case is one-step evidence
    direct = [f for f in findings if "directly inside" in f.evidence[0]]
    assert len(direct) == 1


def test_recompile_surface_accepts_ladder_routed_form():
    """The acceptance pin: a raw len() shape is flagged; the
    pick_capacity/next_bucket-routed twin is accepted."""
    bad = _project_fixture("recompile_surface_bad.py", "recompile-surface")
    assert any("len(win.events)" in f.message for f in bad)
    assert any("shape" in f.message and ".shape[0]" in f.message
               for f in bad)
    assert _project_fixture("recompile_surface_clean.py",
                            "recompile-surface") == []


def test_donation_cross_evidence_names_wrapper_definition():
    bad = _project_fixture("donation_safety_bad.py", "donation-safety")
    ev = "\n".join(e for f in bad for e in f.evidence)
    assert "donating wrapper `step" in ev
    assert "inline `jax.jit(…, donate_argnums=…)` call" in ev


# -- pragma staleness --------------------------------------------------------

def _staleness(tmp_path, source):
    f = tmp_path / "mod.py"
    f.write_text(textwrap.dedent(source))
    report = driver.run(paths=[str(f)], pass_names=["pragma-staleness"],
                        use_cache=False)
    return report.findings


def test_stale_pragma_is_a_finding(tmp_path):
    findings = _staleness(tmp_path, """
        x = 1  # sfcheck: ok=hotpath -- suppresses nothing
    """)
    assert len(findings) == 1
    assert findings[0].pass_name == "pragma-staleness"
    assert "hotpath" in findings[0].message


def test_live_pragma_is_not_stale(tmp_path):
    findings = _staleness(tmp_path, """
        import jax
        def f(x):
            jax.block_until_ready(x)  # sfcheck: ok=sync-discipline -- why
    """)
    assert findings == []


def test_pragma_in_string_or_prose_is_not_a_pragma(tmp_path):
    findings = _staleness(tmp_path, '''
        SRC = """
        y = jnp.zeros(4)  # sfcheck: ok=hotpath -- inside a string
        """
        # doc comment mentioning `# sfcheck: ok` semantics is prose
        x = 1
    ''')
    assert findings == []


def test_stale_pragma_not_self_suppressible(tmp_path):
    # A bare pragma would suppress every pass on its line — staleness
    # findings deliberately bypass suppression or every dead bare pragma
    # would hide itself.
    findings = _staleness(tmp_path, """
        x = 1  # sfcheck: ok
    """)
    assert len(findings) == 1


# -- incremental cache / --changed -------------------------------------------

def test_cache_invalidation_and_hits(tmp_path, monkeypatch):
    import time as _time

    proj = tmp_path / "proj"
    proj.mkdir()
    a = proj / "aa.py"
    b = proj / "bb.py"
    a.write_text("import jax\ndef f(x):\n    jax.block_until_ready(x)\n")
    b.write_text("x = 1\n")
    monkeypatch.setattr(core, "default_targets", lambda: [str(proj)])
    cache_path = str(tmp_path / "cache.json")

    analyzed = []
    real = driver._analyze_file

    def counting(path, relpath, passes, force):
        analyzed.append(relpath)
        return real(path, relpath, passes, force)

    monkeypatch.setattr(driver, "_analyze_file", counting)

    r1 = driver.run(changed=True, cache_path=cache_path)
    assert sorted(analyzed) == ["aa.py", "bb.py"]
    assert [f.pass_name for f in r1.findings] == ["sync-discipline"]
    assert os.path.exists(cache_path)

    # untouched → cache hit: nothing re-analyzed, identical findings
    analyzed.clear()
    t0 = _time.monotonic()
    r2 = driver.run(changed=True, cache_path=cache_path)
    warm_s = _time.monotonic() - t0
    assert analyzed == []
    assert [(f.pass_name, f.lineno) for f in r2.findings] == \
        [(f.pass_name, f.lineno) for f in r1.findings]
    assert warm_s < 1.0  # the sub-second pre-commit contract

    # edit one file → exactly that file re-analyzed, verdict updates
    a.write_text("x = 2\n")
    analyzed.clear()
    r3 = driver.run(changed=True, cache_path=cache_path)
    assert analyzed == ["aa.py"]
    assert r3.findings == []

    # mtime bump with unchanged content (git checkout): still a cache
    # hit via the sha check, and the entry's stored mtime refreshes so
    # the NEXT run takes the stat fast path again
    os.utime(b, ns=(1, 1))
    analyzed.clear()
    driver.run(changed=True, cache_path=cache_path)
    assert analyzed == []
    entry = json.load(open(cache_path))["files"]["bb.py"]
    assert entry["mtime_ns"] == os.stat(b).st_mtime_ns

    # plain (non --changed) runs ignore the cache and fully re-analyze
    analyzed.clear()
    driver.run(changed=False, cache_path=cache_path)
    assert sorted(analyzed) == ["aa.py", "bb.py"]


def test_cache_entries_survive_roundtrip_uncorrupted(tmp_path, monkeypatch):
    """Two consecutive cached runs must agree with the uncached verdict —
    regression for the facts_from_dict mutation that gutted call facts
    out of the cache on re-save."""
    proj = tmp_path / "proj"
    (proj / "parallel").mkdir(parents=True)
    (proj / "ops").mkdir()
    (proj / "parallel" / "k.py").write_text(
        "from ops.s import base\n\ndef sharded_k(mesh, x):\n"
        "    return base(x)\n"
    )
    (proj / "ops" / "s.py").write_text("def base(x):\n    return x\n")
    (proj / "tests").mkdir()
    (proj / "tests" / "test_k.py").write_text(
        "from parallel.k import sharded_k\n"
    )
    monkeypatch.setattr(core, "default_targets", lambda: [str(proj)])
    monkeypatch.setattr(core, "relpath_of", lambda p: os.path.relpath(
        os.path.abspath(p), str(proj)).replace(os.sep, "/"))
    cache_path = str(tmp_path / "cache.json")
    for _ in range(3):  # cold, warm, warm-after-resave
        report = driver.run(changed=True, cache_path=cache_path)
        assert report.findings == [], "\n".join(
            f.format() for f in report.findings)


# -- v3 passes: fixture mini-repos + evidence chains -------------------------


def _mini_repo(name, pass_name):
    root = os.path.join(FIXTURES, name)
    return driver.run(paths=[root], pass_names=[pass_name],
                      use_cache=False, project_root=root).findings


def test_lock_discipline_fixture_repo():
    bad = _mini_repo("lock_discipline_bad", "lock-discipline")
    assert len(bad) == 4, "\n".join(f.format() for f in bad)
    msgs = "\n".join(f.message for f in bad)
    # the three hazard classes, each detected under a held lock
    assert "telemetry emit/flush" in msgs
    assert "blocking call `time.sleep" in msgs
    assert "user callback" in msgs
    # the seeded two-module cycle, with both halves in the evidence
    cyc = [f for f in bad if "lock-order cycle" in f.message]
    assert len(cyc) == 1
    ev = "\n".join(cyc[0].evidence)
    assert "moda.py" in ev and "modb.py" in ev
    assert "_LOCK_A" in cyc[0].message and "_LOCK_B" in cyc[0].message
    assert all(f.evidence for f in bad)
    assert _mini_repo("lock_discipline_clean", "lock-discipline") == []


def test_module_singleton_fixture_repo():
    bad = _mini_repo("module_singleton_bad", "module-singleton")
    assert len(bad) == 1, "\n".join(f.format() for f in bad)
    f = bad[0]
    assert "python -m pkg.state" in f.message
    ev = "\n".join(f.evidence)
    # both state kinds named: the install slot AND the instance
    assert "rebinds module global `_slot`" in ev
    assert "registry = Registry()" in ev
    assert _mini_repo("module_singleton_clean", "module-singleton") == []


def test_env_registry_fixture_repo():
    bad = _mini_repo("env_registry_bad", "env-registry")
    assert len(bad) == 3, "\n".join(f.format() for f in bad)
    msgs = "\n".join(f.message for f in bad)
    assert "`SFT_UNREGISTERED` is read here but not registered" in msgs
    assert "`SFT_DEAD` has no read site" in msgs
    assert "SFT_ARMED_UNSCRUBBED" in msgs and "gate stages" in msgs
    assert all(f.evidence for f in bad)
    assert _mini_repo("env_registry_clean", "env-registry") == []


def test_contract_twin_fixture_repo():
    bad = _mini_repo("contract_twin_bad", "contract-twin")
    assert len(bad) == 9, "\n".join(f.format() for f in bad)
    msgs = "\n".join(f.message for f in bad)
    # spec-field drift, both directions (incl. the e2e lineage ceiling)
    assert "declares field `extra_live_only`" in msgs
    assert "declares field `e2e_p99_ms`" in msgs
    assert "lists `mirror_only`" in msgs
    # version pin drift
    assert "version twin drift" in msgs
    # injection-point ↔ matrix drift, both directions
    assert "`p.two` is registered in INJECTION_POINTS" in msgs
    assert "`p.ghost` matches no registered" in msgs
    # emit-name contract: typo, dynamic head, and consumer drift
    assert "`typo_event` is emitted but absent" in msgs
    assert "no literal head" in msgs
    assert "`never_emitted` but nothing emits it" in msgs
    assert all(f.evidence for f in bad)
    assert _mini_repo("contract_twin_clean", "contract-twin") == []


def _scratch_repo(tmp_path, files, pass_name):
    root = tmp_path / "proj"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return driver.run(paths=[str(root)], pass_names=[pass_name],
                      use_cache=False, project_root=str(root)).findings


def test_lock_discipline_multi_item_with_orders(tmp_path):
    """`with a, b:` acquires left-to-right: the same-statement spans
    share a lineno, so rank — not line nesting — must supply the A→B
    order edge, or this common form hides a real deadlock."""
    found = _scratch_repo(tmp_path, {"m.py": (
        "import threading\n"
        "_LOCK_A = threading.Lock()\n"
        "_LOCK_B = threading.Lock()\n"
        "def f():\n"
        "    with _LOCK_A, _LOCK_B:\n"
        "        return 1\n"
        "def g():\n"
        "    with _LOCK_B:\n"
        "        with _LOCK_A:\n"
        "            return 2\n"
    )}, "lock-discipline")
    assert len(found) == 1, "\n".join(f.format() for f in found)
    assert "lock-order cycle" in found[0].message


def test_lock_discipline_imported_lock_identity(tmp_path):
    """A lock acquired through `from m1 import _LOCK` is the same
    graph node as m1's own acquisitions — direct opposite-order
    acquisition across two files must close the cycle."""
    found = _scratch_repo(tmp_path, {
        "m1.py": (
            "import threading\n"
            "_LOCK_A = threading.Lock()\n"
            "_LOCK_B = threading.Lock()\n"
            "def f():\n"
            "    with _LOCK_A:\n"
            "        with _LOCK_B:\n"
            "            return 1\n"
        ),
        "m2.py": (
            "from m1 import _LOCK_A, _LOCK_B\n"
            "def g():\n"
            "    with _LOCK_B:\n"
            "        with _LOCK_A:\n"
            "            return 2\n"
        ),
    }, "lock-discipline")
    assert len(found) == 1, "\n".join(f.format() for f in found)
    assert "lock-order cycle" in found[0].message
    ev = "\n".join(found[0].evidence)
    assert "m1.py" in ev and "m2.py" in ev


def test_env_registry_membership_test_is_a_read(tmp_path):
    """`"SFT_X" in os.environ` counts as a read: a registered var read
    only that way is NOT drift, and an unregistered one IS a finding."""
    registry = (
        'ENV_VARS = {"SFT_FLAG": {"owner": "m", "hazard": "tuning"}}\n'
        "def gate_scrub_vars():\n"
        "    return []\n"
    )
    clean = _scratch_repo(tmp_path, {
        "spatialflink_tpu/envvars.py": registry,
        "spatialflink_tpu/mod.py": (
            "import os\n"
            "def f():\n"
            '    return "SFT_FLAG" in os.environ\n'
        ),
    }, "env-registry")
    assert clean == [], "\n".join(f.format() for f in clean)
    bad = _scratch_repo(tmp_path / "b", {
        "spatialflink_tpu/envvars.py": registry,
        "spatialflink_tpu/mod.py": (
            "import os\n"
            "def f():\n"
            '    return ("SFT_FLAG" in os.environ\n'
            '            and "SFT_NOPE" in os.environ)\n'
        ),
    }, "env-registry")
    assert len(bad) == 1, "\n".join(f.format() for f in bad)
    assert "SFT_NOPE" in bad[0].message


def test_v3_cli_json_carries_evidence_chains():
    root = os.path.join(FIXTURES, "lock_discipline_bad")
    res = _cli("--no-cache", "--pass", "lock-discipline",
               "--project-root", root, "--json", root)
    assert res.returncode == 1, res.stdout + res.stderr
    data = json.loads(res.stdout)
    assert data["counts"]["lock-discipline"] == 4
    evs = [f["evidence"] for f in data["findings"]]
    assert all(evs), "every v3 finding carries a resolved chain"
    # the cycle finding resolves the full ring across both modules
    assert any(len(e) >= 5 for e in evs)


def test_lock_discipline_tree_pragmas_are_live():
    """The four telemetry provider-callback sites (stream seal + the
    overload, qserve, and dag snapshot providers) are real findings
    held by documented pragmas — if any goes stale (the hazard is fixed
    or the pass stops seeing it), pragma-staleness fails the tree, so
    this pin just keeps the justification honest."""
    import re

    src = open(os.path.join(
        REPO, "spatialflink_tpu", "telemetry.py")).read()
    assert len(re.findall(r"sfcheck: ok=lock-discipline", src)) == 4


# -- v3 satellite: analyzer-cost telemetry -----------------------------------


def test_json_carries_timings_and_cache_stats(tmp_path, monkeypatch):
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "aa.py").write_text("x = 1\n")
    monkeypatch.setattr(core, "default_targets", lambda: [str(proj)])
    cache_path = str(tmp_path / "cache.json")
    r1 = driver.run(changed=True, cache_path=cache_path)
    assert r1.cache_misses == 1 and r1.cache_hits == 0
    assert r1.elapsed_s > 0
    assert set(PASS_NAMES) - {"pragma-staleness"} <= \
        set(r1.timings) | {p.name for p in ALL_PASSES}
    # project passes + the call-graph build are timed individually
    for name in ("call-graph", "lock-discipline", "contract-twin"):
        assert name in r1.timings
    r2 = driver.run(changed=True, cache_path=cache_path)
    assert r2.cache_hits == 1 and r2.cache_misses == 0


def test_changed_warm_one_file_edit_stays_subsecond(tmp_path, monkeypatch):
    """The satellite pin: with all seventeen passes registered, a warm
    --changed run (everything cached) stays sub-second."""
    import time as _time

    proj = tmp_path / "proj"
    proj.mkdir()
    for i in range(20):
        (proj / f"m{i}.py").write_text(
            "import threading\n_LOCK = threading.Lock()\n"
            "def f():\n    with _LOCK:\n        return 1\n")
    monkeypatch.setattr(core, "default_targets", lambda: [str(proj)])
    cache_path = str(tmp_path / "cache.json")
    driver.run(changed=True, cache_path=cache_path)  # cold fill
    t0 = _time.monotonic()
    report = driver.run(changed=True, cache_path=cache_path)
    assert _time.monotonic() - t0 < 1.0
    assert report.cache_hits == 20 and report.cache_misses == 0


def test_cli_human_summary_line_in_default_mode(tmp_path, monkeypatch):
    """Whole-tree (gate) runs always print the cost summary; targeted
    runs stay quiet-when-clean (pinned above in the exit-code test)."""
    from tools.sfcheck import cli
    from tools.sfcheck.core import Report

    monkeypatch.setattr(cli.driver, "run", lambda **k: Report(
        [], 42, ["hotpath"], timings={"hotpath": 0.5},
        cache_hits=40, cache_misses=2, elapsed_s=0.9,
        default_mode=True))
    import io
    import contextlib

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert cli.main([]) == 0
    out = buf.getvalue()
    assert "42 file(s)" in out and "cache 40 hit / 2 miss" in out
    assert "slowest pass hotpath" in out


def test_cache_roundtrip_preserves_v3_facts(tmp_path, monkeypatch):
    """Cache-invalidation legs for the new fact kinds: verdicts from
    cached facts must equal fresh analysis — lock spans, env reads,
    emit sites, constants, and the main guard all ride the JSON cache."""
    proj = tmp_path / "proj"
    (proj / "spatialflink_tpu").mkdir(parents=True)
    (proj / "tools").mkdir()
    (proj / "spatialflink_tpu" / "envvars.py").write_text(
        'ENV_VARS = {"SFT_A": {"owner": "m", "hazard": "armed"}}\n'
        "def gate_scrub_vars():\n"
        '    return [n for n, m in ENV_VARS.items()'
        ' if m["hazard"] == "armed"]\n'
    )
    mod = proj / "spatialflink_tpu" / "mod.py"
    mod.write_text(
        "import os\nimport threading\n_LOCK = threading.Lock()\n"
        "def f(tel):\n"
        '    a = os.environ.get("SFT_A")\n'
        "    with _LOCK:\n        pass\n"
        "    return a\n"
    )
    (proj / "tools" / "ci.py").write_text(
        "def _cpu_env(reg):\n"
        "    for v in reg.gate_scrub_vars():\n"
        "        pass\n"
    )
    monkeypatch.setattr(core, "default_targets", lambda: [str(proj)])
    monkeypatch.setattr(core, "relpath_of", lambda p: os.path.relpath(
        os.path.abspath(p), str(proj)).replace(os.sep, "/"))
    cache_path = str(tmp_path / "cache.json")
    for _ in range(3):  # cold, warm, warm-after-resave
        report = driver.run(changed=True, cache_path=cache_path)
        assert report.findings == [], "\n".join(
            f.format() for f in report.findings)
    # edit the reader to add an unregistered var + an emit-under-lock:
    # only that file re-analyzes, and BOTH new-fact verdicts update
    mod.write_text(
        "import os\nimport threading\n_LOCK = threading.Lock()\n"
        "def f(tel):\n"
        '    a = os.environ.get("SFT_A")\n'
        '    b = os.environ.get("SFT_NEW_UNREGISTERED")\n'
        "    with _LOCK:\n"
        '        tel.emit_instant("boom")\n'
        "    return a, b\n"
    )
    report = driver.run(changed=True, cache_path=cache_path)
    assert report.cache_misses == 1 and report.cache_hits == 2
    by_pass = {}
    for f in report.findings:
        by_pass.setdefault(f.pass_name, []).append(f)
    assert len(by_pass.get("env-registry", [])) == 1
    assert len(by_pass.get("lock-discipline", [])) == 1


# -- v4: checkpoint-schema / replay-determinism / collective-accounting ------


def test_checkpoint_schema_fixture_repo():
    bad = _mini_repo("checkpoint_schema_bad", "checkpoint-schema")
    assert len(bad) == 3, "\n".join(f.format() for f in bad)
    msgs = "\n".join(f.message for f in bad)
    # all three rules, one finding each
    assert "has no published producer" in msgs
    assert "is never restored" in msgs
    assert "published conditionally but read without a legacy default" \
        in msgs
    assert all(f.evidence for f in bad)
    # the publish-without-legacy-default pair: evidence names BOTH halves
    rule3 = next(f for f in bad if "legacy default" in f.message)
    ev = "\n".join(rule3.evidence)
    assert "writes 'compaction_rung' inside a conditional branch" in ev
    assert "bare unconditional" in ev
    assert _mini_repo("checkpoint_schema_clean", "checkpoint-schema") == []


def test_replay_determinism_fixture_repo():
    bad = _mini_repo("replay_determinism_bad", "replay-determinism")
    assert len(bad) == 3, "\n".join(f.format() for f in bad)
    msgs = "\n".join(f.message for f in bad)
    assert "wall-clock read" in msgs
    assert "global unseeded RNG draw" in msgs
    # the set-iteration-into-commit egress repro, root named in evidence
    setf = next(f for f in bad if "set" in f.message
                and "hash seed" in f.message)
    assert "exactly-once egress commit" in setf.evidence[0]
    # the cross-function leg resolves the commit -> _stamp call step
    wall = next(f for f in bad if "wall-clock" in f.message)
    assert len(wall.evidence) >= 3
    assert any("`commit` calls `_stamp" in e for e in wall.evidence)
    # the checkpoint-publisher root class is also covered
    rng = next(f for f in bad if "RNG" in f.message)
    assert "checkpoint publisher" in rng.evidence[0]
    assert _mini_repo("replay_determinism_clean", "replay-determinism") \
        == []


def test_collective_accounting_fixture_repo():
    bad = _mini_repo("collective_accounting_bad", "collective-accounting")
    assert len(bad) == 3, "\n".join(f.format() for f in bad)
    msgs = "\n".join(f.message for f in bad)
    assert "lax.all_gather" in msgs and "lax.psum" in msgs
    assert "lax.ppermute" in msgs  # the unaccounted halo-exchange kind
    # the wrapper-covered stats_kernel stays clean; only the uncovered
    # kernels (halo.py's gather/psum pair, ring.py's ppermute) flag
    assert all(f.path.endswith(("halo.py", "ring.py")) for f in bad)
    ev = "\n".join(e for f in bad for e in f.evidence)
    assert "unreachable from all 1 accounting wrapper(s)" in ev
    assert all(f.evidence for f in bad)
    assert _mini_repo("collective_accounting_clean",
                      "collective-accounting") == []


@pytest.mark.parametrize("fixture,pass_name,expect", [
    ("checkpoint_schema_bad", "checkpoint-schema", 3),
    ("replay_determinism_bad", "replay-determinism", 3),
    ("collective_accounting_bad", "collective-accounting", 3),
])
def test_v4_cli_json_project_root_evidence(fixture, pass_name, expect):
    """The --project-root CLI leg per new pass: exit 1, per-pass count,
    and a resolved evidence chain on every finding."""
    root = os.path.join(FIXTURES, fixture)
    res = _cli("--no-cache", "--pass", pass_name,
               "--project-root", root, "--json", root)
    assert res.returncode == 1, res.stdout + res.stderr
    data = json.loads(res.stdout)
    assert data["counts"][pass_name] == expect
    assert all(f["evidence"] for f in data["findings"])
    assert any(len(f["evidence"]) >= 2 for f in data["findings"])


def test_cache_roundtrip_preserves_v4_facts(tmp_path, monkeypatch):
    """Cache-invalidation legs for the v4 fact kinds: checkpoint payload
    writes/reads and nondeterminism sites ride the JSON cache, and an
    edit that adds new instances re-analyzes exactly the edited file
    with both verdicts updating."""
    proj = tmp_path / "proj"
    proj.mkdir()
    op = proj / "op.py"
    op.write_text(
        "class Op:\n"
        "    def state(self):\n"
        '        return {"carry": self.carry}\n'
        "    def restore(self, state):\n"
        '        self.carry = state["carry"]\n'
    )
    (proj / "sink.py").write_text(
        "class FileSink:\n"
        "    def commit(self, rows):\n"
        "        for r in sorted({x.oid for x in rows}):\n"
        "            self.fh.write(str(r))\n"
    )
    monkeypatch.setattr(core, "default_targets", lambda: [str(proj)])
    monkeypatch.setattr(core, "relpath_of", lambda p: os.path.relpath(
        os.path.abspath(p), str(proj)).replace(os.sep, "/"))
    cache_path = str(tmp_path / "cache.json")
    for _ in range(2):  # cold fill, then fully-cached verdict
        report = driver.run(changed=True, cache_path=cache_path)
        assert report.findings == [], "\n".join(
            f.format() for f in report.findings)
    # edit: a bare read of a key the publisher never writes + a
    # wall-clock read inside the publisher
    op.write_text(
        "import time\n"
        "class Op:\n"
        "    def state(self):\n"
        '        return {"carry": self.carry, "at": time.time()}\n'
        "    def restore(self, state):\n"
        '        self.carry = state["carry"]\n'
        '        self.wm = state["watermark"]\n'
    )
    report = driver.run(changed=True, cache_path=cache_path)
    assert report.cache_misses == 1 and report.cache_hits == 1
    by_pass = {}
    for f in report.findings:
        by_pass.setdefault(f.pass_name, []).append(f)
    assert len(by_pass.get("checkpoint-schema", [])) >= 1
    assert len(by_pass.get("replay-determinism", [])) == 1


# -- v4 satellite: --format=github + per-pass summary counts -----------------


def test_cli_github_format_emits_workflow_commands(tmp_path):
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax.numpy as jnp\nX = jnp.zeros(3)\n")
    res = _cli("--no-cache", "--pass", "hotpath", "--format=github",
               str(dirty))
    assert res.returncode == 1, res.stdout + res.stderr  # codes unchanged
    lines = [ln for ln in res.stdout.splitlines()
             if ln.startswith("::error ")]
    assert len(lines) == 1
    assert "line=2" in lines[0] and "title=hotpath" in lines[0]
    # same input, human mode: identical exit, no workflow commands
    res_h = _cli("--no-cache", "--pass", "hotpath", str(dirty))
    assert res_h.returncode == 1
    assert "::error" not in res_h.stdout
    # clean input exits 0 with no commands either way
    clean = tmp_path / "clean.py"
    clean.write_text("import numpy as np\nX = np.zeros(3)\n")
    res_c = _cli("--no-cache", "--pass", "hotpath", "--format=github",
                 str(clean))
    assert res_c.returncode == 0 and "::error" not in res_c.stdout


def test_cli_github_format_escapes_evidence_chain():
    """Project-pass findings carry the ↳ chain inside the annotation,
    %0A-escaped — one single-line workflow command per finding."""
    root = os.path.join(FIXTURES, "replay_determinism_bad")
    res = _cli("--no-cache", "--pass", "replay-determinism",
               "--project-root", root, "--format=github", root)
    assert res.returncode == 1, res.stdout + res.stderr
    errors = [ln for ln in res.stdout.splitlines()
              if ln.startswith("::error ")]
    assert len(errors) == 3
    assert all("%0A↳" in ln for ln in errors)
    assert all("title=replay-determinism" in ln for ln in errors)


def test_cli_summary_line_prints_per_pass_counts():
    root = os.path.join(FIXTURES, "checkpoint_schema_bad")
    res = _cli("--no-cache", "--pass", "checkpoint-schema",
               "--project-root", root, root)
    assert res.returncode == 1
    assert "(checkpoint-schema 3)" in res.stdout


# -- targeted regressions for the violations fixed in this tree --------------

def test_no_block_until_ready_outside_telemetry():
    # __graft_entry__.py and tests/test_graft_entry.py used the no-op
    # block_until_ready as a "sync"; they now device_get. The ban covers
    # the driver surface, the whole test tree, the SLO engine and the
    # sfprof stream/recover modules
    # (the link probe's true-sync fetch lives in telemetry.py, the ONE
    # exempt module).
    sync = get_pass("sync-discipline")
    report = core.run_paths(
        [os.path.join(REPO, p) for p in
         ("__graft_entry__.py", "tests",
          os.path.join("spatialflink_tpu", "slo.py"),
          os.path.join("tools", "sfprof"))],
        [sync], force_files=True,
    )
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings
    )


def test_egress_fstrings_are_numpy_safe():
    # The twice-shipped bug: numpy ≥2 scalars reaching egress f-strings
    # print as np.float32(…). The egress layers now wrap in float() —
    # including the PR 7 surfaces: the SLO engine (check rows/violation
    # events land in ledgers and streams) and all of tools/sfprof
    # (report/diff/health/recover print parsed ledger values).
    fstr = get_pass("fstring-numpy")
    report = core.run_paths(
        [os.path.join(REPO, "spatialflink_tpu", "sncb"),
         os.path.join(REPO, "spatialflink_tpu", "mn"),
         os.path.join(REPO, "spatialflink_tpu", "telemetry.py"),
         os.path.join(REPO, "spatialflink_tpu", "slo.py"),
         os.path.join(REPO, "tools", "sfprof")],
        [fstr], force_files=True,
    )
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings
    )


def test_new_observability_modules_are_in_pass_scope():
    """The scope EXTENSION itself is pinned: fstring-numpy must apply to
    the SLO engine and every sfprof module; sync-discipline must apply
    everywhere except telemetry.py (slo.py and the stream modules are
    NOT exempt)."""
    fstr = get_pass("fstring-numpy")
    assert fstr.applies_to("spatialflink_tpu/slo.py")
    assert fstr.applies_to("tools/sfprof/stream.py")
    assert fstr.applies_to("tools/sfprof/slo.py")
    assert fstr.applies_to("tools/sfprof/cli.py")
    sync = get_pass("sync-discipline")
    assert sync.applies_to("spatialflink_tpu/slo.py")
    assert sync.applies_to("tools/sfprof/stream.py")
    assert not sync.applies_to("spatialflink_tpu/telemetry.py")


def test_overload_module_is_in_pass_scope():
    """ISSUE 9 scope pin: overload.py joined the fstring-numpy egress
    scope (transition events + smoke output) and the hotpath
    import-purity scope (the fire-site hooks import it from every
    assembler — an import-time dispatch there would touch the device)."""
    fstr = get_pass("fstring-numpy")
    assert fstr.applies_to("spatialflink_tpu/overload.py")
    hot = get_pass("hotpath")
    assert hot.applies_to("spatialflink_tpu/overload.py")
    assert hot.applies_to("spatialflink_tpu/driver.py")


def test_trajectory_wkt_formats_numpy_scalars_clean():
    from spatialflink_tpu.sncb.common import GpsEvent
    from spatialflink_tpu.sncb.ops import trajectory_wkt

    events = [
        GpsEvent(device_id="t1", ts=i,
                 lon=np.float64(4.5 + i), lat=np.float64(50.85))
        for i in range(2)
    ]
    wkt = trajectory_wkt(events)
    assert "np." not in wkt
    assert wkt == "LINESTRING (4.5 50.85, 5.5 50.85)"
    single = trajectory_wkt(events[:1])
    assert single == "POINT (4.5 50.85)"


def test_metrics_sink_row_numpy_safe(tmp_path):
    from spatialflink_tpu.sncb.metrics import MetricsSink

    sink = MetricsSink("q", path=str(tmp_path / "m.csv"), interval_s=0.0)
    # Event timestamp as a numpy scalar — the latency column must still
    # render as a plain decimal.
    sink.record(event_ts_ms=np.int64(0), n=3)
    sink.close()
    assert sink.rows, "no interval flushed"
    for row in sink.rows:
        assert "np." not in row, row


def test_reporter_line_numpy_safe(tmp_path):
    from spatialflink_tpu.mn.metrics import MetricNames, MetricRegistry
    from spatialflink_tpu.mn.reporter import NESFileReporter

    reg = MetricRegistry()
    reg.inc(MetricNames.SOURCE_IN, 10)
    reg.inc(MetricNames.SINK_OUT, 5)
    rep = NESFileReporter(reg, "q1", out_dir=str(tmp_path))
    line = rep.report(now=rep._last_time + 2.0)
    assert line.startswith("METRICS ts=")
    assert "np." not in line
    assert "eps_in_avg=5.00" in line


def test_fault_tolerance_modules_are_in_pass_scope():
    """ISSUE 8 satellite pin: the fault-tolerance layer joined the
    sfcheck scopes — fstring-numpy (driver/faults render egress lines
    and fault events), sync-discipline (tree-wide already, pinned
    explicitly), and hotpath's import-purity rule (module-scope eager
    jnp would be an import-time device touch — the one thing faults.py
    exists to survive). The wall-clock rule stays ops/-only: retry
    backoff and the hang kind legitimately read the clock."""
    fstr = get_pass("fstring-numpy")
    assert fstr.applies_to("spatialflink_tpu/driver.py")
    assert fstr.applies_to("spatialflink_tpu/faults.py")
    sync = get_pass("sync-discipline")
    assert sync.applies_to("spatialflink_tpu/driver.py")
    assert sync.applies_to("spatialflink_tpu/faults.py")
    hp = get_pass("hotpath")
    assert hp.applies_to("spatialflink_tpu/driver.py")
    assert hp.applies_to("spatialflink_tpu/faults.py")
    assert not hp.applies_to("spatialflink_tpu/streaming_job.py")

    # Import-purity finding fires in the fault-tolerance modules...
    src = """
        import jax.numpy as jnp
        BAD = jnp.zeros(4)
    """
    findings = _check(src, "hotpath", name="spatialflink_tpu/driver.py")
    assert len(findings) == 1 and "module-level" in findings[0].message
    # ...but the wall-clock rule does not (host control plane).
    src = """
        import time

        def backoff():
            return time.monotonic()
    """
    assert _check(src, "hotpath",
                  name="spatialflink_tpu/driver.py") == []
    assert len(_check(src, "hotpath",
                      name="spatialflink_tpu/ops/k.py")) == 1


def test_fault_tolerance_modules_are_clean():
    """The new modules pass their own scopes with zero findings."""
    report = core.run_paths(
        [os.path.join(REPO, "spatialflink_tpu", "driver.py"),
         os.path.join(REPO, "spatialflink_tpu", "faults.py")],
        [get_pass("hotpath"), get_pass("fstring-numpy"),
         get_pass("sync-discipline")],
        force_files=True,
    )
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings
    )
