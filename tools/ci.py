"""THE pre-commit gate: ``python -m tools.ci`` (repo root).

One shot, five stages, fail-fast, distinct banners:

1. **sfcheck** — the whole-program static analyzer (all fourteen
   passes; ``--changed`` passes the incremental flag through for the
   sub-second path);
2. **quick-tier pytest** — ``pytest tests/ -m 'not slow'`` on CPU
   (``JAX_PLATFORMS=cpu`` in every stage — the gate never takes the
   chip);
3. **bench smoke + sfprof health** — an ``SFT_BENCH_SMOKE`` toy-size
   bench.py run on XLA:CPU writing a run ledger AND a ledger stream
   (``SFT_LEDGER_STREAM``), then ``python -m tools.sfprof health
   <ledger>`` threshold verdicts (recompile churn, overflows, late
   drops, watermark lag), then ``sfprof trend --gate`` checking the
   smoke capture against the committed toy trajectory fixture
   (``tests/fixtures/trend`` — robust median + MAD band;
   ``--require-history`` so a broken fixture fails loudly; tainted
   ablation captures are hard-rejected), then the crash-recovery round
   trip: ``sfprof recover <stream>`` → ``sfprof health <recovered>`` —
   every commit proves the durable capture path still reconstructs a
   gateable ledger;
4. **chaos smoke** — ``python -m spatialflink_tpu.driver
   --chaos-smoke``: a toy driver pipeline killed mid-run by an armed
   ``abort`` fault (``os._exit(137)``, the SIGKILL analog) and resumed
   from its checkpoint — the concatenated exactly-once egress must be
   byte-identical to a clean run;
5. **overload smoke** — ``python -m spatialflink_tpu.overload
   --smoke``: a toy burst past a tiny admission budget must shed
   deterministically, step the degradation ladder down AND back up,
   carry the shed/degradation budgets through the SLO verdict
   (including the per-tenant-class budgets), and seal every overload
   transition in the ledger stream;
6. **dag smoke** — ``python -m spatialflink_tpu.dag --smoke``: the
   7-node SNCB DAG (Q1–Q5 + StayTime + qserve on one source/interner/
   window clock) under an armed overload policy, killed by an
   ``abort`` fault BETWEEN two sink commits of the atomic unit
   checkpoint, resumed — every node's exactly-once egress must be
   byte-identical to the clean run's.

Exit code: the first failing stage's (sfcheck keeps its 0/1/2/3
contract; pytest and sfprof theirs). ``--skip-tests`` / ``--skip-bench``
/ ``--skip-chaos`` / ``--skip-overload`` / ``--skip-dag`` trim stages
for quick iteration (the chaos/overload/dag smokes are CPU-only and
independent of the bench stage, so ``--skip-bench`` keeps them);
``--dry-run`` prints the stage commands without running anything
(pinned by tests/test_ci.py).
"""

from __future__ import annotations

import argparse
import functools
import os
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=1)
def _envvars_registry():
    """Load spatialflink_tpu/envvars.py by FILE PATH, never by package
    import: the package __init__ configures jax, and the gate's own
    process must stay off it. The registry module is deliberately
    stdlib-only for exactly this loader."""
    import importlib.util

    path = os.path.join(REPO_ROOT, "spatialflink_tpu", "envvars.py")
    spec = importlib.util.spec_from_file_location("_sft_envvars", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env() -> Dict[str, str]:
    env = dict(os.environ)
    # The gate is a CPU run: it must never take the chip from a
    # measurement in flight.
    env["JAX_PLATFORMS"] = "cpu"
    # Scrub every hazard-class-`armed` var (ambient fault plans,
    # overload policies, live SLO specs, bench failure-forcing knobs):
    # any of them left over from test iteration would sabotage a
    # healthy gate run with injected behavior. The list is DERIVED from
    # the registry (spatialflink_tpu/envvars.py), so the next armed
    # var registered there is scrubbed here automatically — sfcheck's
    # env-registry pass pins this derivation.
    for var in _envvars_registry().gate_scrub_vars():
        env.pop(var, None)
    return env


def stages(changed: bool, skip_tests: bool, skip_bench: bool,
           skip_chaos: bool = False,
           skip_overload: bool = False,
           skip_dag: bool = False,
           ledger_path: Optional[str] = None,
           stream_path: Optional[str] = None) \
        -> List[Tuple[str, List[List[str]]]]:
    """(name, [argv, ...]) per stage — a stage may chain commands."""
    py = sys.executable
    out: List[Tuple[str, List[List[str]]]] = []
    sfcheck = [py, "-m", "tools.sfcheck"]
    if changed:
        sfcheck.append("--changed")
    if os.environ.get("GITHUB_ACTIONS"):
        # Under Actions the findings double as PR diff annotations
        # (::error workflow commands); exit codes are format-invariant.
        sfcheck.append("--format=github")
    out.append(("sfcheck", [sfcheck]))
    if not skip_tests:
        out.append(("pytest-quick", [[
            py, "-m", "pytest", "tests/", "-q", "-m", "not slow",
            "-p", "no:cacheprovider",
        ]]))
    if not skip_bench:
        ledger = ledger_path or os.path.join(
            tempfile.gettempdir(), "sft_ci_ledger.json")
        stream = stream_path or os.path.join(
            tempfile.gettempdir(), "sft_ci_ledger_stream.jsonl")
        recovered = stream + ".recovered.json"
        out.append(("bench-smoke+health", [
            [py, "bench.py"],
            [py, "-m", "tools.sfprof", "health", ledger],
            # Trajectory gate: the smoke capture against the committed
            # toy trend fixture (robust median + MAD band, tainted
            # captures hard-rejected). --require-history so a missing/
            # mismatched fixture FAILS instead of waving runs through.
            [py, "-m", "tools.sfprof", "trend",
             os.path.join("tests", "fixtures", "trend"),
             "--gate", ledger, "--require-history"],
            # Crash-recovery round trip on the stream the smoke run just
            # wrote: recover must rebuild a schema-valid ledger and that
            # ledger must pass the same health gate.
            [py, "-m", "tools.sfprof", "recover", stream,
             "-o", recovered],
            [py, "-m", "tools.sfprof", "health", recovered],
        ]))
    if not skip_chaos:
        # Chaos smoke: one kill (armed abort fault = SIGKILL analog) →
        # resume round trip on toy shapes, asserting byte-identical
        # exactly-once egress (spatialflink_tpu/driver.py). CPU-only and
        # independent of the bench stage, so --skip-bench keeps it.
        out.append(("chaos-smoke", [
            [py, "-m", "spatialflink_tpu.driver", "--chaos-smoke"],
        ]))
    if not skip_overload:
        # Overload smoke: burst → shed → degrade → recover round trip
        # on toy shapes (spatialflink_tpu/overload.py) — sheds counted,
        # ladder stepped both ways, budgets in the SLO verdict, every
        # transition sealed in the ledger stream. CPU-only too.
        out.append(("overload-smoke", [
            [py, "-m", "spatialflink_tpu.overload", "--smoke"],
        ]))
    if not skip_dag:
        # DAG smoke: the 7-node SNCB pipeline under an armed overload
        # policy, killed BETWEEN two sink commits of the atomic unit
        # checkpoint, resumed — byte-identical egress on every node's
        # sink (spatialflink_tpu/dag.py). CPU-only too.
        out.append(("dag-smoke", [
            [py, "-m", "spatialflink_tpu.dag", "--smoke"],
        ]))
    return out


def _bench_env(ledger: str, stream: str) -> Dict[str, str]:
    env = _cpu_env()
    env.update({
        "SFT_BENCH_SMOKE": "1",
        "SFT_LEDGER_PATH": ledger,
        "SFT_LEDGER_STREAM": stream,
    })
    return env


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.ci",
        description="pre-commit gate: sfcheck → quick pytest → "
                    "bench smoke + sfprof health → chaos smoke",
    )
    ap.add_argument("--changed", action="store_true",
                    help="incremental sfcheck (--changed cache mode)")
    ap.add_argument("--skip-tests", action="store_true",
                    help="skip the quick-tier pytest stage")
    ap.add_argument("--skip-bench", action="store_true",
                    help="skip the bench-smoke + sfprof health stage")
    ap.add_argument("--skip-chaos", action="store_true",
                    help="skip the kill/resume chaos-smoke stage")
    ap.add_argument("--skip-overload", action="store_true",
                    help="skip the burst/shed/degrade overload-smoke stage")
    ap.add_argument("--skip-dag", action="store_true",
                    help="skip the SNCB-DAG kill/resume dag-smoke stage")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the stage commands and exit 0")
    args = ap.parse_args(argv)

    with tempfile.TemporaryDirectory(prefix="sft_ci_") as tmpdir:
        ledger = os.path.join(tmpdir, "ledger.json")
        stream = os.path.join(tmpdir, "ledger_stream.jsonl")
        plan = stages(args.changed, args.skip_tests, args.skip_bench,
                      args.skip_chaos, args.skip_overload, args.skip_dag,
                      ledger_path=ledger, stream_path=stream)
        if args.dry_run:
            for name, cmds in plan:
                for cmd in cmds:
                    print(f"[{name}] {' '.join(cmd)}")
            return 0
        for name, cmds in plan:
            for cmd in cmds:
                print(f"== ci stage: {name}: {' '.join(cmd)}", flush=True)
                env = _bench_env(ledger, stream) \
                    if name.startswith("bench") else _cpu_env()
                proc = subprocess.run(cmd, cwd=REPO_ROOT, env=env)
                if proc.returncode != 0:
                    print(f"== ci FAILED at stage {name} "
                          f"(exit {proc.returncode})", flush=True)
                    return proc.returncode
        print("== ci: all stages green", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
