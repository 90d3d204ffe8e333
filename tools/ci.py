"""THE pre-commit gate: ``python -m tools.ci`` (repo root).

One shot, five stages, fail-fast, distinct banners:

1. **sfcheck** — the whole-program static analyzer (all fourteen
   passes; ``--changed`` passes the incremental flag through for the
   sub-second path);
2. **quick-tier pytest** — ``pytest tests/ -m 'not slow'`` on CPU
   (``JAX_PLATFORMS=cpu`` in every stage — the gate never takes the
   chip);
3. **chaos smoke** — ``python -m spatialflink_tpu.driver
   --chaos-smoke``: a toy driver pipeline killed mid-run by an armed
   ``abort`` fault (``os._exit(137)``, the SIGKILL analog) and resumed
   from its checkpoint — the concatenated exactly-once egress must be
   byte-identical to a clean run;
4. **overload smoke** — ``python -m spatialflink_tpu.overload
   --smoke``: a toy burst past a tiny admission budget must shed
   deterministically, step the degradation ladder down AND back up,
   carry the shed/degradation budgets through the SLO verdict
   (including the per-tenant-class budgets), and seal every overload
   transition in the ledger stream;
5. **dag smoke** — ``python -m spatialflink_tpu.dag --smoke``: the
   7-node SNCB DAG (Q1–Q5 + StayTime + qserve on one source/interner/
   window clock) under an armed overload policy, killed by an
   ``abort`` fault BETWEEN two sink commits of the atomic unit
   checkpoint, resumed — every node's exactly-once egress must be
   byte-identical to the clean run's.

Exit code: the first failing stage's (sfcheck keeps its 0/1/2/3
contract; pytest its own). ``--skip-tests`` / ``--skip-chaos`` /
``--skip-overload`` / ``--skip-dag`` trim stages for quick iteration;
``--dry-run`` prints the stage commands without running anything
(pinned by tests/test_ci.py).
"""

from __future__ import annotations

import argparse
import functools
import os
import subprocess
import sys
from typing import Dict, List, Tuple

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
    # overload policies, standing-query serving configs):
    # any of them left over from test iteration would sabotage a
    # healthy gate run with injected behavior. The list is DERIVED from
    # the registry (spatialflink_tpu/envvars.py), so the next armed
    # var registered there is scrubbed here automatically — sfcheck's
    # env-registry pass pins this derivation.
    for var in _envvars_registry().gate_scrub_vars():
        env.pop(var, None)
    return env


def stages(changed: bool, skip_tests: bool,
           skip_chaos: bool = False,
           skip_overload: bool = False,
           skip_dag: bool = False) \
        -> List[Tuple[str, List[str]]]:
    """(name, argv) per stage, in order."""
    py = sys.executable
    out: List[Tuple[str, List[str]]] = []
    sfcheck = [py, "-m", "tools.sfcheck"]
    if changed:
        sfcheck.append("--changed")
    if os.environ.get("GITHUB_ACTIONS"):
        # Under Actions the findings double as PR diff annotations
        # (::error workflow commands); exit codes are format-invariant.
        sfcheck.append("--format=github")
    out.append(("sfcheck", sfcheck))
    if not skip_tests:
        out.append(("pytest-quick", [
            py, "-m", "pytest", "tests/", "-q", "-m", "not slow",
            "-p", "no:cacheprovider",
        ]))
    if not skip_chaos:
        # Chaos smoke: one kill (armed abort fault = SIGKILL analog) →
        # resume round trip on toy shapes, asserting byte-identical
        # exactly-once egress (spatialflink_tpu/driver.py). CPU-only.
        out.append(("chaos-smoke", [
            py, "-m", "spatialflink_tpu.driver", "--chaos-smoke"]))
    if not skip_overload:
        # Overload smoke: burst → shed → degrade → recover round trip
        # on toy shapes (spatialflink_tpu/overload.py) — sheds counted,
        # ladder stepped both ways, budgets in the SLO verdict, every
        # transition sealed in the ledger stream. CPU-only too.
        out.append(("overload-smoke", [
            py, "-m", "spatialflink_tpu.overload", "--smoke"]))
    if not skip_dag:
        # DAG smoke: the 7-node SNCB pipeline under an armed overload
        # policy, killed BETWEEN two sink commits of the atomic unit
        # checkpoint, resumed — byte-identical egress on every node's
        # sink (spatialflink_tpu/dag.py). CPU-only too.
        out.append(("dag-smoke", [
            py, "-m", "spatialflink_tpu.dag", "--smoke"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.ci",
        description="pre-commit gate: sfcheck → quick pytest → "
                    "chaos / overload / DAG smokes",
    )
    ap.add_argument("--changed", action="store_true",
                    help="incremental sfcheck (--changed cache mode)")
    ap.add_argument("--skip-tests", action="store_true",
                    help="skip the quick-tier pytest stage")
    ap.add_argument("--skip-chaos", action="store_true",
                    help="skip the kill/resume chaos-smoke stage")
    ap.add_argument("--skip-overload", action="store_true",
                    help="skip the burst/shed/degrade overload-smoke stage")
    ap.add_argument("--skip-dag", action="store_true",
                    help="skip the SNCB-DAG kill/resume dag-smoke stage")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the stage commands and exit 0")
    args = ap.parse_args(argv)

    plan = stages(args.changed, args.skip_tests, args.skip_chaos,
                  args.skip_overload, args.skip_dag)
    if args.dry_run:
        for name, cmd in plan:
            print(f"[{name}] {' '.join(cmd)}")
        return 0
    env = _cpu_env()
    for name, cmd in plan:
        print(f"== ci stage: {name}: {' '.join(cmd)}", flush=True)
        proc = subprocess.run(cmd, cwd=REPO_ROOT, env=env)
        if proc.returncode != 0:
            print(f"== ci FAILED at stage {name} "
                  f"(exit {proc.returncode})", flush=True)
            return proc.returncode
    print("== ci: all stages green", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
