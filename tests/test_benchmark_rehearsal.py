"""Every cell of ``BENCHMARK.json`` at toy size on the CPU backend: the
harness's own ``--rehearsal`` mode, one process a cell, as the driver
starts it on the chip. What it guards is what a ``run_failed`` or an
``outputs_incorrect`` costs there: a change of the program that breaks
an adapter, or a comparison with a plain reference, is seen here first.

Asserted: the run exits 0, its last line is the contract's object, and
the run's own account says that it checked windows against the reference
and found none wrong. NOT asserted: lateness (``late``, and through it
``failed`` / ``correct`` in the paced cells) — six loaded test workers
cannot hold a slide steady, and a timing is no CPU run's to give.

The cells are read at collection, so a later cell counts itself.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    _BENCH = json.load(_f)
CELLS = [w["name"] for w in _BENCH["workloads"]]
#: The cells judged by a latency release on the wall clock, a result a
#: slide (5 s): 6 s leaves the one result of the window a second to land
#: in, 8 s leaves it three. The flood cells need no such room.
PACED = {name for m in _BENCH["end_to_end"]
         if m["name"] == "result_latency_p50_ms"
         for name in m.get("workloads", [])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_and_agrees_with_its_reference(cell):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    p = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"),
         "--workload", cell, "--rehearsal",
         "--seconds", "8" if cell in PACED else "6", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert set(last) >= {"correct", "attempted", "failed", "metrics"}
    (account,) = [ln["detail"] for ln in lines[:-1]
                  if "checked" in ln.get("detail", {})]
    assert account["cell"] == cell
    assert account["checked"] >= 1, account
    assert account["wrong"] == {}, account["wrong"]
