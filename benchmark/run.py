"""One cell, one run: the command ``BENCHMARK.json`` names.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses any platform but ``tpu`` (exit 2, no result line). ``--rehearsal``
is the harness's own flag, never given by the driver: toy sizes on the
CPU backend with Pallas interpreted, the same paths and the same last
line, every timing printed as ``null``. ``--workload all`` (rehearsal
only) runs every cell, each in a process of its own.
"""

import time

T_PROCESS_START = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=T_PROCESS_START))
