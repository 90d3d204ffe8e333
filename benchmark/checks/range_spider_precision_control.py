"""The precision control of ``range-polygons-10k-spider``: what
``adapter.verify`` says of an answer computed in the nearest precision below
the one the configuration states (float32 on the chip -> bfloat16). It has to
come out not correct, or ``tolerance_deg`` holds a run to nothing.

    python benchmark/checks/range_spider_precision_control.py \
        [--seed N] [--windows K] [--dtype bfloat16|float32] [--rehearsal]

As ``range_polygons_precision_control.py`` does for ``range-polygons-1k``
(whose :func:`control` this reuses): per window the plain reference's own
answer on inputs rounded as the program would round them one precision down
— the mapped points and the zones' vertices centred on the bbox (as
``center_coords`` does before the cast), rounded to bfloat16, everything
after that in float64 — handed to the cell's adapter as ``run_soa``'s yield,
then ``verify`` at the cell's load: 10,000 Spider zones, 1,000,000 Gaussian
points a window. No chip is needed: nothing of the program runs but the
adapter's ``prepare``. ``--dtype float32`` puts the configuration's own
precision through the same path (it has to pass).

Prints one JSON line: ``correct``, ``verify``'s readings
(``max_distance_deviation_deg`` against ``tolerance_deg``,
``points_wrong_outside_band`` against 0) and the first window's complaints.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.checks.range_polygons_precision_control import (  # noqa: E402
    DTYPES,
    control,
)
from benchmark.harness import spec, traffic  # noqa: E402

CELL = "range_spider.flood"


def prepared(seed: int, rehearsal: bool):
    """The cell's adapter on the seed's stream, prepared as a run prepares
    it (positions mapped, zones made), and the reference module."""
    cell = spec.load_cell(CELL)
    stream_cfg = traffic.effective(cell.config["stream"], rehearsal)
    tr = traffic.effective(cell.traffic, rehearsal)
    windows = traffic.Windows(
        int(cell.config["window_s"] * 1000), int(cell.config["slide_s"] * 1000),
        int(cell.config["fire_delay_ms"]), int(stream_cfg["t0_ms"]))
    stream, _w = traffic.build_stream(stream_cfg, tr, windows, seed, 30.0,
                                      False)
    ad = spec.plugin("adapters", cell.config["adapter"]).Adapter(
        cell.config, stream_cfg, "/nonexistent", rehearsal)
    ad.prepare(stream, windows)
    return ad, spec.plugin("references", cell.config["reference"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2**31 + 47)
    ap.add_argument("--windows", type=int, default=1)
    ap.add_argument("--dtype", default="bfloat16", choices=sorted(DTYPES))
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    ad, ref_mod = prepared(args.seed, args.rehearsal)
    out = control(ad, ref_mod, DTYPES[args.dtype], args.windows)
    wrong = out.pop("wrong")
    print(json.dumps({
        "control": args.dtype, "seed": args.seed, "rehearsal": args.rehearsal,
        "correct": not wrong and not out["problems"],
        "tolerance_deg": ad.cfg["tolerance_deg"], **out,
        "first_wrong": next(iter(wrong.values()), [])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
