"""The precision control of ``join-tdrive-2x100k-skew``: what
``adapter.verify`` says of an answer computed in the nearest precision below
the one the configuration states (float32 on the chip -> bfloat16). It has to
come out not correct, or ``tolerance_deg`` holds a run to nothing.

    python benchmark/checks/join_skew_precision_control.py \
        [--seed N] [--windows K] [--dtype bfloat16|float32] [--rehearsal]

The control stands in the program's place: per window the plain reference's
own answer on inputs rounded as the program would round them one precision
down — both sides' mapped points centred on the bbox (as ``center_coords``
does before the cast), rounded to bfloat16, everything after that in float64
— handed to the cell's adapter as ``run_soa``'s yield (the pairs whose
rounded distance is within r, that distance as float32, -1 / inf past the
count in the count's padding bucket), then ``verify`` at the cell's load. No
chip is needed: nothing of the program runs but the adapter's ``prepare``.
``--dtype float32`` puts the configuration's own precision through the same
path (it has to pass).

Prints one JSON line: ``correct``, ``verify``'s readings and the first
window's complaints.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.adapters.join_soa import Demux  # noqa: E402
from benchmark.checks.range_polygons_precision_control import (  # noqa: E402
    DTYPES,
    rounded,
)
from benchmark.harness import spec, traffic  # noqa: E402

CELL = "join_skew.flood"


def prepared(seed: int, rehearsal: bool):
    """The cell's adapter on the seed's stream, prepared as a run prepares
    it (positions mapped), and the reference module."""
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


def control(ad, ref_mod, dtype, n_windows: int):
    """``ad.verify``'s verdict on ``n_windows`` windows answered in
    ``dtype``."""
    cfg = ad.cfg
    min_x, min_y, max_x, max_y = ad.stream_cfg["bbox"]
    cx, cy = (min_x + max_x) / 2.0, (min_y + max_y) / 2.0
    low = ref_mod.Reference(
        bbox=ad.stream_cfg["bbox"], grid_cells=ad.grid_cells,
        radius=float(cfg["radius"]), tol=0.0)
    per_window = int(ad.stream.rate_eps * ad.windows.size_ms // 1000)
    got = []
    for k in range(n_windows):
        win = ad._chunk(k * per_window, (k + 1) * per_window)
        a, b = slice(0, None, 2), slice(1, None, 2)  # event 0 is even: A's
        li, ri, d = low.pairs(
            rounded(win["x"][a], cx, dtype), rounded(win["y"][a], cy, dtype),
            rounded(win["x"][b], cx, dtype), rounded(win["y"][b], cy, dtype))
        n = len(li)
        bucket = 1 << max(8, int(n - 1).bit_length())
        pad = lambda v, fill, t: np.concatenate(
            [v.astype(t), np.full(bucket - n, fill, t)])
        got.append((ad.windows.end(k), pad(li, -1, np.int32),
                    pad(ri, -1, np.int32), pad(d, np.inf, np.float32), n, 0))
    ad.got = got
    ad.demux = Demux(iter(()), None)  # verify reports what the feed handed
    return ad.verify(None)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2**31 + 41)
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
