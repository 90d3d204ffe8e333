"""Operations and bytes a kernel's algorithm needs for one call, from the
configuration's shapes — the numerator of a roofline share. A function per
kernel, named in the metric's data file; each returns ``(flops, bytes)``."""

from __future__ import annotations

from typing import Any, Dict, Tuple


def knn_wire_digest(config: Dict[str, Any]) -> Tuple[float, float]:
    """One pane through the wire->digest step (``ops/wire_knn.py``).

    Reads the pane's three uint16 planes (x, y, object id: 6 B a point) and
    writes the digest: per object id a float32 minimum distance and an int32
    representative. Per point: two dequantisations (multiply-add), two
    differences, two squares, a sum, a square root, a comparison with the
    radius — 10 operations. Padding lanes are not needed by the algorithm and
    are not counted. On a v5e the bytes bound it by two orders of magnitude."""
    s = config["stream"]
    pane_points = int(s["event_rate_eps"] * config["slide_s"])
    return 10.0 * pane_points, 6.0 * pane_points + 8.0 * int(s["ids"])
