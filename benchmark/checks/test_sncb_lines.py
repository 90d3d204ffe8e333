"""The SNCB stream's text lines: what the worker processes render is the
one-process expression's output line for line, and a run that stops early
leaves no worker behind. Then the result's account of how much of a bounded
flood the window took, from a rehearsal of each flood cell.

    python -m pytest benchmark/checks -q        (not part of tier-1)
"""

import json
import multiprocessing
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.adapters import sncb_dag  # noqa: E402
from benchmark.harness import spec, traffic  # noqa: E402


def _stream(events, seed):
    """A seeded flood stream of the configuration's own shapes, ``events``
    long past the warm-up (rate and batch at rehearsal size)."""
    cell = spec.load_cell("sncb.flood")
    cfg = cell.config
    stream_cfg = traffic.effective(cfg["stream"], True)
    tr = {**traffic.effective(cell.traffic, True), "stream_eps": events}
    wn = traffic.Windows(int(cfg["window_s"] * 1000),
                         int(cfg["slide_s"] * 1000),
                         int(cfg["fire_delay_ms"]), int(stream_cfg["t0_ms"]))
    stream, w = traffic.build_stream(stream_cfg, tr, wn, seed, 1.0, True)
    return cfg, stream_cfg, wn, stream, w


def _serial(names, stream):
    """The expression ``prepare`` held until PR 31, in one process."""
    ts = stream.ts(0, stream.n_total)
    return [f"{names[d]},{t},{x!r},{y!r}"
            for d, t, x, y in zip(stream.ids.tolist(), ts.tolist(),
                                  stream.x.tolist(), stream.y.tolist())]


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_rendered_in_workers_equals_rendered_in_one_process(seed):
    cfg, stream_cfg, wn, stream, w = _stream(10_000, seed)
    assert stream.n_total == w + 10_000 + 4_000
    ad = sncb_dag.Adapter(cfg, stream_cfg, "/nonexistent", True)
    ad.before_backend(stream, wn)
    try:
        assert len(ad.parts) == sncb_dag.RENDERERS > 1
        lines = ad.rendered()
    finally:
        ad.close()
    assert ad.renderers is None and not ad.parts
    assert not multiprocessing.active_children()
    want = _serial(ad.names, stream)
    assert len(lines) == stream.n_total and lines == want
    name, t, x, y = lines[-1].split(",")
    assert name == ad.names[(stream.n_total - 1) % len(ad.names)]
    assert int(t) == stream.ts(stream.n_total - 1, stream.n_total)[0]
    assert float(x) == stream.x[-1] and float(y) == stream.y[-1]  # exact


def test_a_run_that_stops_early_leaves_no_worker():
    cfg, stream_cfg, wn, stream, _w = _stream(10_000, 5)
    ad = sncb_dag.Adapter(cfg, stream_cfg, "/nonexistent", True)
    before = set(multiprocessing.active_children())
    ad.before_backend(stream, wn)
    workers = set(multiprocessing.active_children()) - before
    assert len(workers) == sncb_dag.RENDERERS
    ad.close()  # what main() does when the platform is refused
    assert all(not p.is_alive() for p in workers)
    ad.close()  # and once more is nothing


@pytest.mark.parametrize("cell", ["sncb.flood", "knn.flood", "join.flood"])
def test_stream_used_share_in_the_result_of_a_rehearsal(cell):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    p = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         cell, "--rehearsal", "--seconds", "6", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    (account,) = [d["detail"] for d in map(json.loads,
                                           p.stdout.strip().splitlines())
                  if "checked" in d.get("detail", {})]
    assert account["checked"] >= 1 and account["wrong"] == {}
    if cell == "sncb.flood":  # a bounded flood: text lines, no pool
        assert 0.0 < account["stream_used_share"] < 1.0
        assert account["stream_used_share"] == account["handed_in_window"] / (
            account["stream_events"] - account["warmup_events"])
    else:  # a pool without an end
        assert "stream_used_share" not in account
        assert account["stream_events"] is None
