"""A device program's share of its roofline (%): the least time the chip could
take for its executions — the larger of operations / peak FLOP/s and bytes /
peak bytes/s, both from the configuration's shapes (``harness/roofline.py``) —
over the time the trace shows for them on the ``XLA Modules`` line.

The program is found by an operation it holds (``op``: the start of the
operation's name, e.g. the Pallas kernel's), because a step jitted from a
``functools.partial`` reaches the trace as ``jit__unknown``."""

from benchmark.harness import roofline


def read(trace, op, cost):
    if trace.device is None or trace.peaks is None:
        return None
    runs = seconds = 0
    for prog in trace.device["programs"].values():
        if any(name.startswith(op) for name in prog["ops"]):
            runs += prog["runs"]
            seconds += prog["seconds"]
    if not runs or not seconds:
        return None
    flops, nbytes = getattr(roofline, cost)(trace.cell.config)
    least = max(flops / trace.peaks["bf16_flops_per_s"],
                nbytes / trace.peaks["hbm_bytes_per_s"])
    return runs * least / seconds * 100.0
