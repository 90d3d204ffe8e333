"""Median length of the program's own spans of one exact ``name`` inside the
window (ms)."""

import statistics

from benchmark.readers.program_span_ms_per_window import durations


def read(trace, name):
    durs = durations(trace, [name])
    if not durs:
        return None
    return statistics.median(durs) / 1000.0
