"""Median length of the host's spans of one name inside the window (ms)."""

import statistics


def read(trace, span):
    lengths = [d for n, _s, d in trace.host if n == span]
    if not lengths:
        return None
    return statistics.median(lengths) * 1000.0
