"""Median of a list the adapter hands over under ``key`` (e.g. the size of
each published checkpoint)."""

import statistics


def read(trace, key):
    values = trace.extras.get(key)
    if not values:
        return None
    return float(statistics.median(values))
