"""One number the adapter hands over under ``key`` (a gauge of the program
at the window's close, e.g. the device bytes of an index). Nothing where the
adapter hands none (a program without that gauge)."""


def read(trace, key):
    value = trace.extras.get(key)
    return None if value is None else float(value)
