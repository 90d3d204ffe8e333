"""A sum of the run's counters over another (both over the window). A name is
looked up in the program's counters the harness takes (``trace.counters``:
``d2h_bytes``, ...), then in what the adapter hands over (``trace.extras``:
``join.pairs``, ...); ``results`` is the number of results inside the window.
Nothing where a name is not there (a program without that counter) or the
denominator is 0."""


def _total(trace, names):
    total = 0.0
    for name in names:
        if name == "results":
            value = trace.windows
        else:
            value = trace.counters.get(name, trace.extras.get(name))
        if value is None:
            return None
        total += value
    return total


def read(trace, num, den):
    top, bottom = _total(trace, num), _total(trace, den)
    if top is None or not bottom:
        return None
    return top / bottom
