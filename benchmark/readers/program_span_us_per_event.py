"""Total length of the program's own spans of the given names inside the
window, per event handed over (us). ``names`` as in
``program_span_ms_per_window``."""

from benchmark.readers.program_span_ms_per_window import durations


def read(trace, names):
    durs = durations(trace, names)
    if not durs or not trace.events:
        return None
    return sum(durs) / trace.events
