"""Host seconds under one name of the host's timeline, per event handed over
inside the window (us). The names are the harness's: generate, ingest, window,
commit. Over the names they add up to the wall time per event."""


def read(trace, span):
    seconds = sum(d for n, _s, d in trace.host if n == span)
    if not seconds or not trace.events:
        return None
    return seconds / trace.events * 1e6
