"""One of the program's counters over the window, per event handed over.
(Telemetry counts h2d bytes only at ``operators/base.py:ship``; arrays that
reach the device through a bare ``jnp.asarray`` are not in it.)"""


def read(trace, counter):
    if not trace.events or counter not in trace.counters:
        return None
    return trace.counters[counter] / trace.events
