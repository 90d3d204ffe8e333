"""Seconds in which an operation ran on the device, per result inside the
window (ms): the union of the ``XLA Ops`` intervals, from the trace."""


def read(trace):
    if trace.device is None or not trace.windows:
        return None
    return trace.device["busy_s"] / trace.windows * 1000.0
