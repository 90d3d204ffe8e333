"""1 - busy / window, in %, from the trace (the driver works the same share
out of ``device.busy_s`` and ``device.window_s``)."""


def read(trace):
    if trace.device is None:
        return None
    return (1.0 - trace.device["busy_s"] / trace.device["window_s"]) * 100.0
