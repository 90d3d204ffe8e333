"""Program executions on the device (events of the trace's ``XLA Modules``
line) per result inside the window: every dispatch, instrumented or not."""


def read(trace):
    if trace.device is None or not trace.windows:
        return None
    return trace.device["module_runs"] / trace.windows
