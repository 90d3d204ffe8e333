"""Programs that ran on the device and that no ``instrument_jit`` saw, per
result inside the window: executions on the trace's ``XLA Modules`` line less
the calls the program's own kernel table counted. 0 = every program goes
through the one wrapper; what is left are eager strays and bare jits."""


def read(trace):
    if trace.device is None or not trace.windows \
            or "kernel_calls" not in trace.counters:
        return None
    return (trace.device["module_runs"]
            - trace.counters["kernel_calls"]) / trace.windows
