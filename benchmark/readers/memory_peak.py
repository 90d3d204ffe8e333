"""``peak_bytes_in_use`` of the fullest device, from ``memory_stats()``."""


def read(trace):
    return trace.memory_peak_bytes
