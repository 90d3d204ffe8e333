"""Longest a released segment waited to be pulled (ms), paced feeds only.

Measured on each segment's last event, the one whose due time releases the
segment; the job is one thread, so the wait rises by one window's work at each
fire and has to drain before the next."""


def read(trace):
    waited = [p[3] for p in trace.feed.pulls]
    if not (trace.feed.paced and waited):
        return None
    return max(waited) * 1000.0
