"""Of the program's own spans whose name starts with ``prefix``, the largest
total as a share (%) of the spans named ``of`` — e.g. the slowest DAG node's
part of the window walk."""


def read(trace, prefix, of):
    totals, whole = {}, 0.0
    for e in trace.spans:
        if e["name"] == of:
            whole += e["dur"]
        elif e["name"].startswith(prefix):
            totals[e["name"]] = totals.get(e["name"], 0.0) + e["dur"]
    if not whole or not totals:
        return None
    return max(totals.values()) / whole * 100.0
