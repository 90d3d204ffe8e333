"""Total length of the program's own spans of the given names inside the
window, per result (ms). ``names``: exact span names, and prefixes ending in
``:`` (``dispatch:`` selects every ``dispatch:<kernel>``). An exact name never
matches by prefix: ``commit`` does not count ``commit.egress``."""


def durations(trace, names):
    """``dur`` (us) of every selected span of ``trace.spans``."""
    exact = {n for n in names if not n.endswith(":")}
    prefixes = tuple(n for n in names if n.endswith(":"))
    return [e["dur"] for e in trace.spans if e["name"] in exact
            or (prefixes and e["name"].startswith(prefixes))]


def read(trace, names):
    durs = durations(trace, names)
    if not durs or not trace.windows:
        return None
    return sum(durs) / trace.windows / 1000.0
