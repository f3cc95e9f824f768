"""Server (models/multidoc.tick outside every phase: the per-tick scans
and gauges): the self time of the program's ``tick`` span (its total
less its child spans), host clock, in ms per tick of the window."""


def read(rd):
    s = rd["spans"]["spans"].get("tick")
    n = rd["run"].get("ticks")
    if not s or not s["count"] or not n or "self_s" not in s:
        return None
    return s["self_s"] * 1e3 / n
