"""Server (models/multidoc.tick, the route phase: the delta splices and
promotions): the program's ``tick.route`` span, host clock, in ms per
tick of the window."""


def read(rd):
    s = rd["spans"]["spans"].get("tick.route")
    n = rd["run"].get("ticks")
    if not s or not s["count"] or not n or "total_s" not in s:
        return None
    return s["total_s"] * 1e3 / n
