"""Read path (the resident engine's lazy materialize on ``cache``): the
program's ``engine.flush`` span, host clock, in ms per tick of the
window."""


def read(rd):
    s = rd["spans"]["spans"].get("engine.flush")
    n = rd["run"].get("ticks")
    if not s or not s["count"] or not n or "total_s" not in s:
        return None
    return s["total_s"] * 1e3 / n
