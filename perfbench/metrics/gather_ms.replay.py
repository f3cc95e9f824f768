"""Materialize (models/replay.gather: the fetch and the host assembly of
winners and orders): the program's ``gather`` span, host clock, in ms
per replay of the window."""


def read(rd):
    s = rd["spans"]["spans"].get("gather")
    n = rd["run"].get("replays")
    if not s or not s["count"] or not n or "total_s" not in s:
        return None
    return s["total_s"] * 1e3 / n
