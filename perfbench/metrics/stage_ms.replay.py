"""Staging (models/replay.stage: ``native.kernel_columns`` and the delete
set): the program's ``stage`` span, host clock, in ms per replay of the
window."""


def read(rd):
    s = rd["spans"]["spans"].get("stage")
    n = rd["run"].get("replays")
    if not s or not s["count"] or not n or "total_s" not in s:
        return None
    return s["total_s"] * 1e3 / n
