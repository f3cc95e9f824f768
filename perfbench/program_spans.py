#!/usr/bin/env python3
"""The program's own spans beside a benchmark run:

    python3 perfbench/program_spans.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ``perfbench/run.py``'s run unchanged and adds to what it reports:

- one stderr line, ``program spans:``, with the window's count, total,
  self time and max of every ``tick*``, ``engine.*``, ``decode``,
  ``stage``, ``gather`` and ``compact`` span of the program's tracer;
- in a traced run, ``idle_by_program`` in the profile summary and its
  top ten as ``idle_gaps_program`` in the result's breakdown: each idle
  gap of the device goes to the innermost ``crdt.*`` host span open at
  the gap's middle (the program's tracer spans, which open a profiler
  annotation of that name), else to the innermost ``bench.*`` span,
  else to ``other``; and ``program_cover``, for each ``bench.*`` span,
  the share of its time and of the device idle inside it that some
  ``crdt.*`` span covers.

The result line (the last line of stdout) is ``run.py``'s, with those
keys added; every key ``run.py`` gives keeps its value.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import profile  # noqa: E402
from perfbench.common import say  # noqa: E402

PROGRAM_PREFIX = "crdt."
SHOWN = ("tick", "engine.", "decode", "stage", "gather", "compact")


def _host_lines(pd, prefix: str) -> List[List[Tuple[float, float, str]]]:
    """Every ``prefix`` span of the host planes, one list per thread."""
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            spans = [(a, b, n) for n, a, b in profile._events(ln)
                     if n.startswith(prefix)]
            if spans:
                out.append(spans)
    return out


def _innermost(spans: List[Tuple[float, float, str]],
               points: List[float]) -> List[Optional[Tuple[float, float, str]]]:
    """For each point (ascending), the latest-starting span that holds
    it (the shorter of two that start together), or None: a sweep over
    the spans by start, with the spans still open kept in a heap keyed
    on their start."""
    spans = sorted(spans)
    heap: list = []
    out = []
    i = 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            a, b, n = spans[i]
            heapq.heappush(heap, (-a, b, n))
            i += 1
        while heap and heap[0][1] < t:
            heapq.heappop(heap)
        out.append((-heap[0][0], heap[0][1], heap[0][2]) if heap else None)
    return out


def _gaps(pd, window) -> List[Tuple[float, float]]:
    """The device idle gaps inside the window, as ``profile.reduce`` cuts
    them: per chip, the complement of the union of op intervals."""
    w0, w1 = window
    gaps = []
    for p in profile._device_planes(pd):
        lines = {ln.name: ln for ln in p.lines}
        iv = []
        for _, a, b in profile._events(lines[profile.OPS_LINE]):
            a, b = max(a, w0), min(b, w1)
            if b > a:
                iv.append((a, b))
        busy = profile._union(iv)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    return gaps


def _window(pd) -> Tuple[float, float]:
    wins = [(a, b) for line in _host_lines(pd, profile.WINDOW_SPAN)
            for a, b, n in line if n == profile.WINDOW_SPAN]
    if not wins:
        raise profile.BenchError(f"no {profile.WINDOW_SPAN} span in the trace")
    return min(a for a, _ in wins), max(b for _, b in wins)


def _covered(union: List[Tuple[float, float]], ends: List[float],
             a: float, b: float) -> float:
    """Length of [a, b] that ``union`` (disjoint, sorted, with
    ``ends`` its interval ends) covers."""
    total = 0.0
    for x, y in union[bisect.bisect_right(ends, a):]:
        if x >= b:
            break
        total += min(y, b) - max(x, a)
    return total


def program_idle(pd, window=None) -> dict:
    """``idle_by_program`` (seconds per span name), ``idle_gaps_program``
    (its top ten) and ``program_cover`` for one trace."""
    if window is None:
        window = _window(pd)
    gaps = sorted(_gaps(pd, window), key=lambda g: g[0] + g[1])
    mids = [(a + b) / 2 for a, b in gaps]
    prog = [s for line in _host_lines(pd, PROGRAM_PREFIX) for s in line]
    bench = [s for line in _host_lines(pd, profile.HOST_PREFIX) for s in line
             if s[2] != profile.WINDOW_SPAN]
    in_prog = _innermost(prog, mids)
    in_bench = _innermost(bench, mids)
    by: Dict[str, float] = defaultdict(float)
    idle_in: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    for (a, b), p, h in zip(gaps, in_prog, in_bench):
        dt = (b - a) * 1e-9
        by[p[2] if p else h[2] if h else "other"] += dt
        if h:
            idle_in[h[2]][0] += dt
            idle_in[h[2]][1] += dt if p else 0.0
    # host time of each bench.* span that program spans cover
    w0, w1 = window
    union = profile._union([(a, b) for a, b, _ in prog])
    ends = [b for _, b in union]
    span_in: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    for a, b, n in bench:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            span_in[n][0] += (b - a) * 1e-9
            span_in[n][1] += _covered(union, ends, a, b) * 1e-9
    cover = {n: {"time_s": t, "time_covered": c / t if t else 0.0,
                 "idle_s": idle_in[n][0],
                 "idle_covered": (idle_in[n][1] / idle_in[n][0]
                                  if idle_in[n][0] else 0.0)}
             for n, (t, c) in sorted(span_in.items())}
    top = sorted(by.items(), key=lambda kv: -kv[1])[:profile.TOP]
    return {"idle_by_program": dict(by),
            "idle_gaps_program": [[k, v] for k, v in top],
            "program_cover": cover}


def span_line(report: dict) -> str:
    """The window's program spans named in ``SHOWN``: count, total,
    self time and max, in ms."""
    rows = {}
    for name, s in sorted(report["spans"].items()):
        if name.startswith(SHOWN):
            rows[name] = {"count": s["count"],
                          "total_ms": s["total_s"] * 1e3,
                          "self_ms": s.get("self_s", s["total_s"]) * 1e3,
                          "max_ms": s["max_s"] * 1e3}
    return f"program spans: {json.dumps(rows, sort_keys=True)}"


def load_run():
    """``perfbench/run.py`` as a module, as the harness tests load it."""
    import importlib.util

    sp = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(HERE, "run.py"))
    run = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(run)
    return run


_REDUCE_DIR = profile.reduce_dir


def reduce_dir(d: str, device_kind: str) -> dict:
    """``profile.reduce_dir`` with the program's idle attribution added:
    ``idle_by_program`` and ``program_cover`` in the summary,
    ``idle_gaps_program`` in its breakdown. Every other key is
    ``profile.reduce_dir``'s own."""
    out = _REDUCE_DIR(d, device_kind)
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    extra = program_idle(ProfileData.from_file(
        max(found, key=os.path.getmtime)))
    out["idle_by_program"] = extra["idle_by_program"]
    out["program_cover"] = extra["program_cover"]
    out["breakdown"]["idle_gaps_program"] = extra["idle_gaps_program"]
    return out


def execute(run, args, require_tpu: bool = True) -> dict:
    """``run.execute(args)`` with the window's tracer report kept (its
    span line said on stderr) and, in a traced run, the program's idle
    attribution added to the profile and the breakdown."""
    seen: dict = {}
    real_guard = run.guard_counters

    def guard_counters(tracer):  # called as the window closes
        seen["report"] = tracer.report()
        return real_guard(tracer)

    def reduce_dir_kept(d, device_kind):
        seen["profile"] = reduce_dir(d, device_kind)
        return seen["profile"]

    run.guard_counters = guard_counters
    profile.reduce_dir = reduce_dir_kept
    try:
        out = run.execute(args, require_tpu=require_tpu)
    finally:
        run.guard_counters = real_guard
        profile.reduce_dir = _REDUCE_DIR
    say(span_line(seen["report"]))
    if "profile" in seen:
        say(f"idle gaps by program span: "
            f"{json.dumps(out['breakdown']['idle_gaps_program'])}")
        say(f"program cover: {json.dumps(seen['profile']['program_cover'])}")
        out["program_cover"] = seen["profile"]["program_cover"]
    return out


def main(argv=None) -> int:
    run = load_run()
    args = run.parse(argv)
    try:
        out = execute(run, args)
    except run.BenchError as e:
        say(f"perfbench: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
