#!/usr/bin/env python3
"""What one tracer span costs, in ns, in each state it can be in:

    python tools/span_cost.py [--reps N]

- ``disabled``: the tracer off (one attribute check, a shared no-op);
- ``enabled``: the tracer on, no profiler capture running (histogram,
  self-time stack, and a ``TraceAnnotation`` that records nothing);
- ``enabled_capturing``: the tracer on while ``jax.profiler`` captures
  with the benchmark's options (host tracer level 1, no Python
  tracer), so the annotation is written into the trace;
- ``annotation`` / ``annotation_capturing``: a bare
  ``jax.profiler.TraceAnnotation``, for comparison;
- ``loop``: the timing loop around a shared no-op context manager,
  which every other number includes.

Each is the best of five timed loops of ``--reps`` empty spans. Prints
one JSON line. The capture is written to a temporary directory inside
the checkout and removed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def per_span_ns(make, reps: int) -> float:
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(reps):
            with make():
                pass
        best = min(best, time.perf_counter() - t0)
    return best / reps * 1e9


def measure(reps: int) -> dict:
    import jax

    from crdt_tpu.obs.tracer import Tracer

    off, on = Tracer(enabled=False), Tracer(enabled=True)
    ann = jax.profiler.TraceAnnotation
    noop = contextlib.nullcontext()
    out = {
        "device": jax.devices()[0].device_kind,
        "reps": reps,
        "loop": per_span_ns(lambda: noop, reps),
        "disabled": per_span_ns(lambda: off.span("tick.route"), reps),
        "enabled": per_span_ns(lambda: on.span("tick.route"), reps),
        "annotation": per_span_ns(lambda: ann("crdt.tick.route"), reps),
    }
    d = tempfile.mkdtemp(prefix="span-cost-", dir=ROOT)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # as the benchmark's traced runs
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["enabled_capturing"] = per_span_ns(
                lambda: on.span("tick.route"), reps)
            out["annotation_capturing"] = per_span_ns(
                lambda: ann("crdt.tick.route"), reps)
        finally:
            jax.profiler.stop_trace()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=100_000)
    args = ap.parse_args(argv)
    print(json.dumps(measure(args.reps)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
