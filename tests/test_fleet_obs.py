"""Tier-1 guard over the round-19 multi-process tracing leg.

``bench.py --fleet-trace`` spawns three REAL subprocess replicas over
UDP routers under the seeded round-7 fault schedule, with two
children permanently partitioned (their traffic forced through the
rendezvous relay), and asserts the acceptance numbers internally:
100% cross-process path reconstruction, digest convergence, < 5%
trace-context wire overhead, a three-pid merged Perfetto timeline.
Running it here keeps the evidence pipeline live in every tier-1 run
— and, via ``BENCH_FLEET_ARTIFACT``, produces the observability
artifact CI uploads (same pattern as ``BENCH_SMOKE_ARTIFACT``).
"""

import json
import os
import pathlib
import subprocess
import sys


def test_fleet_trace_leg(tmp_path):
    art = (pathlib.Path(os.environ["BENCH_FLEET_ARTIFACT"])
           if os.environ.get("BENCH_FLEET_ARTIFACT")
           else tmp_path / "fleet_trace.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_FLEET_OUT"] = str(art)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--fleet-trace"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["ok"] is True
    ft = out["fleet_trace"]
    # the acceptance numbers, re-asserted on the emitted evidence
    # (the leg's own asserts are the gate; this pins the SHAPE)
    assert ft["procs"] == 3
    assert ft["pair_rate"] == 1.0
    assert ft["traced_recvs"] > 0
    assert ft["converged"] is True
    assert ft["wire_overhead_ratio"] < 0.05
    assert ft["relay_frames_forwarded"] > 0
    for route in ("direct", "relayed", "sync_answer"):
        assert ft["routes"].get(route, 0) > 0, route
    # multi-hop deliveries really happened (the relay incrementer)
    assert ft["hops"].get("2", 0) > 0
    # the artifact CI uploads carries the full evidence
    full = json.loads(art.read_text())
    assert full["fleet_trace"]["pair_rate"] == 1.0
    assert len(full["perfetto_pids"]) >= 3
    assert full["latency"]["paths"]["pair_rate"] == 1.0
    assert full["latency"]["routes"]  # per-route leg percentiles
