"""Tier-1 guard over the multi-process tracing leg.

``tests/fleet_procs.py fleet-trace`` starts three REAL replica
processes over UDP routers under the seeded round-7 fault schedule,
with two children permanently partitioned (their traffic forced
through the rendezvous relay), and asserts the acceptance numbers
itself: 100% cross-process path reconstruction, digest convergence,
< 5% trace-context wire overhead, a three-pid merged Perfetto
timeline, federated control advice.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def test_fleet_trace_leg():
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "fleet_procs.py"),
         "fleet-trace"],
        capture_output=True, text=True, timeout=400,
        cwd=os.path.dirname(HERE),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    ft = json.loads(proc.stdout.strip().splitlines()[-1])
    # the leg's own asserts are the gate; this pins the result's shape
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
    assert len(ft["perfetto_pids"]) >= 3
    assert ft["latency"]["paths"]["pair_rate"] == 1.0
    assert ft["latency"]["routes"]  # per-route leg percentiles
