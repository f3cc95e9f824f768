#!/usr/bin/env python
"""Fleet checks too heavy for a module test, run by the tests:

    python tests/fleet_procs.py fleet-trace   # tests/test_fleet_obs.py
    python tests/fleet_procs.py rebalance     # tests/test_fleet_migration.py

Each starts three real OS processes (this script again, as
``fleet-trace-child`` / ``rebalance-child``) on loopback UDP and
prints one JSON line, the last line of stdout: ``fleet-trace`` asserts
its acceptance conditions itself, ``rebalance`` reports what each
server's done file says and the test asserts on it. Every process is
CPU-only: the checks never touch a device.

Ports are taken free by the parent and bound again by the children;
a child that finds its port taken exits with ``PORT_TAKEN``, and the
parent starts all three again on fresh ports (``ATTEMPTS`` in all).

:func:`rebalance_leg` is the placement loop's A/B on an in-process
fabric, which ``tests/test_fleet_migration.py`` imports.
"""

from __future__ import annotations

import errno
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

HERE = os.path.abspath(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
PORT_TAKEN = 5
ATTEMPTS = 3


def _port_taken(exc: OSError) -> bool:
    return (exc.errno == errno.EADDRINUSE
            or os.strerror(errno.EADDRINUSE) in str(exc))


def _free_ports(n: int, *, udp: bool) -> list:
    """n distinct free ports (bind, then release: the children bind
    them again, and one may be taken in between; see PORT_TAKEN)."""
    import socket

    socks, ports = [], []
    for _ in range(n):
        s = socket.socket(
            socket.AF_INET,
            socket.SOCK_DGRAM if udp else socket.SOCK_STREAM,
        )
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _start(mode: str, cfgs: list) -> list:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return [subprocess.Popen(
        [sys.executable, HERE, mode, json.dumps(cfg)],
        env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    ) for cfg in cfgs]


def _wait_done(procs: list, done_paths: list, timeout_s: float) -> bool:
    """True once every child wrote its done file; False when one found
    its port taken. Raises, with the children's stderr, on any other
    exit or on the deadline."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if all(os.path.exists(p) for p in done_paths):
            return True
        codes = [p.poll() for p in procs]
        if PORT_TAKEN in codes:
            for p in procs:
                p.kill()
            return False
        if any(c not in (None, 0) for c in codes):
            break
        time.sleep(0.05)
    for p in procs:
        p.kill()
    tails = [p.communicate()[1][-800:] for p in procs]
    raise RuntimeError(f"children incomplete: codes "
                       f"{[p.returncode for p in procs]} stderr={tails}")


def _stop(procs: list, outdir: str) -> None:
    with open(os.path.join(outdir, "stop"), "w") as f:
        f.write("done")
    for p in procs:
        try:
            p.wait(timeout=20)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
        p.stdout.close()
        p.stderr.close()


# ---------------------------------------------------------------------------
# fleet-trace: three replicas over UDP under faults, a live collector
# ---------------------------------------------------------------------------


def fleet_trace_child(argv) -> int:
    """One replica: a real UdpRouter peer under the seeded round-7
    fault schedule, tracing and recording on, serving its obs surfaces
    over HTTP while it edits and converges. Children 1 and 2 are
    partitioned from each other at the router seam for good, so their
    traffic crosses only through the rendezvous relay: the forced
    multi-hop path whose full reconstruction the parent asserts."""
    cfg = json.loads(argv[0])
    idx = int(cfg["idx"])
    ports = cfg["ports"]
    outdir = cfg["outdir"]
    K = int(cfg["ops"])
    val_bytes = int(cfg["val_bytes"])

    from crdt_tpu.net.faults import (
        FaultSchedule,
        Partition,
        install_faults,
    )
    from crdt_tpu.net.replica import Replica
    from crdt_tpu.net.udp_router import UdpRouter
    from crdt_tpu.obs import (
        FlightRecorder,
        ObsHTTPServer,
        PropagationLedger,
        TickTimeline,
        Tracer,
        get_propagation,
        get_timeline,
        set_propagation,
        set_recorder,
        set_timeline,
        set_tracer,
        state_digest,
    )

    tracer = set_tracer(Tracer(enabled=True))
    set_recorder(FlightRecorder(enabled=True, capacity=16384))
    set_propagation(PropagationLedger())
    set_timeline(TickTimeline(enabled=True))

    try:
        router = UdpRouter(
            port=int(ports[idx]),
            seed=bytes([int(cfg["seed"]) % 200 + 1 + idx]) * 32,
            rendezvous=(idx == 0),
            bootstrap=([] if idx == 0
                       else [("127.0.0.1", int(ports[0]))]),
            relay_after_s=0.25,
            dial_retry_s=0.1,
            dial_retry_max_s=0.5,
            # under the fault schedule a dropped (one-shot, relayed)
            # announce is repaired on the next ttl/3, not after 20 s
            announce_ttl=1.0,
        )
    except OSError as exc:
        if _port_taken(exc):
            return PORT_TAKEN
        raise
    part = None
    if idx in (1, 2):
        # children 1 and 2 never hear each other directly: the
        # introduction dial escalates to the rendezvous relay
        part = Partition({int(ports[1])}, {int(ports[2])})
    install_faults(router, FaultSchedule(
        int(cfg["seed"]), drop=float(cfg["drop"]), duplicate=0.02,
        delay=float(cfg["delay"]), delay_polls=(1, 3),
        partition=part,
    ))
    rep = Replica(router, topic="fleet", client_id=101 + idx,
                  anti_entropy_s=0.2, batch_incoming=True)
    # child 1 carries a live control plane: a seeded flood drives a
    # budget squeeze whose placement-advice row the parent's collector
    # must surface at /fleet within one scrape (the other children
    # have none: the collector's /control fetch must take the 404)
    ctrl = None
    if idx == 1:
        from crdt_tpu.obs import Controller

        ctrl = Controller(cooldown_ticks=2)
        for ct in range(4):
            ctrl.observe({
                "tick": ct,
                "budget": {"max_bytes": 2048, "max_updates": 4},
                "tenants": {"flood!": {
                    "burn": 1.0, "shed": 8 * (ct + 1),
                    "pending_bytes": 4096,
                }},
            })
        assert ctrl.advice(), "fleet-trace child: no advice"
    try:
        obs = ObsHTTPServer(port=int(cfg["obs_ports"][idx]),
                            snapshot_extra=lambda: {
                                "propagation": get_propagation().report(),
                            },
                            control=ctrl).start()
    except OSError as exc:
        if _port_taken(exc):
            router.close()
            return PORT_TAKEN
        raise

    def pump_for(seconds: float) -> None:
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            router.poll()
            time.sleep(0.002)

    # phase 1: join. The bootstrap hello is a one-shot the fault
    # schedule can eat, so it is re-dialed until the router hears
    # anyone (the reference re-dials its bootstrap DHT too)
    deadline = time.monotonic() + 30.0
    next_redial = time.monotonic() + 1.0
    while time.monotonic() < deadline:
        router.poll()
        if len(router.peers_on("fleet")) >= 2 and rep.synced:
            break
        now = time.monotonic()
        if idx != 0 and not router.peers and now >= next_redial:
            router.add_peer("127.0.0.1", int(ports[0]))
            next_redial = now + 1.0
        time.sleep(0.002)
    else:
        print(json.dumps({"child": idx, "error": "join timeout",
                          "peers": router.peers_on("fleet")}),
              file=sys.stderr)
        return 3

    # phase 2: seeded edits, one tick-timeline record per op window
    # (the merged Perfetto timeline's per-process phases)
    rng = np.random.default_rng(int(cfg["seed"]) * 31 + idx)
    tl = get_timeline()
    for j in range(K):
        tl.tick_begin(j, label=f"ops[{idx}]")
        with tl.phase("edit"):
            payload = "".join(
                chr(97 + int(c)) for c in rng.integers(0, 26,
                                                       val_bytes)
            )
            rep.set("m", f"{idx}:{j}", payload)
        with tl.phase("pump"):
            pump_for(0.02)
        tl.tick_end()

    # phase 3: converge, every client's K ops visible everywhere
    # (drops and the partition are repaired by probe retries, the AE
    # cadence and the relay path)
    cids = [101, 102, 103]
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        router.poll()
        sv = rep.doc.state_vector()
        if all(sv.get(c) >= K for c in cids):
            break
        time.sleep(0.002)
    else:
        print(json.dumps({
            "child": idx, "error": "converge timeout",
            "sv": {c: rep.doc.state_vector().get(c) for c in cids},
        }), file=sys.stderr)
        return 4
    # settle: stop originating repair frames (the AE cadence would
    # mint traced frames forever, and the parent's scrape could race
    # one in flight toward a peer it scrapes later), then drain what
    # is in flight; held delays release within a few polls
    rep._next_ae_at = None
    rep._resync_at = None
    pump_for(0.5)
    rep.flush_incoming()

    done = {
        "idx": idx,
        "digest": state_digest(rep.doc),
        "ledger": get_propagation().report(),
        "relay": {k: v for k, v in router.stats.items()
                  if k.startswith("relay")},
        "counters": tracer.report()["counters"],
    }
    done_path = os.path.join(outdir, f"done_{idx}.json")
    with open(done_path + ".tmp", "w") as f:
        json.dump(done, f)
    os.replace(done_path + ".tmp", done_path)

    # phase 4: stay scrapeable until the parent's live collector pass
    # is over (the stop file), then exit clean
    stop = os.path.join(outdir, "stop")
    deadline = time.monotonic() + 60.0
    while not os.path.exists(stop) and time.monotonic() < deadline:
        router.poll()
        time.sleep(0.01)
    obs.stop()
    router.close()
    return 0


def fleet_trace(seed: int = 7, ops: int = 10, val_bytes: int = 1024,
                drop: float = 0.04, delay: float = 0.08) -> dict:
    """Three replicas gossip over real UDP routers under a round-7
    fault schedule (drops, dups, delays), children 1 and 2 relayed
    through the rendezvous; a live FleetCollector scrapes their
    ObsHTTPServers mid-run. Asserted here:

    - every traced receive's full path reconstructs across processes
      (``pair_rate == 1.0``), with direct, relayed and sync_answer
      legs present;
    - all three documents converge to one digest despite the faults;
    - the trace-context wire overhead stays under 5% of traced update
      bytes;
    - the merged Perfetto timeline carries the three processes under
      distinct pids;
    - child 1's control advice is federated at /fleet."""
    from crdt_tpu.obs import FleetCollector

    n_procs = 3
    for _attempt in range(ATTEMPTS):
        with tempfile.TemporaryDirectory() as outdir:
            ports = _free_ports(n_procs, udp=True)
            obs_ports = _free_ports(n_procs, udp=False)
            procs = _start("fleet-trace-child", [{
                "idx": idx, "seed": seed, "ports": ports,
                "obs_ports": obs_ports, "outdir": outdir,
                "ops": ops, "val_bytes": val_bytes,
                "drop": drop, "delay": delay,
            } for idx in range(n_procs)])
            done_paths = [os.path.join(outdir, f"done_{i}.json")
                          for i in range(n_procs)]
            try:
                if not _wait_done(procs, done_paths, 150.0):
                    continue
                # the live half: the children still poll and serve
                col = FleetCollector(events_limit=16384)
                for idx in range(n_procs):
                    col.add_proc(
                        f"p{idx}", f"http://127.0.0.1:{obs_ports[idx]}"
                    )
                ok = col.scrape()
                assert all(ok.values()), f"live scrape failed: {ok}"
                report = col.fleet_report()
                merged = col.merged_perfetto()
            finally:
                _stop(procs, outdir)
            dones = []
            for p in done_paths:
                with open(p) as f:
                    dones.append(json.load(f))
        break
    else:
        raise RuntimeError(f"ports taken on all {ATTEMPTS} attempts")

    digests = {d["digest"] for d in dones}
    assert len(digests) == 1, \
        f"fleet-trace: documents diverged under faults: {digests}"
    paths = report["paths"]
    assert paths["traced_recvs"] > 0, "fleet-trace: nothing traced"
    assert paths["pair_rate"] == 1.0, (
        f"fleet-trace: only {paths['complete']}/"
        f"{paths['traced_recvs']} paths reconstructed "
        f"(sample: {paths['incomplete_sample']})"
    )
    routes = set(paths["routes"])
    assert {"direct", "relayed", "sync_answer"} <= routes, \
        f"fleet-trace: route coverage incomplete: {routes}"
    assert sorted(paths["origin_procs"]) == ["p0", "p1", "p2"], \
        f"fleet-trace: origin procs {paths['origin_procs']}"
    ctx_bytes = sum(d["ledger"]["context_bytes"] for d in dones)
    upd_bytes = sum(d["ledger"]["traced_update_bytes"] for d in dones)
    overhead = ctx_bytes / upd_bytes if upd_bytes else 0.0
    assert overhead < 0.05, \
        f"fleet-trace: context overhead {overhead:.3f} >= 5%"
    relay_forwards = sum(
        d["relay"].get("relay_frames_forwarded", 0) for d in dones
    )
    assert relay_forwards > 0, "fleet-trace: no frames were relayed"
    pids = {e.get("pid") for e in merged["traceEvents"]
            if isinstance(e, dict)}
    assert len(pids) >= n_procs, \
        f"fleet-trace: merged timeline pids collided: {pids}"
    advice = report.get("advice") or []
    assert any(a.get("proc") == "p1"
               and a.get("action") == "rebalance_away"
               for a in advice), \
        f"fleet-trace: control advice not federated: {advice}"
    assert report.get("control", {}).get("p1", {}).get("rows"), \
        "fleet-trace: control ledger tail missing from /fleet"
    return {
        "procs": len(report["procs"]),
        "pair_rate": paths["pair_rate"],
        "traced_recvs": paths["traced_recvs"],
        "wire_overhead_ratio": overhead,
        "routes": paths["routes"],
        "hops": report["latency"]["hops"],
        "latency": report["latency"],
        "relay_frames_forwarded": relay_forwards,
        "control_advice_rows": len(advice),
        "perfetto_pids": sorted(p for p in pids if isinstance(p, int)),
        "converged": True,
    }


# ---------------------------------------------------------------------------
# rebalance: three fleet servers, one crash-safe live migration
# ---------------------------------------------------------------------------


def rebalance_child(argv) -> int:
    """One fleet server: a real ``FleetNode`` whose fabric is the
    sealed ``UdpEndpoint`` (X25519 static identities derived from
    fixed seeds, so every child knows every peer's public key, no key
    exchange). Child "a" seeds the doc and live-migrates it to "c"
    mid-run; the parent asserts that exactly one process serves
    afterwards and that the losers' fences counted."""
    cfg = json.loads(argv[0])
    idx = int(cfg["idx"])
    names = list(cfg["names"])
    me = names[idx]
    ports = cfg["ports"]
    outdir = cfg["outdir"]
    ticks = int(cfg["ticks"])

    from crdt_tpu.codec import v1
    from crdt_tpu.core.ids import DeleteSet
    from crdt_tpu.core.records import ItemRecord
    from crdt_tpu.fleet import FleetNode, UdpFabric
    from crdt_tpu.net.transport import SecureBox, UdpEndpoint, keypair
    from crdt_tpu.obs import Tracer, set_tracer
    from crdt_tpu.storage.snapshot import SnapshotStore

    tracer = set_tracer(Tracer(enabled=True))
    keys = {n: keypair(bytes([j + 1]) * 32)
            for j, n in enumerate(names)}
    _pub, sec = keys[me]
    peers = {n: ("127.0.0.1", int(ports[j]),
                 SecureBox(sec, keys[n][0]))
             for j, n in enumerate(names) if n != me}
    try:
        ep = UdpEndpoint("127.0.0.1", int(ports[idx]))
    except OSError as exc:
        if _port_taken(exc):
            return PORT_TAKEN
        raise
    fab = UdpFabric(me, ep, peers)
    store = SnapshotStore(os.path.join(outdir, me))
    node = FleetNode(me, names, fab, store=store,
                     timeout_ticks=25, beacon_every=8,
                     server_kw={"slo_ms": 1e9})

    # start barrier: frames to an unbound port are lost, so nobody
    # ticks until every endpoint is up
    with open(os.path.join(outdir, f"ready_{idx}.json"), "w") as f:
        json.dump({"port": ep.port}, f)
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        if all(os.path.exists(os.path.join(outdir, f"ready_{j}.json"))
               for j in range(len(names))):
            break
        time.sleep(0.01)

    def chain_blob(k0, n_ops=4):
        recs = []
        for j in range(n_ops):
            k = k0 + j
            recs.append(ItemRecord(
                client=7, clock=k, parent_root="l",
                origin=(7, k - 1) if k else None,
                content=7000 + k,
            ))
        return v1.encode_update(recs, DeleteSet())

    doc = "doc"           # ring arc: a
    d0 = None
    migrate_ok = None
    # children tick on their own wall clocks (child "a" pays the
    # first submit's compile inside its loop), so the run ends on a
    # barrier, not a tick count: once "a" marks the handoff complete,
    # everyone runs a grace window (a beacon cadence: "b" must adopt
    # the new owner) and only then writes its done file
    handoff_path = os.path.join(outdir, "handoff.json")
    t = 0
    grace = None
    while True:
        if me == "a":
            if t < 4:
                r, _ = node.submit(doc, chain_blob(4 * t))
                assert r == "ok", f"seed submit: {r}"
            if t == 4:
                d0 = node.server.digest(doc)
            if t == 10:
                migrate_ok = node.migrate(doc, "c")
            if migrate_ok and node.migrator.completed >= 1 \
                    and not os.path.exists(handoff_path):
                with open(handoff_path + ".tmp", "w") as f:
                    json.dump({"tick": t}, f)
                os.replace(handoff_path + ".tmp", handoff_path)
        ep.poll()
        node.tick()
        time.sleep(0.02)
        t += 1
        if grace is None:
            if t >= ticks and os.path.exists(handoff_path):
                grace = 24
            elif t > 40 * ticks:   # runaway guard: fail loudly
                break
        else:
            grace -= 1
            if grace <= 0:
                break

    served = node.digest(doc)   # fence-refused (and counted) on losers
    counters = tracer.counters()

    def csum(name):
        return sum(v for k, v in counters.items()
                   if k == name or k.startswith(name + "{"))
    done = {
        "proc": me,
        "digest": served,
        "d0": d0,
        "lease": list(node.lease.lease(doc)),
        "migrate_ok": migrate_ok,
        "completed": node.migrator.completed,
        "fence_rejects": csum("fleet.fence_rejects"),
        "fork_refused": csum("fleet.fork_refused"),
        "udp_failed": ep.failed,
    }
    tmp_path = os.path.join(outdir, f"done_{idx}.json.tmp")
    with open(tmp_path, "w") as f:
        json.dump(done, f)
    os.replace(tmp_path, os.path.join(outdir, f"done_{idx}.json"))
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        if os.path.exists(os.path.join(outdir, "stop")):
            break
        ep.poll()
        node.drain_inbox()
        time.sleep(0.01)
    ep.close()
    return 0


def rebalance_smoke(ticks: int = 30) -> dict:
    """Three fleet servers in separate OS processes over sealed
    loopback UDP, one crash-safe live migration between them; the
    done files say who serves, who holds which lease, and what each
    fence counted."""
    names = ["a", "b", "c"]
    for _attempt in range(ATTEMPTS):
        with tempfile.TemporaryDirectory() as outdir:
            ports = _free_ports(len(names), udp=True)
            procs = _start("rebalance-child", [
                {"idx": idx, "names": names, "ports": ports,
                 "outdir": outdir, "ticks": ticks}
                for idx in range(len(names))])
            done_paths = [os.path.join(outdir, f"done_{i}.json")
                          for i in range(len(names))]
            try:
                if not _wait_done(procs, done_paths, 120.0):
                    continue
            finally:
                _stop(procs, outdir)
            dones = {}
            for i, path in enumerate(done_paths):
                with open(path) as f:
                    dones[names[i]] = json.load(f)
        break
    else:
        raise RuntimeError(f"ports taken on all {ATTEMPTS} attempts")
    return {
        "servers": [n for n in names if dones[n]["digest"] is not None],
        "leases": {n: dones[n]["lease"] for n in names},
        "migrate_ok": dones["a"]["migrate_ok"],
        "completed": dones["a"]["completed"],
        "served_digest": dones["c"]["digest"],
        "seeded_digest": dones["a"]["d0"],
        "fence_rejects": {n: dones[n]["fence_rejects"] for n in names},
        "fork_refused": {n: dones[n]["fork_refused"] for n in names},
        "udp_failed": {n: dones[n]["udp_failed"] for n in names},
    }


# ---------------------------------------------------------------------------
# the placement loop's A/B, in one process
# ---------------------------------------------------------------------------


def rebalance_leg(seed: int = 7, flood_ticks: int = 8,
                  sustain_ticks: int = 28, hysteresis: int = 10) -> dict:
    """The fleet placement loop's A/B under seeded chaos: three
    ``FleetNode`` processes on an in-process ``MemFabric``, one
    flooding tenant beside small steady neighbors, identical
    submissions and identical STATIC per-tenant budgets twice:

    - **OFF** (oracle): the same three servers, the same in-process
      controllers (round 22 squeeze/restore) — but NO placement
      loop: the flooder stays where the ring put it, the squeeze is
      the only defense, and the sustain load keeps breaching the
      squeezed budget forever (burn pins high);
    - **ON**: a :class:`crdt_tpu.fleet.PlacementLoop` consumes the
      controllers' federated ``rebalance_away`` advice — mangled by
      a seeded :class:`DuplicateAdviceSchedule` (duplicates +
      stale-seq replays) — and live-migrates the flooder to a clean
      process, where the sustain load fits the static budget and the
      serving burn drains.

    Chaos riding both legs (`net/faults.HandoffFaultSchedule`): every
    migration ``ack`` on the c->a link is dropped (the epoch-fenced
    probe path must complete those handoffs: ``migration.recovery``)
    and every ``commit`` on a->c is duplicated (the dst's idempotent
    re-ack). The ON leg additionally live-migrates an untouched
    identity doc a->c mid-stream, then kills process "a" cold and
    revives it from its snapshot store. A per-tick digest sweep over
    every doc x process counts double-serves (must be zero — each
    refused serve bumps ``fleet.fence_rejects{op=serve}``) and the
    identity doc + steady neighbors must be byte-identical across
    ON/OFF: digest, state vector, state-as-update, and the round-13
    snapshot generation."""
    from crdt_tpu.codec import v1
    from crdt_tpu.core.ids import DeleteSet
    from crdt_tpu.core.records import ItemRecord
    from crdt_tpu.fleet import FleetNode, PlacementLoop
    from crdt_tpu.fleet.fabric import MemFabric
    from crdt_tpu.net.faults import (
        DuplicateAdviceSchedule,
        HandoffFaultSchedule,
    )
    from crdt_tpu.obs import Tracer, set_tracer
    from crdt_tpu.obs.control import Controller
    from crdt_tpu.obs.slo import SLOLedger
    from crdt_tpu.storage.snapshot import SnapshotStore, encode_engine

    from tests.test_multidoc import DocStream

    settle_ticks = 8
    budget_bytes, budget_updates = 2048, 4
    burn_window = 16
    recover_lo = 0.25
    members = ["a", "b", "c"]
    flooder = "doc"        # ring arc: a (pinned by test_placement)
    ident = "y"            # ring arc: a — the migrated identity doc
    steady = ["w", "tenant-0"]   # ring arcs: b, c — never moved
    docs = [flooder, ident] + steady

    def flood_blob(i: int) -> bytes:
        # independent single-record update; the UPDATE-COUNT cap is
        # the working constraint: 4 of them fit the static byte
        # budget (so a handoff tail never squeezes the destination)
        # while the squeezed cap of 1 update/tick sheds one of every
        # sustain pair forever — the self-sustaining breach the
        # placement loop exists to break
        return v1.encode_update([ItemRecord(
            client=10_000 + i, clock=0, parent_root="m",
            key=f"f{i}", content="f" * 400,
        )], DeleteSet())

    blob_len = len(flood_blob(0))
    assert budget_updates * blob_len <= budget_bytes, \
        "rebalance: a full update-cap tick must fit the byte budget"
    assert 2 * blob_len > budget_bytes // 4, \
        "rebalance: a sustain pair must breach the squeezed budget"

    def run(on: bool) -> dict:
        tracer = set_tracer(Tracer(enabled=True))
        tmp = tempfile.TemporaryDirectory()
        chaos = HandoffFaultSchedule(seed, windows=[
            # every handoff ack on c->a dies: the src must fence-
            # probe the dst and complete from its reply
            {"src": "c", "dst": "a", "kinds": ("ack",),
             "mode": "drop"},
            # every commit on a->c arrives twice: the dst re-acks
            # idempotently (and the re-ack dies too)
            {"src": "a", "dst": "c", "kinds": ("commit",),
             "mode": "dup"},
        ])
        fab = MemFabric(faults=chaos)
        dead: set = set()
        nodes: dict = {}
        ctrls: dict = {}
        stores: dict = {}

        def make_hint(p):
            # fleet-layer wiring: never advise moving a tenant onto
            # a process that is squeezing it (its budget override
            # would keep breaching) or a dead one
            def hint(t):
                excl = [p] + [q for q in members
                              if q in dead
                              or str(t) in {str(k) for k in
                                            ctrls[q].overrides()}]
                loads = {q: nodes[q].load() for q in members
                         if q not in dead}
                return nodes[p].ring.least_loaded_successor(
                    str(t), exclude=excl, loads=loads)
            return hint

        def build_node(p):
            ctrl = Controller(cooldown_ticks=4, restore_after=2)
            node = FleetNode(
                p, members, fab, store=stores[p],
                timeout_ticks=3, beacon_every=4,
                server_kw=dict(
                    tenant_max_pending_bytes=budget_bytes,
                    tenant_max_pending_updates=budget_updates,
                    slo_ms=1e9,   # sheds drive burn, never clocks
                    control=ctrl,
                ))
            # fast-flushing burn window, the autopilot idiom
            node.server.slo = SLOLedger(1e9, burn_window=burn_window)
            ctrl.placement_hint = make_hint(p)
            nodes[p], ctrls[p] = node, ctrl
            return node

        for p in members:
            stores[p] = SnapshotStore(os.path.join(tmp.name, p))
            build_node(p)
        ring = nodes["a"].ring
        adv_chaos = DuplicateAdviceSchedule(
            seed, duplicate=0.5, replay=0.5)
        # placement hysteresis ~ a burn window: a flood spike
        # shorter than that is the squeeze's job — moving a tenant
        # mid-spike just ships the spike to the destination (the
        # tail rides the commit) and cascades squeezes around the
        # ring. Only SUSTAINED pressure pays for a migration.
        loop = PlacementLoop(
            ring, lambda p: None if p in dead else nodes.get(p),
            hysteresis=hysteresis, budget_per_tick=1) if on else None

        streams = {d: DocStream(1 + i)
                   for i, d in enumerate([ident] + steady)}
        lost = {d: 0 for d in docs}

        def submit(doc, blob):
            # redirect-chasing client: offer to each live process in
            # order; exactly one accepts (or the update is lost for
            # one tick — tolerated ONLY for the flooder, whose sheds
            # already differ by design)
            for p in members:
                if p in dead:
                    continue
                r, _info = nodes[p].submit(doc, blob)
                if r in ("ok", "buffered"):
                    return r
            lost[doc] += 1
            return "lost"

        def flood_owner():
            for p in members:
                if p not in dead and nodes[p].lease.holds(flooder) \
                        and not nodes[p].migrator.migrating(flooder):
                    return p
            return None

        total = flood_ticks + sustain_ticks
        t_ident = flood_ticks + 2
        t_kill = total - 6
        t_revive = t_kill + 2
        nblob = 0
        recovery = None
        burn_flood_end = None
        burn_last = None
        double_serves = 0
        tail_restores = 0
        for t in range(total + settle_ticks):
            settling = t >= total
            if not settling:
                if t < flood_ticks:
                    for _ in range(10):
                        submit(flooder, flood_blob(nblob))
                        nblob += 1
                else:
                    # sustain: a pair per tick — fits the static
                    # update budget, breaches the squeezed one
                    # (1 update/tick) every single tick: the load
                    # that makes "squeeze forever" the wrong answer
                    # and "move it" right
                    for _ in range(2):
                        submit(flooder, flood_blob(nblob))
                        nblob += 1
                for d in [ident] + steady:
                    assert submit(d, streams[d].delta(4)) != "lost", \
                        f"rebalance: steady update lost for {d}"
            if on:
                if t == t_ident:
                    assert nodes["a"].migrate(ident, "c"), \
                        "rebalance: identity migration refused"
                if t == t_kill - 1:
                    nodes["a"].checkpoint()
                if t == t_kill:
                    fab.kill("a")
                    dead.add("a")
                if t == t_revive:
                    dead.discard("a")
                    node = build_node("a")
                    fab.revive("a", node)
                    before = tracer.counters().get(
                        "migration.tail_restores", 0)
                    node.restore()
                    tail_restores += tracer.counters().get(
                        "migration.tail_restores", 0) - before
            for p in members:
                if p not in dead:
                    nodes[p].tick()
            if on:
                rows = [dict(r, proc=p)
                        for p in members if p not in dead
                        for r in ctrls[p].advice()]
                # PlacementLoop.observe takes advice rows, not a
                # metric name  # crdtlint: disable=CL203
                loop.observe(t, adv_chaos.mangle(t, rows))
            # the fork guard sweep: every refused serve counts
            # fleet.fence_rejects{op=serve}; >1 server is a fork
            for d in docs:
                n_serving = sum(
                    1 for p in members
                    if p not in dead
                    and nodes[p].digest(d) is not None)
                if n_serving > 1:
                    double_serves += 1
            owner = flood_owner()
            burn = None
            if owner is not None:
                burn = nodes[owner].server.slo.report()[
                    "tenants"].get(flooder, {}).get("burn_rate")
            if burn is not None:
                burn_last = burn
            if t == flood_ticks - 1:
                burn_flood_end = burn
            if (recovery is None and t >= flood_ticks
                    and burn is not None and burn <= recover_lo):
                recovery = t - flood_ticks + 1
        # re-warm the identity doc so the engine snapshot comparison
        # sees a resident matrix on both legs
        assert submit(ident, streams[ident].delta(2)) == "ok"
        for p in members:
            if p not in dead:
                nodes[p].tick()
        serving_ident = [p for p in members if p not in dead
                         and nodes[p].digest(ident) is not None]
        assert len(serving_ident) == 1, \
            f"rebalance: identity doc served by {serving_ident}"
        counters = dict(tracer.counters())
        out = {
            "nodes": nodes, "loop": loop, "fab": fab,
            "adv_chaos": adv_chaos, "counters": counters,
            "recovery": recovery, "burn_flood_end": burn_flood_end,
            "burn_last": burn_last, "double_serves": double_serves,
            "lost": lost, "tail_restores": tail_restores,
            "ident_proc": serving_ident[0], "tmp": tmp,
        }
        set_tracer(Tracer(enabled=False))
        return out

    on = run(True)
    off = run(False)

    def ident_state(leg):
        srv = leg["nodes"][leg["ident_proc"]].server
        eng = srv._docs[ident].resident
        assert eng is not None, "rebalance: identity doc went cold"
        return {
            "digest": srv.digest(ident),
            "sv": eng.state_vector(),
            "update": eng.encode_state_as_update(),
            "snapshot": encode_engine(eng, seq=0),
        }

    s_on, s_off = ident_state(on), ident_state(off)
    identical = {
        "ident_digest": s_on["digest"] == s_off["digest"],
        "ident_sv": s_on["sv"] == s_off["sv"],
        "ident_update": s_on["update"] == s_off["update"],
        "ident_snapshot": s_on["snapshot"] == s_off["snapshot"],
    }
    for d in steady:
        a = [on["nodes"][p].digest(d) for p in members
             if on["nodes"][p].digest(d) is not None]
        b = [off["nodes"][p].digest(d) for p in members
             if off["nodes"][p].digest(d) is not None]
        identical[f"steady_{d}"] = bool(a) and a == b
    on["tmp"].cleanup()
    off["tmp"].cleanup()

    c_on = on["counters"]

    def csum(name: str) -> int:
        # labeled counters live under name{label=...} keys only
        return sum(v for k, v in c_on.items()
                   if k == name or k.startswith(name + "{"))

    hops = [r for r in on["loop"].ledger.rows()
            if r.get("action") == "migrate"]
    return {
        "seed": seed,
        "flood_ticks": flood_ticks,
        "sustain_ticks": sustain_ticks,
        "recovery_ticks": on["recovery"],
        "recovery_ticks_off": off["recovery"],
        "recovery_budget_ticks": 20,
        "burn_flood_end": on["burn_flood_end"],
        "burn_end_off": off["burn_last"],
        "migrations": on["loop"].migrations,
        "hops": [{"src": r["src"], "dst": r["dst"],
                  "tick": r["tick"]} for r in hops],
        "migrations_completed": csum("migration.completed"),
        "migration_recoveries": csum("migration.recovery"),
        "recoveries_by_step": {
            k.split('step="', 1)[1].rstrip('"}'): v
            for k, v in c_on.items()
            if k.startswith('migration.recovery{')},
        "fence_rejects": csum("fleet.fence_rejects"),
        "fork_refused": csum("fleet.fork_refused"),
        "forks": on["double_serves"] + off["double_serves"],
        "double_serves": on["double_serves"],
        "tail_blobs": csum("migration.tail_blobs"),
        "tail_restores": on["tail_restores"],
        "advice_dups": on["loop"].dup_drops,
        "advice_injected": on["adv_chaos"].injected,
        "ledger_rows": on["loop"].ledger.total,
        "frames_sent": on["fab"].sent,
        "frames_dropped": on["fab"].dropped,
        "frames_duplicated": on["fab"].duplicated,
        "lost_flood_updates": on["lost"][flooder],
        "identical": identical,
        "all_identical": all(identical.values()),
    }


def main(argv) -> int:
    mode, rest = argv[0], argv[1:]
    if mode == "fleet-trace-child":
        return fleet_trace_child(rest)
    if mode == "rebalance-child":
        return rebalance_child(rest)
    out = {"fleet-trace": fleet_trace, "rebalance": rebalance_smoke}[mode]()
    print(json.dumps(out, sort_keys=True, default=str))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main(sys.argv[1:]))
