"""Round-24 chaos matrix: crash-safe live migration, never a fork.

The tentpole proof, pinned four ways:

- **Kill matrix** — the source process dies at EVERY step of the
  handoff ladder (drain / ship / commit / ack) and the destination
  dies mid-rehydrate; each kill lands on its own counted recovery
  rung (``migration.recovery{step=...}``), exactly ONE process
  serves the doc afterwards, and that process serves the
  pre-migration digest.
- **Partition matrix** — scripted frame drops (offer / commit / ack
  windows on ``net.faults.HandoffFaultSchedule``) resolve through
  the probe/NACK path: a lost ack completes via probe, a lost commit
  reclaims at a HIGHER epoch (the late replay is fenced off), a lost
  offer aborts cleanly.
- **Byte identity** — updates submitted mid-handoff (buffered into
  the migration tail, riding the commit frame) converge to a doc
  whose digest, state vector, snapshot-generation bytes, and
  state-as-update bytes all equal a migration-free oracle's.
- **Durability** — a committed handoff survives the destination
  dying before its first checkpoint (the commit-path tail stash,
  ``migration.tail_restores``), and a checkpoint stamped by a NEWER
  fencing epoch is refused on restore
  (``snap.fallbacks{reason=stale_epoch}`` — satellite 2).
"""

import pytest

from crdt_tpu.codec import v1
from crdt_tpu.core.ids import DeleteSet
from crdt_tpu.core.records import ItemRecord
from crdt_tpu.fleet import (
    FleetNode,
    HashRing,
    LeaseTable,
    MemFabric,
    PlacementLoop,
)
from crdt_tpu.fleet import wire
from crdt_tpu.guard.faults import MigrationCrashPlan, SimulatedCrash
from crdt_tpu.models.multidoc import MultiDocServer
from crdt_tpu.net.faults import (
    DuplicateAdviceSchedule,
    HandoffFaultSchedule,
)
from crdt_tpu.obs import Tracer, set_tracer
from crdt_tpu.obs.control import Controller
from crdt_tpu.storage.snapshot import SnapshotStore

MEMBERS = ("a", "b", "c")
DOC = "doc"  # ring-owned by "a" at vnodes=64 (test_placement pins)
SERVER_KW = {"slo_ms": 10_000.0}


@pytest.fixture(autouse=True)
def _quiet_obs():
    old = set_tracer(Tracer(enabled=False))
    yield
    set_tracer(old)


def chain_blob(client, k0, n_ops=4):
    """One doc's chained list appends (clocks k0..k0+n_ops-1)."""
    recs = []
    for j in range(n_ops):
        k = k0 + j
        recs.append(ItemRecord(
            client=client, clock=k, parent_root="l",
            origin=(client, k - 1) if k else None,
            content=client * 1000 + k,
        ))
    return v1.encode_update(recs, DeleteSet())


def make_fleet(tmp_path, *, faults=None, crash_plans=None,
               timeout_ticks=3, beacon_every=0):
    """Three FleetNodes on one MemFabric, each with its own
    SnapshotStore (the crash-revive seam)."""
    fab = MemFabric(faults=faults)
    stores, nodes = {}, {}
    for p in MEMBERS:
        stores[p] = SnapshotStore(str(tmp_path / p))
        nodes[p] = FleetNode(
            p, MEMBERS, fab, store=stores[p],
            timeout_ticks=timeout_ticks, beacon_every=beacon_every,
            crash_plan=(crash_plans or {}).get(p),
            server_kw=dict(SERVER_KW))
    return fab, nodes, stores


def run_ticks(fab, nodes, n):
    """Drive the fleet; a SimulatedCrash kills that process (its
    queue dies with it) — the driver half of MigrationCrashPlan."""
    for _ in range(n):
        for p in sorted(nodes):
            if p in fab.dead:
                continue
            try:
                nodes[p].tick()
            except SimulatedCrash:
                fab.kill(p)


def revive(fab, nodes, stores, proc, *, timeout_ticks=3,
           beacon_every=0):
    """Rebuild a killed process from its own store (lease table and
    intent blob reload in restore()) — volatile state is gone."""
    node = FleetNode(
        proc, MEMBERS, fab, store=stores[proc],
        timeout_ticks=timeout_ticks, beacon_every=beacon_every,
        server_kw=dict(SERVER_KW))
    fab.revive(proc, node)
    node.restore()
    nodes[proc] = node
    return node


def seed_doc(nodes, doc=DOC, owner="a", rounds=4):
    """Submit ``rounds`` chained blobs over as many ticks so the doc
    settles resident (warm) on its owner; returns the digest."""
    for k in range(rounds):
        r, _ = nodes[owner].submit(doc, chain_blob(7, 4 * k))
        assert r == "ok"
        for p in sorted(nodes):
            nodes[p].tick()
    return nodes[owner].server.digest(doc)


def serving(nodes, doc=DOC):
    """Who will actually serve the doc right now? (A refused serve
    counts ``fleet.fence_rejects{op=serve}`` on the refuser — the
    sweep itself exercises the fence.)"""
    return [p for p in sorted(nodes)
            if nodes[p].digest(doc) is not None]


# ---- the happy path ------------------------------------------------


class TestHappyPath:
    def test_live_migration_is_lossless_and_single_owner(self, tmp_path):
        fab, nodes, stores = make_fleet(tmp_path)
        d0 = seed_doc(nodes)
        assert serving(nodes) == ["a"]
        assert nodes["a"].migrate(DOC, "c")
        run_ticks(fab, nodes, 6)
        assert serving(nodes) == ["c"]
        assert nodes["c"].server.digest(DOC) == d0
        assert nodes["a"].migrator.completed == 1
        assert nodes["a"].migrator.recoveries == {}
        # the lease moved to epoch 2 everywhere that heard about it
        assert nodes["c"].lease.lease(DOC) == (2, "c")
        assert nodes["a"].lease.lease(DOC) == (2, "c")
        # no fork was ever even attempted
        assert all(nodes[p].lease.fork_refused == 0 for p in nodes)
        # a mis-routed submit redirects to the new owner
        r, owner = nodes["a"].submit(DOC, chain_blob(7, 16))
        assert (r, owner) == ("redirect", "c")
        assert nodes["a"].redirects == 1

    def test_migrate_refusals(self, tmp_path):
        fab, nodes, stores = make_fleet(tmp_path)
        seed_doc(nodes)
        assert not nodes["b"].migrate(DOC, "c")   # not the owner
        assert not nodes["a"].migrate(DOC, "a")   # self-move
        assert nodes["a"].migrate(DOC, "c")
        assert not nodes["a"].migrate(DOC, "b")   # already in flight
        assert nodes["a"].migrator.started == 1


# ---- the kill matrix -----------------------------------------------


KILL_CASES = [
    # (kill step, crashed proc, expected recoveries on the REVIVED
    #  process, expected recoveries on the surviving peer, winner)
    ("drain", "a", {"drain": 1}, {}, "a"),
    ("ship", "a", {"ship": 1}, {}, "a"),
    ("commit", "a", {"commit": 2}, {"commit": 1}, "a"),
    ("ack", "a", {"commit": 1, "ack": 1}, {}, "c"),
    ("rehydrate", "c", {}, {"rehydrate": 1}, "a"),
]


class TestKillMatrix:
    @pytest.mark.parametrize(
        "step,victim,rec_revived,rec_peer,winner", KILL_CASES,
        ids=[c[0] for c in KILL_CASES])
    def test_kill_at_step(self, tmp_path, step, victim, rec_revived,
                          rec_peer, winner):
        """Kill one process at exactly one ladder step; the fleet
        must converge to ONE serving owner holding the seeded
        digest, with the recovery counted on the pinned rung."""
        plans = {victim: MigrationCrashPlan(kill_at={step: 1})}
        fab, nodes, stores = make_fleet(tmp_path, crash_plans=plans)
        d0 = seed_doc(nodes)
        # durability floor: the owner checkpoints BEFORE the move
        # (the crash matrix is about ownership, not WAL loss)
        nodes["a"].checkpoint()
        assert nodes["a"].migrate(DOC, "c")
        run_ticks(fab, nodes, 4)
        assert fab.dead == {victim}, (
            f"crash plan for step {step!r} never fired")
        # let the survivor's timeouts run before the revive
        run_ticks(fab, nodes, 4)
        revived = revive(fab, nodes, stores, victim)
        run_ticks(fab, nodes, 12)
        peer = "c" if victim == "a" else "a"
        assert revived.migrator.recoveries == rec_revived
        assert nodes[peer].migrator.recoveries == rec_peer
        assert serving(nodes) == [winner]
        assert nodes[winner].server.digest(DOC) == d0
        # the fence refused the losers (the serving sweep above
        # asked every process)
        for p in nodes:
            if p != winner:
                assert nodes[p].lease.fence_rejects >= 1
        assert all(nodes[p].lease.fork_refused == 0 for p in nodes)

    def test_commit_kill_reclaims_above_the_granted_epoch(
            self, tmp_path):
        """The commit-step crash is the fork trap: src already
        granted (and persisted) the lease away. The revived source
        must NOT serve until the destination's binding NACK, and the
        reclaim lands ABOVE the failed epoch so a delayed commit
        replay can never resurrect the grant."""
        plans = {"a": MigrationCrashPlan(kill_at={"commit": 1})}
        fab, nodes, stores = make_fleet(tmp_path, crash_plans=plans)
        seed_doc(nodes)
        nodes["a"].checkpoint()
        assert nodes["a"].migrate(DOC, "c")
        run_ticks(fab, nodes, 4)
        assert fab.dead == {"a"}
        revived = revive(fab, nodes, stores, "a")
        # straight after restore: the persisted grant fences the
        # restart — it knows the doc MAY belong to c and probes
        # instead of serving
        assert revived.lease.lease(DOC) == (2, "c")
        assert revived.digest(DOC) is None
        run_ticks(fab, nodes, 12)
        # c's binding NACK proved the commit never landed: reclaim
        # at epoch 3 (> the failed grant's 2)
        assert revived.lease.lease(DOC) == (3, "a")
        assert serving(nodes) == ["a"]


# ---- the partition matrix (scripted frame drops) -------------------


DROP_CASES = [
    # (dropped kind, link, src recoveries, dst recoveries, winner,
    #  final lease epoch at the winner)
    ("commit", ("a", "c"), {"commit": 1}, {"commit": 1}, "a", 3),
    ("ack", ("c", "a"), {"ack": 1}, {}, "c", 2),
    ("offer", ("a", "c"), {"rehydrate": 1}, {}, "a", 1),
]


class TestPartitionMatrix:
    @pytest.mark.parametrize(
        "kind,link,rec_src,rec_dst,winner,epoch", DROP_CASES,
        ids=[c[0] for c in DROP_CASES])
    def test_dropped_frame_window(self, tmp_path, kind, link,
                                  rec_src, rec_dst, winner, epoch):
        faults = HandoffFaultSchedule(seed=3, windows=[{
            "src": link[0], "dst": link[1], "kinds": (kind,),
            "from_n": 1, "mode": "drop",
        }])
        fab, nodes, stores = make_fleet(tmp_path, faults=faults)
        d0 = seed_doc(nodes)
        assert nodes["a"].migrate(DOC, "c")
        run_ticks(fab, nodes, 20)
        assert faults.window_hits >= 1
        assert nodes["a"].migrator.recoveries == rec_src
        assert nodes["c"].migrator.recoveries == rec_dst
        assert serving(nodes) == [winner]
        assert nodes[winner].server.digest(DOC) == d0
        assert nodes[winner].lease.lease(DOC) == (epoch, winner)
        assert all(nodes[p].lease.fork_refused == 0 for p in nodes)

    def test_dropped_ack_completes_via_probe(self, tmp_path):
        """The lost-ack case must end COMPLETED (not reclaimed): the
        probe reply proves dst serves at the new epoch, so the
        source finishes the handoff instead of forking it back."""
        faults = HandoffFaultSchedule(seed=3, windows=[{
            "src": "c", "dst": "a", "kinds": ("ack",),
            "from_n": 1, "to_n": 1, "mode": "drop",
        }])
        fab, nodes, stores = make_fleet(tmp_path, faults=faults)
        seed_doc(nodes)
        assert nodes["a"].migrate(DOC, "c")
        run_ticks(fab, nodes, 20)
        assert nodes["a"].migrator.completed == 1
        assert DOC not in nodes["a"].server._docs  # state released


# ---- byte identity under mid-handoff traffic -----------------------


def try_submit(nodes, doc, blob):
    """A redirect-chasing client: offer the update to each process,
    following ownership redirects — exactly one accepts."""
    for p in sorted(nodes):
        r, info = nodes[p].submit(doc, blob)
        if r in ("ok", "buffered"):
            return r
    raise AssertionError("no process accepted the update")


class TestByteIdentity:
    def test_mid_handoff_tail_vs_migration_free_oracle(self, tmp_path):
        """Updates landing DURING the handoff ride the migration
        tail; afterwards the moved doc is byte-identical — digest,
        state vector, snapshot generation, state-as-update — to a
        single-server oracle fed the same blobs in the same order."""
        from crdt_tpu.storage.snapshot import encode_engine

        fab, nodes, stores = make_fleet(tmp_path)
        oracle = MultiDocServer(**SERVER_KW)
        blobs = [chain_blob(7, 4 * k) for k in range(6)]
        for k in range(3):                       # before the move
            assert try_submit(nodes, DOC, blobs[k]) == "ok"
            oracle.submit(DOC, blobs[k])
            run_ticks(fab, nodes, 1)
            oracle.tick()
        assert nodes["a"].migrate(DOC, "c")
        for k in range(3, 6):                    # during / after
            try_submit(nodes, DOC, blobs[k])
            oracle.submit(DOC, blobs[k])
            run_ticks(fab, nodes, 1)
            oracle.tick()
        run_ticks(fab, nodes, 8)
        for _ in range(8):
            oracle.tick()
        assert serving(nodes) == ["c"]
        assert nodes["a"].migrator.completed == 1
        srv = nodes["c"].server
        assert srv.digest(DOC) == oracle.digest(DOC)
        got = srv._docs[DOC].resident
        want = oracle._docs[DOC].resident
        assert got is not None and want is not None
        assert got.state_vector() == want.state_vector()
        assert got.encode_state_as_update() == \
            want.encode_state_as_update()
        assert encode_engine(got, seq=0) == encode_engine(want, seq=0)


# ---- durability: the commit-path tail stash ------------------------


class TestDstDurability:
    def test_dst_crash_after_commit_restores_from_tail_stash(
            self, tmp_path):
        """dst dies right after taking ownership, BEFORE any
        checkpoint: the commit handler stashed the doc's full
        history durably before acking, so the revived dst re-seeds
        the doc (``migration.tail_restores``) instead of losing a
        committed handoff."""
        fab, nodes, stores = make_fleet(tmp_path)
        d0 = seed_doc(nodes)
        assert nodes["a"].migrate(DOC, "c")
        # one more update mid-drain: buffers into the tail
        r, _ = nodes["a"].submit(DOC, chain_blob(7, 16))
        assert r == "buffered"
        run_ticks(fab, nodes, 8)
        assert serving(nodes) == ["c"]
        d1 = nodes["c"].server.digest(DOC)
        assert d1 != d0  # the tail blob landed
        # kill c cold (no checkpoint ever ran on it)
        fab.kill("c")
        tracer = set_tracer(Tracer(enabled=True))
        try:
            revived = revive(fab, nodes, stores, "c")
            assert tracer.counters().get(
                "migration.tail_restores", 0) == 1
        finally:
            set_tracer(Tracer(enabled=False))
        run_ticks(fab, nodes, 4)
        assert serving(nodes) == ["c"]
        assert revived.server.digest(DOC) == d1


# ---- beacons: the partitioned ex-owner heals -----------------------


class TestBeacons:
    def test_stale_owner_demotes_on_newer_epoch_beacon(self, tmp_path):
        fab, nodes, stores = make_fleet(tmp_path, beacon_every=2)
        seed_doc(nodes)
        # b returns from a partition holding a NEWER lease (epoch 5)
        # and the doc's state — the beacon must demote a, not fork
        nodes["b"].lease.grant(DOC, 5, "b")
        nodes["b"].server.submit(DOC, chain_blob(7, 0))
        run_ticks(fab, nodes, 6)
        assert nodes["a"].demotions == 1
        assert nodes["a"].lease.lease(DOC) == (5, "b")
        assert serving(nodes) == ["b"]

    def test_equal_epoch_rival_beacon_is_a_refused_fork(self, tmp_path):
        fab, nodes, stores = make_fleet(tmp_path, beacon_every=2)
        seed_doc(nodes)
        # b claims the doc at the SAME epoch a holds: a fork attempt
        nodes["b"].lease._leases[DOC] = (1, "b")  # corrupted rival
        nodes["b"].server.submit(DOC, chain_blob(7, 0))
        run_ticks(fab, nodes, 6)
        assert nodes["a"].lease.fork_refused >= 1
        assert nodes["a"].lease.lease(DOC) == (1, "a")
        assert nodes["a"].demotions == 0
        assert "a" in serving(nodes)


# ---- satellite 2: fenced checkpoint/restore ------------------------


class TestFencedRestore:
    def _seeded_server(self, store):
        srv = MultiDocServer(snap_store=store, **SERVER_KW)
        for k in range(4):
            srv.submit("w", chain_blob(7, 4 * k))
            srv.tick()
        assert srv._docs["w"].resident is not None
        return srv

    def test_restore_refuses_newer_fencing_epoch(self, tmp_path):
        """A snapshot stamped by a NEWER fencing epoch than the
        restoring process holds is poison (it was written by a later
        owner this process has not heard of): refused and counted,
        never adopted."""
        store = SnapshotStore(str(tmp_path))
        ring = HashRing(["a", "b"], vnodes=64)
        writer = LeaseTable("a", ring)
        writer.grant("w", 5, "a")
        srv = self._seeded_server(store)
        assert srv.checkpoint(fence=writer) >= 1
        # the restoring process only knows the ring default (epoch 1)
        stale = LeaseTable("a", ring)
        tracer = set_tracer(Tracer(enabled=True))
        try:
            srv2 = MultiDocServer(snap_store=store, **SERVER_KW)
            warm = srv2.restore(fence=stale)
            assert warm == 0
            assert "w" not in srv2._docs
            assert srv2.snap_fallback_count == 1
            assert tracer.counters()[
                'snap.fallbacks{reason="stale_epoch"}'] == 1
        finally:
            set_tracer(Tracer(enabled=False))

    def test_restore_admits_matching_epoch(self, tmp_path):
        store = SnapshotStore(str(tmp_path))
        ring = HashRing(["a", "b"], vnodes=64)
        writer = LeaseTable("a", ring)
        writer.grant("w", 5, "a")
        srv = self._seeded_server(store)
        d0 = srv.digest("w")
        srv.checkpoint(fence=writer)
        srv2 = MultiDocServer(snap_store=store, **SERVER_KW)
        assert srv2.restore(fence=writer) == 1
        assert srv2.digest("w") == d0
        assert srv2.snap_fallback_count == 0

    def test_unfenced_restore_unchanged(self, tmp_path):
        store = SnapshotStore(str(tmp_path))
        srv = self._seeded_server(store)
        d0 = srv.digest("w")
        srv.checkpoint()
        srv2 = MultiDocServer(snap_store=store, **SERVER_KW)
        assert srv2.restore() == 1
        assert srv2.digest("w") == d0


# ---- the placement loop (advice in, migrations out) ----------------


class TestPlacementLoop:
    def test_advice_rows_carry_seq_and_target(self):
        """Satellite 1: the controller's rebalance advice carries a
        monotonic seq (consumer dedup) and the advised destination
        when the fleet layer wires a placement hint."""
        c = Controller(cooldown_ticks=0)
        c.observe({
            "tick": 7,
            "budget": {"max_bytes": 2048, "max_updates": 4},
            "tenants": {DOC: {"burn": 1.0}},
        })
        adv = c.advice()
        assert len(adv) == 1
        assert adv[0]["seq"] == 1
        assert adv[0]["target"] is None
        ring = HashRing(MEMBERS, vnodes=64)
        c.placement_hint = lambda t: ring.least_loaded_successor(
            t, exclude=["a"], loads={"b": 9.0, "c": 1.0})
        assert c.advice()[0]["target"] == "c"

    def test_duplicated_and_replayed_advice_is_idempotent(
            self, tmp_path):
        """The chaos schedule duplicates rows within a poll and
        replays stale rows from earlier polls; the loop must start
        exactly ONE migration per distinct breach, after the
        hysteresis streak, inside the per-tick budget."""
        fab, nodes, stores = make_fleet(tmp_path)
        seed_doc(nodes)
        ring = HashRing(MEMBERS, vnodes=64)
        loop = PlacementLoop(ring, nodes.get, hysteresis=2,
                             budget_per_tick=1)
        sched = DuplicateAdviceSchedule(seed=7, duplicate=0.9,
                                        replay=0.9)
        row = {"action": "rebalance_away", "tenant": DOC,
               "proc": "a", "seq": 1, "burn": 1.3, "target": None}
        for poll in range(6):
            mangled = sched.mangle(poll, [dict(row)])
            loop.observe(poll, mangled, loads={"b": 2.0, "c": 1.0})
            run_ticks(fab, nodes, 1)
        assert sched.injected > 0
        assert loop.dup_drops > 0
        assert loop.migrations == 1
        assert nodes["a"].migrator.started == 1
        run_ticks(fab, nodes, 6)
        assert serving(nodes) == ["c"]
        acts = [r for r in loop.ledger.rows()
                if r["action"] == "migrate"]
        assert len(acts) == 1
        assert acts[0]["tenant"] == DOC and acts[0]["dst"] == "c"

    def test_in_flight_breach_is_skipped_with_a_ledger_row(
            self, tmp_path):
        fab, nodes, stores = make_fleet(tmp_path)
        seed_doc(nodes)
        ring = HashRing(MEMBERS, vnodes=64)
        loop = PlacementLoop(ring, nodes.get, hysteresis=1)
        row = {"action": "rebalance_away", "tenant": DOC,
               "proc": "a", "seq": 1, "burn": 1.3, "target": "c"}
        loop.observe(0, [dict(row)])
        assert loop.migrations == 1
        # same breach, higher seq, while the handoff is in flight
        loop.observe(1, [dict(row, seq=2)])
        assert loop.migrations == 1
        skips = [r for r in loop.ledger.rows()
                 if r["action"] == "skip"]
        assert skips and skips[-1]["why"] == "in_flight"


# ---- the frame codec -----------------------------------------------


class TestWire:
    def test_frame_round_trip(self):
        hdr = {"kind": "offer", "doc": DOC, "epoch": 2, "proc": "a"}
        payload = wire.pack_blobs([b"one", b"", b"three"])
        frame = wire.encode_frame(hdr, payload)
        dec = wire.decode_frame(frame)
        assert dec is not None
        assert dec[0] == hdr
        assert wire.unpack_blobs(dec[1]) == [b"one", b"", b"three"]

    def test_malformed_frames_counted_not_raised(self):
        tracer = set_tracer(Tracer(enabled=True))
        try:
            assert wire.decode_frame(b"garbage") is None
            assert wire.decode_frame(b"CFR1\xff\xff\xff\xff") is None
            bad_kind = wire.encode_frame({"kind": "nope"}, b"")
            assert wire.decode_frame(bad_kind) is None
            assert wire.unpack_blobs(b"\x02\x00\x00\x00") is None
            assert tracer.counters()["fleet.frames_malformed"] == 4
        finally:
            set_tracer(Tracer(enabled=False))

    def test_fabric_drops_malformed_without_counting_codec(self):
        fab = MemFabric()

        class _Sink:
            def __init__(self):
                self.got = []

            def handle(self, src, data):
                self.got.append(data)

        node = FleetNode("a", MEMBERS, fab, beacon_every=0,
                         server_kw=dict(SERVER_KW))
        fab.send("b", "a", b"not a frame")
        assert node.drain_inbox() == 1  # delivered, decode refused
        assert node.server._docs == {}


# ---- three OS processes, and the placement loop's A/B --------------


def test_three_process_rebalance():
    """Three fleet servers in separate OS processes over sealed
    loopback UDP (``tests/fleet_procs.py rebalance``), one crash-safe
    live migration a -> c: exactly one server afterwards, every lease
    on it, the losers' fences counted, no fork, no UDP failure."""
    import json
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "fleet_procs.py"),
         "rebalance"],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(here),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rb = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rb["migrate_ok"] is True
    assert rb["completed"] == 1
    assert rb["servers"] == ["c"]
    assert rb["served_digest"] == rb["seeded_digest"]
    # b adopts the new owner from its ownership beacon
    assert rb["leases"] == {p: [2, "c"] for p in MEMBERS}
    assert rb["fence_rejects"]["a"] >= 1
    assert sum(rb["fork_refused"].values()) == 0
    assert sum(rb["udp_failed"].values()) == 0


def test_placement_loop_ab_under_chaos():
    """The placement loop on and off under the same flood, the same
    dropped acks and duplicated commits, the same duplicated and
    replayed advice: on, the flooder is moved and its burn recovers
    within budget, with an identity doc migrated and its source killed
    and revived; off, the squeeze alone never recovers. Never a
    double serve; every untouched doc byte-identical across the two."""
    from tests.fleet_procs import rebalance_leg

    leg = rebalance_leg()
    assert leg["all_identical"], leg["identical"]
    assert leg["double_serves"] == 0 and leg["forks"] == 0
    assert leg["fence_rejects"] > 0
    assert leg["migrations"] >= 1
    assert leg["migrations_completed"] >= 2
    assert leg["migration_recoveries"] >= 1
    assert leg["advice_dups"] > 0
    assert leg["recovery_ticks"] is not None
    assert leg["recovery_ticks"] <= leg["recovery_budget_ticks"]
    assert leg["recovery_ticks_off"] is None
    assert (leg["burn_end_off"] or 0.0) > 0.25
