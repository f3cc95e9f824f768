"""Round-24 placement: the deterministic ring + the fencing gate.

Pins the two properties everything in ``crdt_tpu/fleet`` leans on:

- **Ring determinism + minimal movement** — every process computes
  the SAME doc->owner map with zero communication (sha1-based
  hashing, never ``hash()``), and a member join/leave moves only the
  docs whose arc changed.
- **The fence, both ways** — the ``LeaseTable`` admit ladder (stale
  refused + counted, equal-epoch rival refused as a fork, newer
  adopted), its crash persistence through the snapshot store, and
  the registry pin: ``fleet.fence_rejects`` is DOCUMENTED in the
  README counter tables AND the tracer actually emits it with the
  documented label shape — name drift in either direction fails.
"""

import os

import pytest

from crdt_tpu.fleet.placement import (
    LEASE_BLOB,
    FencingToken,
    HashRing,
    LeaseTable,
    stable_hash,
)
from crdt_tpu.obs import Tracer, set_tracer
from crdt_tpu.storage.snapshot import SnapshotStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _quiet_obs():
    old = set_tracer(Tracer(enabled=False))
    yield
    set_tracer(old)


# ---- the hash ------------------------------------------------------


class TestStableHash:
    def test_pinned_values(self):
        """sha1-prefix hashing: stable across processes and
        interpreter runs (PYTHONHASHSEED randomizes ``hash()``; a
        ring built on it would fork the fleet's ownership map).
        Literal pins so an accidental algorithm change screams."""
        assert stable_hash("doc") == int.from_bytes(
            __import__("hashlib").sha1(b"doc").digest()[:8], "big")
        assert stable_hash("") == 0xDA39A3EE5E6B4B0D
        assert stable_hash("a#0") != stable_hash("a#1")

    def test_independent_of_pythonhashseed(self):
        # same-process proxy: str.__hash__ varies run to run, sha1
        # cannot — equality with a recomputation is the contract
        assert stable_hash("tenant-0") == stable_hash("tenant-0")


# ---- the ring ------------------------------------------------------


class TestHashRing:
    def test_owner_map_is_deterministic_and_pinned(self):
        """Two independently built rings agree doc-by-doc, and the
        concrete assignments are pinned: every fleet test and the
        bench chaos leg rely on these exact owners."""
        r1 = HashRing(["a", "b", "c"], vnodes=64)
        r2 = HashRing(["c", "a", "b"], vnodes=64)  # order-insensitive
        docs = ["doc", "w", "x", "y", "tenant-0", "flood!"]
        assert {d: r1.owner(d) for d in docs} == \
            {d: r2.owner(d) for d in docs}
        assert r1.owner("doc") == "a"
        assert r1.owner("w") == "b"
        assert r1.owner("tenant-0") == "c"
        assert r1.owner("flood!") == "b"

    def test_member_required(self):
        with pytest.raises(ValueError):
            HashRing([], vnodes=8).owner("doc")
        with pytest.raises(ValueError):
            HashRing(["a"], vnodes=0)

    def test_join_moves_only_to_the_joiner(self):
        """Minimal movement: adding ``d`` may claim docs, but every
        doc that CHANGED owner changed to ``d`` — no unrelated
        churn (the property that makes live rebalance affordable)."""
        before = HashRing(["a", "b", "c"], vnodes=64)
        after = HashRing(["a", "b", "c", "d"], vnodes=64)
        docs = ["d%d" % i for i in range(200)]
        moved = [d for d in docs if before.owner(d) != after.owner(d)]
        assert moved, "a joining member should claim some arc"
        assert all(after.owner(d) == "d" for d in moved)

    def test_leave_moves_only_the_leavers_docs(self):
        before = HashRing(["a", "b", "c"], vnodes=64)
        after = HashRing(["a", "b", "c"], vnodes=64)
        after.remove("c")
        docs = ["d%d" % i for i in range(200)]
        for d in docs:
            if before.owner(d) != "c":
                assert after.owner(d) == before.owner(d)
            else:
                assert after.owner(d) in ("a", "b")

    def test_successors_distinct_owner_first(self):
        r = HashRing(["a", "b", "c"], vnodes=64)
        succ = r.successors("doc", 3)
        assert succ[0] == r.owner("doc")
        assert sorted(succ) == ["a", "b", "c"]  # distinct, all
        assert r.successors("doc", 2) == succ[:2]

    def test_least_loaded_successor(self):
        r = HashRing(["a", "b", "c"], vnodes=64)
        # owner excluded; smallest load wins; ties break by name so
        # every process computes the same hint
        dst = r.least_loaded_successor(
            "doc", exclude=["a"], loads={"b": 10.0, "c": 1.0})
        assert dst == "c"
        assert r.least_loaded_successor(
            "doc", exclude=["a"], loads={"b": 5.0, "c": 5.0}) == "b"
        # no loads: ring order decides (deterministic fallback)
        assert r.least_loaded_successor("doc", exclude=["a"]) in \
            ("b", "c")
        assert r.least_loaded_successor(
            "doc", exclude=["a", "b", "c"]) is None


# ---- the fence -----------------------------------------------------


class TestLeaseTable:
    def _table(self, proc="a", store=None):
        return LeaseTable(proc, HashRing(["a", "b", "c"], vnodes=64),
                          store=store)

    def test_ring_seeded_epoch_one(self):
        """Every process derives the same initial (epoch, owner)
        with zero communication: epoch 1, the ring arc owner."""
        t = self._table("a")
        assert t.lease("doc") == (1, "a")
        assert t.holds("doc") and not t.holds("w")
        assert t.token("doc") == FencingToken(1, "a")
        assert t.owned_docs(["doc", "w", "tenant-0"]) == ["doc"]
        assert t.epochs_of(["doc", "w"]) == {"doc": 1, "w": 1}
        assert t.recorded() == {}  # nothing explicitly granted yet

    def test_grant_ladder(self):
        t = self._table("a")
        assert t.grant("doc", 2, "c")           # forward: recorded
        assert t.lease("doc") == (2, "c")
        assert not t.holds("doc")
        assert not t.grant("doc", 1, "a")       # backward: stale
        assert t.fence_rejects == 1
        assert not t.grant("doc", 2, "b")       # equal-epoch rival
        assert t.fork_refused == 1
        assert t.grant("doc", 2, "c")           # idempotent re-grant
        assert t.lease("doc") == (2, "c")

    def test_admit_ladder(self):
        t = self._table("a")
        # stale epoch refused + counted
        t.grant("doc", 3, "a")
        assert not t.admit("doc", FencingToken(2, "b"), op="update")
        assert t.fence_rejects == 1
        # equal epoch, different claimant: fork refused
        assert not t.admit("doc", FencingToken(3, "b"), op="update")
        assert t.fork_refused == 1
        assert t.lease("doc") == (3, "a")
        # equal epoch, the recorded owner: admitted, no change
        assert t.admit("doc", FencingToken(3, "a"), op="update")
        # newer epoch: adopted AND admitted (higher epoch wins —
        # the partition-heal path)
        assert t.admit("doc", FencingToken(5, "b"), op="beacon")
        assert t.lease("doc") == (5, "b")

    def test_fence_reject_tracer_labels(self):
        tracer = set_tracer(Tracer(enabled=True))
        try:
            t = self._table("a")
            t.grant("doc", 3, "a")
            t.admit("doc", FencingToken(1, "b"), op="serve")
            t.admit("doc", FencingToken(1, "b"), op="update")
            t.admit("doc", FencingToken(3, "b"), op="update")
            counters = tracer.counters()
            assert counters['fleet.fence_rejects{op="serve"}'] == 1
            assert counters['fleet.fence_rejects{op="update"}'] == 1
            assert counters["fleet.fork_refused"] == 1
        finally:
            set_tracer(Tracer(enabled=False))

    def test_persistence_round_trip(self, tmp_path):
        """The crash-safety half: grants survive a restart through
        the snapshot store, so a revived process resumes with the
        epochs it held — never the ring defaults."""
        store = SnapshotStore(str(tmp_path))
        t = self._table("a", store=store)
        t.grant("doc", 4, "c")
        t.grant("w", 7, "a")
        raw = store.get_blob(LEASE_BLOB)
        assert raw is not None and b'"doc"' in raw
        t2 = self._table("a", store=store)
        assert t2.lease("doc") == (4, "c")
        assert t2.lease("w") == (7, "a")
        assert t2.holds("w") and not t2.holds("doc")
        # a stale grant is STILL refused after the restart
        assert not t2.grant("doc", 3, "a")
        assert t2.fence_rejects == 1

    def test_corrupt_lease_blob_falls_back_to_ring(self, tmp_path):
        store = SnapshotStore(str(tmp_path))
        store.put_blob(LEASE_BLOB, b"not json {")
        t = self._table("a", store=store)
        assert t.lease("doc") == (1, "a")
        store.put_blob(LEASE_BLOB, b'{"doc": "bogus", "w": [9, "b"]}')
        t2 = self._table("a", store=store)
        assert t2.lease("doc") == (1, "a")  # malformed row skipped
        assert t2.lease("w") == (9, "b")


# ---- the registry pin (both directions) ----------------------------


def test_fence_counters_documented_in_registry():
    """The README counter tables must carry the round-24 fencing
    names — ``tools/crdtlint`` lints emissions against this registry,
    so a name dropping out silently un-checks the namespace."""
    from tools.crdtlint.registry import NAMESPACES, load_registry

    reg = load_registry(
        os.path.join(REPO, "README.md"),
        os.path.join(REPO, "tests", "test_registry.py"),
    )
    for name in (
        "fleet.fence_rejects", "fleet.fork_refused",
        "fleet.redirects", "fleet.demotions", "fleet.beacons_sent",
        "fleet.frames_malformed", "fleet.advice_dups",
        "fleet.migrations_started",
        "migration.started", "migration.completed",
        "migration.recovery", "migration.tail_blobs",
        "migration.tail_restores",
        "snap.fallbacks",
    ):
        assert name in reg.metrics, (
            f"{name} missing from the README registry tables "
            f"(round-24 fleet contract)"
        )
    assert "migration" in NAMESPACES, (
        "the migration.* namespace must be registry-checked, not "
        "an allowlist hole"
    )
