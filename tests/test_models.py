"""ReplicaFleet — flagship batched convergence model on the CPU mesh."""

import numpy as np
import pytest

from crdt_tpu.models import FleetStep, ReplicaFleet
from crdt_tpu.utils import Tracer, get_tracer, set_tracer


def test_fleet_step_shapes_and_handshake():
    fleet = ReplicaFleet(8, 16, n_devices=4, num_clients=10, num_segments=256)
    cols, dels = fleet.synth(num_maps=2, keys_per_map=8)
    out = fleet.step(cols, dels)
    assert isinstance(out, FleetStep)
    assert out.sv_local.shape == (8, 10)
    assert out.global_sv.shape == (10,)
    assert all(out.global_sv[r + 1] == 16 for r in range(8))
    assert out.deficit.shape == (8, 8)
    assert out.deficit[0][0] == 0 and out.deficit[0][1] == 16
    assert (out.winners >= 0).sum() > 0


def test_fleet_winners_match_scalar_oracle():
    """The fleet's converged LWW winners equal the host engine's on the
    same op set (differential test at the model level)."""
    from crdt_tpu.core.engine import Engine
    from crdt_tpu.core.ids import DeleteSet
    from crdt_tpu.core.records import ItemRecord

    fleet = ReplicaFleet(4, 8, n_devices=4, num_clients=8, num_segments=64)
    cols, dels = fleet.synth(num_maps=2, keys_per_map=4, seed=3)

    # replay the identical ops through the scalar engine
    records = []
    R, N = cols["client"].shape
    for r in range(R):
        for k in range(N):
            records.append(
                ItemRecord(
                    client=int(cols["client"][r, k]),
                    clock=int(cols["clock"][r, k]),
                    parent_root=f"m{int(cols['parent_a'][r, k])}",
                    key=f"k{int(cols['key_id'][r, k])}",
                    content=0,
                )
            )
    eng = Engine(0)
    eng.apply_records(records, DeleteSet())
    oracle = eng.map_winner_table()

    out = fleet.step(cols, dels)
    # map winner ids: reconstruct from flattened op order
    flat_client = cols["client"].reshape(-1)
    flat_clock = cols["clock"].reshape(-1)
    got = {}
    # fleet orders ops by sorted packed id; winners index into that order
    order = np.lexsort((flat_clock, flat_client))
    for w, vis in zip(out.winners, out.winner_visible):
        if w < 0 or w >= len(order):
            continue
        i = order[w]
        r, k = divmod(int(i), N)
        key = (("root", f"m{int(cols['parent_a'][r, k])}"),
               f"k{int(cols['key_id'][r, k])}")
        got[key] = ((int(flat_client[i]), int(flat_clock[i])), bool(vis))
    assert got == oracle


def test_fleet_rejects_uneven_sharding():
    with pytest.raises(ValueError, match="divide"):
        ReplicaFleet(5, 8, n_devices=4)


def test_fleet_traces_step():
    old = get_tracer()
    tr = set_tracer(Tracer(enabled=True))
    try:
        fleet = ReplicaFleet(4, 4, n_devices=2, num_clients=6, num_segments=64)
        cols, dels = fleet.synth(num_maps=1, keys_per_map=4)
        fleet.step(cols, dels)
        rep = tr.report()
        assert rep["spans"]["fleet.step"]["count"] == 1
        assert rep["counters"]["fleet.ops_converged"] == 16
    finally:
        set_tracer(old)


class TestTraceReplay:
    """BASELINE config #5 as a product API: trace replay + snapshot
    compaction through the firehose path, differential against the
    scalar document."""

    def _trace(self, n_peers=6, ops=12):
        from crdt_tpu.api.doc import Crdt

        blobs = []
        docs = []
        for i in range(n_peers):
            out = []
            d = Crdt(i + 1, on_update=lambda u, m: out.append(u))
            docs.append((d, out))
        for i, (d, out) in enumerate(docs):
            for k in range(ops):
                if k % 3 == 0:
                    d.push("log", [f"p{i}.{k}"])
                else:
                    d.set("m", f"k{(i * ops + k) % 10}", i * ops + k)
            # nested array under a map key (each doc creates its own;
            # LWW shadows all but one — the replay must agree with
            # the scalar document on which one and on its contents)
            d.set("nested", "l", f"n{i}", array_method="push")
            d.set("nested", "l", f"m{i}", array_method="push")
            d.delete("m", f"k{i % 10}")
            blobs.extend(out)
        return blobs

    def test_replay_matches_scalar_document(self):
        from crdt_tpu.api.doc import Crdt
        from crdt_tpu.models.replay import replay_trace

        blobs = self._trace()
        res = replay_trace(blobs)

        oracle = Crdt(999)
        oracle.apply_updates(blobs)
        assert res.cache == dict(oracle.c)
        assert res.n_ops > 0

        # the compacted snapshot alone rebuilds the same state
        fresh = Crdt(998)
        fresh.apply_update(res.snapshot)
        assert dict(fresh.c) == res.cache

    def test_replay_empty_and_single(self):
        from crdt_tpu.models.replay import replay_trace

        res = replay_trace([])
        assert res.cache == {} and res.n_ops == 0

        from crdt_tpu.api.doc import Crdt

        out = []
        d = Crdt(5, on_update=lambda u, m: out.append(u))
        d.set("solo", "k", [1, 2])
        res = replay_trace(out)
        assert res.cache == {"solo": {"k": [1, 2]}}

    def test_fully_tombstoned_root_still_materializes_empty(self):
        from crdt_tpu.api.doc import Crdt
        from crdt_tpu.models.replay import replay_trace

        out = []
        d = Crdt(3, on_update=lambda u, m: out.append(u))
        d.set("gone", "k", 1)
        d.delete("gone", "k")
        d.set("other", "x", 2)
        res = replay_trace(out)
        oracle = Crdt(99)
        oracle.apply_updates(out)
        assert res.cache == dict(oracle.c)
        assert res.cache["gone"] == {}


class TestFleetIntegration:
    def test_fleet_on_2d_mesh_matches_1d(self):
        """ReplicaFleet accepts a (hosts, replicas) mesh and produces
        the flat mesh's exact outputs."""
        from crdt_tpu.models import ReplicaFleet
        from crdt_tpu.parallel.gossip import make_mesh2d

        R, N = 16, 16
        flat = ReplicaFleet(R, N, n_devices=8, num_clients=R + 2,
                            num_segments=256)
        cols, dels = flat.synth(num_maps=2, keys_per_map=8, num_lists=2)
        out1 = flat.step(cols, dels)

        hier = ReplicaFleet(R, N, mesh=make_mesh2d(2, 4),
                            num_clients=R + 2, num_segments=256)
        out2 = hier.step(cols, dels)
        import numpy as np

        for name, a, b in zip(out1._fields, out1, out2):
            np.testing.assert_array_equal(a, b, err_msg=name)

    def test_fleet_delta_round(self):
        """The targeted anti-entropy round is reachable straight from
        the fleet: needed counts equal the per-replica fresh rows."""
        import numpy as np

        from crdt_tpu.models import ReplicaFleet
        from crdt_tpu.parallel.delta import synth_resident_columns

        fleet = ReplicaFleet(8, 104, n_devices=8, num_clients=10,
                             num_segments=256)
        cols = synth_resident_columns(8, 96, 8, seed=4)
        svs, deficit, needed, delta = fleet.delta_round(cols, budget=16)
        np.testing.assert_array_equal(needed, np.full(8, 8))
        assert len(delta["client"]) == 8 * 16  # R * budget, not R * N
        assert deficit[0, 1] == 8

    def test_replay_handles_prepends_and_inserts(self):
        """Honest right origins (multi-client prepends, mid-inserts)
        must replay to the document's exact order — the append-only
        kernel path hands those sequences to the host machinery."""
        from crdt_tpu.api.doc import Crdt
        from crdt_tpu.models.replay import replay_trace

        out1, out2 = [], []
        a = Crdt(1, on_update=lambda u, m: out1.append(u))
        b = Crdt(2, on_update=lambda u, m: out2.append(u))
        a.push("l", ["base1", "base2"])
        for u in out1:
            b.apply_update(u)
        b.unshift("l", "pre")
        b.insert("l", 2, "mid")
        a.insert("l", 1, "amid")
        blobs = out1 + out2
        res = replay_trace(blobs)
        oracle = Crdt(9)
        oracle.apply_updates(blobs)
        assert res.cache == dict(oracle.c), (res.cache, dict(oracle.c))
        fresh = Crdt(8)
        fresh.apply_update(res.snapshot)
        assert dict(fresh.c) == res.cache

    def test_replay_redelivered_blobs_with_map_rights(self):
        """Duplicate delivery of a blob containing crafted map rights
        must not drop keys (the dedup inside the scalar fallback)."""
        from crdt_tpu.api.doc import Crdt
        from crdt_tpu.codec import v1
        from crdt_tpu.core.records import ItemRecord
        from crdt_tpu.models.replay import replay_trace

        blob = v1.encode_update([
            ItemRecord(client=1, clock=0, parent_root="m", key="k",
                       content="A"),
            ItemRecord(client=2, clock=0, parent_root="m", key="k",
                       right=(1, 0), content="B"),
        ], None)
        res = replay_trace([blob, blob])  # at-least-once redelivery
        oracle = Crdt(9)
        oracle.apply_updates([blob, blob])
        assert res.cache == dict(oracle.c)
        assert res.cache["m"]["k"] == "A"
        # the compacted snapshot from the redelivered trace rebuilds
        # the same state (duplicate rows must not corrupt the encode)
        fresh = Crdt(8)
        fresh.apply_update(res.snapshot)
        assert dict(fresh.c) == res.cache

    def test_redelivered_interactive_trace_snapshot(self):
        """Prepends + inserts + cuts + a redelivered prefix: replay and
        its compacted snapshot both match the live document."""
        from crdt_tpu.api.doc import Crdt
        from crdt_tpu.models.replay import replay_trace

        out1, out2 = [], []
        a = Crdt(1, on_update=lambda u, m: out1.append(u))
        b = Crdt(2, on_update=lambda u, m: out2.append(u))
        a.push("text", ["one", "two", "three"])
        for u in out1:
            b.apply_update(u)
        b.unshift("text", "zero")
        b.insert("text", 2, "1.5")
        b.cut("text", 4)
        a.insert("text", 1, "a-mid")
        a.set("meta", "title", "notes")
        blobs = out1 + out2 + out1  # at-least-once prefix redelivery
        res = replay_trace(blobs)
        oracle = Crdt(9)
        oracle.apply_updates(blobs)
        assert res.cache == dict(oracle.c)
        fresh = Crdt(8)
        fresh.apply_update(res.snapshot)
        assert dict(fresh.c) == res.cache

    def test_dedup_does_not_alias_large_client_ids(self):
        """Distinct 31-bit clients differing by a multiple of 2^24 must
        both survive dedup (the old packed key aliased them)."""
        from crdt_tpu.api.doc import Crdt
        from crdt_tpu.models.replay import replay_trace

        c1, c2 = 1 << 24, 2 << 24
        out = []
        a = Crdt(c1, on_update=lambda u, m: out.append(u))
        b = Crdt(c2, on_update=lambda u, m: out.append(u))
        a.set("m", "ka", "A")
        b.set("m", "kb", "B")
        res = replay_trace(out + out)  # with redelivery
        assert res.cache["m"] == {"ka": "A", "kb": "B"}

    def test_mixed_append_and_prepend_parents_stay_selective(self):
        """Only right-bearing parents re-order on host; a pure-append
        list in the same trace keeps its (correct) kernel order."""
        from crdt_tpu.api.doc import Crdt
        from crdt_tpu.models.replay import replay_trace

        out = []
        a = Crdt(1, on_update=lambda u, m: out.append(u))
        b = Crdt(2, on_update=lambda u, m: out.append(u))
        for i in range(10):
            a.push("appendy", [i])
        a.push("edity", ["base"])
        for u in list(out):
            b.apply_update(u)
        b.unshift("edity", "pre")
        res = replay_trace(out)
        oracle = Crdt(9)
        oracle.apply_updates(out)
        assert res.cache == dict(oracle.c)
        assert res.cache["edity"] == ["pre", "base"]
        assert res.cache["appendy"] == list(range(10))


from crdt_tpu.models import replay_trace


class TestReplayRoutes:
    """replay_trace's convergence engines must be interchangeable:
    "device" (packed pipeline, the differential-oracle default),
    "host" (the identical fused kernel on the local CPU backend), and
    "replica" (the incremental machinery a resident replica uses to
    ingest the same backlog) produce identical results; "auto" picks
    by the session-calibrated crossover and records its choice."""

    def test_all_routes_agree(self):
        import workloads as B

        for builder in (B.build_trace, B.build_conflict_trace,
                        B.build_text_trace):
            blobs = builder(30, 20)
            dev = replay_trace(blobs, route="device")
            host = replay_trace(blobs, route="host")
            rep = replay_trace(blobs, route="replica")
            assert dev.path == "device" and host.path == "host"
            assert rep.path == "replica"
            assert host.cache == dev.cache, builder.__name__
            assert host.snapshot == dev.snapshot, builder.__name__
            assert rep.cache == dev.cache, builder.__name__
            assert rep.snapshot == dev.snapshot, builder.__name__

    def test_auto_records_its_choice(self):
        import workloads as B

        blobs = B.build_trace(10, 10)
        res = replay_trace(blobs, route="auto")
        assert res.path in ("host", "replica", "device")
        assert res.cache == replay_trace(blobs, route="device").cache

    def test_unknown_route_rejected(self):
        import pytest

        with pytest.raises(ValueError):
            replay_trace([], route="warp")
