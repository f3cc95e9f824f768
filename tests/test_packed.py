"""Packed one-dispatch replay path (crdt_tpu.ops.packed).

The end-to-end exactness of this path is covered by the replay
differentials (tests/test_models.py, tests/test_grand_differential.py,
which route through it); these tests pin the staging contract and the
branches those suites do not reach: wide clocks, bound fallbacks, and
the stream layout.
"""

import numpy as np

from crdt_tpu.ops import packed


def _cols(n, *, clock_base=0, clients=None, seq=False):
    clients = clients if clients is not None else np.arange(1, n + 1)
    kid = np.full(n, -1 if seq else 0, np.int64)
    origin_c = np.full(n, -1, np.int64)
    origin_k = np.full(n, -1, np.int64)
    return {
        "client": np.asarray(clients, np.int64),
        "clock": np.arange(n, dtype=np.int64) + clock_base,
        "parent_is_root": np.ones(n, bool),
        "parent_a": np.zeros(n, np.int64),
        "parent_b": np.full(n, -1, np.int64),
        "key_id": kid,
        "origin_client": origin_c,
        "origin_clock": origin_k,
        "valid": np.ones(n, bool),
    }


class TestStage:
    def test_staged_matrix(self):
        # a tiny batch narrows to the int16 transfer-diet layout: one
        # flat array of the eight layout sections
        plan = packed.stage(_cols(8))
        assert plan is not None
        assert plan.mat.dtype == np.int16 and plan.mat.ndim == 1
        assert len(plan.encs) == len(packed.SECTION_NAMES)
        assert all(e in ("i16", "d16", "hilo") for e in plan.encs)
        assert plan.n == 8

    def test_forced_wide_matrix(self):
        plan = packed.stage(_cols(8), wide=True)
        assert plan is not None
        assert plan.mat.dtype == np.int32 and plan.mat.ndim == 1
        assert all(e == "i32" for e in plan.encs)

    def test_wide_clock_stays_packed(self):
        # clocks below the shared pack_id bound stay on the packed path
        plan = packed.stage(_cols(8, clock_base=1 << 33))
        assert plan is not None and plan.mat.dtype in (
            np.int16, np.int32
        )

    def test_clock_beyond_pack_bound_falls_back(self):
        assert packed.stage(_cols(8, clock_base=1 << 40)) is None

    def test_empty_returns_none(self):
        cols = _cols(4)
        cols["valid"][:] = False
        assert packed.stage(cols) is None
        assert packed.stage(_cols(0)) is None

    def test_key_bound_fallback(self):
        cols = _cols(4)
        cols["key_id"][:] = 1 << packed._KID_BITS
        assert packed.stage(cols) is None

    def test_seq_bucket_covers_seq_rows(self):
        cols = _cols(200, clients=np.ones(200), seq=True)
        plan = packed.stage(cols)
        assert plan.seq_bucket >= 200

    def test_map_bucket_tracks_map_rows_not_padded_n(self):
        # the round-12 satellite: the map chain runs at MAP-BUCKET
        # width (mirroring the seq compact block), so a seq-heavy
        # union must get a map bucket far below the padded kernel
        # width
        n = 600
        cols = _cols(n, clients=np.ones(n), seq=True)
        cols["key_id"][:8] = 0  # 8 map rows in a 600-row union
        plan = packed.stage(cols)
        assert plan.map_bucket <= 64  # bucket of 8, not of 600
        assert plan.seq_bucket >= n - 8
        assert len(plan.map_back) == plan.map_bucket

    def test_client_interning_order_preserving(self):
        cols = _cols(3, clients=np.array([900, 5, 37]))
        plan = packed.stage(cols)
        assert list(plan.clients) == [5, 37, 900]
        # rows stage id-sorted: the sort permutation maps each staged
        # row back to its caller row, and the grouped map block (one
        # root run here) keeps that id order in its translation table
        assert list(plan.order[:3]) == [1, 2, 0]
        assert list(plan.map_back[:3]) == [1, 2, 0]


class TestConverge:
    def test_map_winners_and_stream(self):
        # 3 clients set the same key; client 3 wins (no chains)
        cols = _cols(3, clients=np.array([1, 2, 3]))
        cols["clock"][:] = 0
        plan = packed.stage(cols)
        res = packed.converge(plan)
        wins = res.win_rows[res.win_rows >= 0]
        assert list(wins) == [2]
        assert not (res.stream_row >= 0).any()

    def test_sequence_stream_document_order(self):
        # one client appends a 5-chain: stream = rows in append order
        n = 5
        cols = _cols(n, clients=np.ones(n), seq=True)
        cols["origin_client"] = np.asarray([-1, 1, 1, 1, 1], np.int64)
        cols["origin_clock"] = np.asarray([-1, 0, 1, 2, 3], np.int64)
        plan = packed.stage(cols)
        res = packed.converge(plan)
        rows = res.stream_row[res.stream_row >= 0]
        assert list(rows) == [0, 1, 2, 3, 4]
        segs = res.stream_seg[res.stream_seg >= 0]
        assert len(set(segs.tolist())) == 1

    def test_duplicate_ids_dedup(self):
        # same (client, clock) delivered twice: one winner, first row kept
        cols = _cols(2, clients=np.array([7, 7]))
        cols["clock"][:] = 0
        plan = packed.stage(cols)
        res = packed.converge(plan)
        wins = res.win_rows[res.win_rows >= 0]
        assert len(wins) == 1

    def test_resident_fallback_matches_packed(self, monkeypatch):
        """The general resident path (taken when stage() refuses a
        batch) must produce the same replay result as the packed
        path — forced here by stubbing stage to refuse."""
        from crdt_tpu.codec import v1
        from crdt_tpu.core.ids import DeleteSet
        from crdt_tpu.core.records import ItemRecord
        from crdt_tpu.models import replay_trace

        rng = np.random.default_rng(5)
        blobs = []
        for client in (1, 2, 3):
            recs, ds = [], DeleteSet()
            prev = None
            for k in range(30):
                if k % 3 == 0:
                    recs.append(ItemRecord(
                        client=client, clock=k, parent_root="m",
                        key=f"k{int(rng.integers(0, 4))}", content=k))
                else:
                    recs.append(ItemRecord(
                        client=client, clock=k, parent_root="L",
                        origin=(client, prev) if prev is not None else None,
                        content=k))
                    prev = k
            ds.add(client, 1)
            blobs.append(v1.encode_update(recs, ds))

        want = replay_trace(blobs)  # packed path
        monkeypatch.setattr(packed, "stage", lambda cols, **kw: None)
        got = replay_trace(blobs)   # resident fallback
        assert got.cache == want.cache
        assert got.snapshot == want.snapshot

    def test_wide_path_matches_narrow(self):
        n = 40
        rng = np.random.default_rng(0)
        base = _cols(n, clients=rng.integers(1, 6, n), seq=True)
        base["origin_client"][:] = -1
        base["origin_clock"][:] = -1
        narrow = packed.converge(packed.stage(base))
        wide_cols = {k: v.copy() for k, v in base.items()}
        wide_cols["clock"] = wide_cols["clock"] + (1 << 33)
        wide = packed.converge(packed.stage(wide_cols))
        n_rows = narrow.stream_row[narrow.stream_row >= 0]
        w_rows = wide.stream_row[wide.stream_row >= 0]
        assert list(n_rows) == list(w_rows)


class TestStagedRightOrdering:
    """The packed path orders attachment groups at staging
    (ops.packed._stage_rights): exact conflict-scan ranks ride the
    client column into the fused dispatch. These differentials target
    the shapes that killed the earlier closed-form attempt —
    prepend trees with client drift — plus hard shapes that must
    still take the scalar fallback."""

    @staticmethod
    def _replay_vs_engine(blobs):
        from crdt_tpu.codec import v1
        from crdt_tpu.core.engine import Engine
        from crdt_tpu.models import replay_trace

        out = replay_trace(blobs)
        eng = Engine(10**6)
        for b in blobs:
            v1.apply_update(eng, b)
        assert out.cache == eng_cache(eng), (out.cache, eng_cache(eng))
        return out

    def test_prepend_storm_with_client_drift(self):
        """Every writer keeps prepending at the head (origin None,
        right = current head) — the order depends on the full conflict
        scan, and writers' client ids interleave both ways."""
        import numpy as np

        from crdt_tpu.codec import v1
        from crdt_tpu.core.ids import DeleteSet
        from crdt_tpu.core.records import ItemRecord

        for seed in range(6):
            rng = np.random.default_rng(seed)
            # client ids straddle each other so the scan's client
            # comparisons flip direction between groups
            clients = [int(c) for c in rng.permutation([3, 50, 7000, 2])]
            blobs = []
            heads: dict = {}
            for client in clients:
                recs = []
                head = None
                for k in range(12):
                    recs.append(ItemRecord(
                        client=client, clock=k, parent_root="L",
                        origin=None, right=head, content=f"{client}:{k}"))
                    head = (client, k)
                heads[client] = head
                blobs.append(v1.encode_update(recs, DeleteSet()))
            order = rng.permutation(len(blobs))
            self._replay_vs_engine([blobs[i] for i in order])

    def test_mixed_mid_inserts_vs_engine(self):
        """Random interleaved typing with 35% mid-inserts carrying
        both origins, shuffled delivery with duplicates."""
        import numpy as np

        from crdt_tpu.codec import v1
        from crdt_tpu.core.ids import DeleteSet
        from crdt_tpu.core.records import ItemRecord

        for seed in range(5):
            rng = np.random.default_rng(100 + seed)
            blobs = []
            for r in range(4):
                client = [5, 80, 3, 900][r]
                recs, chain = [], []
                for k in range(25):
                    if chain and rng.random() < 0.35:
                        j = int(rng.integers(0, len(chain)))
                        recs.append(ItemRecord(
                            client=client, clock=k, parent_root="text",
                            origin=chain[j - 1] if j > 0 else None,
                            right=chain[j], content=k))
                        chain.insert(j, (client, k))
                    else:
                        recs.append(ItemRecord(
                            client=client, clock=k, parent_root="text",
                            origin=chain[-1] if chain else None,
                            content=k))
                        chain.append((client, k))
                blobs.append(v1.encode_update(recs, DeleteSet()))
            delivery = blobs + [blobs[int(rng.integers(0, 4))]]  # dup
            rng.shuffle(delivery)
            self._replay_vs_engine(delivery)

    def test_hard_shape_takes_fallback(self):
        """A right pointing INTO a member's subtree is inexpressible
        by sibling ranks: the plan must mark the segment hard and the
        result must still match the engine."""
        from crdt_tpu.codec import v1
        from crdt_tpu.core.ids import DeleteSet
        from crdt_tpu.core.records import ItemRecord
        from crdt_tpu.models import replay as rp
        from crdt_tpu.ops import packed

        recs = [
            ItemRecord(client=1, clock=0, parent_root="L", content="a"),
            ItemRecord(client=1, clock=1, parent_root="L",
                       origin=(1, 0), content="b"),
            # c attaches under b (subtree of a's sibling group member)
            ItemRecord(client=1, clock=2, parent_root="L",
                       origin=(1, 1), content="c"),
            # hostile: same-origin sibling whose right dives into b's
            # subtree (points at c, a DESCENDANT of member b)
            ItemRecord(client=2, clock=0, parent_root="L",
                       origin=(1, 0), right=(1, 2), content="X"),
        ]
        blob = v1.encode_update(recs, DeleteSet())
        dec = rp.decode([blob])
        cols, _ = rp.stage(dec)
        plan = packed.stage(cols)
        assert plan is not None and len(plan.hard_rows) > 0
        self._replay_vs_engine([blob])

    def test_dangling_right_marks_hard(self):
        from crdt_tpu.codec import v1
        from crdt_tpu.core.ids import DeleteSet
        from crdt_tpu.core.records import ItemRecord
        from crdt_tpu.models import replay as rp
        from crdt_tpu.ops import packed

        recs = [
            ItemRecord(client=1, clock=0, parent_root="L", content="a"),
            ItemRecord(client=2, clock=0, parent_root="L",
                       origin=(1, 0), right=(77, 5), content="X"),
        ]
        blob = v1.encode_update(recs, DeleteSet())
        dec = rp.decode([blob])
        cols, _ = rp.stage(dec)
        plan = packed.stage(cols)
        assert plan is not None and len(plan.hard_rows) > 0

    def test_clean_attachments_stage_without_fallback(self):
        """The bench's text shape (mid-inserts, all refs resolvable)
        must produce ZERO hard segments — the whole point of staged
        ordering."""
        import numpy as np

        from crdt_tpu.codec import v1
        from crdt_tpu.core.ids import DeleteSet
        from crdt_tpu.core.records import ItemRecord
        from crdt_tpu.models import replay as rp
        from crdt_tpu.ops import packed

        rng = np.random.default_rng(7)
        blobs = []
        for r in range(3):
            client, recs, chain = r + 1, [], []
            for k in range(30):
                if chain and rng.random() < 0.3:
                    j = int(rng.integers(0, len(chain)))
                    recs.append(ItemRecord(
                        client=client, clock=k, parent_root="text",
                        origin=chain[j - 1] if j > 0 else None,
                        right=chain[j], content=k))
                    chain.insert(j, (client, k))
                else:
                    recs.append(ItemRecord(
                        client=client, clock=k, parent_root="text",
                        origin=chain[-1] if chain else None, content=k))
                    chain.append((client, k))
            blobs.append(v1.encode_update(recs, DeleteSet()))
        dec = rp.decode(blobs)
        cols, _ = rp.stage(dec)
        plan = packed.stage(cols)
        assert plan is not None and len(plan.hard_rows) == 0
        self._replay_vs_engine(blobs)


def eng_cache(eng):
    """Visible JSON of an engine — same shape replay_trace's cache has."""
    return eng.to_json()


def test_new_shape_counted_once_per_compile_key(monkeypatch):
    """``converge.new_shape`` names each fused-converge compile key
    (program, rounds, plan length) at its first dispatch in the
    process, and never again for the same key."""
    from crdt_tpu.obs import Tracer, set_tracer

    monkeypatch.setattr(packed, "_SEEN_SHAPES", set())
    tracer = set_tracer(Tracer(enabled=True))
    try:
        for n in (8, 8, 40):
            packed.converge(packed.stage(_cols(n)))
        got = tracer.counters("converge.new_shape")
        assert len(got) == 2 and set(got.values()) == {1}, got
        for key in got:
            assert 'program="_converge_packed"' in key
            assert "rank_rounds=" in key and "map_rounds=" in key
            assert 'plan="int16[' in key
    finally:
        set_tracer(Tracer(enabled=False))
