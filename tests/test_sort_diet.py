"""Sort diet (round 12): Pallas segmented-merge kernels, differential.

Two layers of oracle under test, both against the SAME jnp fallbacks
that production uses for non-TPU backends and past-width-guard blocks:

1. **Kernel differentials.** ``seg_argmax_scan`` and
   ``stream_scatter`` under ``CRDT_TPU_PALLAS=interpret`` must equal
   their jnp oracles (``*_jnp``) at every position — single-row runs,
   one whole-block run, random run layouts, ragged (non-tile-multiple)
   lengths, ties on the major key, and out-of-range scatter targets.
2. **Route differentials.** The fused converge driven through the
   interpret-mode kernels must produce byte-identical cache + snapshot
   to the jnp path (``CRDT_TPU_PALLAS=0``) across the one-shot,
   streaming, and incremental routes — including int16-narrowed and
   hi/lo staging edges, delete-only chunks, single-row and
   crossover-width segments, and clock ties at 2^15-1 / 2^31-1.

The width guard's fallback (a block past ``_SCAN_PALLAS_MAX`` must
take the jnp path and count ``converge.pallas_fallback``) is pinned
with a shrunken guard, not a 128k-row trace.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from crdt_tpu.codec import v1
from crdt_tpu.core.engine import Engine
from crdt_tpu.core.ids import DeleteSet
from crdt_tpu.core.records import ItemRecord
from crdt_tpu.models import replay_trace, stream_replay
from crdt_tpu.obs import Tracer, get_tracer, set_tracer
from crdt_tpu.ops import packed
from crdt_tpu.ops import pallas_kernels as pk


@pytest.fixture
def tracer():
    old = get_tracer()
    tr = set_tracer(Tracer(enabled=True))
    try:
        yield tr
    finally:
        set_tracer(old)


# ---------------------------------------------------------------------------
# kernel differentials: interpret-mode pallas vs the jnp oracle
# ---------------------------------------------------------------------------


def _run_layout(rng, n, runs):
    """Random (client, flags) with `runs` run-start positions."""
    client = rng.integers(0, 1 << 14, n).astype(np.int32)
    flags = np.zeros(n, np.int32)
    flags[0] = 1
    if runs > 1:
        starts = rng.choice(np.arange(1, n), size=min(runs - 1, n - 1),
                            replace=False)
        flags[starts] = 1
    return client, flags


class TestSegArgmaxScan:
    @pytest.mark.parametrize("n,runs", [
        (1, 1),            # single row
        (7, 7),            # every row its own run (single-row segments)
        (128, 1),          # one whole-lane-row run
        (1000, 37),        # ragged length, random runs
        (8 * 128 + 3, 96),  # > one sublane tile, ragged
    ])
    def test_matches_jnp_oracle(self, n, runs):
        rng = np.random.default_rng(n * 1000 + runs)
        client, flags = _run_layout(rng, n, runs)
        want = np.asarray(pk.seg_argmax_scan_jnp(
            jnp.asarray(client), jnp.asarray(flags)))
        got = np.asarray(pk.seg_argmax_scan(
            jnp.asarray(client), jnp.asarray(flags), mode="interpret"))
        assert (got == want).all()

    def test_tie_keeps_earlier_position(self):
        # equal major key: the run-prefix argmax must keep the EARLIER
        # position (the sibling rule's minimum clock at equal client)
        client = jnp.asarray(np.asarray([5, 5, 5, 2], np.int32))
        flags = jnp.asarray(np.asarray([1, 0, 0, 0], np.int32))
        for mode in ("interpret", "jnp"):
            out = np.asarray(pk.seg_argmax_scan(client, flags, mode=mode))
            assert out[3] == 0, mode

    def test_run_boundaries_isolate(self):
        # a huge client in run 0 must not leak into run 1
        client = jnp.asarray(np.asarray([999, 1, 3, 2], np.int32))
        flags = jnp.asarray(np.asarray([1, 0, 1, 0], np.int32))
        for mode in ("interpret", "jnp"):
            out = np.asarray(pk.seg_argmax_scan(client, flags, mode=mode))
            assert out[1] == 0 and out[3] == 2, mode


class TestStreamScatter:
    @pytest.mark.parametrize("n", [1, 5, 128, 700, 8 * 128 + 9,
                                   2 * pk._SCATTER_CHUNK + 77])
    def test_permutation_round_trip(self, n):
        rng = np.random.default_rng(n)
        pos = rng.permutation(n).astype(np.int32)
        want = np.asarray(pk.stream_scatter_jnp(jnp.asarray(pos), n))
        got = np.asarray(pk.stream_scatter(
            jnp.asarray(pos), n, mode="interpret"))
        assert (got == want).all()
        assert (np.sort(got) == np.arange(n)).all()

    def test_dropped_targets_and_holes(self):
        # -1 (invalid) and past-the-end targets drop; untargeted
        # output slots stay -1 holes — identically in both paths
        pos = jnp.asarray(np.asarray([3, -1, 0, 99, 5], np.int32))
        want = np.asarray(pk.stream_scatter_jnp(pos, 8))
        got = np.asarray(pk.stream_scatter(pos, 8, mode="interpret"))
        assert (got == want).all()
        assert got[3] == 0 and got[0] == 2 and got[5] == 4
        assert (got[[1, 2, 4, 6, 7]] == -1).all()


# ---------------------------------------------------------------------------
# route differentials: interpret-mode converge vs the jnp path,
# byte-identical cache + snapshot
# ---------------------------------------------------------------------------


def sort_diet_blobs(clock_base=0, R=5, K=16, seed=12, tie=False):
    """Map chains + list appends + right-bearing mid-inserts + deletes,
    clocks offset to straddle a chosen width boundary; ``tie`` makes
    every client reuse the SAME clock values (Lamport ties resolved by
    client id alone)."""
    rng = np.random.default_rng(seed)
    blobs = []
    for r in range(R):
        client = r + 1
        recs, chain, prev = [], [], None
        for k in range(K):
            clock = clock_base + (k if not tie else k // 2 * 2)
            clock += 0 if not tie else (k % 2)  # keep ids unique
            kind = int(rng.integers(0, 3))
            if kind == 0:
                recs.append(ItemRecord(
                    client=client, clock=clock, parent_root="m",
                    key=f"k{int(rng.integers(0, 4))}", content=k))
            elif kind == 1 and chain:
                j = int(rng.integers(0, len(chain)))
                recs.append(ItemRecord(
                    client=client, clock=clock, parent_root="text",
                    origin=chain[j - 1] if j > 0 else None,
                    right=chain[j], content=k))
                chain.insert(j, (client, clock))
            else:
                recs.append(ItemRecord(
                    client=client, clock=clock, parent_root="l",
                    origin=(client, prev) if prev is not None else None,
                    content=k))
                prev = clock
                chain.append((client, clock))
        ds = DeleteSet()
        ds.add(client, clock_base + int(rng.integers(0, K)))
        blobs.append(v1.encode_update(recs, ds))
    return blobs


def _pallas_vs_jnp(blobs, monkeypatch, *, incremental=True):
    """interpret-mode kernels vs the jnp oracle on every route:
    byte-identical cache + snapshot (and vs the scalar engine)."""
    monkeypatch.setenv("CRDT_TPU_PALLAS", "0")
    want = replay_trace(blobs, route="device")
    st_want = stream_replay(blobs, chunk_blobs=2, max_shards=3,
                            min_shard_rows=1)
    assert st_want.cache == want.cache

    monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")
    got = replay_trace(blobs, route="device")
    assert got.cache == want.cache
    assert got.snapshot == want.snapshot
    st = stream_replay(blobs, chunk_blobs=2, max_shards=3,
                       min_shard_rows=1)
    assert st.cache == want.cache and st.snapshot == want.snapshot
    if incremental:
        from crdt_tpu.models.incremental import IncrementalReplay

        inc = IncrementalReplay(capacity=1 << 13)
        inc.device_min_rows = 0  # force the device splice every chunk
        for i in range(0, len(blobs), 2):
            inc.apply(blobs[i:i + 2])
        assert inc.cache == want.cache
    return want


class TestRouteDifferentials:
    def test_small_clocks_all_routes(self, monkeypatch):
        res = _pallas_vs_jnp(sort_diet_blobs(0), monkeypatch)
        eng = Engine(10 ** 6)
        for b in sort_diet_blobs(0):
            v1.apply_update(eng, b)
        assert res.cache == eng.to_json()

    # the offset-clock tie traces skip the incremental route: its
    # engine-shaped admission stashes records until the client's SV is
    # contiguous from 0, so a trace starting at clock 2^15-8 is
    # (correctly) all-pending there — the one-shot and streaming
    # routes cover the kernel boundary behavior

    def test_clock_ties_at_int16_boundary(self, monkeypatch):
        _pallas_vs_jnp(sort_diet_blobs((1 << 15) - 8, tie=True),
                       monkeypatch, incremental=False)

    def test_clock_ties_at_int31_boundary(self, monkeypatch):
        _pallas_vs_jnp(sort_diet_blobs((1 << 31) - 8, tie=True),
                       monkeypatch, incremental=False)

    def test_delete_only_and_empty_chunks(self, monkeypatch):
        ds = DeleteSet()
        ds.add(1, 3, 4)
        blobs = sort_diet_blobs(0, R=4, K=12) + [
            v1.encode_update([], ds),
            v1.encode_update([], DeleteSet()),
        ]
        _pallas_vs_jnp(blobs, monkeypatch)

    def test_single_row_segments(self, monkeypatch):
        # one op per root: every segment is a single row, every run in
        # the kernels is width 1
        recs = [
            ItemRecord(client=1, clock=k, parent_root=f"r{k}", content=k)
            for k in range(7)
        ] + [
            ItemRecord(client=2, clock=k, parent_root=f"m{k}",
                       key="k", content=k)
            for k in range(7)
        ]
        blobs = [v1.encode_update(recs, DeleteSet())]
        _pallas_vs_jnp(blobs, monkeypatch)

    def test_int16_narrowed_staging_edges(self, monkeypatch, tracer):
        # a hi/lo (forced-wide-section) staging edge through the
        # interpret kernels: the self-referential origin makes
        # map_chain_end take the exact hi/lo stretches, and the
        # interpret path must still match jnp exactly
        n = 6
        cols = {
            "client": np.full(n, 1, np.int64),
            "clock": np.arange(n, dtype=np.int64),
            "parent_is_root": np.ones(n, bool),
            "parent_a": np.zeros(n, np.int64),
            "parent_b": np.full(n, -1, np.int64),
            "key_id": np.zeros(n, np.int64),
            "origin_client": np.full(n, -1, np.int64),
            "origin_clock": np.full(n, -1, np.int64),
            "valid": np.ones(n, bool),
        }
        cols["origin_client"][3] = 1
        cols["origin_clock"][3] = 3
        monkeypatch.setenv("CRDT_TPU_PALLAS", "0")
        want = packed.converge(packed.stage(cols))
        monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")
        plan = packed.stage(cols)
        assert dict(zip(packed.SECTION_NAMES,
                        plan.encs))["map_chain_end"] == "hilo"
        got = packed.converge(plan)
        assert list(got.win_rows) == list(want.win_rows)
        assert list(got.stream_row) == list(want.stream_row)

    def test_crossover_width_guard_falls_back(self, monkeypatch, tracer):
        # a block past the VMEM width guard must take the jnp oracle
        # path (and count the fallback) even with pallas requested
        monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")
        monkeypatch.setattr(pk, "_SCAN_PALLAS_MAX", 16)
        blobs = sort_diet_blobs(0, R=4, K=16)
        got = replay_trace(blobs, route="device")
        cnt = tracer.counters("converge.")
        assert cnt.get("converge.pallas_fallback", 0) > 0, cnt
        assert cnt.get('converge.pallas{mode="jnp"}', 0) > 0, cnt
        monkeypatch.setenv("CRDT_TPU_PALLAS", "0")
        want = replay_trace(blobs, route="device")
        assert got.cache == want.cache
        assert got.snapshot == want.snapshot

    def test_mode_counter_fires_per_dispatch(self, monkeypatch, tracer):
        monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")
        replay_trace(sort_diet_blobs(0, R=3, K=8), route="device")
        cnt = tracer.counters("converge.")
        assert cnt.get('converge.pallas{mode="interpret"}', 0) > 0, cnt
