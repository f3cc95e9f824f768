"""Differential suite for the round-14 tenant packing.

The tentpole contract: doc-id is a first-class segment column in the
staged packed layout, so ONE converge dispatch over a packed tenant
batch yields per-doc outputs BYTE-identical to each doc converged
alone — pinned here for {2, 3, 17} docs with mixed LWW/YATA ops,
deletes, right origins, shared raw client ids, duplicate redelivery,
and empty docs, on both the single-chip route and the forced-2-device
sharded route (whose partition places whole docs per chip). On top:
the MultiDocServer tick loop (fairness, bin-packing, vectorized vs
stock unpack equality), the tenant admission ladder (a flooding
tenant is shed ALONE — the chaos leg), and the multi-doc divergence
sentinel (a fork in one doc is attributed to that doc only).
"""

import numpy as np
import pytest

from crdt_tpu.codec import v1
from crdt_tpu.core.ids import DeleteSet
from crdt_tpu.core.records import ItemRecord
from crdt_tpu.guard.tenant import TenantBudget, fair_order, pack_batches
from crdt_tpu.models import replay as rp
from crdt_tpu.models.multidoc import (
    MultiDocServer,
    _concat_cols,
    cache_digest,
)
from crdt_tpu.obs import Tracer, get_tracer, set_tracer
from crdt_tpu.obs.sentinel import MultiDocSentinel
from crdt_tpu.ops import packed
from crdt_tpu.ops import shard
from crdt_tpu.ops.device import NULLI


@pytest.fixture(autouse=True)
def _no_ambient_sharding(monkeypatch):
    monkeypatch.delenv(shard.SHARD_ENV, raising=False)
    monkeypatch.delenv(shard.MIN_ROWS_ENV, raising=False)


def doc_blobs(seed, *, n_clients=3, K=24, rights=False, deletes=True,
              shared_clients=True, maps=2, lists=2):
    """One doc's update blobs: per-client chained list appends over
    ``lists`` roots + LWW map sets over ``maps`` roots, optional
    mid-insert right origins and tombstones. ``shared_clients`` keeps
    the same raw client ids across docs — the hard case the
    doc-composite interning must keep apart."""
    rng = np.random.default_rng(seed)
    base = 10 if shared_clients else 1000 * (seed + 1)
    blobs = []
    for c in range(n_clients):
        client = base + c
        recs = []
        chain = []
        for k in range(K):
            r = k % 3
            if r == 0:
                recs.append(ItemRecord(
                    client=client, clock=k,
                    parent_root=f"m{k % maps}",
                    key=f"k{int(rng.integers(0, 6))}",
                    content=int(seed * 1000 + c * 100 + k),
                ))
            elif rights and chain and k % 7 == 5:
                j = int(rng.integers(0, len(chain)))
                recs.append(ItemRecord(
                    client=client, clock=k,
                    parent_root=f"l{k % lists}",
                    origin=chain[j - 1] if j > 0 else None,
                    right=chain[j], content=k,
                ))
                chain.insert(j, (client, k))
            else:
                recs.append(ItemRecord(
                    client=client, clock=k,
                    parent_root=f"l{k % lists}",
                    origin=chain[-1] if chain else None,
                    content=int(seed * 1000 + c * 100 + k),
                ))
                chain.append((client, k))
        ds = DeleteSet()
        if deletes:
            ds.add(client, 1)
            ds.add(client, K - 1)
        blobs.append(v1.encode_update(recs, ds))
    return blobs


def oracle_cache(blobs):
    return rp.replay_trace(blobs).cache if blobs else {}


def split_result(res, row_off, i):
    """Reference per-doc slice of a combined result (row-range based,
    independent of the server's vectorized partition)."""
    lo, hi = int(row_off[i]), int(row_off[i + 1])
    win = np.asarray(res.win_rows)
    srow = np.asarray(res.stream_row)
    sseg = np.asarray(res.stream_seg)
    wm = (win >= lo) & (win < hi)
    sm = (srow >= lo) & (srow < hi)
    return packed.PackedResult(
        win_rows=np.where(wm, win - lo, NULLI),
        stream_seg=sseg[sm],
        stream_row=(srow - lo)[sm],
        hard_rows=tuple(
            int(r) - lo for r in res.hard_rows if lo <= int(r) < hi
        ),
    )


def converge_combined(doc_sets, *, sharded=None):
    """Stage + converge a list of per-doc blob sets as one multi-doc
    dispatch; returns (per-doc caches, result, plan staging ok)."""
    decs = [rp.decode(bs) for bs in doc_sets]
    staged = [rp.stage(d) for d in decs]
    live = [i for i, d in enumerate(decs) if len(d["client"])]
    comb, row_off = _concat_cols([staged[i][0] for i in live])
    if sharded is not None:
        splan = shard.stage(comb, n_shards=sharded)
        assert splan is not None, "sharded multi-doc staging refused"
        res = shard.converge(splan)
    else:
        plan = packed.stage(comb)
        assert plan is not None, "multi-doc staging refused"
        res = packed.converge(plan)
    caches = {}
    for pos, i in enumerate(live):
        dec, (cols, ds) = decs[i], staged[i]
        sub = split_result(res, row_off, pos)
        w, v, o = rp.gather(dec, ds, ("packed", sub))
        caches[i] = rp.materialize(dec, ds, w, v, o)
    for i in range(len(doc_sets)):
        caches.setdefault(i, {})
    return caches


@pytest.mark.parametrize("n_docs", [2, 3, 17])
def test_packed_multidoc_identical_to_per_doc_oracle(n_docs):
    """{2, 3, 17} docs with mixed LWW/YATA ops, deletes, shared raw
    client ids, one rights-bearing doc and one empty doc: the packed
    multi-doc dispatch reproduces every per-doc oracle cache."""
    doc_sets = []
    for i in range(n_docs):
        if i == 1:
            doc_sets.append([])  # empty doc rides the batch
        elif i == 2:
            doc_sets.append(doc_blobs(i, rights=True))
        else:
            doc_sets.append(doc_blobs(i, K=16 + 5 * (i % 3)))
    caches = converge_combined(doc_sets)
    for i, bs in enumerate(doc_sets):
        assert caches[i] == oracle_cache(bs), f"doc {i} diverged"


@pytest.mark.parametrize("n_docs", [2, 3, 17])
def test_sharded_multidoc_identical_to_per_doc_oracle(n_docs):
    """The same batches through the forced-2-device sharded route
    (doc-first partition): byte-identical per-doc caches."""
    doc_sets = []
    for i in range(n_docs):
        if i == 1 and n_docs > 2:
            doc_sets.append([])
        else:
            doc_sets.append(doc_blobs(
                i, K=14 + 3 * (i % 4), rights=(i == 2)
            ))
    caches = converge_combined(doc_sets, sharded=2)
    for i, bs in enumerate(doc_sets):
        assert caches[i] == oracle_cache(bs), f"doc {i} diverged"


def test_multidoc_redelivery_and_shared_ids():
    """Duplicate blobs within one doc dedup (first wins, like the
    single-doc path) while the SAME (client, clock) ids in another
    doc stay separate rows — the doc-composite id space at work."""
    a = doc_blobs(0, K=12)
    b = doc_blobs(0, K=12)  # identical ids + content in another doc
    caches = converge_combined([a + a, b])
    assert caches[0] == oracle_cache(a)
    assert caches[1] == oracle_cache(b)


def test_doc_first_shard_partition():
    """With >1 distinct doc the sharded partition keeps whole docs
    per shard (segments of one doc never split across chips)."""
    doc_sets = [doc_blobs(i, K=18) for i in range(5)]
    decs = [rp.decode(bs) for bs in doc_sets]
    staged = [rp.stage(d) for d in decs]
    comb, row_off = _concat_cols([c for c, _ in staged])
    parts, pb_tag = shard._partition(comb, 2)
    assert parts is not None and len(parts) == 2
    assert pb_tag is None  # multi-doc unions never pre-cut
    doc_col = comb["doc"]
    seen = {}
    for k, rows in enumerate(parts):
        for d in np.unique(doc_col[rows]).tolist():
            assert seen.setdefault(d, k) == k, (
                f"doc {d} split across shards"
            )
    assert len(seen) == 5


def test_server_tick_matches_oracle_and_is_fair():
    """The tick loop end to end: bin-packed batches, vectorized +
    stock unpack, per-doc caches identical to replay_trace, fairness
    ordering serving least-recently-served docs first."""
    docs = {f"d{i:02d}": doc_blobs(i, K=15 + (i % 5),
                                   rights=(i == 3))
            for i in range(12)}
    docs["empty"] = []
    srv = MultiDocServer(max_rows_per_dispatch=256)
    for d, bs in docs.items():
        srv.submit_many(d, bs)
    rep = srv.tick()
    assert rep.docs == 12  # the empty doc has nothing pending
    assert rep.dispatches < 12, "no packing happened"
    for d, bs in docs.items():
        if bs:
            assert srv.cache(d) == oracle_cache(bs), d
            assert srv.latency_s(d) is not None
    assert srv.cache("empty") == {}
    # fairness: docs served this tick are deprioritized next tick
    srv.submit_many("d00", doc_blobs(0, K=6))
    order = fair_order(["d00", "zz_new"], {
        "d00": srv._docs["d00"].served_tick
    })
    assert order == ["zz_new", "d00"]


def test_server_incremental_resubmit_reconverges():
    """New deltas for an already-converged doc re-converge its full
    history; untouched docs keep their caches."""
    a1 = doc_blobs(1, K=10)
    b = doc_blobs(2, K=10)
    srv = MultiDocServer()
    srv.submit_many("a", a1)
    srv.submit_many("b", b)
    srv.tick()
    extra = [v1.encode_update([ItemRecord(
        client=99, clock=0, parent_root="m0", key="kx", content="v",
    )], DeleteSet())]
    srv.submit_many("a", extra)
    rep = srv.tick()
    assert rep.docs == 1
    assert srv.cache("a") == oracle_cache(a1 + extra)
    assert srv.cache("b") == oracle_cache(b)


def test_flooding_tenant_sheds_alone():
    """The chaos leg: one tenant floods past its admission budget in
    a shared tick; it is shed (bounded, oldest-first) while every
    other tenant's converged bytes are IDENTICAL to an unflooded
    run."""
    tracer = set_tracer(Tracer(enabled=True))
    try:
        normal = {f"n{i}": doc_blobs(i, K=12) for i in range(6)}
        flood = [doc_blobs(50 + j, n_clients=1, K=30,
                           shared_clients=False)[0]
                 for j in range(12)]

        def run(with_flood):
            srv = MultiDocServer(
                max_rows_per_dispatch=512,
                tenant_max_pending_bytes=1200,
                tenant_max_pending_updates=3,
            )
            for d, bs in normal.items():
                srv.submit_many(d, bs)
            if with_flood:
                for blob in flood:
                    srv.submit("flooder", blob)
            srv.tick()
            return srv

        clean = run(False)
        flooded = run(True)
        assert flooded.shed_count > 0
        assert flooded.shed_bytes > 0
        counters = get_tracer().counters()
        assert counters.get("tenant.shed", 0) >= flooded.shed_count
        # the flooder degraded ALONE: neighbors byte-identical
        for d in normal:
            assert flooded.cache(d) == clean.cache(d), d
            assert flooded.digest(d) == clean.digest(d), d
        # the flooder's own queue was bounded keep-the-newest: its
        # converged state is the ADMITTED suffix
        kept = flood[-3:]
        assert flooded.cache("flooder") == oracle_cache(kept)
    finally:
        set_tracer(Tracer(enabled=False))


def test_multidoc_sentinel_attributes_fork_to_one_doc():
    """Per-doc digest beacons: a fork in one doc raises exactly one
    event naming THAT doc; equal docs agree; op-count mismatches are
    lag, never a fork."""
    tracer = set_tracer(Tracer(enabled=True))
    try:
        shared = {f"d{i}": doc_blobs(i, K=12) for i in range(4)}
        a = MultiDocServer()
        b = MultiDocServer()
        for d, bs in shared.items():
            a.submit_many(d, bs)
            b.submit_many(d, bs)
        # the fork: same op COUNT in doc d2 on b, different content
        forked = doc_blobs(2, K=12)
        forked[0] = v1.encode_update([ItemRecord(
            client=10, clock=k, parent_root="m0", key="k0",
            content=f"forked{k}",
        ) for k in range(12)], DeleteSet())
        b._docs.pop("d2")
        b.submit_many("d2", forked)
        # and lag: one doc with extra (fresh-client) ops on b only,
        # so the op counts genuinely differ
        b.submit_many("d3", [v1.encode_update([ItemRecord(
            client=777, clock=k, parent_root="m0", key="kq",
            content=k,
        ) for k in range(4)], DeleteSet())])
        a.tick()
        b.tick()
        assert a.doc_digests()["d2"]["ops"] == \
            b.doc_digests()["d2"]["ops"]
        sen = MultiDocSentinel(a, topic="t", replica="a")
        peer = MultiDocSentinel(b, topic="t", replica="b")
        events = sen.check("b", peer.beacon_payload())
        assert len(events) == 1
        assert events[0]["doc"] == "d2"
        assert events[0]["kind"] == "divergence"
        counters = get_tracer().counters()
        assert counters.get("sentinel.doc_divergence", 0) == 1
        assert counters.get("sentinel.doc_lag", 0) == 1  # d3
        assert counters.get("sentinel.agree", 0) >= 2  # d0, d1
        # a permanent fork raises once, later beacons only count
        assert sen.check("b", peer.beacon_payload()) == []
        assert get_tracer().counters().get(
            "sentinel.doc_divergence") == 2
    finally:
        set_tracer(Tracer(enabled=False))


def test_tenant_budget_units():
    """TenantBudget.trim: keep-the-newest under both limits;
    pack_batches: fairness-ordered greedy fill, oversized docs get
    their own batch."""
    from collections import deque

    q = deque([b"a" * 100, b"b" * 100, b"c" * 100])
    shed = TenantBudget(max_bytes=250, max_updates=10).trim(q)
    assert shed == [b"a" * 100]
    assert len(q) == 2
    q2 = deque([b"x", b"y", b"z"])
    shed = TenantBudget(max_bytes=1 << 20, max_updates=1).trim(q2)
    assert shed == [b"x", b"y"]
    # a single over-budget update is always kept whole
    q3 = deque([b"huge" * 100])
    assert TenantBudget(max_bytes=10, max_updates=1).trim(q3) == []
    assert len(q3) == 1

    batches = pack_batches(
        [("a", 40), ("b", 40), ("c", 50), ("d", 200)], 100
    )
    assert batches == [["a", "b"], ["c"], ["d"]]
    assert pack_batches([("big", 500)], 100) == [["big"]]


def test_cache_digest_canonical():
    assert cache_digest({"a": [1, 2], "b": {"x": 1}}) == \
        cache_digest({"b": {"x": 1}, "a": [1, 2]})
    assert cache_digest({"a": [1, 2]}) != cache_digest({"a": [2, 1]})


# ------------------------------------------------------- round 15 --
# Delta ticks: per-doc resident incremental engines inside the
# multi-tenant server. Contract: a dirty doc whose delta is
# SV-admissible converges at delta cost through its resident engine,
# BYTE-identical (canonical digest and cache) to the cold full-replay
# oracle; anything else falls back per doc to the round-14 cold path.


class DocStream:
    """Incremental doc generator whose deltas continue each client's
    clock contiguously — the SV-admissible steady-state shape. Keeps
    the YATA chain state so list ops anchor on real resident ids."""

    def __init__(self, seed, n_clients=2):
        self.seed = seed
        self.clients = [10 + c for c in range(n_clients)]
        self.clock = {c: 0 for c in self.clients}
        self.chain: list = []

    def delta(self, k_ops, *, deletes=False, mid_insert=False):
        recs = []
        for i in range(k_ops):
            c = self.clients[i % len(self.clients)]
            k = self.clock[c]
            self.clock[c] = k + 1
            if i % 3 == 0:
                recs.append(ItemRecord(
                    client=c, clock=k, parent_root="m",
                    key=f"k{(self.seed + i) % 5}",
                    content=self.seed * 1000 + k,
                ))
            elif mid_insert and len(self.chain) > 2 and i % 3 == 2:
                j = len(self.chain) // 2
                recs.append(ItemRecord(
                    client=c, clock=k, parent_root="l",
                    origin=self.chain[j - 1], right=self.chain[j],
                    content=self.seed * 1000 + k,
                ))
                self.chain.insert(j, (c, k))
            else:
                recs.append(ItemRecord(
                    client=c, clock=k, parent_root="l",
                    origin=self.chain[-1] if self.chain else None,
                    content=self.seed * 1000 + k,
                ))
                self.chain.append((c, k))
        ds = DeleteSet()
        if deletes and self.chain:
            dc, dk = self.chain[0]
            ds.add(dc, dk, 1)
        return v1.encode_update(recs, ds)


def test_delta_ticks_match_cold_oracle_every_tick():
    """The tentpole differential: N ticks of small contiguous deltas
    on resident docs — every tick's caches and canonical digests
    byte-identical to the full-replay server AND the replay_trace
    oracle, with the route evidence pinned (tick 0 cold, tick 1
    promotions, tick 2+ pure delta serves)."""
    streams = {f"d{i}": DocStream(i, n_clients=1 + i % 3)
               for i in range(5)}
    delta_srv = MultiDocServer()  # delta ticks on by default
    cold_srv = MultiDocServer(delta_ticks=False)
    history = {d: [] for d in streams}
    reports = []
    for t in range(4):
        for d, s in streams.items():
            blob = s.delta(12 if t == 0 else 3,
                           deletes=(t == 2), mid_insert=(t == 3))
            history[d].append(blob)
            delta_srv.submit(d, blob)
            cold_srv.submit(d, blob)
        reports.append((delta_srv.tick(), cold_srv.tick()))
        for d in streams:
            assert delta_srv.cache(d) == oracle_cache(history[d]), \
                (t, d)
            assert delta_srv.digest(d) == cold_srv.digest(d), (t, d)
            assert (delta_srv._docs[d].n_ops
                    == cold_srv._docs[d].n_ops), (t, d)
    assert reports[0][0].delta_docs == 0
    assert reports[1][0].promotions == 5
    for rep_d, rep_c in reports[2:]:
        assert rep_d.delta_docs == 5
        assert rep_d.delta_rows == 15  # the delta IS the staging cost
        assert rep_d.promotions == 0
        assert rep_c.delta_docs == 0   # the baseline stays cold


def test_delta_tick_redelivery_idempotent_across_ticks():
    """The same delta submitted in tick t and t+1 leaves resident
    state, cache, and canonical digest byte-identical to single
    delivery — redelivery rides the (still admissible) delta route,
    dedups inside the engine, and never falls back."""
    import copy

    s = DocStream(3)
    srv = MultiDocServer()
    b0 = s.delta(10)
    srv.submit("a", b0)
    srv.tick()                      # cold (first sight)
    b1 = s.delta(3)
    srv.submit("a", b1)
    rep1 = srv.tick()               # promotion
    assert rep1.promotions == 1
    b2 = s.delta(3)
    srv.submit("a", b2)
    rep2 = srv.tick()               # the delta route proper
    assert rep2.delta_docs == 1 and rep2.delta_rows == 3
    st = srv._docs["a"]
    digest0 = srv.digest("a")
    cache0 = copy.deepcopy(srv.cache("a"))
    n0 = st.resident.cols.n
    sv0 = dict(st.resident._next_clock)
    srv.submit("a", b2)             # redelivered in the NEXT tick
    rep3 = srv.tick()
    assert rep3.delta_docs == 1     # still the delta route
    assert srv.delta_fallback_count == 0
    assert srv.digest("a") == digest0
    assert srv.cache("a") == cache0
    assert st.resident.cols.n == n0
    assert dict(st.resident._next_clock) == sv0
    assert srv.cache("a") == oracle_cache([b0, b1, b2])


def test_offset_clock_delta_falls_back_to_cold():
    """A clock gap is inadmissible to the incremental route (the
    engine would stash what the cold oracle admits): the doc falls
    back per-doc to the cold replay — bytes identical to the oracle
    — and a history the engine cannot settle pins the doc cold."""
    s = DocStream(5, n_clients=1)
    srv = MultiDocServer()
    blobs = [s.delta(8)]
    srv.submit("a", blobs[0])
    srv.tick()
    blobs.append(s.delta(3))
    srv.submit("a", blobs[1])
    rep = srv.tick()
    assert rep.promotions == 1
    c = s.clients[0]
    s.clock[c] += 5                 # the offset: a clock gap
    blobs.append(s.delta(4))
    srv.submit("a", blobs[2])
    rep2 = srv.tick()
    assert rep2.delta_docs == 0
    assert srv.delta_fallback_count == 1
    assert srv.cache("a") == oracle_cache(blobs)
    blobs.append(s.delta(2))        # still past the gap
    srv.submit("a", blobs[3])
    rep3 = srv.tick()
    assert rep3.delta_docs == 0 and rep3.promotions == 0
    assert srv.cache("a") == oracle_cache(blobs)
    # the pin is NOT permanent: once the missing clocks arrive the
    # history settles, promotion succeeds on the next growth, and
    # the doc returns to the delta route
    gap = [v1.encode_update([ItemRecord(
        client=c, clock=k, parent_root="m", key="gapfill", content=k,
    ) for k in range(11, 16)], DeleteSet())]
    blobs.extend(gap)
    srv.submit("a", gap[0])
    rep4 = srv.tick()               # retry: history grew + settles
    assert rep4.promotions == 1
    assert srv.cache("a") == oracle_cache(blobs)
    blobs.append(s.delta(3))
    srv.submit("a", blobs[-1])
    rep5 = srv.tick()
    assert rep5.delta_docs == 1     # back on the delta route
    assert srv.cache("a") == oracle_cache(blobs)


def test_resident_budget_evicts_lru_and_reconverges():
    """The resident-memory budget: committed resident bytes never
    exceed it (peak == ledger high-water mark), overflow evicts the
    least-recently-served docs back to cold replay, and an evicted
    doc reconverges byte-identically on its next touch."""
    from crdt_tpu.models.incremental import IncrementalReplay

    tracer = set_tracer(Tracer(enabled=True))
    try:
        streams = {f"w{i}": DocStream(i, n_clients=1)
                   for i in range(6)}
        history = {d: [] for d in streams}
        budget = int(
            IncrementalReplay.estimate_resident_bytes(64) * 2.5
        )
        srv = MultiDocServer(resident_max_bytes=budget)

        def touch(docs, k):
            for d in docs:
                b = streams[d].delta(k)
                history[d].append(b)
                srv.submit(d, b)
            srv.tick()

        wave1 = ["w0", "w1", "w2"]
        wave2 = ["w3", "w4", "w5"]
        touch(wave1, 12)            # cold
        touch(wave1, 3)             # promotions (to budget room)
        touch(wave2, 12)            # cold, wave 1 idle
        touch(wave2, 3)             # promotions evict wave-1 LRU
        touch(wave2, 3)
        assert srv.eviction_count > 0
        assert srv.resident_peak_bytes() <= budget
        assert srv.resident_bytes_total() <= budget
        counters = get_tracer().counters()
        assert counters.get("tenant.resident_evictions", 0) \
            == srv.eviction_count
        evicted = [d for d in wave1
                   if srv._docs[d].resident is None]
        assert evicted, "no wave-1 resident was evicted"
        d = evicted[0]
        b = streams[d].delta(3)     # the resubmit after eviction
        history[d].append(b)
        srv.submit(d, b)
        srv.tick()
        assert srv.cache(d) == oracle_cache(history[d])
        assert srv.resident_peak_bytes() <= budget
    finally:
        set_tracer(Tracer(enabled=False))


def test_eviction_flood_writes_snapshots_and_rehydrates(tmp_path):
    """Round 21: the eviction cold-start tax fix. Same budget-flood
    shape as above, but with a snapshot store attached — every
    committed eviction leaves a snapshot behind (``snap.evict_writes``
    tracks it), and the evicted doc's next promotion REHYDRATES
    (``snap.loads`` grows) instead of replaying its full history,
    byte-identical to the oracle."""
    from crdt_tpu.models.incremental import IncrementalReplay
    from crdt_tpu.storage.snapshot import SnapshotStore

    tracer = set_tracer(Tracer(enabled=True))
    try:
        streams = {f"w{i}": DocStream(i, n_clients=1)
                   for i in range(6)}
        history = {d: [] for d in streams}
        budget = int(
            IncrementalReplay.estimate_resident_bytes(64) * 2.5
        )
        store = SnapshotStore(str(tmp_path))
        srv = MultiDocServer(resident_max_bytes=budget,
                             snap_store=store)

        def touch(docs, k):
            for d in docs:
                b = streams[d].delta(k)
                history[d].append(b)
                srv.submit(d, b)
            srv.tick()

        wave1 = ["w0", "w1", "w2"]
        wave2 = ["w3", "w4", "w5"]
        touch(wave1, 12)
        touch(wave1, 3)
        touch(wave2, 12)
        touch(wave2, 3)             # promotions evict wave-1 LRU
        touch(wave2, 3)
        assert srv.eviction_count > 0
        counters = get_tracer().counters()
        assert counters.get("snap.evict_writes", 0) \
            == srv.eviction_count
        assert counters.get("snap.evict_writes", 0) \
            <= counters.get("snap.writes", 0)
        evicted = [d for d in wave1
                   if srv._docs[d].resident is None]
        assert evicted, "no wave-1 resident was evicted"
        d = evicted[0]
        loads0 = counters.get("snap.loads", 0)
        # resubmit twice: serve-cold then promote — the promotion
        # must go through the snapshot, not a full-history rebuild
        for _ in range(2):
            b = streams[d].delta(3)
            history[d].append(b)
            srv.submit(d, b)
            srv.tick()
        assert srv.cache(d) == oracle_cache(history[d])
        if srv._docs[d].resident is not None:
            assert get_tracer().counters().get("snap.loads", 0) \
                > loads0, "re-promotion did not rehydrate"
        assert srv.resident_peak_bytes() <= budget
    finally:
        set_tracer(Tracer(enabled=False))


def test_serve_live_ingest_scheduler():
    """The round-15 live-ingest loop: a stream of update batches is
    drained across bounded ticks (ingest overlapping in-flight
    dispatches via the tick hook), every doc converges to its full-
    history oracle, and the settled history is exactly the submitted
    blobs in order — a mid-tick arrival is never marked converged
    without being converged."""
    streams = {f"s{i}": DocStream(i) for i in range(4)}
    history = {d: [] for d in streams}

    def source():
        for t in range(5):
            batch = []
            for d, s in streams.items():
                b = s.delta(8 if t == 0 else 2)
                history[d].append(b)
                batch.append((d, b))
            yield batch

    srv = MultiDocServer()
    rep = srv.serve(source(), max_ticks=12)
    assert rep.submitted == 20
    assert 0 < rep.ticks <= 12
    assert not srv.dirty_docs()
    assert rep.delta_docs > 0, "steady state never reached the " \
        "delta route"
    for d in streams:
        assert srv.cache(d) == oracle_cache(history[d]), d
        assert srv._docs[d].blobs == history[d], d


def test_doc_digests_skip_clean_docs(monkeypatch):
    """Digest caching (round-15 satellite): converging never
    digests; the first beacon computes one digest per doc; a second
    beacon over a clean population computes ZERO digests and counts
    every skip; a touched doc re-digests while clean neighbors still
    skip."""
    import crdt_tpu.models.multidoc as md

    tracer = set_tracer(Tracer(enabled=True))
    try:
        streams = {f"d{i}": DocStream(i) for i in range(4)}
        srv = MultiDocServer()
        for d, s in streams.items():
            srv.submit(d, s.delta(6))
        srv.tick()
        calls = {"n": 0}
        real = md.cache_digest

        def counting(c):
            calls["n"] += 1
            return real(c)

        monkeypatch.setattr(md, "cache_digest", counting)
        srv.doc_digests()
        assert calls["n"] == 4
        srv.doc_digests()           # clean: zero digest work
        assert calls["n"] == 4
        assert get_tracer().counters().get(
            "sentinel.doc_digest_skips") == 4
        srv.submit("d0", streams["d0"].delta(3))
        srv.tick()
        srv.doc_digests()           # only the touched doc recomputes
        assert calls["n"] == 5
    finally:
        set_tracer(Tracer(enabled=False))


def test_multidoc_stage_counts_docs_packed():
    """The staging seam counts docs per multi-doc plan — the
    amortization evidence the bench publishes."""
    tracer = set_tracer(Tracer(enabled=True))
    try:
        doc_sets = [doc_blobs(i, K=10) for i in range(3)]
        staged = [rp.stage(rp.decode(bs)) for bs in doc_sets]
        comb, _ = _concat_cols([c for c, _ in staged])
        plan = packed.stage(comb)
        assert plan is not None
        assert get_tracer().counters().get(
            "converge.docs_packed") == 3
    finally:
        set_tracer(Tracer(enabled=False))


def test_tick_phases_are_tracer_spans_and_the_ring_still_records():
    """A delta tick on a resident doc is traced end to end: ``tick``,
    its phases as ``tick.<phase>`` (route, slo among them), the delta's
    ``decode`` and the lazy read's ``engine.flush``; each phase also
    lands in the tick timeline's ring when the timeline is on."""
    from crdt_tpu.obs.timeline import (
        TickTimeline, get_timeline, set_timeline,
    )

    tracer = set_tracer(Tracer(enabled=True))
    old_tl = get_timeline()
    tl = set_timeline(TickTimeline(enabled=True))
    try:
        s = DocStream(3)
        srv = MultiDocServer()
        for t in range(3):  # cold, promotion, delta
            srv.submit("d", s.delta(12 if t == 0 else 3))
            srv.tick()
            srv.cache("d")
        assert srv.is_resident("d")
        tracer.reset()
        srv.submit("d", s.delta(3))
        rep = srv.tick()
        assert rep.delta_docs == 1
        srv.cache("d")
        spans = tracer.report()["spans"]
        for name in ("tick", "tick.prepare", "tick.route", "tick.settle",
                     "tick.slo", "decode", "engine.flush"):
            assert spans.get(name, {}).get("count", 0) > 0, name
        tick = spans["tick"]
        phases = sum(v["total_s"] for k, v in spans.items()
                     if k.startswith("tick."))
        assert tick["self_s"] == pytest.approx(
            tick["total_s"] - phases, abs=1e-9)
        last = tl.records()[-1]
        assert {"prepare", "route", "settle", "slo"} <= {
            n for n, _, _ in last["phases"]}
        # the ring takes the span's own stamps: one timing for both
        route = [b - a for n, a, b in last["phases"] if n == "route"]
        assert route == [spans["tick.route"]["total_s"]]
    finally:
        set_tracer(Tracer(enabled=False))
        set_timeline(old_tl)
