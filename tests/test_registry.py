"""The observability registry: every documented span, counter and gauge
is live.

``REGISTRY`` names, per leg, the spans/counters/gauges README's
"Observability" tables document. Each leg is a small in-process run
of the mechanism that emits them (a device replay, a sharded
converge, a multi-tenant server, the snapshot store, a loopback
swarm, ...), made once per module with its own enabled tracer; each
name is one case of :func:`test_registered_name_is_live`, which
asserts the name is in that leg's tracer report. A rename or a
dropped hook fails here, not in a postmortem.

crdtlint's registry check (rule CL201) reads this file: the string
literals that name a metric, plus ``HOT_PATH_SPANS`` verbatim
(``tools/crdtlint/registry.py``).

The legs light names; they do not re-check results. Each result the
legs produce is pinned against its oracle by a module test:
packed/per-doc and flood isolation by ``test_multidoc``, pooled vs
unpooled by ``test_pooled``, snapshot join/fallback/restore by
``test_snapshot``, split vs unsplit by ``test_subtree_split``,
sharded vs single-chip by ``test_shard``.
"""

import json
import os
import tempfile
import time
import urllib.request

import pytest

import workloads
from crdt_tpu.codec import v1
from crdt_tpu.core.engine import Engine
from crdt_tpu.core.ids import DeleteSet, StateVector
from crdt_tpu.core.records import ItemRecord
from crdt_tpu.models import replay as rp
from crdt_tpu.models.incremental import IncrementalReplay
from crdt_tpu.models.multidoc import MultiDocServer
from crdt_tpu.obs import (
    Controller,
    FleetCollector,
    FlightRecorder,
    ObsHTTPServer,
    PropagationLedger,
    TickTimeline,
    Tracer,
    get_propagation,
    get_recorder,
    get_timeline,
    get_tracer,
    set_propagation,
    set_recorder,
    set_timeline,
    set_tracer,
)
from crdt_tpu.ops import packed, shard
from crdt_tpu.storage import snapshot as sn
from crdt_tpu.storage.persistence import LogPersistence
from tests.test_multidoc import DocStream, doc_blobs
from tests.test_pooled import _warm, delta_blobs

# the hot-path spans (README "Observability"), one per pipeline stage
HOT_PATH_SPANS = (
    "decode", "pack", "converge.dispatch", "converge.fetch",
    "gather", "materialize", "compact", "persist", "persist.compact",
    "stage", "tick",
)

# leg -> the names it must light. A name matches a report key equal to
# it or the same name with labels (``converge.pallas{mode="jnp"}``).
REGISTRY = {
    "hot": HOT_PATH_SPANS,
    "xfer": (
        "xfer.h2d_bytes", "xfer.h2d_puts", "xfer.d2h_bytes",
        "xfer.d2h_fetches", "xfer.h2d", "xfer.d2h",
        "xfer.narrowed_ratio", "xfer.col_width",
    ),
    "converge.pallas": ("converge.pallas",),
    "shard": (
        "shard.dispatches", "shard.boundary_bytes", "shard.shards",
        "converge.wyllie_rounds",
    ),
    "subtree": (
        "converge.subtree_cuts", "converge.map_chain_cuts",
        "converge.map_rounds",
    ),
    "tenant": (
        "converge.docs_packed", "tenant.submitted",
        "tenant.docs_converged", "tenant.shed", "tenant.shed_bytes",
        "tenant.pending_bytes", "tenant.dispatch_docs",
    ),
    "steady": (
        "tenant.delta_docs", "tenant.delta_rows", "tenant.promotions",
        "tenant.resident_evictions", "sentinel.doc_digest_skips",
        "tenant.resident_bytes", "tenant.resident_docs",
    ),
    "pool": ("tenant.pool_dispatches", "tenant.pool_bytes",
             "tenant.pool_docs"),
    "snap": (
        "snap.writes", "snap.loads", "snap.bytes",
        "tenant.checkpoint_docs", "snap.fallbacks", "snap.write_ms",
        "snap.load_ms",
    ),
    "slo": (
        "slo.breaches", "slo.burn_rate", "slo.route_shed",
        "slo.ingest_to_converged", "slo.ingest_to_served",
        "timeline.ticks", "timeline.overlap_efficiency",
        "timeline.stall_ms",
    ),
    "propagation": (
        "propagation.contexts_sent", "propagation.contexts_received",
        "propagation.context_bytes", "propagation.traced_update_bytes",
        "propagation.malformed_contexts",
        "propagation.wire_overhead_ratio",
        'replica.hop_lag{route="direct"}',
        'replica.hop_lag{route="sync_answer"}',
        'replica.hop_lag{route="anti_entropy"}',
        "replica.birth_to_visibility",
        "collector.scrapes", "collector.procs", "collector.pair_rate",
    ),
    "control": (
        "control.decisions", "control.cooldown_skips",
        "control.ledger_dropped", "snap.cadence_writes",
        'control.decisions{rule="budget_squeeze"}',
        'control.decisions{rule="budget_restore"}',
        "control.setpoint",
    ),
    "guard": (
        "guard.inbox_shed", "guard.inbox_shed_bytes",
        "engine.pending_evictions", "persist.retries",
        "persist.degraded_writes", "persist.recovered_updates",
        "device.retries", "device.fallback", "persist.degraded",
    ),
}


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------


def _replay_leg():
    """A device replay of the fan-in shape, its WAL, one server tick:
    the hot-path spans, the byte accounting and the kernel mode."""
    blobs = workloads.build_trace(48, 40)
    res = rp.replay_trace(blobs)
    with tempfile.TemporaryDirectory() as td:
        lp = LogPersistence(os.path.join(td, "hot.kvlog"))
        for blob in blobs[:8]:
            lp.store_update("hot", blob)
        lp.compact("hot", res.snapshot)
        lp.close()
    srv = MultiDocServer()
    srv.submit_many("d", blobs[:4])
    srv.tick()


def _shard_leg():
    """A 2-way sharded converge of the fan-in shape."""
    blobs = workloads.build_trace(48, 40)
    dec = rp.decode(blobs)
    cols, ds = rp.stage(dec)
    plan = shard.stage(cols, n_shards=2)
    assert plan is not None, "sharded staging refused"
    rp.gather(dec, ds, ("packed", shard.converge(plan)))


def _subtree_leg():
    """A bushy tree plus a deep origin-chained map key, split at a
    width both exceed."""
    recs = [ItemRecord(client=1, clock=k, parent_root="t",
                       origin=(1, k // 3) if k else None, content=k)
            for k in range(96)]
    for k in range(40):
        recs.append(ItemRecord(
            client=1, clock=96 + k, parent_root="m", key="hot",
            origin=(1, 95 + k) if k else None, content=k))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(packed._CHAIN_SPLIT_ENV, "16")
        rp.replay_trace([v1.encode_update(recs, DeleteSet())])


def _tenant_leg():
    """Mixed tenants packed into shared dispatches, then one flooding
    tenant shed against a tiny budget."""
    docs = workloads.build_doc_trace(8, 18, 1, 128)
    srv = MultiDocServer(pack_docs=True)
    for d, bs in docs.items():
        srv.submit_many(d, bs)
    while srv.dirty_docs():
        srv.tick()
    chaos = MultiDocServer(tenant_max_pending_bytes=2048,
                           tenant_max_pending_updates=4)
    for bs in workloads.build_doc_trace(24, 18, 0, 0,
                                        seed=9090).values():
        chaos.submit_many("flood!", bs)
    chaos.tick()
    assert chaos.shed_count > 0


def _slo_leg():
    """Served ticks against a zero objective (every blob breaches)
    with a shedding tenant, recorded by the tick timeline."""
    srv = MultiDocServer(tenant_max_pending_bytes=2048,
                         tenant_max_pending_updates=4, slo_ms=0.0)
    for d, bs in workloads.build_doc_trace(4, 18, 0, 0).items():
        srv.submit_many(d, bs)
    for bs in workloads.build_doc_trace(24, 18, 0, 0,
                                        seed=9090).values():
        srv.submit_many("flood!", bs)
    srv.prepare()
    srv.tick()
    assert len(get_timeline()) > 0


def _steady_leg():
    """Delta ticks on resident docs, the digest beacon twice, then a
    resident budget too small for the population."""
    streams = [DocStream(seed) for seed in range(3)]
    srv = MultiDocServer(delta_ticks=True)
    for _tick in range(4):
        for i, s in enumerate(streams):
            srv.submit(f"s{i}", s.delta(24 if _tick == 0 else 3))
        srv.tick()
    srv.doc_digests()
    srv.doc_digests()
    budget = IncrementalReplay.estimate_resident_bytes(64) * 2
    fsrv = MultiDocServer(delta_ticks=True, resident_max_bytes=budget)
    fstreams = [DocStream(100 + i) for i in range(6)]
    for i, s in enumerate(fstreams):
        fsrv.submit(f"f{i}", s.delta(48))
    fsrv.tick()
    for _pass in range(2):
        for i, s in enumerate(fstreams):
            fsrv.submit(f"f{i}", s.delta(3))
            fsrv.tick()
    assert fsrv.eviction_count > 0


def _pool_leg():
    """Warm docs above the crossover, their steady deltas batched into
    one pooled dispatch."""
    doc_sets = {f"p{i}": doc_blobs(i, K=18) for i in range(3)}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CRDT_TPU_DEVICE_MIN", "1")
        srv = MultiDocServer(delta_ticks=True, pool=True)
        _warm(srv, doc_sets)
        for start in (18, 24):
            for i, d in enumerate(doc_sets):
                srv.submit_many(d, delta_blobs(i, start))
            srv.tick()
    assert srv.pool.dispatches > 0


def _snap_leg():
    """A snapshot join, a corrupted snapshot falling back to the WAL,
    and a server checkpoint restored into a fresh server."""
    with tempfile.TemporaryDirectory() as root:
        wal = LogPersistence(os.path.join(root, "wal.kvlog"))
        store = sn.SnapshotStore(os.path.join(root, "snaps"))
        s = DocStream(0)
        for _ in range(6):
            wal.store_update("scale", s.delta(50))
        eng, _ = rp.cold_start("scale", wal, None)
        assert sn.compact_with_snapshot(wal, "scale", eng, store)
        wal.store_update("scale", s.delta(50))
        assert rp.cold_start("scale", wal, store)[1] == "snapshot"
        snaps = os.path.join(root, "snaps")
        path = os.path.join(snaps, [n for n in os.listdir(snaps)
                                    if n.endswith(".snap")][0])
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            byte = f.read(1)[0]
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([byte ^ 0x40]))
        assert rp.cold_start("scale", wal, store)[1] == "wal"
        wal.close()
        srv = MultiDocServer(snap_store=store)
        streams = {f"doc{i}": DocStream(i + 1) for i in range(2)}
        for _ in range(3):
            for d, st in streams.items():
                srv.submit(d, st.delta(24))
            srv.tick()
        assert srv.checkpoint() > 0
        assert MultiDocServer(snap_store=store).restore() > 0


def _propagation_leg():
    """A traced loopback swarm (broadcast, late-join sync answer, one
    forced anti-entropy round, a hostile context), then a collector
    scraping this process's own endpoint."""
    from crdt_tpu.net.replica import Replica
    from crdt_tpu.net.router import LoopbackNetwork, LoopbackRouter

    set_recorder(FlightRecorder(enabled=True))
    set_propagation(PropagationLedger())
    net = LoopbackNetwork()
    pa = Replica(LoopbackRouter(net, "pa"), topic="ptrace", client_id=11)
    pb = Replica(LoopbackRouter(net, "pb"), topic="ptrace", client_id=12)
    pa.set("m", "k0", "v" * 256)
    net.run()
    pc = Replica(LoopbackRouter(net, "pc"), topic="ptrace", client_id=13)
    net.run()
    pb.set("m", "k1", "w" * 256)
    net.run()
    # a real deficit for the anti-entropy round: forget pb's SV
    pa.peer_state_vectors["pb"] = StateVector()
    pa.anti_entropy_s = 0.5
    pa._ae_interval = 0.5
    pa._next_ae_at = time.monotonic() - 1
    pa.tick()
    net.run()
    pc._on_data({"update": pa.doc.encode_state_as_update(),
                 "tid": [11, 999, 0.0], "hop": 0,
                 "tc": b"\xff\x01hostile"}, "pa")
    pc.flush_incoming()
    obs_self = ObsHTTPServer(port=0).start()
    try:
        col = FleetCollector()
        col.add_proc("self", obs_self.url)
        assert col.scrape().get("self")
        assert col.fleet_report()["paths"]["pair_rate"] == 1.0
        obs_fleet = ObsHTTPServer(port=0, collector=col).start()
        try:
            body = json.loads(urllib.request.urlopen(
                obs_fleet.url + "/fleet?scrape=0").read())
            assert body["procs"] == ["self"]
        finally:
            obs_fleet.stop()
    finally:
        obs_self.stop()


def _control_leg():
    """A synthetic flood/clean sensor trace through a tiny-ledger
    controller (squeeze, cooldown-blocked flips, restore), and a
    server checkpointing on a tick cadence."""
    ctrl = Controller(cooldown_ticks=3, restore_after=2,
                      ledger_capacity=2)
    for st in range(14):
        ctrl.observe({
            "tick": st,
            "budget": {"max_bytes": 2048, "max_updates": 4},
            "tenants": {"flood!": {
                "burn": 1.0 if st in (0, 4, 5, 6) else 0.0,
                "shed": 4 * st,
                "pending_bytes": 4096 if st < 6 else 0,
            }},
        })
    with tempfile.TemporaryDirectory() as td:
        srv = MultiDocServer(snap_store=sn.SnapshotStore(td),
                             checkpoint_every_ticks=2)
        s = DocStream(7)
        for _ in range(5):
            srv.submit("cadence", s.delta(4))
            srv.tick()
        assert srv.cadence_checkpoints > 0


def _guard_leg():
    """Each degradation ladder once: device retry to the host route,
    the engine's pending cap, WAL retry/degrade/write-back, and a
    replica inbox over its byte budget."""
    from crdt_tpu.guard.device import dispatch_guarded
    from crdt_tpu.guard.faults import (
        DeviceFaultPlan,
        DiskFaultSchedule,
        FaultyKv,
    )
    from crdt_tpu.net.replica import Replica
    from crdt_tpu.net.router import LoopbackNetwork, LoopbackRouter

    with DeviceFaultPlan(fail_attempts=2):
        dispatch_guarded("registry.guard", lambda: 0, host=lambda: 0)
    eng = Engine(1)
    eng.pending_limit = 2
    eng.apply_records([
        ItemRecord(client=9, clock=k, parent_root="s",
                   origin=(9, k - 1), content=k)
        for k in range(1, 7)
    ])
    blobs = workloads.build_trace(2, 8)
    with tempfile.TemporaryDirectory() as td:
        lp = LogPersistence(
            os.path.join(td, "ladder.kvlog"),
            kv_wrapper=lambda kv: FaultyKv(
                kv, DiskFaultSchedule(fail_writes={0, 1, 2})),
            retries=2, retry_backoff_s=0.001,
        )
        lp.store_update("g", blobs[0])   # degrades
        lp.store_update("g", blobs[1])   # drains and syncs
        assert lp.get_all_updates("g") == blobs
        lp.close()
    net = LoopbackNetwork()
    Replica(LoopbackRouter(net, "a"), topic="g", client_id=1,
            batch_incoming=True, inbox_max_bytes=128)
    rb = Replica(LoopbackRouter(net, "b"), topic="g", client_id=2)
    net.run()
    for i in range(4):
        rb.set("m", f"k{i}", "x" * 48)
    net.run()


RUNNERS = {
    "hot": _replay_leg,
    "xfer": _replay_leg,
    "converge.pallas": _replay_leg,
    "shard": _shard_leg,
    "subtree": _subtree_leg,
    "tenant": _tenant_leg,
    "steady": _steady_leg,
    "pool": _pool_leg,
    "snap": _snap_leg,
    "slo": _slo_leg,
    "propagation": _propagation_leg,
    "control": _control_leg,
    "guard": _guard_leg,
}


@pytest.fixture(scope="module")
def live():
    """leg -> the tracer report of its run. Each runner runs once,
    under a fresh enabled tracer and tick timeline; the process's own
    tracer, timeline, recorder and propagation ledger are put back
    after it."""
    reports = {}

    def report_of(leg):
        fn = RUNNERS[leg]
        if fn not in reports:
            prev = (get_tracer(), get_timeline(), get_recorder(),
                    get_propagation())
            tracer = set_tracer(Tracer(enabled=True))
            set_timeline(TickTimeline(enabled=True))
            try:
                fn()
                reports[fn] = tracer.report()
            finally:
                set_tracer(prev[0])
                set_timeline(prev[1])
                set_recorder(prev[2])
                set_propagation(prev[3])
        return reports[fn]

    return report_of


def _find(section, name):
    return [k for k in section
            if k == name or ("{" not in name and k.startswith(name + "{"))]


@pytest.mark.parametrize(
    "leg,name",
    [(leg, name) for leg, names in REGISTRY.items() for name in names],
    ids=[name for names in REGISTRY.values() for name in names],
)
def test_registered_name_is_live(live, leg, name):
    rep = live(leg)
    spans = _find(rep["spans"], name)
    counters = _find(rep["counters"], name)
    gauges = _find(rep["gauges"], name)
    assert spans or counters or gauges, f"{name!r} not emitted by {leg}"
    for key in spans:
        sp = rep["spans"][key]
        assert sp["count"] > 0, key
        assert sp["p50_s"] <= sp["p99_s"] + 1e-12, key
        assert sp["p99_s"] <= sp["max_s"] + 1e-12, key
    for key in counters:
        assert rep["counters"][key] > 0, key


def test_gauge_values_after_their_legs(live):
    """The gauges whose value, not presence, is the evidence."""
    prop = live("propagation")["gauges"]
    assert prop["collector.procs"] == 1
    assert prop["collector.pair_rate"] == 1.0
    # degraded during the guard leg, and cleared by the write-back
    assert live("guard")["gauges"]["persist.degraded"] == 0


def test_disabled_span_cost():
    """A disabled tracer's span stays one attribute check and one
    shared no-op context manager: under 5 us a span on a loaded box
    (the hot paths run it millions of times)."""
    off = Tracer(enabled=False)
    reps = 50_000
    t0 = time.perf_counter()
    for _ in range(reps):
        with off.span("converge.dispatch"):
            pass
    per_ns = (time.perf_counter() - t0) / reps * 1e9
    assert per_ns < 5000, f"disabled span costs {per_ns:.0f} ns"
    assert not off.report()["spans"]
