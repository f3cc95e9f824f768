"""The observability layer (crdt_tpu/obs): histogram math, tracer
thread-safety, flight recorder, Prometheus exposition, divergence
sentinel, trace-id propagation, jax_profile hardening — and the
round-18 serving surfaces: per-tenant SLO ledger (breach exactness
under the seeded flood), tick-timeline profiler (ring wraparound +
Perfetto schema), the HTTP scrape endpoint (live during serve()),
and the obsq CLI round-trip."""

import json
import sys
import threading
import time

import pytest

from crdt_tpu.obs import (
    FlightRecorder,
    Tracer,
    get_recorder,
    get_tracer,
    set_recorder,
    set_tracer,
    snapshot_json,
    to_prometheus,
)
from crdt_tpu.obs.tracer import BUCKET_EDGES_S, N_BUCKETS, bucket_index


@pytest.fixture
def installed():
    """Enabled global tracer + recorder, restored afterwards."""
    old_t, old_r = get_tracer(), get_recorder()
    tr = set_tracer(Tracer(enabled=True))
    rec = set_recorder(FlightRecorder(enabled=True))
    try:
        yield tr, rec
    finally:
        set_tracer(old_t)
        set_recorder(old_r)


# ---------------------------------------------------------------------------
# histogram bucket math (the edges are a contract: Prometheus les)
# ---------------------------------------------------------------------------


class TestHistogramBuckets:
    def test_edges_are_powers_of_two_microseconds(self):
        assert BUCKET_EDGES_S[0] == 1e-6
        for k in range(1, N_BUCKETS):
            assert BUCKET_EDGES_S[k] == 2 * BUCKET_EDGES_S[k - 1]

    def test_bucket_index_at_edges_is_upper_inclusive(self):
        # an observation exactly AT an edge lands in that edge's bucket
        for k in (0, 1, 5, 17, N_BUCKETS - 1):
            assert bucket_index(BUCKET_EDGES_S[k]) == k
        # just above an edge spills into the next bucket
        for k in (0, 3, 20):
            assert bucket_index(BUCKET_EDGES_S[k] * 1.0000001) == k + 1

    def test_below_floor_and_overflow(self):
        assert bucket_index(0.0) == 0
        assert bucket_index(-1.0) == 0  # clock skew: clamp, not crash
        assert bucket_index(5e-7) == 0
        assert bucket_index(1e9) == N_BUCKETS  # +Inf bucket

    def test_single_observation_quantiles_equal_max(self):
        tr = Tracer(enabled=True)
        tr.observe("x", 3e-6)  # inside (2e-6, 4e-6]: edge=4e-6 > max
        s = tr.report()["spans"]["x"]
        # the bucket-edge estimate is clamped to the observed max
        assert s["p50_s"] == s["p99_s"] == s["max_s"] == 3e-6

    def test_tail_separates_from_median(self):
        tr = Tracer(enabled=True)
        for _ in range(99):
            tr.observe("x", 1e-3)
        tr.observe("x", 1.0)
        s = tr.report()["spans"]["x"]
        assert s["count"] == 100
        assert s["p50_s"] <= 2e-3       # median in the 1ms bucket
        assert s["max_s"] == 1.0
        assert s["p99_s"] <= 2e-3       # rank 99 of 100 still 1ms...
        tr.observe("x", 1.0)            # ...until the tail thickens
        tr.observe("x", 1.0)
        s = tr.report()["spans"]["x"]
        assert s["p99_s"] >= 0.5        # now p99 lives in the 1s bucket

    def test_quantiles_are_monotone_and_bounded(self):
        import random

        rng = random.Random(7)
        tr = Tracer(enabled=True)
        for _ in range(500):
            tr.observe("x", rng.uniform(1e-6, 0.1))
        s = tr.report()["spans"]["x"]
        assert s["min_s"] <= s["p50_s"] * 2  # bucket resolution slack
        assert s["p50_s"] <= s["p90_s"] <= s["p99_s"] <= s["max_s"]

    def test_report_keeps_legacy_schema(self):
        tr = Tracer(enabled=True)
        with tr.span("merge"):
            pass
        s = tr.report()["spans"]["merge"]
        for k in ("count", "total_s", "mean_s", "max_s"):
            assert k in s  # the pinned pre-obs surface
        assert s["count"] == 1


# ---------------------------------------------------------------------------
# thread-safety: the satellite the old tracer failed
# ---------------------------------------------------------------------------


class TestTracerThreadSafety:
    def test_concurrent_spans_and_counters_are_exact(self):
        """8 threads hammer one tracer; totals must be EXACT. The old
        tracer's unlocked read-modify-write dicts lost updates under
        preemption (models/streaming.py decodes on a thread pool into
        the process-global tracer), which this pins at a switch
        interval tight enough to make the race near-certain."""
        tr = Tracer(enabled=True)
        threads, per = 8, 3000
        old_interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            def work():
                for _ in range(per):
                    tr.count("ops")
                    with tr.span("phase"):
                        pass
                    tr.observe("lag", 1e-5)

            ts = [threading.Thread(target=work) for _ in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        finally:
            sys.setswitchinterval(old_interval)
        rep = tr.report()
        assert rep["counters"]["ops"] == threads * per
        assert rep["spans"]["phase"]["count"] == threads * per
        assert rep["spans"]["lag"]["count"] == threads * per
        # histogram buckets must account for every observation too
        assert sum(
            rep["spans"]["lag"]["buckets"].values()
        ) == threads * per

    def test_streaming_decode_pool_records_spans(self, installed):
        """The real seam: the chunked thread-pooled decode records
        into the process-global tracer from pool threads."""
        tr, _ = installed
        from crdt_tpu.codec import v1
        from crdt_tpu.core.ids import DeleteSet
        from crdt_tpu.core.records import ItemRecord

        blobs = [
            v1.encode_update(
                [ItemRecord(client=c + 1, clock=k, parent_root="m",
                            key=f"k{k}", content=k)
                 for k in range(4)],
                DeleteSet(),
            )
            for c in range(8)
        ]
        from crdt_tpu.models.streaming import _Phases, stream_decode

        dec = stream_decode(blobs, chunk_blobs=2, ph=_Phases())
        assert len(dec["client"]) > 0
        spans = tr.report()["spans"]
        assert spans["decode"]["count"] >= len(blobs) // 2


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_wraparound_keeps_newest(self):
        fr = FlightRecorder(capacity=4, enabled=True)
        for i in range(10):
            fr.record("k", i=i)
        assert len(fr) == 4
        assert fr.recorded == 10
        assert [e["i"] for e in fr.events()] == [6, 7, 8, 9]
        # timestamps monotone oldest-first
        ts = [e["ts"] for e in fr.events()]
        assert ts == sorted(ts)

    def test_jsonl_dump_roundtrips(self, tmp_path):
        fr = FlightRecorder(capacity=8, enabled=True)
        fr.record("update.send", topic="t", size=12, digest="aa")
        fr.record("update.recv", topic="t", size=12, digest="aa")
        path = tmp_path / "dump.jsonl"
        text = fr.dump_jsonl(str(path))
        assert path.read_text() == text
        lines = [json.loads(ln) for ln in text.splitlines()]
        assert [e["kind"] for e in lines] == ["update.send", "update.recv"]
        assert all("ts" in e for e in lines)

    def test_disabled_records_nothing(self):
        fr = FlightRecorder(capacity=4, enabled=False)
        fr.record("k")
        assert len(fr) == 0 and fr.dump_jsonl() == ""

    def test_kind_filter(self):
        fr = FlightRecorder(enabled=True)
        fr.record("a")
        fr.record("b")
        fr.record("a")
        assert len(fr.events("a")) == 2


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


class TestPrometheusExport:
    def test_types_and_name_sanitization(self):
        tr = Tracer(enabled=True)
        tr.count("router.relay-sends")       # dot + dash -> _
        tr.gauge("9pending", 3)              # leading digit -> prefix
        with tr.span("converge.dispatch"):
            pass
        text = to_prometheus(tr.report())
        assert "# TYPE crdt_router_relay_sends counter" in text
        assert "crdt_router_relay_sends 1" in text
        assert "# TYPE crdt__9pending gauge" in text
        assert (
            "# TYPE crdt_converge_dispatch_seconds histogram" in text
        )
        assert "crdt_converge_dispatch_seconds_count 1" in text

    def test_histogram_buckets_cumulative_with_inf(self):
        tr = Tracer(enabled=True)
        tr.observe("x", 1e-6)
        tr.observe("x", 2e-6)
        tr.observe("x", 2e-6)
        tr.observe("x", 1e9)  # overflow
        text = to_prometheus(tr.report())
        assert 'crdt_x_seconds_bucket{le="1e-06"} 1' in text
        assert 'crdt_x_seconds_bucket{le="2e-06"} 3' in text
        assert 'crdt_x_seconds_bucket{le="+Inf"} 4' in text
        assert "crdt_x_seconds_count 4" in text
        # cumulative counts never decrease
        counts = [
            int(ln.rsplit(" ", 1)[1])
            for ln in text.splitlines() if "_bucket{" in ln
        ]
        assert counts == sorted(counts)

    def test_labeled_counters_pass_through(self):
        tr = Tracer(enabled=True)
        tr.count("bytes", 7, labels={"peer": "abc", "topic": "t"})
        text = to_prometheus(tr.report())
        assert 'crdt_bytes{peer="abc",topic="t"} 7' in text

    def test_one_type_line_per_metric_across_label_sets(self):
        # a duplicate TYPE line for one metric name is a fatal
        # exposition parse error: label variants group under ONE
        tr = Tracer(enabled=True)
        tr.count("bytes", 1, labels={"peer": "a"})
        tr.count("bytes", 2, labels={"peer": "b"})
        tr.gauge("depth", 3, labels={"topic": "x"})
        tr.gauge("depth", 4, labels={"topic": "y"})
        text = to_prometheus(tr.report())
        assert text.count("# TYPE crdt_bytes counter") == 1
        assert text.count("# TYPE crdt_depth gauge") == 1
        assert 'crdt_bytes{peer="a"} 1' in text
        assert 'crdt_bytes{peer="b"} 2' in text

    def test_json_snapshot_matches_report(self):
        tr = Tracer(enabled=True)
        tr.count("x")
        assert json.loads(snapshot_json(tr.report())) == json.loads(
            json.dumps(tr.report())
        )


# ---------------------------------------------------------------------------
# jax_profile hardening
# ---------------------------------------------------------------------------


class TestJaxProfile:
    def test_capture_works_on_cpu(self, tmp_path):
        import jax.numpy as jnp

        from crdt_tpu.utils.trace import jax_profile

        with jax_profile(str(tmp_path)):
            (jnp.arange(16) + 1).block_until_ready()

    def test_body_failure_stops_profiler(self, tmp_path):
        """A crash inside the block must stop the trace: the NEXT
        capture would otherwise fail with 'profiler already running'
        (the pre-obs bug class this satellite fixes)."""
        import jax.numpy as jnp

        from crdt_tpu.utils.trace import jax_profile

        with pytest.raises(RuntimeError, match="boom"):
            with jax_profile(str(tmp_path / "a")):
                raise RuntimeError("boom")
        with jax_profile(str(tmp_path / "b")):  # must not raise
            (jnp.arange(4) * 2).block_until_ready()

    def test_clear_error_without_profiler(self, monkeypatch):
        import types

        from crdt_tpu.utils.trace import jax_profile

        monkeypatch.setitem(
            sys.modules, "jax", types.SimpleNamespace()
        )
        with pytest.raises(RuntimeError, match="profiler unavailable"):
            with jax_profile("/tmp/never"):
                pass


# ---------------------------------------------------------------------------
# divergence sentinel + trace-id propagation (loopback fabric)
# ---------------------------------------------------------------------------


def _pair(net=None, **kw):
    from crdt_tpu.net import LoopbackNetwork, LoopbackRouter, Replica

    net = net or LoopbackNetwork()
    r1 = Replica(LoopbackRouter(net, "a"), topic="t", client_id=1, **kw)
    r2 = Replica(LoopbackRouter(net, "b"), topic="t", client_id=2, **kw)
    net.run()
    return net, r1, r2


class TestDivergenceSentinel:
    def test_silent_on_fault_free_run(self, installed):
        net, r1, r2 = _pair()
        for i in range(6):
            (r1 if i % 2 else r2).set("kv", f"k{i}", i)
        net.run()
        assert dict(r1.c) == dict(r2.c)
        r1.beacon()
        r2.beacon()
        net.run()
        assert r1.sentinel.events == [] and r2.sentinel.events == []
        tr, _ = installed
        assert tr.counters()["sentinel.agree"] >= 2
        # mutate again (invalidates the cached digest), re-beacon:
        # still silent, still agreeing on the NEW state
        r1.set("kv", "fresh", 99)
        net.run()
        r1.beacon()
        r2.beacon()
        net.run()
        assert r1.sentinel.events == [] and r2.sentinel.events == []
        assert tr.counters()["sentinel.agree"] >= 4

    def test_fires_on_injected_state_fork(self, installed):
        from crdt_tpu.net.faults import ForkFault

        tr, rec = installed
        net, r1, r2 = _pair()
        r1.set("kv", "k", 1)
        net.run()
        assert dict(r1.c) == dict(r2.c)
        # seeded fork: same id, different content, equal SVs — the
        # sync protocol sees nothing; only the beacon can
        fork = ForkFault(seed=3)
        fork.inject([r1, r2])
        assert r1.doc.state_vector() == r2.doc.state_vector()
        assert dict(r1.c) != dict(r2.c)
        r1.beacon()
        net.run()
        assert len(r2.sentinel.events) == 1
        ev = r2.sentinel.events[0]
        assert ev["kind"] == "divergence"
        assert ev["peer"] == "a" and ev["topic"] == "t"
        assert ev["local_digest"] != ev["peer_digest"]
        # the event carries a flight-recorder dump with the fork in it
        kinds = [
            json.loads(ln)["kind"]
            for ln in ev["flight_recorder"].splitlines()
        ]
        assert "fault.fork" in kinds
        assert tr.counters()["sentinel.divergence"] == 1
        # a permanent fork is raised ONCE per peer: later beacons of
        # the same fork bump the counter but never re-event (no
        # unbounded event/dump growth on a long-lived divergence)
        r1.beacon()
        net.run()
        assert len(r2.sentinel.events) == 1
        assert tr.counters()["sentinel.divergence"] == 2

    def test_sv_lag_stays_silent(self, installed):
        """Unequal SVs (ops in flight) are lag, not divergence."""
        net, r1, r2 = _pair()
        r1.set("kv", "k", 1)
        # beacon BEFORE delivery: r2's SV is behind
        r1.beacon()
        net.run()
        assert r2.sentinel.events == []
        tr, _ = installed
        assert tr.counters().get("sentinel.divergence", 0) == 0

    def test_deterministic_fork_schedule(self):
        from crdt_tpu.net.faults import ForkFault

        a, b = ForkFault(seed=9), ForkFault(seed=9)
        assert (a.client, a.key) == (b.client, b.key)
        assert ForkFault(seed=10).client != a.client or \
            ForkFault(seed=10).key != a.key


class TestTraceIdPropagation:
    def test_tid_rides_updates_and_measures_lag(self, installed):
        tr, rec = installed
        net, r1, r2 = _pair()
        r1.set("kv", "k", 1)
        r2.push("log", "e")
        net.run()
        assert dict(r1.c) == dict(r2.c)
        sent = [tuple(e["tid"]) for e in rec.events("update.send")]
        recv = [
            tuple(e["tid"]) for e in rec.events("update.recv")
            if e.get("tid")
        ]
        assert sent and set(sent) <= set(recv)
        # tid = (client, seq, ts): origin client rides the stamp
        clients = {t[0] for t in sent}
        assert clients == {1, 2}
        spans = tr.report()["spans"]
        assert spans["replica.propagation_lag"]["count"] >= 2
        assert spans["replica.convergence_lag"]["count"] >= 2
        assert "replica.propagation_lag_s" in tr.report()["gauges"]

    def test_anti_entropy_beacon_detects_fork_on_udp(self, installed):
        """The acceptance pin: under a seeded fault schedule plus a
        seeded state fork, the sentinel riding the REAL anti-entropy
        cadence (UDP routers, chaos faults on the wire) raises a
        divergence event carrying a flight-recorder dump; the
        fault-free run stays silent."""
        from crdt_tpu.net.faults import (
            FaultSchedule, ForkFault, install_faults, pump_until,
        )
        from crdt_tpu.net.replica import Replica
        from crdt_tpu.net.udp_router import UdpRouter

        def run(forked):
            routers = [UdpRouter() for _ in range(2)]
            routers[1].add_peer(*routers[0].addr)
            try:
                pump_until(
                    routers,
                    lambda: all(len(r.peers) == 1 for r in routers),
                    timeout_s=30.0,
                )
                reps = [
                    Replica(r, topic="room", client_id=i + 1,
                            probe_retry_s=0.05, anti_entropy_s=0.05)
                    for i, r in enumerate(routers)
                ]
                pump_until(
                    routers,
                    lambda: all(
                        len(r.peers_on("room")) == 1 for r in routers
                    ),
                    timeout_s=30.0,
                )
                if forked:
                    # chaos on the wire + the fork fault itself
                    for r in routers:
                        install_faults(
                            r, FaultSchedule(11, drop=0.05, delay=0.05)
                        )
                    ForkFault(seed=11).inject(reps)
                reps[0].set("kv", "x", 1)
                pump_until(
                    routers,
                    lambda: "kv" in reps[1].c,
                    timeout_s=30.0,
                )
                if forked:
                    pump_until(
                        routers,
                        lambda: any(r.sentinel.events for r in reps),
                        timeout_s=30.0,
                    )
                    events = [
                        e for r in reps for e in r.sentinel.events
                    ]
                    assert events[0]["kind"] == "divergence"
                    assert events[0]["flight_recorder"]
                else:
                    # let several anti-entropy/beacon rounds fire
                    deadline = time.monotonic() + 0.5
                    while time.monotonic() < deadline:
                        for r in routers:
                            r.poll()
                        time.sleep(0.002)
                    assert all(not r.sentinel.events for r in reps)
                    assert any(
                        r.sentinel.beacons_checked > 0 for r in reps
                    )
            finally:
                for r in routers:
                    r.close()

        run(forked=False)
        run(forked=True)


# ---------------------------------------------------------------------------
# round 18: tracer hardening (quantile edges, disabled-path freedom)
# ---------------------------------------------------------------------------


class TestTracerEdges:
    def test_quantile_unknown_span_is_zero(self):
        assert Tracer(enabled=True).quantile("nothing", 0.5) == 0.0

    def test_quantile_edges_single_sample(self):
        tr = Tracer(enabled=True)
        tr.observe("x", 3e-3)
        # one observation answers itself at EVERY q, 0 and 1 included
        for q in (0.0, 0.5, 0.99, 1.0):
            assert tr.quantile("x", q) == 3e-3

    def test_quantile_q0_and_q1(self):
        tr = Tracer(enabled=True)
        for v in (1e-6, 1e-3, 1.0):
            tr.observe("x", v)
        # q=0 is the rank-1 (minimum-bucket) estimate: the first
        # bucket's upper edge, never above the min's bucket edge
        assert tr.quantile("x", 0.0) <= 2e-6
        # q=1 (and beyond) is the observed max exactly
        assert tr.quantile("x", 1.0) == 1.0
        assert tr.quantile("x", 2.0) == 1.0

    def test_observe_disabled_records_nothing(self):
        tr = Tracer(enabled=False)
        tr.observe("x", 1.0)
        tr.count("c")
        tr.gauge("g", 2.0)
        rep = tr.report()
        assert rep["spans"] == {} and rep["counters"] == {} \
            and rep["gauges"] == {}

    def test_disabled_span_is_shared_object(self):
        tr = Tracer(enabled=False)
        assert tr.span("a") is tr.span("b")

    def test_histogram_summary_matches_report(self):
        from crdt_tpu.obs.tracer import Histogram

        tr = Tracer(enabled=True)
        h = Histogram()
        for v in (1e-5, 2e-4, 3e-3):
            tr.observe("x", v)
            h.add(v)
        # the report is the histogram's summary plus the span's self
        # time (all of an observed duration)
        assert tr.report()["spans"]["x"] == dict(h.summary(),
                                                 self_s=h.total_s)


# ---------------------------------------------------------------------------
# round 18: Prometheus sanitization-collision disambiguation
# ---------------------------------------------------------------------------


class TestPrometheusCollisions:
    def test_distinct_keys_never_merge(self):
        import zlib

        tr = Tracer(enabled=True)
        tr.count("guard.a-b", 3)
        tr.count("guard.a_b", 4)
        text = to_prometheus(tr.report())
        # both raw keys sanitize to crdt_guard_a_b: each colliding
        # member gets its deterministic crc32 suffix, no silent merge
        tag1 = zlib.crc32(b"counters:guard.a-b") & 0xFFFFFFFF
        tag2 = zlib.crc32(b"counters:guard.a_b") & 0xFFFFFFFF
        assert f"crdt_guard_a_b_{tag1:08x} 3" in text
        assert f"crdt_guard_a_b_{tag2:08x} 4" in text
        assert "\ncrdt_guard_a_b 3" not in text
        assert "\ncrdt_guard_a_b 4" not in text
        # deterministic: a fresh render is byte-identical
        assert to_prometheus(tr.report()) == text

    def test_counter_gauge_name_clash_disambiguated(self):
        tr = Tracer(enabled=True)
        tr.count("depth", 1)
        tr.gauge("depth", 2.0)
        text = to_prometheus(tr.report())
        # pre-fix this emitted TWO TYPE lines for crdt_depth (a fatal
        # exposition parse error); now each section owns its series
        names = [
            ln.split()[2] for ln in text.splitlines()
            if ln.startswith("# TYPE")
        ]
        assert len(names) == len(set(names))
        assert any(n.startswith("crdt_depth_") for n in names)

    def test_collision_free_names_unchanged(self):
        tr = Tracer(enabled=True)
        tr.count("tenant.shed", 5)
        tr.gauge("tenant.pending_bytes", 7)
        with tr.span("converge.dispatch"):
            pass
        text = to_prometheus(tr.report())
        assert "crdt_tenant_shed 5" in text
        assert "crdt_tenant_pending_bytes 7" in text
        assert "crdt_converge_dispatch_seconds_count 1" in text

    def test_labeled_variants_share_one_series(self):
        tr = Tracer(enabled=True)
        tr.count("slo.breaches", 1, labels={"tenant": "a"})
        tr.count("slo.breaches", 2, labels={"tenant": "b"})
        text = to_prometheus(tr.report())
        assert text.count("# TYPE crdt_slo_breaches counter") == 1
        assert 'crdt_slo_breaches{tenant="a"} 1' in text
        assert 'crdt_slo_breaches{tenant="b"} 2' in text


# ---------------------------------------------------------------------------
# round 18: per-tenant SLO ledger
# ---------------------------------------------------------------------------


class TestSLOLedger:
    def test_breach_counting_and_routes(self, installed):
        from crdt_tpu.obs.slo import SLOLedger

        led = SLOLedger(slo_ms=10.0)
        led.converged("t1", [0.001, 0.020], "delta")
        led.served("t1", [0.001, 0.020])  # one over the 10ms bar
        led.shed("t1", 3)                 # sheds are breaches
        rep = led.report()
        t1 = rep["tenants"]["t1"]
        assert t1["breaches"] == 1 + 3
        assert t1["routes"] == {"delta": 1, "cold": 0,
                                "fallback": 0, "shed": 3}
        assert t1["ingest_to_served"]["count"] == 2
        assert t1["ingest_to_converged"]["count"] == 2
        # window: [F, T, T, T, T] -> burn 0.8
        assert t1["burn_rate"] == 0.8
        assert rep["total_breaches"] == 4
        tr, _ = installed
        assert tr.counters()["slo.breaches"] == 4
        assert tr.counters()['slo.breaches{tenant="t1"}'] == 4
        assert tr.counters()['slo.route_shed{tenant="t1"}'] == 3

    def test_env_objective(self, monkeypatch):
        from crdt_tpu.obs.slo import SLOLedger

        monkeypatch.setenv("CRDT_TPU_SLO_MS", "5")
        assert SLOLedger().slo_ms == 5.0
        monkeypatch.setenv("CRDT_TPU_SLO_MS", "garbage")
        assert SLOLedger().slo_ms == 250.0

    def test_zero_objective_breaches_everything(self):
        from crdt_tpu.obs.slo import SLOLedger

        led = SLOLedger(slo_ms=0.0)
        led.served("t", [1e-9, 1e-6])
        assert led.breaches("t") == 2

    def test_flood_breaches_pin_admission_oracle(self, installed):
        """The acceptance exactness pin: under the seeded round-14
        flood, the flooding tenant's breach count equals its shed
        count equals the admission oracle (submitted minus the
        admitted suffix the budget kept), while every neighbor shows
        ZERO breaches — diagnosable from the ledger alone."""
        from crdt_tpu.models.multidoc import MultiDocServer

        from crdt_tpu.codec import v1
        from crdt_tpu.core.ids import DeleteSet
        from crdt_tpu.core.records import ItemRecord

        def blob(c, k0, n=4):
            return v1.encode_update(
                [ItemRecord(client=c, clock=k0 + i, parent_root="m",
                            key=f"k{i}", content=k0 + i)
                 for i in range(n)],
                DeleteSet(),
            )

        # a generous objective: nothing served on time breaches, so
        # EVERY breach is a shed — exactly countable
        srv = MultiDocServer(tenant_max_pending_bytes=1 << 20,
                             tenant_max_pending_updates=4,
                             slo_ms=1e9)
        neighbors = [f"n{i}" for i in range(3)]
        for i, d in enumerate(neighbors):
            assert srv.submit(d, blob(10 + i, 0)) == 0
        flooder = "flood!"
        submitted, shed_oracle = 0, 0
        for j in range(23):  # 23 blobs into a 4-update budget
            shed_oracle += srv.submit(flooder, blob(99, j * 4))
            submitted += 1
        assert shed_oracle == submitted - 4  # keep-the-newest suffix
        srv.tick()
        assert not srv.dirty_docs()
        assert srv.slo.breaches(flooder) == shed_oracle
        routes = srv.slo.route_counts(flooder)
        assert routes["shed"] == shed_oracle
        rep = srv.slo.report()
        ften = rep["tenants"][flooder]
        # the admitted suffix (4 blobs) was served, late never
        assert ften["ingest_to_served"]["count"] == 4
        for d in neighbors:
            assert srv.slo.breaches(d) == 0
            assert rep["tenants"][d]["breaches"] == 0
            assert rep["tenants"][d]["ingest_to_served"]["count"] == 1
        # the labeled shed attribution rode the guard layer too
        tr, rec = installed
        key = 'tenant.shed{tenant=%s}' % '"flood!"'
        assert tr.counters()[key] == shed_oracle
        shed_events = rec.events("tenant.shed")
        assert shed_events and all(
            e["doc"] == flooder for e in shed_events
        )
        assert sum(e["count"] for e in shed_events) == shed_oracle


# ---------------------------------------------------------------------------
# round 18: tick-timeline profiler
# ---------------------------------------------------------------------------


def _fake_tick(tl, base, i):
    """One synthetic tick with two overlapping dispatch windows."""
    tl.tick_begin(i)
    t0 = tl._cur["t0"]
    tl.add_phase("pack", t0, t0 + 0.010)
    a = tl.dispatch_begin(t=t0 + 0.010)
    tl.add_phase("pack", t0 + 0.012, t0 + 0.020)
    b = tl.dispatch_begin(t=t0 + 0.020)
    tl.dispatch_end(a, t0 + 0.022, t0 + 0.030)
    tl.dispatch_end(b, t0 + 0.030, t0 + 0.041)
    return tl.tick_end()


class TestTickTimeline:
    def test_disabled_is_noop(self):
        from crdt_tpu.obs.timeline import TickTimeline

        tl = TickTimeline(enabled=False)
        tl.tick_begin(1)
        with tl.phase("x"):
            pass
        assert tl.dispatch_begin() is None
        assert tl.tick_end() is None
        assert len(tl) == 0
        # the disabled phase() is one shared object — no allocation
        assert tl.phase("a") is tl.phase("b")

    def test_ring_wraparound_keeps_newest(self):
        from crdt_tpu.obs.timeline import TickTimeline

        tl = TickTimeline(capacity=4, enabled=True)
        for i in range(10):
            tl.tick_begin(i)
            with tl.phase("p"):
                pass
            tl.tick_end()
        assert len(tl) == 4
        assert tl.recorded == 10
        assert [r["tick"] for r in tl.records()] == [6, 7, 8, 9]

    def test_overlap_and_stall_accounting(self):
        from crdt_tpu.obs.timeline import TickTimeline

        tl = TickTimeline(enabled=True)
        rec = _fake_tick(tl, 0.0, 1)
        # stall = the two fetch waits: 8ms + 11ms
        assert rec["stall_ms"] == pytest.approx(19.0, abs=0.2)
        # lanes: pack 18ms + merged dispatch window [10,41]=31ms;
        # busy 49ms over a ~41ms wall -> efficiency strictly > 0
        assert rec["lanes"]["pack"] == pytest.approx(0.018, abs=1e-6)
        assert rec["lanes"]["dispatch"] == pytest.approx(
            0.031, abs=1e-6
        )
        assert rec["overlap_efficiency"] > 0.0
        assert len(rec["dispatches"]) == 2

    def test_overlap_of_bounds(self):
        from crdt_tpu.obs.timeline import overlap_of

        # fully serial: wall == busy sum
        assert overlap_of({"a": 1.0, "b": 1.0}, 2.0) == 0.0
        # fully hidden: wall == longest lane
        assert overlap_of({"a": 1.0, "b": 1.0}, 1.0) == 1.0
        # degenerate single lane: clamped, not divide-by-zero
        assert overlap_of({"a": 1.0}, 1.0) == 1.0
        assert overlap_of({}, 0.5) == 0.0

    def test_perfetto_schema(self):
        from crdt_tpu.obs.timeline import TickTimeline

        tl = TickTimeline(enabled=True)
        for i in range(3):
            _fake_tick(tl, 0.0, i)
        pf = tl.to_perfetto()
        assert set(pf) == {"traceEvents", "displayTimeUnit"}
        evs = pf["traceEvents"]
        assert evs, "no events exported"
        for ev in evs:
            for k in ("name", "ph", "ts", "pid", "tid"):
                assert k in ev, (ev, k)
            assert ev["ph"] in ("X", "M", "C")
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
        # dispatch windows live on the device track (tid 2)
        disp = [e for e in evs
                if e["ph"] == "X" and e["name"].startswith("dispatch")]
        assert disp and all(e["tid"] == 2 for e in disp)
        # the whole thing is valid JSON end to end
        assert json.loads(tl.perfetto_json())["traceEvents"]

    def test_perfetto_json_writes_file(self, tmp_path):
        from crdt_tpu.obs.timeline import TickTimeline

        tl = TickTimeline(enabled=True)
        _fake_tick(tl, 0.0, 0)
        p = tmp_path / "trace.json"
        text = tl.perfetto_json(str(p))
        assert p.read_text() == text


# ---------------------------------------------------------------------------
# round 18: HTTP scrape endpoint + the serve() acceptance run
# ---------------------------------------------------------------------------


def _mt_blob(c, k0, n=4):
    from crdt_tpu.codec import v1
    from crdt_tpu.core.ids import DeleteSet
    from crdt_tpu.core.records import ItemRecord

    return v1.encode_update(
        [ItemRecord(client=c, clock=k0 + i, parent_root="m",
                    key=f"k{i}", content=k0 + i)
         for i in range(n)],
        DeleteSet(),
    )


@pytest.fixture
def timeline_installed():
    from crdt_tpu.obs import TickTimeline, get_timeline, set_timeline

    old = get_timeline()
    tl = set_timeline(TickTimeline(enabled=True))
    try:
        yield tl
    finally:
        set_timeline(old)


class TestObsHTTP:
    def _get(self, url):
        from urllib.request import urlopen

        with urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()

    def test_endpoints_smoke(self, installed, timeline_installed):
        from crdt_tpu.obs import ObsHTTPServer

        tr, rec = installed
        tr.count("tenant.submitted", 3)
        tr.gauge("tenant.pending_bytes", 64)
        with tr.span("converge.dispatch"):
            pass
        rec.record("update.send", topic="room", digest="aa",
                   tid=[1, 1, 0.0], hop=0)
        rec.record("update.recv", topic="room", digest="aa",
                   tid=[1, 1, 0.0], hop=1, peer="p1")
        rec.record("tenant.shed", doc="flood!", count=2, bytes=99)
        with ObsHTTPServer(port=0, snapshot_extra=lambda: {
            "slo": {"slo_ms": 250.0},
        }) as obs:
            st, metrics = self._get(obs.url + "/metrics")
            assert st == 200
            assert "crdt_tenant_submitted 3" in metrics
            assert "crdt_converge_dispatch_seconds_count 1" in metrics

            st, snap = self._get(obs.url + "/snapshot")
            assert st == 200
            data = json.loads(snap)
            assert data["tracer"]["counters"]["tenant.submitted"] == 3
            assert data["slo"]["slo_ms"] == 250.0

            # filters: kind, doc (matches doc OR topic), peer, limit
            st, ev = self._get(obs.url + "/events?kind=tenant.shed")
            assert st == 200
            lines = [json.loads(ln) for ln in ev.splitlines()]
            assert [e["kind"] for e in lines] == ["tenant.shed"]
            assert lines[0]["doc"] == "flood!"
            st, ev = self._get(obs.url + "/events?doc=room")
            kinds = {json.loads(ln)["kind"]
                     for ln in ev.splitlines()}
            assert kinds == {"update.send", "update.recv"}
            st, ev = self._get(obs.url + "/events?peer=p1")
            assert len(ev.splitlines()) == 1
            st, ev = self._get(
                obs.url + "/events?doc=room&limit=1"
            )
            assert len(ev.splitlines()) == 1

            st, tl_text = self._get(obs.url + "/timeline")
            assert st == 200
            assert "traceEvents" in json.loads(tl_text)

        # unknown path: 404 with the route list, not a crash
        obs2 = ObsHTTPServer(port=0).start()
        try:
            from urllib.error import HTTPError

            with pytest.raises(HTTPError) as ei:
                self._get(obs2.url + "/nope")
            assert ei.value.code == 404
        finally:
            obs2.stop()

    def test_serve_flood_slo_timeline_scrapeable_live(
            self, installed, timeline_installed):
        """The round-18 acceptance pin: a seeded serve() run under
        the round-14 flood scenario yields (a) a per-tenant SLO
        report whose flooding-tenant breach/shed counts equal the
        admission oracle while neighbors hold zero, (b) a
        schema-valid Perfetto timeline whose double-buffered ticks
        show overlap_efficiency > 0, (c) all of it scraped LIVE from
        the HTTP endpoint while serve() is mid-run."""
        from urllib.request import urlopen

        from crdt_tpu.models.multidoc import MultiDocServer
        from crdt_tpu.obs import ObsHTTPServer

        tl = timeline_installed
        neighbors = [f"n{i}" for i in range(6)]
        flooder = "flood!"
        srv = MultiDocServer(
            # small dispatches: >=3 async batches per cold tick, so
            # the double-buffer has windows to overlap
            max_rows_per_dispatch=60,
            tenant_max_pending_bytes=1 << 20,
            tenant_max_pending_updates=4,
            slo_ms=1e9,  # served-on-time never breaches: breach==shed
        )
        obs = ObsHTTPServer(port=0, snapshot_extra=lambda: {
            "slo": srv.slo.report(),
        }).start()
        live: dict = {}
        oracle = {"submitted": 0, "shed": 0}

        def source():
            # batch 1: neighbors' histories (30 ops each)
            yield [(d, _mt_blob(10 + i, 0, 30))
                   for i, d in enumerate(neighbors)]
            # mid-run scrape: serve()'s ingest hook pulls this batch
            # while tick 1's dispatches are still in flight
            with urlopen(obs.url + "/metrics", timeout=10) as r:
                live["metrics"] = (r.status, r.read().decode())
            with urlopen(obs.url + "/snapshot", timeout=10) as r:
                live["snapshot"] = json.loads(r.read().decode())
            # batch 2: the flood — 23 blobs into a 4-update budget
            yield [(flooder, _mt_blob(99, j * 4)) for j in range(23)]
            yield [(d, _mt_blob(10 + i, 30, 2))
                   for i, d in enumerate(neighbors)]

        class _Counting:
            def __init__(self, it):
                self.it = it

            def __iter__(self):
                return self

            def __next__(self):
                batch = next(self.it)
                oracle["submitted"] += len(batch)
                return batch

        rep = srv.serve(_Counting(source()), max_ticks=10)
        obs_stop_exc = None
        try:
            # (c) live scrape happened mid-run and carried real data
            assert live["metrics"][0] == 200
            assert "crdt_tenant_submitted" in live["metrics"][1]
            assert "slo" in live["snapshot"]
            # (a) SLO exactness against the admission oracle
            shed_oracle = srv.shed_count
            assert shed_oracle == 23 - 4  # keep-the-newest suffix
            assert srv.slo.breaches(flooder) == shed_oracle
            assert srv.slo.route_counts(flooder)["shed"] == \
                shed_oracle
            for d in neighbors:
                assert srv.slo.breaches(d) == 0
            sr = srv.slo.report()
            assert sr["total_breaches"] == shed_oracle
            assert sr["tenants"][flooder]["burn_rate"] > 0.5
            # every tenant's serves are in the route mix
            assert rep.docs == sum(
                sum(t["routes"][r] for r in
                    ("delta", "cold", "fallback"))
                for t in sr["tenants"].values()
            )
            # (b) the double-buffered legs overlapped, measurably
            recs = tl.records()
            dbl = [r for r in recs if len(r["dispatches"]) > 1]
            assert dbl, "no double-buffered tick recorded"
            for r in dbl:
                assert r["overlap_efficiency"] > 0.0, r
            pf = tl.to_perfetto()
            for ev in pf["traceEvents"]:
                for k in ("name", "ph", "ts", "pid", "tid"):
                    assert k in ev
                if ev["ph"] == "X":
                    assert ev["dur"] >= 0
            names = {e["name"] for e in pf["traceEvents"]}
            assert any(n.startswith("dispatch(") for n in names)
            assert "prepare" in names and "settle" in names
            # the endpoint serves the SAME timeline
            with urlopen(obs.url + "/timeline", timeout=10) as r:
                served_pf = json.loads(r.read().decode())
            assert len(served_pf["traceEvents"]) >= len(
                pf["traceEvents"]
            )
        finally:
            try:
                obs.stop()
            except Exception as exc:  # pragma: no cover
                obs_stop_exc = exc
        assert obs_stop_exc is None


# ---------------------------------------------------------------------------
# round 18: obsq CLI round-trip
# ---------------------------------------------------------------------------


class TestObsqCLI:
    @pytest.fixture
    def dumps(self, tmp_path):
        a = FlightRecorder(enabled=True)
        b = FlightRecorder(enabled=True)
        # process A originates two updates; B receives them one hop
        # later (ts offsets are synthetic but monotone per ring)
        a.record("update.send", topic="room", replica="A", size=10,
                 digest="d1", tid=[1, 1, 100.0], hop=0)
        a.record("update.send", topic="room", replica="A", size=12,
                 digest="d2", tid=[1, 2, 100.5], hop=0)
        b.record("update.recv", topic="room", replica="B", peer="A",
                 size=10, digest="d1", tid=[1, 1, 100.0], hop=1)
        b.record("update.recv", topic="room", replica="B", peer="A",
                 size=12, digest="d2", tid=[1, 2, 100.5], hop=1)
        b.record("divergence", topic="room", replica="B", peer="A",
                 local_digest="xx", peer_digest="yy")
        pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        a.dump_jsonl(str(pa))
        b.dump_jsonl(str(pb))
        return str(pa), str(pb)

    def _run(self, capsys, *argv):
        import obsq_under_test as obsq

        rc = obsq.main(list(argv))
        out = capsys.readouterr().out
        return rc, out

    @pytest.fixture(autouse=True)
    def _import_obsq(self, monkeypatch):
        import importlib
        import os
        import sys as _sys

        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        monkeypatch.syspath_prepend(os.path.join(repo, "tools"))
        mod = importlib.import_module("obsq")
        _sys.modules.setdefault("obsq_under_test", mod)

    def test_filter_by_kind_doc_tid(self, capsys, dumps):
        pa, pb = dumps
        rc, out = self._run(capsys, "filter", pa, pb,
                            "--kind", "update.recv")
        assert rc == 0
        evs = [json.loads(ln) for ln in out.splitlines()]
        assert len(evs) == 2
        assert all(e["kind"] == "update.recv" for e in evs)
        assert all(e["_src"] == "b.jsonl" for e in evs)
        # --doc matches the topic field; --tid is a client:seq prefix
        rc, out = self._run(capsys, "filter", pa, pb,
                            "--doc", "room", "--tid", "1:2")
        evs = [json.loads(ln) for ln in out.splitlines()]
        assert {e["kind"] for e in evs} == \
            {"update.send", "update.recv"}
        assert all(e["tid"][:2] == [1, 2] for e in evs)
        rc, out = self._run(capsys, "filter", pa,
                            "--doc", "elsewhere")
        assert rc == 0 and out.strip() == ""

    def test_summary(self, capsys, dumps):
        rc, out = self._run(capsys, "summary", *dumps)
        assert rc == 0
        s = json.loads(out)
        assert s["events"] == 5
        assert s["kinds"]["update.send"] == 2
        assert s["kinds"]["divergence"] == 1
        assert s["sources"] == {"a.jsonl": 2, "b.jsonl": 3}

    def test_latency_pairs_across_dumps(self, capsys, dumps):
        rc, out = self._run(capsys, "latency", *dumps)
        assert rc == 0
        lat = json.loads(out)
        assert lat["sends"] == 2
        assert lat["pairs"] == 2
        assert lat["unmatched_recv"] == 0
        assert lat["propagation"]["count"] == 2
        assert lat["hops"] == {"1": 2}

    def test_diverge_correlates(self, capsys, dumps):
        rc, out = self._run(capsys, "diverge", *dumps,
                            "--context", "2")
        assert rc == 0
        dv = json.loads(out)
        assert dv["divergences"] == 1
        ev = dv["events"][0]
        assert ev["divergence"]["local_digest"] == "xx"
        assert set(ev["context"]) == {"a.jsonl", "b.jsonl"}
        assert all(len(c) <= 2 for c in ev["context"].values())
        # both sides saw d1/d2 before the fork — the common tail
        assert "d2" in ev["last_common_digests"]

    def test_unreadable_input_exits_2(self, capsys, tmp_path):
        import obsq_under_test as obsq

        rc = obsq.main(["summary", str(tmp_path / "missing.jsonl")])
        assert rc == 2


class TestLabelEscaping:
    def test_hostile_label_values_cannot_corrupt_exposition(self):
        """Round 18 made label values caller-controlled (doc ids ->
        tenant= labels): quotes, backslashes and newlines must escape
        per the exposition spec, never inject lines or break parse."""
        tr = Tracer(enabled=True)
        tr.count("tenant.shed", 1, labels={"tenant": 'a"b'})
        tr.count("tenant.shed", 2, labels={"tenant": "c\\d"})
        tr.count("tenant.shed", 3,
                 labels={"tenant": "evil\nfake_metric 9"})
        text = to_prometheus(tr.report())
        assert 'crdt_tenant_shed{tenant="a\\"b"} 1' in text
        assert 'crdt_tenant_shed{tenant="c\\\\d"} 2' in text
        # the newline is escaped INTO the value: no injected line
        assert "\nfake_metric 9" not in text
        assert 'tenant="evil\\nfake_metric 9"' in text
        # every non-comment line still parses as `name{...} value`
        for ln in text.splitlines():
            if ln.startswith("#"):
                continue
            float(ln.rsplit(" ", 1)[1])  # the value field parses
            assert ln.count("{") <= 1

    def test_slo_ledger_with_hostile_doc_id(self, installed):
        from crdt_tpu.obs.slo import SLOLedger

        led = SLOLedger(slo_ms=0.0)
        led.served('doc"with"quotes', [1e-3])
        text = to_prometheus()
        assert 'tenant="doc\\"with\\"quotes"' in text


class TestEventsLimitSemantics:
    def test_limit_zero_returns_nothing(self):
        from crdt_tpu.obs.http import _filter_events

        evs = [{"kind": "a"}, {"kind": "b"}, {"kind": "c"}]
        assert _filter_events(evs, {"limit": ["0"]}) == []
        assert _filter_events(evs, {"limit": ["2"]}) == evs[-2:]
        # over-large and garbage limits degrade to "all"
        assert _filter_events(evs, {"limit": ["99"]}) == evs
        assert _filter_events(evs, {"limit": ["x"]}) == evs


class TestObsqExitCodes:
    def test_malformed_jsonl_exits_2(self, tmp_path, capsys,
                                     monkeypatch):
        import importlib
        import os

        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        monkeypatch.syspath_prepend(os.path.join(repo, "tools"))
        obsq = importlib.import_module("obsq")
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"kind": "a"}\nnot json at all\n')
        rc = obsq.main(["summary", str(bad)])
        assert rc == 2
        err = capsys.readouterr().err
        assert "not JSONL" in err


# ---------------------------------------------------------------------------
# one span primitive: profiler annotation + self time
# ---------------------------------------------------------------------------


class _StubAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs each
    open and close by name."""

    log: list = []
    capturing = True

    def __init__(self, name):
        self.name = name

    @staticmethod
    def is_enabled():
        return _StubAnnotation.capturing

    def __enter__(self):
        _StubAnnotation.log.append(("enter", self.name))

    def __exit__(self, *exc):
        _StubAnnotation.log.append(("exit", self.name))
        return False


class TestSpanPrimitive:
    @pytest.fixture
    def stub(self, monkeypatch):
        from crdt_tpu.obs import profiling

        _StubAnnotation.log = []
        monkeypatch.setattr(profiling, "_annotation_cls", _StubAnnotation)
        return _StubAnnotation.log

    def test_enabled_span_opens_the_crdt_annotation(self, stub):
        t = Tracer(enabled=True)
        with t.span("tick"):
            with t.span("tick.route"):
                pass
        assert stub == [("enter", "crdt.tick"), ("enter", "crdt.tick.route"),
                        ("exit", "crdt.tick.route"), ("exit", "crdt.tick")]
        assert t.report()["spans"]["tick"]["count"] == 1

    def test_disabled_span_opens_no_annotation(self, stub):
        t = Tracer(enabled=False)
        with t.span("tick"):
            pass
        assert stub == []

    def test_no_annotation_while_no_capture_records(self, stub,
                                                     monkeypatch):
        monkeypatch.setattr(_StubAnnotation, "capturing", False)
        t = Tracer(enabled=True)
        with t.span("tick"):
            pass
        assert stub == []
        assert t.report()["spans"]["tick"]["count"] == 1

    def test_self_time_is_duration_less_children(self, stub):
        t = Tracer(enabled=True)
        with t.span("outer"):
            time.sleep(0.004)
            for _ in range(2):
                with t.span("mid"):
                    time.sleep(0.002)
                    with t.span("leaf"):
                        time.sleep(0.003)
        sp = t.report()["spans"]
        assert sp["leaf"]["self_s"] == sp["leaf"]["total_s"]
        assert sp["mid"]["self_s"] == pytest.approx(
            sp["mid"]["total_s"] - sp["leaf"]["total_s"], abs=1e-12)
        assert sp["outer"]["self_s"] == pytest.approx(
            sp["outer"]["total_s"] - sp["mid"]["total_s"], abs=1e-12)
        assert sp["outer"]["self_s"] >= 0.004
        # observe() has no children: all of it is self time
        t.observe("ext", 0.5)
        assert t.report()["spans"]["ext"]["self_s"] == 0.5

    def test_self_time_is_per_thread(self, stub):
        """A span open on one thread is no parent of a span on
        another: each thread keeps its own stack."""
        t = Tracer(enabled=True)
        gate = threading.Barrier(2, timeout=10)

        def lone():
            with t.span("lone"):
                gate.wait()   # the other thread's spans run now
                gate.wait()

        def nested():
            gate.wait()
            with t.span("outer"):
                with t.span("inner"):
                    time.sleep(0.003)
            gate.wait()

        ths = [threading.Thread(target=f) for f in (lone, nested)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=20)
            assert not th.is_alive()
        sp = t.report()["spans"]
        assert sp["lone"]["self_s"] == sp["lone"]["total_s"]
        assert sp["lone"]["total_s"] >= sp["outer"]["total_s"]
        assert sp["outer"]["self_s"] == pytest.approx(
            sp["outer"]["total_s"] - sp["inner"]["total_s"], abs=1e-12)

    def test_disabled_span_allocates_nothing(self):
        import tracemalloc

        import crdt_tpu.obs.tracer as tr

        t = Tracer(enabled=False)
        assert t.span("a") is t.span("b")
        with t.span("warm"):
            pass
        flt = [tracemalloc.Filter(True, tr.__file__)]
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot().filter_traces(flt)
            for _ in range(1000):
                with t.span("decode"):
                    pass
            after = tracemalloc.take_snapshot().filter_traces(flt)
        finally:
            tracemalloc.stop()
        grown = [s for s in after.compare_to(before, "lineno")
                 if s.size_diff > 0]
        assert not grown, grown
        assert not t.report()["spans"]
