"""Tier-1 guard over the bench pipeline accounting + observability.

``bench.py --smoke`` replays a tiny trace through all three contenders
(numpy baseline, one-shot device pipeline, streaming executor) on the
CPU backend, asserts equality, and prints one JSON line with the
per-phase + overlap accounting. Running it here catches accounting
regressions — a phase silently re-serializing, a lane dropping out of
the busy sum, the streamed path diverging — without a full scale run.

The observability half: the smoke runs with tracing enabled and
writes a BENCH_OUT-shaped artifact embedding the full tracer report,
and this test asserts the DOCUMENTED hot-path spans (README
"Observability" registry) are present with real p50/p99 data — so the
instrumentation cannot silently rot out of the hot path.
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np

# the hot-path span registry tier-1 pins (README "Observability"):
# any rename or dropped hook fails here, not in a future postmortem
HOT_PATH_SPANS = (
    "decode", "pack", "converge.dispatch", "converge.fetch",
    "gather", "materialize", "compact", "persist", "persist.compact",
    "stage", "tick",
)


def test_bench_smoke_mode(tmp_path):
    # CI points BENCH_SMOKE_ARTIFACT at the workspace so THIS run's
    # obs snapshot uploads as the workflow artifact — the smoke is
    # expensive enough that CI must not run it a second time just to
    # place the file somewhere known
    art = (pathlib.Path(os.environ["BENCH_SMOKE_ARTIFACT"])
           if os.environ.get("BENCH_SMOKE_ARTIFACT")
           else tmp_path / "smoke_bench_out.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_SMOKE_OUT"] = str(art)
    env["BENCH_TRACE"] = "1"  # pin: an exported BENCH_TRACE=0 must
    #                           not turn this into a confusing failure
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--smoke"],
        env=env, capture_output=True, text=True, timeout=240, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["ok"] is True
    assert out["platform"] == "cpu"
    ph = out["stream_phases_s"]
    # every pipeline lane accounted, overlap metrics present
    for key in ("decode", "converge", "materialize", "busy_sum_s",
                "wall_s", "wall_vs_phases", "overlap_efficiency"):
        assert key in ph, key
    assert ph["busy_sum_s"] > 0
    assert 0.0 <= ph["overlap_efficiency"] <= 1.0
    # the serial contenders' phase dicts stay r05-shaped
    for key in ("decode", "pack", "converge", "materialize", "compact"):
        assert key in out["phases_device_s"], key

    # the BENCH_OUT-shaped artifact embeds a non-empty tracer report
    # with the documented hot-path spans (p50/p99 per span)
    assert out.get("tracer_spans_ok") is True
    full = json.loads(art.read_text())
    report = full["tracer"]
    assert report["spans"], "embedded tracer report is empty"
    for name in HOT_PATH_SPANS:
        span = report["spans"].get(name)
        assert span is not None, f"hot-path span {name!r} missing"
        assert span["count"] > 0
        for k in ("p50_s", "p90_s", "p99_s", "max_s", "total_s"):
            assert k in span, (name, k)
        assert span["p50_s"] <= span["p99_s"] + 1e-12
        assert span["p99_s"] <= span["max_s"] + 1e-12

    # the byte-accounting registry (transfer diet): counters, latency
    # histograms, and the narrowing gauge must all be live, or the
    # xfer regression gate reads nothing and the diet can rot
    for cname in ("xfer.h2d_bytes", "xfer.h2d_puts", "xfer.d2h_bytes",
                  "xfer.d2h_fetches"):
        assert report["counters"].get(cname, 0) > 0, cname
    for sname in ("xfer.h2d", "xfer.d2h"):
        span = report["spans"].get(sname)
        assert span is not None and span["count"] > 0, sname
    assert "xfer.narrowed_ratio" in report["gauges"]
    # per-column chosen widths recorded (the width histogram)
    assert any(k.startswith("xfer.col_width{") for k in
               report["counters"]), "per-column width histogram missing"
    # the smoke device leg's own xfer digest rides the stdout line
    assert out["xfer"]["h2d_bytes"] > 0
    assert out["xfer"]["d2h_bytes"] > 0

    # the round-12 kernel-dispatch registry (sort diet): every fused
    # converge counts its static kernel-mode decision
    # (converge.pallas{mode=...}), so the ablation evidence and the
    # metrics_diff gates always have data to read
    assert out.get("kernel_registry_ok") is True
    assert any(k.startswith('converge.pallas{mode=')
               for k in report["counters"]), \
        "converge.pallas mode counter missing from tracer report"

    # the round-13 sharded-converge registry: the smoke runs a 2-way
    # sharded converge on its forced 2-device mesh, byte-identical to
    # the single-chip leg, and the shard.* evidence the multichip
    # regression gate reads must be live
    assert out.get("shard_registry_ok") is True
    for cname in ("shard.dispatches", "shard.boundary_bytes"):
        assert report["counters"].get(cname, 0) > 0, cname
    assert "shard.shards" in report["gauges"]
    assert "converge.wyllie_rounds" in report["gauges"]

    # the round-23 subtree-split registry: the smoke replays a small
    # branching-tree + deep-map-chain doc at a tiny width — a shape
    # the round-13 chain split refused outright — byte-identical to
    # the split-disabled plan (asserted inside the leg, which also
    # requires the cut counts to fire), and the gauges the --conflict
    # regression gate reads stay in the registry (the final report
    # carries the LAST staging's values, so only presence pins here;
    # the flag rides the artifact — the stdout line's 1500-byte
    # budget drops it, like phases_numpy_s)
    assert full.get("subtree_split_ok") is True
    assert "converge.subtree_cuts" in report["gauges"]
    assert "converge.map_chain_cuts" in report["gauges"]
    assert "converge.map_rounds" in report["gauges"]

    # the round-14 multi-tenant registry: the smoke runs a tiny
    # mixed-tenant batch through MultiDocServer, digest-identical to
    # the per-doc baseline, and publishes the gated keys + tenant.*
    # counters the multitenant regression gate reads
    assert out.get("multitenant_registry_ok") is True
    mt = out["multitenant"]
    for key in ("docs_converged_per_s", "p99_per_doc_ms",
                "dispatches_per_tick", "speedup"):
        assert isinstance(mt.get(key), (int, float)), key
    assert mt["oracle_identical"] is True
    for cname in ("converge.docs_packed", "tenant.submitted",
                  "tenant.docs_converged", "tenant.shed",
                  "tenant.shed_bytes"):
        assert report["counters"].get(cname, 0) > 0, cname
    assert "tenant.pending_bytes" in report["gauges"]
    assert "tenant.dispatch_docs" in report["gauges"]

    # the round-15 delta-tick registry: the smoke runs a tiny
    # steady-state leg (small deltas on resident docs + a rolling
    # eviction flood) digest-identical to the full-replay oracle,
    # and the delta-route / resident-ledger / digest-skip evidence
    # the steady regression gates read must be live
    assert out.get("mt_incremental_registry_ok") is True
    mts = out["multitenant"]["steady"]
    for key in ("docs_per_s", "speedup", "delta_docs_per_tick"):
        assert isinstance(mts.get(key), (int, float)), key
    assert mts["oracle_identical"] is True
    for cname in ("tenant.delta_docs", "tenant.delta_rows",
                  "tenant.promotions", "tenant.resident_evictions",
                  "sentinel.doc_digest_skips"):
        assert report["counters"].get(cname, 0) > 0, cname
    assert "tenant.resident_bytes" in report["gauges"]
    assert "tenant.resident_docs" in report["gauges"]

    # the round-20 pooled-resident registry: the smoke runs a tiny
    # all-warm device-forced leg — every doc's device round batches
    # into ONE pooled dispatch per tick (the gated dispatch floor),
    # byte-identical to the unpooled route, with the tenant.pool_*
    # evidence live. The gated keys ride the ARTIFACT (the stdout
    # line's 1500-byte budget drops them, like phases_numpy_s)
    assert out.get("mt_pooled_registry_ok") is True
    fsteady = full["multitenant"]["steady"]
    for key in ("device_dispatches_per_tick", "pool_peak_bytes"):
        assert isinstance(fsteady.get(key), (int, float)), key
    assert fsteady["device_dispatches_per_tick"] <= 2
    assert report["counters"].get("tenant.pool_dispatches", 0) > 0
    assert "tenant.pool_bytes" in report["gauges"]
    assert "tenant.pool_docs" in report["gauges"]

    # the round-21 snapshot registry: the smoke runs a tiny coldstart
    # leg (snapshot join digest-identical to WAL replay, corruption
    # falls back counted, server checkpoint/restore round-trips) with
    # the snap.* write/load/fallback evidence live
    assert out.get("snap_registry_ok") is True
    for cname in ("snap.writes", "snap.loads", "snap.bytes",
                  "tenant.checkpoint_docs"):
        assert report["counters"].get(cname, 0) > 0, cname
    assert any(k.startswith("snap.fallbacks{")
               for k in report["counters"]), "snap.fallbacks missing"
    assert "snap.write_ms" in report["gauges"]
    assert "snap.load_ms" in report["gauges"]

    # the round-18 observability-v2 registries: the SLO ledger lit
    # breaches/burn-rate/route-mix (the chaos flood leg runs with
    # slo_ms=0 and shed==breach is asserted inside the leg), the
    # tick timeline recorded the multitenant ticks with live
    # overlap/stall gauges and a schema-valid Perfetto export, and
    # the disabled-tracer span cost stays pinned (obs is free when
    # off — the measured bound is generous for CI boxes)
    assert out.get("slo_registry_ok") is True
    assert out.get("timeline_registry_ok") is True
    assert report["counters"].get("slo.breaches", 0) > 0
    assert "slo.burn_rate" in report["gauges"]
    assert any(k.startswith("slo.route_shed{")
               for k in report["counters"]), "route mix missing"
    for sname in ("slo.ingest_to_converged", "slo.ingest_to_served"):
        span = report["spans"].get(sname)
        assert span is not None and span["count"] > 0, sname
    assert report["counters"].get("timeline.ticks", 0) > 0
    assert "timeline.overlap_efficiency" in report["gauges"]
    assert "timeline.stall_ms" in report["gauges"]
    assert isinstance(out.get("obs_disabled_span_ns"), (int, float))
    assert out["obs_disabled_span_ns"] < 5000
    assert out["multitenant"]["steady"]["slo_ms"] > 0

    # the round-19 distributed-tracing registries: the traced
    # loopback swarm lit the per-route hop-lag histograms, the
    # birth-to-visibility span, the context byte/overhead accounting
    # (with a hostile context counted, not fatal), and the
    # self-scrape collector leg federated this process with full
    # path reconstruction
    assert out.get("propagation_registry_ok") is True
    assert out.get("collector_registry_ok") is True
    for cname in ("propagation.contexts_sent",
                  "propagation.contexts_received",
                  "propagation.context_bytes",
                  "propagation.traced_update_bytes",
                  "propagation.malformed_contexts",
                  "collector.scrapes"):
        assert report["counters"].get(cname, 0) > 0, cname
    assert "propagation.wire_overhead_ratio" in report["gauges"]
    assert report["gauges"].get("collector.procs") == 1
    assert report["gauges"].get("collector.pair_rate") == 1.0
    for sname in ('replica.hop_lag{route="direct"}',
                  'replica.hop_lag{route="sync_answer"}',
                  'replica.hop_lag{route="anti_entropy"}',
                  "replica.birth_to_visibility"):
        span = report["spans"].get(sname)
        assert span is not None and span["count"] > 0, sname

    # the round-22 control-plane registry: the smoke drives a
    # deterministic synthetic squeeze/skip/restore schedule through
    # a Controller (ledger bounded with drop accounting, replay
    # byte-identical) plus a tiny cadence-checkpoint server leg, so
    # every control.* counter/gauge the regression gates read is
    # live, and the decision ledger artifact uploads from CI
    assert out.get("control_registry_ok") is True
    for cname in ("control.decisions", "control.cooldown_skips",
                  "control.ledger_dropped", "snap.cadence_writes"):
        assert report["counters"].get(cname, 0) > 0, cname
    for cname in ('control.decisions{rule="budget_squeeze"}',
                  'control.decisions{rule="budget_restore"}'):
        assert report["counters"].get(cname, 0) > 0, cname
    assert any(k.startswith("control.setpoint{knob=")
               for k in report["gauges"]), "setpoint gauges missing"

    # the guard-layer registry (README "Overload & failure policy"):
    # (kernel_ablation_leg is pinned in-process below — the smoke
    # subprocess stays on its <30s budget)
    # each degradation ladder fired once in the smoke and its
    # counters are live, so the robustness regression gate
    # (tools/metrics_diff.py GUARD_PREFIXES) always has data to read
    assert out.get("guard_registry_ok") is True
    for cname in ("guard.inbox_shed", "guard.inbox_shed_bytes",
                  "engine.pending_evictions", "persist.retries",
                  "persist.degraded_writes", "persist.recovered_updates",
                  "device.retries", "device.fallback"):
        assert report["counters"].get(cname, 0) > 0, cname
    # degraded flipped on AND recovered during the leg
    assert report["gauges"].get("persist.degraded") == 0


def test_kernel_ablation_leg_shape():
    """The round-12 per-primitive ablation rig (bench.kernel_ablation_
    leg) must keep producing the gated keys — sort_ms / map_winners_ms
    / rank_ms with both paths, and the sort_map_speedup acceptance
    number — on a tiny trace, so the evidence pipeline can't rot
    between full bench runs."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import bench
    from jax import enable_x64

    blobs = bench.build_trace(30, 20)
    dec = bench.decode_stage(blobs)
    cols, _ = bench.column_stage(dec)

    def b2b(fn, reps=2, outer=1):
        import jax
        import time

        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e3

    with enable_x64(True):
        out = bench.kernel_ablation_leg(cols, b2b, 0.0)
    for prim in ("sort_ms", "map_winners_ms", "rank_ms"):
        assert set(out[prim]) == {"jnp", "pallas"}, prim
        assert out[prim]["jnp"] > 0 and out[prim]["pallas"] > 0
    assert out["sort_map_speedup"] > 0
    assert out["shape"] == int(np.count_nonzero(cols["valid"]))
    assert out["mode"] in ("pallas", "interpret", "jnp")
