#!/usr/bin/env python
"""Chip smoke: the document server and the fan-in replay on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # four chips: the sharded route and
                                     # ReplicaFleet, nothing else

One chip runs two phases through the entry points users call:

- **server**: ``MultiDocServer`` takes the multitenant shape of
  ``workloads.build_doc_trace`` (1000 docs x 64 ops + 4 docs x 4096
  ops, v1 wire blobs), ticks until clean, then serves steady-state
  rounds big enough for the device route.
- **fan-in**: BASELINE config #5 (``build_trace(1000, 100)``, 100k ops)
  through ``replay_trace``, then the 16x union (1.6M ops) through
  ``stream_replay``.

Four chips run the 1.6M-op union on the sharded route against the same
union on one device, and one ``ReplicaFleet.step`` on a 4-device mesh
against the same step on one device.

Every document is checked against the scalar engine
(``crdt_tpu/core/engine.py``), never against the system itself. The
script fails (non-zero exit, no result line) when JAX finds no TPU,
when the repo is not beside it, when any check fails, and when the
device guard (``device.fallback`` / ``device.dispatch_errors``) or a
Pallas kernel in interpret mode had to stand in for the chip. The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(*a) -> None:
    print(*a, flush=True)


class CompileClock:
    """Seconds of XLA backend compilation, as ``jax.monitoring``
    reports them (persistent-cache hits compile nothing)."""

    def __init__(self):
        import jax.monitoring

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.total += duration


def oracle(blobs):
    """The scalar engine fed one update at a time (``Y.applyUpdate``).
    Delivery order is free for a CRDT; newest writer first keeps each
    YATA scan short on these traces, where the batch order (client
    ascending) makes the scan quadratic (hours at 1.6M ops)."""
    from crdt_tpu.codec import v1
    from crdt_tpu.core.engine import Engine

    eng = Engine(0)
    for b in reversed(blobs):
        v1.apply_update(eng, b)
    return eng


def snapshot_sv(snapshot: bytes):
    from crdt_tpu.core.ids import StateVector
    from crdt_tpu.models import replay as rp

    dec = rp.decode([snapshot])
    sv = StateVector()
    for c, k in zip(dec["client"].tolist(), dec["clock"].tolist()):
        sv.observe(c, k)
    return sv


def check_replay(name: str, res, blobs) -> None:
    """Cache, digest and snapshot state vector equal the oracle's."""
    from crdt_tpu.models.multidoc import cache_digest

    eng = oracle(blobs)
    want = eng.to_json()
    check(res.cache == want, f"{name}: cache differs from the scalar engine")
    got_d, want_d = cache_digest(res.cache), cache_digest(want)
    check(got_d == want_d, f"{name}: digest {got_d} != oracle {want_d}")
    check(snapshot_sv(res.snapshot) == eng.state_vector(),
          f"{name}: snapshot state vector differs from the scalar engine")
    say(f"{name}: {res.n_ops} ops, path={res.path}, digest {got_d} "
        f"== scalar engine")


def phase_server(n_small=1000, ops_small=64, n_big=4, ops_big=4096,
                 rounds=3, round_writers=8, round_ops=2560) -> dict:
    """MultiDocServer on v1 blobs, ticked clean, then steady rounds of
    fresh writers on the big docs (``round_writers * round_ops`` ops
    per doc per round: above the host/device crossover)."""
    import workloads
    from crdt_tpu.models.multidoc import MultiDocServer, cache_digest
    from crdt_tpu.ops import packed

    docs = workloads.build_doc_trace(n_small, ops_small, n_big, ops_big)
    srv = MultiDocServer()
    for d, bs in docs.items():
        srv.submit_many(d, bs)
    ticks = dispatches = 0
    while srv.dirty_docs():
        rep = srv.tick()
        ticks += 1
        dispatches += rep.dispatches
        check(ticks <= 16, "server: dirty docs after 16 ticks")
    say(f"server: {len(docs)} docs clean after {ticks} tick(s), "
        f"{dispatches} converge dispatch(es)")

    big = [d for d in docs if d.startswith("zbig")]
    d0 = packed.device_dispatch_count
    delta_docs = promotions = 0
    for r in range(rounds):
        for j, d in enumerate(big):
            bs = workloads.build_trace(round_writers, round_ops,
                                       seed=100 * r + j,
                                       client_base=1000 * (r + 1))
            docs[d] = docs[d] + bs
            srv.submit_many(d, bs)
        rep = srv.tick()
        check(not srv.dirty_docs(), f"server: round {r} left dirty docs")
        delta_docs += rep.delta_docs
        promotions += rep.promotions
    device_rounds = packed.device_dispatch_count - d0
    say(f"server steady: {rounds} rounds x {len(big)} docs x "
        f"{round_writers * round_ops} ops, promotions={promotions}, "
        f"delta_docs={delta_docs}, device dispatches={device_rounds}")
    check(delta_docs > 0, "server steady: no doc took the delta route")
    check(device_rounds > 0, "server steady: no round reached the device")

    bad = []
    for d, bs in docs.items():
        eng = oracle(bs)
        if (srv.digest(d) != cache_digest(eng.to_json())
                or srv.state_vector(d) != eng.state_vector()):
            bad.append(d)
    check(not bad, f"server: {len(bad)} doc(s) differ from the scalar "
                   f"engine, first {bad[:5]}")
    say(f"server: all {len(docs)} docs' digests and state vectors == "
        f"scalar engine")
    return {"docs": len(docs), "ticks": ticks, "delta_docs": delta_docs,
            "device_dispatches": device_rounds}


def phase_fanin(R=1000, K=100, scale=16) -> dict:
    """BASELINE config #5 through replay_trace, then the ``scale``x
    union through stream_replay."""
    import workloads
    from crdt_tpu.models import replay_trace, stream_replay

    blobs = workloads.build_trace(R, K)
    check_replay("fan-in replay_trace", replay_trace(blobs), blobs)
    blobs_l = workloads.build_trace(R * scale, K, seed=1)
    check_replay("fan-in stream_replay", stream_replay(blobs_l), blobs_l)
    return {"ops": R * K, "union_ops": R * scale * K}


def phase_sharded(R=16000, K=100, chips=4) -> dict:
    """The union on the sharded route over ``chips`` devices, byte-
    identical to the same union converged on one device, and the
    sharded output spread over all of them."""
    import workloads
    from crdt_tpu.models import replay as rp
    from crdt_tpu.ops import shard

    blobs = workloads.build_trace(R, K, seed=1)
    cols, _ = rp.stage(rp.decode(blobs))
    n = len(cols["client"])
    check(shard.active_for(n), f"sharded: route off for {n} rows")
    splan = shard.stage(cols)
    check(splan is not None, "sharded: union not expressible")
    handle = shard.converge_async(splan)
    spread = len(handle[1].sharding.device_set)
    shard.converge_fetch(handle)
    check(spread == chips,
          f"sharded: output on {spread} device(s), want {chips}")
    res_k = rp.replay_trace(blobs)
    prev = os.environ.get(shard.SHARD_ENV)
    os.environ[shard.SHARD_ENV] = "1"
    try:
        res_1 = rp.replay_trace(blobs)
    finally:
        if prev is None:
            os.environ.pop(shard.SHARD_ENV)
        else:
            os.environ[shard.SHARD_ENV] = prev
    check(res_k.cache == res_1.cache and res_k.snapshot == res_1.snapshot,
          f"sharded: {chips}-device union differs from one device")
    check_replay(f"sharded {chips}-device union", res_k, blobs)
    return {"ops": n, "output_devices": spread}


def phase_fleet(chips=4, R=64, N=256) -> dict:
    """One ReplicaFleet.step on a ``chips``-device mesh, identical to
    the same step on one device, its output on every device."""
    import numpy as np

    from crdt_tpu.models import ReplicaFleet
    from crdt_tpu.parallel.gossip import pack_cols, pack_dels

    fleet = ReplicaFleet(R, N, n_devices=chips, num_clients=R + 2)
    cols, dels = fleet.synth(num_maps=4, keys_per_map=256, num_lists=4)
    out = fleet._step(pack_cols(cols), pack_dels(dels))
    spread = len(out.sharding.device_set)
    check(spread == chips,
          f"fleet: output on {spread} device(s), want {chips}")
    got = fleet.step(cols, dels)
    one = ReplicaFleet(R, N, n_devices=1, num_clients=R + 2).step(
        cols, dels)
    for name in got._fields:
        check(np.array_equal(getattr(got, name), getattr(one, name)),
              f"fleet: {name} differs between {chips} devices and one")
    say(f"fleet: {R} replicas x {N} ops on {chips} devices == 1 device")
    return {"replicas": R, "ops": R * N, "output_devices": spread}


def build_native() -> None:
    """Build native/ from its committed sources: the checkout carries
    no build output."""
    subprocess.run(["make", "-s", "-C", os.path.join(ROOT, "native"),
                    "clean", "all"], check=True)
    from crdt_tpu.codec import native

    check(native.available(), "native codec did not load")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "crdt_tpu")):
        print("chip_smoke: no crdt_tpu package beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {platform!r} devices",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    kind = devs[0].device_kind
    say(f"devices: {devs}")
    say(f"device_kind: {kind}, count: {len(devs)}")

    jax.config.update("jax_enable_x64", True)
    from crdt_tpu.obs.tracer import Tracer, set_tracer

    tracer = set_tracer(Tracer(enabled=True))
    clock = CompileClock()
    try:
        build_native()
        say("codec: native (native/ built from source)")
        if args.chips == 1:
            phases = (("server", phase_server), ("fan-in", phase_fanin))
        else:
            phases = (("sharded", lambda: phase_sharded(chips=args.chips)),
                      ("fleet", lambda: phase_fleet(chips=args.chips)))
        for name, fn in phases:
            c0, t0 = clock.total, time.perf_counter()
            fn()
            say(f"phase {name}: {time.perf_counter() - t0:.1f} s wall, "
                f"{clock.total - c0:.1f} s compile")

        from crdt_tpu.models.incremental import IncrementalReplay

        counters = tracer.counters()
        say(f"crossover calibration: {IncrementalReplay.calibration_info()}")
        shown = ("converge.pallas", "device.", "xfer.h2d_bytes",
                 "xfer.d2h_bytes", "shard.dispatches", "tenant.delta",
                 "tenant.promotions", "tenant.pool")
        for k in sorted(counters):
            if k.startswith(shown):
                say(f"counter {k} = {counters[k]}")
        for k in ("device.fallback", "device.dispatch_errors",
                  'converge.pallas{mode="interpret"}'):
            if k not in counters:
                say(f"counter {k} = 0")
        check(counters.get('converge.pallas{mode="interpret"}', 0) == 0,
              "a Pallas kernel ran in interpret mode")
        if args.chips == 1:
            check(counters.get('converge.pallas{mode="pallas"}', 0) >= 1,
                  "no converge ran the compiled Pallas kernels")
        for k in ("device.fallback", "device.dispatch_errors"):
            check(counters.get(k, 0) == 0,
                  f"{k} = {counters.get(k)}: the device guard stood in")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    say(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
