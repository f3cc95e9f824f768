"""Observability subsystem: tracer, flight recorder, sentinel, export,
and the round-18 serving surfaces (SLO ledger, tick timeline, HTTP
scrape endpoint).

The framework's evidence layer (ROADMAP north star: converging 1k
replicas x 100k ops needs to be *seen*, not just claimed):

- :mod:`crdt_tpu.obs.tracer` — thread-safe phase tracer with
  log-bucketed latency histograms (p50/p90/p99/max per span), labeled
  counters and gauges. One process-global instance, disabled by
  default; every hot-path hook is a single attribute check when off.
- :mod:`crdt_tpu.obs.recorder` — bounded ring buffer of structured
  sync events (monotonic ts, kind, replica/topic, update digest, byte
  size), dumpable as JSONL on demand or automatically on divergence.
- :mod:`crdt_tpu.obs.sentinel` — the divergence sentinel: periodic
  snapshot-hash beacons riding the anti-entropy cadence turn silent
  divergence (equal state vectors, unequal state) into an observable
  event carrying a flight-recorder dump.
- :mod:`crdt_tpu.obs.export` — Prometheus text-format exposition and
  the JSON snapshot (the same schema as ``Tracer.report()``), with
  deterministic disambiguation of sanitization collisions.
- :mod:`crdt_tpu.obs.slo` — per-tenant SLO accounting for the
  serving path: ingest-to-converged / ingest-to-served latency
  histograms, breach counters against a configurable objective
  (``CRDT_TPU_SLO_MS``), burn-rate gauges, route-mix counters.
- :mod:`crdt_tpu.obs.timeline` — the tick-timeline profiler: a
  bounded ring of per-tick phase records with dispatch in-flight
  windows, per-tick ``overlap_efficiency`` / ``stall_ms``, exported
  as Chrome/Perfetto trace-event JSON.
- :mod:`crdt_tpu.obs.http` — stdlib-only scrape endpoint
  (``/metrics`` / ``/snapshot`` / ``/events`` / ``/timeline``; with
  a collector attached, ``/fleet`` / ``/fleet/timeline``).
- :mod:`crdt_tpu.obs.propagation` — round 19: the wire trace
  context (origin tid + bounded route-tagged path records) carried
  on update/sync-answer/AE frames, per-hop lag attribution
  (``replica.hop_lag{route=}``, ``replica.birth_to_visibility``),
  and the tid-pairing/diverge analysis core shared by ``obsq`` and
  the collector.
- :mod:`crdt_tpu.obs.collector` — round 19: the live fleet
  collector federating N processes' scrape endpoints (proc-labeled
  registries, live cross-process path reconstruction + divergence
  correlation, merged Perfetto timelines).
- :mod:`crdt_tpu.obs.control` — round 22: the SLO-driven control
  plane — a deterministic tick-synchronous rule engine over the
  sensors above (burn rates, queue/pool pressure) actuating the
  serving knobs (tenant budget squeeze/restore with hysteresis,
  LRU protection, dispatch pacing, checkpoint cadence), every
  decision in a bounded auditable ledger served at ``/control``.
- :mod:`crdt_tpu.obs.profiling` — ``jax_profile`` (device trace
  capture that cannot leak a running profiler) and the cached
  ``TraceAnnotation`` resolver every enabled tracer span opens
  (``crdt.<span>``), plus ``device_annotation`` for code with no span.

See README "Observability" / "Observability v2" for the
metric/span/event name registry; ``tools/obsq.py`` is the offline
query CLI over flight-recorder dumps.
"""

from crdt_tpu.obs.collector import FleetCollector, merge_perfetto
from crdt_tpu.obs.control import Actuation, ControlLedger, Controller
from crdt_tpu.obs.export import snapshot_json, to_prometheus
from crdt_tpu.obs.http import ObsHTTPServer
from crdt_tpu.obs.profiling import device_annotation, jax_profile
from crdt_tpu.obs.propagation import (
    PropagationLedger,
    TraceContext,
    decode_context,
    encode_context,
    get_propagation,
    set_propagation,
)
from crdt_tpu.obs.recorder import (
    FlightRecorder,
    get_recorder,
    set_recorder,
)
from crdt_tpu.obs.sentinel import (
    DivergenceSentinel,
    MultiDocSentinel,
    delete_set_digest,
    state_digest,
)
from crdt_tpu.obs.slo import SLOLedger
from crdt_tpu.obs.timeline import TickTimeline, get_timeline, set_timeline
from crdt_tpu.obs.tracer import Histogram, Tracer, get_tracer, set_tracer

__all__ = [
    "Actuation",
    "ControlLedger",
    "Controller",
    "DivergenceSentinel",
    "MultiDocSentinel",
    "FleetCollector",
    "FlightRecorder",
    "Histogram",
    "ObsHTTPServer",
    "PropagationLedger",
    "SLOLedger",
    "TickTimeline",
    "TraceContext",
    "Tracer",
    "decode_context",
    "delete_set_digest",
    "device_annotation",
    "encode_context",
    "get_propagation",
    "get_recorder",
    "get_timeline",
    "get_tracer",
    "jax_profile",
    "merge_perfetto",
    "set_propagation",
    "set_recorder",
    "set_timeline",
    "set_tracer",
    "snapshot_json",
    "state_digest",
    "to_prometheus",
]
