"""Thread-safe phase tracer with log-bucketed latency histograms.

Replaces the aggregating count/total/max tracer that lived in
``crdt_tpu/utils/trace.py`` (which documented itself as single-thread
only while ``models/streaming.py`` decodes on a thread pool — a latent
race on every shared-dict update). This one takes a lock around every
mutation; the off-path cost when disabled stays a single attribute
check (``span`` returns one shared no-op context manager, ``count`` /
``gauge`` / ``observe`` return before touching any state).

Spans aggregate count / total / max / min AND a base-2 log-bucketed
histogram (1 microsecond floor), so ``report()`` carries tail
latencies (p50/p90/p99) per phase, not just means — the difference
between "converge averaged 12 ms" and "one dispatch in a hundred
stalls 400 ms".

``span`` is the program's one span primitive. An enabled span also
opens ``jax.profiler.TraceAnnotation("crdt.<name>")`` (resolved once
by :func:`crdt_tpu.obs.profiling.annotation_cls`), so a profiler
capture shows the phase on its host plane, on the device ops' clock;
and it keeps a per-thread stack of open spans, so ``report()`` gives
each span's ``self_s``: its total less the time its child spans on
the same thread cover.

The public surface is a strict superset of the old tracer:
``get_tracer() / set_tracer / span / count / gauge /
counters(prefix) / report / to_json / reset`` all behave identically
(``report()`` keeps ``count/total_s/mean_s/max_s`` per span and adds
``min_s/p50_s/p90_s/p99_s/buckets/self_s``). New: ``observe(name, seconds)``
records a duration measured elsewhere (e.g. propagation lag stamped
by a trace id) into the same histogram machinery, and ``count`` /
``gauge`` accept a ``labels`` dict rendered Prometheus-style into the
metric key (``name{k="v"}``). See README "Observability"; subclassers
of the old Tracer: see MIGRATING.
"""

from __future__ import annotations

import json
import threading
import time
from bisect import bisect_left
from contextlib import nullcontext
from typing import Any, Dict, Optional

from crdt_tpu.obs import profiling as _profiling

# base-2 bucket upper edges, 1us floor: bucket k holds durations in
# (edge[k-1], edge[k]] — an observation exactly AT an edge lands in
# that edge's bucket (bisect_left semantics, pinned by test_obs).
# 40 edges reach ~5.5e5 s; anything beyond lands in the +Inf bucket.
N_BUCKETS = 40
BUCKET_EDGES_S = tuple(1e-6 * (1 << k) for k in range(N_BUCKETS))
_OVERFLOW = N_BUCKETS  # index of the +Inf bucket


def bucket_index(seconds: float) -> int:
    """Histogram bucket for a duration (upper-edge inclusive)."""
    if seconds <= BUCKET_EDGES_S[0]:
        return 0
    return bisect_left(BUCKET_EDGES_S, seconds)


class Histogram:
    """Log2-bucketed duration aggregate (the span accumulator).

    Public since round 18: the SLO ledger (:mod:`crdt_tpu.obs.slo`)
    keeps per-tenant latency histograms on exactly these edges, so a
    scrape and an SLO report bucket identically."""

    __slots__ = ("count", "total_s", "max_s", "min_s", "buckets")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self.min_s = float("inf")
        self.buckets: Dict[int, int] = {}

    def add(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        if dt > self.max_s:
            self.max_s = dt
        if dt < self.min_s:
            self.min_s = dt
        b = bucket_index(dt)
        self.buckets[b] = self.buckets.get(b, 0) + 1

    def summary(self) -> Dict[str, Any]:
        """The per-span report dict (shared by ``Tracer.report()`` and
        the SLO ledger's per-tenant summaries)."""
        return {
            "count": self.count,
            "total_s": self.total_s,
            "mean_s": self.total_s / self.count if self.count else 0.0,
            "max_s": self.max_s,
            "min_s": self.min_s if self.count else 0.0,
            "p50_s": self.quantile(0.50),
            "p90_s": self.quantile(0.90),
            "p99_s": self.quantile(0.99),
            "buckets": {
                (
                    f"{BUCKET_EDGES_S[b]:.9g}"
                    if b < _OVERFLOW else "+Inf"
                ): n
                for b, n in sorted(self.buckets.items())
            },
        }

    def quantile(self, q: float) -> float:
        """Bucket-resolution quantile: the upper edge of the bucket
        holding the q-rank observation, clamped to the observed max
        (so p99 never reports above the true maximum). Edge
        semantics (pinned in test_obs): an empty histogram answers
        0.0 for every q; ``q=0`` is the rank-1 (minimum-bucket)
        estimate; ``q>=1`` is the observed max; a single observation
        answers that observation at every q."""
        if not self.count:
            return 0.0
        rank = max(1, min(self.count, int(q * self.count + 0.5)))
        cum = 0
        for b in sorted(self.buckets):
            cum += self.buckets[b]
            if cum >= rank:
                edge = (
                    BUCKET_EDGES_S[b] if b < _OVERFLOW else self.max_s
                )
                return min(edge, self.max_s)
        return self.max_s


# legacy alias: subclassers of the round-8 tracer reached the span
# accumulator under this name (MIGRATING "Tracer subclassers")
_Span = Histogram


# shared no-op context manager: the disabled-tracer span (stdlib
# nullcontext is reusable and reentrant)
_NULL_SPAN = nullcontext()


class _LiveSpan:
    """One enabled span: timed on ``perf_counter`` (``t0``/``t1``),
    bracketed by the profiler annotation, pushed on its thread's
    stack so that its parent's self time leaves it out."""

    __slots__ = ("_tracer", "_name", "_ann", "_stack", "_child", "t0",
                 "t1")

    def __init__(self, tracer: "Tracer", name: str):
        self._tracer = tracer
        self._name = name
        self._child = 0.0
        self._ann = None

    def __enter__(self):
        self._stack = self._tracer._stack()
        self._stack.append(self)
        cls = _profiling._annotation_cls
        if cls is None:
            cls = _profiling.annotation_cls()
        # the annotation is built only while a capture records: with
        # no profiler session running it would record nothing
        if cls and cls.is_enabled():
            self._ann = cls("crdt." + self._name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return None

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        dt = self.t1 - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = self._stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # closed out of order
            stack.remove(self)
        if stack:
            stack[-1]._child += dt
        self._tracer._record(self._name, dt, dt - self._child)
        return False


def _esc_label(value: Any) -> str:
    """Prometheus exposition label-value escaping (backslash, quote,
    newline). Label values are caller-controlled since round 18 (doc
    ids become ``tenant=`` labels) — an unescaped ``"`` or newline
    would corrupt the whole /metrics scrape, and a newline could
    inject arbitrary exposition lines."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _labeled(name: str, labels: Optional[Dict[str, Any]]) -> str:
    if not labels:
        return name
    inner = ",".join(
        f'{k}="{_esc_label(labels[k])}"' for k in sorted(labels)
    )
    return f"{name}{{{inner}}}"


class Tracer:
    """Aggregating phase timer + counters + gauges. Thread-safe: all
    mutations take one lock (sub-microsecond uncontended; the timed
    region of a span is measured OUTSIDE the lock)."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._spans: Dict[str, _Span] = {}
        self._self_s: Dict[str, float] = {}
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}
        self._local = threading.local()  # .stack: open spans

    # -- phases ----------------------------------------------------------
    def span(self, name: str):
        if not self.enabled:
            return _NULL_SPAN
        return _LiveSpan(self, name)

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def observe(self, name: str, seconds: float) -> None:
        """Record an externally measured duration into ``name``'s
        histogram (same aggregate a ``span`` produces; it has no
        children, so all of it is self time)."""
        if not self.enabled:
            return
        self._record(name, seconds, seconds)

    def _record(self, name: str, seconds: float, self_s: float) -> None:
        with self._lock:
            s = self._spans.get(name)
            if s is None:
                s = self._spans[name] = Histogram()
            s.add(seconds)
            self._self_s[name] = self._self_s.get(name, 0.0) + self_s

    def quantile(self, name: str, q: float) -> float:
        """Bucket-resolution quantile of one span's histogram (0.0
        for a span never observed — the always-on serving path must
        be able to probe a quantile without try/except)."""
        with self._lock:
            s = self._spans.get(name)
            return s.quantile(q) if s is not None else 0.0

    # -- counters / gauges ----------------------------------------------
    def count(self, name: str, n: int = 1,
              labels: Optional[Dict[str, Any]] = None) -> None:
        if not self.enabled:
            return
        key = _labeled(name, labels)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def gauge(self, name: str, value: float,
              labels: Optional[Dict[str, Any]] = None) -> None:
        if not self.enabled:
            return
        key = _labeled(name, labels)
        with self._lock:
            self._gauges[key] = value

    def counters(self, prefix: str = "") -> Dict[str, int]:
        """Counter snapshot, optionally filtered by name prefix —
        e.g. ``counters("router.relay")`` for the relay path or
        ``counters("replica.probe")`` for the retry schedule (the
        partition-tolerance counters: ``router.dial_retries``,
        ``router.predict_probes``, ``router.relay_*``,
        ``replica.probe_retries``, ``replica.anti_entropy_rounds`` —
        a stable contract, see README "Observability")."""
        with self._lock:
            return {
                k: v for k, v in sorted(self._counters.items())
                if k.startswith(prefix)
            }

    # -- reporting -------------------------------------------------------
    def report(self) -> Dict[str, Any]:
        """One plain JSON-ready dict — the shared schema the JSON
        snapshot and the Prometheus exposition both read."""
        with self._lock:
            spans = {
                k: dict(s.summary(), self_s=self._self_s.get(k, 0.0))
                for k, s in sorted(self._spans.items())
            }
            return {
                "spans": spans,
                "counters": dict(sorted(self._counters.items())),
                "gauges": dict(sorted(self._gauges.items())),
            }

    def to_json(self) -> str:
        return json.dumps(self.report())

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._self_s.clear()
            self._counters.clear()
            self._gauges.clear()


_tracer = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _tracer


def set_tracer(tracer: Tracer) -> Tracer:
    global _tracer
    _tracer = tracer
    return tracer
