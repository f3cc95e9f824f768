"""Parse the observability registries the repo pins in prose + tests.

Two sources of documented truth:

- ``README.md`` — the "Observability" / "Failure semantics" /
  "Overload & failure policy" registry tables name every public
  metric (span/counter/gauge) and flight-recorder event kind in
  backticks. We extract every backticked token that *looks like* a
  metric (lowercase dotted name whose first segment is a known
  namespace), expanding the ``fault.drop/dup/delay`` slash shorthand
  and stripping ``{label=...}`` suffixes.
- ``tests/test_registry.py`` — ``HOT_PATH_SPANS`` plus every string
  literal there that names a metric (the ``REGISTRY`` table).

The registry conformance checker diffs these against the names the
package actually emits, both ways.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Set

# first dotted segment of every registered metric/event namespace;
# extraction is restricted to these so file paths (`tools/...`),
# module paths (`crdt_tpu.obs`) and API references in the same prose
# never read as registry entries
NAMESPACES = frozenset({
    "xfer", "guard", "persist", "engine", "device", "replica",
    "router", "sentinel", "fleet", "gossip", "update", "sync",
    "probe", "ae", "beacon", "dial", "relay", "envelope", "fault",
    "converge", "shard", "tenant",
    # round 18 (observability v2): the SLO ledger and the
    # tick-timeline profiler
    "slo", "timeline",
    # round 19 (distributed tracing): the wire trace-context /
    # per-hop lag plane and the live fleet collector
    "propagation", "collector",
    # round 21 (crash-proof recovery): the snapshot store's
    # write/load/fallback plane
    "snap",
    # round 22 (control plane): the SLO-driven controller's
    # decision/cooldown/ledger/setpoint registry
    "control",
    # round 24 (fleet serving): the live-migration recovery ladder
    # (the `fleet.*` ownership counters were already listed above)
    "migration",
    # the server tick's phases as tracer spans (tick.route, tick.slo, ...)
    "tick",
    # the process's allocator policy (heap.policy{state=...})
    "heap",
    # the cache assembly's row paths (materialize.rows{path=...})
    "materialize",
})

# backticked dotted names that share a namespace but are NOT metrics
# or event kinds (attribute paths, artifact keys, config knobs)
NON_METRICS = frozenset({
    "replica.sentinel.events",   # Replica attribute, not a counter
    "router.stats",              # router's tracer-free stats dict
    "fleet.leases",               # snapshot-store blob key (round 24)
    "shard.mat",                  # xfer_put call-site labels, not
    "shard.wire",                 # tracer names (they surface only as
    "shard.out",                  # {path=...} label values on the
    "shard.sv",                   # xfer byte counters)
    "timeline.to_perfetto",       # API reference in the round-19
    #                               tracing section, not a metric
})

# span names without a dot, pinned only by HOT_PATH_SPANS
_TOKEN_RE = re.compile(
    r"^[a-z][a-z0-9_]*(?:\.[a-z0-9_/]+)+(?:\{[^}]*\})?$"
)
_BACKTICK_RE = re.compile(r"`([^`\n]+)`")


@dataclass
class Registry:
    """Documented names. ``sources`` maps name -> (path, line) of its
    registry mention, so dead-entry findings point at the prose."""

    metrics: Set[str] = field(default_factory=set)
    events: Set[str] = field(default_factory=set)
    sources: Dict[str, tuple] = field(default_factory=dict)

    @property
    def all_names(self) -> Set[str]:
        return self.metrics | self.events

    def add(self, name: str, kind: str, path: str, line: int) -> None:
        (self.metrics if kind == "metric" else self.events).add(name)
        self.sources.setdefault(name, (path, line))


# event-kind namespaces: first segments that name flight-recorder
# event kinds rather than tracer metrics (``fault.drop`` vs the
# ``fault.disk`` recorder kind share one; the conformance diff treats
# metrics+events as one documented pool, so the split is cosmetic)
_EVENT_FIRST = frozenset({
    "update", "sync", "probe", "ae", "beacon", "dial", "relay",
    "envelope",
})


def _norm(token: str) -> str:
    return re.sub(r"\{[^}]*\}$", "", token.strip())


# dotless flight-recorder event kinds: backticked single words are
# far too common in prose to extract generically, so the known ones
# are named here explicitly
DOTLESS_EVENTS = frozenset({"divergence"})


def parse_readme(path: str, reg: Registry) -> None:
    with open(path, encoding="utf-8") as f:
        text = f.read()
    for lineno, line in enumerate(text.splitlines(), start=1):
        for raw in _BACKTICK_RE.findall(line):
            tok = _norm(raw)
            if tok in DOTLESS_EVENTS:
                reg.add(tok, "event", path, lineno)
                continue
            if not _TOKEN_RE.match(tok):
                continue
            first = tok.split(".", 1)[0]
            if first not in NAMESPACES:
                continue
            # expand  fault.drop/dup/delay/corrupt/partition/fork
            head, _, tail = tok.rpartition(".")
            names = (
                [f"{head}.{p}" for p in tail.split("/")]
                if "/" in tail else [tok]
            )
            for name in names:
                if name in NON_METRICS or "/" in name:
                    continue
                kind = (
                    "event" if first in _EVENT_FIRST else "metric"
                )
                reg.add(name, kind, path, lineno)


def parse_registry_test(path: str, reg: Registry) -> None:
    """Every string literal in the registry test that names a
    registered span/counter (HOT_PATH_SPANS, the REGISTRY table)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            tok = _norm(node.value)
            if ("." in tok
                    and _TOKEN_RE.match(tok)
                    and tok.split(".", 1)[0] in NAMESPACES
                    and tok not in NON_METRICS):
                reg.add(tok, "metric", path, node.lineno)
    # dotless hot-path span names (decode, pack, gather…) come only
    # from the HOT_PATH_SPANS tuple assignment, taken verbatim
    for node in tree.body:
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name)
                        and t.id == "HOT_PATH_SPANS"
                        for t in node.targets)
                and isinstance(node.value, ast.Tuple)):
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(
                    elt.value, str
                ):
                    reg.add(elt.value, "metric", path, elt.lineno)


def load_registry(readme_path: Optional[str],
                  registry_test_path: Optional[str]) -> Registry:
    reg = Registry()
    if readme_path:
        parse_readme(readme_path, reg)
    if registry_test_path:
        parse_registry_test(registry_test_path, reg)
    return reg
