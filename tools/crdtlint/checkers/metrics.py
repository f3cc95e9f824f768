"""CL201/CL202/CL203: observability registry conformance (round 8).

The README "Observability" tables and ``tests/test_registry.py``
pin the span/counter/gauge and flight-recorder event registries as
stable contracts. This checker diffs those registries against what
the package actually emits, **both ways**:

- **CL201 — unregistered name.** A string literal passed to
  ``span()`` / ``count()`` / ``gauge()`` / ``observe()`` / recorder
  ``record()`` that no registry documents: new instrumentation must
  land with its registry row (or be baselined while the docs PR is in
  flight).
- **CL202 — dead registry entry.** A documented name nothing emits:
  the docs promise a metric that rotted out of the code.
- **CL203 — non-literal metric name.** A computed first argument
  outside the allowlist. Computed names silently bypass CL201/CL202
  (and make grep-ability lies), so they are opt-in per seam.

Names dotless at the top level (the hot-path spans ``decode``,
``pack`` …) are matched against the HOT_PATH_SPANS pin. Label suffixes
(``name{k="v"}``) are stripped on both sides. Tracer/recorder
infrastructure modules are excluded from the usage scan — they pass
names through generically.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set, Tuple

from tools.crdtlint.astutil import enclosing_function_map, str_const
from tools.crdtlint.core import Checker, Finding, LintContext, Module
from tools.crdtlint.registry import Registry, load_registry

_EMIT_METHODS = ("span", "count", "gauge", "observe")
_INFRA_SUFFIXES = (
    "obs/tracer.py", "obs/recorder.py", "obs/export.py",
    "utils/trace.py", "obs/profiling.py",
)
# (path suffix, enclosing function) pairs allowed to emit COMPUTED
# metric names: seams that take the name as an explicit parameter so
# call sites stay greppable
COMPUTED_ALLOWLIST = (
    ("guard/faults.py", "retry_with_backoff"),
    ("ops/device.py", "xfer_put"),
    ("ops/device.py", "xfer_fetch"),
    # round 22: Controller.observe(sensors) is the control plane's
    # rule-engine consult (one sensor dict per tick), not a metric
    # emission — the controller's own tracer calls stay literal and
    # registry-checked
    ("models/multidoc.py", "_run_control"),
    ("obs/control.py", "replay"),
)


class MetricsRegistryChecker(Checker):
    name = "metrics-registry"
    codes = {
        "CL201": "metric/event name emitted but absent from the "
                 "documented registry (README / test_registry)",
        "CL202": "registry documents a name nothing emits",
        "CL203": "computed (non-literal) metric name outside the "
                 "allowlist",
    }
    explain = {
        "CL201": (
            "A metric emitted but missing from the README registry "
            "tables is invisible to reviewers and unpinned by "
            "tests/test_registry.py — exactly the round-8 drift "
            "crdtlint was built to stop.\n"
            "Fix: add the name to the README Observability/Failure "
            "tables (backticked), or rename to an existing "
            "documented name."
        ),
        "CL202": (
            "A documented name nothing emits is a dead registry "
            "entry: dashboards chart nothing and reviewers trust a "
            "fiction.\n"
            "Fix: delete the registry row, or wire the emission it "
            "promised."
        ),
        "CL203": (
            "A computed metric name defeats both registry "
            "directions — the checker cannot see what will be "
            "emitted.\n"
            "Fix: declare the closed name set at the call site with "
            "`# crdtlint: emits=a.b,a.c` (each declared name stays "
            "registry-checked), or switch to a literal name with a "
            "label dict."
        ),
    }

    def prepare(self, ctx: LintContext) -> None:
        reg = ctx.shared.get("metric_registry")
        if reg is None:
            reg = load_registry(
                ctx.config.readme_path, ctx.config.registry_test_path
            )
            ctx.shared["metric_registry"] = reg
        # name -> first (path, line) that emits it
        ctx.shared["emitted_metrics"] = {}

    def check_module(self, mod: Module,
                     ctx: LintContext) -> Iterable[Finding]:
        if any(mod.path.endswith(s) for s in _INFRA_SUFFIXES):
            return ()
        reg: Registry = ctx.shared["metric_registry"]
        emitted: Dict[str, Tuple[str, int]] = ctx.shared["emitted_metrics"]
        findings: List[Finding] = []

        # enclosing-function map (innermost) for the computed-name
        # allowlist and CL203 symbols
        func_of = enclosing_function_map(mod.tree)

        def check_registered(name: str, lineno: int, what: str):
            emitted.setdefault(name, (mod.path, lineno))
            if name not in reg.all_names:
                findings.append(Finding(
                    mod.path, lineno, "CL201",
                    f"`{name}` ({what}) is not in the documented "
                    f"registry — add it to the README registry "
                    f"table (round-8 contract)",
                    symbol=name,
                ))

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            # metric-name kwargs on pass-through seams (the
            # `retry_with_backoff(..., counter="persist.retries")`
            # pattern): the literal at the CALL site is the emission
            for k in node.keywords:
                if k.arg in ("counter", "metric"):
                    klit = str_const(k.value)
                    if klit:
                        check_registered(klit, node.lineno, "counter")
            # require a receiver (`tracer.count`, `get_tracer().count`,
            # `rec.record`): bare `count()` calls are unrelated
            if not isinstance(node.func, ast.Attribute):
                continue
            meth = node.func.attr
            is_record = meth == "record"
            if meth not in _EMIT_METHODS and not is_record:
                continue
            if not node.args:
                continue
            lit = str_const(node.args[0])
            if lit is None:
                declared = mod.emits_near(node.lineno)
                fn = func_of.get(id(node), "<module>")
                if declared:
                    # the site declares its closed name set; each
                    # declared name is still registry-checked
                    for name in sorted(declared):
                        check_registered(
                            name, node.lineno,
                            "event" if is_record else meth,
                        )
                    continue
                if any(
                    mod.path.endswith(p) and fn == f
                    for p, f in COMPUTED_ALLOWLIST
                ):
                    continue
                findings.append(Finding(
                    mod.path, node.lineno, "CL203",
                    f"computed metric name passed to `{meth}()` — "
                    f"registry conformance can't see it; use a "
                    f"string literal, or declare the closed name "
                    f"set with `# crdtlint: emits=a.b,c.d`",
                    symbol=f"{fn}:{meth}",
                ))
                continue
            check_registered(
                lit, node.lineno, "event" if is_record else meth
            )
        return findings

    def finalize(self, ctx: LintContext) -> Iterable[Finding]:
        reg: Registry = ctx.shared["metric_registry"]
        emitted: Dict[str, Tuple[str, int]] = ctx.shared["emitted_metrics"]
        if not emitted:
            return ()  # synthetic runs with no instrumented modules
        findings: List[Finding] = []
        emitted_names: Set[str] = set(emitted)
        for name in sorted(reg.all_names - emitted_names):
            src_path, src_line = reg.sources.get(name, ("<registry>", 1))
            findings.append(Finding(
                _relish(src_path, ctx), src_line, "CL202",
                f"registry documents `{name}` but nothing in the "
                f"scanned tree emits it — dead entry or renamed "
                f"metric",
                symbol=name,
            ))
        return findings


def _relish(path: str, ctx: LintContext) -> str:
    """Registry source paths are absolute; findings use repo-relative
    posix paths like every other checker."""
    import os

    root = ctx.config.repo_root
    try:
        return os.path.relpath(path, root).replace(os.sep, "/")
    except ValueError:
        return path.replace(os.sep, "/")
