"""crdtlint core: findings, suppressions, baseline, checker runner.

The analyzer is deliberately **stdlib-only** (``ast`` + ``tokenize``-free
line scanning): it must run in any environment that can run the tests,
import nothing from ``crdt_tpu`` (so a broken package still lints), and
finish in well under ten seconds on the whole tree.

Vocabulary:

- A **Finding** is one violation: ``path:line CODE message``. Its
  *fingerprint* — ``path|code|symbol`` — is stable across line moves,
  so baseline entries survive unrelated edits to the same file.
- A **suppression** is an inline ``# crdtlint: disable=CL101`` (or
  ``disable=CL101,CL402`` / ``disable=all``) on the finding's line or
  the line directly above it; ``# crdtlint: disable-file=CODE`` in the
  first ten lines silences a code for the whole file.
- The **baseline** (``tools/crdtlint/baseline.json``) lists known,
  *justified* findings by fingerprint. Baselined findings don't fail
  the run but are counted (``LintResult.total_raw`` — growing the
  baseline is visible). Every entry must carry a non-empty
  ``justification``.

Checkers subclass :class:`Checker` and register in
``tools.crdtlint.checkers.ALL_CHECKERS``; each gets three hooks —
``prepare`` (build cross-module indexes), ``check_module`` (per-file
findings), ``finalize`` (cross-module findings such as dead registry
entries).
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_DISABLE_RE = re.compile(
    r"#\s*crdtlint:\s*disable=([A-Za-z0-9_,\s]+|all)"
)
_DISABLE_FILE_RE = re.compile(
    r"#\s*crdtlint:\s*disable-file=([A-Za-z0-9_,\s]+|all)"
)
# a computed-metric-name call site DECLARES the closed set of names
# it can emit: `# crdtlint: emits=fault.drop,fault.dup`. The declared
# names count as emitted (no false dead-entry) and the declaration
# suppresses the computed-name finding — while still registry-checking
# every declared name.
_EMITS_RE = re.compile(
    r"#\s*crdtlint:\s*emits=([A-Za-z0-9_.,\s]+)"
)


@dataclass(frozen=True)
class Finding:
    path: str       # repo-relative, posix separators
    line: int       # 1-based
    code: str       # e.g. "CL101"
    message: str
    symbol: str = ""  # stable context (function / metric name) for
    #                   the baseline fingerprint

    @property
    def fingerprint(self) -> str:
        return f"{self.path}|{self.code}|{self.symbol}"

    def format(self) -> str:
        return f"{self.path}:{self.line} {self.code} {self.message}"


class Module:
    """One parsed source file: tree + per-line suppression map."""

    def __init__(self, path: str, source: str):
        self.path = path.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree: Optional[ast.Module] = None
        self.parse_error: Optional[str] = None
        try:
            self.tree = ast.parse(source, filename=path)
        except SyntaxError as e:  # surfaced as a finding, not a crash
            self.parse_error = f"syntax error: {e.msg} (line {e.lineno})"
        # line -> set of disabled codes ("all" disables everything)
        self._disabled: Dict[int, set] = {}
        self._file_disabled: set = set()
        self.emits: Dict[int, set] = {}  # line -> declared metric names
        for i, text in enumerate(self.lines, start=1):
            if "crdtlint" not in text:
                continue
            m = _DISABLE_FILE_RE.search(text)
            if m and i <= 10:
                self._file_disabled |= _parse_codes(m.group(1))
            m = _DISABLE_RE.search(text)
            if m:
                self._disabled.setdefault(i, set()).update(
                    _parse_codes(m.group(1))
                )
            m = _EMITS_RE.search(text)
            if m:
                self.emits.setdefault(i, set()).update(
                    _parse_codes(m.group(1))
                )

    def emits_near(self, lineno: int) -> set:
        """Names declared by an `emits=` directive on ``lineno`` or
        the comment line directly above it."""
        out = set(self.emits.get(lineno, ()))
        if _comment_only(self.lines, lineno - 1):
            out |= self.emits.get(lineno - 1, set())
        return out

    def suppressed(self, finding: Finding) -> bool:
        if _hits(self._file_disabled, finding.code):
            return True
        for line in (finding.line, finding.line - 1):
            if _hits(self._disabled.get(line, ()), finding.code):
                # a bare-comment line above applies to the statement
                # below it; a trailing comment applies to its own line
                if line == finding.line or _comment_only(
                    self.lines, line
                ):
                    return True
        return False


def _parse_codes(raw: str) -> set:
    return {c.strip() for c in raw.split(",") if c.strip()}


def _hits(codes, code: str) -> bool:
    return "all" in codes or code in codes


def _comment_only(lines: Sequence[str], lineno: int) -> bool:
    if not (1 <= lineno <= len(lines)):
        return False
    return lines[lineno - 1].lstrip().startswith("#")


class Checker:
    """Base checker. ``codes`` maps code -> one-line invariant."""

    name: str = ""
    codes: Dict[str, str] = {}

    def prepare(self, ctx: "LintContext") -> None:
        pass

    def check_module(self, mod: Module,
                     ctx: "LintContext") -> Iterable[Finding]:
        return ()

    def finalize(self, ctx: "LintContext") -> Iterable[Finding]:
        return ()


@dataclass
class LintConfig:
    """Paths the checkers read. Defaults resolve against the repo
    root (the directory holding ``tools/``); tests override to point
    at synthetic fixtures."""

    repo_root: str = ""
    readme_path: Optional[str] = None     # metric/event registry prose
    registry_test_path: Optional[str] = None  # HOT_PATH_SPANS, REGISTRY
    baseline_path: Optional[str] = None

    def __post_init__(self):
        if not self.repo_root:
            self.repo_root = os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            ))
        if self.readme_path is None:
            p = os.path.join(self.repo_root, "README.md")
            self.readme_path = p if os.path.exists(p) else None
        if self.registry_test_path is None:
            p = os.path.join(self.repo_root, "tests", "test_registry.py")
            self.registry_test_path = p if os.path.exists(p) else None
        if self.baseline_path is None:
            self.baseline_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "baseline.json",
            )


@dataclass
class LintContext:
    config: LintConfig
    modules: List[Module] = field(default_factory=list)
    # shared cross-checker indexes, keyed by checker-chosen names
    shared: Dict[str, object] = field(default_factory=dict)

    def module_by_path(self, suffix: str) -> Optional[Module]:
        for m in self.modules:
            if m.path.endswith(suffix):
                return m
        return None


# ---------------------------------------------------------------------------
# baseline


class BaselineError(ValueError):
    pass


def load_baseline(path: str) -> Dict[str, dict]:
    """fingerprint -> entry. Every entry must carry a non-empty
    ``justification`` — the baseline is a ledger of *intentional*
    exceptions, not a mute button."""
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        data = json.load(f)
    out: Dict[str, dict] = {}
    for entry in data.get("entries", ()):
        fp = entry.get("fingerprint")
        if not fp:
            raise BaselineError(f"baseline entry missing fingerprint: {entry}")
        if not str(entry.get("justification", "")).strip():
            raise BaselineError(
                f"baseline entry for {fp!r} has no justification"
            )
        out[fp] = entry
    return out


def write_baseline(path: str, findings: Sequence[Finding],
                   preserved: Iterable[dict] = ()) -> None:
    """Write a baseline: skeleton entries (justification TODO) for
    ``findings`` merged with ``preserved`` existing entries, whose
    hand-written justifications survive verbatim. A preserved entry
    wins over a skeleton with the same fingerprint — regenerating the
    baseline must never wipe the ledger's reasoning."""
    by_fp: Dict[str, dict] = {}
    for f in findings:
        by_fp[f.fingerprint] = {
            "fingerprint": f.fingerprint,
            "code": f.code,
            "path": f.path,
            "message": f.message,
            "justification": "TODO: justify or fix",
        }
    for entry in preserved:
        fp = entry.get("fingerprint")
        if fp:
            by_fp[fp] = entry
    entries = [by_fp[fp] for fp in sorted(by_fp)]
    with open(path, "w") as fh:
        json.dump({"entries": entries}, fh, indent=2, sort_keys=True)
        fh.write("\n")


# ---------------------------------------------------------------------------
# runner


@dataclass
class LintResult:
    findings: List[Finding]            # unsuppressed: these fail the run
    suppressed: List[Finding]          # inline-disabled
    baselined: List[Finding]
    stale_baseline: List[str]          # fingerprints with no live finding
    # analysis-layer evidence (round 16): the memoized call graph's
    # size stats, so graph growth/decay is visible next to the
    # finding counts
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def total_raw(self) -> int:
        """Every violation the checkers saw, suppressed or not (growing
        the baseline moves it)."""
        return len(self.findings) + len(self.suppressed) + len(self.baselined)

    def open_by_family(self, families=("CL1", "CL2", "CL3", "CL4",
                                       "CL5", "CL6", "CL7", "CL8",
                                       "CL9", "CL10",
                                       "CL11")) -> Dict[str, int]:
        """OPEN finding count per code family (``cl7`` counts every
        CL7xx); the committed tree holds every family at zero
        (tier-1). Codes are
        ``CL`` + 3 digits (families cl1–cl9) or ``CL`` + 4 digits
        (the round-17 cl10/cl11 wire-taint families) — a CL1001 must
        count under ``cl10``, never under the donate family ``cl1``."""
        out = {f.lower(): 0 for f in families}
        for f in self.findings:
            fam = (f.code[:4] if len(f.code) == 6
                   else f.code[:3]).lower()
            if fam in out:
                out[fam] += 1
        return out


def iter_py_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs
                    if d != "__pycache__" and not d.startswith(".")
                )
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        yield os.path.join(root, fn)


def load_modules(paths: Sequence[str],
                 repo_root: str) -> List[Module]:
    mods = []
    for fp in iter_py_files(paths):
        with open(fp, encoding="utf-8") as f:
            source = f.read()
        rel = os.path.relpath(os.path.abspath(fp), repo_root)
        mods.append(Module(rel, source))
    return mods


def run_lint(
    modules: Sequence[Tuple[str, str]] | Sequence[Module],
    *,
    config: Optional[LintConfig] = None,
    checkers: Optional[Sequence[Checker]] = None,
    baseline: Optional[Dict[str, dict]] = None,
    use_baseline: bool = True,
    shared: Optional[Dict[str, object]] = None,
) -> LintResult:
    """Lint in-memory or pre-loaded modules. ``modules`` accepts
    ``(relpath, source)`` pairs (the unit-test surface) or
    :class:`Module` objects (the CLI surface). ``shared`` pre-seeds
    cross-checker state — tests inject a synthetic metric registry as
    ``{"metric_registry": Registry(...)}``."""
    config = config or LintConfig()
    if checkers is None:
        from tools.crdtlint.checkers import ALL_CHECKERS

        checkers = [cls() for cls in ALL_CHECKERS]
    mods = [
        m if isinstance(m, Module) else Module(m[0], m[1])
        for m in modules
    ]
    ctx = LintContext(config=config, modules=mods)
    if shared:
        ctx.shared.update(shared)

    raw: List[Finding] = []
    for m in mods:
        if m.parse_error:
            raw.append(Finding(m.path, 1, "CL000", m.parse_error))
    # per-checker wall time (prepare + check_module + finalize),
    # surfaced by --statistics and asserted against the tier-1 <10 s
    # whole-tree budget: a checker that quietly turns quadratic shows
    # up as a named number, not as a mystery slowdown
    import time as _time

    checker_seconds: Dict[str, float] = {}

    def _timed(ch, fn):
        t0 = _time.perf_counter()
        out = fn()
        checker_seconds[ch.name] = (
            checker_seconds.get(ch.name, 0.0)
            + _time.perf_counter() - t0
        )
        return out

    for ch in checkers:
        _timed(ch, lambda ch=ch: ch.prepare(ctx))
    for ch in checkers:
        for m in mods:
            if m.tree is None:
                continue
            raw.extend(_timed(ch, lambda ch=ch, m=m: list(
                ch.check_module(m, ctx)
            )))
    for ch in checkers:
        raw.extend(_timed(ch, lambda ch=ch: list(ch.finalize(ctx))))

    by_path = {m.path: m for m in mods}
    if baseline is None and use_baseline:
        baseline = load_baseline(config.baseline_path)
    baseline = baseline or {}

    open_f: List[Finding] = []
    suppressed: List[Finding] = []
    baselined: List[Finding] = []
    seen_fps = set()
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.code)):
        mod = by_path.get(f.path)
        if mod is not None and mod.suppressed(f):
            suppressed.append(f)
        elif f.fingerprint in baseline:
            baselined.append(f)
            seen_fps.add(f.fingerprint)
        else:
            open_f.append(f)
    stale = sorted(set(baseline) - seen_fps)
    stats: Dict[str, object] = {
        "checker_seconds": {
            k: round(v, 4) for k, v in checker_seconds.items()
        },
    }
    if "callgraph_stats" in ctx.shared:
        stats["callgraph"] = ctx.shared["callgraph_stats"]
    return LintResult(open_f, suppressed, baselined, stale, stats)
