"""Tier-1 static-analysis gate: crdtlint over the package + ruff.

Three jobs:

1. **The gate itself** — ``python -m tools.crdtlint crdt_tpu/`` must
   exit 0 on the committed tree (baselined/suppressed findings
   allowed, open findings fail), and fast (<10 s: it runs on every
   tier-1 invocation forever).
2. **Anti-rot** — every registered checker code still FIRES on a
   violating snippet. Without this, a refactor that breaks a checker
   reads as "the tree got cleaner" and the contract silently dies.
3. **Pinned regressions for the drift crdtlint surfaced on its first
   run** — the registry names that were emitted-but-undocumented, the
   computed fault-event names, and the unlocked device-hook mutations
   each stay fixed.

Plus the ruff satellite behind a skip-if-unavailable guard (the
container may not ship ruff; when it does, `ruff check .` must be
clean — config in pyproject [tool.ruff]).
"""

import os
import re
import shutil
from collections import Counter
import subprocess
import sys
import textwrap
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.crdtlint.checkers import ALL_CHECKERS, ALL_CODES  # noqa: E402
from tools.crdtlint.core import LintConfig, run_lint  # noqa: E402
from tools.crdtlint.registry import Registry, load_registry  # noqa: E402


# ---------------------------------------------------------------------------
# 1. the gate


def test_package_lints_clean_via_cli():
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.crdtlint", "crdt_tpu/"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    dt = time.perf_counter() - t0
    assert proc.returncode == 0, (
        "crdtlint found unsuppressed violations:\n"
        + proc.stdout + proc.stderr
    )
    # no stale baseline entries either: a fixed finding must drop its
    # baseline row in the same PR, or the ledger rots into fiction
    assert "stale baseline" not in proc.stderr, proc.stderr
    assert dt < 10.0, f"crdtlint took {dt:.1f}s (must stay under ~10s)"


def test_checker_suite_is_complete():
    """≥11 checkers (round 17 added the CL10xx wire-taint and CL11xx
    decode-allocation families) and every advertised code belongs to
    exactly one, with an --explain text."""
    from tools.crdtlint.checkers import ALL_EXPLAIN

    assert len(ALL_CHECKERS) >= 11
    seen = {}
    for cls in ALL_CHECKERS:
        for code in cls.codes:
            assert code not in seen, f"{code} registered twice"
            seen[code] = cls.name
    assert len(seen) >= 26
    for code in seen:
        assert ALL_EXPLAIN.get(code), f"{code} has no --explain text"
    # the round-17 codes are present and --explain is substantive
    # (a rationale + fix recipe, not the one-line invariant)
    for code in ("CL1001", "CL1002", "CL1003", "CL1004",
                 "CL1101", "CL1102"):
        assert code in seen, code
        assert len(ALL_EXPLAIN[code]) > len(ALL_CODES[code]), (
            f"{code} --explain text is just the invariant line"
        )


def test_cli_runs_without_importing_jax():
    """The analysis layer is stdlib-only BY CONTRACT: the whole-tree
    pass (call graph included) must never import jax — that is what
    keeps it runnable in any environment and inside the <10 s
    budget."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.');"
         "from tools.crdtlint.__main__ import main;"
         "rc = main(['crdt_tpu/']);"
         "assert 'jax' not in sys.modules, 'crdtlint imported jax';"
         "sys.exit(rc)"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# 2. anti-rot: every code fires on its violating snippet


def _lint_snippet(path, src, registry=None):
    config = LintConfig(
        repo_root="/synthetic", readme_path="", registry_test_path="",
        baseline_path="/synthetic/absent.json",
    )
    return run_lint(
        [(path, textwrap.dedent(src))], config=config, baseline={},
        shared={
            "metric_registry":
                registry if registry is not None else Registry()
        },
    )


def _reg(*names):
    r = Registry()
    for n in names:
        r.add(n, "metric", "README.md", 1)
    return r


_DONATE = '''
from functools import partial
import jax

@partial(jax.jit, donate_argnums=(0,))
def _converge_x(mat):
    return mat
'''

# code -> (path, violating snippet, registry or None)
STILL_FIRES = {
    "CL000": ("crdt_tpu/ops/x.py", "def broken(:\n", None),
    "CL101": ("crdt_tpu/ops/x.py", _DONATE + '''
def caller(mat):
    out = _converge_x(mat)
    return mat.sum()
''', None),
    "CL102": ("crdt_tpu/ops/x.py", _DONATE, None),
    "CL201": ("crdt_tpu/core/x.py", '''
def f(tracer):
    tracer.count("engine.not_in_registry", 1)
''', None),
    "CL202": ("crdt_tpu/core/x.py", '''
def f(tracer):
    tracer.count("engine.real", 1)
''', _reg("engine.real", "engine.dead_entry")),
    "CL203": ("crdt_tpu/core/x.py", '''
def f(tracer, name):
    tracer.count(name, 1)
''', None),
    "CL301": ("crdt_tpu/codec/x.py", '''
def decode_x(b):
    try:
        return b[0]
    except:
        return None
''', None),
    "CL302": ("crdt_tpu/codec/x.py", '''
def decode_x(b):
    raise KeyError("boom")
''', None),
    "CL303": ("crdt_tpu/guard/x.py", '''
def ladder(fn):
    try:
        return fn()
    except SimulatedCrash:
        return None
''', None),
    "CL401": ("crdt_tpu/models/x.py", '''
import jax

def upload(arr):
    return jax.device_put(arr)
''', None),
    "CL501": ("crdt_tpu/ops/x.py", '''
import time

def stamp():
    return time.time()
''', None),
    "CL502": ("crdt_tpu/parallel/x.py", '''
import random

def jitter():
    return random.random()
''', None),
    "CL503": ("crdt_tpu/parallel/x.py", None, None),  # two-file case
    "CL504": ("crdt_tpu/core/x.py", '''
def pack(items):
    return [k for k in set(items)]
''', None),
    "CL601": ("crdt_tpu/obs/tracer.py", '''
_state = dict()

def put(k, v):
    _state[k] = v
''', None),
    "CL701": ("crdt_tpu/ops/x.py", '''
import jax
from crdt_tpu.obs.tracer import get_tracer

@jax.jit
def step(x):
    get_tracer().count("engine.calls", 1)
    return x
''', None),
    "CL702": ("crdt_tpu/ops/x.py", '''
import os
import jax

@jax.jit
def step(x):
    if os.environ.get("CRDT_TPU_FLAG"):
        return x
    return x + 1
''', None),
    "CL703": ("crdt_tpu/ops/x.py", '''
import jax

@jax.jit
def step(x):
    jax.block_until_ready(x)
    return x
''', None),
    "CL704": ("crdt_tpu/ops/x.py", '''
import jax

_CACHE = {}

@jax.jit
def step(x):
    _CACHE["last"] = x
    return x
''', None),
    "CL801": ("crdt_tpu/ops/x.py", '''
import threading

LOCK_A = threading.Lock()
LOCK_B = threading.Lock()

def ab():
    with LOCK_A:
        with LOCK_B:
            pass

def ba():
    with LOCK_B:
        with LOCK_A:
            pass
''', None),
    "CL802": ("crdt_tpu/ops/x.py", '''
import subprocess
import threading

_BUILD_LOCK = threading.Lock()

def build():
    with _BUILD_LOCK:
        subprocess.run(["make"])
''', None),
    "CL803": ("crdt_tpu/models/x.py", '''
import threading

class SharedState:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def locked_bump(self):
        with self._lock:
            self.n += 1

    def bare_reset(self):
        self.n = 0

def worker():
    SharedState().locked_bump()

def spawn():
    return threading.Thread(target=worker)
''', None),
    "CL901": ("crdt_tpu/models/x.py", '''
from crdt_tpu.ops import packed

def leak(plan):
    h = packed.converge_async(plan)
    return 0
''', None),
    "CL902": ("crdt_tpu/obs/x.py", '''
import jax

def capture(log_dir, work):
    jax.profiler.start_trace(log_dir)
    work()
    jax.profiler.stop_trace()
''', None),
    "CL1001": ("crdt_tpu/codec/x.py", '''
def decode_x(d):
    n = d.read_var_uint()
    return d.data[n]
''', None),
    "CL1002": ("crdt_tpu/codec/x.py", '''
def decode_x(d):
    n = d.read_var_uint()
    return bytearray(n)
''', None),
    "CL1003": ("crdt_tpu/codec/x.py", '''
def decode_x(d):
    n = d.read_var_uint()
    out = []
    for _ in range(n):
        out.append(1)
    return out
''', None),
    "CL1004": ("crdt_tpu/codec/x.py", '''
def decode_x(d, cols):
    n = d.read_var_uint()
    return stage(cols, rows=n)
''', None),
    "CL1101": ("crdt_tpu/codec/x.py", '''
def decode_x(d):
    n = d.read_var_uint()
    if n > (1 << 31):
        raise ValueError("too big")
    return bytearray(n)
''', None),
    "CL1102": ("crdt_tpu/codec/x.py", '''
def _helper(b):
    raise KeyError("boom")

def decode_x(b):
    return _helper(b)
''', None),
}


@pytest.mark.parametrize("code", sorted(ALL_CODES) + ["CL000"])
def test_checker_still_fires(code):
    assert code in STILL_FIRES, (
        f"checker code {code} has no still-fires snippet — add one "
        f"(tools/crdtlint/checkers/__init__.py documents the rule)"
    )
    path, src, registry = STILL_FIRES[code]
    if code == "CL503":
        config = LintConfig(
            repo_root="/synthetic", readme_path="",
            registry_test_path="",
            baseline_path="/synthetic/absent.json",
        )
        result = run_lint(
            [
                ("crdt_tpu/net/faults.py", textwrap.dedent('''
                class FaultSchedule:
                    def __init__(self, seed: int = 0, *, drop=0.0):
                        self.seed = seed
                ''')),
                ("crdt_tpu/parallel/x.py", textwrap.dedent('''
                from crdt_tpu.net.faults import FaultSchedule

                def chaos():
                    return FaultSchedule(drop=0.5)
                ''')),
            ],
            config=config, baseline={},
            shared={"metric_registry": Registry()},
        )
    else:
        result = _lint_snippet(path, src, registry)
    assert any(f.code == code for f in result.findings), (
        f"{code} no longer fires on its violating snippet — the "
        f"checker rotted into a no-op"
    )


# ---------------------------------------------------------------------------
# round-17 satellites: SARIF export, per-checker timing, prune-stale


def test_cli_sarif_output(tmp_path):
    """--sarif writes a SARIF 2.1.0 log (one rule per registered
    code, --explain text as help, baselined findings carried as
    suppressions) WITHOUT changing exit-code semantics."""
    import json

    sarif_path = tmp_path / "out.sarif"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.crdtlint", "crdt_tpu/",
         "--sarif", str(sarif_path)],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    log = json.loads(sarif_path.read_text())
    assert log["version"] == "2.1.0"
    run = log["runs"][0]
    assert run["tool"]["driver"]["name"] == "crdtlint"
    # informationUri must be a valid absolute URI or absent (SARIF
    # 2.1.0 `format: uri`); a repo-relative hint gets the whole log
    # rejected by upload-sarif, silently killing the annotation lane
    info = run["tool"]["driver"].get("informationUri")
    assert info is None or re.match(r"^[a-z][a-z0-9+.-]*://", info)
    rules = {r["id"]: r for r in run["tool"]["driver"]["rules"]}
    assert set(rules) == set(ALL_CODES)
    from tools.crdtlint.checkers import ALL_EXPLAIN

    for code in ("CL1001", "CL1102"):
        assert rules[code]["help"]["text"] == ALL_EXPLAIN[code]
    # the committed tree is clean: every result is a suppressed
    # (baselined) finding with its ledger justification attached
    results = run["results"]
    assert results, "expected the baselined findings as results"
    for r in results:
        assert r["level"] == "note"
        supp = r["suppressions"][0]
        assert supp["kind"] == "external"
        assert supp["justification"].strip()
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].startswith("crdt_tpu/")
        assert loc["region"]["startLine"] >= 1


def test_cli_sarif_open_findings_are_errors(tmp_path):
    """An open finding lands as an error-level SARIF result and the
    exit code still fails the run."""
    import json

    bad = tmp_path / "crdt_tpu" / "codec"
    bad.mkdir(parents=True)
    (bad / "x.py").write_text(
        "def decode_x(d):\n"
        "    n = d.read_var_uint()\n"
        "    return d.data[n]\n"
    )
    sarif_path = tmp_path / "out.sarif"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.crdtlint",
         str(bad / "x.py"), "--sarif", str(sarif_path)],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    log = json.loads(sarif_path.read_text())
    errs = [r for r in log["runs"][0]["results"]
            if r["level"] == "error"]
    assert any(r["ruleId"] == "CL1001" for r in errs)


def test_cli_statistics_reports_per_checker_time():
    """The round-17 --statistics surface itemizes the <10 s budget:
    one wall-time line per checker, the two new families included."""
    # a subtree is enough: every checker's wall time is recorded
    # whether or not its scope matched, and this keeps the tier-1
    # wall cost of the assertion small
    proc = subprocess.run(
        [sys.executable, "-m", "tools.crdtlint", "crdt_tpu/codec/",
         "--statistics"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    timed = {
        line.split()[1].rstrip(":")
        for line in proc.stdout.splitlines()
        if line.startswith("time ")
    }
    for name in ("wire-taint", "decode-alloc", "donate",
                 "trace-purity"):
        assert name in timed, (name, sorted(timed))


def test_cli_prune_stale_drops_dead_entries_only(tmp_path):
    """--prune-stale rewrites the ledger in place: entries with no
    live finding drop, surviving justifications stay verbatim, and a
    ledger with nothing stale is left byte-identical."""
    import json

    committed = os.path.join(REPO, "tools", "crdtlint",
                             "baseline.json")
    bl_path = tmp_path / "baseline.json"
    data = json.loads(open(committed).read())
    data["entries"].append({
        "code": "CL401",
        "fingerprint": "crdt_tpu/ops/removed.py|CL401|ghost",
        "justification": "row for a file deleted rounds ago",
        "path": "crdt_tpu/ops/removed.py",
    })
    bl_path.write_text(json.dumps(data))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.crdtlint", "crdt_tpu/",
         "--baseline", str(bl_path), "--prune-stale"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "pruned 1 stale" in proc.stderr
    after = json.loads(bl_path.read_text())
    fps = {e["fingerprint"] for e in after["entries"]}
    assert "crdt_tpu/ops/removed.py|CL401|ghost" not in fps
    # every surviving entry kept its hand-written justification
    before_by_fp = {
        e["fingerprint"]: e["justification"] for e in data["entries"]
    }
    for e in after["entries"]:
        assert e["justification"] == before_by_fp[e["fingerprint"]]
    # idempotent: nothing stale now, ledger untouched
    unchanged = bl_path.read_text()
    proc2 = subprocess.run(
        [sys.executable, "-m", "tools.crdtlint", "crdt_tpu/",
         "--baseline", str(bl_path), "--prune-stale"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert proc2.returncode == 0
    assert "pruned" not in proc2.stderr
    assert bl_path.read_text() == unchanged


def test_committed_baseline_has_no_stale_entries_audit():
    """Round-17 baseline audit: the committed ledger carries ONLY
    live fingerprints (the CLI gate already asserts no stale
    warnings; this pins the audited count so silent ledger growth is
    a visible diff)."""
    import json

    committed = os.path.join(REPO, "tools", "crdtlint",
                             "baseline.json")
    data = json.loads(open(committed).read())
    by_code = Counter(e["code"] for e in data["entries"])
    # the audited composition: 9 donation twins, 14 seam waits, 2
    # singleton setters, 3 native-build locks, 2 round-17
    # environment-error raises
    assert by_code == Counter({
        "CL102": 9, "CL401": 14, "CL601": 2, "CL802": 3,
        "CL1102": 2,
    }), by_code
    for e in data["entries"]:
        assert e["justification"].strip()
        assert "TODO" not in e["justification"]


# ---------------------------------------------------------------------------
# 3. pinned regressions from crdtlint's first run over the tree


def _real_registry():
    return load_registry(
        os.path.join(REPO, "README.md"),
        os.path.join(REPO, "tests", "test_registry.py"),
    )


def test_registry_drift_fixed_fleet_and_engine_names():
    """First-run CL201 drift: these names were emitted by the code
    but missing from the README registry tables. They must stay
    documented."""
    reg = _real_registry()
    for name in (
        "fleet.step", "fleet.seg_step", "fleet.ops_converged",
        "engine.pending_delete_ranges",
        "persist.overflow_bytes", "persist.log_size_bytes",
        "replica.anti_entropy_bytes",
        "replica.propagation_lag_s", "replica.convergence_lag_s",
        "router.relay_send_bytes", "router.relay_bytes_forwarded",
        "router.relay_activations",
    ):
        assert name in reg.metrics, (
            f"{name} dropped out of the README registry tables "
            f"(round-8 drift fixed by crdtlint PR must stay fixed)"
        )


def test_registry_covers_converge_kernel_counters():
    """Round 12 (the sort diet) added the `converge.*` namespace for
    the Pallas kernel-dispatch evidence. Both directions must hold:
    the emitted names stay documented, and an UNdocumented converge
    name still fires CL201 — i.e. the namespace genuinely joined the
    registry-checked pool rather than an allowlist."""
    reg = _real_registry()
    for name in ("converge.pallas", "converge.pallas_fallback",
                 "converge.dispatch", "converge.fetch"):
        assert name in reg.metrics, (
            f"{name} dropped out of the README registry (round-12 "
            f"sort-diet contract)"
        )
    result = _lint_snippet("crdt_tpu/ops/x.py", '''
def f(tracer):
    tracer.count("converge.bogus_kernel", 1)
''', _reg("converge.pallas"))
    assert any(f.code == "CL201" for f in result.findings), (
        "an undocumented converge.* metric no longer fires CL201"
    )


def test_registry_covers_shard_counters():
    """Round 13 (multi-chip sharding) added the `shard.*` namespace
    and the chain-split staging gauges. Both directions must hold:
    the emitted names stay documented in the README registry, and an
    UNdocumented shard name still fires CL201."""
    reg = _real_registry()
    for name in ("shard.dispatches", "shard.boundary_bytes",
                 "shard.seam_rows", "shard.shards",
                 "converge.wyllie_rounds", "converge.chain_seams"):
        assert name in reg.metrics, (
            f"{name} dropped out of the README registry (round-13 "
            f"multi-chip contract)"
        )
    result = _lint_snippet("crdt_tpu/ops/x.py", '''
def f(tracer):
    tracer.count("shard.bogus_exchange", 1)
''', _reg("shard.dispatches"))
    assert any(f.code == "CL201" for f in result.findings), (
        "an undocumented shard.* metric no longer fires CL201"
    )


def test_registry_covers_tenant_counters():
    """Round 14 (tenant packing) added the `tenant.*` namespace, the
    `converge.docs_packed` staging counter, and the multi-doc
    sentinel rows. Both directions must hold: the emitted names stay
    documented in the README registry, and an UNdocumented tenant
    name still fires CL201 — the namespace genuinely joined the
    registry-checked pool."""
    reg = _real_registry()
    for name in ("converge.docs_packed", "tenant.submitted",
                 "tenant.docs_converged", "tenant.shed",
                 "tenant.shed_bytes", "tenant.fallback_docs",
                 "tenant.pending_bytes", "tenant.dispatch_docs",
                 "sentinel.doc_divergence", "sentinel.doc_lag"):
        assert name in reg.metrics, (
            f"{name} dropped out of the README registry (round-14 "
            f"tenant-packing contract)"
        )
    result = _lint_snippet("crdt_tpu/models/x.py", '''
def f(tracer):
    tracer.count("tenant.bogus_budget", 1)
''', _reg("tenant.submitted"))
    assert any(f.code == "CL201" for f in result.findings), (
        "an undocumented tenant.* metric no longer fires CL201"
    )


def test_registry_covers_delta_tick_counters():
    """Round 15 (delta ticks) added the resident-state ledger rows
    and the sentinel digest-cache row. Both directions must hold:
    the emitted names stay documented in the README registry, and an
    UNdocumented tenant/sentinel name still fires CL201 — the new
    rows genuinely joined the registry-checked pool."""
    reg = _real_registry()
    for name in ("tenant.delta_docs", "tenant.delta_rows",
                 "tenant.promotions", "tenant.delta_fallbacks",
                 "tenant.resident_evictions",
                 "tenant.resident_bytes", "tenant.resident_docs",
                 "sentinel.doc_digest_skips"):
        assert name in reg.metrics, (
            f"{name} dropped out of the README registry (round-15 "
            f"delta-tick contract)"
        )
    result = _lint_snippet("crdt_tpu/models/x.py", '''
def f(tracer):
    tracer.count("sentinel.bogus_digest_row", 1)
''', _reg("sentinel.doc_digest_skips"))
    assert any(f.code == "CL201" for f in result.findings), (
        "an undocumented sentinel.* metric no longer fires CL201"
    )


def test_registry_covers_pooled_resident_counters():
    """Round 20 (pooled resident matrix) added the pool's dispatch /
    compaction counters and allocation gauges. Both directions must
    hold: the emitted names stay documented in the README registry
    (never bare baseline entries), and an UNdocumented pool name
    still fires CL201 — the rows genuinely joined the
    registry-checked pool."""
    reg = _real_registry()
    for name in ("tenant.pool_dispatches", "tenant.pool_compactions",
                 "tenant.pool_bytes", "tenant.pool_docs"):
        assert name in reg.metrics, (
            f"{name} dropped out of the README registry (round-20 "
            f"pooled-resident contract)"
        )
    result = _lint_snippet("crdt_tpu/ops/x.py", '''
def f(tracer):
    tracer.count("tenant.pool_bogus_extent", 1)
''', _reg("tenant.pool_dispatches"))
    assert any(f.code == "CL201" for f in result.findings), (
        "an undocumented tenant.pool_* metric no longer fires CL201"
    )


def test_registry_covers_snapshot_counters():
    """Round 21 (crash-proof recovery) added the snapshot store's
    write/load/fallback plane. Both directions must hold: the emitted
    names stay documented in the README registry, and an undocumented
    ``snap.*`` name still fires CL201 — the new namespace genuinely
    joined the registry-checked pool."""
    reg = _real_registry()
    for name in ("snap.writes", "snap.loads", "snap.bytes",
                 "snap.fallbacks", "snap.write_errors",
                 "snap.evict_writes", "snap.write_ms", "snap.load_ms",
                 "tenant.checkpoint_docs"):
        assert name in reg.metrics, (
            f"{name} dropped out of the README registry (round-21 "
            f"snapshot contract)"
        )
    result = _lint_snippet("crdt_tpu/ops/x.py", '''
def f(tracer):
    tracer.count("snap.bogus_extent", 1)
''', _reg("snap.writes"))
    assert any(f.code == "CL201" for f in result.findings), (
        "an undocumented snap.* metric no longer fires CL201"
    )


def test_registry_covers_control_plane_counters():
    """Round 22 (the SLO-driven control plane) added the
    ``control.*`` decision/cooldown/ledger/setpoint registry plus the
    cadence-checkpoint counter. Both directions must hold: the
    emitted names stay documented in the README registry, and an
    undocumented ``control.*`` name still fires CL201 — the new
    namespace genuinely joined the registry-checked pool."""
    reg = _real_registry()
    for name in ("control.decisions", "control.cooldown_skips",
                 "control.ledger_dropped", "control.setpoint",
                 "snap.cadence_writes"):
        assert name in reg.metrics, (
            f"{name} dropped out of the README registry (round-22 "
            f"control-plane contract)"
        )
    result = _lint_snippet("crdt_tpu/obs/x.py", '''
def f(tracer):
    tracer.count("control.bogus_rule", 1)
''', _reg("control.decisions"))
    assert any(f.code == "CL201" for f in result.findings), (
        "an undocumented control.* metric no longer fires CL201"
    )


def test_registry_drift_fixed_event_kinds():
    """First-run CL201 drift on flight-recorder event kinds from the
    guard/storage/device adversaries."""
    reg = _real_registry()
    for name in ("guard.shed", "guard.evict", "device.fault",
                 "fault.disk", "persist.error"):
        assert name in reg.events | reg.metrics, (
            f"event kind {name} missing from the README event "
            f"registry"
        )


def test_fault_kind_events_declared_at_computed_site():
    """The one CL203 on first run: net/faults.py records
    f"fault.{kind}" — the closed name set must stay declared with an
    `emits=` directive so both registry directions keep seeing it."""
    with open(os.path.join(REPO, "crdt_tpu", "net", "faults.py")) as f:
        src = f.read()
    assert "crdtlint: emits=" in src
    for name in ("fault.drop", "fault.partition", "fault.corrupt",
                 "fault.delay", "fault.dup"):
        assert name in src


def test_device_hook_mutations_hold_lock():
    """First-run CL601 in ops/device.py: the fault-hook swap is
    reached from the streaming thread pool. Pin the behavior, not
    just the lint: concurrent swap-and-restore must never lose or
    duplicate a hook."""
    from crdt_tpu.ops import device as dev

    # swap storm: N threads each install a stream of unique tokens,
    # collecting what the swap hands back. An atomic exchange
    # conserves values under ANY interleaving: every installed token
    # (plus the initial hook) is returned by exactly one later swap
    # or is the final resident — a torn read-then-write would hand
    # the same predecessor to two threads and lose a token.
    initial = dev.device_fault_hook()
    n, rounds = 8, 200
    barrier = threading.Barrier(n)
    seen = [[] for _ in range(n)]

    def storm(tid):
        barrier.wait()
        for i in range(rounds):
            seen[tid].append(dev.set_device_fault_hook((tid, i)))

    threads = [
        threading.Thread(target=storm, args=(t,)) for t in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    final = dev.set_device_fault_hook(initial)  # restore + read last
    handed_out = Counter(v for lst in seen for v in lst)
    handed_out[final] += 1
    installed = Counter(
        (t, i) for t in range(n) for i in range(rounds)
    )
    installed[initial] += 1
    assert handed_out == installed, "hook swap lost/duplicated a value"
    assert dev.device_fault_hook() == initial


def test_device_memo_caches_locked_under_threads():
    """Review-pass CL601s (surfaced once the checker learned annotated
    globals): ``_pack_fns`` is a module memo cache reached from the
    streaming pool; its get-or-create
    now runs under ``_CACHE_LOCK``. Storm ``fetch_packed_i32`` across
    arities and pin byte-correct outputs with no lost cache entries."""
    import numpy as np

    jnp = pytest.importorskip("jax.numpy")
    from crdt_tpu.ops import device as dev

    with dev._CACHE_LOCK:
        dev._pack_fns.clear()
    n = 9
    errs = []
    barrier = threading.Barrier(n)

    def storm(tid):
        arity = 1 + (tid % 3)
        try:
            barrier.wait()
            arrays = [jnp.arange(4) + i for i in range(arity)]
            out = dev.fetch_packed_i32(*arrays)
            for i, a in enumerate(out):
                np.testing.assert_array_equal(
                    np.asarray(a), np.arange(4) + i
                )
        except Exception as e:  # noqa: BLE001 — collected for assert
            errs.append(e)

    threads = [
        threading.Thread(target=storm, args=(t,)) for t in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    # one jitted concat per distinct arity — racing threads must not
    # have lost entries (the pre-lock failure mode was a silent
    # overwrite: wasted recompile, never detected)
    assert sorted(dev._pack_fns) == [1, 2, 3]


# ---------------------------------------------------------------------------
# round-16 CL8xx audit: the lock-discipline checkers cleared the
# thread-shared surface (Tracer, FlightRecorder, the streaming
# _Phases accumulator, the serve() in-flight window) — these seeded
# storms pin the audited behavior so a refactor that drops a lock
# fails HERE, not just in the lint


def test_tracer_storm_conserves_counts():
    """CL803 audit pin: every Tracer mutation path (count/gauge/
    observe) under 8 racing threads loses nothing — the round-8 lock
    is load-bearing, not decorative."""
    from crdt_tpu.obs.tracer import Tracer

    tr = Tracer(enabled=True)
    n, rounds = 8, 400
    barrier = threading.Barrier(n)

    def storm(tid):
        barrier.wait()
        for i in range(rounds):
            tr.count("storm.hits")
            tr.count("storm.bytes", 3)
            tr.observe("storm.lat", 0.001 * ((tid + i) % 7 + 1))
            tr.gauge("storm.last", tid)

    threads = [
        threading.Thread(target=storm, args=(t,)) for t in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rep = tr.report()
    assert rep["counters"]["storm.hits"] == n * rounds
    assert rep["counters"]["storm.bytes"] == 3 * n * rounds
    span = rep["spans"]["storm.lat"]
    assert span["count"] == n * rounds
    assert sum(span["buckets"].values()) == n * rounds
    assert rep["gauges"]["storm.last"] in set(range(n))


def test_recorder_storm_conserves_events():
    """CL803 audit pin: FlightRecorder.record under racing producers
    never loses an increment, and the ring never exceeds capacity."""
    from crdt_tpu.obs.recorder import FlightRecorder

    rec = FlightRecorder(capacity=256, enabled=True)
    n, rounds = 8, 300
    barrier = threading.Barrier(n)

    def storm(tid):
        barrier.wait()
        for i in range(rounds):
            rec.record("update.sent", tid=tid, seq=i)

    threads = [
        threading.Thread(target=storm, args=(t,)) for t in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.recorded == n * rounds
    assert len(rec) == 256  # ring clamped at capacity, oldest evicted


def test_streaming_phase_accumulator_storm():
    """CL803 audit pin: the stager thread and the decode pool both
    charge busy seconds into one _Phases instance; racing adds must
    sum exactly (integer-valued floats — fp64 exact far beyond this
    count)."""
    from crdt_tpu.models.streaming import _Phases

    ph = _Phases()
    n, rounds = 9, 500
    barrier = threading.Barrier(n)

    def storm(tid):
        barrier.wait()
        for _ in range(rounds):
            ph.add("decode", 1.0)
            ph.add(f"lane{tid % 3}", 1.0)

    threads = [
        threading.Thread(target=storm, args=(t,)) for t in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ph.t["decode"] == float(n * rounds)
    assert sum(
        v for k, v in ph.t.items() if k.startswith("lane")
    ) == float(n * rounds)


def test_serve_inflight_window_ledger_exact():
    """CL803 audit pin for the serve() in-flight window: mid-tick
    arrivals (the live-ingest hook fires while a tick's dispatches
    are in flight) must never be marked converged without being
    converged, and the O(1) pending-byte ledger must land at exactly
    zero once the stream drains — the window accounting is
    single-thread-confined BY DESIGN (hook runs inside the tick),
    and this pins that the bookkeeping stays exact under it."""
    from crdt_tpu.codec import v1
    from crdt_tpu.core.records import ItemRecord
    from crdt_tpu.models import replay as rp
    from crdt_tpu.models.multidoc import MultiDocServer, cache_digest

    def blob(doc, b):
        return v1.encode_update([ItemRecord(
            client=1000 + doc, clock=b, parent_root=f"m{doc}",
            key=f"k{b}", content=b * 10 + doc,
        )])

    # 3 docs, 4 batches: each serve tick admits one batch, and the
    # ingest hook drains the next while dispatches are in flight
    batches = [
        [(d, blob(d, b)) for d in range(3)] for b in range(4)
    ]
    srv = MultiDocServer()
    rep = srv.serve(iter(batches), max_ticks=16)
    assert rep.submitted == 12
    assert srv.pending_bytes() == 0
    for d in range(3):
        st = srv._docs[d]
        assert not st.pending and not st.in_flight
        assert len(st.blobs) == 4  # every admitted blob converged
        # digest matches the cold oracle over the same history
        oracle = rp.replay_trace(st.blobs).cache
        assert cache_digest(srv.cache(d)) == cache_digest(oracle)


# ---------------------------------------------------------------------------
# round-16 CL702 regression: the Pallas dispatch decision is a
# host-computed static, never an ambient read inside a traced body


def test_pallas_mode_statics_thread_not_ambient(monkeypatch):
    """The traced-safe entries (apply_mask_static / missing_static /
    ds_mask_static / sv_deficit_static) must not read CRDT_TPU_PALLAS
    at all — poison the env readers and drive them with explicit
    modes. The first-run CL702 findings (env reads baked into
    converge_maps' trace via lax.cond) stay fixed."""
    import numpy as np

    jnp = pytest.importorskip("jax.numpy")
    from crdt_tpu.ops import deleteset, pallas_kernels as pk, statevec

    def boom(*a, **kw):
        raise AssertionError(
            "traced-safe path read CRDT_TPU_PALLAS (CL702 regression)"
        )

    monkeypatch.setattr(pk, "use_pallas", boom)
    monkeypatch.setattr(pk, "_interpret", boom)

    client = jnp.asarray(np.array([1, 1, 2], np.int32))
    clock = jnp.asarray(np.array([0, 5, 1], np.int64))
    valid = jnp.asarray(np.array([True, True, True]))
    dc = jnp.asarray(np.array([1], np.int32))
    dstart = jnp.asarray(np.array([0], np.int64))
    dend = jnp.asarray(np.array([1], np.int64))
    for mode in ("jnp", "interpret"):
        mask = deleteset.apply_mask_static(
            client, clock, valid, dc, dstart, dend, mode=mode
        )
        assert np.asarray(mask).tolist() == [True, False, False]
    svs = jnp.asarray(np.array([[3, 0], [1, 2]], np.int64))
    ref = np.asarray(statevec.missing_static(svs, "jnp"))
    got = np.asarray(statevec.missing_static(svs, "interpret"))
    np.testing.assert_array_equal(ref, got)


def test_mask_mode_reflects_env(monkeypatch):
    """The HOST-side mode helpers keep honoring runtime env flips —
    that is the contract the statics thread down."""
    from crdt_tpu.ops import deleteset, statevec

    monkeypatch.setenv("CRDT_TPU_PALLAS", "0")
    assert deleteset.mask_mode() == "jnp"
    assert statevec.deficit_mode() == "jnp"
    monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")
    assert deleteset.mask_mode() == "interpret"
    assert statevec.deficit_mode() == "interpret"


# ---------------------------------------------------------------------------
# ruff (satellite): targeted rule set, skip when unavailable


def test_ruff_clean_if_available():
    ruff = shutil.which("ruff")
    if ruff is None:
        pytest.skip("ruff not installed in this environment")
    proc = subprocess.run(
        [ruff, "check", "."], cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_registry_covers_propagation_and_collector_counters():
    """Round 19 (distributed tracing) added the `propagation.*` and
    `collector.*` namespaces plus the per-hop replica rows and the
    bad-context event kind. Both directions must hold: the emitted
    names stay documented in the README registry, and an
    UNdocumented propagation/collector name still fires CL201 — the
    namespaces genuinely joined the registry-checked pool."""
    reg = _real_registry()
    for name in ("propagation.contexts_sent",
                 "propagation.contexts_received",
                 "propagation.malformed_contexts",
                 "propagation.hops_appended",
                 "propagation.hops_capped",
                 "propagation.context_bytes",
                 "propagation.traced_update_bytes",
                 "propagation.wire_overhead_ratio",
                 "replica.hop_lag",
                 "replica.birth_to_visibility",
                 "collector.procs", "collector.pair_rate",
                 "collector.scrapes", "collector.scrape_errors",
                 "collector.events_ingested",
                 "collector.divergences"):
        assert name in reg.metrics, (
            f"{name} dropped out of the README registry (round-19 "
            f"distributed-tracing contract)"
        )
    assert "update.bad_context" in reg.events | reg.metrics, (
        "update.bad_context event kind missing from the README "
        "event registry"
    )
    for path, snippet in (
        ("crdt_tpu/obs/x.py",
         'def f(tracer):\n    tracer.count("propagation.bogus", 1)\n'),
        ("crdt_tpu/obs/x.py",
         'def f(tracer):\n    tracer.gauge("collector.bogus", 1)\n'),
    ):
        result = _lint_snippet(path, snippet,
                               _reg("propagation.contexts_sent"))
        assert any(f.code == "CL201" for f in result.findings), (
            "an undocumented propagation/collector metric no longer "
            "fires CL201"
        )


def test_hop_lag_route_labels_declared_at_computed_site():
    """The route-labeled hop-lag observe is a computed name (one
    f-string over the closed route enum): the `emits=` directive
    must keep declaring it so both registry directions see it."""
    with open(os.path.join(REPO, "crdt_tpu", "obs",
                           "propagation.py")) as f:
        src = f.read()
    assert "crdtlint: emits=replica.hop_lag" in src


def test_wiretaint_scope_covers_trace_context_decode():
    """The round-19 decode path is inside the CL10xx/CL11xx scope:
    an unfenced wire read feeding an allocation in
    obs/propagation.py must fire, exactly like codec/."""
    from tools.crdtlint.checkers.decodealloc import DECODE_SCOPE
    from tools.crdtlint.checkers.wiretaint import SCOPE

    assert any("obs/propagation" in s for s in SCOPE)
    assert any("obs/propagation" in s for s in DECODE_SCOPE)
    result = _lint_snippet("crdt_tpu/obs/propagation.py", '''
def decode_thing(dec):
    n = dec.read_var_uint()
    return [0] * n
''')
    assert any(f.code == "CL1002" for f in result.findings), (
        "an unfenced allocation in obs/propagation.py no longer "
        "fires the wire-taint checker"
    )
