"""Unit tests for the crdtlint analyzer itself (tools/crdtlint).

Synthetic in-memory snippets per checker — positive (fires),
negative (clean), suppressed (inline disable), and baseline-matched —
plus the suppression-comment and baseline-file round-trips. The
tier-1 gate over the real package lives in tests/test_lint.py; THIS
file proves the analyzer's own semantics, so a checker regression
shows up as a unit failure, not as silently-green lint.
"""

import json
import os
import sys
import textwrap

import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from tools.crdtlint.core import (  # noqa: E402
    BaselineError,
    LintConfig,
    load_baseline,
    run_lint,
    write_baseline,
)
from tools.crdtlint.registry import Registry  # noqa: E402


def lint(files, registry=None, baseline=None):
    """Lint {relpath: source} snippets with an empty default registry
    (synthetic runs opt into documented names explicitly)."""
    config = LintConfig(
        repo_root="/synthetic", readme_path="", registry_test_path="",
        baseline_path="/synthetic/absent.json",
    )
    return run_lint(
        [(path, textwrap.dedent(src)) for path, src in files.items()],
        config=config,
        baseline=baseline or {},
        shared={
            "metric_registry":
                registry if registry is not None else Registry()
        },
    )


def codes(result):
    return sorted(f.code for f in result.findings)


# ---------------------------------------------------------------------------
# CL101 use-after-donate


DONATING_DEF = '''
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(0,))
    def _converge(mat, n):
        return mat * n

    def _converge_nodonate(mat, n):
        return mat * n
'''


def test_cl101_read_after_donate_fires():
    r = lint({"crdt_tpu/ops/x.py": DONATING_DEF + '''
    def caller(mat):
        out = _converge(mat, 3)
        return mat.sum() + out
    '''})
    assert "CL101" in codes(r)


def test_cl101_rebind_is_clean():
    r = lint({"crdt_tpu/ops/x.py": DONATING_DEF + '''
    def caller(mat):
        mat = _converge(mat, 3)
        return mat.sum()
    '''})
    assert "CL101" not in codes(r)


def test_cl101_self_attribute_tracking():
    r = lint({"crdt_tpu/ops/x.py": DONATING_DEF + '''
    class C:
        def run(self):
            out = _converge(self._mat, 3)
            return self._mat.shape
    '''})
    assert "CL101" in codes(r)


def test_cl101_branches_do_not_cross():
    # donation in one branch must not poison the sibling branch
    r = lint({"crdt_tpu/ops/x.py": DONATING_DEF + '''
    def caller(mat, flag):
        if flag:
            out = _converge(mat, 3)
        else:
            out = mat.sum()
        return out
    '''})
    assert "CL101" not in codes(r)


def test_cl101_donation_in_if_test_fires():
    # a donation INSIDE the if-test expression flows into both
    # branches and past the if (the test is evaluated exactly once,
    # before either branch runs)
    r = lint({"crdt_tpu/ops/x.py": DONATING_DEF + '''
    def caller(mat):
        if _converge(mat, 3):
            return mat.sum()
        return mat.shape
    '''})
    assert codes(r).count("CL101") == 2


def test_cl101_loop_without_rebind_fires():
    r = lint({"crdt_tpu/ops/x.py": DONATING_DEF + '''
    def caller(mat):
        acc = 0
        for i in range(3):
            acc += _converge(mat, i)
        return acc
    '''})
    assert any(
        f.code == "CL101" and "loop" in f.message for f in r.findings
    )


def test_cl101_loop_with_rebind_clean():
    r = lint({"crdt_tpu/ops/x.py": DONATING_DEF + '''
    def caller(mat):
        for i in range(3):
            mat = _converge(mat, i)
        return mat
    '''})
    assert "CL101" not in codes(r)


def test_cl101_factory_result_in_loop():
    # the gossip factory pattern: step donates arg 0; packed rebuilt
    # each round is clean, reused is a finding
    src = '''
    import jax

    def make_step(n):
        def step(block, dels):
            return block * n
        return jax.jit(step, donate_argnums=(0,))

    def good(n, build):
        step = make_step(n)
        for i in range(4):
            block = build(i)
            out = step(block, ())
        return out

    def bad(n, block):
        step = make_step(n)
        for i in range(4):
            out = step(block, ())
        return out
    '''
    r = lint({"crdt_tpu/parallel/x.py": src})
    bad_lines = [f for f in r.findings if f.code == "CL101"]
    assert len(bad_lines) == 1
    assert "block" in bad_lines[0].message


def test_cl102_missing_twin_and_satisfied_twin():
    src = '''
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(0,))
    def _converge_solo(mat):
        return mat
    '''
    r = lint({"crdt_tpu/ops/x.py": src})
    assert "CL102" in codes(r)
    # DONATING_DEF has a _nodonate twin: no CL102
    r2 = lint({"crdt_tpu/ops/y.py": DONATING_DEF})
    assert "CL102" not in codes(r2)


# ---------------------------------------------------------------------------
# CL201/202/203 registry conformance


def reg(*names, events=()):
    r = Registry()
    for n in names:
        r.add(n, "metric", "README.md", 1)
    for n in events:
        r.add(n, "event", "README.md", 2)
    return r


def test_cl201_unregistered_metric_fires():
    r = lint(
        {"crdt_tpu/core/x.py": '''
    def f(tracer):
        tracer.count("engine.bogus_counter", 1)
    '''},
        registry=reg("engine.real"),
    )
    assert "CL201" in codes(r)
    # and the documented-but-dead entry fires the other way
    assert "CL202" in codes(r)


def test_cl202_documented_and_emitted_clean():
    r = lint(
        {"crdt_tpu/core/x.py": '''
    def f(tracer):
        tracer.count("engine.real", 1)
    '''},
        registry=reg("engine.real"),
    )
    assert codes(r) == []


def test_cl203_computed_name_fires_and_emits_declares():
    src_bad = '''
    def f(tracer, name):
        tracer.count(name, 1)
    '''
    r = lint({"crdt_tpu/core/x.py": src_bad}, registry=reg("engine.real"))
    assert "CL203" in codes(r)

    src_declared = '''
    def f(rec, kind):
        # crdtlint: emits=fault.drop,fault.dup
        rec.record(f"fault.{kind}", size=1)
    '''
    r2 = lint(
        {"crdt_tpu/net/x.py": src_declared},
        registry=reg(events=("fault.drop", "fault.dup")),
    )
    assert "CL203" not in codes(r2)
    assert "CL202" not in codes(r2)  # declared names count as emitted


def test_cl203_symbol_uses_innermost_enclosing_function():
    # the old lineno-keyed map attributed a closure's lines to the
    # OUTERMOST function, so two closures' findings could collide on
    # one symbol (and an allowlisted outer name would leak to nested
    # helpers); the fingerprint must anchor on the innermost def
    r = lint({"crdt_tpu/core/x.py": '''
    def outer(tracer, name):
        def inner():
            tracer.count(name, 1)
        return inner
    '''}, registry=reg("engine.real"))
    cl = [f for f in r.findings if f.code == "CL203"]
    assert [f.symbol for f in cl] == ["inner:count"]


def test_cl201_counter_kwarg_literal_checked():
    r = lint(
        {"crdt_tpu/storage/x.py": '''
    def f():
        retry(lambda: 0, counter="persist.bogus")
    '''},
        registry=reg("persist.real"),
    )
    assert "CL201" in codes(r)


# ---------------------------------------------------------------------------
# CL301/302/303 exception discipline


def test_cl301_bare_except_in_codec():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_thing(b):
        try:
            return b[0]
        except:
            return None
    '''})
    assert "CL301" in codes(r)


def test_cl302_decode_raises_non_valueerror():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_thing(b):
        if not b:
            raise KeyError("empty")
        return b[0]
    '''})
    assert "CL302" in codes(r)


def test_cl302_valueerror_and_encode_paths_clean():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_thing(b):
        if not b:
            raise ValueError("empty")
        return b[0]

    def write_thing(v):
        raise TypeError("encode paths may type-check")
    '''})
    assert "CL302" not in codes(r)


def test_cl303_guard_catches_simulated_crash():
    r = lint({"crdt_tpu/guard/x.py": '''
    def ladder(fn):
        try:
            return fn()
        except SimulatedCrash:
            return None
    '''})
    assert "CL303" in codes(r)


def test_cl30x_out_of_scope_module_clean():
    r = lint({"crdt_tpu/models/x.py": '''
    def decode_thing(b):
        try:
            raise KeyError("x")
        except:
            pass
    '''})
    assert "CL301" not in codes(r)
    assert "CL302" not in codes(r)


# ---------------------------------------------------------------------------
# CL401 transfer seam


def test_cl401_device_put_outside_seam():
    r = lint({"crdt_tpu/models/x.py": '''
    import jax

    def upload(arr):
        return jax.device_put(arr)
    '''})
    assert "CL401" in codes(r)


def test_cl401_asarray_of_dispatch_result():
    r = lint({"crdt_tpu/models/x.py": DONATING_DEF + '''
    import numpy as np

    def fetch(mat):
        out = _converge_nodonate(mat, 2)
        dev = _converge(out, 2)
        return np.asarray(dev)
    '''})
    assert any(
        f.code == "CL401" and "asarray" in f.message for f in r.findings
    )


def test_cl401_rebind_through_xfer_fetch_is_clean():
    # `dev = xfer_fetch(dev, n)` yields a HOST array — the later
    # asarray/.item() is not a seam bypass (the whole-function taint
    # pass used to flag it anyway, forcing bogus baseline entries)
    r = lint({"crdt_tpu/models/x.py": DONATING_DEF + '''
    import numpy as np

    def fetch(mat, xfer_fetch):
        dev = _converge(mat, 2)
        dev = xfer_fetch(dev, 8)
        return np.asarray(dev) + dev.item()
    '''})
    assert "CL401" not in codes(r)


def test_cl401_asarray_before_dispatch_is_clean():
    # the host-materialization textually PRECEDES the dispatch that
    # binds the name — order matters, this is not a bypass
    r = lint({"crdt_tpu/models/x.py": DONATING_DEF + '''
    import numpy as np

    def fetch(dev, mat):
        host = np.asarray(dev)
        dev = _converge(mat, 2)
        return host
    '''})
    assert "CL401" not in codes(r)


def test_cl401_same_line_rebinding_asarray_still_fires():
    # `x = np.asarray(x)` on a tainted x is the bypass itself; the
    # rebind must not untaint before the use is checked
    r = lint({"crdt_tpu/models/x.py": DONATING_DEF + '''
    import numpy as np

    def fetch(mat):
        dev = _converge(mat, 2)
        dev = np.asarray(dev)
        return dev
    '''})
    assert any(
        f.code == "CL401" and "asarray" in f.message for f in r.findings
    )


def test_cl401_seam_module_itself_clean():
    r = lint({"crdt_tpu/ops/device.py": '''
    import jax

    def xfer_put(arr):
        return jax.device_put(arr)
    '''})
    assert "CL401" not in codes(r)


# ---------------------------------------------------------------------------
# CL501-504 determinism


def test_cl501_time_time_in_core():
    r = lint({"crdt_tpu/ops/x.py": '''
    import time

    def stamp():
        return time.time()
    '''})
    assert "CL501" in codes(r)
    # perf_counter is fine; net/ modules are out of scope
    r2 = lint({"crdt_tpu/ops/y.py": '''
    import time

    def span():
        return time.perf_counter()
    '''})
    assert "CL501" not in codes(r2)
    r3 = lint({"crdt_tpu/net/x.py": '''
    import time

    def stamp():
        return time.time()
    '''})
    assert "CL501" not in codes(r3)


def test_cl502_unseeded_randomness():
    r = lint({"crdt_tpu/parallel/x.py": '''
    import random
    import numpy as np

    def jitter():
        return random.random()

    def rng():
        return np.random.default_rng()
    '''})
    assert codes(r).count("CL502") == 2
    r2 = lint({"crdt_tpu/parallel/y.py": '''
    import numpy as np

    def rng(seed):
        return np.random.default_rng(seed)
    '''})
    assert "CL502" not in codes(r2)


def test_cl503_unseeded_fault_schedule():
    faults = '''
    class FaultSchedule:
        def __init__(self, seed: int = 0, *, drop=0.0):
            self.seed = seed
    '''
    user_bad = '''
    from crdt_tpu.net.faults import FaultSchedule

    def chaos():
        return FaultSchedule(drop=0.5)
    '''
    user_good = '''
    from crdt_tpu.net.faults import FaultSchedule

    def chaos():
        return FaultSchedule(seed=7, drop=0.5)
    '''
    r = lint({
        "crdt_tpu/net/faults.py": faults,
        "crdt_tpu/parallel/bad.py": user_bad,
        "crdt_tpu/parallel/good.py": user_good,
    })
    hits = [f for f in r.findings if f.code == "CL503"]
    assert len(hits) == 1
    assert hits[0].path.endswith("bad.py")


def test_cl504_set_iteration():
    r = lint({"crdt_tpu/core/x.py": '''
    def pack(items):
        out = []
        for k in set(items):
            out.append(k)
        return out
    '''})
    assert "CL504" in codes(r)
    r2 = lint({"crdt_tpu/core/y.py": '''
    def pack(items):
        return [k for k in sorted(set(items))]
    '''})
    assert "CL504" not in codes(r2)


# ---------------------------------------------------------------------------
# CL601 thread-shared state


def test_cl601_unlocked_global_fires_and_locked_clean():
    r = lint({"crdt_tpu/obs/tracer.py": '''
    import threading

    _tracer = dict()
    _LOCK = threading.Lock()


    def set_bad(v):
        global _tracer
        _tracer = v


    def set_good(v):
        global _tracer
        with _LOCK:
            _tracer = v


    def mutate_bad(k, v):
        _tracer[k] = v


    def mutate_good(k, v):
        with _LOCK:
            _tracer[k] = v
    '''})
    cl = [f for f in r.findings if f.code == "CL601"]
    assert {f.symbol for f in cl} == {"set_bad:_tracer",
                                      "mutate_bad:_tracer"}


def test_cl601_lock_like_names_only():
    # `self._blocker` contains 'lock' as a raw substring (b·lock) but
    # is NOT a lock — it must not silence the checker; real lock
    # spellings (_CACHE_LOCK, threading.Lock(), cacheLock) all count
    r = lint({"crdt_tpu/obs/tracer.py": '''
    import threading

    _events = dict()
    _CACHE_LOCK = threading.Lock()
    cacheLock = threading.Lock()


    class W:
        def mutate_blocker(self, k, v):
            with self._blocker:
                _events[k] = v

        def mutate_unblocked(self, k, v):
            with _unblocked_region():
                _events[k] = v

        def mutate_const_lock(self, k, v):
            with _CACHE_LOCK:
                _events[k] = v

        def mutate_ctor_lock(self, k, v):
            with threading.Lock():
                _events[k] = v

        def mutate_camel_lock(self, k, v):
            with cacheLock:
                _events[k] = v
    '''})
    cl = {f.symbol for f in r.findings if f.code == "CL601"}
    assert cl == {"mutate_blocker:_events", "mutate_unblocked:_events"}


def test_cl601_untargeted_module_clean():
    r = lint({"crdt_tpu/core/x.py": '''
    _cache = {}

    def put(k, v):
        _cache[k] = v
    '''})
    assert "CL601" not in codes(r)


# ---------------------------------------------------------------------------
# suppression + baseline machinery


VIOLATION = '''
import jax

def upload(arr):
    return jax.device_put(arr)
'''


def test_inline_disable_suppresses():
    src = '''
    import jax

    def upload(arr):
        return jax.device_put(arr)  # crdtlint: disable=CL401
    '''
    r = lint({"crdt_tpu/models/x.py": src})
    assert "CL401" not in codes(r)
    assert any(f.code == "CL401" for f in r.suppressed)


def test_inline_disable_on_line_above():
    src = '''
    import jax

    def upload(arr):
        # crdtlint: disable=CL401
        return jax.device_put(arr)
    '''
    r = lint({"crdt_tpu/models/x.py": src})
    assert "CL401" not in codes(r)


def test_inline_disable_wrong_code_does_not_suppress():
    src = '''
    import jax

    def upload(arr):
        return jax.device_put(arr)  # crdtlint: disable=CL999
    '''
    r = lint({"crdt_tpu/models/x.py": src})
    assert "CL401" in codes(r)


def test_disable_file_directive():
    src = '''
    # crdtlint: disable-file=CL401
    import jax

    def upload(arr):
        return jax.device_put(arr)

    def download(arr):
        return jax.device_get(arr)
    '''
    r = lint({"crdt_tpu/models/x.py": src})
    assert "CL401" not in codes(r)
    assert len([f for f in r.suppressed if f.code == "CL401"]) == 2


def test_baseline_roundtrip(tmp_path):
    # 1. a violation fires
    r = lint({"crdt_tpu/models/x.py": VIOLATION})
    assert len(r.findings) == 1
    # 2. write it to a baseline file, justify it, reload
    path = tmp_path / "baseline.json"
    write_baseline(str(path), r.findings)
    data = json.loads(path.read_text())
    assert len(data["entries"]) == 1
    data["entries"][0]["justification"] = "synthetic fixture"
    path.write_text(json.dumps(data))
    base = load_baseline(str(path))
    # 3. the same violation is now baselined, not open
    r2 = lint({"crdt_tpu/models/x.py": VIOLATION}, baseline=base)
    assert r2.findings == []
    assert len(r2.baselined) == 1
    # 4. fixing the code leaves a stale baseline entry (warned)
    r3 = lint(
        {"crdt_tpu/models/x.py": "def upload(arr):\n    return arr\n"},
        baseline=base,
    )
    assert r3.findings == [] and r3.baselined == []
    assert len(r3.stale_baseline) == 1


def test_baseline_requires_justification(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({
        "entries": [{"fingerprint": "a|CL401|b", "justification": ""}]
    }))
    with pytest.raises(BaselineError):
        load_baseline(str(path))


def test_baseline_fingerprint_survives_line_moves(tmp_path):
    r = lint({"crdt_tpu/models/x.py": VIOLATION})
    fp = r.findings[0].fingerprint
    shifted = "'''module docstring'''\nX = 1\n" + VIOLATION
    r2 = lint({"crdt_tpu/models/x.py": shifted})
    assert r2.findings[0].fingerprint == fp


def test_syntax_error_is_a_finding():
    r = lint({"crdt_tpu/models/x.py": "def broken(:\n"})
    assert [f.code for f in r.findings] == ["CL000"]


def test_run_lint_public_entry(tmp_path):
    """The public run_lint() surface used by the CLI."""
    config = LintConfig(
        repo_root=str(tmp_path), readme_path="", registry_test_path="",
        baseline_path=str(tmp_path / "none.json"),
    )
    result = run_lint(
        [("crdt_tpu/models/x.py", VIOLATION)], config=config
    )
    assert [f.code for f in result.findings] == ["CL401"]
    assert result.total_raw == 1


# ---------------------------------------------------------------------------
# review-pass regressions: analyzer gaps found after the first run


def test_cl601_annotated_module_global_fires():
    # `X: set = set()` binds the same shared state as `X = set()` —
    # a type annotation must not silence CL601 (the ops/device.py
    # memo-cache shape the first review pass slipped through)
    r = lint({"crdt_tpu/ops/device.py": '''
    _CACHE: set = set()

    def remember(key):
        _CACHE.add(key)
    '''})
    assert "CL601" in codes(r)


def test_cl601_annotated_global_locked_clean():
    r = lint({"crdt_tpu/ops/device.py": '''
    import threading

    _CACHE: set = set()
    _CACHE_LOCK = threading.Lock()

    def remember(key):
        with _CACHE_LOCK:
            _CACHE.add(key)
    '''})
    assert "CL601" not in codes(r)


def test_cl401_method_form_block_until_ready_fires():
    # `out.block_until_ready()` — the array-method spelling — is the
    # same wait as `jax.block_until_ready(out)` and must not slip
    # past the seam checker
    r = lint({"crdt_tpu/models/x.py": '''
    def wait(out):
        out.block_until_ready()
        return out
    '''})
    assert "CL401" in codes(r)


def test_cl401_method_form_on_call_result_fires():
    # even with no dotted receiver (`f(x).block_until_ready()`)
    r = lint({"crdt_tpu/models/x.py": '''
    def wait(f, x):
        f(x).block_until_ready()
    '''})
    assert "CL401" in codes(r)


def test_cl101_local_def_shadows_foreign_donating_name():
    # module B's own non-donating `_step` shadows module A's donating
    # `_step`: reading the arg after B's local call is NOT a
    # use-after-donate (the collision used to invent one)
    r = lint({
        "crdt_tpu/ops/a.py": '''
        from functools import partial
        import jax

        @partial(jax.jit, donate_argnums=(0,))
        def _step(mat):
            return mat
        ''',
        "crdt_tpu/models/b.py": '''
        def _step(mat):
            return mat + 1

        def caller(mat):
            out = _step(mat)
            return mat.sum() + out
        ''',
    })
    assert "CL101" not in codes(r)


def test_cl101_same_name_donating_defs_keep_their_argnums():
    # two modules donate under one name with DIFFERENT argnums; the
    # old name-keyed index let one overwrite the other, hiding one
    # module's real use-after-donate — both must fire
    r = lint({
        "crdt_tpu/ops/a.py": '''
        from functools import partial
        import jax

        @partial(jax.jit, donate_argnums=(0,))
        def _step(x, y):
            return x + y

        def caller(x, y):
            out = _step(x, y)
            return x.sum() + out
        ''',
        "crdt_tpu/ops/b.py": '''
        from functools import partial
        import jax

        @partial(jax.jit, donate_argnums=(1,))
        def _step(x, y):
            return x + y

        def caller(x, y):
            out = _step(x, y)
            return y.sum() + out
        ''',
    })
    assert codes(r).count("CL101") == 2


def test_cl101_import_resolves_defining_module():
    # B imports A's donating `_step`; the import picks A's argnums
    # even though B defines nothing itself
    r = lint({
        "crdt_tpu/ops/a.py": '''
        from functools import partial
        import jax

        @partial(jax.jit, donate_argnums=(0,))
        def _step(mat):
            return mat
        ''',
        "crdt_tpu/models/b.py": '''
        from crdt_tpu.ops.a import _step

        def caller(mat):
            out = _step(mat)
            return mat.sum() + out
        ''',
    })
    assert "CL101" in codes(r)


MOD_ATTR_DEFS = {
    "crdt_tpu/ops/a.py": '''
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(0,))
    def _step(x, y):
        return x + y
    ''',
    "crdt_tpu/ops/b.py": '''
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(1,))
    def _step(x, y):
        return x + y
    ''',
}


def test_cl101_module_attr_call_resolves_receiver_module():
    # `b._step(x, y)` must take b's argnums (donates y), not whichever
    # same-named def was scanned first — reading x stays clean, reading
    # y fires
    r = lint({
        **MOD_ATTR_DEFS,
        "crdt_tpu/models/c.py": '''
        from crdt_tpu.ops import b

        def caller(x, y):
            out = b._step(x, y)
            return x.sum() + y.sum() + out
        ''',
    })
    hits = [f for f in r.findings if f.code == "CL101"]
    assert len(hits) == 1 and "`y`" in hits[0].message


def test_cl101_module_attr_full_dotted_path_resolves():
    # plain `import crdt_tpu.ops.a` — the attribute chain spells the
    # real module path, so a's argnums (donates x) apply
    r = lint({
        **MOD_ATTR_DEFS,
        "crdt_tpu/models/c.py": '''
        import crdt_tpu.ops.a

        def caller(x, y):
            out = crdt_tpu.ops.a._step(x, y)
            return x.sum() + out
        ''',
    })
    hits = [f for f in r.findings if f.code == "CL101"]
    assert len(hits) == 1 and "`x`" in hits[0].message


def test_cl101_module_attr_without_donating_def_refuses_guess():
    # the receiver resolves to a module that defines NO donating
    # `_step` — another module's same-named argnums must not be lent
    r = lint({
        **MOD_ATTR_DEFS,
        "crdt_tpu/ops/plain.py": '''
        def _step(x, y):
            return x + y
        ''',
        "crdt_tpu/models/c.py": '''
        from crdt_tpu.ops import plain

        def caller(x, y):
            out = plain._step(x, y)
            return x.sum() + y.sum() + out
        ''',
    })
    assert "CL101" not in codes(r)


def test_write_baseline_preserves_justifications(tmp_path):
    # regenerating a baseline must MERGE: hand-written justifications
    # for still-live findings survive verbatim, only open findings get
    # TODO skeletons (the old CLI wrote open-findings-only, wiping the
    # whole ledger)
    r = lint({"crdt_tpu/models/x.py": VIOLATION})
    path = tmp_path / "baseline.json"
    write_baseline(str(path), r.findings)
    data = json.loads(path.read_text())
    data["entries"][0]["justification"] = "hand-written reason"
    path.write_text(json.dumps(data))
    base = load_baseline(str(path))

    # a second, new violation appears; the first is baselined
    two = VIOLATION + '''
    def upload2(arr):
        return jax.device_put(arr)
    '''
    r2 = lint({"crdt_tpu/models/x.py": two}, baseline=base)
    assert len(r2.findings) == 1 and len(r2.baselined) == 1
    # the __main__ --write-baseline flow: preserved = still-live
    # baseline entries, skeletons only for the open finding
    live = {f.fingerprint for f in r2.baselined}
    preserved = [e for fp, e in base.items() if fp in live]
    write_baseline(str(path), r2.findings, preserved)
    merged = load_baseline(str(path))
    assert len(merged) == 2
    justs = sorted(e["justification"] for e in merged.values())
    assert justs == ["TODO: justify or fix", "hand-written reason"]


def test_write_baseline_cli_merges_not_clobbers(tmp_path, capsys):
    """End-to-end through the CLI entry: pointing --write-baseline at
    the live baseline file must not wipe existing justifications."""
    from tools.crdtlint.__main__ import main

    src = tmp_path / "ops" / "bad.py"
    src.parent.mkdir()
    src.write_text(
        "import jax\n\n\ndef f(x):\n    return jax.device_put(x)\n"
    )
    bl = tmp_path / "bl.json"
    # generation 1: one skeleton; justify it by hand
    assert main([str(src), "--baseline", str(bl),
                 "--write-baseline", str(bl)]) == 0
    data = json.loads(bl.read_text())
    assert len(data["entries"]) == 1
    data["entries"][0]["justification"] = "hand-written reason"
    bl.write_text(json.dumps(data))
    # a second violation lands; regenerate against the live baseline
    src.write_text(
        src.read_text()
        + "\n\ndef g(x):\n    return jax.device_get(x)\n"
    )
    assert main([str(src), "--baseline", str(bl),
                 "--write-baseline", str(bl)]) == 0
    merged = load_baseline(str(bl))
    assert len(merged) == 2
    justs = sorted(e["justification"] for e in merged.values())
    assert justs == ["TODO: justify or fix", "hand-written reason"]
    # --no-baseline only changes reporting; combined with
    # --write-baseline it must STILL merge against the ledger, not
    # rewrite every live entry as a TODO skeleton
    assert main([str(src), "--baseline", str(bl), "--no-baseline",
                 "--write-baseline", str(bl)]) == 0
    remerged = load_baseline(str(bl))
    assert len(remerged) == 2
    justs = sorted(e["justification"] for e in remerged.values())
    assert justs == ["TODO: justify or fix", "hand-written reason"]
    capsys.readouterr()


# ---------------------------------------------------------------------------
# round 16: call-graph + CFG core


def test_callgraph_resolution_and_thread_reachability():
    """Cross-module strong resolution, weak method fan-out, thread
    roots, and the reachability closure the CL803 discovery rides."""
    from tools.crdtlint.callgraph import build_callgraph, STRONG
    from tools.crdtlint.core import Module

    mods = [
        Module("crdt_tpu/a.py", textwrap.dedent('''
            from crdt_tpu.b import helper
            import threading

            def start():
                t = threading.Thread(target=entry)
                t.start()

            def entry():
                helper()
        ''')),
        Module("crdt_tpu/b.py", textwrap.dedent('''
            def helper():
                pass
        ''')),
    ]
    cg = build_callgraph(mods)
    assert "crdt_tpu/a.py:entry" in cg.thread_roots
    assert "crdt_tpu/b.py:helper" in cg.thread_reachable
    edges = {(c.callee, c.confidence)
             for c in cg.edges.get("crdt_tpu/a.py:entry", ())}
    assert ("crdt_tpu/b.py:helper", STRONG) in edges


def test_callgraph_collision_links_all_candidates():
    """Two classes defining the same method name: an unresolvable
    receiver fans out to BOTH (weak edges) and the collision is
    counted — over-approximation is the right direction for
    reachability, and the stats make the guessing visible."""
    from tools.crdtlint.callgraph import build_callgraph, WEAK
    from tools.crdtlint.core import Module

    mods = [
        Module("crdt_tpu/a.py", textwrap.dedent('''
            class A:
                def run(self):
                    pass

            class B:
                def run(self):
                    pass

            def call(x):
                x.run()
        ''')),
    ]
    cg = build_callgraph(mods)
    callees = {c.callee for c in cg.edges.get("crdt_tpu/a.py:call", ())}
    assert callees == {"crdt_tpu/a.py:A.run", "crdt_tpu/a.py:B.run"}
    assert all(
        c.confidence == WEAK
        for c in cg.edges["crdt_tpu/a.py:call"]
    )
    assert cg.collisions >= 1
    assert cg.stats()["functions"] == 3


def test_callgraph_local_def_shadows_import():
    """A local def wins over a same-named def in another module —
    the donate checker's shadowing rule, now shared machinery."""
    from tools.crdtlint.callgraph import build_callgraph
    from tools.crdtlint.core import Module

    mods = [
        Module("crdt_tpu/a.py", textwrap.dedent('''
            def helper():
                pass

            def caller():
                helper()
        ''')),
        Module("crdt_tpu/b.py", textwrap.dedent('''
            def helper():
                pass
        ''')),
    ]
    cg = build_callgraph(mods)
    callees = {c.callee
               for c in cg.edges.get("crdt_tpu/a.py:caller", ())}
    assert callees == {"crdt_tpu/a.py:helper"}


def test_cfg_exception_edges():
    """The lite CFG's exception edges: a statement inside try lands
    in the handler; a finally is reached on both the normal and the
    unwinding path."""
    import ast as _ast

    from tools.crdtlint.cfg import CFG, EXIT, RAISE, every_path_hits

    fn = _ast.parse(textwrap.dedent('''
        def f(work, cleanup):
            try:
                work()
            finally:
                cleanup()
    ''')).body[0]
    cfg = CFG(fn)

    def hits_cleanup(st):
        return any(
            isinstance(n, _ast.Call) and getattr(n.func, "id", "")
            == "cleanup"
            for n in _ast.walk(st)
        )

    # every path — normal AND raising — passes through cleanup()
    missing = every_path_hits(
        cfg, cfg.entry, hits_cleanup, with_exc=True
    )
    assert missing is None

    fn2 = _ast.parse(textwrap.dedent('''
        def g(work, cleanup):
            work()
            cleanup()
    ''')).body[0]
    cfg2 = CFG(fn2)

    # without try/finally, work()'s exception edge skips cleanup
    missing = every_path_hits(
        cfg2, cfg2.entry, hits_cleanup, with_exc=True
    )
    assert missing == RAISE
    # ...but every NORMAL path still hits it
    assert every_path_hits(cfg2, cfg2.entry, hits_cleanup) is None


# ---------------------------------------------------------------------------
# CL7xx trace purity


def test_cl701_tracer_call_in_jitted_body():
    r = lint({"crdt_tpu/ops/x.py": '''
    import jax
    from crdt_tpu.obs.tracer import get_tracer

    @jax.jit
    def step(x):
        get_tracer().count("engine.ticks")
        return x
    '''})
    assert "CL701" in codes(r)


def test_cl701_interprocedural_through_helper():
    """The side effect sits one call away from the jit root — only
    the call-graph closure sees it."""
    r = lint({"crdt_tpu/ops/x.py": '''
    import jax

    def note(x):
        print("traced!", x)
        return x

    @jax.jit
    def step(x):
        return note(x)
    '''})
    assert "CL701" in codes(r)


def test_cl701_host_dispatcher_clean():
    """The same tracer call OUTSIDE any traced body is the sanctioned
    dispatcher pattern."""
    r = lint({"crdt_tpu/ops/x.py": '''
    import jax
    from crdt_tpu.obs.tracer import get_tracer

    @jax.jit
    def step(x):
        return x

    def dispatch(x):
        get_tracer().count("engine.ticks")
        return step(x)
    '''})
    assert "CL701" not in codes(r)


def test_cl702_env_read_in_lax_cond_branch():
    """The sv_deficit shape that motivated the fix: a nested def
    passed to lax.cond reads the env at trace time."""
    r = lint({"crdt_tpu/ops/x.py": '''
    import os
    import jax

    def outer(x):
        def a(v):
            if os.environ.get("CRDT_TPU_PALLAS"):
                return v
            return v + 1

        def b(v):
            return v

        return jax.lax.cond(x.sum() > 0, a, b, x)
    '''})
    assert "CL702" in codes(r)


def test_cl702_host_env_read_clean():
    r = lint({"crdt_tpu/ops/x.py": '''
    import os

    def mode():
        return os.environ.get("CRDT_TPU_PALLAS", "auto")
    '''})
    assert "CL702" not in codes(r)


def test_cl703_host_sync_in_traced_body():
    r = lint({"crdt_tpu/ops/x.py": '''
    import jax
    import numpy as np

    @jax.jit
    def step(x):
        h = np.asarray(x)
        return h.sum()
    '''})
    assert "CL703" in codes(r)


def test_cl704_captured_mutation_and_local_clean():
    r = lint({"crdt_tpu/ops/x.py": '''
    import jax

    _MEMO = {}

    @jax.jit
    def bad(x):
        _MEMO["x"] = x
        return x

    @jax.jit
    def good(x):
        local = {}
        local["x"] = x
        return x
    '''})
    found = [f for f in r.findings if f.code == "CL704"]
    assert len(found) == 1 and "bad" in found[0].symbol


def test_cl7xx_suppressed_and_baselined():
    src = '''
    import jax
    from crdt_tpu.obs.tracer import get_tracer

    @jax.jit
    def step(x):
        get_tracer().count("engine.ticks")  # crdtlint: disable=CL701
        return x
    '''
    r = lint({"crdt_tpu/ops/x.py": src})
    assert "CL701" not in codes(r)
    assert any(f.code == "CL701" for f in r.suppressed)
    # baselined: same snippet without the inline disable
    src2 = src.replace("  # crdtlint: disable=CL701", "")
    r2 = lint({"crdt_tpu/ops/x.py": src2})
    fp = next(f for f in r2.findings if f.code == "CL701").fingerprint
    r3 = lint({"crdt_tpu/ops/x.py": src2}, baseline={
        fp: {"fingerprint": fp, "justification": "pinned by test"}
    })
    assert "CL701" not in codes(r3)
    assert any(f.code == "CL701" for f in r3.baselined)


# ---------------------------------------------------------------------------
# CL8xx lock discipline


def test_cl801_lock_order_cycle_fires_and_ordered_clean():
    bad = {
        "crdt_tpu/a.py": '''
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
    '''}
    assert "CL801" in codes(lint(bad))
    good = {
        "crdt_tpu/a.py": '''
    import threading

    LOCK_A = threading.Lock()
    LOCK_B = threading.Lock()

    def ab():
        with LOCK_A:
            with LOCK_B:
                pass

    def ab2():
        with LOCK_A:
            with LOCK_B:
                pass
    '''}
    assert "CL801" not in codes(lint(good))


def test_cl801_interprocedural_cycle():
    """The inversion hides behind a call: f holds A and calls g,
    which takes B; h holds B and calls k, which takes A."""
    r = lint({"crdt_tpu/a.py": '''
    import threading

    LOCK_A = threading.Lock()
    LOCK_B = threading.Lock()

    def take_b():
        with LOCK_B:
            pass

    def take_a():
        with LOCK_A:
            pass

    def f():
        with LOCK_A:
            take_b()

    def h():
        with LOCK_B:
            take_a()
    '''})
    assert "CL801" in codes(r)


def test_cl801_lock_alias_suppresses_phantom_cycle():
    """`self._lock = other._lock` aliases the two identities: the
    apparent A->B / B->A inversion is one lock taken twice in one
    direction — no cycle."""
    r = lint({"crdt_tpu/a.py": '''
    import threading

    class Owner:
        def __init__(self):
            self._lock = threading.Lock()

    class Borrower:
        def __init__(self, owner):
            self._lock = owner._lock

        def locked_pair(self, owner):
            with self._lock:
                with owner._lock:
                    pass

        def locked_pair_rev(self, owner):
            with owner._lock:
                with self._lock:
                    pass
    '''})
    assert "CL801" not in codes(r)


def test_cl802_blocking_under_lock_and_outside_clean():
    bad = {"crdt_tpu/a.py": '''
    import subprocess
    import threading

    _BUILD_LOCK = threading.Lock()

    def build():
        with _BUILD_LOCK:
            subprocess.run(["make"])
    '''}
    assert "CL802" in codes(lint(bad))
    good = {"crdt_tpu/a.py": '''
    import subprocess
    import threading

    _BUILD_LOCK = threading.Lock()

    def build():
        subprocess.run(["make"])
        with _BUILD_LOCK:
            done = True
        return done
    '''}
    assert "CL802" not in codes(lint(good))


def test_cl802_interprocedural_blocking_callee():
    """The kv.py _load shape: the blocking call hides inside a
    helper invoked under the lock."""
    r = lint({"crdt_tpu/a.py": '''
    import subprocess
    import threading

    _lib_lock = threading.Lock()

    def _build_so():
        subprocess.run(["g++"])

    def _load():
        with _lib_lock:
            _build_so()
    '''})
    found = [f for f in r.findings if f.code == "CL802"]
    assert found and "via `_build_so`" in found[0].message


def test_cl803_thread_shared_guarded_field():
    src = '''
    import threading

    class Shared:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def bump(self):
            with self._lock:
                self.n += 1

        def reset(self):
            self.n = 0

    def worker():
        Shared().bump()

    def spawn():
        return threading.Thread(target=worker)
    '''
    r = lint({"crdt_tpu/models/x.py": src})
    found = [f for f in r.findings if f.code == "CL803"]
    assert len(found) == 1
    assert "reset" in found[0].symbol
    # consistent locking is clean
    src_good = src.replace(
        "        def reset(self):\n            self.n = 0",
        "        def reset(self):\n            with self._lock:\n"
        "                self.n = 0",
    )
    assert src_good != src
    r2 = lint({"crdt_tpu/models/x.py": src_good})
    assert "CL803" not in codes(r2)


def test_cl803_init_writes_exempt_and_unthreaded_clean():
    """__init__ writes don't count (object unshared), and a class no
    thread reaches is out of scope entirely."""
    r = lint({"crdt_tpu/models/x.py": '''
    import threading

    class NotShared:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def bump(self):
            with self._lock:
                self.n += 1

        def reset(self):
            self.n = 0
    '''})
    assert "CL803" not in codes(r)


# ---------------------------------------------------------------------------
# CL9xx async-handle / paired-protocol discipline


def test_cl901_dropped_handle_fires():
    r = lint({"crdt_tpu/models/x.py": '''
    from crdt_tpu.ops import packed

    def leak(plan):
        h = packed.converge_async(plan)
        return 0
    '''})
    assert "CL901" in codes(r)


def test_cl901_branch_without_fetch_fires():
    r = lint({"crdt_tpu/models/x.py": '''
    from crdt_tpu.ops import packed

    def maybe(plan, flag):
        h = packed.converge_async(plan)
        if flag:
            return packed.converge_fetch(h)
        return None
    '''})
    assert "CL901" in codes(r)


def test_cl901_all_paths_consumed_clean():
    r = lint({"crdt_tpu/models/x.py": '''
    from crdt_tpu.ops import packed

    def both(plan, flag):
        h = packed.converge_async(plan)
        if flag:
            return packed.converge_fetch(h)
        return packed.converge_fetch(h)

    def queued(plan, q):
        h = packed.converge_async(plan)
        q.put((h, 1))

    def returned(plan):
        return packed.converge_async(plan)
    '''})
    assert "CL901" not in codes(r)


def test_cl901_loop_rebind_without_consume_fires():
    r = lint({"crdt_tpu/models/x.py": '''
    from crdt_tpu.ops import packed

    def spin(plans):
        for p in plans:
            h = packed.converge_async(p)
        return packed.converge_fetch(h)
    '''})
    assert "CL901" in codes(r)


def test_cl901_bare_expression_drop_fires():
    r = lint({"crdt_tpu/models/x.py": '''
    from crdt_tpu.ops import packed

    def fire_and_forget(plan):
        packed.converge_async(plan)
    '''})
    found = [f for f in r.findings if f.code == "CL901"]
    assert found and "drop" in found[0].symbol


def test_cl902_exception_edge_skips_closer():
    r = lint({"crdt_tpu/obs/x.py": '''
    import jax

    def capture(log_dir, work):
        jax.profiler.start_trace(log_dir)
        work()
        jax.profiler.stop_trace()
    '''})
    found = [f for f in r.findings if f.code == "CL902"]
    assert found and "exception" in found[0].symbol


def test_cl902_finally_closes_clean():
    r = lint({"crdt_tpu/obs/x.py": '''
    import jax

    def capture(log_dir, work):
        jax.profiler.start_trace(log_dir)
        try:
            work()
        finally:
            jax.profiler.stop_trace()
    '''})
    assert "CL902" not in codes(r)


def test_cl902_alias_resolution():
    """The profiling.py shape: locally aliased opener/closer."""
    r = lint({"crdt_tpu/obs/x.py": '''
    import jax

    def capture(log_dir, work):
        start = jax.profiler.start_trace
        stop = jax.profiler.stop_trace
        start(log_dir)
        work()
        stop()
    '''})
    assert "CL902" in codes(r)
    r2 = lint({"crdt_tpu/obs/x.py": '''
    import jax

    def capture(log_dir, work):
        start = jax.profiler.start_trace
        stop = jax.profiler.stop_trace
        start(log_dir)
        try:
            work()
        finally:
            stop()
    '''})
    assert "CL902" not in codes(r2)


def test_cl902_protocol_object_exempt():
    """install/uninstall pairs on one class are the context-manager
    discipline, not a leak."""
    r = lint({"crdt_tpu/guard/x.py": '''
    from crdt_tpu.ops.device import set_device_fault_hook

    class Plan:
        def install(self):
            self._old = set_device_fault_hook(self)
            return self

        def uninstall(self):
            set_device_fault_hook(self._old)
    '''})
    assert "CL902" not in codes(r)


def test_cl902_bare_acquire_without_release_fires():
    r = lint({"crdt_tpu/models/x.py": '''
    def f(my_lock, work):
        my_lock.acquire()
        work()
        my_lock.release()
    '''})
    found = [f for f in r.findings if f.code == "CL902"]
    assert found
    r2 = lint({"crdt_tpu/models/x.py": '''
    def f(my_lock, work):
        my_lock.acquire()
        try:
            work()
        finally:
            my_lock.release()
    '''})
    assert "CL902" not in codes(r2)


# ---------------------------------------------------------------------------
# round-16 review regressions (each was a demonstrated failure)


def test_cl902_specific_except_with_finally_clean():
    """Review finding: a raise inside an except handler must route
    through the finally (which holds the closer) — the canonical
    close-in-finally-with-specific-except pattern is NOT a leak."""
    r = lint({"crdt_tpu/models/x.py": '''
    def f(my_lock, work, handle):
        my_lock.acquire()
        try:
            work()
        except ValueError:
            handle()
        finally:
            my_lock.release()
    '''})
    assert "CL902" not in codes(r)


def test_cl7xx_partial_shard_map_body_is_traced():
    """Review finding: @partial(shard_map, ...) — the repo's dominant
    traced-step shape — must join the traced set like
    @partial(jax.jit, ...)."""
    r = lint({"crdt_tpu/parallel/x.py": '''
    import os
    from functools import partial

    from jax import shard_map

    @partial(shard_map, mesh=None)
    def step(x):
        if os.environ.get("CRDT_TPU_PALLAS"):
            return x
        return x + 1
    '''})
    assert "CL702" in codes(r)


def test_reach_closure_complete_through_call_cycles():
    """Review finding: mutually recursive helpers must not poison the
    closure memo — A<->B with B->D has D in BOTH closures (a blocking
    call in D behind the cycle must stay visible to CL801/CL802)."""
    from tools.crdtlint.callgraph import build_callgraph, reach_closure
    from tools.crdtlint.core import Module

    mods = [Module("crdt_tpu/a.py", textwrap.dedent('''
        def a():
            b()

        def b():
            a()
            d()

        def d():
            pass
    '''))]
    cg = build_callgraph(mods)
    memo = {}
    ca = reach_closure(cg, "crdt_tpu/a.py:a", strong_only=True,
                       memo=memo)
    cb = reach_closure(cg, "crdt_tpu/a.py:b", strong_only=True,
                       memo=memo)
    assert "crdt_tpu/a.py:d" in ca and "crdt_tpu/a.py:d" in cb
    assert "crdt_tpu/a.py:a" in ca  # cyclic: members reach themselves


def test_cl802_blocking_behind_mutual_recursion():
    """End-to-end: the blocking primitive sits behind a recursive
    helper pair under the lock — the SCC closure must surface it."""
    r = lint({"crdt_tpu/a.py": '''
    import subprocess
    import threading

    _build_lock = threading.Lock()

    def ping(n):
        if n:
            pong(n - 1)

    def pong(n):
        ping(n)
        subprocess.run(["make"])

    def build():
        with _build_lock:
            ping(3)
    '''})
    assert "CL802" in codes(r)


def test_cl902_return_inside_try_finally_clean():
    """Review round 2: return/break inside the protected region must
    route through the finally — `acquire(); try: return f() finally:
    release()` is the RECOMMENDED pattern, not a leak."""
    r = lint({"crdt_tpu/models/x.py": '''
    def ret_form(my_lock, work):
        my_lock.acquire()
        try:
            return work()
        finally:
            my_lock.release()

    def brk_form(my_lock, items):
        for it in items:
            my_lock.acquire()
            try:
                if it:
                    break
            finally:
                my_lock.release()
    '''})
    assert "CL902" not in codes(r)


def test_callgraph_nested_class_does_not_shadow_toplevel():
    """Review round 2: a class defined inside a function must keep
    the enclosing qual prefix — previously its methods overwrote a
    same-named top-level class's methods in the graph, a silent
    blind spot for every downstream checker."""
    from tools.crdtlint.callgraph import build_callgraph
    from tools.crdtlint.core import Module

    mods = [Module("crdt_tpu/x.py", textwrap.dedent('''
        class A:
            def f(self):
                pass

        def factory():
            class A:
                def f(self):
                    pass
            return A
    '''))]
    cg = build_callgraph(mods)
    assert "crdt_tpu/x.py:A.f" in cg.funcs
    assert "crdt_tpu/x.py:factory.<locals>.A.f" in cg.funcs


# ---------------------------------------------------------------------------
# CL1001-CL1004 wire taint (round 17)


def test_cl1001_tainted_index_fires():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        return d.data[n]
    '''})
    assert "CL1001" in codes(r)


def test_cl1001_tainted_slice_bound_fires():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d, buf):
        off = d.read_var_uint()
        return buf[2:off]
    '''})
    assert "CL1001" in codes(r)


def test_cl1001_comparison_guard_sanitizes():
    """A comparison-guarded branch on the tainted value kills the
    taint past the guard (the CFG-aware sanitization edge)."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d, buf):
        n = d.read_var_uint()
        if n >= len(buf):
            raise ValueError("offset past buffer")
        return buf[n]
    '''})
    assert codes(r) == []


def test_cl1001_use_before_guard_still_fires():
    """The guard kills taint only downstream: an index BEFORE the
    comparison is still hostile."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d, buf):
        n = d.read_var_uint()
        first = buf[n]
        if n >= len(buf):
            raise ValueError("late")
        return first
    '''})
    assert "CL1001" in codes(r)


def test_cl1001_min_clamp_sanitizes():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d, buf):
        n = min(d.read_var_uint(), len(buf) - 1)
        return buf[n]
    '''})
    assert codes(r) == []


def test_cl1001_declared_sanitizer_helper_kills_taint():
    """A `# crdtlint: sanitizes` helper owns the admission check:
    its result is clean at every caller."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def _read_bounded(d):  # crdtlint: sanitizes
        v = d.read_var_uint()
        if v >= (1 << 31):
            raise ValueError("bound")
        return v

    def decode_x(d, buf):
        n = _read_bounded(d)
        return buf[n]
    '''})
    assert "CL1001" not in codes(r)


def test_cl1001_rebind_from_clean_value_kills_taint():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d, buf):
        n = d.read_var_uint()
        n = 3
        return buf[n]
    '''})
    assert codes(r) == []


def test_cl1001_out_of_scope_module_clean():
    """The taint pass scopes to codec/storage/net — the same snippet
    in ops/ is some kernel's business, not the wire fence's."""
    r = lint({"crdt_tpu/ops/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        return d.data[n]
    '''})
    assert codes(r) == []


def test_cl1001_suppressed_inline():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        return d.data[n]  # crdtlint: disable=CL1001
    '''})
    assert "CL1001" not in codes(r)
    assert any(f.code == "CL1001" for f in r.suppressed)


def test_cl1001_baselined():
    files = {"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        return d.data[n]
    '''}
    first = lint(files)
    (f,) = [f for f in first.findings if f.code == "CL1001"]
    r = lint(files, baseline={f.fingerprint: {
        "fingerprint": f.fingerprint,
        "justification": "trusted test fixture path",
    }})
    assert "CL1001" not in codes(r)
    assert any(f2.code == "CL1001" for f2 in r.baselined)


def test_cl1002_tainted_allocation_fires():
    for alloc in ("bytearray(n)", "np.zeros(n)", "list(range(n))",
                  "b'x' * n", "[0] * n"):
        r = lint({"crdt_tpu/codec/x.py": f'''
    import numpy as np

    def decode_x(d):
        n = d.read_var_uint()
        return {alloc}
    '''})
        assert "CL1002" in codes(r), alloc


def test_cl1002_buffer_guard_sanitizes():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        if d.pos + n > len(d.data):
            raise ValueError("tail")
        return bytearray(n)
    '''})
    assert "CL1002" not in codes(r)


def test_cl1002_tainted_attribute_store_propagates():
    """Attribute stores on decoder objects carry taint (the
    `self.declared_len = n` shape)."""
    r = lint({"crdt_tpu/codec/x.py": '''
    class D:
        def read_header(self, d):
            self.declared = d.read_var_uint()
            return bytearray(self.declared)
    '''})
    assert "CL1002" in codes(r)


def test_cl1003_unconsuming_loop_fires_and_reader_loop_clean():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        out = []
        for _ in range(n):
            out.append(1)
        return out
    '''})
    assert "CL1003" in codes(r)
    # a body that reads the wire each iteration is buffer-capped
    r2 = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        out = []
        for _ in range(n):
            out.append(d.read_uint8())
        return out
    '''})
    assert "CL1003" not in codes(r2)


def test_cl1003_budget_check_in_body_sanitizes():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d, budget):
        n = d.read_var_uint()
        total = 0
        out = []
        for _ in range(n):
            total += 1
            if total > budget:
                raise ValueError("budget")
            out.append(1)
        return out
    '''})
    assert "CL1003" not in codes(r)


def test_cl1003_comprehension_bound_fires():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        return [0 for _ in range(n)]
    '''})
    assert "CL1003" in codes(r)
    r2 = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        return [d.read_any() for _ in range(n)]
    '''})
    assert "CL1003" not in codes(r2)


def test_cl1004_staging_crossing_fires_and_guarded_clean():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d, cols):
        n = d.read_var_uint()
        return stage(cols, rows=n)
    '''})
    assert "CL1004" in codes(r)
    r2 = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d, cols):
        n = d.read_var_uint()
        if n >= (1 << 40):
            raise ValueError("clock bound")
        return stage(cols, rows=n)
    '''})
    assert "CL1004" not in codes(r2)


def test_taints_directive_marks_custom_source():
    """`# crdtlint: taints` on a def makes its result hostile at
    every caller — the kv/udp seam annotation workflow."""
    r = lint({"crdt_tpu/storage/x.py": '''
    def fetch_blob(h):  # crdtlint: taints
        return h.raw()

    def index_of(h, table):
        n = fetch_blob(h)
        return table[n]
    '''})
    assert "CL1001" in codes(r)


def test_return_taint_closes_over_wrappers():
    """A wrapper returning a source's result is itself a source for
    its callers (the interprocedural fixpoint over STRONG edges)."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def _wrap(d):
        return d.read_var_uint()

    def _wrap2(d):
        return _wrap(d) + 1

    def decode_x(d, buf):
        n = _wrap2(d)
        return buf[n]
    '''})
    assert "CL1001" in codes(r)


def test_kv_receiver_results_are_tainted():
    """kv get/scan results taint without a directive when the
    receiver spelling names the store."""
    r = lint({"crdt_tpu/storage/x.py": '''
    def last_seq(kv, table):
        raw = kv.get(b"seq")
        return table[raw]
    '''})
    assert "CL1001" in codes(r)
    # a plain dict .get is NOT a kv source
    r2 = lint({"crdt_tpu/storage/x.py": '''
    def last_seq(cache, table):
        raw = cache.get(b"seq")
        return table[raw]
    '''})
    assert "CL1001" not in codes(r2)


# ---------------------------------------------------------------------------
# CL1101/CL1102 decode-allocation contracts (round 17)


def test_cl1101_absolute_guard_fires_buffer_guard_clean():
    weak = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        if n > (1 << 31):
            raise ValueError("cap")
        return bytearray(n)
    '''})
    assert "CL1101" in codes(weak)
    assert "CL1002" not in codes(weak)  # the guard did kill the taint
    anchored = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        if d.pos + n > len(d.data):
            raise ValueError("tail")
        return bytearray(n)
    '''})
    assert "CL1101" not in codes(anchored)


def test_cl1101_budget_variable_counts_as_anchored():
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d, data):
        budget = max(1 << 20, 4096 * len(data))
        n = d.read_var_uint()
        if n > budget:
            raise ValueError("budget")
        return bytearray(n)
    '''})
    assert "CL1101" not in codes(r)


def test_cl1101_only_on_decode_entries():
    """A non-decode-named function with the same weak guard is
    CL1002-country (when unguarded) or clean — the stricter
    buffer-anchored standard applies to decode entries only."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def helper_alloc(d):
        n = d.read_var_uint()
        if n > (1 << 31):
            raise ValueError("cap")
        return bytearray(n)
    '''})
    assert "CL1101" not in codes(r)


def test_cl1101_sanitizer_params_held_to_contract():
    """A `# crdtlint: sanitizes` helper's PARAMETERS are treated as
    hostile — the helper claims to own the admission check, so an
    absolute-bound-only fence inside it is a contract violation."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def read_block(d, n):  # crdtlint: sanitizes
        if n > (1 << 20):
            raise ValueError("cap")
        return bytearray(n)
    '''})
    assert "CL1101" in codes(r)


def test_cl1101_suppressed_and_baselined():
    src = {"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        if n > (1 << 31):
            raise ValueError("cap")
        # crdtlint: disable=CL1101
        return bytearray(n)
    '''}
    r = lint(src)
    assert "CL1101" not in codes(r)
    assert any(f.code == "CL1101" for f in r.suppressed)
    clean_src = {"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        if n > (1 << 31):
            raise ValueError("cap")
        return bytearray(n)
    '''}
    first = lint(clean_src)
    (f,) = [f for f in first.findings if f.code == "CL1101"]
    r2 = lint(clean_src, baseline={f.fingerprint: {
        "fingerprint": f.fingerprint,
        "justification": "absolute cap is the doc-level contract here",
    }})
    assert "CL1101" not in codes(r2)
    assert any(f2.code == "CL1101" for f2 in r2.baselined)


def test_cl1102_helper_raise_fires():
    r = lint({"crdt_tpu/codec/x.py": '''
    def _helper(b):
        raise KeyError("boom")

    def decode_x(b):
        return _helper(b)
    '''})
    assert "CL1102" in codes(r)


def test_cl1102_valueerror_and_bare_reraise_clean():
    r = lint({"crdt_tpu/codec/x.py": '''
    def _helper(b):
        if not b:
            raise ValueError("empty")
        try:
            return b[0]
        except IndexError:
            raise
    '''  '''
    def decode_x(b):
        return _helper(b)
    '''})
    assert "CL1102" not in codes(r)


def test_cl1102_decode_named_helper_left_to_cl302():
    """A helper that is itself decode-named is CL302's lexical job —
    CL1102 must not double-report it."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def _read_part(b):
        raise KeyError("boom")

    def decode_x(b):
        return _read_part(b)
    '''})
    assert "CL302" in codes(r)
    assert "CL1102" not in codes(r)


def test_cl1102_cross_module_strong_edge():
    r = lint({
        "crdt_tpu/codec/util.py": '''
    def unpack_head(b):
        raise AssertionError("no head")
    ''',
        "crdt_tpu/codec/x.py": '''
    from crdt_tpu.codec.util import unpack_head

    def decode_x(b):
        return unpack_head(b)
    ''',
    })
    found = [f for f in r.findings if f.code == "CL1102"]
    assert found and found[0].path == "crdt_tpu/codec/util.py"


def test_cl1102_weak_edge_never_convicts():
    """A by-method-name (weak) edge must not drag a helper into the
    decode closure — attribute calls on unknown receivers stay out."""
    r = lint({"crdt_tpu/codec/x.py": '''
    class Other:
        def finish(self):
            raise RuntimeError("not mine")

    def decode_x(b, obj):
        return obj.finish()
    '''})
    assert "CL1102" not in codes(r)


def test_cl1102_two_entries_one_finding():
    """Two decode entries reaching the same raise produce ONE
    finding (stable fingerprint for the baseline ledger)."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def _helper(b):
        raise KeyError("boom")

    def decode_x(b):
        return _helper(b)

    def decode_y(b):
        return _helper(b)
    '''})
    assert [f.code for f in r.findings].count("CL1102") == 1


def test_cl1102_suppressed_at_raise_site():
    r = lint({"crdt_tpu/codec/x.py": '''
    def _helper(b):
        raise KeyError("boom")  # crdtlint: disable=CL1102

    def decode_x(b):
        return _helper(b)
    '''})
    assert "CL1102" not in codes(r)
    assert any(f.code == "CL1102" for f in r.suppressed)


def test_open_by_family_buckets_four_digit_codes():
    """CL1001 counts under cl10 (wire taint), never under the donate
    family cl1 — the round-17 family split in LintResult."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def decode_x(d):
        n = d.read_var_uint()
        return d.data[n]
    '''})
    fams = r.open_by_family()
    assert fams["cl10"] >= 1
    assert fams["cl1"] == 0
    assert "cl11" in fams


# every round-17 code: the positive snippet, its inline-suppressed
# twin, and a baseline round-trip — (code, clean lint must fire it;
# the marked line carries the disable comment in the suppressed twin)
_R17_POSITIVES = {
    "CL1001": ("crdt_tpu/codec/x.py", '''
    def decode_x(d):
        n = d.read_var_uint()
        return d.data[n]{MARK}
    '''),
    "CL1002": ("crdt_tpu/codec/x.py", '''
    def decode_x(d):
        n = d.read_var_uint()
        return bytearray(n){MARK}
    '''),
    "CL1003": ("crdt_tpu/codec/x.py", '''
    def decode_x(d):
        n = d.read_var_uint()
        out = []
        for _ in range(n):{MARK}
            out.append(1)
        return out
    '''),
    "CL1004": ("crdt_tpu/codec/x.py", '''
    def decode_x(d, cols):
        n = d.read_var_uint()
        return stage(cols, rows=n){MARK}
    '''),
    "CL1101": ("crdt_tpu/codec/x.py", '''
    def decode_x(d):
        n = d.read_var_uint()
        if n > (1 << 31):
            raise ValueError("cap")
        return bytearray(n){MARK}
    '''),
    "CL1102": ("crdt_tpu/codec/x.py", '''
    def _helper(b):
        raise KeyError("boom"){MARK}

    def decode_x(b):
        return _helper(b)
    '''),
}


@pytest.mark.parametrize("code", sorted(_R17_POSITIVES))
def test_r17_code_suppressed_and_baselined_roundtrip(code):
    path, template = _R17_POSITIVES[code]
    plain = template.replace("{MARK}", "")
    r = lint({path: plain})
    assert code in codes(r), f"{code} positive snippet does not fire"
    # inline suppression on the finding's line
    marked = template.replace(
        "{MARK}", f"  # crdtlint: disable={code}"
    )
    r_supp = lint({path: marked})
    assert code not in codes(r_supp)
    assert any(f.code == code for f in r_supp.suppressed), code
    # baseline round-trip on the plain variant's fingerprint
    (f,) = [f for f in r.findings if f.code == code]
    r_base = lint({path: plain}, baseline={f.fingerprint: {
        "fingerprint": f.fingerprint,
        "justification": "intentional for this synthetic case",
    }})
    assert code not in codes(r_base)
    assert any(f2.code == code for f2 in r_base.baselined), code


def test_cl1102_reraise_of_bound_valueerror_clean():
    """Review fix: `except ValueError as e: raise e` preserves the
    contract — the checker must report the HANDLER's type, never the
    variable name."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def _helper(b):
        try:
            return b[0]
        except ValueError as e:
            raise e

    def decode_x(b):
        return _helper(b)
    '''})
    assert "CL1102" not in codes(r)


def test_cl1102_reraise_of_bound_foreign_type_fires():
    r = lint({"crdt_tpu/codec/x.py": '''
    def _helper(b):
        try:
            return b[0]
        except KeyError as e:
            raise e

    def decode_x(b):
        return _helper(b)
    '''})
    found = [f for f in r.findings if f.code == "CL1102"]
    assert found and "KeyError" in found[0].message


def test_cl1102_unresolvable_variable_raise_stays_silent():
    """A constructed exception variable cannot be traced — the
    conservative direction is silence, never invention."""
    r = lint({"crdt_tpu/codec/x.py": '''
    def _helper(kind):
        exc = RuntimeError("x") if kind else ValueError("y")
        raise exc

    def decode_x(b):
        return _helper(b)
    '''})
    assert "CL1102" not in codes(r)


def test_cl1004_strong_resolved_ops_callee_fires():
    """Review fix: a STRONG-resolved callee under crdt_tpu/ops/ is a
    staging sink even when its name is not a hard-coded stage tail —
    the ops candidate index makes the documented rule real."""
    r = lint({
        "crdt_tpu/ops/packer.py": '''
    def pack_columns(rows, cols):
        return rows
    ''',
        "crdt_tpu/codec/x.py": '''
    from crdt_tpu.ops.packer import pack_columns

    def decode_x(d, cols):
        n = d.read_var_uint()
        return pack_columns(n, cols)
    ''',
    })
    found = [f for f in r.findings if f.code == "CL1004"]
    assert found and found[0].path == "crdt_tpu/codec/x.py"
