"""The process heap's allocator policy (crdt_tpu/utils/heap.py).

``mallopt`` is process-wide, so every case runs in a fresh interpreter
that imports the package, probes, and prints one JSON line.
"""

import ctypes
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIB = 1 << 20


def _glibc() -> bool:
    try:
        libc = ctypes.CDLL(None)
        libc.gnu_get_libc_version
        libc.mallinfo2
    except (OSError, AttributeError, TypeError):
        return False
    return True


glibc_only = pytest.mark.skipif(not _glibc(),
                                reason="glibc's malloc (mallinfo2) only")

# glibc's heap counters, and one malloc probed for a mapped chunk
MALLINFO = """
import ctypes
class Mallinfo2(ctypes.Structure):
    _fields_ = [(f, ctypes.c_size_t) for f in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]
libc = ctypes.CDLL(None)
libc.mallinfo2.restype = Mallinfo2
libc.malloc.restype = ctypes.c_void_p
libc.malloc.argtypes = [ctypes.c_size_t]
libc.free.argtypes = [ctypes.c_void_p]
def mapped(size):
    h = libc.mallinfo2().hblks
    p = libc.malloc(size)
    got = libc.mallinfo2().hblks - h
    libc.free(p)
    return got
"""

# a stand-in for the C library, installed before the package loads:
# ``glibc`` says whether it has glibc's entry points, ``ret`` is what
# its mallopt answers; every mallopt call is recorded
FAKE_LIBC = """
import ctypes
calls = []
class _Mallopt:
    def __call__(self, param, value):
        calls.append([param, value])
        return RET
class _Libc:
    def __init__(self):
        if GLIBC:
            self.gnu_get_libc_version = lambda: b"2.36"
            self.mallopt = _Mallopt()
    def __getattr__(self, name):
        raise AttributeError(name)
_real_cdll = ctypes.CDLL
ctypes.CDLL = lambda name=None, *a, **k: (
    _Libc() if name is None else _real_cdll(name, *a, **k))
"""


def probe(body: str, prelude: str = "", env=None) -> dict:
    """Run ``prelude``, import the package, run ``body`` (which fills
    ``out``) in a fresh interpreter; return ``out``."""
    script = "\n".join([
        textwrap.dedent(prelude),
        "import json",
        "import crdt_tpu",
        "from crdt_tpu.utils import heap",
        "out = {}",
        textwrap.dedent(body),
        "print(json.dumps(out))",
    ])
    full = {k: v for k, v in os.environ.items()
            if not k.startswith("MALLOC_") and k != "GLIBC_TUNABLES"}
    full.update(PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=1")
    full.update(env or {})
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=full,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@glibc_only
def test_import_pins_so_a_large_malloc_comes_from_the_heap():
    out = probe(MALLINFO + """
out["mapped"] = mapped(8 * 1024 * 1024)  # before any call of our own
out["state"] = heap.pin_heap()
""")
    assert out == {"mapped": 0, "state": "pinned"}


@glibc_only
@pytest.mark.parametrize("env, state, mapped, kept", [
    ({}, "pinned", 0, True),
    # glibc's own top pad from the environment: the package leaves the
    # thresholds alone, and glibc's fixed 128 KiB maps every array
    ({"MALLOC_TOP_PAD_": "131072"}, "environment", 40, False),
], ids=["pinned", "environment"])
def test_transient_arrays_grow_the_arena_and_stay(env, state, mapped, kept):
    out = probe(MALLINFO + """
import numpy as np
m0 = libc.mallinfo2()
arrays = [np.ones(1 << 20, np.uint8) for _ in range(40)]
m1 = libc.mallinfo2()
del arrays
m2 = libc.mallinfo2()
out["state"] = heap.pin_heap()
out["hblks"] = m1.hblks - m0.hblks
out["grown"] = m1.arena - m0.arena
out["kept"] = m2.arena - m0.arena
""", env=env)
    assert out["state"] == state
    assert out["hblks"] == mapped
    if kept:
        # 40 MiB from the heap (some may reuse free heap already there),
        # and none of it trimmed: 64 MiB is the trim threshold
        assert 36 * MIB <= out["grown"] <= 48 * MIB
        assert out["kept"] == out["grown"]
    else:
        assert out["grown"] < 4 * MIB


@glibc_only
def test_glibc_environment_is_left_alone():
    # MALLOC_TRIM_THRESHOLD_ alone fixes glibc's mmap threshold at its
    # 128 KiB start: an 8 MiB malloc still maps, so no mallopt was made
    out = probe(MALLINFO + """
out["state"] = heap.pin_heap()
out["mapped"] = mapped(8 * 1024 * 1024)
""", env={"MALLOC_TRIM_THRESHOLD_": str(64 * MIB)})
    assert out == {"state": "environment", "mapped": 1}


@pytest.mark.parametrize("env", [
    {"MALLOC_MMAP_THRESHOLD_": str(4 * MIB)},
    {"MALLOC_TRIM_THRESHOLD_": str(64 * MIB)},
    {"MALLOC_TOP_PAD_": "131072"},
    {"GLIBC_TUNABLES": "glibc.malloc.trim_threshold=67108864"},
], ids=["mmap", "trim", "top_pad", "tunables"])
def test_glibc_settings_mean_no_mallopt_call(env):
    out = probe("""
out["state"] = heap.pin_heap()
out["calls"] = calls
""", prelude="GLIBC, RET = True, 1\n" + FAKE_LIBC, env=env)
    assert out == {"state": "environment", "calls": []}


@pytest.mark.parametrize("glibc, ret, calls", [
    (False, 1, []),  # no glibc: nothing to call
    # glibc refuses the mmap threshold: the trim threshold is not set,
    # since one threshold alone is worse than glibc's default
    (True, 0, [[-3, 32 * MIB]]),
], ids=["no_glibc", "refused"])
def test_unavailable_mallopt_pins_nothing_and_raises_nothing(glibc, ret,
                                                              calls):
    out = probe("""
out["state"] = heap.pin_heap()
out["calls"] = calls
""", prelude=f"GLIBC, RET = {glibc}, {ret}\n" + FAKE_LIBC)
    assert out == {"state": "unavailable", "calls": calls}


def test_both_thresholds_set_once_per_process():
    out = probe("""
import importlib
out["states"] = [heap.pin_heap(), heap.pin_heap(),
                 importlib.reload(crdt_tpu) and heap.pin_heap()]
out["calls"] = calls
""", prelude="GLIBC, RET = True, 1\n" + FAKE_LIBC)
    assert out == {"states": ["pinned"] * 3,
                   "calls": [[-3, 32 * MIB], [-1, 64 * MIB]]}


def test_one_replay_counts_the_policy_once():
    out = probe("""
import jax
jax.config.update("jax_enable_x64", True)
import workloads
from crdt_tpu.models.replay import replay_trace
from crdt_tpu.obs.tracer import Tracer, set_tracer
tr = set_tracer(Tracer(enabled=True))
res = replay_trace(workloads.build_trace(4, 8, seed=3))
out["n_ops"] = res.n_ops
out["policy"] = tr.counters("heap.policy")
""")
    assert out["n_ops"] == 32
    assert out["policy"] == {'heap.policy{state="pinned"}': 1}


def test_each_server_tick_counts_the_policy():
    out = probe("""
from crdt_tpu.models.multidoc import MultiDocServer
from crdt_tpu.obs.tracer import Tracer, set_tracer
tr = set_tracer(Tracer(enabled=True))
srv = MultiDocServer()
out["built"] = tr.counters("heap.policy")
srv.tick()
srv.tick()
out["ticked"] = tr.counters("heap.policy")
""", env={"MALLOC_TOP_PAD_": "131072"})
    assert out == {"built": {},
                   "ticked": {'heap.policy{state="environment"}': 2}}
