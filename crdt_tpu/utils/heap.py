"""The process heap's allocator policy: glibc's two thresholds, fixed.

glibc's malloc serves a request above its mmap threshold with a fresh
mapping and trims free memory above its trim threshold from the heap
top. Both start low (128 KiB) and move: each freed mapped chunk larger
than the mmap threshold raises it to that chunk's size, up to 32 MiB on
64-bit, and the trim threshold follows at twice that. So a process's
thresholds depend on its history. One that compiles frees XLA's large
buffers and ends at 32/64 MiB; one that reads every program from the
persistent compilation cache stays near 4-6 MiB, and then every
transient buffer of a replay above that is mapped and unmapped, and the
heap top is trimmed after each replay and faulted back in (zero-filled
by the kernel) by the next.

:func:`pin_heap` fixes both thresholds at glibc's own ceilings, the
state a compiling process reaches by itself, so every process runs in
it from the package's first large allocation. Setting either threshold
turns glibc's dynamic adjustment off for both, and either one alone is
worse than the default, so both are set or neither. glibc's own
environment settings (``MALLOC_MMAP_THRESHOLD_``,
``MALLOC_TRIM_THRESHOLD_``, ``MALLOC_TOP_PAD_``, or the same tunables in
``GLIBC_TUNABLES``) are an operator's choice and are left alone.

The cost is memory: up to 64 MiB of free heap top kept per arena, and a
resident set that stays at the process's high-water mark.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

M_TRIM_THRESHOLD = -1  # mallopt parameters, <malloc.h>
M_MMAP_THRESHOLD = -3
MMAP_THRESHOLD = 32 << 20  # glibc's ceiling for the dynamic threshold (64-bit)
TRIM_THRESHOLD = 2 * MMAP_THRESHOLD  # glibc's own rule for the trim threshold

_GLIBC_ENV = ("MALLOC_MMAP_THRESHOLD_", "MALLOC_TRIM_THRESHOLD_",
              "MALLOC_TOP_PAD_")
_GLIBC_TUNABLES = ("glibc.malloc.mmap_threshold",
                   "glibc.malloc.trim_threshold", "glibc.malloc.top_pad")

_state: Optional[str] = None


def pin_heap() -> str:
    """Fix glibc's mmap and trim thresholds, once per process (the
    package's ``__init__`` calls it). Returns, and keeps for
    :func:`count_policy`, ``"pinned"``, ``"environment"`` (glibc's own
    settings are present and left alone) or ``"unavailable"`` (no glibc
    ``mallopt``, or it refused)."""
    global _state
    if _state is None:
        _state = _pin()
    return _state


def count_policy(tracer) -> None:
    """Count one unit of work (a replay, a server tick) under the
    process's policy: ``heap.policy{state=...}``. Reads the kept result
    and never calls ``mallopt``."""
    tracer.count("heap.policy", labels={"state": _state})


def _pin() -> str:
    tunables = os.environ.get("GLIBC_TUNABLES", "")
    if (any(k in os.environ for k in _GLIBC_ENV)
            or any(t in tunables for t in _GLIBC_TUNABLES)):
        return "environment"
    try:
        libc = ctypes.CDLL(None)
        libc.gnu_get_libc_version  # glibc, whose mallopt takes these
        mallopt = libc.mallopt
    except (OSError, AttributeError, TypeError):
        return "unavailable"
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    # the mmap threshold first: it is the one glibc may refuse, and a
    # refusal leaves the dynamic adjustment on, so nothing has changed
    if not mallopt(M_MMAP_THRESHOLD, MMAP_THRESHOLD):
        return "unavailable"
    if not mallopt(M_TRIM_THRESHOLD, TRIM_THRESHOLD):
        return "unavailable"
    return "pinned"
