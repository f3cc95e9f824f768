"""Shared device-side helpers: ID packing, lexsort, dense ranks.

Conventions for all kernels in this package:

- Inputs are flat int32/int64/bool arrays of equal length N (static
  shape; callers pad with ``valid=False`` rows).
- Item IDs (client, clock) are packed into one int64 so sorting,
  dedup, and binary search are single-key operations. Limits:
  client < 2**22, clock < 2**40 — far beyond the workloads the
  framework targets (the north-star config is 1k replicas x 100k ops).
- ``NULLI = -1`` marks absent references; packed null IDs sort below
  every real ID.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp

# Persistent XLA compilation cache. JAX_COMPILATION_CACHE_DIR is JAX's
# own knob: when it is set, JAX reads it and nothing here sets another
# directory. Otherwise the cache lives at a FIXED path in the checkout,
# ``<checkout>/.jax_cache`` (git-ignored): the path is part of what a
# later process must find again. CPU-pinned processes (the test suite,
# the multichip dry run) keep no cache there: the checkout is what gets
# copied to the chip machine, and CPU compiles are cheap.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def compile_cache_dir(environ=os.environ, platforms=None):
    """Where this process's persistent compile cache lives: the
    ``JAX_COMPILATION_CACHE_DIR`` directory when set, else
    ``<checkout>/.jax_cache``, else None when the process is pinned to
    the CPU (``JAX_PLATFORMS`` in the env or ``platforms``, default
    the config's; read WITHOUT initializing a backend)."""
    if environ.get(CACHE_ENV):
        return environ[CACHE_ENV]
    if platforms is None:
        platforms = getattr(jax.config, "jax_platforms", None)
    pinned = {environ.get("JAX_PLATFORMS", ""), platforms or ""}
    return None if "cpu" in {p.strip().lower() for p in pinned} \
        else CHECKOUT_CACHE


# never clobber a host application's own cache configuration: this is
# a library — only fill the knob when neither the env nor the config
# has it
if (
    compile_cache_dir() == CHECKOUT_CACHE
    and not getattr(jax.config, "jax_compilation_cache_dir", None)
):
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

NULLI = -1
_CLOCK_BITS = 40


# ---------------------------------------------------------------------------
# host<->device transfer seam: every staged upload and result fetch in
# the package routes through these two calls, so bytes-on-link is a
# first-class, regression-gated metric (``xfer.h2d_bytes`` /
# ``xfer.d2h_bytes`` counters with matching ``xfer.h2d``/``xfer.d2h``
# latency histograms) instead of a number reconstructed from shapes in
# a session log.
# ---------------------------------------------------------------------------

_WIDE_ENV = "CRDT_TPU_WIDE_STAGING"

# ---------------------------------------------------------------------------
# device fault hook: the injection seam for the guarded-dispatch
# ladder (crdt_tpu.guard.device). The hook fires before every guarded
# dispatch attempt and may raise RuntimeError to simulate a device
# fault (OOM, preemption) — chaos schedules drive
# the retry → split → host ladder without a real dying accelerator.
# ---------------------------------------------------------------------------

_DEVICE_FAULT_HOOK = None
# guards the fault hook:
# this module is reached from the streaming decode pool, and the
# hook's swap-and-return-old contract (DeviceFaultPlan nests restore
# inside install) is only correct if the read-modify-write is atomic
# (crdtlint CL601)
_HOOK_LOCK = threading.Lock()


def set_device_fault_hook(fn):
    """Install ``fn(stage, attempt)`` as the guarded-dispatch fault
    hook (None uninstalls). Returns the previous hook so callers can
    restore it; :class:`crdt_tpu.guard.faults.DeviceFaultPlan` wraps
    this in a context manager."""
    global _DEVICE_FAULT_HOOK
    with _HOOK_LOCK:
        old = _DEVICE_FAULT_HOOK
        _DEVICE_FAULT_HOOK = fn
        return old


def device_fault_hook():
    return _DEVICE_FAULT_HOOK


def wide_staging_forced() -> bool:
    """Debug knob (README "Transfer diet"): CRDT_TPU_WIDE_STAGING=1
    forces every staged upload to the wide int32 layout, bypassing the
    narrow-column encodings — for isolating a suspected narrowing bug
    without touching code."""
    return os.environ.get(_WIDE_ENV, "") not in ("", "0")


def xfer_put(arr, *, label: str = "stage"):
    """The ONE host->device seam: ``jax.device_put`` + byte accounting.

    Records ``xfer.h2d_bytes`` / ``xfer.h2d_puts`` (labelled by call
    site) and observes the put's enqueue latency into the ``xfer.h2d``
    histogram. The put itself stays ASYNCHRONOUS — the span measures
    initiation, exactly what the overlapped paths pipeline behind."""
    from crdt_tpu.obs.tracer import get_tracer

    tracer = get_tracer()
    if not tracer.enabled:
        return jax.device_put(arr)
    import time as _t

    nbytes = int(getattr(arr, "nbytes", 0))
    t0 = _t.perf_counter()
    out = jax.device_put(arr)
    tracer.observe("xfer.h2d", _t.perf_counter() - t0)
    tracer.count("xfer.h2d_bytes", nbytes)
    tracer.count("xfer.h2d_puts")
    tracer.count("xfer.h2d_bytes_by", nbytes, labels={"path": label})
    return out


def xfer_fetch(dev, *, label: str = "result"):
    """The ONE device->host seam: ``np.asarray`` + byte accounting.

    BLOCKS until the array is on host (that is the point of a fetch).
    Execution wait is ALWAYS excluded from the ``xfer.d2h`` histogram
    — the seam blocks on completion itself before timing the
    transfer, so every call site's sample means the same thing (pure
    D2H) and a kernel slowdown can never read as a transfer
    regression in the byte gate."""
    import numpy as np

    from crdt_tpu.obs.tracer import get_tracer

    tracer = get_tracer()
    if not tracer.enabled:
        return np.asarray(dev)
    import time as _t

    jax.block_until_ready(dev)  # execution wait, not transfer
    t0 = _t.perf_counter()
    h = np.asarray(dev)
    tracer.observe("xfer.d2h", _t.perf_counter() - t0)
    tracer.count("xfer.d2h_bytes", int(h.nbytes))
    tracer.count("xfer.d2h_fetches")
    tracer.count("xfer.d2h_bytes_by", int(h.nbytes),
                 labels={"path": label})
    return h


def record_staged_widths(widths: dict, shipped_bytes: int,
                         wide_bytes: int) -> None:
    """Per-upload narrowing record: one ``xfer.col_width`` count per
    column at its chosen width (the per-column width histogram) and
    the ``xfer.narrowed_ratio`` gauge = shipped / wide-equivalent
    bytes (1.0 = no diet, 0.5 = halved)."""
    from crdt_tpu.obs.tracer import get_tracer

    tracer = get_tracer()
    if not tracer.enabled:
        return
    for col, bits in widths.items():
        tracer.count("xfer.col_width", labels={"col": col, "bits": bits})
    if wide_bytes > 0:
        tracer.gauge(
            "xfer.narrowed_ratio", round(shipped_bytes / wide_bytes, 4)
        )
        # staged-upload bytes tracked SEPARATELY from the all-traffic
        # xfer.h2d_bytes: the run-level narrowing ratio is
        # staged / (staged + saved), and mixing in non-staged traffic
        # (fleet columns, resident deltas) would let an unrelated
        # upload-mix change masquerade as a narrowing regression
        tracer.count("xfer.staged_bytes", shipped_bytes)
        tracer.count("xfer.h2d_bytes_saved",
                     max(wide_bytes - shipped_bytes, 0))


# guards the module-level memo cache (_pack_fns):
# this module is reached from the streaming thread pool, and an
# unlocked read-then-write loses one thread's entry (a wasted
# recompile, and CL601 exists to keep the class of bug out)
_CACHE_LOCK = threading.Lock()


@contextmanager
def on_local_cpu():
    """Execute jitted work on the process's LOCAL CPU backend: the
    same XLA program as the device route, zero accelerator
    interactions. The host side of the host/device crossover runs
    here."""
    with jax.default_device(jax.devices("cpu")[0]):
        yield


def bucket_pow2(n: int, floor: int = 9) -> int:
    """Power-of-two size bucket (host helper): padding to buckets keeps
    jit compiling once per bucket instead of once per exact shape."""
    return 1 << max(floor, (max(n, 1) - 1).bit_length())


def bucket_grid(n: int, floor: int = 9) -> int:
    """Quarter-pow2 size bucket: smallest of {1, 1.25, 1.5, 1.75}*2^k
    >= n. Pow2 padding wastes up to 100% of every downstream sort and
    doubling round; the quarter grid caps waste at 25% for 2x the
    shape-bucket count. Used by the cold packed replay, where the
    doubling loops' width is the dispatch's dominant axis; resident
    buffers keep plain pow2 (their capacity growth amortizes)."""
    n = max(n, 1 << floor)
    k = (n - 1).bit_length() - 1  # candidate exponent: 2^k < n <= 2^(k+1)
    for num in (5, 6, 7, 8):
        cand = num << max(k - 2, 0)
        if cand >= n:
            return cand
    return 1 << (k + 1)


_pack_fns: dict = {}  # arity -> jitted concat (host helper cache)


def fetch_packed_i32(*arrays):
    """Fetch several device index arrays in ONE packed int32 transfer.

    Per-array `np.asarray` fetches pay one transfer per call; all
    kernel index/segment outputs fit int32
    (values < the pad bucket, NULLI = -1). Returns host arrays in
    input order."""
    with _CACHE_LOCK:
        fn = _pack_fns.get(len(arrays))
        if fn is None:
            # cheap under the lock: jax.jit only wraps here, the
            # actual compile happens at the (unlocked) call below
            fn = jax.jit(
                lambda *xs: jnp.concatenate(
                    [x.astype(jnp.int32) for x in xs]
                )
            )
            _pack_fns[len(arrays)] = fn
    h = xfer_fetch(fn(*arrays), label="packed_i32")
    out, off = [], 0
    for a in arrays:
        n = a.shape[0]
        out.append(h[off:off + n])
        off += n
    return out


def pack_id(client: jnp.ndarray, clock: jnp.ndarray) -> jnp.ndarray:
    """(client, clock) -> single sortable int64; null (-1,*) -> -1."""
    packed = (client.astype(jnp.int64) << _CLOCK_BITS) | clock.astype(jnp.int64)
    return jnp.where(client < 0, jnp.int64(NULLI), packed)


def unpack_id(packed: jnp.ndarray):
    client = jnp.where(packed < 0, NULLI, packed >> _CLOCK_BITS).astype(jnp.int32)
    clock = jnp.where(packed < 0, NULLI, packed & ((1 << _CLOCK_BITS) - 1)).astype(
        jnp.int64
    )
    return client, clock


def lexsort(keys) -> jnp.ndarray:
    """argsort by multiple keys; keys[0] is most significant.

    Built from iterated stable argsorts (least-significant first), the
    classic radix-style composition XLA handles well.
    """
    order = jnp.argsort(keys[-1], stable=True)
    for k in reversed(keys[:-1]):
        order = order[jnp.argsort(k[order], stable=True)]
    return order


def dense_ranks_sorted(sorted_key: jnp.ndarray) -> jnp.ndarray:
    """Dense 0..S-1 rank per element of an ALREADY SORTED key array."""
    new_seg = jnp.concatenate(
        [
            jnp.zeros(1, jnp.int32),
            (sorted_key[1:] != sorted_key[:-1]).astype(jnp.int32),
        ]
    )
    return jnp.cumsum(new_seg).astype(jnp.int32)


def searchsorted_ids(sorted_ids: jnp.ndarray, query: jnp.ndarray) -> jnp.ndarray:
    """Index of each query id in sorted_ids, or NULLI if absent.

    method='sort' everywhere in this package: the default binary-search
    lowering is a log(N)-step loop of full-width gathers, an order of
    magnitude slower on TPU than one extra radix sort pass (measured
    ~34ms vs ~2.4ms at N=128k on v5e)."""
    pos = jnp.searchsorted(sorted_ids, query, method="sort")
    pos_c = jnp.clip(pos, 0, sorted_ids.shape[0] - 1)
    found = (sorted_ids.shape[0] > 0) & (sorted_ids[pos_c] == query) & (query >= 0)
    return jnp.where(found, pos_c, NULLI).astype(jnp.int32)


def scatter_perm(perm: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """out[perm[i]] = vals[i] for a PERMUTATION perm — as a gather.

    XLA TPU lowers a real scatter to a serialized update loop (~13ms at
    N=128k on v5e); inverting the permutation with one more argsort and
    gathering is ~50x cheaper. Only valid when perm is a permutation of
    0..N-1 (e.g. any argsort output)."""
    return vals[jnp.argsort(perm, stable=True)]


def run_edge_lookup(slots_sorted: jnp.ndarray, size: int, *, side: str):
    """For each dense slot j in [0, size): the index into `slots_sorted`
    of the FIRST (side='left') or LAST (side='right') element equal to
    j, or NULLI when j is absent. `slots_sorted` must be ascending
    (route invalid rows to a value >= size before sorting).

    This is the scatter-free way to build dense per-slot tables (first
    child per parent, last child per node, max per segment): sort rows
    by slot once, then one searchsorted picks each run's edge."""
    iota = jnp.arange(size, dtype=slots_sorted.dtype)
    pos = jnp.searchsorted(slots_sorted, iota, side=side, method="sort")
    if side == "right":
        pos = pos - 1
    pos_c = jnp.clip(pos, 0, slots_sorted.shape[0] - 1)
    found = slots_sorted[pos_c] == iota
    return jnp.where(found, pos_c, NULLI).astype(jnp.int32), found


def dfs_ranks(
    parent: jnp.ndarray,      # [B] int32 tree parent (root children point
                              #     at B+seg; non-items at B+num_roots)
    next_sib: jnp.ndarray,    # [B] int32 next sibling, NULLI at group end
    first_child: jnp.ndarray, # [B+num_roots] int32 first child per node
    is_item: jnp.ndarray,     # [B] bool real tree members
    num_roots: int,
    rank_rounds: int | None = None,
) -> jnp.ndarray:
    """Distance-to-end of the DFS traversal for every node (items and
    the virtual roots appended after them) via successor pointer
    doubling (Wyllie list ranking).

    The DFS successor of a node is its first child if any, else the
    next sibling of the nearest ancestor (itself included) that has
    one — the "climb past last-child chains" step, itself a pointer
    doubling. Shared by :func:`crdt_tpu.ops.yata.tree_order_ranks`
    (full-width) and the packed replay kernel (compact-width; since
    round 12 the staged cold path feeds PRE-BUILT next_sib /
    first_child tables straight from staging, so this ranking is the
    only tree machinery left in that dispatch).

    ``rank_rounds`` (static), when the caller can bound the longest
    per-segment DFS path on the host (e.g. max segment population from
    one ``np.unique`` at staging), fixes both doubling loops to that
    many rounds: the fixpoint reduce per round disappears and the
    whole ranking runs exactly ceil(log2(path)) gathers. ``None``
    keeps the data-driven while-loop with fixpoint early exit (the
    incremental path, where the bound changes every round and a static
    would recompile).
    """
    B = parent.shape[0]
    m = B + num_roots
    idx_m = jnp.arange(m, dtype=jnp.int32)
    pad_next = jnp.pad(next_sib, (0, num_roots), constant_values=NULLI)
    pad_parent = jnp.pad(parent, (0, num_roots), constant_values=0).astype(
        jnp.int32
    )
    pad_item = jnp.pad(is_item, (0, num_roots))

    is_last_child = (idx_m < B) & (pad_next == NULLI) & pad_item
    g = jnp.where(is_last_child, pad_parent, idx_m)
    climb_t = pointer_double(g, max_iters=rank_rounds)

    y_next = pad_next[jnp.clip(climb_t, 0, m - 1)]
    succ = jnp.where((climb_t >= B) | (y_next < 0), idx_m, y_next)
    succ = jnp.where(
        first_child >= 0, jnp.clip(first_child, 0, m - 1), succ
    )
    succ = jnp.where(pad_item | (idx_m >= B), succ, idx_m).astype(jnp.int32)

    return wyllie_dist(succ, rounds=rank_rounds)


# low 32 bits of the packed (pointer, distance) word hold the distance.
# A plain Python int: a module-level jnp scalar would be constructed at
# import time, when jax_enable_x64 may be off, and truncate to int32.
_W_DIST = (1 << 32) - 1


def wyllie_dist(succ: jnp.ndarray, rounds: int | None = None) -> jnp.ndarray:
    """Distance-to-terminal along ``succ`` for every node (terminals
    are self-loops), by pointer doubling with the (pointer, distance)
    pair packed into ONE int64 per node: each round costs a single
    random gather instead of two, and on a gather-latency-bound TPU
    the ranking loop is where the fused replay dispatch is expected to
    spend its time.

    ``rounds`` (static) runs a fixed ``fori_loop`` with no per-round
    fixpoint reduce; callers must guarantee 2**rounds >= the longest
    path. ``None`` falls back to the early-exit while-loop bounded by
    ceil(log2(m)) + 1 (any malformed cycle terminates there and keeps
    an in-cycle value, same convention as :func:`pointer_double`)."""
    m = succ.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    dist0 = (succ != idx).astype(jnp.int64)
    comb = (succ.astype(jnp.int64) << 32) | dist0
    max_iters = max(1, (max(m, 2) - 1).bit_length() + 1)

    def step(c):
        ptr = (c >> 32).astype(jnp.int32)
        c2 = c[ptr]
        newd = (c & _W_DIST) + (c2 & _W_DIST)
        return (c2 & ~_W_DIST) | newd, ptr, (c2 >> 32).astype(jnp.int32)

    if rounds is not None:
        def fbody(_, c):
            return step(c)[0]

        comb = jax.lax.fori_loop(0, min(rounds, max_iters), fbody, comb)
    else:
        def body(state):
            c, it, _ = state
            nc, ptr, nptr = step(c)
            return nc, it + 1, jnp.any(nptr != ptr)

        def cond(state):
            return state[2] & (state[1] < max_iters)

        p0 = (comb >> 32).astype(jnp.int32)
        comb, _, _ = jax.lax.while_loop(
            cond, body, (comb, jnp.int32(0), jnp.any(p0[p0] != p0))
        )
    return (comb & _W_DIST).astype(jnp.int32)


def pointer_double(f: jnp.ndarray, max_iters: int | None = None) -> jnp.ndarray:
    """Iterate f <- f∘f to a fixpoint. `f` maps node->node with
    self-loops at terminals; returns the terminal reached from each
    node in O(log depth) gather rounds.

    The iteration count is hard-bounded at ceil(log2(n))+1: any valid
    forest converges by then, and a malformed input whose pointers form
    a cycle (e.g. a hostile update with cyclic origins) terminates
    instead of spinning the device forever — cycle members simply keep
    an in-cycle value, which downstream visibility checks treat like
    any other non-root result.

    ``max_iters`` (static) tightens the bound when the caller knows the
    chain depth (the early-exit reduce still runs; the cap only clips
    the worst case)."""
    n = f.shape[0]
    cap = max(1, (max(n, 2) - 1).bit_length() + 1)
    max_iters = cap if max_iters is None else max(1, min(max_iters, cap))

    def body(state):
        g, it, _ = state
        g2 = g[g]
        return g2, it + 1, jnp.any(g2 != g)

    def cond(state):
        _, it, changed = state
        return changed & (it < max_iters)

    # initial `changed` is derived from f (not a constant) so the carry
    # carries f's varying-axes type under shard_map, and an input
    # already at fixpoint exits immediately
    g, _, _ = jax.lax.while_loop(
        cond, body, (f, jnp.int32(0), jnp.any(f[f] != f))
    )
    return g
