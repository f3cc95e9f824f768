"""HBM-resident columnar union — converge without restaging the doc.

The north star names this explicitly: incoming peer updates are
buffered into columnar tensors, applied as one vectorized applyUpdate,
and the ``crdt.c`` cache is rebuilt from HBM — NOT re-uploaded from the
host every dispatch. :class:`ResidentColumns` is that buffer:

- the op columns live in device memory across rounds (capacity grows
  by power-of-two buckets, one recompile per bucket);
- ``append`` ships ONLY the new delta over PCIe/ICI (padded to a
  delta bucket) and splices it in-place with ``dynamic_update_slice``;
- ``converge`` dispatches the LWW map kernel and the YATA sequence
  kernel over the resident buffers and returns DEVICE arrays — nothing
  crosses back to the host until the caller materializes.

Client ids are interned to DENSE, ORDER-PRESERVING values on append:
the kernels pack (client << 40 | clock) into int64, which random
31-bit replica ids would alias (same rationale as the remap in
``core.device_apply``), and YATA/LWW sibling rules compare client ids,
so the mapping must be monotone in the raw id. Dense id = rank among
all raw ids seen; a new id arriving BETWEEN existing ones shifts later
ranks, triggering a one-off on-device relabel of the client columns
(O(capacity), at most once per distinct client — and never when the
client set is pre-registered via the ``clients=`` argument, which the
fleet path can always do).

Product-path counterpart: ``core.device_apply.rebuild_chains`` keeps
per-parent incremental state on the host engine; this class is the
firehose path (ReplicaFleet fan-in, trace replay, the benchmark).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from jax import enable_x64
import jax.numpy as jnp
import numpy as np

from crdt_tpu.obs.tracer import get_tracer
from crdt_tpu.ops import deleteset as ds_ops
from crdt_tpu.ops.device import bucket_pow2 as _bucket  # shared policy

# (name, dtype) in kernel argument order
COLUMNS = (
    ("client", np.int32),
    ("clock", np.int64),
    ("parent_is_root", np.bool_),
    ("parent_a", np.int64),
    ("parent_b", np.int64),
    ("key_id", np.int32),
    ("origin_client", np.int32),
    ("origin_clock", np.int64),
    ("valid", np.bool_),
)

_FILL = {
    "client": 0,
    "clock": 0,
    "parent_is_root": False,
    "parent_a": -2,
    "parent_b": -2,
    "key_id": -1,
    "origin_client": -1,
    "origin_clock": -1,
    "valid": False,
}


@partial(jax.jit, static_argnames=("num_segments", "ds_mode"))
def _converge_all(bufs, d_client, d_start, d_end, num_segments,
                  ds_mode=None):
    """Map + sequence convergence as ONE XLA program: both kernels
    share the packed-id sort and dedup, which XLA CSEs when they are
    traced together — one dispatch instead of two. ``ds_mode``
    is the host-computed delete-mask kernel static (crdtlint CL702 —
    never read CRDT_TPU_PALLAS in here)."""
    from crdt_tpu.ops.merge import converge_maps
    from crdt_tpu.ops.yata import converge_sequences

    maps_out = converge_maps(
        *bufs, d_client, d_start, d_end, num_segments=num_segments,
        ds_mode=ds_mode,
    )
    seq_out = converge_sequences(*bufs, num_segments=num_segments)
    return maps_out, seq_out


@partial(jax.jit, donate_argnums=(0,))
def _splice(bufs, delta, n):
    """In-place (donated) append of a padded delta at offset n."""
    return tuple(
        jax.lax.dynamic_update_slice(b, d, (n,)) for b, d in zip(bufs, delta)
    )


@partial(jax.jit, donate_argnums=(0,),
         static_argnames=("num_segments", "ds_mode"))
def _splice_and_converge(bufs, delta, n, d_client, d_start, d_end,
                         num_segments, ds_mode=None):
    """Append + full convergence as ONE program: the splice, the LWW
    map kernel, and the YATA sequence kernel trace together, so a
    single-delta replay pays one dispatch instead of two. ``ds_mode``
    threads through to the delete-mask kernel (host-computed static,
    crdtlint CL702)."""
    bufs = tuple(
        jax.lax.dynamic_update_slice(b, d, (n,)) for b, d in zip(bufs, delta)
    )
    maps_out, seq_out = _converge_all(
        bufs, d_client, d_start, d_end, num_segments=num_segments,
        ds_mode=ds_mode,
    )
    return bufs, maps_out, seq_out


@partial(jax.jit, donate_argnums=(0,))
def _relabel(bufs, perm):
    """Rewrite the client columns through an old-dense -> new-dense
    permutation (invalid rows hold 0, which perm covers; -1 origins
    stay -1)."""
    bufs = list(bufs)
    bufs[0] = perm[bufs[0]].astype(bufs[0].dtype)
    oc = bufs[6]
    bufs[6] = jnp.where(
        oc >= 0, perm[jnp.clip(oc, 0, perm.shape[0] - 1)], oc
    ).astype(oc.dtype)
    return tuple(bufs)


class ResidentColumns:
    """Growable device-resident op columns + in-place convergence."""

    def __init__(
        self,
        capacity: int = 1 << 15,
        clients: Optional[Sequence[int]] = None,
    ):
        cap = _bucket(capacity)
        self.n = 0
        self._seen: List[int] = []  # sorted raw client ids
        self._dense: Dict[int, int] = {}  # raw -> rank among seen
        if clients is not None and len(clients) > 0:
            self._intern(np.asarray(sorted(set(int(c) for c in clients))))
        with enable_x64(True):
            self._bufs: Tuple[jnp.ndarray, ...] = tuple(
                jnp.full(cap, _FILL[name], dtype=dt) for name, dt in COLUMNS
            )

    @property
    def capacity(self) -> int:
        return int(self._bufs[0].shape[0])

    def device_bytes(self) -> int:
        """Device-memory footprint of the resident buffers — the
        firehose-path counterpart of :meth:`crdt_tpu.models.
        incremental.IncrementalReplay.resident_bytes` (which is what
        the multi-doc server's ``CRDT_TPU_MT_RESIDENT_BYTES`` budget
        actually sums): a capacity planner sizing a fleet of
        ResidentColumns stores reads it per store. Computed from
        dtype itemsizes, so it tracks the column schema
        automatically."""
        cap = self.capacity
        return sum(
            cap * np.dtype(dt).itemsize for _, dt in COLUMNS
        )

    # -- client interning ---------------------------------------------
    def _intern(self, raw_ids: np.ndarray) -> Optional[np.ndarray]:
        """Register raw ids. Returns an old-dense->new-dense permutation
        when existing ranks shifted (caller must relabel the resident
        columns), else None."""
        new = sorted(set(int(c) for c in raw_ids) - self._dense.keys())
        if not new:
            return None
        shifted = bool(self._seen) and new[0] < self._seen[-1]
        old = dict(self._dense) if shifted else None
        self._seen = sorted(self._seen + new)
        self._seen_arr = np.asarray(self._seen)
        self._dense = {raw: i for i, raw in enumerate(self._seen)}
        if old and self.n:
            perm = np.zeros(len(old), np.int32)
            for raw, od in old.items():
                perm[od] = self._dense[raw]
            return perm
        return None

    def _map_clients(self, arr: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Raw -> dense for the masked entries; others untouched."""
        out = arr.astype(np.int32).copy()
        if mask.any():
            vals = arr[mask]
            out[mask] = np.searchsorted(self._seen_arr, vals).astype(np.int32)
        return out

    # -- append / converge --------------------------------------------
    def _prepare_delta(self, cols: Dict[str, np.ndarray], k: int):
        """Shared append preamble: client interning (+ on-device
        relabel when ranks shifted), capacity growth, and the padded
        delta arrays. Caller splices inside the same x64 scope."""
        valid = np.asarray(cols["valid"][:k], bool)
        raw_cl = np.asarray(cols["client"][:k])
        raw_ocl = np.asarray(cols["origin_client"][:k])
        perm = self._intern(
            np.concatenate([raw_cl[valid], raw_ocl[raw_ocl >= 0]])
        )
        if perm is not None:
            self._bufs = _relabel(self._bufs, jnp.asarray(perm))
        if self.n + k > self.capacity:
            self._grow(self.n + k)
        kpad = min(_bucket(k, floor=6), self.capacity)
        if self.n + kpad > self.capacity:
            self._grow(self.n + kpad)
        from crdt_tpu.ops.device import xfer_put

        delta = []
        for name, dt in COLUMNS:
            arr = np.full(kpad, _FILL[name], dtype=dt)
            if name == "client":
                arr[:k] = np.where(
                    valid, self._map_clients(raw_cl, valid), 0
                )
            elif name == "origin_client":
                arr[:k] = self._map_clients(raw_ocl, raw_ocl >= 0)
            else:
                arr[:k] = cols[name][:k]
            # the xfer seam accounts every appended delta column:
            # resident rounds must show DELTA-sized h2d growth, never
            # the full matrix (pinned by tests/test_transfer_diet.py)
            delta.append(xfer_put(arr, label="resident.delta"))
        return tuple(delta)

    def append(self, cols: Dict[str, np.ndarray]) -> None:
        """Splice a host-side delta into the resident union. Only the
        delta (padded to its power-of-two bucket) crosses to the
        device; resident rows never re-upload."""
        k = len(cols["client"])
        if k == 0:
            return
        with enable_x64(True):
            delta = self._prepare_delta(cols, k)
            self._bufs = _splice(self._bufs, delta, jnp.int32(self.n))
        self.n += k

    def append_converge(
        self,
        cols: Dict[str, np.ndarray],
        num_segments: Optional[int] = None,
        d_client=None,
        d_start=None,
        d_end=None,
    ):
        """Fused append + convergence: the splice and both kernels run
        as ONE dispatch — the single-delta replay path. Equivalent to
        ``append(cols)`` then ``converge(...)``."""
        k = len(cols["client"])
        if k == 0:
            return self.converge(
                num_segments=num_segments, d_client=d_client,
                d_start=d_start, d_end=d_end,
            )
        with enable_x64(True):
            delta = self._prepare_delta(cols, k)
            # default segments AFTER _prepare_delta: it may grow the
            # capacity, and a pre-growth default would alias segment
            # ids (diverging from append() + converge())
            segs = num_segments or self.capacity
            if d_client is None:
                d_client = jnp.full(16, -1, jnp.int32)
                d_start = jnp.full(16, -1, jnp.int64)
                d_end = jnp.full(16, -1, jnp.int64)
            self._bufs, maps_out, seq_out = _splice_and_converge(
                self._bufs, delta, jnp.int32(self.n),
                d_client, d_start, d_end, num_segments=segs,
                ds_mode=ds_ops.mask_mode(),  # host static (CL702)
            )
        self.n += k
        return maps_out, seq_out

    def _grow(self, need: int) -> None:
        new_cap = _bucket(need)
        grown = []
        for (name, dt), b in zip(COLUMNS, self._bufs):
            nb = jnp.full(new_cap, _FILL[name], dtype=dt)
            grown.append(jax.lax.dynamic_update_slice(nb, b, (0,)))
        self._bufs = tuple(grown)

    def dense_client(self, raw: int) -> Optional[int]:
        """Dense id currently assigned to a raw client id."""
        return self._dense.get(int(raw))

    def converge(
        self,
        num_segments: Optional[int] = None,
        d_client=None,
        d_start=None,
        d_end=None,
    ):
        """One full device applyUpdate over the resident union: map
        winners (converge_maps) + sequence order (converge_sequences)
        in a single fused dispatch. Returns the two kernels' raw
        outputs as DEVICE arrays.

        Delete ranges, when given, must use DENSE client ids
        (:meth:`dense_client`).
        """
        segs = num_segments or self.capacity
        with enable_x64(True):
            if d_client is None:
                d_client = jnp.full(16, -1, jnp.int32)
                d_start = jnp.full(16, -1, jnp.int64)
                d_end = jnp.full(16, -1, jnp.int64)
            return _converge_all(
                self._bufs, d_client, d_start, d_end,
                num_segments=segs,
                ds_mode=ds_ops.mask_mode(),  # host static (CL702)
            )


# ---- the POOLED resident matrix (round 20) --------------------------

from crdt_tpu.ops import packed as pk  # noqa: E402  (pool device ops)


def _octave8(n: int, floor: int) -> int:
    """Factor-8 size bucket (the incremental dispatch's static-shape
    policy — see ``models.incremental._octave``): a handful of XLA
    variants over the pool's lifetime instead of one per doubling."""
    b = floor
    while b < n:
        b *= 8
    return b


_LANES = 8            # pooled matrix lanes (7 delta columns + slot)
_EXT_FLOOR = 1 << 10  # smallest extent, in rows (pow2 buckets above)
_CLIENT_BOUND = 1 << 22   # composite client must fit pack_id's width
_PREF_BOUND = 1 << 40     # composite pref must stay under segkey bit 62


class _Extent:
    """One doc's reserved column range in the pooled matrix. The
    invariant the splice relies on: device position of a doc's host
    row ``r`` is ``off + r`` (admission appends rows in order, and a
    relocation moves the WHOLE extent)."""

    __slots__ = ("off", "cap", "n", "slot", "move_from")

    def __init__(self, off: int, cap: int, slot: int):
        self.off = off
        self.cap = cap
        self.n = 0          # rows spliced so far (== engine.n_dev)
        self.slot = slot
        # (old off, old cap) awaiting the flush's device move — the
        # copy width must be the OLD bucket: the new cap can overrun
        # the old region into a neighbour's extent
        self.move_from = None


class ResidentPool:
    """ONE device allocation for every warm doc's resident matrix
    (round 20): per-doc extents co-locate the docs' columns, and all
    above-crossover deltas of a `MultiDocServer` tick batch into ONE
    scatter-splice + converge dispatch
    (:func:`crdt_tpu.ops.packed._pool_splice_select_converge`)
    instead of one per doc. Engines attach via the ``pool=``
    constructor argument of :class:`crdt_tpu.models.incremental.
    IncrementalReplay`; their device rounds then DEFER here
    (:meth:`defer`) and the server's tick flushes once
    (:meth:`flush`).

    Geometry: extents are pow2-bucketed row ranges allocated at the
    tail; a doc outgrowing its extent relocates by an on-device copy
    (never a host restage), eviction frees the extent, and holes are
    squeezed by a bounded compaction (one device gather) when they
    exceed the live rows — or on demand, when an allocation would
    otherwise burst ``max_bytes``. ``max_bytes`` bounds the pooled
    ALLOCATION (``CRDT_TPU_MT_POOL_BYTES`` at the server); a doc that
    cannot fit even after compaction is refused and falls back to a
    private resident matrix — correctness never depends on pooling.

    Counters/gauges (README "Observability" registry):
    ``tenant.pool_dispatches`` per pooled flush,
    ``tenant.pool_compactions`` per hole squeeze, and the
    ``tenant.pool_bytes`` / ``tenant.pool_docs`` gauges for the live
    allocation and extent count."""

    def __init__(self, max_bytes: Optional[int] = None,
                 capacity: int = 1 << 15):
        self.max_bytes = None if max_bytes is None else int(max_bytes)
        self._cap0 = _bucket(capacity)
        self._mat = None                       # lazy [8, cap] int64
        self._ext: Dict[object, _Extent] = {}
        self._free_slots: List[int] = []
        self._next_slot = 0
        self._pending: Dict[object, set] = {}
        # released extents' (off, cap) whose columns are still LIVE
        # on device: killed lazily at the next dispatch (or dropped
        # wholesale by a compaction's gather). Until then a reused
        # slot could alias the stale rows onto another doc's
        # composite ids — the kill runs BEFORE any splice.
        self._dead: List[Tuple[int, int]] = []
        self.dispatches = 0
        self.compactions = 0
        self.peak_bytes = 0

    # -- accounting ---------------------------------------------------
    def device_bytes(self) -> int:
        """Live pooled allocation — the ``tenant.pool_bytes`` gauge.
        Unit contract (pinned by tests/test_pooled.py): lanes x
        capacity x int64 itemsize, the same dtype-derived accounting
        as :meth:`ResidentColumns.device_bytes`."""
        if self._mat is None:
            return 0
        return int(self._mat.shape[0]) * int(self._mat.shape[1]) * 8

    def doc_device_bytes(self, eng) -> int:
        """One doc's reserved share — what the engine's
        ``resident_bytes`` (and through it the MT resident ledger)
        accounts for a pooled doc."""
        ext = self._ext.get(eng)
        return 0 if ext is None else ext.cap * _LANES * 8

    def doc_count(self) -> int:
        return len(self._ext)

    def has_pending(self, eng=None) -> bool:
        return bool(self._pending) if eng is None \
            else eng in self._pending

    def take_pending(self, eng) -> set:
        """Pop an engine's deferred segments (the unpooling fallback
        host-routes them itself)."""
        return set(self._pending.pop(eng, ()))

    def _note_peak(self) -> None:
        self.peak_bytes = max(self.peak_bytes, self.device_bytes())

    def _tail(self) -> int:
        return max((e.off + e.cap for e in self._ext.values()),
                   default=0)

    def _live_rows(self) -> int:
        return sum(e.cap for e in self._ext.values())

    # -- membership ---------------------------------------------------
    def register(self, eng) -> None:
        """Attach an engine (host bookkeeping only — no extent, no
        device touch: a doc that never crosses to the device route
        costs the pool nothing)."""
        if eng in self._ext:
            return
        slot = (self._free_slots.pop()
                if self._free_slots else self._next_slot)
        if slot == self._next_slot:
            self._next_slot += 1
        self._ext[eng] = _Extent(0, 0, slot)

    def release(self, eng) -> None:
        """Detach an engine (eviction / fallback): free its extent
        and slot; squeeze holes when they outgrow the live rows. The
        last doc leaving drops the whole allocation."""
        ext = self._ext.pop(eng, None)
        self._pending.pop(eng, None)
        if ext is None:
            return
        self._free_slots.append(ext.slot)
        if self._mat is not None and ext.n:
            # the doc's columns are still live on device (at the old
            # location when a relocation is still pending)
            self._dead.append(
                ext.move_from if ext.move_from is not None
                else (ext.off, ext.cap)
            )
        if not self._ext:
            self._mat = None
            self._free_slots.clear()
            self._next_slot = 0
            self._dead.clear()
        elif self._mat is not None and \
                self._tail() > 2 * self._live_rows():
            self.compact()
        tracer = get_tracer()
        if tracer.enabled:
            tracer.gauge("tenant.pool_bytes", self.device_bytes())
            tracer.gauge("tenant.pool_docs", self.doc_count())

    def _reset_all(self) -> None:
        """Device-failure ladder exhausted mid-flush: a post-donation
        failure may have invalidated the pooled matrix, so drop it —
        every attached engine restages its WHOLE host column set on
        the next flush (n_dev=0), the same full-rebuild contract the
        private matrix uses."""
        self._mat = None
        self._dead.clear()
        for eng, ext in self._ext.items():
            ext.n = 0
            ext.move_from = None
            eng.n_dev = 0

    # -- geometry -----------------------------------------------------
    def _fits_budget(self, cap_rows: int) -> bool:
        return self.max_bytes is None or \
            cap_rows * _LANES * 8 <= self.max_bytes

    def defer(self, eng, segs) -> bool:
        """Queue one engine device round for the batched flush:
        reserve (or pow2-grow) the doc's extent — host bookkeeping
        now, device moves at the flush — and merge its touched
        segments. Returns False when the pool cannot hold the doc
        within ``max_bytes`` even after compaction: the caller falls
        back to a private resident matrix."""
        ext = self._ext.get(eng)
        if ext is None:
            self.register(eng)
            ext = self._ext[eng]
        need = _bucket(max(eng.cols.n, _EXT_FLOOR))
        if ext.cap < need:
            tail = self._tail()
            if not self._fits_budget(_bucket(tail + need)) and \
                    self._mat is not None and \
                    self._tail() > self._live_rows():
                self.compact()
                tail = self._tail()
            if not self._fits_budget(_bucket(tail + need)):
                return False
            if ext.cap and ext.n:
                # relocation: the device copy runs inside the
                # flush's guarded dispatch; splice positions already
                # use the new offset
                if ext.move_from is None:
                    ext.move_from = (ext.off, ext.cap)
            ext.off = tail
            ext.cap = need
        self._pending.setdefault(eng, set()).update(segs)
        return True

    def relabel(self, eng, perm: np.ndarray) -> None:
        """Per-doc client relabel after a mid-table insertion —
        :meth:`IncrementalReplay._intern_clients`'s pooled branch.
        Only the doc's spliced extent columns rewrite."""
        ext = self._ext.get(eng)
        if ext is None or not ext.n or self._mat is None:
            return
        with enable_x64(True):
            self._mat = pk._pool_relabel_range(
                self._mat, jnp.asarray(perm),
                jnp.int32(ext.off), jnp.int32(ext.n),
            )

    def _ensure_mat(self, need_cols: int):
        with enable_x64(True):
            if self._mat is None:
                cap = _bucket(max(need_cols, self._cap0))
                if not self._fits_budget(cap):
                    # a budget tighter than the default first bucket:
                    # allocate only what the extents need
                    cap = _bucket(max(need_cols, 1))
                m = jnp.zeros((_LANES, cap), jnp.int64)
                m = m.at[3:6, :].set(-1)
                self._mat = m.at[7, :].set(-1)
            elif need_cols > self._mat.shape[1]:
                self._mat = pk._pool_grow(
                    self._mat, new_cap=_bucket(need_cols)
                )
        return self._mat

    def compact(self) -> None:
        """Squeeze eviction holes: repack live extents tight (in off
        order) with ONE device gather, shrinking the allocation to
        the covering pow2 bucket. Extents relocate wholesale, so the
        ``off + host_row`` position invariant is untouched. Bounded:
        O(pool) work, triggered only by releases and budget-pressed
        allocations — never on the steady path."""
        if self._mat is None or not self._ext:
            return
        exts = sorted(self._ext.values(), key=lambda e: e.off)
        tail = 0
        plan = []
        for e in exts:
            plan.append((e, tail))
            tail += e.cap
        # the default first bucket is only a FLOOR when it fits the
        # budget — a compaction must never re-grow a budget-clamped
        # pool past ``max_bytes`` (the ``tenant.pool_bytes`` peak is
        # pinned <= budget mid-compaction by tests/test_pooled.py)
        floor_cap = self._cap0 if self._fits_budget(self._cap0) else 1
        new_cap = _bucket(max(tail, floor_cap))
        src = np.zeros(new_cap, np.int32)
        keep = np.zeros(new_cap, bool)
        for e, new_off in plan:
            # a pending relocation's live rows are still at the OLD
            # location — gather from there (the compaction subsumes
            # the move); the new cap's surplus columns init dead
            s_off, s_cap = (e.move_from if e.move_from is not None
                            else (e.off, e.cap))
            w = min(s_cap, e.cap)
            src[new_off : new_off + w] = np.arange(
                s_off, s_off + w, dtype=np.int32
            )
            keep[new_off : new_off + w] = True
        from crdt_tpu.guard.device import dispatch_guarded

        def _gather():
            with enable_x64(True):
                return pk._pool_compact(
                    self._mat, jnp.asarray(src), jnp.asarray(keep)
                )

        res = dispatch_guarded("pool.compact", _gather,
                               host=lambda: None)
        if res is None:
            self._reset_all()
            return
        self._mat = res
        self._dead.clear()  # the gather dropped every hole
        for e, new_off in plan:
            e.off = new_off
            e.move_from = None
        self.compactions += 1
        self._note_peak()
        tracer = get_tracer()
        if tracer.enabled:
            tracer.count("tenant.pool_compactions")
            tracer.gauge("tenant.pool_bytes", self.device_bytes())

    # -- the one pooled dispatch --------------------------------------
    def flush(self) -> int:
        """Converge EVERY deferred device round in one dispatch:
        execute pending extent moves, scatter-splice the combined
        delta block at the docs' extents, select the touched
        COMPOSITE segments, converge, and unpack winners/orders back
        to each engine. Returns the number of converge dispatches
        issued (0 when nothing pends, 1 on the batched path).

        Composite id bases are recomputed per flush from the live
        engines' id tables (traced operands — growth never
        recompiles); when the combined tables would overflow the
        kernel's packed widths the round routes host-side instead
        (exact, conservative). Device failure follows the guarded
        ladder: retry, then host-route the round and drop the pooled
        matrix for a full rebuild on the next flush."""
        if not self._pending:
            return 0
        pending = {e: sorted(s) for e, s in self._pending.items()}
        self._pending = {}

        # composite bases over ALL attached docs (live rows compose
        # too): disjoint, cumulative, slot-indexed. A doc's client
        # span carries HEADROOM for its pending tail — the staging
        # below interns the tail's clients AFTER these bases are
        # fixed, and the composite ranges only need to be disjoint
        # and order-preserving, not tight (each tail row introduces
        # at most one new client; origin clients always own a row).
        by_slot = sorted(self._ext.items(), key=lambda kv: kv[1].slot)
        spad = _octave8(self._next_slot, floor=16)
        cbase = np.zeros(spad, np.int64)
        pbase = np.zeros(spad, np.int64)
        tot_c = tot_p = 0
        for eng, ext in by_slot:
            cbase[ext.slot] = tot_c
            pbase[ext.slot] = tot_p
            tail = eng.cols.n - eng.n_dev if eng in pending else 0
            tot_c += len(eng._clients) + tail + 1
            tot_p += len(eng._pref_spec) + 1
        if tot_c >= _CLIENT_BOUND or tot_p >= _PREF_BOUND:
            # packed-width overflow (thousands of docs x clients):
            # host-route this round — exact, never wrong
            self._host_fallback(pending)
            return 0

        from crdt_tpu.guard.device import dispatch_guarded
        from crdt_tpu.ops.device import xfer_fetch, xfer_put

        n_sel = sum(
            len(eng._seg_rows[sk])
            for eng, segs in pending.items() for sk in segs
        )
        n_touch = sum(len(segs) for segs in pending.values())
        k_tot = sum(eng.cols.n - eng.n_dev for eng in pending)
        tpad = _octave8(n_touch, floor=1 << 10)
        kpad = _octave8(max(k_tot, 1), floor=1 << 6)

        def _dispatch():
            # EVERY device interaction of the flush — interning
            # relabels, extent moves, growth, the splice — runs
            # inside the guarded attempt (same idempotence contract
            # as the private round: intern commits only after its
            # relabel, moves clear only after their copy, staging
            # rebuilds per attempt)
            mat = self._ensure_mat(self._tail())
            with enable_x64(True):
                # released extents' stale columns die FIRST — a
                # reused slot (or an extent re-allocated over the
                # hole) must never see them alive. Idempotent per
                # guarded attempt.
                for d_off, d_cap in self._dead:
                    mat = pk._pool_kill(
                        mat, jnp.int32(d_off), width=d_cap
                    )
                for eng, _segs in pending.items():
                    ext = self._ext[eng]
                    if ext.move_from is not None and ext.n:
                        s_off, s_cap = ext.move_from
                        mat = pk._pool_move(
                            mat, jnp.int32(s_off),
                            jnp.int32(ext.off), width=s_cap,
                        )
                    ext.move_from = None
                self._mat = mat
                parts = []
                touched = []
                for eng, segs in pending.items():
                    ext = self._ext[eng]
                    rows = np.arange(eng.n_dev, eng.cols.n)
                    oc_tail = eng.cols.col("oc")[rows]
                    eng._intern_clients(np.concatenate([
                        eng.cols.col("client")[rows],
                        oc_tail[oc_tail >= 0],
                    ]))
                    parts.append((
                        eng._dense_of(eng.cols.col("client")[rows]),
                        eng.cols.col("clock")[rows],
                        eng.cols.col("pref")[rows],
                        eng.cols.col("kid")[rows],
                        np.where(
                            oc_tail >= 0,
                            eng._dense_of(np.clip(
                                oc_tail,
                                eng._clients[0] if eng._clients else 0,
                                None,
                            )),
                            -1,
                        ),
                        eng.cols.col("ock")[rows],
                        np.full(len(rows), ext.slot, np.int64),
                        (ext.off + rows).astype(np.int64),
                    ))
                    pb = int(pbase[ext.slot])
                    touched.extend(
                        sk + (pb << pk._KID_BITS) for sk in segs
                    )
                cat = [np.concatenate(c) for c in zip(*parts)]
                delta, ppos = pk.stage_pooled_delta(
                    *cat[:7], cat[7], kpad,
                    int(self._mat.shape[1]),
                )
                tarr = np.full(tpad, np.iinfo(np.int64).max, np.int64)
                tarr[: len(touched)] = np.sort(
                    np.asarray(touched, np.int64)
                )
                sel_bucket = min(
                    _octave8(n_sel, floor=1 << 13),
                    int(self._mat.shape[1]),
                )
                mat2, packed_out = pk._pool_splice_select_converge(
                    self._mat,
                    xfer_put(delta, label="incremental.delta"),
                    xfer_put(ppos, label="incremental.delta"),
                    xfer_put(tarr, label="incremental.delta"),
                    xfer_put(cbase, label="incremental.delta"),
                    xfer_put(pbase, label="incremental.delta"),
                    num_segments=tpad, sel_bucket=sel_bucket,
                    seq_bucket=sel_bucket,
                    mode=pk.kernel_mode_for(sel_bucket),
                    # None = sel_bucket bound: device-side segment
                    # numbering root-attaches in-flight-origin rows,
                    # so host `_seg_rows` counts can undercount the
                    # device populations (see the private-round note
                    # in models/incremental.py)
                    rank_rounds=None, map_rounds=None,
                )
                return mat2, xfer_fetch(
                    packed_out, label="incremental.out"
                ), sel_bucket

        res = dispatch_guarded("pool.converge", _dispatch,
                               host=lambda: None)
        if res is None:
            self._reset_all()
            self._host_fallback(pending)
            return 0
        self._mat, h, sel_bucket = res
        self._dead.clear()
        self._unpack(pending, h, tpad, sel_bucket)
        for eng in pending:
            ext = self._ext[eng]
            eng.n_dev = eng.cols.n
            ext.n = eng.n_dev
        self.dispatches += 1
        pk.count_device_dispatch()
        self._note_peak()
        tracer = get_tracer()
        if tracer.enabled:
            tracer.count("tenant.pool_dispatches")
            tracer.gauge("tenant.pool_bytes", self.device_bytes())
            tracer.gauge("tenant.pool_docs", self.doc_count())
        return 1

    def _unpack(self, pending, h, tpad: int, sel_bucket: int) -> None:
        """Split the fetch and route winners / orders back per doc:
        pool position -> (engine, host row) through the pending
        extents (position = off + host row, the extent invariant)."""
        exts = sorted(
            ((self._ext[eng].off, self._ext[eng].cap, eng)
             for eng in pending),
            key=lambda t: t[0],
        )
        offs = np.asarray([o for o, _, _ in exts], np.int64)
        engs = [e for _, _, e in exts]

        def locate(pos: int):
            i = int(np.searchsorted(offs, pos, side="right")) - 1
            return engs[i], pos - int(offs[i])

        s, b = tpad, sel_bucket
        win_local = h[:s]
        stream_seg = h[s : s + b]
        stream_row = h[s + b : s + 2 * b]
        sel_rows = h[s + 2 * b : s + 3 * b]
        for w in win_local[win_local >= 0]:
            eng, row = locate(int(sel_rows[w]))
            eng._win[eng._row_segkey(row)] = row
        m = stream_row >= 0
        rows_s, segs_s = stream_row[m], stream_seg[m]
        if len(rows_s):
            pool_rows = sel_rows[rows_s]
            cuts = np.r_[
                0, np.flatnonzero(segs_s[1:] != segs_s[:-1]) + 1,
                len(segs_s),
            ]
            for a, bnd in zip(cuts[:-1], cuts[1:]):
                eng, first = locate(int(pool_rows[a]))
                off = int(pool_rows[a]) - first
                chunk = (pool_rows[a:bnd] - off).tolist()
                eng._set_order(eng._row_segkey(chunk[0]), chunk)

    def _host_fallback(self, pending) -> None:
        """Exact host route for a flush that cannot (bounds) or could
        not (dead device) dispatch: each pending segment re-derives
        against the host columns; the unspliced tails simply wait for
        the next healthy flush — latency, never state."""
        for eng, segs in pending.items():
            for sk in segs:
                eng._host_order_segment(sk)
