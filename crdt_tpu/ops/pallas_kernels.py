"""Pallas TPU kernels for the merge hot path (SURVEY.md §7 stage 3).

Two ops earn hand-written kernels; everything else in :mod:`crdt_tpu.ops`
is already one fused XLA expression (sorts, scans, pointer doubling)
that Mosaic could not schedule better:

- ``ds_mask``   — delete-set membership for every item. The jnp path
  is a packed binary search (O(N log D)); this kernel is the fused
  dense compare (O(N·D)) that wins when D is small (the common case:
  a transaction's delete set holds a handful of ranges) because the
  ranges live in SMEM and the item columns stream through VMEM once —
  no [N, D] broadcast ever hits HBM.
- ``sv_deficit`` — the pairwise anti-entropy plan ``missing`` over
  [R, C] state vectors. The jnp path materializes the full [R, R, C]
  deficit tensor in HBM (4 GB at the north-star 1k replicas × 1k
  clients); this kernel tiles (i, j, c-chunk) over the grid so HBM
  holds only the [R, R] result and VMEM only (tile × chunk) blocks.

Both kernels run in interpret mode off-TPU so the differential tests
(tests/test_pallas.py) exercise the same code path on the CPU mesh.

Dtype strategy: the framework's clocks are int64 with < 2**40 packing
headroom (ops/device.py), but Mosaic wants 32-bit lanes. ``ds_mask``
is EXACT over the full 2**40 range via hi/lo split compares (clock ->
(clock >> 31, clock & 0x7fffffff), lexicographic i32 compares).
``sv_deficit`` subtracts the per-column minimum before narrowing —
deficits are invariant to per-column shifts, so the i32 magnitude
limit applies to the clock SPREAD between replicas (how far apart two
replicas' views are), not to absolute clock values; per-pair deficit
totals likewise accumulate in i32. The envelope is ENFORCED, not
assumed: a traced bound check routes batches whose spread/total could
reach 2**31 to the exact int64 scan fallback (lax.cond, so the check
works under jit/shard_map where gossip calls it).

The reference has no analogue of any of this — its merge is the
scalar Yjs integrate loop (/root/reference/crdt.js:294) and its sync
handshake diffs one peer at a time (crdt.js:286-291).
"""

from __future__ import annotations

import functools
import os

import jax

from jax import enable_x64
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# SMEM budget for the delete-range quintuple (5 arrays × _DS_MAX_RANGES
# int32) — a hard capacity limit, NOT the dispatch heuristic.
_DS_MAX_RANGES = 2048

# Dispatch crossover, measured on a real chip (N=131072 items, jitted
# callers like converge_maps): both paths are dispatch-bound ~20-30us
# up to D=64; beyond that the kernel's sequential D-step fori_loop
# only loses ground to the searchsorted path (1.7x slower at D=2048).
# Callers use pallas for D <= this and the jnp binary search above it.
_DS_PALLAS_CROSSOVER = 64

_LANES = 128
_DS_BLOCK_ROWS = 64  # rows of 128 lanes per program: 8192 items

_LO_BITS = 31
_LO_MASK = (1 << _LO_BITS) - 1


def backend() -> str:
    return jax.default_backend()


def use_pallas() -> bool:
    """Trace-time dispatch: pallas on TPU, jnp elsewhere.

    CRDT_TPU_PALLAS=0 forces jnp everywhere; =interpret forces the
    pallas kernels in interpreter mode (how the CPU-mesh tests run);
    =1 forces the pallas kernels — compiled on TPU, interpreter mode
    on any other backend (off-TPU there is nothing for Mosaic to
    compile, so =1 and =interpret coincide there).
    """
    flag = os.environ.get("CRDT_TPU_PALLAS", "auto")
    if flag == "0":
        return False
    if flag in ("1", "interpret"):
        return True
    return backend() == "tpu"


def _interpret() -> bool:
    if os.environ.get("CRDT_TPU_PALLAS") == "interpret":
        return True
    return backend() != "tpu"


def pallas_mode() -> str:
    """HOST-side dispatch decision: ``"jnp"`` | ``"pallas"`` |
    ``"interpret"``. Round 16: the env read must happen on the host,
    once per call, and flow DOWN into traced bodies as a static
    argument — ``use_pallas()``/``_interpret()`` called inside a
    ``jax.jit``/``lax.cond`` body bake the flag into the compiled
    artifact, so a later ``CRDT_TPU_PALLAS`` flip silently reuses the
    stale branch until an unrelated shape change recompiles
    (crdtlint CL702; :func:`converge_kernel_mode` is the same
    discipline with the width guard added)."""
    if not use_pallas():
        return "jnp"
    return "interpret" if _interpret() else "pallas"


def _pad_len(n: int, mult: int) -> int:
    return max(mult, ((n + mult - 1) // mult) * mult)


def _split_hi_lo(x: jnp.ndarray) -> tuple:
    """int64 -> (hi, lo) int32 with lexicographic order preserved for
    non-negative values; negatives (null sentinels) map to (-1, -1)."""
    hi = jnp.where(x < 0, -1, x >> _LO_BITS).astype(jnp.int32)
    lo = jnp.where(x < 0, -1, x & _LO_MASK).astype(jnp.int32)
    return hi, lo


# ---------------------------------------------------------------------------
# delete-set membership
# ---------------------------------------------------------------------------


def _ds_mask_kernel(
    dcl_ref, dsh_ref, dsl_ref, deh_ref, delo_ref, cl_ref, ckh_ref, ckl_ref, out_ref
):
    """One program = one (rows, 128) item block vs ALL ranges.

    Ranges sit in SMEM (scalar memory) and are walked with a
    fori_loop; each step is a full-block VPU compare, so the work is
    D vector ops over an 8192-lane block with zero HBM traffic beyond
    streaming the item columns once. Clocks are (hi, lo) i32 pairs;
    the lexicographic compares are exact over the full int64 range.
    """
    cl = cl_ref[:]
    ckh = ckh_ref[:]
    ckl = ckl_ref[:]
    acc = jnp.zeros(cl.shape, jnp.int32)
    num_ranges = dcl_ref.shape[0]

    def body(d, acc):
        dc = dcl_ref[d]
        sh, sl = dsh_ref[d], dsl_ref[d]
        eh, el = deh_ref[d], delo_ref[d]
        ge_start = (ckh > sh) | ((ckh == sh) & (ckl >= sl))
        lt_end = (ckh < eh) | ((ckh == eh) & (ckl < el))
        hit = (cl == dc) & ge_start & lt_end
        return acc | hit.astype(jnp.int32)

    # int32 bounds: the framework traces under x64, and an i64 loop
    # index fails Mosaic legalization
    out_ref[:] = jax.lax.fori_loop(jnp.int32(0), jnp.int32(num_ranges), body, acc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ds_mask_call(cl2, ckh2, ckl2, dcl, dsh, dsl, deh, delo, interpret):
    rows = cl2.shape[0]
    grid = (rows // _DS_BLOCK_ROWS,)
    block = (_DS_BLOCK_ROWS, _LANES)
    # trace with x64 off: the framework traces under x64 and the
    # promoted i64 literals (index maps, reductions) fail Mosaic
    # legalization; every input here is already explicit int32
    with enable_x64(False):
        return pl.pallas_call(
            _ds_mask_kernel,
            out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 5
            + [
                pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
            interpret=interpret,
            name="ds_mask",
        )(dcl, dsh, dsl, deh, delo, cl2, ckh2, ckl2)


def ds_mask(
    client: jnp.ndarray,  # [N] int32
    clock: jnp.ndarray,  # [N] int64/int32
    valid: jnp.ndarray,  # [N] bool
    d_client: jnp.ndarray,  # [D] int32
    d_start: jnp.ndarray,  # [D] int64/int32
    d_end: jnp.ndarray,  # [D] int64/int32
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """HOST entry for :func:`ds_mask_static`: resolves the kernel
    mode from the env when ``interpret`` is None. Never call from a
    traced body — the env read would bake into the compiled artifact
    (crdtlint CL702); traced callers use :func:`ds_mask_static` with
    a host-computed static."""
    return ds_mask_static(
        client, clock, valid, d_client, d_start, d_end,
        _interpret() if interpret is None else interpret,
    )


def ds_mask_static(
    client: jnp.ndarray,  # [N] int32
    clock: jnp.ndarray,  # [N] int64/int32
    valid: jnp.ndarray,  # [N] bool
    d_client: jnp.ndarray,  # [D] int32
    d_start: jnp.ndarray,  # [D] int64/int32
    d_end: jnp.ndarray,  # [D] int64/int32
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas counterpart of :func:`crdt_tpu.ops.deleteset.apply_mask`.

    Returns the same [N] bool mask, exact over the framework's full
    clock range. Requires D <= _DS_MAX_RANGES; callers dispatch via
    :func:`use_pallas` and fall back to the jnp path otherwise.
    ``interpret`` is a STATIC, host-computed on the other side of the
    trace boundary — this function is traced-safe (no ambient reads).
    """
    n = client.shape[0]
    d = d_client.shape[0]
    if d == 0:
        return jnp.zeros_like(valid)
    if d > _DS_MAX_RANGES:
        raise ValueError(f"ds_mask: {d} ranges > SMEM budget {_DS_MAX_RANGES}")

    npad = _pad_len(n, _DS_BLOCK_ROWS * _LANES)
    ckh, ckl = _split_hi_lo(clock.astype(jnp.int64))
    # padded item slots get client/clock -1: a real range never has
    # client -1, and a null (-1) range filler's half-open compare
    # rejects even the (-1, -1) padded clock (start == end)
    cl = jnp.full(npad, -1, jnp.int32).at[:n].set(client.astype(jnp.int32))
    ch = jnp.full(npad, -1, jnp.int32).at[:n].set(ckh)
    cg = jnp.full(npad, -1, jnp.int32).at[:n].set(ckl)
    dsh, dsl = _split_hi_lo(d_start.astype(jnp.int64))
    deh, delo = _split_hi_lo(d_end.astype(jnp.int64))

    out2 = _ds_mask_call(
        cl.reshape(-1, _LANES),
        ch.reshape(-1, _LANES),
        cg.reshape(-1, _LANES),
        d_client.astype(jnp.int32),
        dsh,
        dsl,
        deh,
        delo,
        interpret,
    )
    return out2.reshape(-1)[:n].astype(bool) & valid


# ---------------------------------------------------------------------------
# pairwise state-vector deficit (the anti-entropy plan)
# ---------------------------------------------------------------------------

_DEF_TI = 8  # i-tile (sublane batch)
_DEF_TJ = _LANES  # j-tile
_DEF_TC = _LANES  # C chunk per grid step


def _sv_deficit_kernel(svi_ref, svj_ref, out_ref):
    """One program = an (8 × 128) tile of [R, R] for ONE 128-wide C
    chunk; the innermost grid dimension walks C and accumulates into
    the same output tile (index map ignores the chunk index).

    deficit[i, j] = sum_c max(sv[i, c] - sv[j, c], 0): the broadcasts
    ride the two non-lane axes (i over the batch dim, j over the
    second-minor dim) so no relayout is needed.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    a = svi_ref[:]  # [TI, TC]
    b = svj_ref[:]  # [TJ, TC]
    diff = a[:, None, :] - b[None, :, :]  # [TI, TJ, TC]
    out_ref[:] += jnp.maximum(diff, 0).sum(axis=2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sv_deficit_call(svs, interpret):
    r, c = svs.shape
    grid = (r // _DEF_TI, r // _DEF_TJ, c // _DEF_TC)
    with enable_x64(False):  # see _ds_mask_call
        return pl.pallas_call(
            _sv_deficit_kernel,
            out_shape=jax.ShapeDtypeStruct((r, r), jnp.int32),
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (_DEF_TI, _DEF_TC),
                    lambda i, j, k: (i, k),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (_DEF_TJ, _DEF_TC),
                    lambda i, j, k: (j, k),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (_DEF_TI, _DEF_TJ), lambda i, j, k: (i, j), memory_space=pltpu.VMEM
            ),
            interpret=interpret,
            name="sv_deficit",
        )(svs, svs)


def sv_deficit(svs: jnp.ndarray,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """HOST entry for :func:`sv_deficit_static`: resolves the kernel
    mode from the env when ``interpret`` is None. Never call from a
    traced body (crdtlint CL702) — the read would bake into the
    compiled artifact; traced callers use :func:`sv_deficit_static`
    with a host-computed static."""
    return sv_deficit_static(
        svs, _interpret() if interpret is None else interpret
    )


def sv_deficit_static(svs: jnp.ndarray,
                      interpret: bool = False) -> jnp.ndarray:
    """Pallas counterpart of :func:`crdt_tpu.ops.statevec.missing`.

    [R, C] state vectors -> [R, R] total clocks i holds that j lacks,
    without the [R, R, C] HBM intermediate the jnp path builds.

    Exactness: deficits are invariant to subtracting any per-column
    offset, so the per-column minimum is removed before narrowing to
    i32 — absolute clocks may use the full int64 range. The i32 tile
    math is exact while the summed per-column spread stays below
    2**31; that bound is CHECKED on the traced values and batches
    beyond it (a replica lagging another by ~2e9 ops) fall back to
    the exact scan (:func:`crdt_tpu.ops.statevec.exact_missing`), so
    the anti-entropy plan is never silently wrapped.
    """
    from crdt_tpu.ops import statevec

    r, c = svs.shape
    centered = svs.astype(jnp.int64) - jnp.min(svs, axis=0, keepdims=True).astype(
        jnp.int64
    )
    # sum of per-column max spreads bounds every pair's deficit AND
    # (since all terms are >= 0) every single column's spread
    safe = jnp.sum(jnp.max(centered, axis=0)) < jnp.int64(2**31)

    def _pallas(cent):
        rpad = _pad_len(r, _DEF_TJ)
        cpad = _pad_len(c, _DEF_TC)
        # zero-padding is semantically neutral: phantom clients
        # contribute max(0-0, 0)=0, phantom replicas produce rows/cols
        # sliced away
        p = jnp.zeros((rpad, cpad), jnp.int32)
        p = p.at[:r, :c].set(cent.astype(jnp.int32))
        # `interpret` is the host-computed static from the wrapper:
        # an env read HERE would run at trace time inside the
        # lax.cond branch and bake the flag (crdtlint CL702)
        out = _sv_deficit_call(p, interpret)
        return out[:r, :r].astype(svs.dtype)

    def _exact(cent):
        return statevec.exact_missing(cent).astype(svs.dtype)

    return jax.lax.cond(safe, _pallas, _exact, centered)


# ---------------------------------------------------------------------------
# converge hot-path kernels (round 12, the sort diet): segmented
# Lamport argmax + document-order scatter
#
# The fused converge dispatch (ops/packed.py) stages its rows GROUPED
# by dense segment id — staging is a host radix pass that any columnar
# store pays at ingest — so the two primitives that used to burn the
# dispatch budget on global XLA argsorts become one-VMEM-pass kernels:
#
# - ``seg_argmax_scan``: per-run argmax over Lamport (client, position)
#   keys for CONTIGUOUS runs (child groups of the LWW map chain
#   forest, root-children runs per segment). A Hillis–Steele segmented
#   scan over the whole block resident in VMEM: log2(N) rounds of flat
#   rolls + selects, ZERO random gathers and ZERO sorts — the
#   replacement for the collapsed-key argsort + run-edge sort chain in
#   ``lww.map_winners``.
# - ``stream_scatter``: document-order assembly. Per-segment DFS ranks
#   are a permutation within each segment, so with contiguous segments
#   the final stream is out[offset[seg] + rank] = row — a permutation
#   scatter into VMEM, replacing the global ``argsort(skey2)``
#   document-order sort.
#
# Both run in interpret mode off-TPU (the tier-1 differential suite,
# tests/test_sort_diet.py) against the jnp oracles below, which are
# the SAME algorithms expressed as XLA ops (associative_scan /
# .at[].set) — the production fallback for non-TPU backends and for
# blocks past the VMEM width guard. Callers pass the dispatch decision
# as a STATIC mode argument (see :func:`converge_kernel_mode`) so an
# env-var flip between calls recompiles instead of reusing a stale
# cached branch.
# ---------------------------------------------------------------------------

# whole-block-in-VMEM width guard for the scan/scatter kernels: above
# this the jnp oracle path runs (a 1.6M-row scale shard would not fit
# the scan's working set in 16 MB of VMEM). Like _DS_PALLAS_CROSSOVER
# this is a dispatch bound, not a correctness bound.
_SCAN_PALLAS_MAX = 1 << 17

_SUBLANES = 8  # int32 min tile is (8, 128): pad rows to a multiple


def converge_kernel_mode(*widths: int) -> str:
    """STATIC dispatch decision for the fused converge's kernels:
    ``"pallas"`` (compiled), ``"interpret"`` (CPU-mesh tests), or
    ``"jnp"`` (kernels off, or any block past the VMEM width guard).
    Computed by the host wrapper per call and passed down as a static
    argument, so CRDT_TPU_PALLAS flips take effect on the next call
    instead of silently reusing a stale compiled branch."""
    if not use_pallas() or max(widths, default=0) > _SCAN_PALLAS_MAX:
        return "jnp"
    return "interpret" if _interpret() else "pallas"


def _rows2d(x: jnp.ndarray):
    """Flat [N] -> (R, 128) VMEM layout, R a multiple of the int32
    sublane tile."""
    n = x.shape[0]
    npad = _pad_len(n, _SUBLANES * _LANES)
    return jnp.pad(x, (0, npad - n), constant_values=-1).reshape(-1, _LANES)


def _flat_roll(x, s: int):
    """x[i - s] at flat position i of a row-major (R, 128) block
    (positions < s receive wrapped garbage — callers mask)."""
    a, b = s // _LANES, s % _LANES
    if b == 0:
        return pltpu.roll(x, shift=a, axis=0)
    y1 = pltpu.roll(pltpu.roll(x, shift=a, axis=0), shift=b, axis=1)
    y2 = pltpu.roll(pltpu.roll(x, shift=a + 1, axis=0), shift=b, axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane >= b, y1, y2)


def _seg_argmax_kernel(cl_ref, fl_ref, arg_ref):
    """Segmented inclusive argmax scan, whole block in VMEM.

    State per position: (best client, best arg, boundary-seen flag).
    Round k combines each position with the one 2^k before it unless a
    run boundary lies between them — the textbook segmented-scan
    operator, with the argmax tie rule "equal client keeps the EARLIER
    position" (clock ascends within a (run, client) group, and the
    sibling rule wants minimum clock at equal client — exactly the
    run-tail the sort-based path selects). log2(N) rounds of flat
    rolls + selects: no sorts, no gathers, no HBM round trips.
    """
    cl = cl_ref[:]
    fl = fl_ref[:]
    shape = cl.shape
    n = shape[0] * shape[1]
    arg = (
        jax.lax.broadcasted_iota(jnp.int32, shape, 0) * jnp.int32(_LANES)
        + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    )
    flat = arg  # flat position index (reused for the wrap mask)
    best_c, best_a, seen = cl, arg, fl
    s = 1
    while s < n:
        ok = flat >= s
        p_c = jnp.where(ok, _flat_roll(best_c, s), jnp.int32(-1))
        p_a = jnp.where(ok, _flat_roll(best_a, s), jnp.int32(0))
        p_f = jnp.where(ok, _flat_roll(seen, s), jnp.int32(1))
        take_prev = (seen == 0) & (
            (p_c > best_c) | ((p_c == best_c) & (p_a < best_a))
        )
        best_c = jnp.where(take_prev, p_c, best_c)
        best_a = jnp.where(take_prev, p_a, best_a)
        seen = seen | p_f
        s <<= 1
    arg_ref[:] = best_a


def seg_argmax_scan_jnp(client: jnp.ndarray,
                        flags: jnp.ndarray) -> jnp.ndarray:
    """jnp oracle of the segmented argmax scan: the identical
    segmented-scan operator via ``lax.associative_scan`` (log-depth
    shifted selects — still sortless; the Pallas kernel wins by
    keeping the whole working set in VMEM)."""
    n = client.shape[0]
    arg0 = jnp.arange(n, dtype=jnp.int32)

    def comb(a, b):
        c1, a1, f1 = a
        c2, a2, f2 = b
        blocked = f2 != 0
        take_prev = (~blocked) & (
            (c1 > c2) | ((c1 == c2) & (a1 < a2))
        )
        return (
            jnp.where(take_prev, c1, c2),
            jnp.where(take_prev, a1, a2),
            f1 | f2,
        )

    _, arg, _ = jax.lax.associative_scan(
        comb, (client.astype(jnp.int32), arg0, flags.astype(jnp.int32))
    )
    return arg


def seg_argmax_scan(client: jnp.ndarray, flags: jnp.ndarray, *,
                    mode: str) -> jnp.ndarray:
    """Per-position inclusive argmax over contiguous runs.

    ``client`` [N] int32 (the Lamport major key; -1 on padding rows),
    ``flags`` [N] int32 (1 = run start; padding rows are their own
    runs). Returns [N] int32: the position holding the run-prefix
    argmax — read at a run's END it is the run's argmax, i.e. the
    sibling-sorted run TAIL of the sort-based path. ``mode`` is the
    static :func:`converge_kernel_mode` decision.
    """
    if mode == "jnp":
        return seg_argmax_scan_jnp(client, flags)
    n = client.shape[0]
    cl2 = _rows2d(client.astype(jnp.int32))
    # _rows2d pads with -1: padded flag slots normalize to 1, so the
    # pad tail forms its own runs and never leaks into a real one
    fl2 = jnp.where(_rows2d(flags.astype(jnp.int32)) != 0, 1, 0).astype(
        jnp.int32
    )
    with enable_x64(False):
        out = pl.pallas_call(
            _seg_argmax_kernel,
            out_shape=jax.ShapeDtypeStruct(cl2.shape, jnp.int32),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=(mode == "interpret"),
            name="seg_argmax_scan",
        )(cl2, fl2)
    return out.reshape(-1)[:n]


NULL_I32 = -1


# input rows walked per grid step: one (chunk,) int32 block of targets
# DMA'd into SMEM (32 KiB), so SMEM use is fixed whatever the width
_SCATTER_CHUNK = 8192


def _stream_scatter_kernel(pos_ref, out_ref):
    """Permutation scatter: out[pos[i]] = i for in-range targets.

    Grid over input chunks; each step's targets sit in SMEM, where a
    scalar read is cheap, and the whole output block stays resident in
    VMEM across the grid (one writeback at the end). Mosaic refuses
    scalar stores to VMEM, so each target is written as an aligned
    (8, 128) tile: load, one-slot select, store. Sequential, but every
    access is on-chip, and the targets are unique by construction
    (per-segment DFS ranks + exclusive segment offsets), so there is no
    ordering hazard. Rows routed past the output width (invalid /
    padding) fall out via the bounds predicate.
    """
    step = pl.program_id(0)
    # explicit i32 scalars: the kernel body may be traced outside the
    # wrapper's enable_x64(False) window, where a weak python literal
    # promotes to i64 and breaks the i32 index arithmetic
    chunk = jnp.int32(pos_ref.shape[0])
    lanes = jnp.int32(_LANES)
    subl = jnp.int32(_SUBLANES)
    limit = jnp.int32(out_ref.shape[0] * out_ref.shape[1])
    tile = (_SUBLANES, _LANES)
    sub = jax.lax.broadcasted_iota(jnp.int32, tile, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile, 1)

    @pl.when(step == 0)
    def _():
        out_ref[:] = jnp.full(out_ref.shape, NULL_I32, jnp.int32)

    base = step * chunk

    def body(j, carry):
        p = pos_ref[j]

        @pl.when((p >= 0) & (p < limit))
        def _():
            row = p // lanes
            r0 = pl.multiple_of((row // subl) * subl, _SUBLANES)
            hit = (sub == row - r0) & (lane == p % lanes)
            cur = out_ref[pl.ds(r0, _SUBLANES), :]
            out_ref[pl.ds(r0, _SUBLANES), :] = jnp.where(
                hit, base + j, cur
            )

        return carry

    jax.lax.fori_loop(jnp.int32(0), chunk, body, jnp.int32(0))


def stream_scatter_jnp(pos: jnp.ndarray, n_out: int) -> jnp.ndarray:
    """jnp oracle of the document-order scatter: one XLA scatter with
    out-of-range targets dropped. Targets are unique by construction
    (rank + exclusive offset), so drop-mode scatter is deterministic
    here. Negative targets are redirected PAST the output before the
    scatter: ``.at[-1]`` would wrap to the last slot (jnp negative
    indexing), not drop."""
    idx = jnp.arange(pos.shape[0], dtype=jnp.int32)
    tgt = jnp.where(pos >= 0, pos, jnp.int32(n_out))
    return jnp.full(n_out, NULL_I32, jnp.int32).at[tgt].set(
        idx, mode="drop"
    )


def stream_scatter(pos: jnp.ndarray, n_out: int, *,
                   mode: str) -> jnp.ndarray:
    """Document-order assembly: ``out[pos[i]] = i`` over int32
    positions (targets outside [0, n_out) are dropped — callers route
    invalid rows there). Returns [n_out] int32 with -1 holes. ``mode``
    is the static :func:`converge_kernel_mode` decision."""
    if mode == "jnp":
        return stream_scatter_jnp(pos, n_out)
    n = pos.shape[0]
    chunk = min(_SCATTER_CHUNK, _pad_len(n, _SUBLANES * _LANES))
    pos1 = jnp.pad(pos.astype(jnp.int32), (0, _pad_len(n, chunk) - n),
                   constant_values=-1)
    opad = _pad_len(n_out, _SUBLANES * _LANES)
    with enable_x64(False):
        out = pl.pallas_call(
            _stream_scatter_kernel,
            out_shape=jax.ShapeDtypeStruct(
                (opad // _LANES, _LANES), jnp.int32
            ),
            grid=(pos1.shape[0] // chunk,),
            in_specs=[pl.BlockSpec((chunk,), lambda s: (s,),
                                   memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec((opad // _LANES, _LANES),
                                   lambda s: (0, 0)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=(mode == "interpret"),
            name="stream_scatter",
        )(pos1)
    return out.reshape(-1)[:n_out]
