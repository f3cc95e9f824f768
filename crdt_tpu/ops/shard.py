"""Multi-chip sharded converge — the staged packed pipeline spread
over a device mesh (round 13, ROADMAP item 1).

The single-chip cold path (:mod:`crdt_tpu.ops.packed`) stages the
whole union into one flat section array and converges it in one
dispatch on one device. This module cuts the SAME staged layout at
segment granularity across the mesh:

1. **Partition** — the union's rows group by full segment identity
   (parent ref + key), and whole segments greedy-balance across K
   shards by row count. YATA origins and LWW key chains never cross
   segments, so every shard's converge is independent — Wyllie
   doubling never crosses a chip. A DOMINATING sequence segment is
   pre-cut at DFS-suffix subtree granularity (round 23, the same
   :func:`crdt_tpu.ops.packed.dfs_suffix_boundaries` cut the staging
   split uses) and its pieces spread ACROSS chips — one hot list no
   longer bounds one shard. Inside each shard, anything still over
   the staging split width is re-cut by
   :func:`crdt_tpu.ops.packed._subtree_split`, so per-shard doubling
   runs ceil(log2(split width)) rounds, not
   ceil(log2(deepest structure)).
2. **Stage** — each shard runs the ordinary packed staging
   (layout-only), then every shard's eight sections are padded to
   COMMON bucket sizes and narrow-encoded with ONE shared encoding
   tuple, giving a [K, L] block a single compiled program serves.
3. **Converge** — ONE ``jax.shard_map`` program
   (:func:`crdt_tpu.parallel.gossip.make_packed_shard_step`): each
   device runs the full sortless fused converge on its shard; the
   only inter-chip traffic is the **boundary exchange** — the
   per-shard state vectors, narrow-encoded with the round-9 codec as
   the wire format, all-gathered and max-merged into the swarm SV on
   device (the fetch audits the merge against the host-staged
   vectors and raises on divergence).
4. **Assemble** — the host maps each shard's block-local results
   through its own translation tables and row map; concatenating the
   per-shard streams with disjoint segment ids reproduces the
   single-chip result BIT-identically (tests/test_shard.py pins
   cache + snapshot + SV equality at 2/4/8-way).

Route selection: ``CRDT_TPU_SHARDS`` (unset = all visible devices,
``0``/``1`` disables) and ``CRDT_TPU_SHARD_MIN_ROWS`` (default 2^15 —
below it the extra per-shard fixed costs beat the division). The
one-shot replay, the streaming executor's stream shards, and the
fleet replay all take this route through :func:`active_for`.

Evidence: ``shard.dispatches`` / ``shard.boundary_bytes`` /
``shard.seam_rows`` counters and the ``shard.shards`` gauge (README
"Observability" registry).
"""

from __future__ import annotations

import os
import threading
from typing import NamedTuple, Optional

import numpy as np

from jax import enable_x64
from crdt_tpu.obs.tracer import get_tracer
from crdt_tpu.ops.device import (
    NULLI,
    bucket_grid,
    record_staged_widths,
    wide_staging_forced,
    xfer_fetch,
    xfer_put,
)
from crdt_tpu.ops import packed

SHARD_ENV = "CRDT_TPU_SHARDS"
MIN_ROWS_ENV = "CRDT_TPU_SHARD_MIN_ROWS"
MIN_ROWS_DEFAULT = 1 << 15


def shard_count(n_shards: Optional[int] = None) -> int:
    """Resolved shard count: explicit arg, else ``CRDT_TPU_SHARDS``,
    else every visible device. 0/1 means the sharded route is off."""
    if n_shards is not None:
        return max(0, int(n_shards))
    raw = os.environ.get(SHARD_ENV, "")
    if raw != "":
        try:
            return max(0, int(raw))
        except ValueError:
            pass
    import jax

    return len(jax.devices())


def min_rows() -> int:
    raw = os.environ.get(MIN_ROWS_ENV, "")
    if raw != "":
        try:
            return max(0, int(raw))
        except ValueError:
            pass
    return MIN_ROWS_DEFAULT


def active_for(n_rows: int,
               n_shards: Optional[int] = None) -> bool:
    """Should this union take the sharded route? >1 shard resolved
    AND the union is big enough to amortize the per-shard costs."""
    return shard_count(n_shards) > 1 and n_rows >= min_rows()


class ShardPlan(NamedTuple):
    """Host-side staging result of :func:`stage`: K repadded per-shard
    plans + the common-encoded [K, L] section block + the narrow
    boundary wire. Like a :class:`~crdt_tpu.ops.packed.PackedPlan`,
    a sharded plan is consumed by its one dispatch (the block is
    donated)."""

    plans: tuple            # per-shard PackedPlan (repadded metadata)
    row_maps: tuple         # per-shard caller-row index arrays
    block: np.ndarray       # [K, L] staged sections, shared encoding
    wire: np.ndarray        # [K, W] boundary wire (SV + meta)
    encs: tuple             # shared per-section encodings
    num_segments: int       # common S bucket
    seq_bucket: int         # common B bucket
    map_bucket: int         # common M bucket
    rank_rounds: int        # max over shards
    map_rounds: int         # max over shards
    sv_clients: np.ndarray  # dense rank -> raw client id
    sv_host: np.ndarray     # [K, C] host copy of the per-shard SVs
    sv_mode: str            # wire encoding: 'i16' / 'hilo' / 'wide'
    n_rows: int             # total valid rows staged
    widths: dict = {}       # per-section chosen widths (one record
                            # at upload, like packed._put_mat)
    wide_bytes: int = 0     # pre-diet byte baseline for the record


class ShardResult(NamedTuple):
    """Merged caller-space result — duck-compatible with
    :class:`~crdt_tpu.ops.packed.PackedResult` (the replay gather
    consumes it unchanged), plus the boundary exchange's merged
    swarm state vector."""

    win_rows: np.ndarray
    stream_seg: np.ndarray
    stream_row: np.ndarray
    hard_rows: tuple = ()
    global_sv: Optional[np.ndarray] = None  # [C] dense-rank clocks+1
    sv_clients: Optional[np.ndarray] = None


def _chain_weights(counts: np.ndarray,
                   origin_counts: np.ndarray) -> np.ndarray:
    """Greedy-partition weights honoring CHAIN DEPTH, not just row
    count (ROADMAP item 1 remainder): a shard's converge runs
    ``ceil(log2(longest chain))`` pointer-doubling rounds — the
    Wyllie bound — over ALL its rows, so a deep append chain costs
    ``rows x log2(depth)`` where an equally-sized wide segment costs
    ``rows x 1``. Depth is bounded above by the segment's
    origin-bearing rows + 1 (every chain hop needs a live origin;
    root-attached rows never deepen a chain), which is exact for
    pure chains and errs toward over-weighting branchy segments —
    the safe direction for balance. Returns per-segment integer
    weights ``rows * max(1, ceil(log2(1 + origin_rows)))``."""
    depth = np.maximum(origin_counts, 0) + 1
    rounds = np.maximum(
        1, np.ceil(np.log2(np.maximum(depth, 1))).astype(np.int64)
    )
    return np.asarray(counts, np.int64) * rounds


def _partition(cols, K: int):
    """Whole-segment greedy partition of the union's valid rows into
    K depth-weighted shards (:func:`_chain_weights` — segments weigh
    ``rows x ceil(log2(chain_len))``, the Wyllie rounds bound, so a
    deep chain and a wide segment of equal row count no longer read
    as equal work). Returns ``(shard_rows, pb_tag)``: a list of
    caller-row index arrays (some possibly empty: fewer segments
    than shards) and the cross-shard pre-cut's parent-ref tag column
    (None when nothing was pre-cut — see below), or None for an
    empty union.

    A DOMINATING sequence segment (more rows than ``total // K``) no
    longer bounds one shard: it is pre-cut at DFS-suffix subtree
    granularity (:func:`crdt_tpu.ops.packed.dfs_suffix_boundaries` —
    the exact round-23 staging cut, so concatenating the pieces in
    piece order reproduces the segment's stream bit-for-bit) and the
    pieces assign MONOTONICALLY across shards. ``pb_tag`` carries
    per-piece offsets of ``(piece+1) << 45`` for the ``parent_b``
    column: each piece becomes its own full segment identity inside
    its shard (the offsets cannot collide with real parent refs, all
    < 2^44 by the guard), pieces order by tag within a shard and by
    shard across shards — so the assembler's same-parent run merge
    stitches them back in exact document order with no new seam
    plumbing.

    Duplicate ids are dropped GLOBALLY first (keep the first caller
    row, packed._stage's rule): equal-id rows under different parents
    would land in different shards, where no shard-local dedup could
    see the pair — the single-chip oracle keeps only the leftmost, so
    the sharded route must too.

    Multi-doc unions (round 14: a ``doc`` column is present with >1
    distinct doc) partition by DOC first: a doc's segments — and
    therefore its whole converge — stay co-located on one chip, docs
    greedy-balance across shards by row count, and the duplicate drop
    and segment identity are doc-scoped (two docs legitimately reuse
    the same (client, clock) ids and parent refs). Within a
    single-doc union the whole-segment partition is unchanged."""
    valid = np.asarray(cols["valid"], bool)
    idx = np.flatnonzero(valid)
    if not len(idx):
        return None
    dv = (np.asarray(cols["doc"], np.int64)[idx]
          if "doc" in cols else np.zeros(len(idx), np.int64))
    multi_doc = len(idx) > 0 and int(dv.max()) != int(dv.min())
    cl_v = np.asarray(cols["client"], np.int64)[idx]
    ck_v = np.asarray(cols["clock"], np.int64)[idx]
    so = np.lexsort((np.arange(len(idx)), ck_v, cl_v, dv))
    dup = np.r_[
        False,
        (cl_v[so][1:] == cl_v[so][:-1]) & (ck_v[so][1:] == ck_v[so][:-1])
        & (dv[so][1:] == dv[so][:-1]),
    ]
    if dup.any():
        keep = np.sort(so[~dup])
        idx, dv = idx[keep], dv[keep]
    oc_live = np.asarray(cols["origin_client"], np.int64)[idx] >= 0
    if multi_doc:
        # doc-first: greedy balance whole docs, heaviest first into
        # the lightest bin (fewer docs than shards leaves shards
        # empty — the all-padding shard body handles them). Doc
        # weight honors chain depth like the segment cut: a doc's
        # rounds bound is log2 of its chained rows.
        docs_u, doc_inv, doc_counts = np.unique(
            dv, return_inverse=True, return_counts=True
        )
        doc_oc = np.bincount(
            doc_inv, weights=oc_live, minlength=len(docs_u)
        ).astype(np.int64)
        weights = _chain_weights(doc_counts, doc_oc)
        bins = np.zeros(len(docs_u), np.int64)
        loads = np.zeros(K, np.int64)
        for d in np.argsort(-weights, kind="stable"):
            b = int(np.argmin(loads))
            bins[d] = b
            loads[b] += int(weights[d])
        shard_of_row = bins[doc_inv]
        return [idx[shard_of_row == k] for k in range(K)], None
    pir = np.asarray(cols["parent_is_root"], bool)[idx]
    pa = np.asarray(cols["parent_a"], np.int64)[idx]
    pb = np.asarray(cols["parent_b"], np.int64)[idx]
    kid = np.asarray(cols["key_id"], np.int64)[idx]
    order = np.lexsort((kid, pb, pa, pir))
    same = (
        (pir[order][1:] == pir[order][:-1])
        & (pa[order][1:] == pa[order][:-1])
        & (pb[order][1:] == pb[order][:-1])
        & (kid[order][1:] == kid[order][:-1])
    )
    seg_sorted = np.cumsum(np.r_[True, ~same]) - 1
    seg = np.empty(len(idx), np.int64)
    seg[order] = seg_sorted
    counts = np.bincount(seg)
    seg_oc = np.bincount(
        seg, weights=oc_live, minlength=len(counts)
    ).astype(np.int64)

    # cross-shard subtree pre-cut (round 23): a dominating sequence
    # segment's DFS-suffix pieces spread across chips instead of
    # bounding one shard. The pieces' loads pre-seed the greedy bins;
    # cut segments skip the whole-segment loop below.
    pb_tag = None
    piece_of = {}     # seg id -> (rows_s, piece index per row)
    loads = np.zeros(K, np.int64)
    big = np.flatnonzero(
        counts > max(2048, len(idx) // max(K, 1))
    )
    if len(big):
        cl_i = np.asarray(cols["client"], np.int64)[idx]
        ck_i = np.asarray(cols["clock"], np.int64)[idx]
        rr_i = (np.asarray(cols["right_client"], np.int64)[idx]
                if "right_client" in cols
                else np.full(len(idx), -1, np.int64))
        oc_i = np.asarray(cols["origin_client"], np.int64)[idx]
        ock_i = np.asarray(cols["origin_clock"], np.int64)[idx]
        kid_i = np.asarray(cols["key_id"], np.int64)[idx]
        pb_i = np.asarray(cols["parent_b"], np.int64)[idx]
        uniq_cl = np.unique(cl_i)
        # id-key packing + tag-offset guards, packed._stage's bounds:
        # skip the pre-cut (never the route) when a bound trips
        feasible = (
            len(uniq_cl) < (1 << 22)
            and int(ck_i.max(initial=0)) < (1 << packed._CLOCK_BITS)
            and int(ock_i.max(initial=0)) < (1 << packed._CLOCK_BITS)
            and int(np.abs(pb_i).max(initial=0)) < (1 << 44)
        )
        for s in (big.tolist() if feasible else []):
            rows_s = np.flatnonzero(seg == s)
            if (kid_i[rows_s] >= 0).any() or (rr_i[rows_s] >= 0).any():
                continue  # map segments / right origins stay whole
            # compact-local forest, exactly as packed._stage builds
            # it: rows in id order, origins resolved same-segment
            so_l = np.lexsort((ck_i[rows_s], cl_i[rows_s]))
            rs = rows_s[so_l]
            cd = np.searchsorted(uniq_cl, cl_i[rs])
            ikey_l = (cd << packed._CLOCK_BITS) | ck_i[rs]
            ocd = np.searchsorted(uniq_cl, np.clip(oc_i[rs], 0, None))
            okey_l = np.where(
                oc_i[rs] >= 0,
                (ocd << packed._CLOCK_BITS) | ock_i[rs], np.int64(-1),
            )
            p = np.searchsorted(ikey_l, okey_l)
            pc = np.clip(p, 0, len(rs) - 1)
            par_l = np.where(
                (okey_l >= 0) & (ikey_l[pc] == okey_l), pc, -1
            )
            # hostile cyclic origins: the unsplit path's semantics
            # must stand — leave the segment whole
            m = len(rs)
            f = np.where(par_l >= 0, par_l,
                         np.arange(m, dtype=np.int64))
            for _ in range(max(1, (max(m, 2) - 1).bit_length() + 1)):
                f = f[f]
            if (par_l[f] >= 0).any():
                continue
            width = -(-m // K)
            pos, cuts = packed.dfs_suffix_boundaries(
                par_l, cd, (m - 1) - np.arange(m, dtype=np.int64),
                width, max_pieces=2 * K + 2,
            )
            if len(cuts) < 2:
                continue
            piece = (np.searchsorted(cuts, pos, side="right")
                     - 1).astype(np.int64)
            np_c = len(cuts)
            pshard = (piece * K) // np_c  # monotone piece -> shard
            prows = np.bincount(piece, minlength=np_c)
            in_piece = (par_l >= 0) & (
                piece[np.clip(par_l, 0, m - 1)] == piece
            )
            poc = np.bincount(
                piece, weights=in_piece, minlength=np_c
            ).astype(np.int64)
            pw = _chain_weights(prows, poc)
            for j in range(np_c):
                loads[(j * K) // np_c] += int(pw[j])
            if pb_tag is None:
                pb_tag = np.zeros(
                    len(np.asarray(cols["valid"])), np.int64
                )
            pb_tag[idx[rs]] = (piece + 1) << 45
            piece_of[s] = (rs, pshard)

    # greedy balance by DEPTH-WEIGHTED load, heaviest segments first
    # into the lightest bin (a single huge segment no longer bounds
    # one shard — its pre-cut pieces are already seeded above; only
    # refused shapes keep the honest whole-segment limit)
    weights = _chain_weights(counts, seg_oc)
    bins = np.zeros(len(counts), np.int64)
    for s in np.argsort(-weights, kind="stable"):
        if s in piece_of:
            continue
        b = int(np.argmin(loads))
        bins[s] = b
        loads[b] += int(weights[s])
    shard_of_row = bins[seg]
    for s, (rs, pshard) in piece_of.items():
        shard_of_row[rs] = pshard
    return [idx[shard_of_row == k] for k in range(K)], pb_tag


# per-section pad values for the common-bucket repad (seg_off pads
# with 0: offsets of absent segments are never read through a live
# sseg)
_PAD_VALS = {"seg_off": 0}


def _repad_sections(secs, S: int, B: int, M: int,
                    S2: int, B2: int, M2: int):
    """Pad one shard's eight sections from its natural buckets to the
    common ones. Only ``seq_first`` is position-dependent (its root
    block sits at offset B); every other section pads at the tail —
    values are block-local indices below their own bucket, unchanged
    by a wider block."""
    out = []
    for name, arr in secs:
        pad = _PAD_VALS.get(name, -1)
        if name == "seq_first":
            new = np.full(B2 + S2, -1, arr.dtype)
            new[:B] = arr[:B]
            new[B2:B2 + S] = arr[B:B + S]
        else:
            tgt = {"seq_seg": B2, "seg_off": S2, "seq_parent": B2,
                   "seq_next": B2, "map_key": M2, "map_chain_end": M2,
                   "map_root_end": S2}[name]
            new = np.full(tgt, pad, arr.dtype)
            new[: len(arr)] = arr
        out.append((name, new))
    return out


def _empty_sections(S2: int, B2: int, M2: int):
    """An all-padding shard (fewer segments than shards): the fused
    body on pure padding yields no winners and an all-hole stream."""
    z = np.int64
    return [
        ("seq_seg", np.full(B2, -1, z)),
        ("seg_off", np.zeros(S2, z)),
        ("seq_parent", np.full(B2, -1, z)),
        ("seq_next", np.full(B2, -1, z)),
        ("seq_first", np.full(B2 + S2, -1, z)),
        ("map_key", np.full(M2, -1, z)),
        ("map_chain_end", np.full(M2, -1, z)),
        ("map_root_end", np.full(S2, -1, z)),
    ]


def _empty_plan(S2: int, B2: int, M2: int) -> packed.PackedPlan:
    return packed.PackedPlan(
        mat=None, n=0, num_segments=S2, seq_bucket=B2, map_bucket=M2,
        order=np.empty(0, np.int32), clients=np.empty(0, np.int64),
        rank_rounds=2, map_rounds=2,
        map_back=np.full(M2, NULLI, np.int32),
        seq_back=np.full(B2, NULLI, np.int32),
        seg_counts=np.zeros(S2, np.int64),
    )


def stage(cols, n_shards: Optional[int] = None) -> Optional[ShardPlan]:
    """Partition + per-shard staging + common-bucket encode (the
    tracer's ``pack`` span covers the per-shard layout passes).
    Returns None when the union cannot take the sharded route (a
    shard exceeded the packed bounds, no valid rows, or <2 shards
    resolved) — callers fall back to the single-chip path."""
    K = shard_count(n_shards)
    if K <= 1:
        return None
    part = _partition(cols, K)
    if part is None:
        return None
    shard_rows, pb_tag = part

    col_arrays = {k: np.asarray(v) for k, v in cols.items()}
    if pb_tag is not None:
        # cross-shard pre-cut: the piece tags ride a COPY of the
        # parent_b column (the caller's cols stay untouched — a
        # fallback to the single-chip path must see the original
        # refs). Tags only shape segment identity and pref order;
        # assembly decodes parents from dec, never from this column.
        col_arrays["parent_b"] = (
            col_arrays["parent_b"].astype(np.int64) + pb_tag
        )
    layouts = []  # (plan, secs, rows) per non-empty shard; None empty
    for rows_k in shard_rows:
        if not len(rows_k):
            layouts.append(None)
            continue
        sub = {k: v[rows_k] for k, v in col_arrays.items()}
        secs: list = []
        plan = packed.stage(sub, _sections=secs)
        if plan is None:
            return None
        layouts.append((plan, secs, rows_k))

    live = [lay for lay in layouts if lay is not None]
    if not live:
        return None
    S2 = max(lay[0].num_segments for lay in live)
    B2 = max(lay[0].seq_bucket for lay in live)
    M2 = max(lay[0].map_bucket for lay in live)
    rank2 = max(lay[0].rank_rounds for lay in live)
    map2 = max(lay[0].map_rounds for lay in live)

    wide = wide_staging_forced()
    padded = []
    for lay in layouts:
        if lay is None:
            padded.append(_empty_sections(S2, B2, M2))
        else:
            plan, secs, _ = lay
            padded.append(_repad_sections(
                secs, plan.num_segments, plan.seq_bucket,
                plan.map_bucket, S2, B2, M2,
            ))
    # ONE shared encoding tuple: a section narrows only when it
    # narrows on EVERY shard (forcing hilo elsewhere is exact)
    force = []
    for i, name in enumerate(packed.SECTION_NAMES):
        kind = "i32" if wide else packed._SECTION_NARROW[name]
        if not wide:
            for secs_k in padded:
                arr = secs_k[i][1]
                enc = (packed._narrow_ident(arr) if kind == "i16"
                       else packed._narrow_delta_ref(arr))
                if enc is None:
                    kind = "hilo"
                    break
        force.append(kind)
    flats = []
    encs = widths = None
    for secs_k in padded:
        flat, encs, widths = packed._encode_sections(
            secs_k, wide, force=None if wide else tuple(force)
        )
        flats.append(flat)
    block = np.stack(flats)

    # repadded per-shard plans (assembly metadata at common buckets)
    plans = []
    row_maps = []
    for lay in layouts:
        if lay is None:
            plans.append(_empty_plan(S2, B2, M2))
            row_maps.append(np.empty(0, np.int64))
            continue
        plan, _, rows_k = lay
        mb = np.full(M2, NULLI, np.int32)
        mb[: len(plan.map_back)] = plan.map_back
        sb = np.full(B2, NULLI, np.int32)
        sb[: len(plan.seq_back)] = plan.seq_back
        sc = np.zeros(S2, np.int64)
        sc[: len(plan.seg_counts)] = plan.seg_counts
        ws = plan.win_src
        if ws is not None:
            # identity pad to the common bucket (pad slots read their
            # own — always empty — winner), keeping _assemble_result's
            # index math valid at S2
            ws2 = np.arange(S2, dtype=np.int64)
            ws2[: len(ws)] = ws
            ws = ws2
        plans.append(plan._replace(
            num_segments=S2, seq_bucket=B2, map_bucket=M2,
            map_back=mb, seq_back=sb, seg_counts=sc, win_src=ws,
        ))
        row_maps.append(np.asarray(rows_k, np.int64))

    # the boundary wire: each shard's partial SV over one shared
    # dense client table — the whole inter-chip payload of a sharded
    # round (seam/row evidence rides the tracer counters, never the
    # wire)
    client = col_arrays["client"].astype(np.int64)
    clock = col_arrays["clock"].astype(np.int64)
    valid = col_arrays["valid"].astype(bool)
    uniq = np.unique(client[valid])
    C = max(len(uniq), 1)
    svs = np.zeros((K, C), np.int64)
    n_rows = sum(len(rows_k) for rows_k in shard_rows)
    for k, rows_k in enumerate(shard_rows):
        if len(rows_k):
            r = np.searchsorted(uniq, client[rows_k])
            np.maximum.at(svs[k], r, clock[rows_k] + 1)
    # the wire narrows with the round-9 codec: SV entries are
    # clocks+1, which for real swarms fit ONE identity int16 stretch
    # (the handshake then costs 2 bytes per client per shard, a small
    # fraction of the staged upload); hi/lo below 2^31, int64 past it
    top = int(svs.max(initial=0))
    if wide or top >= (1 << 31):
        sv_mode = "wide"
        wire = svs
    elif top <= (1 << 15) - 1:
        sv_mode = "i16"
        wire = svs.astype(np.int16)
    else:
        sv_mode = "hilo"
        svh, svl = packed._split_hi_lo(svs)
        wire = np.concatenate([svh, svl], axis=1)

    return ShardPlan(
        plans=tuple(plans),
        row_maps=tuple(row_maps),
        block=block,
        wire=wire,
        encs=encs,
        num_segments=S2,
        seq_bucket=B2,
        map_bucket=M2,
        rank_rounds=rank2,
        map_rounds=map2,
        sv_clients=uniq,
        sv_host=svs,
        sv_mode=sv_mode,
        n_rows=n_rows,
        widths=dict(widths or {}),
        wide_bytes=sum(
            5 * bucket_grid(lay[0].n, floor=6) * 4 for lay in live
        ),
    )


# compiled shard_map programs, keyed on every static of the step; the
# stager thread (models/streaming) reaches this module concurrently
_STEP_CACHE: dict = {}
_CACHE_LOCK = threading.Lock()


def _get_step(splan: ShardPlan, mode: str):
    import jax

    from crdt_tpu.parallel.gossip import make_mesh, make_packed_shard_step

    K = splan.block.shape[0]
    key = (
        tuple(id(d) for d in jax.devices()[:K]), K,
        splan.num_segments, splan.seq_bucket, splan.map_bucket,
        splan.rank_rounds, splan.map_rounds, splan.encs, mode,
        splan.sv_mode, splan.wire.shape[1], len(splan.sv_clients),
    )
    with _CACHE_LOCK:
        step = _STEP_CACHE.get(key)
        if step is None:
            mesh = make_mesh(K)
            step = make_packed_shard_step(
                mesh,
                num_segments=splan.num_segments,
                seq_bucket=splan.seq_bucket,
                map_bucket=splan.map_bucket,
                rank_rounds=splan.rank_rounds,
                map_rounds=splan.map_rounds,
                encs=splan.encs,
                mode=mode,
                sv_len=max(len(splan.sv_clients), 1),
                sv_mode=splan.sv_mode,
            )
            _STEP_CACHE[key] = step
    return step


def converge_async(splan: ShardPlan):
    """ENQUEUE the sharded converge: one accounted upload of the
    [K, L] block (donated) + the boundary wire, one shard_map
    dispatch. Returns a handle for :func:`converge_fetch` — the same
    two-step seam the streaming executor drives on the single-chip
    path."""
    K = splan.block.shape[0]
    mode = packed.kernel_mode_for(splan.map_bucket, splan.seq_bucket)
    step = _get_step(splan, mode)
    tracer = get_tracer()
    with tracer.span("converge.dispatch"), enable_x64(True):
        record_staged_widths(
            splan.widths, splan.block.nbytes, splan.wide_bytes
        )
        blk = xfer_put(splan.block, label="shard.mat")
        wire = xfer_put(splan.wire, label="shard.wire")
        out, gsv = step(blk, wire)
    if tracer.enabled:
        tracer.count("shard.dispatches")
        tracer.gauge("shard.shards", K)
        # the boundary payload crossing the mesh per round (every
        # shard's wire row travels to the other shards once in the
        # gather) — THE number the multichip gate compares against
        # the staged upload
        tracer.count("shard.boundary_bytes", int(splan.wire.nbytes))
        n_seams = sum(len(p.seam_rows) for p in splan.plans)
        if n_seams:
            tracer.count("shard.seam_rows", n_seams)
    return splan, out, gsv


def converge_fetch(handle) -> ShardResult:
    """Block on an in-flight sharded dispatch and assemble the K
    per-shard results into ONE caller-space result (the tracer's
    ``converge.fetch`` span). Fails LOUDLY when the device-side
    boundary exchange disagrees with the host staging — a shard that
    silently dropped rows or mis-decoded the wire must never
    propagate a wrong document."""
    import jax

    splan, out, gsv = handle
    S2, B2 = splan.num_segments, splan.seq_bucket
    with get_tracer().span("converge.fetch"):
        jax.block_until_ready(out)  # execution wait, not transfer
        h = xfer_fetch(out, label="shard.out")
        gs = xfer_fetch(gsv, label="shard.sv")
    want = splan.sv_host.max(axis=0) if len(splan.sv_host) else gs
    if len(splan.sv_clients) and not np.array_equal(
            gs[: len(splan.sv_clients)], want):
        raise RuntimeError(
            "sharded converge boundary exchange diverged from the "
            "host-staged state vectors (wire codec or gather fault)"
        )
    win_parts = []
    seg_parts = []
    row_parts = []
    hard: list = []
    for k, plan in enumerate(splan.plans):
        rm = splan.row_maps[k]
        res = packed._assemble_result(plan, h[k])
        if len(rm):
            win_parts.append(np.where(
                res.win_rows >= 0,
                rm[np.clip(res.win_rows, 0, len(rm) - 1)], NULLI,
            ))
            row_parts.append(np.where(
                res.stream_row >= 0,
                rm[np.clip(res.stream_row, 0, len(rm) - 1)], NULLI,
            ))
            hard.extend(int(rm[r]) for r in res.hard_rows)
        else:
            win_parts.append(np.full(S2, NULLI, np.int64))
            row_parts.append(np.full(B2, NULLI, np.int64))
        # disjoint segment ids across shards: offset by the shard's
        # block position (values only cut runs in the assembler)
        seg_parts.append(np.where(
            res.stream_seg >= 0, res.stream_seg + k * S2, NULLI
        ))
    return ShardResult(
        win_rows=np.concatenate(win_parts),
        stream_seg=np.concatenate(seg_parts).astype(np.int32),
        stream_row=np.concatenate(row_parts),
        hard_rows=tuple(hard),
        global_sv=gs,
        sv_clients=splan.sv_clients,
    )


def converge(splan: ShardPlan) -> ShardResult:
    """Stage -> one sharded dispatch -> one fetch (the production
    two-step seam, synchronously)."""
    return converge_fetch(converge_async(splan))
