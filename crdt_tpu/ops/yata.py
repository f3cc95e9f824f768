"""YATA sequence-ordering kernel.

Order semantics (must match ``Engine``'s faithful integrate scan):

- The document order of a sequence is the depth-first traversal of the
  *origin tree*: every item hangs under its left origin (or the
  sequence's virtual root), and a node is emitted before its subtree.
  Subtrees always ride with their root: the Yjs conflict scan never
  separates an item from its origin-descendants (case 2 of the scan
  either adopts or skips whole subtrees).
- What the scan does decide is the ORDER OF SIBLINGS within one origin
  group. For groups where no member's right origin is another member
  ("no attachments" — true for every append-only workload), the order
  is ascending client id with DESCENDING clock within one client: a
  later same-client same-origin sibling hits the scan's break rule and
  is placed BEFORE its predecessor, and an induction over the scan
  shows attachment-free placement otherwise always lands a new sibling
  directly after the last smaller-client sibling, whatever the right
  origins are. The device key (client, ~clock) is therefore EXACT for
  every attachment-free group, duplicates included — the same
  lexicographic rule the map winner kernel uses (ops/lww.py). In the
  general (attachment) case the order follows the full scan rule: a
  new sibling lands after the last smaller-client sibling positioned
  before its *stop point* (its right origin, or the first
  larger-client sibling with the same right origin); larger-client
  siblings with different right origins are scanned through
  transparently.

The split of labor is therefore:

  host   sibling ranks ONLY for groups containing right-origin
         attachments (concurrent inserts anchored inside the same
         sibling set — an exact group-local replay of the scan,
         O(g^2) worst case on that group's g siblings only; g is the
         number of concurrent same-position inserts, bounded by the
         writers racing one position, not by doc size);
  device everything else, vectorized: group detection,
         (client, ~clock) sibling ranks, and the full tree-DFS
         ranking — one lexsort for sibling adjacency, pointer
         doubling to climb last-child chains, successor pointers, and
         Wyllie list ranking. O(n log n) work in O(log n) gather
         rounds, independent of tree depth (the reference's scalar
         integrate is O(n) sequential per chain, crdt.js:294).

Round 12 (the sort diet) narrowed where this full-width kernel runs:
the staged cold replay now precomputes the sibling adjacency and
first-child tables on the host (``ops.packed._stage``, shipped as
staged sections) and ranks them sortlessly with the Pallas
document-order scatter (``ops.pallas_kernels.stream_scatter``), so
``order_sequences``/``tree_order_ranks`` remain the engine-mode
merge path and the differential oracle the staged kernels are tested
against — same semantics, two routes.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax

from jax import enable_x64
import jax.numpy as jnp

from crdt_tpu.ops.device import (
    NULLI,
    dfs_ranks,
    lexsort,
    pack_id,
    run_edge_lookup,
    scatter_perm,
    searchsorted_ids,
)


@partial(jax.jit, static_argnames=("num_segments",))
def tree_order_ranks(
    seg,  # [N] int32 dense sequence id (-1 = not a sequence item)
    parent_idx,  # [N] int32 origin-tree parent (item index), NULLI = root
    key1,  # [N] int64 primary sibling key (scan rank or client)
    key2,  # [N] int64 secondary sibling key (0 or NEGATED clock)
    valid,  # [N] bool
    num_segments: int,
):
    """DFS position of every item within its sequence (tombstones
    included). Returns (rank[N] int32, seq_len[num_segments] int32)."""
    n = seg.shape[0]
    m = n + num_segments
    is_seq = valid & (seg >= 0)

    parent = jnp.where(
        is_seq & (parent_idx >= 0), parent_idx, n + jnp.maximum(seg, 0)
    )
    parent = jnp.where(is_seq, parent, m)  # invalid rows -> overflow bucket

    # sibling adjacency: sort by (parent, key1, key2). Rows routed to
    # the overflow slot m are exactly the non-sequence rows, so every
    # run with parent < m is a clean sibling group.
    order = lexsort([parent, key1, key2])
    p_s = parent[order]
    same_group = jnp.concatenate([p_s[1:] == p_s[:-1], jnp.zeros(1, bool)])
    nxt_sorted = jnp.where(same_group, jnp.roll(order, -1), NULLI).astype(jnp.int32)
    next_sib = scatter_perm(order, nxt_sorted)  # scatter-free inverse

    # dense first-child table via one searchsorted over the run starts
    first_pos, _ = run_edge_lookup(p_s, m, side="left")
    first_child = jnp.where(
        first_pos >= 0, order[jnp.clip(first_pos, 0, n - 1)], NULLI
    ).astype(jnp.int32)

    # DFS successor assembly + Wyllie ranking (shared helper; fixpoint
    # early exit keeps rounds at the real document depth)
    dist_to_end = dfs_ranks(parent, next_sib, first_child, is_seq,
                            num_segments)

    root_dist = dist_to_end[n + jnp.maximum(seg, 0)]
    rank = jnp.where(is_seq, root_dist - dist_to_end[:n] - 1, NULLI).astype(
        jnp.int32
    )
    return rank, dist_to_end[n:]


@partial(jax.jit, static_argnames=("num_segments",))
def converge_sequences(
    client,  # [N] int32
    clock,  # [N] int64
    parent_is_root,  # [N] bool
    parent_a,  # [N] int64  root name id | parent item client
    parent_b,  # [N] int64  -1           | parent item clock
    key_id,  # [N] int32  -1 for sequence rows (map rows are skipped)
    origin_client,  # [N] int32
    origin_clock,  # [N] int64
    valid,  # [N] bool
    num_segments: int,
):
    """Union-level sequence ordering, entirely on device: dedup by
    packed id, dense per-parent segments, origin resolution by binary
    search, then the DFS rank kernel. The union-side counterpart of
    :func:`crdt_tpu.ops.merge.converge_maps` — together they are the
    full device ``applyUpdate`` of a gossip round (crdt.js:294).

    Returns ``(order, seg, rank, seq_len)``; all but ``order`` live in
    id-sorted space and ``order[i]`` maps sorted position i back to the
    caller's row. Sibling order within an origin group is the
    (client asc, clock DESC) key — exact for every attachment-free
    group, same-client duplicates included (see module docstring);
    only right-origin attachment groups need the host scan, which is
    :func:`order_sequences` / ``core.device_apply``'s job.
    """
    n = client.shape[0]
    ikey = jnp.where(valid, pack_id(client, clock), jnp.int64(2**62))
    order = jnp.argsort(ikey, stable=True)
    ikey = ikey[order]
    client = client[order]
    clock = clock[order]
    parent_is_root = parent_is_root[order]
    parent_a = parent_a[order]
    parent_b = parent_b[order]
    key_id = key_id[order]
    origin_client = origin_client[order]
    origin_clock = origin_clock[order]
    valid = valid[order]
    dup = jnp.concatenate([jnp.zeros(1, bool), ikey[1:] == ikey[:-1]])
    uniq_valid = valid & ~dup
    is_seq = uniq_valid & (key_id < 0)

    # dense per-parent segments (same composite-change scheme as
    # converge_maps, restricted to sequence rows)
    segkey = [
        (~is_seq).astype(jnp.int32),
        parent_is_root.astype(jnp.int32),
        jnp.where(is_seq, parent_a, jnp.int64(-2)),
        jnp.where(is_seq, parent_b, jnp.int64(-2)),
    ]
    sorder = lexsort(segkey)
    changed = jnp.zeros(n, bool)
    for k in segkey:
        ks = k[sorder]
        changed = changed | jnp.concatenate([jnp.ones(1, bool), ks[1:] != ks[:-1]])
    seg_sorted = jnp.cumsum(changed.astype(jnp.int32)) - 1
    seg = scatter_perm(sorder, seg_sorted)
    seg = jnp.where(is_seq, seg, NULLI)

    # origin rows; cross-segment / absent origins hang off the segment
    # root (the GC'd-origin convention shared with map_winners)
    okey = pack_id(origin_client, origin_clock)
    origin_idx = searchsorted_ids(ikey, okey)
    oseg = jnp.where(
        origin_idx >= 0, seg[jnp.clip(origin_idx, 0, n - 1)], NULLI
    )
    parent_idx = jnp.where(
        (origin_idx >= 0) & (oseg == seg), origin_idx, NULLI
    )

    rank, seq_len = tree_order_ranks(
        seg,
        parent_idx,
        client.astype(jnp.int64),
        -clock.astype(jnp.int64),  # clock-DESC within a client
        is_seq,
        num_segments=num_segments,
    )
    return order, seg, rank, seq_len


# ---------------------------------------------------------------------------
# host side: orphan drops + sibling ranks for attachment groups
# ---------------------------------------------------------------------------


def drop_orphan_subtrees(rows, seg, parent_idx) -> list:
    """Keep only rows whose origin-ancestor path reaches a chain root
    (parent < 0) without crossing a segment boundary. Orphans (items
    whose origin is a GC filler or a foreign row) get ``seg = -1`` —
    the engine splices them after a chain-less row, so its head walk
    never emits them — and the drop cascades to their subtrees.
    Vectorized reachability: numpy pointer doubling over the parent
    function, O(rows log depth) array work instead of a python BFS.

    ``rows`` is an iterable of row indices; ``seg``/``parent_idx`` are
    indexable by row. Mutates ``seg`` in place; returns the kept rows
    in input order.
    """
    import numpy as np

    rows = np.asarray(list(rows), dtype=np.int64)
    n = len(rows)
    if n == 0:
        return []
    seg_np = np.asarray(seg)
    par_np = np.asarray(parent_idx)
    # local index of each row's parent (rows outside the set, or
    # out-of-range parent references, -> -1)
    m = int(seg_np.shape[0])
    pos = np.full(m, -1, np.int64)
    pos[rows] = np.arange(n)
    p = par_np[rows]
    in_range = (p >= 0) & (p < m)
    pc = np.clip(p, 0, m - 1)
    p_local = np.where(in_range, pos[pc], -1)
    same_seg = in_range & (p_local >= 0) & (seg_np[pc] == seg_np[rows])
    ok = p < 0  # chain roots are reachable; dead ends (cross-seg /
    # foreign parents) self-loop with ok=False and stay False
    idx = np.arange(n)
    ptr = np.where(same_seg, p_local, idx)
    for _ in range(max(1, (max(n, 2) - 1).bit_length() + 1)):
        ok = ok | ok[ptr]
        ptr = ptr[ptr]
    for i in rows[~ok]:
        seg[int(i)] = -1
    return rows[ok].tolist()


def _simulate_group(sibs: List[dict], member_ids: set) -> List[Tuple[int, int]]:
    """Exact group-local replay of the Yjs conflict scan.

    ``sibs``: [{id, client, clock, right}] of one origin group. Returns
    member ids in final order. Items are integrated in causal rounds
    (an item whose right origin is an unplaced member waits); within a
    round, processing order is (client, clock) — convergence makes any
    causal order equivalent.
    """
    remaining = sorted(sibs, key=lambda s: (s["client"], s["clock"]))
    placed: List[dict] = []
    placed_ids: set = set()
    while remaining:
        progress = False
        still = []
        for s in remaining:
            anchor = s["right"] if s["right"] in member_ids else None
            if anchor is not None and anchor not in placed_ids:
                still.append(s)
                continue
            left = -1
            for i, t in enumerate(placed):
                if anchor is not None and t["id"] == anchor:
                    break
                if t["client"] < s["client"]:
                    left = i
                elif t["client"] > s["client"] and t["right"] == s["right"]:
                    break
            placed.insert(left + 1, s)
            placed_ids.add(s["id"])
            progress = True
        if not progress:
            # malformed input (anchor cycle): append rest deterministically
            for s in still:
                placed.append(s)
                placed_ids.add(s["id"])
            still = []
        remaining = still
    return [s["id"] for s in placed]


def order_hard_segment(seg_records, ref_exists=None) -> List[Tuple[int, int]]:
    """Exact chain order for one sequence via a throwaway scalar
    integrate — the fallback for segments whose right origins the
    sibling-rank model cannot express (rights pointing INTO a member's
    subtree, dangling rights, cross-parent rights: shapes honest Yjs
    peers never produce, but hostile updates can).

    The slice is made integrable WITHOUT changing its chain outcome:
    per-client clocks renumber to a contiguous run (the real document
    may interleave other collections' clocks, which must not pend the
    slice), and references to ids outside the slice are rewritten —
    ones that EXIST elsewhere (``ref_exists``; default: treat as
    existing) get a synthetic donor item in a foreign chain (dep
    satisfied, never encountered by this chain's scan, equality
    classes of right origins preserved), while truly dangling ones map
    to absent ids so the member pends, exactly like the engine."""
    from crdt_tpu.core.engine import Engine
    from crdt_tpu.core.records import ItemRecord

    # dedup by id: redelivered blobs reach some callers unmerged, and a
    # duplicate would double-count in the clock renumbering (leaving a
    # gap that pends the whole client)
    uniq: Dict[Tuple[int, int], object] = {}
    for r in seg_records:
        uniq.setdefault(r.id, r)
    seg_records = list(uniq.values())

    by_client: Dict[int, List[Tuple[int, int]]] = {}
    for r in sorted(seg_records, key=lambda x: (x.client, x.clock)):
        by_client.setdefault(r.client, []).append(r.id)
    remap = {
        rid: (rid[0], i)
        for ids_ in by_client.values()
        for i, rid in enumerate(ids_)
    }
    SENT = 1 << 45  # outside any real client-id namespace
    ext: Dict[Tuple[int, int], Tuple[int, int]] = {}
    donors: List[ItemRecord] = []

    def map_ref(ref):
        if ref is None:
            return None
        if ref in remap:
            return remap[ref]
        if ref not in ext:
            sid = (SENT + len(ext), 0)
            ext[ref] = sid
            if ref_exists is None or ref_exists(ref):
                donors.append(ItemRecord(
                    client=sid[0], clock=0, parent_root="__other__",
                    content=None,
                ))
            # else: absent id — the referencing member pends
        return ext[ref]

    rewritten = [
        ItemRecord(
            client=r.client, clock=remap[r.id][1], parent_root="__hard__",
            origin=map_ref(r.origin), right=map_ref(r.right), kind=r.kind,
            type_ref=r.type_ref,
        )
        for r in seg_records
    ]
    eng = Engine(10**9)
    eng.apply_records(donors + rewritten)
    inv = {v: k for k, v in remap.items()}
    return [
        inv[i]
        for i in eng.seq_order_table().get(("root", "__hard__"), [])
        if i in inv
    ]


def right_walk_is_hard(
    right, member_ids, lookup, seg_of, gseg, id_of, origin_of, max_steps
) -> bool:
    """Shared hard-shape walk for one out-of-group right origin: True
    when it is dangling in the caller's universe, in another segment,
    or a DESCENDANT of a group member (the integrate scan would stop
    inside that member's subtree, splitting it — inexpressible by
    sibling ranks). ``max_steps`` must bound the UNIVERSE size, not
    the group size: subtree depth is unrelated to sibling count."""
    cur = lookup(right)
    if cur is None:
        return True  # dangling right: the engine pends the member
    if seg_of(cur) != gseg:
        return True  # cross-parent right: malformed
    steps = 0
    while cur is not None and steps <= max_steps:
        steps += 1
        if id_of(cur) in member_ids:
            return True  # right sits inside a member's subtree
        cur = origin_of(cur)
    return False


def _group_is_hard(rows, member_ids, row_of, records, seg, gseg) -> bool:
    for i in rows:
        right = records[i].right
        if right is None or right in member_ids:
            continue  # no right, or a plain in-group anchor
        if right_walk_is_hard(
            right,
            member_ids,
            row_of.get,
            lambda cur: seg[cur],
            gseg,
            lambda cur: records[cur].id,
            lambda cur: (
                row_of.get(records[cur].origin)
                if records[cur].origin is not None
                else None
            ),
            len(records),
        ):
            return True
    return False


def order_sequences(records):
    """Order a record union's sequences through the device kernel.

    Returns {parent: [(client, clock), ...]} in final document order,
    tombstones included. Parent is ("root", name) or ("item", c, k).
    """
    import numpy as np

    from crdt_tpu.core.store import K_GC
    from crdt_tpu.ops.merge import _pad_to, resolve_parents

    records = resolve_parents(records)
    uniq = {}
    for r in records:
        uniq.setdefault(r.id, r)
    records = list(uniq.values())
    n = len(records)
    if n == 0:
        return {}
    row_of = {r.id: i for i, r in enumerate(records)}

    seq_specs: Dict[Tuple, int] = {}
    seg = np.full(n, -1, np.int32)
    parent_idx = np.full(n, -1, np.int32)
    key1 = np.zeros(n, np.int64)
    key2 = np.zeros(n, np.int64)
    seq_rows: List[int] = []
    for i, r in enumerate(records):
        if r.kind == K_GC or r.key is not None:
            continue
        if r.parent_root is not None:
            spec: Tuple = ("root", r.parent_root)
        elif r.parent_item is not None:
            spec = ("item",) + tuple(r.parent_item)
        else:
            continue  # unresolvable parent (origin outside batch)
        seg[i] = seq_specs.setdefault(spec, len(seq_specs))
        if r.origin is not None and r.origin in row_of:
            parent_idx[i] = row_of[r.origin]
        key1[i] = r.client
        key2[i] = -r.clock  # clock-DESC within a client (break rule)
        seq_rows.append(i)

    seg_all = seg.copy()  # pre-drop assignment (hard fallback needs it)
    seq_rows = drop_orphan_subtrees(seq_rows, seg, parent_idx)

    # group members by origin-tree parent; detect attachment groups
    # and HARD segments (rights the sibling-rank model cannot express
    # — those sequences fall back to an exact scalar integrate)
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i in seq_rows:
        groups.setdefault((seg[i], parent_idx[i]), []).append(i)
    hard_segs: set = set()
    for (gseg, gparent), rows in groups.items():
        if gseg in hard_segs:
            continue
        member_ids = {records[i].id for i in rows}
        if _group_is_hard(rows, member_ids, row_of, records, seg, gseg):
            hard_segs.add(gseg)
            continue
        has_attachment = any(
            records[i].right in member_ids for i in rows if records[i].right
        )
        if not has_attachment:
            # (client, ~clock) keys are exact here — including
            # same-client duplicates, which the break rule places
            # clock-descending (see module docstring)
            continue
        sibs = [
            {
                "id": records[i].id,
                "client": records[i].client,
                "clock": records[i].clock,
                "right": records[i].right,
            }
            for i in rows
        ]
        ordered = _simulate_group(sibs, member_ids)
        for rank_pos, sid in enumerate(ordered):
            key1[row_of[sid]] = rank_pos
            key2[row_of[sid]] = 0

    # power-of-two buckets for BOTH static dims so jit compiles once
    # per bucket, not once per (record count, sequence count) pair
    num_segments = 1 << max(3, (max(1, len(seq_specs)) - 1).bit_length())
    pad = 1 << max(9, (n - 1).bit_length())

    # this is HOST machinery (the right-bearing wholesale pass a
    # resident replica runs below the crossover): the ranking kernel
    # executes on the LOCAL CPU backend, like the rest of the host path
    from crdt_tpu.ops.device import on_local_cpu

    with on_local_cpu(), enable_x64(True):
        rank, _ = tree_order_ranks(
            jnp.asarray(_pad_to(seg, pad, -1)),
            jnp.asarray(_pad_to(parent_idx, pad, -1)),
            jnp.asarray(_pad_to(key1, pad, 0)),
            jnp.asarray(_pad_to(key2, pad, 0)),
            jnp.asarray(np.arange(pad) < n),
            num_segments=num_segments,
        )
        rank = np.asarray(rank[:n])
    by_spec: Dict[int, List[Tuple[int, Tuple[int, int]]]] = {}
    for i in seq_rows:
        if int(seg[i]) in hard_segs:
            continue  # ordered by the scalar fallback below
        by_spec.setdefault(int(seg[i]), []).append((int(rank[i]), records[i].id))
    inv = {v: k for k, v in seq_specs.items()}
    out = {spec: [] for spec in seq_specs}
    for sid, pairs in by_spec.items():
        pairs.sort()
        out[inv[sid]] = [pid for _, pid in pairs]
    for sid in hard_segs:
        out[inv[sid]] = order_hard_segment(
            [records[i] for i in range(n) if seg_all[i] == sid],
            ref_exists=lambda ref: ref in row_of,
        )
    return out
