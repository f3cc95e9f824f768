"""Trace replay — BASELINE config #5 as a product API.

``replay_trace(blobs)`` ingests a batch of v1 update blobs (a captured
swarm trace, a persistence log, a sync backlog) through the firehose
path end to end:

  1. decode: one native-codec pass -> columnar union + contents
     (:mod:`crdt_tpu.codec.native`, Python fallback included);
  2. converge: HBM-resident union, one LWW map dispatch + one YATA
     sequence dispatch (:class:`crdt_tpu.ops.resident.ResidentColumns`);
  3. gather: winner/order indices return in ONE packed int32 transfer;
  4. materialize: the plain-JSON ``crdt.c`` cache, tombstones applied;
  5. compact: one snapshot blob (the log squashed — what a fresh
     replica needs instead of the whole history).

The benchmark (``perfbench/``) times these stages through
:func:`replay_trace`, so the timed pipeline IS the product pipeline.
Differential-tested against the scalar document path in
tests/test_models.py.
"""

from __future__ import annotations

from itertools import chain
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from crdt_tpu.codec import native
from crdt_tpu.core.ids import DeleteSet
from crdt_tpu.obs.tracer import get_tracer
from crdt_tpu.utils.heap import count_policy


class ReplayResult(NamedTuple):
    cache: dict        # converged plain-JSON state (crdt.c)
    snapshot: bytes    # compacted single-blob log
    n_ops: int         # unit items replayed
    path: str = "device"  # which engine converged (see replay_trace)


def decode(blobs: Sequence[bytes]) -> Dict:
    """Wire -> canonical columnar union (native C codec when built;
    duplicate ids from redelivered blobs are dropped, first wins)."""
    with get_tracer().span("decode"):
        return native.dedup_columns(
            native.decode_updates_columns_any(blobs)
        )


def stage(dec: Dict) -> Tuple[Dict[str, np.ndarray], DeleteSet]:
    """Kernel-facing columns + merged delete set."""
    with get_tracer().span("stage"):
        return (native.kernel_columns(dec),
                native.ds_from_triples(dec["ds"]))


def converge(cols: Dict[str, np.ndarray], *,
             clients: Optional[Sequence[int]] = None):
    """One union convergence. Returns an opaque handle for
    :func:`gather`.

    Fast path: the packed single-dispatch pipeline
    (:mod:`crdt_tpu.ops.packed` — one upload, one fused kernel, one
    fetch). Falls back to the general resident path when the batch
    exceeds the packed key bounds (>=2^25 parents, >=2^21 map keys,
    clocks >= 2^40).

    ``clients`` only affects the RESIDENT fallback (it seeds that
    path's client table). The packed plan interns its own
    order-preserving table, which is equivalent for convergence: the
    sibling rules compare clients only through a monotone mapping, so
    any order-preserving table yields the identical document. Callers
    that need a fleet-shared table to be the one actually used (e.g.
    to reuse a resident store across batches) should route through
    :class:`crdt_tpu.ops.resident.ResidentColumns` directly.

    Multi-chip (round 13): when more than one device is visible and
    the union is big enough (``CRDT_TPU_SHARDS`` /
    ``CRDT_TPU_SHARD_MIN_ROWS``; :func:`crdt_tpu.ops.shard.
    active_for`), the union partitions by whole segments over the
    mesh and converges in ONE ``shard_map`` program — byte-identical
    outputs (tests/test_shard.py), only the per-shard state vectors
    cross chips."""
    from crdt_tpu.ops import packed

    from crdt_tpu.ops import shard as shard_ops

    if shard_ops.active_for(len(cols["client"])):
        splan = shard_ops.stage(cols)
        if splan is not None:
            return ("packed", shard_ops.converge(splan))

    # eager row shipping: each staged row starts its async upload as
    # soon as its layout pass completes, hiding transfer behind the
    # remaining staging work (and the seq block ships at its own
    # bucket width) — see packed.stage. Only ABOVE a size threshold:
    # each put pays a fixed per-interaction latency, so four puts on a
    # small batch cost three extra round-trips for nothing
    put = None
    if len(cols["client"]) >= packed.EAGER_PUT_MIN_ROWS:
        from crdt_tpu.ops.device import xfer_put

        put = xfer_put
    plan = packed.stage(cols, put=put)
    if plan is not None:
        return ("packed", packed.converge(plan))
    return ("resident", _converge_resident(cols, clients))


def _converge_resident(cols, clients):
    import jax

    from crdt_tpu.ops.device import bucket_pow2
    from crdt_tpu.ops.resident import ResidentColumns

    n = len(cols["client"])
    rc = ResidentColumns(
        capacity=n,
        clients=clients if clients is not None
        else np.unique(cols["client"][cols["valid"]]),
    )
    # tight segment bound: distinct (map parent, key) pairs + sequence
    # roots (the capacity default doubles the ranking kernel's span)
    n_segs = segment_bound(cols)
    # fused: splice + both kernels = ONE dispatch
    maps_out, seq_out = rc.append_converge(
        cols, num_segments=bucket_pow2(n_segs)
    )
    jax.block_until_ready(maps_out)
    jax.block_until_ready(seq_out)
    return rc, maps_out, seq_out


def parent_spec(dec: Dict, row: int) -> Tuple:
    """("root", name) or ("item", client, clock) of a row's parent."""
    pr = dec["parent_root"][row]
    if pr >= 0:
        return ("root", dec["roots"][pr])
    return (
        "item",
        int(dec["parent_client"][row]),
        int(dec["parent_clock"][row]),
    )


def gather(dec: Dict, ds: DeleteSet, handle):
    """Winner rows + visibility + per-sequence document orders (keyed
    by parent spec — root name or item id) from a :func:`converge`
    handle.

    Right origins (honest prepends/mid-inserts): the packed path
    orders attachment groups AT STAGING — the exact conflict-scan
    ranks ride the client column into the fused dispatch
    (ops.packed._stage_rights) — so only segments carrying shapes the
    sibling-rank model cannot express (dangling/cross-parent rights,
    rights into a member's subtree, orphan subtrees: the plan's
    ``hard_rows``) re-order on the host. The resident fallback keeps
    the blanket host detour for every right-bearing parent."""
    with get_tracer().span("gather"):
        return _gather(dec, ds, handle)


def _gather(dec: Dict, ds: DeleteSet, handle):
    if handle[0] == "packed":
        win_rows, seq_orders = _assemble_packed(dec, handle[1])
        hard = getattr(handle[1], "hard_rows", ())
        if hard:
            affected = {parent_spec(dec, int(r)) for r in hard}
            seq_orders.update(_host_seq_orders(dec, affected))
        return finish_assembly(
            dec, ds, win_rows, seq_orders, blanket_rights=False
        )
    win_rows, seq_orders = _assemble_resident(dec, handle[1])
    return finish_assembly(dec, ds, win_rows, seq_orders)


def finish_assembly(dec: Dict, ds: DeleteSet, win_rows, seq_orders,
                    *, blanket_rights: bool = True):
    """Shared assembly tail for every convergence engine (resident,
    packed, fleet): the blanket right-origin host detour — applied
    when the producing kernels ignore rights entirely, skipped when
    the producer already ordered its expressible rights at staging —
    then crafted-map-chain repair and winner visibility. One
    implementation, so a future right-origin fix reaches every
    route."""
    if blanket_rights:
        rc_col, kid_col = dec["right_client"], dec["key_id"]
        right_seq_rows = np.flatnonzero((rc_col >= 0) & (kid_col < 0))
        if len(right_seq_rows):
            affected = {parent_spec(dec, int(r)) for r in right_seq_rows}
            seq_orders.update(_host_seq_orders(dec, affected))
    win_rows = _fix_map_chains_with_rights(dec, win_rows)
    win_vis = visible_mask(dec, win_rows, ds)
    return win_rows, win_vis, seq_orders


def segment_key(pa: np.ndarray, kid: np.ndarray) -> np.ndarray:
    """ONE packed (parent, key) segment identity, shared by the
    segment-count bound below and the mesh partitioner
    (crdt_tpu.models.fleet.shard_trace): parents shifted past the
    2^20 key space; the no-key sentinel occupies its own slot per
    parent. Both consumers must agree bit-for-bit — the partitioner's
    correctness rests on whole segments staying co-located."""
    pa = np.asarray(pa, np.int64)
    kid = np.asarray(kid, np.int64)
    return (pa << 21) | np.where(kid >= 0, kid, 1 << 20)


def segment_bound(cols: Dict[str, np.ndarray]) -> int:
    """Tight distinct-segment count for the convergence kernels:
    distinct (map parent, key) pairs + sequence parents."""
    if not len(np.asarray(cols["parent_a"])):
        return 1
    return len(np.unique(segment_key(cols["parent_a"], cols["key_id"])))


def _assemble_packed(dec: Dict, res, row_map=None):
    """Vectorized host assembly of the packed kernel's one fetch.
    ``row_map`` translates the result's row space into ``dec``'s (the
    streaming executor stages each chunk's rows separately, so its
    results come back chunk-local); None means they already agree."""
    win = res.win_rows[res.win_rows >= 0]
    m = res.stream_row >= 0
    rows, segs = res.stream_row[m], res.stream_seg[m]
    if row_map is not None:
        win = row_map[win]
        rows = row_map[rows]
    win_rows = win.tolist()
    seq_orders: dict = {}
    if len(rows):
        cuts = np.r_[0, np.flatnonzero(segs[1:] != segs[:-1]) + 1, len(segs)]
        for a, b in zip(cuts[:-1], cuts[1:]):
            chunk = rows[a:b].tolist()
            spec = parent_spec(dec, chunk[0])
            # extend on recurrence: the sharder's cross-shard subtree
            # pre-cut (round 23) emits one list's pieces as separate
            # runs — shard-concatenated in exact piece order, so
            # appending reproduces the unsplit stream bit-for-bit
            if spec in seq_orders:
                seq_orders[spec].extend(chunk)
            else:
                seq_orders[spec] = chunk
    return win_rows, seq_orders


def _assemble_resident(dec: Dict, out):
    rc, maps_out, seq_out = out
    from crdt_tpu.ops.device import fetch_packed_i32

    order, winners, sorder, sseg, srank = fetch_packed_i32(
        maps_out[0], maps_out[2], seq_out[0], seq_out[1], seq_out[2]
    )
    win_rows = [int(order[w]) for w in winners if w >= 0]
    n = len(dec["client"])
    seq_pairs: dict = {}
    for p in np.flatnonzero(srank >= 0):
        row = int(sorder[p])
        if row < n:
            seq_pairs.setdefault(int(sseg[p]), []).append(
                (int(srank[p]), row)
            )
    seq_orders = {}
    for sid, pairs in seq_pairs.items():
        pairs.sort()
        rows = [r for _, r in pairs]
        seq_orders[parent_spec(dec, rows[0])] = rows
    return win_rows, seq_orders


def _host_seq_orders(dec: Dict, specs_needed: set):
    """Exact sequence orders for the given parent specs via the host
    machinery (right origins, attachment groups, hostile shapes).

    The subset keeps full-union semantics: every id referenced from the
    subset but living OUTSIDE it (GC fillers, foreign parents' rows)
    joins as a GC stub — the ordering machinery then drops/hardens
    those references exactly as it would with the whole union in hand,
    while truly dangling references stay absent (members pend)."""
    from crdt_tpu.core.records import ItemRecord
    from crdt_tpu.core.store import K_GC
    from crdt_tpu.ops.yata import order_sequences

    kid_col, kind_col = dec["key_id"], dec["kind"]
    n = len(kid_col)
    rows = [
        i for i in range(n)
        if kid_col[i] < 0 and kind_col[i] != K_GC
        and parent_spec(dec, i) in specs_needed
    ]
    records, _ = native.decoded_to_records(dec, rows)
    sub_ids = {r.id for r in records}
    id_row = {
        (int(dec["client"][i]), int(dec["clock"][i])): i for i in range(n)
    }
    stubs = {
        ref
        for r in records
        for ref in (r.origin, r.right)
        if ref is not None and ref not in sub_ids and ref in id_row
    }
    records += [
        ItemRecord(client=c, clock=k, kind=K_GC) for c, k in stubs
    ]
    return {
        spec: [id_row[i] for i in ids]
        for spec, ids in order_sequences(records).items()
        if spec in specs_needed
    }


def _fix_map_chains_with_rights(dec: Dict, win_rows, bad_rows=None,
                                chain_rows=None, union_ids=None):
    """Crafted rights on MAP rows shift chain tails in ways the argmax
    kernel cannot express; recompute exactly those chains' tails via
    the scalar chain order. The optional subsets are the streaming
    executor's seams: ``bad_rows`` restricts the repair to a chunk's
    right-bearing map rows (so one chunk never emits another chunk's
    tails), ``chain_rows`` restricts the chain-membership scan to the
    chunk's rows (sound because segments never split across chunks),
    and ``union_ids`` shares one precomputed whole-union id set across
    chunks instead of rebuilding it per call. Defaults scan the whole
    union."""
    from crdt_tpu.core.records import ItemRecord
    from crdt_tpu.ops.yata import order_hard_segment

    rc_col, kid_col = dec["right_client"], dec["key_id"]
    if bad_rows is None:
        bad = np.flatnonzero((rc_col >= 0) & (kid_col >= 0))
    else:
        bad = np.asarray(bad_rows, np.int64)
    if not len(bad):
        return win_rows
    affected = {(parent_spec(dec, int(r)), int(kid_col[r])) for r in bad}
    chains: Dict[Tuple, List[int]] = {}
    for i in (range(len(kid_col)) if chain_rows is None else chain_rows):
        i = int(i)
        if kid_col[i] >= 0:
            key = (parent_spec(dec, i), int(kid_col[i]))
            if key in affected:
                chains.setdefault(key, []).append(i)
    id_row = {
        (int(dec["client"][i]), int(dec["clock"][i])): i
        for rows in chains.values()
        for i in rows
    }
    if union_ids is None:
        union_ids = {
            (int(dec["client"][i]), int(dec["clock"][i]))
            for i in range(len(kid_col))
        }
    patched = dict.fromkeys(affected)
    for key, rows in chains.items():
        recs = [
            ItemRecord(
                client=int(dec["client"][i]), clock=int(dec["clock"][i]),
                origin=(
                    (int(dec["origin_client"][i]),
                     int(dec["origin_clock"][i]))
                    if dec["origin_client"][i] >= 0 else None
                ),
                right=(
                    (int(dec["right_client"][i]),
                     int(dec["right_clock"][i]))
                    if dec["right_client"][i] >= 0 else None
                ),
                parent_root="x",  # chain order ignores parent identity
            )
            for i in rows
        ]
        ordered = order_hard_segment(
            recs, ref_exists=lambda ref: ref in union_ids
        )
        patched[key] = id_row[ordered[-1]] if ordered else None
    out = []
    for row in win_rows:
        key = (parent_spec(dec, row), int(kid_col[row]))
        if key in affected:
            continue  # replaced by the exact tail below
        out.append(row)
    out.extend(r for r in patched.values() if r is not None)
    return out


def rows_visible(
    row_client: np.ndarray,
    row_clock: np.ndarray,
    del_c: np.ndarray,
    del_s: np.ndarray,
    del_e: np.ndarray,
) -> np.ndarray:
    """Vectorized tombstone test against delete RANGES — never
    expanded ids: a few delete-set bytes can legitimately declare
    ranges covering a whole GC'd history, so membership is an interval
    search (adversarial matrix, tests/test_yjs_fixtures.py). Ranges
    must be DISJOINT and sorted per client (DeleteSet.normalize's
    invariant). Clients remap densely before packing; the 41-bit clock
    field keeps the exclusive range end (up to the 1<<40 wire bound)
    out of the client bits. Shared by the cold replay's visible_mask
    and the incremental replay's cached-tombstone path."""
    if not len(del_c):
        return np.ones(len(row_client), bool)
    row_client = np.asarray(row_client, np.int64)
    del_c = np.asarray(del_c, np.int64)
    uniq = np.unique(np.concatenate([row_client, del_c]))
    qk = (
        np.searchsorted(uniq, row_client).astype(np.int64) << 41
    ) | np.asarray(row_clock, np.int64)
    dc = np.searchsorted(uniq, del_c).astype(np.int64) << 41
    starts = dc | np.asarray(del_s, np.int64)
    ends = dc | np.asarray(del_e, np.int64)
    order = np.argsort(starts)
    starts, ends = starts[order], ends[order]
    pos = np.searchsorted(starts, qk, side="right") - 1
    posc = np.clip(pos, 0, len(starts) - 1)
    return ~((pos >= 0) & (qk < ends[posc]))


def delete_ranges(ds: DeleteSet) -> Tuple[np.ndarray, ...]:
    """The normalized delete set as (client, start, end) int64 columns
    for :func:`rows_visible` (one array build, no per-range tuples)."""
    ds.normalize()
    clients = list(ds.ranges)
    se = np.fromiter(
        chain.from_iterable(chain.from_iterable(ds.ranges.values())),
        np.int64,
    ).reshape(-1, 2)
    per = np.fromiter((len(r) for r in ds.ranges.values()), np.int64,
                      len(clients))
    return np.repeat(np.asarray(clients, np.int64), per), se[:, 0], se[:, 1]


def visible_mask(dec: Dict, rows: List[int], ds: DeleteSet) -> List[bool]:
    """Tombstone visibility for specific rows (vectorized)."""
    if not rows:
        return []
    idx = np.asarray(rows)
    return list(rows_visible(
        dec["client"][idx], dec["clock"][idx], *delete_ranges(ds)
    ))


def materialize(dec: Dict, ds: DeleteSet, win_rows, win_vis,
                seq_orders) -> dict:
    """Winner rows + sequence orders -> the plain-JSON cache, with
    tombstoned sequence members dropped (the engine's visible walk).
    Nested collections (a Y.Array/Y.Map stored under a map key or a
    sequence slot) materialize recursively through their type items."""
    cache, ix_group = assemble_cache(
        dec, ds, win_rows, win_vis, seq_orders
    )
    finish_cache(cache, dec, ix_group)
    return cache


def assemble_cache(dec: Dict, ds: DeleteSet, win_rows, win_vis,
                   seq_orders) -> Tuple[dict, Dict[str, int]]:
    """The per-subset half of :func:`materialize`: builds the cache
    entries for exactly the root specs present in ``win_rows`` /
    ``seq_orders``. The streaming executor calls this once per chunk
    (each chunk owning whole root subtrees, so nested type items
    resolve within the chunk) and merges the parts; the returned
    ``ix_group`` is the subset's slice of the reserved ``ix`` index
    root, consumed by :func:`finish_cache` once every part is in."""
    with get_tracer().span("materialize"):
        return _assemble_cache(dec, ds, win_rows, win_vis, seq_orders)


def _assemble_cache(dec: Dict, ds: DeleteSet, win_rows, win_vis,
                    seq_orders) -> Tuple[dict, Dict[str, int]]:
    """Column-wise assembly: one tombstone test over every sequence
    row, visible winners grouped by parent with numpy, values copied
    out in bulk. Only type rows (``kind == K_TYPE``) recurse, one by
    one, into the collection they head."""
    from crdt_tpu.core.store import K_TYPE, TYPE_MAP

    keys = dec["keys"]
    kid = dec["key_id"]
    client, clock = dec["client"], dec["clock"]
    kind_col, tref = dec["kind"], dec["type_ref"]
    contents = dec["contents"]
    taken = {"bulk": 0, "nested": 0}

    # every sequence row in one array, one visibility call; each
    # spec keeps its slice of it
    seq_span: Dict[Tuple, Tuple[int, int]] = {}
    at = 0
    for spec, rows in seq_orders.items():
        seq_span[spec] = (at, at + len(rows))
        at += len(rows)
    seq = np.fromiter(
        chain.from_iterable(seq_orders.values()), np.int64, at
    )
    seq_vis = rows_visible(client[seq], clock[seq], *delete_ranges(ds))

    # visible winners grouped by parent (root index, or the parent
    # item's id): a stable sort keeps win_rows order inside a group,
    # and groups run in the order of their first winner
    win = np.asarray(win_rows, np.int64)[np.asarray(win_vis, bool)]
    pr = dec["parent_root"][win].astype(np.int64)
    is_item = pr < 0
    pa = np.where(is_item, dec["parent_client"][win], pr)
    pb = np.where(is_item, dec["parent_clock"][win], 0)
    order = np.lexsort((np.arange(len(win)), pb, pa, is_item))
    cut = np.flatnonzero(
        (np.diff(pa[order]) != 0) | (np.diff(pb[order]) != 0)
        | (np.diff(is_item[order].astype(np.int8)) != 0)
    ) + 1
    starts = np.r_[0, cut] if len(win) else cut
    ends = np.r_[cut, len(win)]
    heads = order[starts]  # each group's first winner, as a win index

    def values(rows: np.ndarray, depth: int) -> list:
        out = [contents[i] for i in rows.tolist()]
        nested = np.flatnonzero(kind_col[rows] == K_TYPE)
        taken["bulk"] += len(rows) - len(nested)
        taken["nested"] += len(nested)
        for j in nested.tolist():
            out[j] = nested_value(int(rows[j]), depth + 1)
        return out

    def map_of(rows: np.ndarray, depth: int) -> dict:
        # a repeated key keeps its first place and its last row's value
        return dict(zip([keys[k] for k in kid[rows].tolist()],
                        values(rows, depth)))

    def seq_of(spec: Tuple, depth: int) -> list:
        a, b = seq_span.get(spec, (0, 0))
        return values(seq[a:b][seq_vis[a:b]], depth)

    item_maps: Dict[Tuple[int, int], np.ndarray] = {}

    def nested_value(row: int, depth: int):
        if depth > 64:
            return None  # malformed cyclic nesting: cut, don't recurse
        ident = (int(client[row]), int(clock[row]))
        if tref[row] == TYPE_MAP:
            rows = item_maps.get(ident)
            return {} if rows is None else map_of(rows, depth)
        return seq_of(("item",) + ident, depth)

    roots = dec["roots"]
    root_maps = []
    ix_group: Dict[str, int] = {}
    for g in np.argsort(heads):
        h, rows = heads[g], win[order[starts[g]:ends[g]]]
        if is_item[h]:
            item_maps[(int(pa[h]), int(pb[h]))] = rows
        elif roots[pa[h]] == "ix":
            # the reserved collection-kind index stays internal,
            # exactly as the document API's `c` hides it
            ix_group = dict(zip([keys[k] for k in kid[rows].tolist()],
                                rows.tolist()))
        else:
            root_maps.append((roots[pa[h]], rows))

    cache: dict = {}
    for name, rows in root_maps:
        cache[name] = map_of(rows, 0)
    for spec in seq_orders:
        if spec[0] == "root" and spec[1] not in cache:
            cache[spec[1]] = seq_of(spec, 0)
    tracer = get_tracer()
    for path, n in taken.items():
        tracer.count("materialize.rows", n, labels={"path": path})
    return cache, ix_group


def finish_cache(cache: dict, dec: Dict,
                 ix_group: Dict[str, int]) -> dict:
    """The cross-subset tail of :func:`materialize`: roots registered
    in the ix index but with no visible content (e.g. a map whose
    every key was tombstoned) still materialize — empty — exactly
    like the document cache. Runs once, after every subset's
    :func:`assemble_cache` part has merged into ``cache``."""
    contents = dec["contents"]
    for name, row in ix_group.items():
        if name not in cache and name != "ix":
            cache[name] = [] if contents[row] == "array" else {}
    return cache


def compact(dec: Dict, ds: DeleteSet) -> bytes:
    """Snapshot compaction: the whole replayed union as one blob."""
    with get_tracer().span("compact"):
        return native.encode_from_columns_any(dec, ds)


def replay_trace(
    blobs: Sequence[bytes],
    *,
    clients: Optional[Sequence[int]] = None,
    route: str = "device",
) -> ReplayResult:
    """One-shot: blobs in, converged cache + compacted snapshot out.

    ``route`` picks the convergence engine:

    - ``"device"`` (default) — the packed single-dispatch pipeline,
      always. The default stays pinned so differential suites that
      use this function as their independent cold oracle keep
      exercising the device kernels, and published device numbers are
      never silently host numbers.
    - ``"host"`` — the IDENTICAL fused convergence executed on the
      process's local CPU backend (:func:`crdt_tpu.ops.packed.
      converge_host`): zero accelerator interactions, byte-identical
      kernel outputs. Unions the packed stager cannot express fall
      back to the replica machinery below.
    - ``"auto"`` — the PRODUCT rule: apply the same session-calibrated
      host/device crossover the live replica uses: below the
      threshold the union converges on the local backend
      (``"host"``), above it the accelerator pipeline runs.
    - ``"replica"`` — ingest through :class:`crdt_tpu.models.
      incremental.IncrementalReplay` pinned to its host path: the
      identical code a LIVE resident replica runs on this backlog
      (kept as a third independent engine for differential suites and
      for measuring the replica ingest itself).
    - ``"fleet"`` — the mesh axis: each blob is treated as one
      replica's pending broadcast and the whole set converges as ONE
      sharded gossip+merge round over the device mesh
      (:func:`crdt_tpu.models.fleet.fleet_replay` — the reference's
      full-mesh propagate round, crdt.js:385,445, as a collective).
      Requires a causally complete union, like the device route.
    - ``"stream"`` — the device pipeline, OVERLAPPED: chunked decode,
      async double-buffered converge dispatches, incremental per-chunk
      materialization (:func:`crdt_tpu.models.streaming.stream_replay`
      — the default engine for the scale replay; same outputs as
      ``"device"``, differential-tested byte-identical).

    All engines are differential-tested against each other and the
    scalar oracle; ``ReplayResult.path`` records which one ran. Each
    call counts ``heap.policy`` (``crdt_tpu/utils/heap.py``)."""
    count_policy(get_tracer())
    if route == "stream":
        from crdt_tpu.models.streaming import stream_replay

        return stream_replay(blobs, clients=clients)
    if route == "fleet":
        from crdt_tpu.models.fleet import fleet_replay

        return fleet_replay(blobs)
    dec = decode(blobs)
    n = len(dec["client"])
    use_host = False
    if route in ("host", "replica"):
        use_host = True
    elif route == "auto":
        from crdt_tpu.models.incremental import IncrementalReplay

        # the live replica's exact rule (one shared implementation:
        # static floor first, session probe beyond it)
        use_host = IncrementalReplay.crossover_use_host(n)
    elif route != "device":
        raise ValueError(f"unknown route {route!r}")
    if use_host and route != "replica":
        from crdt_tpu.ops import packed

        cols, ds = stage(dec)
        # wide staging: this route never touches the link (local CPU
        # backend), so the narrow encode + widening prelude would be
        # pure overhead AND would credit xfer.* savings for bytes
        # that never cross anything
        plan = packed.stage(cols, wide=True)
        if plan is not None:
            handle = ("packed", packed.converge_host(plan))
            win_rows, win_vis, seq_orders = gather(dec, ds, handle)
            cache = materialize(dec, ds, win_rows, win_vis, seq_orders)
            return ReplayResult(
                cache=cache, snapshot=compact(dec, ds), n_ops=n,
                path="host",
            )
        # inexpressible plan (key-width overflow): replica machinery
    if use_host:
        from crdt_tpu.models.incremental import IncrementalReplay

        # minimal capacity: the resident device matrix is never used
        # on this route (device_min_rows pins every round to host), so
        # sizing it to the trace would allocate a large dead buffer
        inc = IncrementalReplay(
            capacity=1 << 10,
            device_min_rows=1 << 62,  # host path, zero device work
        )
        inc.apply_decoded(dec)  # decoded once above, never twice
        ds = native.ds_from_triples(dec["ds"])
        return ReplayResult(
            cache=dict(inc.cache), snapshot=compact(dec, ds), n_ops=n,
            path="replica",
        )
    cols, ds = stage(dec)
    handle = converge(cols, clients=clients)
    win_rows, win_vis, seq_orders = gather(dec, ds, handle)
    cache = materialize(dec, ds, win_rows, win_vis, seq_orders)
    return ReplayResult(
        cache=cache, snapshot=compact(dec, ds), n_ops=n, path="device"
    )


def cold_start(doc_name: str, persistence, snapshots=None,
               *, pool=None):
    """Bring a doc up from durable state, preferring snapshot +
    WAL-tail over full-history replay (round 21, ROADMAP item 4).

    The recovery ladder, top rung first:

    1. newest valid snapshot generation (damage is counted and
       skipped inside ``SnapshotStore.load_latest``) rehydrated into
       a live engine, plus ``persistence.get_updates_since`` for the
       tail the snapshot does not cover;
    2. if the tail does not settle exactly (stashed/rootless rows —
       a snapshot from a FOREIGN log, or coverage skew), counted
       ``snap.fallbacks{reason="tail_stash"}`` and down one rung;
    3. full WAL replay through a fresh ``IncrementalReplay`` — the
       byte-identical baseline every upper rung must match.

    Returns ``(engine, path)`` with path in {"snapshot", "wal"}."""
    from crdt_tpu.models.incremental import IncrementalReplay

    tracer = get_tracer()
    if snapshots is not None:
        loaded = snapshots.load_latest(doc_name)
        if loaded is not None:
            from crdt_tpu.storage import snapshot as snap_mod

            snap, seq = loaded
            eng = None
            try:
                eng = snap_mod.rehydrate(snap, pool=pool)
                eng.apply(persistence.get_updates_since(doc_name, seq))
            except ValueError:
                if tracer.enabled:
                    tracer.count("snap.fallbacks",
                                 labels={"reason": "rehydrate"})
            else:
                if not (eng._pending or eng._rootless):
                    return eng, "snapshot"
                if tracer.enabled:
                    tracer.count("snap.fallbacks",
                                 labels={"reason": "tail_stash"})
            # abandoned rung: give back any pooled registration
            if eng is not None and eng.pool is not None:
                eng.pool.release(eng)
                eng.pool = None
    eng = IncrementalReplay(pool=pool)
    eng.apply(persistence.get_all_updates(doc_name))
    return eng, "wal"
