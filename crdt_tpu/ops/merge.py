"""Batched fan-in merge — the device-side ``applyUpdate`` for N replicas.

The reference merges one update at a time through the Yjs scalar loop
(crdt.js:294). This kernel takes the *union* of many replicas' op
columns in one shot (duplicates included — full-state gossip relies on
idempotent merge, SURVEY.md Q2) and computes, entirely on device:

  1. dedup by packed (client, clock) id           (sort + adjacent-diff)
  2. origin resolution                            (binary search)
  3. dense (parent, key) map segments             (lexsort + rank scan)
  4. per-segment winner                           (lww.map_winners)
  5. tombstones from delete ranges                (deleteset.apply_mask)
  6. visibility of each winner

Content values never touch the device: the kernel returns winner
*indices* into the caller's record list; materializing the JSON cache
is a host-side gather (crdt.c rebuild, crdt.js:304).

This full-width kernel serves the engine-backed merge modes and the
differential suites; the staged cold replay runs the round-12
sortless dispatch instead (``ops.packed._converge_packed_body`` over
Pallas kernels + staging-precomputed layout — see ops/packed.py).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import jax

from jax import enable_x64
import jax.numpy as jnp
import numpy as np

from crdt_tpu.core.ids import DeleteSet
from crdt_tpu.core.records import ItemRecord
from crdt_tpu.ops import deleteset as ds_ops
from crdt_tpu.ops.device import (
    NULLI,
    lexsort,
    pack_id,
    scatter_perm,
    searchsorted_ids,
)
from crdt_tpu.ops.lww import map_winners


@partial(jax.jit, static_argnames=("num_segments", "ds_mode"))
def converge_maps(
    client,  # [N] int32
    clock,  # [N] int64
    parent_is_root,  # [N] bool
    parent_a,  # [N] int64  root name id | parent item client
    parent_b,  # [N] int64  -1           | parent item clock
    key_id,  # [N] int32  interned map key, -1 for non-map rows
    origin_client,  # [N] int32
    origin_clock,  # [N] int64
    valid,  # [N] bool
    d_client,  # [D] delete-range client
    d_start,  # [D]
    d_end,  # [D]
    num_segments: Optional[int] = None,
    ds_mode: Optional[str] = None,
):
    """Returns (order, seg, winners, winner_visible, del_mask, uniq_valid).

    All outputs except `order` live in id-sorted space; `order[i]` maps
    sorted position i back to the caller's row index.

    ``ds_mode`` (static) is the delete-mask kernel dispatch decision
    (``deleteset.mask_mode()``), computed by the HOST caller — this
    body is traced, so reading CRDT_TPU_PALLAS here would bake the
    flag into the compiled artifact (crdtlint CL702, round 16). None
    degrades to the exact jnp path, never to an ambient read.
    """
    n = client.shape[0]
    if num_segments is None:
        num_segments = n

    # -- 1. sort by packed id, drop duplicates --------------------------
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

    # -- 2. origin indices in sorted space ------------------------------
    okey = pack_id(origin_client, origin_clock)
    origin_idx = searchsorted_ids(ikey, okey)

    # -- 3. dense map segments -----------------------------------------
    is_map = uniq_valid & (key_id >= 0)
    segkey_a = jnp.where(is_map, parent_a, jnp.int64(-2))
    segkey = [
        (~is_map).astype(jnp.int32),  # all non-map rows share one bucket
        parent_is_root.astype(jnp.int32),
        segkey_a,
        jnp.where(is_map, parent_b, jnp.int64(-2)),
        jnp.where(is_map, key_id, -2),
    ]
    sorder = lexsort(segkey)
    # composite change detection in segment-sorted space
    changed = jnp.zeros(n, bool)
    for k in segkey:
        ks = k[sorder]
        changed = changed | jnp.concatenate([jnp.ones(1, bool), ks[1:] != ks[:-1]])
    seg_sorted = jnp.cumsum(changed.astype(jnp.int32)) - 1
    seg = scatter_perm(sorder, seg_sorted)
    seg = jnp.where(is_map, seg, NULLI)

    # -- 4. per-segment winners ----------------------------------------
    # rows are id-sorted here (step 1), so the collapsed sibling key
    # applies. Raw client ids flow through this path, so client_bits
    # must be pack_id's true client width (23); when the collapsed key
    # does not fit an int64 at this width, map_winners falls back to
    # the lexsort internally.
    winners = map_winners(seg, client, clock, origin_idx, is_map, num_segments,
                          rows_id_ranked=True, client_bits=23)

    # -- 5. tombstones --------------------------------------------------
    del_mask = ds_ops.apply_mask_static(
        client, clock, uniq_valid, d_client, d_start, d_end,
        mode=ds_mode or "jnp",
    )

    # -- 6. winner visibility ------------------------------------------
    wc = jnp.clip(winners, 0, n - 1)
    winner_visible = (winners != NULLI) & ~del_mask[wc]

    return order, seg, winners, winner_visible, del_mask, uniq_valid


# ---------------------------------------------------------------------------
# host wrapper: records -> device columns -> materialized maps
# ---------------------------------------------------------------------------


class Interner:
    """Canonical string<->int tables shared across a merge batch."""

    def __init__(self):
        self.roots: Dict[str, int] = {}
        self.keys: Dict[str, int] = {}

    def root(self, name: str) -> int:
        return self.roots.setdefault(name, len(self.roots))

    def key(self, name: str) -> int:
        return self.keys.setdefault(name, len(self.keys))


def _pad_to(arr: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full(size, fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def records_to_columns(
    records: List[ItemRecord], interner: Interner, pad: Optional[int] = None
) -> Dict[str, np.ndarray]:
    """Symbolic records -> padded integer columns for the kernel.

    Records whose parent/key is implicit (mid-run items) must have been
    resolved first — `Engine.records_since` always emits explicit rows;
    for raw decoded updates run `resolve_parents` below.
    """
    n = len(records)
    size = pad or max(1, n)
    cols = {
        "client": np.full(size, 0, np.int32),
        "clock": np.full(size, 0, np.int64),
        "parent_is_root": np.zeros(size, bool),
        "parent_a": np.full(size, -2, np.int64),
        "parent_b": np.full(size, -2, np.int64),
        "key_id": np.full(size, -1, np.int32),
        "origin_client": np.full(size, -1, np.int32),
        "origin_clock": np.full(size, -1, np.int64),
        "valid": np.zeros(size, bool),
    }
    for i, r in enumerate(records):
        cols["client"][i] = r.client
        cols["clock"][i] = r.clock
        if r.parent_root is not None:
            cols["parent_is_root"][i] = True
            cols["parent_a"][i] = interner.root(r.parent_root)
            cols["parent_b"][i] = -1
        elif r.parent_item is not None:
            cols["parent_a"][i] = r.parent_item[0]
            cols["parent_b"][i] = r.parent_item[1]
        cols["key_id"][i] = interner.key(r.key) if r.key is not None else -1
        if r.origin is not None:
            cols["origin_client"][i] = r.origin[0]
            cols["origin_clock"][i] = r.origin[1]
        cols["valid"][i] = True
    return cols


def resolve_parents(records: List[ItemRecord]) -> List[ItemRecord]:
    """Fill implicit parent/key of mid-run records from their origins.

    Decoded wire runs omit parent info on parts 2..n (derived from the
    origin chain). The kernel needs every row explicit. Unresolvable
    records (origin outside the batch) keep parent unset and simply
    fall out of map segmentation.

    Duplicate ids (a hostile blob forging a client block twice, or
    redelivered runs) resolve against the FIRST occurrence — the
    convention every other consumer applies (``native.dedup_columns``,
    engine admission, Yjs's clock-watermark skip). The differential
    fuzz found the previous last-wins dict here splitting decoders on
    forged duplicates.
    """
    by_id: dict = {}
    for r in records:
        by_id.setdefault((r.client, r.clock), r)
    out = []
    for r in records:
        if r.parent_root is None and r.parent_item is None and r.kind != 0:
            seen = set()
            cur = r
            while (
                cur is not None
                and cur.parent_root is None
                and cur.parent_item is None
            ):
                if cur.id in seen:
                    cur = None
                    break
                seen.add(cur.id)
                nxt = cur.origin if cur.origin is not None else cur.right
                cur = by_id.get(nxt) if nxt is not None else None
            if cur is not None:
                r = ItemRecord(
                    client=r.client,
                    clock=r.clock,
                    parent_root=cur.parent_root,
                    parent_item=cur.parent_item,
                    key=cur.key if r.key is None else r.key,
                    origin=r.origin,
                    right=r.right,
                    kind=r.kind,
                    type_ref=r.type_ref,
                    content=r.content,
                )
        out.append(r)
    return out


def merge_records(
    records: List[ItemRecord],
    delete_set: Optional[DeleteSet] = None,
    interner: Optional[Interner] = None,
) -> Dict[Tuple, Tuple[Optional[ItemRecord], bool]]:
    """Full host->device->host map merge of a record union.

    Returns {(parent, key): (winning record, visible)} where parent is
    ("root", name) or ("item", client, clock).
    """
    records = resolve_parents(records)
    interner = interner or Interner()
    # pad to power-of-two buckets (floor 512) so jit compiles once per
    # bucket, not once per record count
    pad = 1 << max(9, (len(records) - 1).bit_length())
    cols = records_to_columns(records, interner, pad=pad)
    ds = delete_set or DeleteSet()
    d_client, d_start, d_end = ds_ops.ranges_to_device(ds)
    # bucket-pad ranges as well (null client -1 ranges match nothing)
    dpad = 1 << max(6, (len(d_client) - 1).bit_length()) if d_client else 0
    d_client = list(d_client) + [-1] * (dpad - len(d_client))
    d_start = list(d_start) + [-1] * (dpad - len(d_start))
    d_end = list(d_end) + [-1] * (dpad - len(d_end))
    with enable_x64(True):
        order, seg, winners, visible, _, _ = converge_maps(
            jnp.asarray(cols["client"]),
            jnp.asarray(cols["clock"]),
            jnp.asarray(cols["parent_is_root"]),
            jnp.asarray(cols["parent_a"]),
            jnp.asarray(cols["parent_b"]),
            jnp.asarray(cols["key_id"]),
            jnp.asarray(cols["origin_client"]),
            jnp.asarray(cols["origin_clock"]),
            jnp.asarray(cols["valid"]),
            jnp.asarray(np.asarray(d_client, np.int32)),
            jnp.asarray(np.asarray(d_start, np.int64)),
            jnp.asarray(np.asarray(d_end, np.int64)),
            ds_mode=ds_ops.mask_mode(),  # host-computed static (CL702)
        )
    order = np.asarray(order)
    winners = np.asarray(winners)
    visible = np.asarray(visible)
    out: Dict[Tuple, Tuple[Optional[ItemRecord], bool]] = {}
    for w, vis in zip(winners, visible):
        if w == NULLI:
            continue
        rec = records[order[w]]
        parent = (
            ("root", rec.parent_root)
            if rec.parent_root is not None
            else ("item",) + tuple(rec.parent_item)
        )
        out[(parent, rec.key)] = (rec, bool(vis))
    return out
