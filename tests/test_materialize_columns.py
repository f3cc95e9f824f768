"""Column-wise cache assembly (``models/replay._assemble_cache``) against
the per-row walk, kept here as the reference: the same cache as a dict,
in the same key order at every level, and under ``json.dumps``; the
same ``ix`` group; the same cache after ``finish_cache``. Cases cover a
fan-in trace, nested types, tombstoned types, an all-tombstoned root,
crafted map rights, a repeated key, cyclic nesting, and the streaming
executor's per-chunk parts."""

import json

import numpy as np
import pytest

from crdt_tpu.codec import v1
from crdt_tpu.core.engine import Engine
from crdt_tpu.core.ids import DeleteSet
from crdt_tpu.core.records import ItemRecord
from crdt_tpu.core.store import K_TYPE, TYPE_MAP
from crdt_tpu.models import replay as rp
from crdt_tpu.models import stream_replay
from crdt_tpu.obs.tracer import Tracer, get_tracer, set_tracer


def reference_assemble(dec, ds, win_rows, win_vis, seq_orders):
    """The per-row walk: one ``parent_spec`` per winner, one scalar
    tombstone test and one ``value_of`` per sequence row."""
    keys, kid = dec["keys"], dec["key_id"]
    client, clock = dec["client"], dec["clock"]
    kind_col, tref = dec["kind"], dec["type_ref"]
    contents = dec["contents"]

    map_groups = {}
    for row, vis in zip(win_rows, win_vis):
        if vis:
            map_groups.setdefault(rp.parent_spec(dec, row), {})[
                keys[kid[row]]
            ] = row

    def value_of(row, depth):
        if kind_col[row] == K_TYPE:
            spec = ("item", int(client[row]), int(clock[row]))
            return collection(spec, tref[row] == TYPE_MAP, depth + 1)
        return contents[row]

    def collection(spec, is_map, depth):
        if depth > 64:
            return None
        if is_map:
            return {
                k: value_of(r, depth)
                for k, r in map_groups.get(spec, {}).items()
            }
        return [
            value_of(r, depth)
            for r in seq_orders.get(spec, ())
            if not ds.contains(int(client[r]), int(clock[r]))
        ]

    cache = {}
    for spec in map_groups:
        if spec[0] == "root" and spec[1] != "ix":
            cache[spec[1]] = collection(spec, True, 0)
    for spec in seq_orders:
        if spec[0] == "root" and spec[1] not in cache:
            cache[spec[1]] = collection(spec, False, 0)
    return cache, map_groups.get(("root", "ix"), {})


def _order(x):
    """Key order at every level, as a comparable structure."""
    if isinstance(x, dict):
        return [(k, _order(v)) for k, v in x.items()]
    if isinstance(x, list):
        return [_order(v) for v in x]
    return x


def assert_same_cache(got, want):
    assert got == want
    assert list(got) == list(want)
    assert _order(got) == _order(want)
    assert json.dumps(got) == json.dumps(want)


def _gathered(blobs):
    dec = rp.decode(blobs)
    cols, ds = rp.stage(dec)
    w, v, o = rp.gather(dec, ds, rp.converge(cols))
    return dec, ds, w, v, o


def _fanin_blobs():
    from perfbench import gen

    cfg = {"replicas": 24, "ops_per_replica": 40, "map_frac": 0.6,
           "num_maps": 3, "num_lists": 3, "delete_frac": 0.1}
    return gen.fanin_trace(cfg, gen.rng(2147483659, 1, 0))


def _nested_engines(tombstone=False):
    """Per client: a Y.Array under a map key, a Y.Map inside a Y.Array
    (holding a Y.Array of its own), plain values around them."""
    blobs = []
    for cid in (3, 8, 11):
        eng = Engine(1000 + cid)  # clear of the fan-in's clients
        eng.map_set("cfg", f"plain{cid}", [cid, "x"])
        arr = eng.map_set_type("cfg", f"arr{cid}")
        eng.seq_insert("", 0, [cid * 10, cid * 11, cid * 12],
                       parent=("item",) + arr.id)
        eng.seq_insert("log", 0, [f"a{cid}", f"b{cid}"])
        inner = eng.seq_insert_type("log", 1, TYPE_MAP)
        eng.map_set("", "name", f"n{cid}", parent=("item",) + inner.id)
        deep = eng.map_set_type("", "deep", parent=("item",) + inner.id)
        eng.seq_insert("", 0, [[cid]], parent=("item",) + deep.id)
        if tombstone:
            eng.seq_delete("log", 1, 1)        # the map in the array
            eng.map_delete("cfg", f"arr{cid}")  # the array under a key
            eng.seq_delete("log", 0, 1)        # a plain element
        blobs.append(v1.encode_state_as_update(eng))
    return blobs


def _case_fanin():
    return _gathered(_fanin_blobs())


def _case_nested():
    return _gathered(_nested_engines())


def _case_tombstoned_nested():
    return _gathered(_nested_engines(tombstone=True))


def _case_all_keys_tombstoned():
    from crdt_tpu.api.doc import Crdt

    out = []
    d = Crdt(3, on_update=lambda u, m: out.append(u))
    d.set("gone", "k", 1)
    d.set("gone", "j", 2)
    d.delete("gone", "k")
    d.delete("gone", "j")
    d.set("other", "x", 2)
    d.push("log", ["p"])
    return _gathered(out)


def _case_crafted_map_rights():
    recs = [
        ItemRecord(client=101, clock=0, parent_root="m0", key="kx",
                   content="A"),
        ItemRecord(client=101, clock=1, parent_root="m0", key="ky",
                   content="Y"),
        # right = A stops the scan at the head: B lands BEFORE A, so
        # the chain-tail repair patches key "kx" to the end of win_rows
        ItemRecord(client=102, clock=0, parent_root="m0", key="kx",
                   right=(101, 0), content="B"),
        ItemRecord(client=102, clock=1, parent_root="m0", key="kz",
                   content="Z"),
    ]
    return _gathered([v1.encode_update(recs, DeleteSet())])


def _case_repeated_key():
    """A winner list that names one (parent, key) twice: the later row
    takes the value, the first position keeps the key's place."""
    dec, ds, w, v, o = _gathered(_fanin_blobs())
    kid, pr = dec["key_id"], dec["parent_root"]
    for i, row in enumerate(w):
        if not v[i]:
            continue
        mates = np.flatnonzero((kid == kid[row]) & (pr == pr[row]))
        other = [int(r) for r in mates if r != row]
        if other:
            return dec, ds, list(w) + [other[0]], list(v) + [True], o
    raise AssertionError("no key with two rows in the trace")


def _case_winners_shuffled():
    """Winners in another order than the kernel's (the resident route
    emits its own): roots and keys follow the order given."""
    dec, ds, w, v, o = _gathered(_nested_engines() + _fanin_blobs())
    perm = np.random.default_rng(5).permutation(len(w))
    return (dec, ds, [w[i] for i in perm], [v[i] for i in perm],
            dict(reversed(list(o.items()))))


def _case_cyclic():
    """A sequence order that lists its own type row among its members:
    the nesting is cut at depth 64, as a malformed cycle must be."""
    dec, ds, w, v, o = _gathered(_nested_engines())
    arr = np.flatnonzero((dec["kind"] == K_TYPE) & (dec["key_id"] >= 0)
                         & (dec["type_ref"] != TYPE_MAP)
                         & (dec["parent_root"] >= 0))
    t = int(arr[0])
    spec = ("item", int(dec["client"][t]), int(dec["clock"][t]))
    o = dict(o)
    o[spec] = list(o.get(spec, [])) + [t]
    return dec, ds, w, v, o


CASES = {
    "fanin": _case_fanin,
    "nested": _case_nested,
    "tombstoned_nested": _case_tombstoned_nested,
    "all_keys_tombstoned": _case_all_keys_tombstoned,
    "crafted_map_rights": _case_crafted_map_rights,
    "repeated_key": _case_repeated_key,
    "winners_shuffled": _case_winners_shuffled,
    "cyclic": _case_cyclic,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_column_wise_assembly_matches_per_row_walk(case):
    dec, ds, w, v, o = CASES[case]()
    got, got_ix = rp._assemble_cache(dec, ds, w, v, o)
    want, want_ix = reference_assemble(dec, ds, w, v, o)
    assert_same_cache(got, want)
    assert got_ix == want_ix and list(got_ix) == list(want_ix)
    assert_same_cache(rp.finish_cache(got, dec, got_ix),
                      rp.finish_cache(want, dec, want_ix))


def test_case_shapes_reach_their_paths():
    """Each crafted case holds the shape it is named for."""
    dec, ds, w, v, o = _case_all_keys_tombstoned()
    cache, ix = rp._assemble_cache(dec, ds, w, v, o)
    assert "gone" not in cache and "gone" in ix
    assert rp.finish_cache(cache, dec, ix)["gone"] == {}

    dec, ds, w, v, o = _case_crafted_map_rights()
    m0 = rp._assemble_cache(dec, ds, w, v, o)[0]["m0"]
    assert sorted(m0) == ["kx", "ky", "kz"] and list(m0)[-1] == "kx"

    dec, ds, w, v, o = _case_cyclic()
    cache = rp._assemble_cache(dec, ds, w, v, o)[0]
    arr = [x for x in cache["cfg"].values() if isinstance(x, list)
           and len(x) == 4]
    assert len(arr) == 1
    node, lists = arr[0], 0
    while isinstance(node, list):  # the array at depth 1 .. 64
        node, lists = node[-1], lists + 1
    assert node is None and lists == 64

    cache = rp._assemble_cache(*_case_tombstoned_nested())[0]
    assert sorted(cache["log"]) == ["b11", "b3", "b8"]
    assert not any(k.startswith("arr") for k in cache["cfg"])


@pytest.mark.parametrize("chunk_blobs", [1, 2])
def test_streamed_parts_match_per_row_walk(monkeypatch, chunk_blobs):
    """The streaming executor assembles each chunk's subset on its own:
    every part equals the reference on that subset, and the merged
    parts equal the one-shot cache."""
    blobs = _nested_engines() + _fanin_blobs()[:6]
    column_wise = rp._assemble_cache
    parts = []

    def checked(dec, ds, w, v, o):
        got = column_wise(dec, ds, w, v, o)
        want = reference_assemble(dec, ds, w, v, o)
        assert_same_cache(got[0], want[0])
        assert got[1] == want[1] and list(got[1]) == list(want[1])
        parts.append(got)
        return got

    monkeypatch.setattr(rp, "_assemble_cache", checked)
    st = stream_replay(blobs, chunk_blobs=chunk_blobs, max_shards=3,
                       min_shard_rows=1)
    monkeypatch.setattr(rp, "_assemble_cache", column_wise)
    assert len(parts) > 1
    dec, ds, w, v, o = _gathered(blobs)
    one, ix = reference_assemble(dec, ds, w, v, o)
    rp.finish_cache(one, dec, ix)
    assert st.cache == one
    assert json.dumps(st.cache, sort_keys=True) == json.dumps(
        one, sort_keys=True)


def _count_rows(case):
    prev = get_tracer()
    tr = set_tracer(Tracer(enabled=True))
    try:
        cache, _ = rp.assemble_cache(*case)
        return cache, tr.counters("materialize.rows")
    finally:
        set_tracer(prev)


def _leaves(x):
    if isinstance(x, dict):
        return sum(_leaves(v) for v in x.values())
    if isinstance(x, list):
        return sum(_leaves(v) for v in x)
    return 1


def test_rows_counter_on_fanin_is_all_bulk():
    cache, counts = _count_rows(_case_fanin())
    assert counts == {
        'materialize.rows{path="bulk"}': _leaves(cache),
        'materialize.rows{path="nested"}': 0,
    }
    assert _leaves(cache) > 500


def test_rows_counter_counts_nested_types():
    _, counts = _count_rows(_case_nested())
    # per client: three types (the array under its key, the map in the
    # log, the array inside that map) and eight plain values (one
    # under "cfg", three in its array, two in "log", one under
    # "name", one in the innermost array)
    assert counts == {
        'materialize.rows{path="bulk"}': 24,
        'materialize.rows{path="nested"}': 9,
    }
