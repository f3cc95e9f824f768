"""Device merge mode: the TPU kernels in the PRODUCT hot path.

``Crdt(device_merge=True)`` routes every
remote merge through converge_maps + tree_order_ranks instead of the
scalar integrate loop. These tests assert the two paths produce
IDENTICAL engine state — visible JSON, chain order, delete sets,
encoded full state — on every workload class, and that the acceptance
swarms converge with the device path enabled (VERDICT r1 item #1).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from crdt_tpu.api.doc import Crdt


def _drain(docs):
    """Deliver every doc's outbox to every other doc until quiet."""
    progress = True
    while progress:
        progress = False
        for d in docs:
            out, d.outbox = d.outbox, []
            for upd in out:
                for other in docs:
                    if other is not d:
                        other.doc.apply_update(upd)
                        progress = True


class _Peer:
    """Tiny harness: a Crdt plus an outbox of emitted updates."""

    def __init__(self, client_id, device):
        self.outbox = []
        self.doc = Crdt(
            client_id,
            on_update=lambda u, m: self.outbox.append(u),
            device_merge=device,
        )


def _swarm(n, device):
    return [_Peer(i + 1, device) for i in range(n)]


def _assert_same_state(a: Crdt, b: Crdt):
    """Byte-level equivalence of two docs' CRDT state."""
    assert dict(a.c) == dict(b.c)
    assert a.engine.to_json() == b.engine.to_json()
    assert a.engine.delete_set() == b.engine.delete_set()
    assert a.engine.state_vector() == b.engine.state_vector()
    assert a.engine.map_winner_table() == b.engine.map_winner_table()
    assert a.engine.seq_order_table() == b.engine.seq_order_table()
    assert a.encode_state_as_update() == b.encode_state_as_update()


def _run_script(device, script):
    """Run an op script on a 3-peer swarm; return the converged docs."""
    peers = _swarm(3, device)
    script(peers)
    _drain(peers)
    first = dict(peers[0].doc.c)
    for p in peers[1:]:
        assert dict(p.doc.c) == first
    return peers


def _differential(script):
    """Same script under both modes -> identical converged state."""
    scalar = _run_script(False, script)
    device = _run_script(True, script)
    for s, d in zip(scalar, device):
        _assert_same_state(s.doc, d.doc)
    return device


class TestDifferentialModes:
    def test_concurrent_map_sets(self):
        def script(peers):
            for i, p in enumerate(peers):
                for k in range(20):
                    p.doc.set("m", f"k{k % 7}", f"v{i}.{k}")

        _differential(script)

    def test_map_set_delete_interleaved(self):
        def script(peers):
            a, b, c = peers
            for k in range(10):
                a.doc.set("m", f"k{k}", k)
            _drain(peers)
            b.doc.delete("m", "k3")
            c.doc.set("m", "k3", "resurrect")
            a.doc.delete("m", "k5")

        _differential(script)

    def test_concurrent_seq_ops(self):
        def script(peers):
            a, b, c = peers
            a.doc.push("l", ["a1", "a2"])
            b.doc.push("l", ["b1"])
            _drain(peers)
            a.doc.insert("l", 1, "mid")
            b.doc.unshift("l", "front")
            c.doc.cut("l", 0, 1)

        _differential(script)

    def test_nested_array_in_map(self):
        def script(peers):
            a, b, c = peers
            a.doc.set("cfg", "tags", None, array_method="push")
            _drain(peers)
            b.doc.set("cfg", "tags", ["x", "y"], array_method="push")
            c.doc.set("cfg", "tags", "z", array_method="unshift")
            a.doc.set("cfg", "mode", "dark")

        _differential(script)

    def test_batch_then_remote(self):
        def script(peers):
            a, b, _ = peers
            a.doc.set("m", "k1", 1, batch=True)
            a.doc.push("l", ["x"], batch=True)
            a.doc.set("m", "k2", 2, batch=True)
            a.doc.exec_batch()
            b.doc.set("m", "k1", "b-wins-or-loses")

        _differential(script)

    def test_fuzz_random_ops(self):
        def script(peers):
            # seeded inside the script: both mode runs must draw the
            # exact same op sequence
            rng = np.random.default_rng(7)
            for step in range(60):
                p = peers[rng.integers(len(peers))]
                op = rng.integers(5)
                if op == 0:
                    p.doc.set("m", f"k{rng.integers(6)}", int(step))
                elif op == 1:
                    p.doc.delete("m", f"k{rng.integers(6)}")
                elif op == 2:
                    p.doc.push("l", int(step))
                elif op == 3:
                    if len(p.doc.c.get("l", [])) > 1:
                        p.doc.cut("l", int(rng.integers(len(p.doc.c["l"]))))
                else:
                    if rng.integers(2):
                        _drain(peers)

        _differential(script)


class TestDeviceModePlumbing:
    def test_env_flag_does_not_touch_standalone_crdt(self, monkeypatch):
        """CRDT_TPU_DEVICE is the replica layer's knob (it selects
        merge_mode="resident" there); the standalone Crdt's engine
        device gate is strictly explicit."""
        monkeypatch.setenv("CRDT_TPU_DEVICE", "1")
        assert not Crdt(1).device_merge
        assert Crdt(1, device_merge=True).device_merge
        monkeypatch.delenv("CRDT_TPU_DEVICE")
        assert not Crdt(1).device_merge

    def test_apply_updates_batches_one_txn(self):
        """A backlog of K updates = one merge + one observer flush."""
        src = _Peer(1, False)
        for i in range(5):
            src.doc.set("m", f"k{i}", i)
        events = []
        dst = Crdt(2, observer_function=events.append, device_merge=True)
        dst.apply_updates(src.outbox)
        assert dict(dst.c)["m"] == dict(src.doc.c)["m"]
        assert len(events) == 1  # one flush for the whole backlog

    def test_pending_stash_device_mode(self):
        """Out-of-order delivery waits in pending, exactly like scalar."""
        src = _Peer(1, False)
        src.doc.set("m", "a", 1)
        src.doc.set("m", "b", 2)
        u1, u2 = src.outbox
        dst = Crdt(2, device_merge=True)
        dst.apply_update(u2)  # clock gap: must stash
        assert dst.engine.pending
        assert "m" not in dst.c or "b" not in dst.c.get("m", {})
        dst.apply_update(u1)  # gap filled: both integrate
        assert not dst.engine.pending
        assert dict(dst.c)["m"] == {"a": 1, "b": 2}

    def test_local_ops_after_device_rebuild(self):
        """Local mutations keep working on the rebuilt chain state."""
        a, b = _Peer(1, True), _Peer(2, True)
        a.doc.push("l", ["x", "y"])
        a.doc.set("m", "k", "v1")
        for u in a.outbox:
            b.doc.apply_update(u)
        # b mutates on top of device-rebuilt chains
        b.doc.insert("l", 1, "mid")
        b.doc.set("m", "k", "v2")
        b.doc.cut("l", 0)
        for u in b.outbox:
            a.doc.apply_update(u)
        assert dict(a.doc.c) == dict(b.doc.c)
        assert a.doc.c["l"] == ["mid", "y"]
        assert a.doc.c["m"] == {"k": "v2"}

    def test_large_random_client_ids(self):
        """Real replicas use random 31-bit client ids, which overflow
        the kernels' packed (client << 40) int64 ids; the rebuild's
        dense remap must keep outcomes identical to scalar."""
        ids = [2**31 - 7, 2**30 + 12345, 3]

        def script(peers):
            a, b, c = peers
            a.doc.set("m", "k", "a")
            b.doc.set("m", "k", "b")
            c.doc.set("m", "k", "c")
            a.doc.push("l", ["x"])
            b.doc.push("l", ["y"])

        def run(device):
            peers = [_Peer(cid, device) for cid in ids]
            script(peers)
            _drain(peers)
            return peers

        scalar, device = run(False), run(True)
        for s, d in zip(scalar, device):
            _assert_same_state(s.doc, d.doc)

    def test_duplicate_siblings_rebuild_on_device(self, monkeypatch):
        """rebuild_chains' own (client, ~clock) key (separate code from
        order_sequences) must order attachment-free same-client
        duplicate groups without the host scan."""
        import crdt_tpu.ops.yata as yata
        from crdt_tpu.codec import v1
        from crdt_tpu.core.records import ItemRecord

        recs = [
            ItemRecord(client=1, clock=0, parent_root="s", content="b0"),
            ItemRecord(client=1, clock=1, parent_root="s", origin=(1, 0),
                       content="b1"),
        ]
        for k in range(3):
            recs.append(ItemRecord(client=2, clock=k, parent_root="s",
                                   origin=(1, 0), content=f"dup{k}"))
        blob = v1.encode_update(recs, None)

        scalar = Crdt(999, device_merge=False)
        scalar.apply_update(blob)

        def boom(*a, **k):
            raise AssertionError("host scan ran in rebuild_chains")

        monkeypatch.setattr(yata, "_simulate_group", boom)
        device = Crdt(999, device_merge=True)
        device.apply_update(blob)
        _assert_same_state(scalar, device)
        assert device.c["s"] == ["b0", "b1", "dup2", "dup1", "dup0"]

    def test_hostile_rights_stay_identical_across_modes(self):
        """Crafted updates with rights pointing inside a sibling's
        subtree (or dangling) pass admission but defeat the sibling
        rank model; the hard-segment scalar fallback keeps device mode
        byte-identical to scalar mode."""
        from crdt_tpu.codec import v1
        from crdt_tpu.core.records import ItemRecord

        recs = [
            ItemRecord(client=1, clock=0, parent_root="s", content="a"),
            ItemRecord(client=1, clock=1, parent_root="s", origin=(1, 0),
                       content="b"),
            ItemRecord(client=1, clock=2, parent_root="s", origin=(1, 1),
                       content="c"),
        ]
        # right = grandchild of the sibling (1,1): splits its subtree
        hostile = ItemRecord(client=4, clock=0, parent_root="s",
                             origin=(1, 0), right=(1, 2), content="H")
        blob = v1.encode_update(recs + [hostile], None)
        scalar = Crdt(999, device_merge=False)
        device = Crdt(999, device_merge=True)
        scalar.apply_update(blob)
        device.apply_update(blob)
        _assert_same_state(scalar, device)
        assert scalar.c["s"] == ["a", "b", "H", "c"]

    def test_hostile_right_with_interleaved_clocks(self):
        """The hard-segment fallback must not re-apply admission gates:
        a client whose sequence clocks interleave with map clocks (gaps
        WITHIN the slice) keeps every live item."""
        from crdt_tpu.codec import v1
        from crdt_tpu.core.records import ItemRecord

        recs = [
            ItemRecord(client=1, clock=0, parent_root="s", content="a"),
            ItemRecord(client=1, clock=1, parent_root="m", key="k",
                       content="map-gap"),
            ItemRecord(client=1, clock=2, parent_root="s", origin=(1, 0),
                       content="b"),
            ItemRecord(client=1, clock=3, parent_root="s", origin=(1, 2),
                       content="c"),
            ItemRecord(client=4, clock=0, parent_root="s", origin=(1, 0),
                       right=(1, 3), content="H"),
        ]
        blob = v1.encode_update(recs, None)
        scalar = Crdt(999, device_merge=False)
        device = Crdt(999, device_merge=True)
        scalar.apply_update(blob)
        device.apply_update(blob)
        _assert_same_state(scalar, device)
        assert scalar.c["s"] == ["a", "b", "H", "c"]

    def test_hostile_right_deep_in_subtree(self):
        """Subtree depth exceeds group size: the hard-shape walk must
        bound by universe size, not sibling count."""
        from crdt_tpu.codec import v1
        from crdt_tpu.core.records import ItemRecord

        recs = [ItemRecord(client=1, clock=0, parent_root="s", content="root")]
        # a1: client 2, child of root; then an 8-deep chain under a1
        recs.append(ItemRecord(client=2, clock=0, parent_root="s",
                               origin=(1, 0), content="a1"))
        for k in range(1, 9):
            recs.append(ItemRecord(client=2, clock=k, parent_root="s",
                                   origin=(2, k - 1), content=f"d{k}"))
        hostile = ItemRecord(client=5, clock=0, parent_root="s",
                             origin=(1, 0), right=(2, 8), content="H")
        blob = v1.encode_update(recs + [hostile], None)
        scalar = Crdt(999, device_merge=False)
        device = Crdt(999, device_merge=True)
        scalar.apply_update(blob)
        device.apply_update(blob)
        _assert_same_state(scalar, device)

    def test_cross_parent_right_integrates_in_both_modes(self):
        """A right origin living in ANOTHER collection exists in the
        store, so the member must integrate (scan-to-end), not pend."""
        from crdt_tpu.codec import v1
        from crdt_tpu.core.records import ItemRecord

        recs = [
            ItemRecord(client=1, clock=0, parent_root="other", content="x"),
            ItemRecord(client=1, clock=1, parent_root="s", content="a"),
            ItemRecord(client=3, clock=0, parent_root="s", origin=(1, 1),
                       right=(1, 0), content="weird"),
        ]
        blob = v1.encode_update(recs, None)
        scalar = Crdt(999, device_merge=False)
        device = Crdt(999, device_merge=True)
        scalar.apply_update(blob)
        device.apply_update(blob)
        _assert_same_state(scalar, device)
        assert "weird" in scalar.c["s"]

    def test_hostile_rights_on_map_rows(self):
        """Crafted rights on MAP entries shift the chain tail; both
        modes must agree on the winner (the kernel path falls back to
        the exact scalar tail for those chains)."""
        from crdt_tpu.codec import v1
        from crdt_tpu.core.records import ItemRecord

        recs = [
            ItemRecord(client=1, clock=0, parent_root="m", key="k",
                       content="A"),
            # hostile: right = A makes the scan stop at the head, so B
            # lands BEFORE A and is tombstoned despite the larger client
            ItemRecord(client=2, clock=0, parent_root="m", key="k",
                       right=(1, 0), content="B"),
            ItemRecord(client=1, clock=1, parent_root="m", key="other",
                       content="clean"),
        ]
        blob = v1.encode_update(recs, None)
        scalar = Crdt(999, device_merge=False)
        device = Crdt(999, device_merge=True)
        scalar.apply_update(blob)
        device.apply_update(blob)
        _assert_same_state(scalar, device)
        assert scalar.c["m"]["k"] == "A"


class TestCompileCachePlacement:
    """The persistent compile cache: JAX_COMPILATION_CACHE_DIR when
    set (and no other directory), else ``<checkout>/.jax_cache``;
    CPU-pinned processes keep none in the checkout."""

    def test_env_dir_wins(self, tmp_path):
        from crdt_tpu.ops import device

        env = {device.CACHE_ENV: str(tmp_path), "JAX_PLATFORMS": "tpu"}
        assert device.compile_cache_dir(env) == str(tmp_path)

    def test_unset_is_checkout_cache(self):
        from crdt_tpu.ops import device

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert device.CHECKOUT_CACHE == os.path.join(root, ".jax_cache")
        assert device.compile_cache_dir({}, "") == device.CHECKOUT_CACHE

    def test_cpu_pinned_keeps_none(self):
        from crdt_tpu.ops import device

        assert device.compile_cache_dir({"JAX_PLATFORMS": "cpu"}) is None

    def test_artifacts_only_in_env_dir(self, tmp_path):
        """A fresh CPU process with the variable set writes its
        compiled artifacts there, and nothing into the checkout."""
        from crdt_tpu.ops import device

        before = (set(os.listdir(device.CHECKOUT_CACHE))
                  if os.path.isdir(device.CHECKOUT_CACHE) else set())
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        code = ("import crdt_tpu.ops.device as d, jax, jax.numpy as jnp;"
                "assert jax.config.jax_compilation_cache_dir == "
                f"{str(tmp_path)!r};"
                "jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)"
                "(jnp.arange(64)))")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       check=True, timeout=120)
        assert os.listdir(tmp_path), "no artifact in the env directory"
        after = (set(os.listdir(device.CHECKOUT_CACHE))
                 if os.path.isdir(device.CHECKOUT_CACHE) else set())
        assert after == before
