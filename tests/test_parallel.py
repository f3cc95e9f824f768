"""Sharded gossip step on the 8-device virtual CPU mesh.

Validates the multi-chip path the driver dry-runs (SURVEY.md §5
distributed backend): replica-sharded op columns, all-gather fan-in,
replicated union convergence, SV handshake collectives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crdt_tpu.parallel.gossip import make_gossip_step, make_mesh, synth_columns


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 virtual devices"
    return make_mesh(8)


def run_step(mesh, cols, dels, num_segments, num_clients):
    from crdt_tpu.parallel.gossip import (
        fleet_out_sizes,
        pack_cols,
        pack_dels,
        unpack_fleet_out,
    )

    step = make_gossip_step(mesh, num_segments=num_segments, num_clients=num_clients)
    vec = np.asarray(step(
        jnp.asarray(pack_cols(cols)), jnp.asarray(pack_dels(dels))
    ))
    R, N = np.asarray(cols["client"]).shape
    parts = unpack_fleet_out(vec, R, N, num_clients, num_segments)
    return [
        parts[name]
        for name, _ in fleet_out_sizes(R, N, num_clients, num_segments)
    ]


def test_gossip_step_shapes_and_svs(mesh):
    R, N = 16, 32
    C = R + 2
    cols, dels = synth_columns(R, N, num_maps=2, keys_per_map=16)
    sv_local, global_sv, deficit, winners, visible, *_ = run_step(
        mesh, cols, dels, 256, C)
    assert sv_local.shape == (R, C)
    # replica r knows exactly its own clocks before gossip
    for r in range(R):
        assert sv_local[r, r + 1] == N
        assert sv_local[r].sum() == N
    # merged vector knows everyone
    assert all(global_sv[r + 1] == N for r in range(R))
    # anti-entropy plan: every pair owes the other N clocks
    assert deficit[0, 0] == 0 and deficit[3, 5] == N and deficit[5, 3] == N


def test_gossip_winners_match_host_kernel(mesh):
    """The sharded union converge must equal the single-device kernel
    on the flattened union."""
    from functools import partial

    from crdt_tpu.ops.merge import converge_maps

    R, N = 16, 32
    cols, dels = synth_columns(R, N, num_maps=2, keys_per_map=16, seed=3)
    _, _, _, winners, visible, *_ = run_step(mesh, cols, dels, 256, R + 2)

    flat = {k: np.asarray(v).reshape(-1) for k, v in cols.items()}
    out = partial(converge_maps, num_segments=256)(
        jnp.asarray(flat["client"]),
        jnp.asarray(flat["clock"]),
        jnp.asarray(flat["parent_is_root"]),
        jnp.asarray(flat["parent_a"]),
        jnp.asarray(flat["parent_b"]),
        jnp.asarray(flat["key_id"]),
        jnp.asarray(flat["origin_client"]),
        jnp.asarray(flat["origin_clock"]),
        jnp.asarray(flat["valid"]),
        jnp.asarray(dels[0]),
        jnp.asarray(dels[1]),
        jnp.asarray(dels[2]),
    )
    ref_winners, ref_visible = np.asarray(out[2]), np.asarray(out[3])
    np.testing.assert_array_equal(winners, ref_winners)
    np.testing.assert_array_equal(visible, ref_visible)


def test_gossip_with_deletes(mesh):
    R, N = 8, 16
    cols, _ = synth_columns(R, N, num_maps=1, keys_per_map=4, seed=5)
    # tombstone all of replica 1's ops
    dels = (
        np.asarray([1] + [-1] * 15, np.int32),
        np.asarray([0] + [-1] * 15, np.int64),
        np.asarray([N] + [-1] * 15, np.int64),
    )
    _, _, _, winners, visible, *_ = run_step(mesh, cols, dels, 64, R + 2)
    assert (winners >= 0).sum() > 0


def test_gossip_sequences_match_engine_oracle(mesh):
    """Mesh-sharded YATA: the sharded step's sequence order over the
    union must equal the scalar engine's integrate order on the same
    ops (VERDICT r1 item #4: sequences in the fleet)."""
    from crdt_tpu.core.engine import Engine
    from crdt_tpu.core.records import ItemRecord

    R, N = 8, 16
    num_maps, num_lists = 2, 3
    cols, dels = synth_columns(
        R, N, num_maps=num_maps, keys_per_map=8, num_lists=num_lists, seed=11
    )
    out = run_step(mesh, cols, dels, 256, R + 2)
    seq_order, seq_seg, seq_rank = out[5], out[6], out[7]

    # device order per sequence: rows sorted by rank within segment
    flat = {k: np.asarray(v).reshape(-1) for k, v in cols.items()}
    n = len(flat["client"])
    by_seg = {}
    for pos in range(len(seq_rank)):
        if seq_rank[pos] < 0:
            continue
        row = seq_order[pos]
        assert row < n
        by_seg.setdefault(int(seq_seg[pos]), []).append(
            (int(seq_rank[pos]), (int(flat["client"][row]), int(flat["clock"][row])))
        )
    dev_orders = {}
    for sid, pairs in by_seg.items():
        pairs.sort()
        # identify the sequence by its root id (all rows share parent_a)
        row0 = seq_order[[p for p in range(len(seq_seg)) if seq_seg[p] == sid][0]]
        dev_orders[int(flat["parent_a"][row0])] = [i for _, i in pairs]

    # oracle: feed the same records through the scalar engine
    eng = Engine(0)
    records = []
    for i in range(n):
        if flat["key_id"][i] >= 0:
            records.append(ItemRecord(
                client=int(flat["client"][i]), clock=int(flat["clock"][i]),
                parent_root=f"m{flat['parent_a'][i]}",
                key=f"k{flat['key_id'][i]}", content=i,
            ))
        else:
            org = None
            if flat["origin_client"][i] >= 0:
                org = (int(flat["origin_client"][i]), int(flat["origin_clock"][i]))
            records.append(ItemRecord(
                client=int(flat["client"][i]), clock=int(flat["clock"][i]),
                parent_root=f"l{flat['parent_a'][i]}", origin=org, content=i,
            ))
    eng.apply_records(records)
    oracle = eng.seq_order_table()
    assert len(dev_orders) == num_lists
    for lid, ids in dev_orders.items():
        assert oracle[("root", f"l{lid}")] == ids, f"list {lid} diverges"


def test_hierarchical_2d_mesh_matches_flat_gossip():
    """The (hosts, replicas) two-tier fan-in (ICI all-gather then DCN
    all-gather) must produce exactly the flat 1D step's outputs on the
    same columns — the multi-host mapping changes the fabric, not the
    CRDT result."""
    from crdt_tpu.parallel.gossip import (
        make_hierarchical_gossip_step,
        make_mesh2d,
    )

    from crdt_tpu.parallel.gossip import (
        fleet_out_sizes,
        pack_cols,
        pack_dels,
        unpack_fleet_out,
    )

    R, N = 16, 24
    cols, dels = synth_columns(R, N, num_maps=2, keys_per_map=8,
                               num_lists=2, seed=21)
    flat = run_step(make_mesh(8), cols, dels, 256, R + 2)

    mesh2d = make_mesh2d(n_hosts=2, devices_per_host=4)
    step2d = make_hierarchical_gossip_step(mesh2d, num_segments=256,
                                           num_clients=R + 2)
    vec = np.asarray(step2d(
        jnp.asarray(pack_cols(cols)), jnp.asarray(pack_dels(dels))
    ))
    parts = unpack_fleet_out(vec, R, N, R + 2, 256)
    hier = [
        parts[name] for name, _ in fleet_out_sizes(R, N, R + 2, 256)
    ]

    for (name, _), a, b in zip(
        fleet_out_sizes(R, N, R + 2, 256), flat, hier,
    ):
        np.testing.assert_array_equal(a, b, err_msg=f"{name} diverges")


def test_make_mesh_refuses_more_devices_than_exist():
    """A mesh smaller than asked would run a different program than
    the one reported: make_mesh raises instead of truncating."""
    import jax

    n = len(jax.devices())
    assert make_mesh(n).devices.size == n
    with pytest.raises(ValueError, match="only"):
        make_mesh(n + 1)
