"""Targeted anti-entropy on the device mesh — deltas, not full state.

The full-gossip step (:mod:`crdt_tpu.parallel.gossip`) all-gathers every
replica's complete op columns each round — the reference's Q2 defect
(full-state broadcasts, crdt.js:443) reproduced on-device as a compat
mode. This module is the fix, driven by the state-vector machinery:

- :func:`make_delta_gossip_step` — the ``propagate`` analogue. Replicas
  all-gather their SVs (tiny: [R, C] int64), derive the swarm floor
  (componentwise MIN — clocks every replica already holds), and
  all-gather only rows ABOVE the floor, packed into a static
  ``budget``-sized buffer per replica. ICI bytes scale with the
  deficit, not the doc: cost drops from O(R·N_doc) to
  O(R·C + R·budget) per round.
- :func:`make_ring_delta_step` — the ``toPeer`` analogue
  (crdt.js:290): each replica learns its ring successor's SV via
  ``ppermute``, selects exactly the rows that successor lacks, and
  ``ppermute``s them point-to-point over ICI. R-1 rounds converge a
  ring the way repeated ``toPeer`` unicasts do.

Static-shape discipline: the per-round ``budget`` caps how many rows a
replica may ship; ``needed_count`` in the outputs reports the true
deficit so the caller can loop rounds (or raise the budget bucket)
until it reaches zero. Host-path analogue: ``Replica.anti_entropy``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from crdt_tpu.ops import statevec

COL_NAMES = (
    "client",
    "clock",
    "parent_is_root",
    "parent_a",
    "parent_b",
    "key_id",
    "origin_client",
    "origin_clock",
    "valid",
)


def _pack_rows(cols, needed, budget: int):
    """Select `needed` rows into the first `budget` slots (per replica
    row). Rows beyond the budget (or not needed) come back invalid."""

    def pack_one(row_cols, needed_row):
        order = jnp.argsort(~needed_row, stable=True)  # needed first
        take = order[:budget]
        n_needed = needed_row.sum()
        in_budget = jnp.arange(budget) < n_needed
        out = [c[take] for c in row_cols[:-1]]
        out.append(row_cols[-1][take] & in_budget)  # valid col masked
        return tuple(out), n_needed

    return jax.vmap(pack_one)(cols, needed)


def make_delta_gossip_step(mesh, num_clients: int, budget: int):
    """Deficit-driven gossip: all-gather ONLY rows above the swarm
    floor. Returns a jitted step over [R, N] sharded columns yielding

    - ``svs``          [R, C] every replica's state vector
    - ``deficit``      [R, R] pairwise anti-entropy plan
    - ``needed_count`` [R] rows each replica had to ship (caller
      checks <= budget; loop more rounds otherwise)
    - ``delta_*``      [R * budget] the gathered delta union columns
      (feed to converge_maps / converge_sequences, or integrate into
      resident state)
    """
    axis = mesh.axis_names[0]
    # host-resolved kernel static at factory build: the traced step
    # must not read CRDT_TPU_PALLAS (crdtlint CL702)
    sv_deficit_mode = statevec.deficit_mode()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None),) * 9,
        out_specs=(P(), P(), P(axis)) + (P(),) * 9,
        check_vma=False,
    )
    def step(*cols):
        client, clock = cols[0], cols[1]
        valid = cols[8]
        sv_local = jax.vmap(
            lambda c, k, v: statevec.build(c, k, v, num_clients)
        )(client, clock, valid)
        svs = jax.lax.all_gather(sv_local, axis).reshape(-1, num_clients)
        deficit = statevec.missing_static(svs, sv_deficit_mode)

        # swarm floor: clocks EVERY replica holds; only rows above it
        # can be missing anywhere
        floor = jnp.min(svs, axis=0)
        needed = jax.vmap(
            lambda c, k, v: statevec.diff_mask(c, k, v, floor)
        )(client, clock, valid)

        packed, n_needed = _pack_rows(cols, needed, budget)
        union = tuple(
            jax.lax.all_gather(c, axis).reshape(-1, *c.shape[2:]).reshape(-1)
            for c in packed
        )
        return (svs, deficit, n_needed) + union

    # per-round column uploads donated (freshly built by the caller
    # each round — ReplicaFleet.delta_round); backends without
    # donation skip the reuse (one UserWarning per compiled shape,
    # filtered in the test config and bench)
    return jax.jit(step, donate_argnums=tuple(range(9)))


def make_ring_delta_step(mesh, num_clients: int, budget: int):
    """Point-to-point delta exchange (the ``toPeer`` analogue): every
    replica ships its ring successor exactly the rows that successor
    lacks, via ``ppermute`` over ICI. Requires one replica per device
    (device-level point-to-point). Returns a jitted step yielding

    - ``sent_count`` [R] rows shipped to the successor
    - ``recv_*``     [R, budget] columns received from the predecessor
    """
    axis = mesh.axis_names[0]
    nd = mesh.devices.size
    fwd = [(i, (i + 1) % nd) for i in range(nd)]
    bwd = [(i, (i - 1) % nd) for i in range(nd)]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None),) * 9,
        out_specs=(P(axis),) + (P(axis, None),) * 9,
        check_vma=False,
    )
    def step(*cols):
        client, clock = cols[0], cols[1]
        valid = cols[8]
        sv_local = jax.vmap(
            lambda c, k, v: statevec.build(c, k, v, num_clients)
        )(client, clock, valid)
        # learn the SUCCESSOR's SV: it travels backwards around the ring
        succ_sv = jax.lax.ppermute(sv_local, axis, perm=bwd)
        needed = jax.vmap(
            lambda c, k, v, sv: statevec.diff_mask(c, k, v, sv)
        )(client, clock, valid, succ_sv)
        packed, n_needed = _pack_rows(cols, needed, budget)
        # ship the packed rows forward to the successor
        recv = tuple(jax.lax.ppermute(c, axis, perm=fwd) for c in packed)
        return (n_needed,) + recv

    return jax.jit(step, donate_argnums=tuple(range(9)))


def synth_resident_columns(
    n_replicas: int,
    shared_ops: int,
    fresh_ops: int,
    *,
    num_maps: int = 4,
    keys_per_map: int = 32,
    seed: int = 0,
):
    """Anti-entropy workload: every replica already holds a shared
    history (`shared_ops` rows by client 1, fully replicated) plus its
    own `fresh_ops` unshared writes — the state after a settled swarm
    takes new local edits. The deficit is exactly the fresh rows."""
    rng = np.random.default_rng(seed)
    R, N = n_replicas, shared_ops + fresh_ops
    cols = {
        "client": np.empty((R, N), np.int32),
        "clock": np.empty((R, N), np.int64),
        "parent_is_root": np.ones((R, N), bool),
        "parent_a": rng.integers(0, num_maps, (R, N)).astype(np.int64),
        "parent_b": np.full((R, N), -1, np.int64),
        "key_id": rng.integers(0, keys_per_map, (R, N)).astype(np.int32),
        "origin_client": np.full((R, N), -1, np.int32),
        "origin_clock": np.full((R, N), -1, np.int64),
        "valid": np.ones((R, N), bool),
    }
    # shared history: identical rows on every replica (client 1)
    cols["client"][:, :shared_ops] = 1
    cols["clock"][:, :shared_ops] = np.arange(shared_ops)
    shared_pa = rng.integers(0, num_maps, shared_ops)
    shared_key = rng.integers(0, keys_per_map, shared_ops)
    cols["parent_a"][:, :shared_ops] = shared_pa
    cols["key_id"][:, :shared_ops] = shared_key
    # fresh per-replica rows (client r+2 so client 1 stays the history)
    for r in range(R):
        cols["client"][r, shared_ops:] = r + 2
        cols["clock"][r, shared_ops:] = np.arange(fresh_ops)
    return cols
