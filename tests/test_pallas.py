"""Differential tests: Pallas kernels vs their jnp twins.

The suite runs on the CPU mesh (tests/conftest.py), so the kernels
execute in interpreter mode — the same kernel bodies that compile via
Mosaic on TPU (``tests/test_chip_compile.py`` compiles them for a
described v5e; ``chip_smoke.py`` runs them on the chip). Each test drives the pallas function directly against the pure
jnp implementation on identical inputs.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crdt_tpu.ops import pallas_kernels as pk


@pytest.fixture(autouse=True)
def _force_interpret(monkeypatch):
    monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")


def _jnp_ds_mask(*args):
    """The REAL searchsorted path of deleteset.apply_mask, reached by
    pinning the dispatch env var (no hand-inlined copy to drift)."""
    from crdt_tpu.ops import deleteset

    saved = os.environ.get("CRDT_TPU_PALLAS")
    os.environ["CRDT_TPU_PALLAS"] = "0"
    try:
        return deleteset.apply_mask(*args)
    finally:
        if saved is None:
            os.environ.pop("CRDT_TPU_PALLAS", None)
        else:
            os.environ["CRDT_TPU_PALLAS"] = saved


def _random_ds_case(rng, n, d, num_clients=40, max_clock=2000):
    """Items plus a NORMALIZED delete set (sorted-disjoint ranges per
    client — the DeleteSet invariant both kernels assume; the
    searchsorted path is free to give different answers on overlapping
    ranges, which the engine never produces)."""
    from crdt_tpu.core.ids import DeleteSet
    from crdt_tpu.ops.deleteset import ranges_to_device

    client = rng.integers(0, num_clients, n).astype(np.int32)
    clock = rng.integers(0, max_clock, n).astype(np.int64)
    valid = rng.random(n) < 0.9
    dset = DeleteSet()
    for _ in range(d):
        dset.add(
            int(rng.integers(0, num_clients)),
            int(rng.integers(0, max_clock)),
            int(rng.integers(1, 64)),
        )
    dset.normalize()
    dc, ds, de = ranges_to_device(dset)
    # keep at least one range and requested-size padding with nulls
    dc = np.asarray(list(dc) + [-1] * (d - len(dc)), np.int32)[:d]
    ds = np.asarray(list(ds) + [-1] * (d - len(ds)), np.int64)[:d]
    de = np.asarray(list(de) + [-1] * (d - len(de)), np.int64)[:d]
    return tuple(jnp.asarray(x) for x in (client, clock, valid, dc, ds, de))


@pytest.mark.parametrize("n,d", [(1, 1), (100, 3), (1000, 64), (8192, 200), (5000, 1)])
def test_ds_mask_matches_searchsorted(n, d):
    rng = np.random.default_rng(n * 1000 + d)
    args = _random_ds_case(rng, n, d)
    ref = _jnp_ds_mask(*args)
    got = pk.ds_mask(*args)
    assert bool(jnp.all(ref == got))


def test_ds_mask_null_padded_ranges_match_nothing():
    # bucket padding in merge_records fills ranges with (-1, -1, -1)
    client = jnp.asarray(np.array([0, 1, 2], np.int32))
    clock = jnp.asarray(np.array([0, 5, 7], np.int64))
    valid = jnp.ones(3, bool)
    dc = jnp.asarray(np.array([1, -1, -1], np.int32))
    ds = jnp.asarray(np.array([5, -1, -1], np.int64))
    de = jnp.asarray(np.array([6, -1, -1], np.int64))
    got = np.asarray(pk.ds_mask(client, clock, valid, dc, ds, de))
    assert got.tolist() == [False, True, False]


def test_ds_mask_invalid_rows_stay_false():
    client = jnp.asarray(np.array([3, 3], np.int32))
    clock = jnp.asarray(np.array([10, 10], np.int64))
    valid = jnp.asarray(np.array([True, False]))
    dc = jnp.asarray(np.array([3], np.int32))
    ds = jnp.asarray(np.array([0], np.int64))
    de = jnp.asarray(np.array([100], np.int64))
    got = np.asarray(pk.ds_mask(client, clock, valid, dc, ds, de))
    assert got.tolist() == [True, False]


def test_ds_mask_range_budget_enforced():
    rng = np.random.default_rng(0)
    args = _random_ds_case(rng, 16, pk._DS_MAX_RANGES + 1)
    with pytest.raises(ValueError, match="SMEM budget"):
        pk.ds_mask(*args)


def _jnp_missing(svs):
    deficit = jnp.maximum(svs[:, None, :] - svs[None, :, :], 0)
    return deficit.sum(axis=-1)


@pytest.mark.parametrize("r,c", [(1, 1), (2, 7), (9, 130), (130, 64), (16, 256)])
def test_sv_deficit_matches_jnp(r, c):
    rng = np.random.default_rng(r * 1000 + c)
    svs = jnp.asarray(rng.integers(0, 100000, (r, c)).astype(np.int64))
    ref = _jnp_missing(svs)
    got = pk.sv_deficit(svs)
    assert got.dtype == svs.dtype
    assert bool(jnp.all(ref == got))


def test_sv_deficit_zero_and_identical_rows():
    svs = jnp.asarray(np.zeros((4, 12), np.int64))
    assert bool(jnp.all(pk.sv_deficit(svs) == 0))
    svs = jnp.asarray(np.tile(np.arange(12, dtype=np.int64), (4, 1)))
    assert bool(jnp.all(pk.sv_deficit(svs) == 0))


def test_ds_mask_exact_beyond_int32():
    """Clocks past 2**31 (the framework allows < 2**40): the hi/lo
    split compares must not truncate — a range straddling the int32
    boundary was the review repro that a plain i32 cast got wrong."""
    big = 2**31
    client = jnp.asarray(np.array([1, 1, 1], np.int32))
    clock = jnp.asarray(np.array([big, big - 10, 2**39], np.int64))
    valid = jnp.ones(3, bool)
    dc = jnp.asarray(np.array([1, 1], np.int32))
    ds = jnp.asarray(np.array([big - 5, 2**39 - 1], np.int64))
    de = jnp.asarray(np.array([big + 5, 2**39 + 1], np.int64))
    got = np.asarray(pk.ds_mask(client, clock, valid, dc, ds, de))
    assert got.tolist() == [True, False, True]
    ref = np.asarray(_jnp_ds_mask(client, clock, valid, dc, ds, de))
    assert got.tolist() == ref.tolist()


def test_sv_deficit_exact_beyond_int32():
    """Absolute clocks past 2**31 with small spreads: the per-column
    centering must keep the i32 kernel exact (the review repro showed
    a plain cast flipping the anti-entropy plan's direction)."""
    big = 2**31
    svs = jnp.asarray(
        np.array([[big + 10, 2**39], [0 + big, 2**39 + 7]], np.int64)
    )
    ref = _jnp_missing(svs)
    got = pk.sv_deficit(svs)
    assert bool(jnp.all(ref == got))
    assert int(got[0, 1]) == 10 and int(got[1, 0]) == 7


def test_dispatch_respects_env(monkeypatch):
    monkeypatch.setenv("CRDT_TPU_PALLAS", "0")
    assert not pk.use_pallas()
    monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")
    assert pk.use_pallas() and pk._interpret()
    monkeypatch.setenv("CRDT_TPU_PALLAS", "auto")
    assert pk.use_pallas() == (jax.default_backend() == "tpu")


def test_apply_mask_dispatch_equivalence(monkeypatch):
    """deleteset.apply_mask gives identical answers through both paths."""
    from crdt_tpu.ops import deleteset

    rng = np.random.default_rng(7)
    args = _random_ds_case(rng, 3000, 50)
    monkeypatch.setenv("CRDT_TPU_PALLAS", "0")
    ref = deleteset.apply_mask(*args)
    monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")
    got = deleteset.apply_mask(*args)
    assert bool(jnp.all(ref == got))


def test_missing_dispatch_equivalence(monkeypatch):
    from crdt_tpu.ops import statevec

    rng = np.random.default_rng(8)
    svs = jnp.asarray(rng.integers(0, 5000, (10, 40)).astype(np.int64))
    monkeypatch.setenv("CRDT_TPU_PALLAS", "0")
    ref = statevec.missing(svs)
    monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")
    got = statevec.missing(svs)
    assert bool(jnp.all(ref == got))


def test_exact_missing_matches_dense():
    from crdt_tpu.ops import statevec

    rng = np.random.default_rng(9)
    svs = jnp.asarray(rng.integers(0, 5000, (13, 29)).astype(np.int64))
    assert bool(jnp.all(statevec.exact_missing(svs) == _jnp_missing(svs)))


def test_sv_deficit_overflow_falls_back_exact():
    """Spreads past 2**31 (one replica lagging another by >2e9 clocks
    on one client) must take the exact int64 path, not wrap in i32."""
    lag = 2**31 + 12345
    svs = jnp.asarray(np.array([[lag, 5], [0, 5], [7, 5]], np.int64))
    got = pk.sv_deficit(svs)
    ref = _jnp_missing(svs)
    assert bool(jnp.all(got == ref))
    assert int(got[0, 1]) == lag  # the value an i32 kernel would wrap


def test_apply_mask_crossover_uses_jnp_for_large_d(monkeypatch):
    """Dispatch sends D > _DS_PALLAS_CROSSOVER to the searchsorted
    path even when pallas is enabled (the SMEM cap is not the
    performance crossover)."""
    from crdt_tpu.ops import deleteset

    calls = []
    real = pk.ds_mask_static
    monkeypatch.setattr(
        pk, "ds_mask_static",
        lambda *a, **kw: calls.append(1) or real(*a, **kw),
    )
    rng = np.random.default_rng(11)
    big = _random_ds_case(rng, 256, pk._DS_PALLAS_CROSSOVER + 1)
    small = _random_ds_case(rng, 256, pk._DS_PALLAS_CROSSOVER)
    monkeypatch.setenv("CRDT_TPU_PALLAS", "interpret")
    deleteset.apply_mask(*big)
    assert not calls
    deleteset.apply_mask(*small)
    assert calls
