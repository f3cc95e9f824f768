"""AOT compiles of the main path's kernels for a described v5e.

Interpret mode cannot see what Mosaic refuses (a scalar store to VMEM
passed every interpret test, then failed to compile for the chip).
These tests compile the Pallas kernels at their real widths, and the
fused converge programs that carry them, for a v5e that is described,
not attached, and assert the kernel made it into the program
(``tpu_custom_call``). Nothing runs. One file on purpose: the topology
is described in a module fixture, in the worker that runs this file.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from crdt_tpu.ops import pallas_kernels as pk  # noqa: E402

N = pk._SCAN_PALLAS_MAX  # 131072: the widest block the kernels take


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compile_ok(fn, *args, **kw):
    compiled = jax.jit(fn, **kw).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.fixture(scope="module")
def plan_100k():
    """The north-star fan-in union (1000 replicas x 100 ops) staged as
    the fused converge's one matrix, on the host."""
    import workloads
    from crdt_tpu.models import replay as rp
    from crdt_tpu.ops import packed

    cols, _ = rp.stage(rp.decode(workloads.build_trace(1000, 100)))
    return cols, packed.stage(cols)


def test_stream_scatter(one_chip):
    compile_ok(lambda p: pk.stream_scatter(p, N, mode="pallas"),
               spec((N,), jnp.int32, one_chip))


def test_seg_argmax_scan(one_chip):
    x = spec((N,), jnp.int32, one_chip)
    compile_ok(lambda c, f: pk.seg_argmax_scan(c, f, mode="pallas"), x, x)


def test_ds_mask_static(one_chip):
    d = 64
    compile_ok(
        lambda *a: pk.ds_mask_static(*a, interpret=False),
        spec((N,), jnp.int32, one_chip), spec((N,), jnp.int64, one_chip),
        spec((N,), jnp.bool_, one_chip), spec((d,), jnp.int32, one_chip),
        spec((d,), jnp.int64, one_chip), spec((d,), jnp.int64, one_chip),
    )


def test_sv_deficit_static(one_chip):
    compile_ok(lambda s: pk.sv_deficit_static(s, interpret=False),
               spec((1024, 1024), jnp.int64, one_chip))


def test_fused_converge_100k(one_chip, plan_100k):
    from crdt_tpu.ops import packed

    _, plan = plan_100k
    assert max(plan.seq_bucket, plan.map_bucket) <= N
    with jax.enable_x64(True):
        compiled = packed._converge_packed.lower(
            spec(plan.mat.shape, plan.mat.dtype, one_chip),
            **packed._plan_args(plan), encs=plan.encs, mode="pallas",
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_resident_splice_converge(one_chip):
    from crdt_tpu.ops import packed

    cap, kpad, sel = 1 << 14, 1 << 12, 1 << 13
    with jax.enable_x64(True):
        compiled = packed._splice_select_converge.lower(
            spec((7, cap), jnp.int64, one_chip),
            spec((8, kpad), jnp.int64, one_chip),
            spec((), jnp.int32, one_chip),
            num_segments=1 << 11, sel_bucket=sel, seq_bucket=sel,
            mode="pallas",
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_step_4_devices(topo, plan_100k):
    from crdt_tpu.ops import shard
    from crdt_tpu.parallel.gossip import REPLICA_AXIS, make_packed_shard_step

    cols, _ = plan_100k
    splan = shard.stage(cols, n_shards=4)
    mesh = Mesh(np.asarray(topo.devices), (REPLICA_AXIS,))
    rows = NamedSharding(mesh, P(REPLICA_AXIS, None))
    step = make_packed_shard_step(
        mesh, num_segments=splan.num_segments,
        seq_bucket=splan.seq_bucket, map_bucket=splan.map_bucket,
        rank_rounds=splan.rank_rounds, map_rounds=splan.map_rounds,
        encs=splan.encs, mode="pallas",
        sv_len=max(len(splan.sv_clients), 1), sv_mode=splan.sv_mode,
    )
    with jax.enable_x64(True):
        compiled = step.lower(
            spec(splan.block.shape, splan.block.dtype, rows),
            spec(splan.wire.shape, splan.wire.dtype, rows),
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
