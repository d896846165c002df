"""The main-path pool kernels compile for a described TPU v5e, at real widths.

Nothing runs: each test lowers one Pallas kernel for a ``v5e:2x2`` topology
described by the installed TPU compiler and compiles it, so Mosaic refuses
here whatever it would refuse on the chip (illegal block shapes, unsupported
vector layouts, VMEM overuse). Sizes are those ``chip_smoke.py`` serves
``qwen3-0.6b`` at: 2048-word lanes (64 KiB pages), a 12800-row pool, and
the decode step's ``8 x 28 x 64`` page gather.

The topology, shardings and shapes are built in fixtures — never at import —
so only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.layouts import Layout

W = 2048                       # row_words: 8 lanes x 2048 words = 64 KiB pages
ROWS = 12800                   # local pool rows (4 banks x 3200)
CREAM_ROWS = 2048              # the smoke's CREAM region; the rest SECDED
SHARDS = 4
GATHER = 8 * 28 * 64           # max_batch x layers x max_blocks
BENCH_ROWS = 25600             # the chip benchmark's pool
BENCH_SECDED_ROWS = 7168       # of it, the paid tier's SECDED rows
HBM_BYTES = 16 * 2**30         # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Described-chip compiles cannot be read back from the persistent
    cache (no chip), so keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def native(monkeypatch, no_persistent_cache):
    """Steer the kernel modules off interpret mode (the host is a CPU)."""
    from repro.kernels.hash import kernel as hash_k
    from repro.kernels.migrate import kernel as migrate_k
    from repro.kernels.mixed import kernel as mixed_k
    for mod in (mixed_k, migrate_k, hash_k):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)


def _compile(fn, static, *args):
    """Fresh jit of the kernel's wrapped body (no stale interpret trace)."""
    return jax.jit(fn.__wrapped__, static_argnames=static) \
        .lower(*args).compile()


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES // 2, used


def _pool(sharding, rows=ROWS):
    return jax.ShapeDtypeStruct((rows, 9, W), jnp.uint32, sharding=sharding)


def _ids(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)


@pytest.mark.parametrize("boundary", [CREAM_ROWS, 0],
                         ids=["cream", "secded"])
def test_mixed_read_correct_compiles(native, one_chip, boundary):
    from repro.kernels.mixed import kernel
    _check(_compile(kernel.read_correct, ("layout", "num_rows", "boundary"),
                    _pool(one_chip), _ids(GATHER, one_chip),
                    Layout.INTERWRAP, ROWS, boundary))


@pytest.mark.parametrize("boundary", [BENCH_ROWS - BENCH_SECDED_ROWS, 0],
                         ids=["cream", "secded"])
def test_mixed_read_correct_reads_the_pool_in_place(native, one_chip,
                                                    boundary):
    """At the benchmark's pool the gather reads the storage through its
    plane view: no copy or transpose of the pool, next to no temporaries
    (the per-slice kernel's relayout took ~5.2 GB here)."""
    from repro.kernels.mixed import kernel
    compiled = _compile(kernel.read_correct,
                        ("layout", "num_rows", "boundary"),
                        _pool(one_chip, BENCH_ROWS), _ids(GATHER, one_chip),
                        Layout.INTERWRAP, BENCH_ROWS, boundary)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    relayouts = [line for line in text.splitlines()
                 if ("copy(" in line or "transpose(" in line)
                 and str(BENCH_ROWS) in line]
    assert not relayouts, relayouts
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_mixed_read_correct_routed_compiles(native, one_chip):
    """One bank's slice of a 4-bank pool, global ids, traced shard id."""
    from repro.kernels.mixed import kernel
    sid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _check(_compile(kernel.read_correct_routed,
                    ("layout", "num_rows", "boundary", "num_shards"),
                    _pool(one_chip, ROWS // SHARDS), _ids(GATHER, one_chip),
                    Layout.INTERWRAP, ROWS, CREAM_ROWS, SHARDS, sid))


def test_migrate_gather_encode_compiles(native, one_chip):
    from repro.kernels.migrate import kernel
    _check(_compile(kernel.gather_encode, ("num_rows",),
                    _pool(one_chip), _ids(28 * 18, one_chip), ROWS))


def test_hash_lookup_read_compiles(native, one_chip):
    from repro.kernels.hash import kernel
    cap = 16384                            # the smoke's 4 x 4096 values
    keys = jax.ShapeDtypeStruct((cap,), jnp.uint32, sharding=one_chip)
    queries = jax.ShapeDtypeStruct((4160,), jnp.uint32, sharding=one_chip)
    _check(_compile(kernel.lookup_read,
                    ("layout", "num_rows", "boundary", "probe"),
                    _pool(one_chip), keys, _ids(cap, one_chip), queries,
                    Layout.INTERWRAP, ROWS, CREAM_ROWS, 16))


@pytest.fixture(scope="module")
def banks(topo):
    import numpy as np
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(topo.devices), ("banks",),
                axis_types=(AxisType.Auto,))


@pytest.mark.parametrize("path", ["planned", "routed"])
def test_sharded_pool_read_compiles(native, banks, path):
    """The whole sharded read over a 4-chip banks mesh: per-bank kernels
    inside ``shard_map`` (planned streams, or the router-fused kernel under
    a traced read) — the kernels' outputs must type-check as varying."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.shard import pool as sp
    from repro.shard import router
    storage = jax.ShapeDtypeStruct((SHARDS, ROWS // SHARDS, 9, W),
                                   jnp.uint32,
                                   sharding=NamedSharding(banks, P("banks")))
    pool = sp.ShardedPool(storage, CREAM_ROWS // SHARDS,
                          Layout.INTERWRAP, W, banks, True, 0)
    rep = NamedSharding(banks, P())
    if path == "planned":
        spages, _, inv = router.plan_streams(
            np.arange(GATHER, dtype=np.int32) % ROWS, ROWS, SHARDS)
        args = (jax.ShapeDtypeStruct(spages.shape, jnp.int32,
                                     sharding=NamedSharding(banks,
                                                            P("banks"))),
                jax.ShapeDtypeStruct(inv.shape, jnp.int32, sharding=rep))
        fn = sp._read_planned_impl
    else:
        args = (jax.ShapeDtypeStruct((GATHER,), jnp.int32, sharding=rep),)
        fn = sp.read_any
    _check(jax.jit(fn).lower(pool, *args).compile())
